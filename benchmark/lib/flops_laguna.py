"""Operations and bytes of a ``laguna`` stack that the algorithm needs,
from shapes alone (as ``flops.py`` counts them: causal attention over the
keys a query may see, a window layer's over its band; padding not
counted; a multiply-add is two operations)."""

from __future__ import annotations

from benchmark.lib import flops, weights_laguna

ELEM = 2  # bytes of a bf16 weight or K/V element


def _decode_spans(run):
    return [ev for ev in run.spans if ev.get("name") == "decode_chunk"]


def decode_steps(run) -> int:
    """Decode steps the window's chunks ran."""
    return max(1, len(_decode_spans(run)) * run.counters["chunk"])


def experts_touched_mean(run):
    """Distinct experts a decode step's tokens chose, per expert layer,
    over the window's decode steps; None where the spans count none."""
    spans = [ev for ev in _decode_spans(run) if "experts_touched" in ev["args"]]
    if not spans:
        return None
    layers = len(spans[0]["args"]["experts_touched"])
    total = sum(sum(ev["args"]["experts_touched"]) for ev in spans)
    return total / (len(spans) * run.counters["chunk"] * max(layers, 1))


def expert_params(z: dict) -> int:
    """One routed expert: gate, up and down."""
    return 3 * z["d"] * z["expert_dim"]


def decode_step_min_bytes(cfg: dict, touched_per_layer: float,
                          rows_full: float, rows_window: float) -> float:
    """Least bytes one decode step reads: every weight once but the
    embedding table (a step reads its residents' rows of it) and the
    routed experts, of which the ``touched_per_layer`` the step's tokens
    chose in each expert layer; the live K and V rows, ``rows_full``
    positions summed over the residents in each full layer and
    ``rows_window`` in each window layer."""
    z = weights_laguna.dims(cfg)
    routed = z["n_e"] * z["experts"] * expert_params(z)
    rest = weights_laguna.count(cfg) - routed - z["vocab"] * z["d"]
    kv_row = 2 * z["kv_heads"] * z["head_dim"]
    return ELEM * (
        rest + z["n_e"] * touched_per_layer * expert_params(z)
        + kv_row * (z["n_g"] * rows_full + z["n_w"] * rows_window))


def band_pairs(seq_len: int, window: int) -> int:
    """(query, key) pairs of a causal band of ``window`` keys."""
    w = min(window, seq_len)
    return w * (w + 1) // 2 + (seq_len - w) * window


def flash_fwd_cost(heads: int, seq_len: int, head_dim: int,
                   window: int | None) -> tuple[float, float]:
    """(operations, bytes) of one forward flash call over ``heads`` rows
    x heads: q.k and p.v over the pairs; q, k, v read and o written."""
    pairs = flops.causal_pairs(seq_len) if window is None else band_pairs(
        seq_len, window)
    return (4.0 * heads * pairs * head_dim,
            4.0 * heads * seq_len * head_dim * ELEM)


def flash_fwd_roofline_pct(run):
    """Sum of the least times of the forward flash kernel's calls / their
    device time, on chip 0; a call is a full layer's or a window layer's
    by its rows x heads (``heads`` rows of one kind's query heads for a
    row count the server can dispatch)."""
    from benchmark.lib import trace

    t = run.trace
    if t is None:
        return None
    z = weights_laguna.dims(run.cfg)
    srv = run.traffic["server"]
    sizes = {1, 4, srv["slots"]}  # serve.PREFILL_ROW_SIZES and the bank
    least = took = 0.0
    for key, hlo in t.hlo_of.items():
        if t.opcode_of.get(key) != "custom-call" or "flash_fwd" not in key:
            continue
        bh, seq, dh = trace.result_shapes(hlo)[0][1]
        kinds = [w for h, w in ((z["full_heads"], None),
                                (z["window_heads"], z["window"]))
                 if bh % h == 0 and bh // h in sizes]
        if len(kinds) != 1:
            return None
        calls = t.op_calls.get(key, [])
        least += len(calls) * flops.roofline_seconds(
            *flash_fwd_cost(bh, seq, dh, kinds[0]), run.peaks)
        took += sum(calls)
    return 100.0 * least / took if took else None
