"""Operations and bytes of a ``qwen3_next`` stack's decode step and of its
delta-rule layers' one-token state update, from shapes alone (as
``flops.py`` counts them: padding not counted; a multiply-add is two
operations). The state is float32, weights and K/V bf16."""

from __future__ import annotations

from benchmark.lib import flops_laguna, weights_qwen3next

ELEM = 2  # bytes of a bf16 weight or K/V element
F32 = 4


def _decode_spans(run):
    return [ev for ev in run.spans if ev.get("name") == "decode_chunk"]


def active_mean(run):
    """Slots a decode step advanced, over the window's steps: the
    ``decode_chunk`` spans' ``slot_steps`` / steps; None without spans."""
    spans = [ev for ev in _decode_spans(run) if "slot_steps" in ev["args"]]
    if not spans:
        return None
    steps = sum(ev["args"]["slot_steps"] for ev in spans)
    return steps / (len(spans) * run.counters["chunk"])


def state_update_cost(cfg: dict, active: float) -> tuple[float, float]:
    """(operations, bytes) of one decode step's state updates over the
    ``N`` layers for ``active`` slots: each slot's state ``[hv, dk, dv]``
    float32 read and written once, and its convolution rows (``kernel -
    1`` rows of ``2kd + vd``) read and written; operations: the decay, the
    answer ``S^T k``, the rank-one write and ``S^T q`` (four passes of two
    operations an element) and the convolution's taps."""
    z = weights_qwen3next.dims(cfg)
    kd = z["key_heads"] * z["key_dim"]
    vd = z["value_heads"] * z["value_dim"]
    state = z["value_heads"] * z["key_dim"] * z["value_dim"]
    conv_rows = (z["conv"] - 1) * (2 * kd + vd)
    byts = 2 * F32 * (state + conv_rows)
    ops = 8 * state + 2 * z["conv"] * (2 * kd + vd)
    return active * z["n_n"] * ops, active * z["n_n"] * byts


def expert_params(z: dict) -> int:
    """One routed expert: gate, up and down."""
    return 3 * z["d"] * z["expert_dim"]


def decode_step_min_bytes(cfg: dict, touched_per_layer: float,
                          rows_full: float, active: float) -> float:
    """Least bytes one decode step moves: every weight once but the
    embedding table (a step reads its residents' rows of it) and the held
    routed experts, of which the ``touched_per_layer`` the step's tokens
    chose in each expert layer; the live K and V rows of the full layers
    (``rows_full`` positions summed over the residents, each layer); the
    ``active`` slots' states and convolution rows read and written."""
    z = weights_qwen3next.dims(cfg)
    routed = z["n_e"] * z["held"] * expert_params(z)
    rest = weights_qwen3next.count(cfg) - routed - z["vocab"] * z["d"]
    kv_row = 2 * z["kv_heads"] * z["head_dim"]
    return (ELEM * (rest + z["n_e"] * touched_per_layer * expert_params(z)
                    + kv_row * z["n_g"] * rows_full)
            + state_update_cost(cfg, active)[1])


def decode_roofline_pct(run):
    """Least time of a decode step (:func:`decode_step_min_bytes` at the
    HBM peak) / the median decode program run's time a step; None where
    the trace, the experts' count or the spans are missing."""
    import statistics

    if run.trace is None:
        return None
    runs = run.trace.module_seconds(run.traffic["programs"]["decode"])
    touched = flops_laguna.experts_touched_mean(run)
    active = active_mean(run)
    if not runs or touched is None or active is None:
        return None
    steps = flops_laguna.decode_steps(run)
    least = decode_step_min_bytes(
        run.cfg, touched, run.counters["decode_key_rows_full"] / steps,
        active) / run.peaks["hbm_bytes_per_s"]
    return 100.0 * least / (statistics.median(runs) / run.counters["chunk"])
