"""A serving cell of a ``qwen3_next`` stack (``"kind": "qwen3next_serve"``):
``HybridLM`` over the kinds ``N`` (gated delta rule, a decay per head),
``G`` (gated full attention with q/k norms) and ``E`` (softmax-routed
experts with a gated shared expert) behind ``TextServer``'s paged engine,
driven open loop by ``lib/serve_cell.drive`` as the chat and coding cells
are. It takes the coding cell's (``lib/laguna_serve_cell.py``) prefill
warm-up, program reduction and gap numbers as they are. What is its own:

- the model, with each slot's recurrent and convolution states in the
  cache beside the full layers' pool, built before the weights exist (a
  program without a serving path for these layers refuses at once);
- the schedule: one class of prompts and answers whose order is one fixed
  draw, the same under every seed (``ORDER``, the coding cell's rule);
- the counters the ``.qnext`` metrics read: the full layers' live K/V
  rows of the finished requests' decode steps, and, traced, the decode
  and prefill programs by scope;
- the check: the gaps of the served tokens against
  ``lib/reference_qwen3next.py`` (:func:`gap_numbers`), the sample's
  sequences padded to the model's ``max_len``; the limits file names the
  numbers compared, the others are kept in the counters.
"""

from __future__ import annotations

import gc
import math

import numpy as np

from benchmark.lib import (
    harness, reference_qwen3next as reference, traffic as traffic_lib,
    weights_qwen3next as weights,
)
from benchmark.lib import laguna_serve_cell
from benchmark.lib.laguna_serve_cell import reduce_programs, warm_compile
from benchmark.lib.serve_cell import (
    Collector, drive, pick_sample, tokens_in_window,
)


def build_model(cfg: dict, traffic: dict):
    from distributed_tensorflow_tpu.models.hybrid import HybridLM
    from distributed_tensorflow_tpu.ops.rotary import Rotary

    z = weights.dims(cfg)
    return HybridLM(
        z["vocab"], z["d"], z["pattern"],
        full_heads=z["heads"], num_kv_heads=z["kv_heads"],
        head_dim=z["head_dim"], full_rotary=Rotary(**z["rope"]),
        full_gate="element", full_qk_norm=True,
        gdn_key_heads=z["key_heads"], gdn_key_dim=z["key_dim"],
        gdn_value_heads=z["value_heads"], gdn_value_dim=z["value_dim"],
        conv_kernel=z["conv"], expert_form="silu_gated",
        moe_router="softmax", num_experts=z["experts"],
        experts_held=(0, z["held"]), experts_per_token=z["top_k"],
        expert_dim=z["expert_dim"], shared_dim=z["shared_dim"],
        norm_offset=1.0, norm_eps=z["eps"], depth_for_init=z["depth"],
        max_len=traffic["max_len"],
        attention_impl=traffic.get("attention_impl", "xla"),
    )


def to_program_params(tree: dict):
    from distributed_tensorflow_tpu.models import hybrid

    def layers(cls, group):  # a leaf as a tuple of its layers' arrays
        return cls(**{k: tuple(v) for k, v in tree[group].items()})

    return hybrid.StackLMParams(
        embed=tree["embed"], norm_f=tree["norm_f"], head=tree["head"],
        mamba=None, attn=None, kda=None, mla=None, dense=None,
        moe=layers(hybrid.SoftmaxExpertParams, "moe"),
        full=layers(hybrid.NormedGatedAttnParams, "full"),
        gdn=layers(hybrid.GdnParams, "gdn"))


def build_server(cfg, traffic, journal):
    """The server with no weights yet (they are set by :func:`give_weights`)."""
    from distributed_tensorflow_tpu.serve import TextServer

    return TextServer(build_model(cfg, traffic), None, journal=journal,
                      **traffic["server"])


def give_weights(server, cfg, seed) -> dict:
    server.params = None
    gc.collect()
    tree = weights.make(cfg, seed)
    server.params = to_program_params(tree)
    return tree


# The one draw of a cycle's order that every seed runs.
ORDER = 0


def reason_schedule(traffic: dict, vocab: int, seed: int, seconds: float):
    """:func:`traffic.serve_schedule`'s periodic cycle of ``rate x
    seconds`` requests, with the order of the prompts' lengths, the
    answers' and the gaps one fixed draw (``ORDER``): which requests are
    resident together sets every resident's step, and a seed that drew the
    order moved the coding cell's ``tpot_p50_ms`` by half. The seed draws
    the prompts' tokens."""
    arr = traffic["arrivals"]
    n = max(1, round(arr["rate_per_s"] * seconds))
    own = traffic_lib.length_list(traffic["prompt"], n)[
        traffic_lib._rng(ORDER, 1).permutation(n)]
    ans = traffic_lib.length_list(traffic["answer"], n)[
        traffic_lib._rng(ORDER, 2).permutation(n)]
    gaps = traffic_lib.gap_list(n, seconds)[traffic_lib._rng(ORDER, 4).permutation(n)]
    due = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    ids = traffic_lib._rng(seed, 5)
    warm = float(arr["warmup_s"])
    out = []
    for back in range(math.ceil(warm / seconds), -1, -1):
        for i in range(n):
            t = float(due[i]) - back * seconds
            toks = ids.integers(0, vocab, int(own[i]), dtype=np.int32)
            if t >= -warm:
                out.append(traffic_lib.Request(t, toks, int(ans[i])))
    return out


def serve_window(ctx, server, journal):
    cfg, traffic = ctx.cfg, ctx.traffic
    schedule = reason_schedule(traffic, cfg["vocab_size"], ctx.seed, ctx.seconds)
    warm_compile(server, schedule, cfg["vocab_size"])
    ctx.mark("warm_compile")
    first_event = len(journal.events)
    finished, max_new, info = drive(
        server, schedule, traffic, ctx.seconds, ctx.tracer, ctx.compiles)
    ctx.tracer.stop()
    ctx.mark("warmup_traffic_and_window")
    if info["t_close"] is None:
        raise RuntimeError("the schedule ran out before the window closed")
    run = harness.Run(ctx.cell, cfg, traffic, ctx.chips, ctx.peaks)
    t_open, t_close = info["t_open"], info["t_close"]
    run.window_s = t_close - t_open
    run.end_to_end["setup_s"] = t_open - ctx.t0
    run.compiles_in_window = info["compiles1"] - info["compiles0"]
    run.memory_peak_bytes = harness.memory_peak_bytes(ctx.devices)
    events = journal.events[first_event:]
    by_rid: dict = {}
    for ev in events:
        if ev["kind"] in ("admission", "completion") and "rid" in ev:
            by_rid.setdefault(ev["rid"], {})[ev["kind"]] = ev
    in_window = [r for r in finished if t_open < r["done"] <= t_close]
    requests = []
    for rec in in_window:
        evs = by_rid.get(rec["rid"], {})
        done, adm = evs.get("completion"), evs.get("admission", {})
        ok = (rec["out"] is not None and done is not None
              and len(rec["out"]) == rec["req"].max_new
              and int(np.min(rec["out"])) >= 0
              and int(np.max(rec["out"])) < cfg["vocab_size"])
        run.failed += int(not ok)
        if ok:
            n_out = len(rec["out"])
            requests.append(dict(
                prompt=int(rec["req"].tokens.size), answer=n_out, prefix=0,
                late_s=rec["sent"] - rec["due"],
                ttft_s=rec["sent"] - rec["due"] + done["ttft_s"],
                queue_s=adm.get("queue_wait_s"),
                tpot_ms=1e3 * (done["latency_s"] - done["ttft_s"]) / max(n_out - 1, 1),
            ))
    run.attempted = len(in_window)
    run.requests = requests
    run.spans = [ev for ev in events
                 if ev["kind"] == "span" and t_open <= ev["t"] <= t_close]
    run.end_to_end["serve_tokens_per_s"] = tokens_in_window(
        events, max_new, server.chunk, t_open, t_close) / run.window_s
    if requests:
        run.end_to_end["tpot_p50_ms"] = float(
            np.median([r["tpot_ms"] for r in requests]))
    # keys the finished requests' decode steps read in a full layer: every
    # earlier position
    full = sum(int(np.arange(r["prompt"] + 1, r["prompt"] + r["answer"]).sum())
               for r in requests)
    run.counters.update(
        requests_finished=len(requests), chunk=server.chunk,
        block_size=server.block_size, kv_blocks_total=server.kv_blocks,
        kv_blocks_peak=info["kv_used_peak"],
        slot_state_bytes=server.slot_state_bytes,
        decode_key_rows_full=full,
        step_wall_s=sum(b - a for a, b in info["steps"]
                        if t_open <= a and b <= t_close),
    )
    return run, pick_sample(in_window, ctx.seed, traffic["check_requests"])


def sample_gaps(tree, cfg, traffic, sample, precision="float32", fault=None):
    """For each sampled request, the gaps over its served tokens: how far
    below the reference's best logit the served token lies (the positions
    from the prompt's last), or for a control's ``precision`` (``"int8"``,
    ``"state_bf16"``) the token the reference in that precision puts
    first, at every position. A fault that reads where the decode began is
    told the request's prompt length."""
    import jax.numpy as jnp

    z = weights.dims(cfg)
    length = traffic["max_len"]
    out = []
    for rec in sample:
        prompt, served = rec["req"].tokens, np.asarray(rec["out"])
        n = min(prompt.size + served.size, length)
        seq = np.zeros(length, np.int32)
        seq[:n] = np.concatenate([prompt, served])[:n]
        targets = jnp.asarray(np.roll(seq, -1))
        h = reference.final_hidden(tree, seq, z, "float32", fault,
                                   prompt_len=prompt.size)
        if precision != "float32":
            h8 = reference.final_hidden(tree, seq, z, precision)
            targets = reference.head_scan(h8, tree["head"], targets, precision)[2]
            del h8
        best, picked, _ = reference.head_scan(h, tree["head"], targets)
        gaps = np.asarray(best - picked)
        first = prompt.size - 1 if precision == "float32" else 0
        out.append(gaps[first: n - 1])
    return out


FIRST = 16  # the served positions of a request that ``first_logit_gap`` reads


def gap_numbers(gaps) -> dict:
    """The coding cell's numbers (the widest gap, the mean gap over every
    checked position), and ``first_logit_gap``: the mean gap over the
    first :data:`FIRST` served positions of each sampled request, where a
    state the prefill handed on wrongly shows before the recurrence's
    decay has worn it away."""
    out = laguna_serve_cell.gap_numbers(gaps)
    heads = [g[:FIRST] for g in gaps if g.size]
    out["first_logit_gap"] = (float(np.concatenate(heads).mean()) if heads
                              else float("nan"))
    return out


def run(ctx) -> harness.Run:
    import jax

    if ctx.tracer.enabled:
        # a traced run may not read a program cached without its scopes
        jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    journal = Collector()
    server = build_server(ctx.cfg, ctx.traffic, journal)
    ctx.mark("server")
    tree = give_weights(server, ctx.cfg, ctx.seed)
    ctx.mark("weights")
    run, sample = serve_window(ctx, server, journal)
    by_program = reduce_programs(ctx)
    if by_program:
        run.counters["by_program"] = by_program
    del server  # holds no thread; residents still decoding are dropped
    gc.collect()
    gaps = sample_gaps(tree, ctx.cfg, ctx.traffic, sample)
    lim = harness.limits(ctx.cell)
    numbers = gap_numbers(gaps)
    run.checks = {k: (v, lim[k]) for k, v in numbers.items() if k in lim}
    run.counters.update(checked_tokens=int(sum(g.size for g in gaps)),
                        **{k: v for k, v in numbers.items() if k not in lim})
    ctx.mark("reference")
    run.trace = ctx.tracer.summary(ctx.chips)
    return run
