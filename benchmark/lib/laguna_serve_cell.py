"""A serving cell of a ``laguna`` stack (``"kind": "laguna_serve"``):
``HybridLM`` over the kinds ``G`` (full attention), ``W`` (window
attention), ``D`` and ``E`` behind ``TextServer``'s paged engine, driven
open loop as ``lib/serve_cell.py`` drives the chat cell, whose loop,
window and accounting it takes. What is its own:

- the model and the server are built before the weights exist (a program
  without the window layers' serving path refuses at once);
- the schedule: two length classes in one queue (``traffic.length_list``
  once for each, ``short_share`` of the requests short), every other
  list as ``traffic.serve_schedule`` makes it;
- the warm-up: one dispatch of every (bucket, rows) prefill program the
  queue can reach, and the decode chunk;
- the counters the ``.laguna`` metrics read: live K/V rows of the full
  and the window layers, the experts each decode chunk touched (the
  ``decode_chunk`` spans' ``experts_touched``), and, traced, the decode
  and prefill programs by scope (``lib/program_scopes.py``);
- the check: ``max_logit_gap`` and ``mean_logit_gap`` of the served
  tokens against ``lib/reference_laguna.py``, each served token against
  the reference's best at its position (:func:`gap_numbers`), the
  sample's sequences padded to the model's ``max_len``; the limits file
  names the numbers compared.
"""

from __future__ import annotations

import gc
import glob
import math
import os
import traceback

import numpy as np

from benchmark.lib import (
    harness, program_scopes, reference_laguna as reference,
    traffic as traffic_lib, weights_laguna as weights,
)
from benchmark.lib.serve_cell import (
    Collector, drive, pick_sample, tokens_in_window,
)


def build_model(cfg: dict, traffic: dict):
    from distributed_tensorflow_tpu.models.hybrid import HybridLM
    from distributed_tensorflow_tpu.ops.rotary import Rotary

    z = weights.dims(cfg)
    return HybridLM(
        z["vocab"], z["d"], z["pattern"],
        full_heads=z["full_heads"], window_heads=z["window_heads"],
        num_kv_heads=z["kv_heads"], head_dim=z["head_dim"], window=z["window"],
        full_rotary=Rotary(**z["full_rope"]),
        window_rotary=Rotary(**z["window_rope"]),
        dense_dim=z["dense_dim"], expert_form="silu_gated",
        num_experts=z["experts"], experts_per_token=z["top_k"],
        expert_dim=z["expert_dim"], shared_dim=z["shared_dim"],
        routed_scale=z["routed_scale"], norm_eps=z["eps"],
        depth_for_init=z["depth"], max_len=traffic["max_len"],
        attention_impl=traffic.get("attention_impl", "xla"),
    )


def to_program_params(tree: dict):
    from distributed_tensorflow_tpu.models import hybrid

    def layers(cls, group):  # a leaf as a tuple of its layers' arrays
        return cls(**{k: tuple(v) for k, v in tree[group].items()})

    return hybrid.StackLMParams(
        embed=tree["embed"], norm_f=tree["norm_f"], head=tree["head"],
        mamba=None, attn=None, kda=None, mla=None,
        dense=layers(hybrid.DenseParams, "dense"),
        moe=layers(hybrid.GatedExpertParams, "moe"),
        full=layers(hybrid.GatedAttnParams, "full"),
        window=layers(hybrid.GatedAttnParams, "window"))


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


# The one draw of a cycle's order that every seed runs (see mixed_schedule).
ORDER = 0


def mixed_schedule(traffic: dict, vocab: int, seed: int, seconds: float):
    """:func:`traffic.serve_schedule` over two prompt classes: of ``n``
    requests a cycle, ``round(n * short_share)`` draw their prompt from
    ``prompt_short``'s midpoints and the rest from ``prompt_long``'s, the
    long ones at evenly spread places of the cycle (every fourth at a
    share of 3 in 4). The order of the lengths within each class, of the
    answers and of the gaps is one fixed draw (``ORDER``); the seed draws
    the prompts' tokens. A long prompt's prefill stalls every resident
    and a long context slows every decode step (the full layers read all
    of it), so which requests overlap which decides a request's time per
    token: drawn by the seed, the order moved ``tpot_p50_ms`` by half
    between seeds."""
    arr = traffic["arrivals"]
    n = max(1, round(arr["rate_per_s"] * seconds))
    n_long = n - round(n * traffic["short_share"])
    long_at = np.zeros(n, bool)
    long_at[((np.arange(n_long) + 0.5) * n / max(n_long, 1)).astype(int)] = True
    own = np.zeros(n, np.int64)
    own[~long_at] = traffic_lib.length_list(
        traffic["prompt_short"], n - n_long)[
        traffic_lib._rng(ORDER, 1).permutation(n - n_long)]
    own[long_at] = traffic_lib.length_list(traffic["prompt_long"], n_long)[
        traffic_lib._rng(ORDER, 3).permutation(n_long)]
    ans = traffic_lib.length_list(traffic["answer"], n)
    ans = ans[traffic_lib._rng(ORDER, 2).permutation(n)]
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


def prefill_programs(server, schedule) -> list[tuple[int, int]]:
    """Every (bucket, rows) prefill the schedule can reach."""
    from distributed_tensorflow_tpu import serve

    return sorted({
        (b, serve.prefill_rows(server.slots, b, admitted))
        for b in {server.bucket_for(int(r.tokens.size)) for r in schedule}
        for admitted in range(1, server.slots + 1)})


def warm_compile(server, schedule, vocab: int) -> None:
    """Each (bucket, rows) prefill program once — ``rows`` requests of the
    bucket's length admitted in one round (one, where the dispatch is the
    whole bank) — then the decode chunk."""
    from distributed_tensorflow_tpu.serve import GenerationConfig

    rng = np.random.default_rng(0)
    for bucket, rows in prefill_programs(server, schedule):
        rids = [server.submit(
            rng.integers(0, vocab, bucket, dtype=np.int32),
            GenerationConfig(max_new=2))
            for _ in range(1 if rows == server.slots else rows)]
        while server.step():
            pass
        for rid in rids:
            server.result(rid)


def decode_rows(requests, window: int) -> tuple[int, int]:
    """Keys the decode steps of the finished requests read, summed over
    their steps: a full layer reads every earlier position, a window
    layer at most ``window``."""
    full = win = 0
    for r in requests:
        pos = np.arange(r["prompt"] + 1, r["prompt"] + r["answer"])
        full += int(pos.sum())
        win += int(np.minimum(pos, window).sum())
    return full, win


def serve_window(ctx, server, journal):
    cfg, traffic = ctx.cfg, ctx.traffic
    schedule = mixed_schedule(traffic, cfg["vocab_size"], ctx.seed, ctx.seconds)
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
    z = weights.dims(cfg)
    full, win = decode_rows(requests, z["window"])
    run.counters.update(
        requests_finished=len(requests), chunk=server.chunk,
        block_size=server.block_size, kv_blocks_total=server.kv_blocks,
        kv_blocks_peak=info["kv_used_peak"],
        ring_blocks=server.ring_blocks,
        decode_key_rows_full=full, decode_key_rows_window=win,
        step_wall_s=sum(b - a for a, b in info["steps"]
                        if t_open <= a and b <= t_close),
    )
    return run, pick_sample(in_window, ctx.seed, traffic["check_requests"])


def sample_gaps(tree, cfg, traffic, sample, precision="float32", fault=None):
    """For each sampled request, the gaps over its prompt and served
    tokens: how far below the reference's best logit the served token
    lies (the positions after the prompt), or for ``precision="int8"``
    the token int8 puts first, at every position (the control)."""
    import jax.numpy as jnp

    z = weights.dims(cfg)
    length = traffic["max_len"]
    ring_rows = (math.ceil(z["window"] / traffic["server"]["block_size"]) + 1
                 ) * traffic["server"]["block_size"]
    out = []
    for rec in sample:
        prompt, served = rec["req"].tokens, np.asarray(rec["out"])
        n = min(prompt.size + served.size, length)
        seq = np.zeros(length, np.int32)
        seq[:n] = np.concatenate([prompt, served])[:n]
        targets = jnp.asarray(np.roll(seq, -1))
        h = reference.final_hidden(
            tree, seq, n, z, "float32", fault, ring_rows,
            traffic["server"]["block_size"])
        if precision != "float32":
            h8 = reference.final_hidden(tree, seq, n, z, precision)
            targets = reference.head_scan(h8, tree["head"], targets, precision)[2]
            del h8
        best, picked, _ = reference.head_scan(h, tree["head"], targets)
        gaps = np.asarray(best - picked)
        first = prompt.size - 1 if precision == "float32" else 0
        out.append(gaps[first: n - 1])
    return out


def gap_numbers(gaps) -> dict:
    """What the check compares: the widest gap, and the mean gap over
    every checked position. A routed choice that a bfloat16 rounding tips
    to the next expert moves one position's logits as far as some faults
    move them (the widest gap cannot tell those apart); a fault moves
    many positions, a tipped choice few."""
    if not gaps:
        return {"max_logit_gap": float("nan"), "mean_logit_gap": float("nan")}
    every = np.concatenate(gaps)
    return {"max_logit_gap": float(every.max()),
            "mean_logit_gap": float(every.mean())}


def _xplane(tracer):
    paths = sorted(glob.glob(os.path.join(
        tracer.dir, "plugins", "profile", "*", "*.xplane.pb")))
    return paths[-1] if paths else None


def reduce_programs(ctx) -> None:
    """Traced: the decode and prefill programs by scope, kept in the run's
    counters before the harness reduces and deletes the profile."""
    ctx.tracer.stop()
    path = _xplane(ctx.tracer) if ctx.tracer.state == "done" else None
    if path is None:
        return None
    out = {}
    for which, prefix in ctx.traffic["programs"].items():
        try:
            out[which] = program_scopes.reduce(path, prefix)
        except Exception:
            traceback.print_exc()
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
    run.checks = {k: (v, lim[k]) for k, v in gap_numbers(gaps).items() if k in lim}
    run.counters["checked_tokens"] = int(sum(g.size for g in gaps))
    ctx.mark("reference")
    run.trace = ctx.tracer.summary(ctx.chips)
    return run
