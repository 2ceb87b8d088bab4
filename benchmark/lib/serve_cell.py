"""A serving cell: ``TextServer`` driven open loop through ``submit`` /
``step`` / ``done`` / ``result`` by one thread, each request sent when it
is due. The program's own journal (``journal=``) is where first-token and
completion times, admissions and dispatch spans come from.

The window opens and closes at a boundary between two ``step`` calls (or
after a wait): a request belongs to the window if it finished after the
opening boundary and by the closing one, and the window's length is the
time between the two boundaries.
"""

from __future__ import annotations

import collections
import gc
import time

import numpy as np

from benchmark.lib import flops, harness, reference, traffic as traffic_lib, weights
from benchmark.lib.train_cell import build_model, to_program_params


class Collector:
    """An in-memory journal with the program's journal interface."""

    path = None

    def __init__(self):
        self.events: list[dict] = []

    def emit(self, kind: str, **fields) -> dict:
        ev = {"t": time.perf_counter(), "ts": time.time(), "kind": kind}
        ev.update(fields)
        self.events.append(ev)
        return ev

    def flush(self) -> None:
        pass

    def close(self) -> None:
        pass


def suffix_lengths(schedule, block_size: int) -> set[int]:
    """Every length a prefill may be asked for: the whole prompt, and
    what is left of it behind a cached shared prefix."""
    out = set()
    for r in schedule:
        out.add(int(r.tokens.size))
        if r.shared:
            out.add(int(r.tokens.size) - (r.shared // block_size) * block_size)
    return out


def build_server(cfg, traffic, seed, journal):
    from distributed_tensorflow_tpu.serve import TextServer

    model = build_model(cfg, traffic)
    params = to_program_params(weights.make(cfg, seed))
    return TextServer(model, params, journal=journal, **traffic["server"])


def warm_compile(server, schedule, vocab: int) -> None:
    """One request through each prefill bucket the traffic can reach, and
    through the decode chunk, so that nothing compiles later."""
    from distributed_tensorflow_tpu.serve import GenerationConfig

    max_len = server.model.max_len
    buckets = sorted({server.bucket_for(n) for n in
                      suffix_lengths(schedule, server.block_size)})
    rng = np.random.default_rng(0)
    rids = [
        server.submit(
            rng.integers(0, vocab, min(b, max_len - 2), dtype=np.int32),
            GenerationConfig(max_new=2))
        for b in buckets
    ]
    while server.step():  # one admission round per free slots, one chunk
        pass
    for rid in rids:
        server.result(rid)


def drive(server, schedule, traffic, seconds, tracer, compiles):
    """Warm-up traffic, then the window. Returns the finished requests'
    records, every answer's length by request id, the boundaries, and what
    was sampled at each boundary."""
    from distributed_tensorflow_tpu.serve import (
        GenerationConfig, RequestCancelled, RequestShed)

    warm = float(traffic["arrivals"]["warmup_s"])
    pending = collections.deque(sorted(schedule, key=lambda r: r.due_s))
    t_plan_open = time.perf_counter() + warm + 0.05
    live: dict = {}
    finished: list = []
    max_new: dict = {}
    kv_used = server.metrics.gauge("kv_blocks_used")
    hits = server.metrics.counter("prefix_cache_hits")
    misses = server.metrics.counter("prefix_cache_misses")
    info = dict(steps=[], kv_used_peak=0, t_open=None, t_close=None)
    while True:
        now = time.perf_counter()
        while pending and t_plan_open + pending[0].due_s <= now:
            r = pending.popleft()
            rid = server.submit(r.tokens, GenerationConfig(max_new=r.max_new))
            max_new[rid] = r.max_new
            live[rid] = dict(
                rid=rid, req=r, sent=time.perf_counter(),
                due=t_plan_open + max(r.due_s, -warm))
        if server.idle():
            if not pending and traffic["arrivals"]["process"] == "all_at_start":
                break  # a queue that was to outlast the window ran dry
            wait = (t_plan_open + pending[0].due_s - time.perf_counter()
                    if pending else 0.25)
            with tracer.annotate("bench:loadgen.wait"):
                time.sleep(min(max(wait, 0.0), 0.25))
        else:
            with tracer.annotate("bench:server.step"):
                t_a = time.perf_counter()
                server.step()
                info["steps"].append((t_a, time.perf_counter()))
        now = time.perf_counter()
        for rid in [rid for rid in live if server.done(rid)]:
            rec = live.pop(rid)
            try:
                rec["out"] = server.result(rid)
            except (RequestShed, RequestCancelled):
                rec["out"] = None
            rec["done"] = now
            finished.append(rec)
        if info["t_open"] is not None:
            info["kv_used_peak"] = max(info["kv_used_peak"], kv_used.value)
        if info["t_open"] is None and now >= t_plan_open:
            info.update(t_open=now, hits0=hits.value, misses0=misses.value,
                        compiles0=compiles.count)
        elif info["t_open"] is not None:
            tracer.maybe_start(now - info["t_open"], seconds)
            if now - info["t_open"] >= seconds:
                info.update(t_close=now, hits1=hits.value, misses1=misses.value,
                            compiles1=compiles.count)
                break
    return finished, max_new, info


def reference_gaps(cfg, seed, sample, precision="float32") -> list[np.ndarray]:
    """For each sampled request, the gaps (see ``reference.token_gaps``)
    from one forward pass over the prompt with its served tokens."""
    import jax.numpy as jnp

    params = weights.make(cfg, seed)
    length = cfg["n_positions"]
    out = []
    for rec in sample:
        prompt, served = rec["req"].tokens, np.asarray(rec["out"])
        seq = np.zeros((1, length), np.int32)
        n = prompt.size + served.size
        seq[0, :n] = np.concatenate([prompt, served])[:length]
        targets = np.roll(seq, -1, axis=1)
        gaps = np.asarray(reference.token_gaps(
            params, jnp.asarray(seq), jnp.asarray(targets), cfg["n_head"], precision))
        # The program is judged at its served tokens; a lower precision
        # (the control) at every position of the prompt and the tokens.
        first = prompt.size - 1 if precision == "float32" else 0
        out.append(gaps[0, first: n - 1])
    return out


def pick_sample(in_window: list, seed: int, count: int) -> list:
    """The longest finished request and ``count - 1`` others drawn from
    the seed."""
    done = [r for r in in_window if r["out"] is not None]
    if not done:
        return []
    longest = max(done, key=lambda r: r["req"].tokens.size + len(r["out"]))
    rest = [r for r in done if r is not longest]
    order = np.random.default_rng([int(seed), 7]).permutation(len(rest))
    return [longest] + [rest[i] for i in order[: count - 1]]


def serve_window(ctx, server, journal) -> tuple[harness.Run, list]:
    """Warm-up traffic and one window on a warmed server; the run's record
    (no checks yet) and the sample of finished requests to check."""
    cfg, traffic = ctx.cfg, ctx.traffic
    schedule = traffic_lib.serve_schedule(
        traffic, cfg["vocab_size"], ctx.seed, ctx.seconds)
    warm_compile(server, schedule, cfg["vocab_size"])
    ctx.mark("warm_compile")
    first_event = len(journal.events)
    finished, max_new, info = drive(
        server, schedule, traffic, ctx.seconds, ctx.tracer, ctx.compiles)
    ctx.tracer.stop()
    ctx.mark("warmup_traffic_and_window")
    run = harness.Run(ctx.cell, cfg, traffic, ctx.chips, ctx.peaks)
    if info["t_close"] is None:
        raise RuntimeError(
            "the schedule ran out before the window closed: more requests "
            "are needed (arrivals.spare_cycles)")
    t_open, t_close = info["t_open"], info["t_close"]
    run.window_s = t_close - t_open
    run.end_to_end["setup_s"] = t_open - ctx.t0
    run.compiles_in_window = info["compiles1"] - info["compiles0"]
    run.memory_peak_bytes = harness.memory_peak_bytes(ctx.devices)

    events = journal.events[first_event:]
    by_rid: dict = collections.defaultdict(dict)
    for ev in events:
        if ev["kind"] in ("admission", "completion") and "rid" in ev:
            by_rid[ev["rid"]][ev["kind"]] = ev
    in_window = [r for r in finished if t_open < r["done"] <= t_close]
    requests = []
    for rec in in_window:
        evs = by_rid[rec["rid"]]
        done, adm = evs.get("completion"), evs.get("admission", {})
        ok = (rec["out"] is not None and done is not None
              and len(rec["out"]) == rec["req"].max_new
              and int(np.min(rec["out"])) >= 0
              and int(np.max(rec["out"])) < cfg["vocab_size"])
        run.failed += int(not ok)
        if not ok:
            continue
        n_out, n_prompt = len(rec["out"]), int(rec["req"].tokens.size)
        requests.append(dict(
            prompt=n_prompt, answer=n_out, prefix=int(adm.get("prefix_len", 0)),
            late_s=rec["sent"] - rec["due"],
            ttft_s=rec["sent"] - rec["due"] + done["ttft_s"],
            queue_s=adm.get("queue_wait_s"),
            tpot_ms=1e3 * (done["latency_s"] - done["ttft_s"]) / max(n_out - 1, 1),
        ))
    run.attempted = len(in_window)
    run.requests = requests
    run.spans = [
        ev for ev in events
        if ev["kind"] == "span" and t_open <= ev["t"] <= t_close]
    run.end_to_end["serve_tokens_per_s"] = tokens_in_window(
        events, max_new, server.chunk, t_open, t_close) / run.window_s
    if requests:
        run.end_to_end["tpot_p50_ms"] = float(
            np.median([r["tpot_ms"] for r in requests]))
    decode_rows = [
        sum(range(r["prompt"] + 1, r["prompt"] + r["answer"])) for r in requests]
    run.counters.update(
        requests_finished=len(requests),
        tokens_run=sum(r["prompt"] - r["prefix"] + r["answer"] - 1 for r in requests),
        keys_seen=sum(
            flops.causal_pairs(r["prompt"]) - flops.causal_pairs(r["prefix"]) + d
            for r, d in zip(requests, decode_rows)),
        decode_key_rows=sum(decode_rows),
        logits_rows=sum(r["answer"] for r in requests),
        chunk=server.chunk, block_size=server.block_size,
        kv_blocks_total=server.kv_blocks, kv_blocks_peak=info["kv_used_peak"],
        prefix_hit_blocks=info["hits1"] - info["hits0"],
        prefix_miss_blocks=info["misses1"] - info["misses0"],
        step_wall_s=sum(b - a for a, b in info["steps"] if t_open <= a and b <= t_close),
    )
    return run, pick_sample(in_window, ctx.seed, traffic["check_requests"])


def tokens_in_window(events, max_new: dict, chunk: int, t_open, t_close) -> int:
    """All tokens of the work done inside the window: the prompt tokens of
    every request admitted in it (with the first answer token, which the
    prefill picks) and the answer tokens every decode chunk in it
    delivered, from the program's own admission events and decode_chunk
    spans. A request that straddles an edge counts with the part of it
    that lies inside: the edges are step boundaries, so no chunk does."""
    left: dict = {}
    total = 0
    for ev in events:
        inside = t_open < ev["t"] <= t_close
        if ev["kind"] == "admission" and ev.get("rid") in max_new:
            left[ev["rid"]] = max_new[ev["rid"]] - 1
            total += (ev["prompt_len"] + 1) if inside else 0
        elif ev["kind"] == "span" and ev.get("name") == "decode_chunk":
            for rid in ev["args"]["rids"]:
                n = min(chunk, left.get(rid, 0))
                left[rid] = left.get(rid, 0) - n
                total += n if inside else 0
    return total


def widest_gap(gaps: list) -> float:
    return max((float(g.max()) for g in gaps), default=float("nan"))


def run(ctx) -> harness.Run:
    journal = Collector()
    server = build_server(ctx.cfg, ctx.traffic, ctx.seed, journal)
    ctx.mark("weights_and_server")
    run, sample = serve_window(ctx, server, journal)
    del server  # holds no thread; residents still decoding are dropped
    gc.collect()
    gaps = reference_gaps(ctx.cfg, ctx.seed, sample)
    lim = harness.limits(ctx.cell)
    run.checks = {"max_logit_gap": (widest_gap(gaps), lim["max_logit_gap"])}
    run.counters["checked_tokens"] = int(sum(g.size for g in gaps))
    ctx.mark("reference")
    run.trace = ctx.tracer.summary(ctx.chips)
    return run
