"""Measured record of the serving engine's two perf levers (serve.py).

The engine makes two throughput claims, each a measured-design decision:

- **Batched slots**: 8 concurrent requests through one slot bank vs the
  same requests served one at a time (slots=1) — decode is memory-bound
  per step, so batching rides along nearly free and one dispatch's
  fixed cost is shared by 8 streams.
- **Multi-token chunks**: k decode steps (including sampling) per
  dispatch via ``lax.scan`` vs one dispatch per token — every
  dispatch+fetch has a fixed host cost (not measured on a directly
  attached chip yet — ROADMAP S2), so per-token cost at chunk k
  amortizes it k ways.

Timing discipline: every TextServer chunk ENDS in a D2H fetch of the
token block (the scheduler needs the values), so wall-clock around a
served workload is dispatch-inclusive and barrier-honest by construction
— exactly the quantity a serving client sees. The chunk sweep
additionally separates the per-dispatch fixed cost C from the marginal
per-token cost t by a least-squares fit of ``wall = (N/k)·C + N·t`` over
the chunk sizes — the two-point method generalized to the k-point chain.

Usage::

    python -m distributed_tensorflow_tpu.tools.serve_bench              # print
    python -m distributed_tensorflow_tpu.tools.serve_bench --write-docs # commit

``--write-docs`` writes docs/benchmarks/serving.md + serving.json;
tests/test_serve.py pins the committed md against the committed json
(the perf_record staleness pattern: a new artifact cannot land without
regenerating the doc).
"""

from __future__ import annotations

import argparse
import json
import os
import time

import numpy as np

import jax
import jax.numpy as jnp


def _build(model_kw=None):
    from distributed_tensorflow_tpu.models.gpt import GPTLM

    kw = dict(
        vocab_size=512,
        max_len=256,
        model_dim=128,
        num_heads=4,
        num_layers=2,
    )
    kw.update(model_kw or {})
    model = GPTLM(**kw)
    return model, model.init(seed=1)


def _workload(model, n_requests: int, max_new: int, seed: int = 0):
    rng = np.random.default_rng(seed)
    sizes = rng.integers(8, 60, n_requests)
    prompts = [
        rng.integers(0, model.vocab_size, (int(s),)).astype(np.int32)
        for s in sizes
    ]
    from distributed_tensorflow_tpu.serve import GenerationConfig

    return prompts, GenerationConfig(max_new=max_new)


def _make_server(model, params, *, slots, chunk):
    """One server per (slots, chunk) config, WARMED once: jit caches live
    on the instance, so the measured runs below re-dispatch the compiled
    executables (a fresh server per run would re-trace — the first version
    of this bench did, and its 'per-token cost' was mostly tracing)."""
    from distributed_tensorflow_tpu.serve import GenerationConfig, TextServer

    srv = TextServer(model, params, slots=slots, chunk=chunk, buckets=(64,))
    warm = [np.arange(1, 9, dtype=np.int32)] * min(2, slots)
    srv.generate(warm, GenerationConfig(max_new=max(2, chunk)))
    return srv


def _serve_wall(srv, prompts, cfg) -> float:
    """Wall seconds to serve the workload to completion on a warmed
    server. Each chunk's token fetch is the D2H barrier, so this is
    honest dispatch-inclusive time."""
    t0 = time.perf_counter()
    srv.generate(prompts, cfg)
    return time.perf_counter() - t0


def _serve_wall_tracked(srv, prompts, cfg):
    """Like :func:`_serve_wall` but drives the engine tick by tick,
    tracking peak concurrent occupancy (the slot-density observable)
    and the number of decode dispatches (the tokens/dispatch
    denominator for the speculation row). Both are read off the
    engine's dispatch SPANS, whose ``active`` attr snapshots occupancy
    while the dispatch ran — the ``slots_busy`` gauge is re-set to
    post-completion occupancy before ``step()`` returns, so reading it
    here would miss every tick that finished the last active slot
    (undercounting dispatches inflates tokens/dispatch)."""
    rids = [srv.submit(p, cfg) for p in prompts]
    n0 = len(srv.spans.spans)
    t0 = time.perf_counter()
    while srv.step():
        pass
    wall = time.perf_counter() - t0
    decode = [
        sp
        for sp in list(srv.spans.spans)[n0:]
        if sp["name"] in ("decode_chunk", "spec_verify")
    ]
    peak = max((sp["args"]["active"] for sp in decode), default=0)
    for r in rids:
        srv.result(r)
    return wall, peak, len(decode)


def bench_paged_density(
    *,
    slab_slots: int = 4,
    density_factor: int = 4,
    n_requests: int = 32,
    max_new: int = 40,
    block_size: int = 16,
    model_kw=None,
) -> dict:
    """Paged-vs-slab occupancy at EQUAL KV HBM on a short-request mix.

    The slab bank reserves ``slots × max_len`` positions regardless of
    request size; the paged pool holds the SAME number of positions
    (``kv_blocks × block_size = slab_slots × max_len``) but admits by
    actual footprint (``ceil((prompt+max_new)/bs)`` blocks), so short
    requests pack ``density_factor`` × more concurrent residents into
    identical memory. Measured, not asserted: peak concurrent occupancy
    is counted from dispatch-span ``active`` attrs while each server
    drains the same workload (``_serve_wall_tracked`` — NOT the
    ``slots_busy`` gauge, which is re-set to post-completion occupancy
    before ``step()`` returns and misses every tick that finishes the
    last active slot)."""
    from distributed_tensorflow_tpu.serve import GenerationConfig, TextServer

    model, params = _build(model_kw)
    rng = np.random.default_rng(7)
    prompts = [
        rng.integers(0, model.vocab_size, (int(s),)).astype(np.int32)
        for s in rng.integers(8, 25, n_requests)
    ]
    cfg = GenerationConfig(max_new=max_new)
    pool_positions = slab_slots * model.max_len
    paged_slots = slab_slots * density_factor
    kv_blocks = pool_positions // block_size

    slab = TextServer(
        model, params, slots=slab_slots, chunk=32, buckets=(32,)
    )
    paged = TextServer(
        model, params, slots=paged_slots, chunk=32, buckets=(32,),
        paged=True, block_size=block_size, kv_blocks=kv_blocks,
    )
    warm = [np.arange(1, 9, dtype=np.int32)] * 2
    slab.generate(warm, GenerationConfig(max_new=2))
    paged.generate(warm, GenerationConfig(max_new=2))

    slab_wall, slab_peak, _ = _serve_wall_tracked(slab, prompts, cfg)
    paged_wall, paged_peak, _ = _serve_wall_tracked(paged, prompts, cfg)
    total_tokens = n_requests * max_new
    return {
        "kv_hbm_positions": pool_positions,
        "block_size": block_size,
        "workload": {
            "requests": n_requests,
            "prompt_range": [8, 24],
            "max_new": max_new,
        },
        "slab": {
            "slots": slab_slots,
            "peak_occupancy": int(slab_peak),
            "wall_s": round(slab_wall, 4),
            "tokens_per_s": round(total_tokens / slab_wall, 1),
        },
        "paged": {
            "slots": paged_slots,
            "kv_blocks": kv_blocks,
            "peak_occupancy": int(paged_peak),
            "wall_s": round(paged_wall, 4),
            "tokens_per_s": round(total_tokens / paged_wall, 1),
        },
        "density_x": round(paged_peak / max(slab_peak, 1), 2),
        "throughput_x": round(slab_wall / paged_wall, 2),
    }


def bench_quantized_density(
    *,
    bf16_blocks: int = 64,
    block_size: int = 16,
    n_requests: int = 16,
    max_new: int = 144,
    slots: int = 12,
    kv_dtype: str = "int8",
    model_kw=None,
) -> dict:
    """Quantized-vs-bf16 paged pools at EQUAL KV HBM **bytes** (round
    15). The bf16 pool holds ``bf16_blocks``; the quantized pool gets
    the SAME byte budget through ``kv_hbm_bytes``, so its block count
    derives from the element size (int8 payload + the f32 per-row
    scales, charged honestly by ``serve_pool.kv_block_bytes``) — ~1.8×
    the blocks at these shapes. On a long-generation mix (every request
    reserves the same worst-case block count) the byte-smaller blocks
    also pack the bf16 pool's remainder, and the measured peak
    occupancy doubles: the ``slot_density_q`` series. Peaks are counted
    from dispatch-span ``active`` attrs exactly as
    :func:`bench_paged_density` does; occupancy is admission-control
    arithmetic (deterministic for a fixed workload), so the series is
    stable under the regression gate even off-chip — only the wall
    columns carry device provenance."""
    from distributed_tensorflow_tpu import serve_pool
    from distributed_tensorflow_tpu.ops.quantized import kv_elem_bytes
    from distributed_tensorflow_tpu.serve import GenerationConfig, TextServer

    model, params = _build(model_kw)
    rng = np.random.default_rng(13)
    prompts = [
        rng.integers(0, model.vocab_size, (int(s),)).astype(np.int32)
        for s in rng.integers(17, 33, n_requests)
    ]
    cfg = GenerationConfig(max_new=max_new)
    budget = bf16_blocks * serve_pool.kv_block_bytes(
        block_size,
        num_layers=model.num_layers,
        kv_heads=model.num_kv_heads,
        head_dim=model.head_dim,
        elem_bytes=kv_elem_bytes("bf16", model.compute_dtype),
    )
    kw = dict(
        slots=slots, chunk=32, buckets=(32,), paged=True,
        block_size=block_size,
    )
    bf16 = TextServer(model, params, kv_blocks=bf16_blocks, **kw)
    quant = TextServer(
        model, params, kv_hbm_bytes=budget, kv_dtype=kv_dtype, **kw
    )
    warm = [np.arange(1, 9, dtype=np.int32)] * 2
    bf16.generate(warm, GenerationConfig(max_new=2))
    quant.generate(warm, GenerationConfig(max_new=2))

    bf16_wall, bf16_peak, _ = _serve_wall_tracked(bf16, prompts, cfg)
    q_wall, q_peak, _ = _serve_wall_tracked(quant, prompts, cfg)
    total_tokens = n_requests * max_new
    device = jax.devices()[0].device_kind
    return {
        "device": device,
        "kv_hbm_bytes": budget,
        "block_size": block_size,
        "workload": {
            "requests": n_requests,
            "prompt_range": [17, 32],
            "max_new": max_new,
        },
        "bf16": {
            "kv_blocks": bf16.kv_blocks,
            "positions": bf16.kv_blocks * block_size,
            "block_bytes": bf16.kv_block_bytes,
            "peak_occupancy": int(bf16_peak),
            "wall_s": round(bf16_wall, 4),
            "tokens_per_s": round(total_tokens / bf16_wall, 1),
        },
        "quantized": {
            "kv_dtype": kv_dtype,
            "kv_blocks": quant.kv_blocks,
            "positions": quant.kv_blocks * block_size,
            "block_bytes": quant.kv_block_bytes,
            "peak_occupancy": int(q_peak),
            "wall_s": round(q_wall, 4),
            "tokens_per_s": round(total_tokens / q_wall, 1),
        },
        "positions_x": round(quant.kv_blocks / bf16.kv_blocks, 2),
        "density_q_x": round(q_peak / max(bf16_peak, 1), 2),
    }


def bench_weight_only_decode(
    *,
    n_requests: int = 8,
    max_new: int = 64,
    slots: int = 4,
    chunk: int = 32,
    dtype: str = "int8",
    model_kw=None,
) -> dict:
    """Decode tokens/s A/B for the weight-only path: the same greedy
    workload through a full-precision server and one with
    ``decode_matmul_dtype`` set (projection weights pre-quantized at
    construction, ``wo_dot`` at every block matmul). The claim is HBM
    traffic — decode reads every weight per token — so CPU numbers are
    provenance only (the dequant-and-dot emulation can even run SLOWER
    there); the chip's speedup is not measured, exactly like the
    round-13 ``matmul_dtype`` row."""
    from distributed_tensorflow_tpu.serve import GenerationConfig, TextServer

    model, params = _build(model_kw)
    prompts, cfg = _workload(model, n_requests, max_new, seed=3)
    kw = dict(slots=slots, chunk=chunk, buckets=(64,))
    base = TextServer(model, params, **kw)
    wo = TextServer(model, params, decode_matmul_dtype=dtype, **kw)
    warm = [np.arange(1, 9, dtype=np.int32)] * 2
    base.generate(warm, GenerationConfig(max_new=4))
    wo.generate(warm, GenerationConfig(max_new=4))
    base_wall = min(_serve_wall(base, prompts, cfg) for _ in range(2))
    wo_wall = min(_serve_wall(wo, prompts, cfg) for _ in range(2))
    total_tokens = n_requests * max_new
    return {
        "device": jax.devices()[0].device_kind,
        "dtype": dtype,
        "workload": {"requests": n_requests, "max_new": max_new},
        "baseline_tokens_per_s": round(total_tokens / base_wall, 1),
        "wo_tokens_per_s": round(total_tokens / wo_wall, 1),
        "baseline_wall_s": round(base_wall, 4),
        "wo_wall_s": round(wo_wall, 4),
        "speedup": round(base_wall / wo_wall, 2),
    }


def bench_speculation(
    *,
    n_requests: int = 8,
    max_new: int = 96,
    spec_draft: int = 4,
    model_kw=None,
) -> dict:
    """Speculative decoding vs one-token-per-dispatch decode: the same
    greedy workload through (a) a paged server at chunk=1 (every token
    pays a dispatch) and (b) the same pool with n-gram drafts verified
    in one batched extend per tick. Reports the measured acceptance
    rate and tokens/dispatch — the quantity that beats 1.0 exactly when
    speculation amortizes the dispatch round-trip. Prompts carry
    repeated n-grams (the prompt-lookup drafter's food); greedy-exact
    acceptance means the streams are identical either way (the parity
    tests pin it), so this row is pure speed."""
    from distributed_tensorflow_tpu.serve import GenerationConfig, TextServer

    model, params = _build(model_kw)
    rng = np.random.default_rng(11)
    prompts = []
    for _ in range(n_requests):
        pat = rng.integers(0, model.vocab_size, (8,)).astype(np.int32)
        prompts.append(np.tile(pat, 6)[: int(rng.integers(32, 49))])
    cfg = GenerationConfig(max_new=max_new)
    # slots=1 keeps batching out of the quotient: baseline
    # tokens/dispatch is exactly 1, so the spec row's excess over 1 is
    # pure speculation depth (speculation composes with batching — the
    # verify pass is one ragged extend across slots — but the record
    # should not conflate the two levers).
    kw = dict(slots=1, buckets=(64,), paged=True, block_size=16)

    base = TextServer(model, params, chunk=1, **kw)
    spec = TextServer(model, params, chunk=1, spec_draft=spec_draft, **kw)
    warm = [np.arange(1, 9, dtype=np.int32)] * 2
    base.generate(warm, GenerationConfig(max_new=4))
    spec.generate(warm, GenerationConfig(max_new=4))
    for c in ("spec_tokens_proposed", "spec_tokens_accepted"):
        spec.metrics.counter(c).value = 0.0  # drop warmup counts

    base_wall, _, base_disp = _serve_wall_tracked(base, prompts, cfg)
    spec_wall, _, spec_disp = _serve_wall_tracked(spec, prompts, cfg)
    proposed = int(spec.metrics.counter("spec_tokens_proposed").value)
    accepted = int(spec.metrics.counter("spec_tokens_accepted").value)
    total_tokens = n_requests * max_new
    return {
        "draft": spec_draft,
        "workload": {"requests": n_requests, "max_new": max_new},
        "proposed": proposed,
        "accepted": accepted,
        "acceptance_rate": round(accepted / max(proposed, 1), 3),
        "decode_dispatches": int(spec_disp),
        "baseline_dispatches": int(base_disp),
        "tokens_per_dispatch": round(total_tokens / max(spec_disp, 1), 2),
        "baseline_tokens_per_dispatch": round(
            total_tokens / max(base_disp, 1), 2
        ),
        "wall_s": round(spec_wall, 4),
        "baseline_wall_s": round(base_wall, 4),
        "speedup": round(base_wall / spec_wall, 2),
    }


def bench_fleet(
    *,
    replicas: int = 3,
    n_requests: int = 30,
    max_new: int = 32,
    slots: int = 2,
    chunk: int = 8,
    queue_limit: int = 64,
    kill_after_done: int = 3,
    model_kw=None,
    timeout_s: float = 900.0,
) -> dict:
    """Load generator over a REAL subprocess fleet (serve_fleet.py) with
    one mid-run SIGKILL: ≥3 replicas serve a greedy workload, the
    busiest replica is killed once a few requests completed (so the kill
    lands mid-decode with requests in flight), and the row records fleet
    throughput, TTFT/latency percentiles from the merged journals
    (``obs_report`` fleet reconstruction — the operator's own path), the
    failover count, and the FAILED-request count, which must be 0: the
    zero-loss contract, measured rather than asserted (the RUN_SLOW
    fault-injection test additionally pins token parity through the
    failover). Replicas run on CPU subprocesses regardless of the bench
    host — the row is a ROUTING/failover property (admission arithmetic
    + mailbox mechanics), not a model-speed claim; wall columns carry
    that provenance."""
    import shutil
    import signal
    import tempfile

    from distributed_tensorflow_tpu import serve_fleet
    from distributed_tensorflow_tpu.observability import aggregate
    from distributed_tensorflow_tpu.tools import obs_report

    mk = dict(
        vocab_size=512, max_len=256, model_dim=128, num_heads=4,
        num_layers=2,
    )
    mk.update(model_kw or {})
    model, params = _build(mk)
    fleet_dir = tempfile.mkdtemp(prefix="dtf-fleet-bench-")
    repo_root = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "..")
    )
    try:
        ckpt = os.path.join(fleet_dir, "ckpt")
        serve_fleet.publish_checkpoint(model, params, ckpt, step=1)
        # One process owns the chip, and this parent has touched JAX:
        # the replicas are CPU children by explicit choice (the row says
        # "device": "cpu") — a routing/failover property, not model speed.
        env = {
            "JAX_PLATFORMS": "cpu",
            "XLA_FLAGS": "--xla_force_host_platform_device_count=1",
            "PYTHONPATH": os.environ.get("PYTHONPATH", "")
            + os.pathsep
            + repo_root,
        }
        router = serve_fleet.local_fleet(
            mk,
            ckpt,
            os.path.join(fleet_dir, "run"),
            replicas=replicas,
            slots=slots,
            chunk=chunk,
            queue_limit=queue_limit,
            buckets=(64,),
            env=env,
            min_replicas=1,
            max_restarts=2,
            backoff=0.5,
            probe_interval_s=0.25,
            poll_interval=0.02,
            print_fn=lambda *a: None,
        )
        rng = np.random.default_rng(17)
        prompts = [
            rng.integers(0, model.vocab_size, (int(s),)).astype(np.int32)
            for s in rng.integers(8, 49, n_requests)
        ]
        try:
            # Readiness gate: replica startup (jax import + restore +
            # first compile) is not serving — submitting before the
            # fleet is up would fold ~15 s of cold start into every TTFT.
            router.wait_until_up(timeout_s=timeout_s)
            for p in prompts:
                router.submit(p, {"max_new": max_new})
            t0 = time.perf_counter()
            killed = None
            deadline = t0 + timeout_s
            while router.step():
                st = router.stats()
                if killed is None and st["done"] >= kill_after_done:
                    victim = max(
                        router.replicas.values(),
                        key=lambda h: len(h.inflight),
                    )
                    if victim.inflight and victim.agent.handle is not None:
                        os.kill(victim.agent.handle.pid, signal.SIGKILL)
                        killed = victim.name
                if time.perf_counter() > deadline:
                    break  # failed requests show up in the count below
                time.sleep(0.02)
            wall = time.perf_counter() - t0
            stats = router.stats()
            failed = n_requests - stats["done"]
        finally:
            # Every exit path (FleetBelowFloor included) must stop the
            # replica subprocesses BEFORE the rmtree below deletes their
            # mailboxes out from under them.
            router.shutdown()
            router.journal.close()
        merged = aggregate.merge(os.path.join(fleet_dir, "run"))
        records = obs_report.reconstruct_fleet_requests(merged)
        pct = obs_report.request_percentiles(
            [
                {
                    "done": True,
                    "ttft_s": r["ttft_s"],
                    "latency_s": r["latency_s"],
                }
                for r in records
                # rid None = replica-local warmup traffic, not fleet load
                if r["done"] and r["rid"] is not None
            ]
        ) or {}
        total_tokens = stats["done"] * max_new
        return {
            "device": "cpu",  # subprocess replicas are pinned to CPU
            "replicas": replicas,
            "slots": slots,
            "chunk": chunk,
            "queue_limit": queue_limit,
            "workload": {
                "requests": n_requests,
                "max_new": max_new,
                "prompt_range": [8, 48],
            },
            "kill": {"victim": killed, "after_done": kill_after_done},
            "wall_s": round(wall, 4),
            "tokens_per_s": round(total_tokens / wall, 1),
            "failed_requests": int(failed),
            "failovers": stats["failovers"],
            "reroutes": stats["reroutes"],
            "ttft_s": pct.get("ttft_s"),
            "latency_s": pct.get("latency_s"),
        }
    finally:
        shutil.rmtree(fleet_dir, ignore_errors=True)


def _disagg_workload(vocab: int, n: int, seed: int):
    """The mixed workload disaggregation exists for: interleaved
    LONG-prefill/short-decode requests (summarization shape) and
    short-prefill/long-decode requests (chat shape). On a homogeneous
    fleet a long prefill admitted at a chunk boundary stalls every
    resident decoder on that replica for a full prefill dispatch;
    role-split replicas absorb prefills away from the decode stream."""
    rng = np.random.default_rng(seed)
    reqs = []
    for i in range(n):
        if i % 2:
            size = int(rng.integers(96, 161))   # long prefill ...
            max_new = 8                         # ... short continuation
        else:
            size = int(rng.integers(8, 25))     # chat: short prefill ...
            max_new = 40                        # ... long decode
        reqs.append(
            (rng.integers(0, vocab, (size,)).astype(np.int32), max_new)
        )
    return reqs


def _run_disagg_fleet(
    mk, reqs, *, roles, fleet_dir, env, slots, chunk, timeout_s,
    migrate_threshold=None, arrival_gap=0.0,
):
    """One side of the disagg A/B: serve ``reqs`` to completion on a
    fresh subprocess fleet (role-split or homogeneous — SAME paged cache
    geometry either way, so the only variable is routing topology) and
    return wall, per-request TTFT/latency percentiles from the merged
    journals, and the migration accounting. ``arrival_gap`` spaces the
    submissions (request i arrives at ``i * gap`` seconds): a streamed
    workload is the scenario disaggregation exists for — a one-burst
    submit admits everything in a single wave and levels the field, a
    stream keeps NEW prefills arriving while decodes are resident,
    which is exactly the interference role-splitting removes."""
    from distributed_tensorflow_tpu import serve_fleet
    from distributed_tensorflow_tpu.observability import aggregate
    from distributed_tensorflow_tpu.observability.journal import read_events
    from distributed_tensorflow_tpu.tools import obs_report

    router = serve_fleet.local_fleet(
        mk,
        os.path.join(os.path.dirname(fleet_dir), "ckpt"),
        fleet_dir,
        replicas=len(roles),
        roles=roles if any(r != "both" for r in roles) else None,
        slots=slots,
        chunk=chunk,
        queue_limit=64,
        buckets=(32, 192),
        paged=True,
        block_size=16,
        kv_blocks=96,
        env=env,
        min_replicas=1,
        max_restarts=2,
        backoff=0.5,
        probe_interval_s=0.25,
        poll_interval=0.02,
        print_fn=lambda *a: None,
        migrate_threshold=migrate_threshold,
    )
    try:
        router.wait_until_up(timeout_s=timeout_s)
        t0 = time.perf_counter()
        rids = []
        pending = list(enumerate(reqs))
        deadline = t0 + timeout_s
        while True:
            now = time.perf_counter() - t0
            while pending and pending[0][0] * arrival_gap <= now:
                _, (p, m) = pending.pop(0)
                rids.append(router.submit(p, {"max_new": m}))
            if not router.step() and not pending:
                break
            if time.perf_counter() > deadline:
                break
            time.sleep(0.005)
        wall = time.perf_counter() - t0
        stats = router.stats()
        tokens = sum(
            len(router.result(rid)) for rid in rids if router.done(rid)
        )
    finally:
        router.shutdown()
        router.journal.close()
    merged = aggregate.merge(fleet_dir)
    records = obs_report.reconstruct_fleet_requests(merged)
    pct = obs_report.request_percentiles(
        [
            {"done": True, "ttft_s": r["ttft_s"], "latency_s": r["latency_s"]}
            for r in records
            if r["done"] and r["rid"] is not None
        ]
    ) or {}
    migr = [
        e for e in read_events(os.path.join(fleet_dir, "events.jsonl"))
        if e.get("kind") == "request_migrated"
    ]
    mig_bytes = [e["nbytes"] for e in migr if e.get("nbytes")]
    return {
        "roles": list(roles),
        "wall_s": round(wall, 4),
        "done": stats["done"],
        "failed_requests": len(reqs) - stats["done"],
        "tokens_per_s": round(tokens / wall, 1),
        "ttft_s": pct.get("ttft_s"),
        "latency_s": pct.get("latency_s"),
        "migrated": len(migr),
        "kv_migration_bytes_per_req": (
            round(sum(mig_bytes) / len(mig_bytes), 1) if mig_bytes else None
        ),
    }


def bench_disagg(
    *,
    n_requests: int = 32,
    slots=None,
    homog_slots: int = 16,
    chunk: int = 4,
    seed: int = 29,
    arrival_gap: float = 0.08,
    migrate_threshold: int | None = 32,
    model_kw=None,
    timeout_s: float = 900.0,
) -> dict:
    """The tentpole's A/B (round 23): the SAME mixed long-prefill/chat
    workload STREAMED (``arrival_gap`` seconds between arrivals) at a
    disaggregated fleet (2 prefill + 2 decode, two-leg migration) and a
    homogeneous fleet (4 both) — equal total replicas, equal paged-cache
    geometry, so the measured difference is the routing topology.
    Disaggregation must win BOTH TTFT p95 (chat decoders never stall
    behind a stranger's long prefill) and tokens/s (decode batches stay
    dense) to justify the migration payload it ships per request
    (``kv_migration_bytes_per_req`` — gate-covered, fails HIGH like
    every wire-bytes series). The config is role-TUNED, which is the
    point of roles: decode replicas pack more resident streams
    (``slots`` default [8, 8, 16, 16] per replica), short prompts skip
    migration entirely (``migrate_threshold``), while the homogeneous
    side gets the same max slot count uniformly. CPU subprocess
    replicas: a routing-topology property, not a model-speed claim; the
    TTFT for migrated requests is measured CONSERVATIVELY (the decode
    leg's first continuation token — the prefill leg's true first token
    lands earlier), so a disagg win here understates the real one."""
    import shutil
    import tempfile

    from distributed_tensorflow_tpu import serve_fleet

    mk = dict(
        vocab_size=512, max_len=256, model_dim=128, num_heads=4,
        num_layers=2,
    )
    mk.update(model_kw or {})
    model, params = _build(mk)
    reqs = _disagg_workload(model.vocab_size, n_requests, seed)
    root = tempfile.mkdtemp(prefix="dtf-disagg-bench-")
    repo_root = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "..")
    )
    # CPU children by explicit choice, as in bench_fleet.
    env = {
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=1",
        "PYTHONPATH": os.environ.get("PYTHONPATH", "")
        + os.pathsep
        + repo_root,
    }
    try:
        serve_fleet.publish_checkpoint(
            model, params, os.path.join(root, "ckpt"), step=1
        )
        disagg = _run_disagg_fleet(
            mk, reqs,
            roles=["prefill", "prefill", "decode", "decode"],
            fleet_dir=os.path.join(root, "disagg"),
            env=env, slots=slots if slots is not None else [8, 8, 16, 16],
            chunk=chunk, timeout_s=timeout_s,
            migrate_threshold=migrate_threshold, arrival_gap=arrival_gap,
        )
        homog = _run_disagg_fleet(
            mk, reqs,
            roles=["both", "both", "both", "both"],
            fleet_dir=os.path.join(root, "homog"),
            env=env, slots=homog_slots, chunk=chunk, timeout_s=timeout_s,
            arrival_gap=arrival_gap,
        )
    finally:
        shutil.rmtree(root, ignore_errors=True)
    d_p95 = (disagg.get("ttft_s") or {}).get("p95")
    h_p95 = (homog.get("ttft_s") or {}).get("p95")
    return {
        "device": "cpu",  # subprocess replicas are pinned to CPU
        "replicas": 4,
        "slots": slots if slots is not None else [8, 8, 16, 16],
        "homog_slots": homog_slots,
        "chunk": chunk,
        "seed": seed,
        "arrival_gap_s": arrival_gap,
        "migrate_threshold": migrate_threshold,
        "workload": {
            "requests": n_requests,
            "mix": "alternating long-prefill/short-decode (96-160 prompt, "
            "8 new) and chat (8-24 prompt, 40 new), streamed at one "
            "arrival per arrival_gap_s",
        },
        "disagg": disagg,
        "homogeneous": homog,
        "ttft_p95_speedup": (
            round(h_p95 / d_p95, 3) if d_p95 and h_p95 else None
        ),
        "tokens_per_s_speedup": round(
            disagg["tokens_per_s"] / homog["tokens_per_s"], 3
        ),
    }


def bench_load_gen(
    *,
    n: int = 48,
    rate: float = 150.0,
    slots: int = 2,
    chunk: int = 8,
    queue_limit: int = 16,
    seed: int = 21,
    model_kw=None,
) -> dict:
    """Overload row (round 21): the ``priority_mix`` load-gen scenario
    replayed at well over 2x capacity (``rate`` rps offered into
    ``slots`` slots behind a ``queue_limit``-deep queue), plus a
    ``steady`` baseline at the same shape. The measured contract —
    acceptance criteria of the round-21 scheduler, not aspirations:

    - every shed lands on the LOWEST class (batch p0), as a loud
      terminal ``RequestShed`` (the ``request_shed`` journal event the
      per-class summary is built from);
    - the deadline-capable classes (interactive p2, standard p1) lose
      NOTHING: ``hi_class_misses`` must be 0;
    - excess p0 arrivals that find no lower class to displace get
      round-16 ``QueueFull`` backpressure (the ``rejected`` column),
      never a silent drop.

    Per-class TTFT here is submit -> admission (the scheduler
    observable; see load_gen.summarize). The shed-rate magnitude is
    timing-dependent (how many arrivals catch a full queue), so the
    gate series carries it with the default tolerance; the ZERO on the
    hi classes is the hard claim and is also test-pinned."""
    from distributed_tensorflow_tpu.observability.journal import (
        EventJournal,
        read_events,
    )
    from distributed_tensorflow_tpu.serve import GenerationConfig, TextServer
    from distributed_tensorflow_tpu.tools import load_gen

    import tempfile

    model, params = _build(model_kw)
    scenarios = {}
    for scenario, q in (("priority_mix", queue_limit), ("steady", None)):
        path = os.path.join(tempfile.mkdtemp(), "events.jsonl")
        journal = EventJournal(path, run_id="load_gen")
        srv = TextServer(
            model, params, slots=slots, chunk=chunk, buckets=(64,),
            queue_limit=q, journal=journal,
        )
        warm = [np.arange(1, 9, dtype=np.int32)] * min(2, slots)
        srv.generate(warm, GenerationConfig(max_new=4))
        reqs = load_gen.generate(
            scenario, seed=seed, n=n, vocab=model.vocab_size, rate=rate
        )
        out = load_gen.drive(srv, reqs, timeout_s=600.0)
        journal.close()
        workload = [e for e in read_events(path) if e.get("rid", -1) >= 2]
        summary = load_gen.summarize(workload)
        hi_miss = sum(
            c["requests"] - c["done"]
            for p, c in summary["classes"].items()
            if p > 0
        )
        lo_sheds = sum(
            c["shed"] for p, c in summary["classes"].items() if p == 0
        )
        all_sheds = sum(c["shed"] for c in summary["classes"].values())
        scenarios[scenario] = {
            "n": n,
            "rate_rps": rate,
            "queue_limit": q,
            "wall_s": round(out["wall_s"], 4),
            "rejected": out["rejected"],
            "hi_class_misses": int(hi_miss),
            "sheds_on_lowest_class_only": bool(lo_sheds == all_sheds),
            **summary,
        }
    return {
        "device": jax.devices()[0].device_kind,
        "slots": slots,
        "chunk": chunk,
        "seed": seed,
        "scenarios": scenarios,
    }


def bench_request_percentiles(
    model,
    params,
    *,
    n_requests: int = 24,
    max_new: int = 96,
    slots: int = 8,
    chunk: int = 32,
) -> dict | None:
    """Per-request TTFT/latency percentiles (round 12): the same batched
    workload served once more with an event journal attached, then the
    trace reconstruction (``obs_report.reconstruct_requests`` — the
    path an operator runs on a production journal) yields p50/p95/p99
    TTFT and end-to-end latency. A separate run, not a re-read of the
    headline rows: those stay journal-free so their methodology is
    unchanged. Warmup requests are dropped by rid."""
    import tempfile

    from distributed_tensorflow_tpu.observability.journal import (
        EventJournal,
        read_events,
    )
    from distributed_tensorflow_tpu.serve import GenerationConfig, TextServer
    from distributed_tensorflow_tpu.tools import obs_report

    path = os.path.join(tempfile.mkdtemp(), "events.jsonl")
    journal = EventJournal(path)
    srv = TextServer(
        model, params, slots=slots, chunk=chunk, buckets=(64,),
        journal=journal,
    )
    warm = [np.arange(1, 9, dtype=np.int32)] * min(2, slots)
    srv.generate(warm, GenerationConfig(max_new=max(2, chunk)))
    prompts, cfg = _workload(model, n_requests, max_new)
    srv.generate(prompts, cfg)
    journal.close()
    records = [
        r
        for r in obs_report.reconstruct_requests(read_events(path))
        if r["rid"] >= len(warm)  # warmup rids precede the workload's
    ]
    pct = obs_report.request_percentiles(records)
    if pct is None:
        return None
    return {"slots": slots, "chunk": chunk, **pct}


def bench(
    *,
    n_requests: int = 24,
    max_new: int = 96,
    slots: int = 8,
    chunk: int = 32,
    chunk_sweep: tuple[int, ...] = (1, 8, 32, 64),
    model_kw=None,
) -> dict:
    model, params = _build(model_kw)
    prompts, cfg = _workload(model, n_requests, max_new)
    total_tokens = n_requests * max_new

    # -- batched vs sequential at the default chunk -----------------------
    srv_b = _make_server(model, params, slots=slots, chunk=chunk)
    srv_s = _make_server(model, params, slots=1, chunk=chunk)
    wall_batched = min(_serve_wall(srv_b, prompts, cfg) for _ in range(2))
    wall_seq = min(_serve_wall(srv_s, prompts, cfg) for _ in range(2))

    # -- per-token cost vs chunk size (one long request, slots=1) ---------
    long_prompt, long_cfg = _workload(model, 1, max_new=192, seed=1)
    sweep = []
    for k in chunk_sweep:
        srv_k = _make_server(model, params, slots=1, chunk=k)
        w = min(
            _serve_wall(srv_k, long_prompt, long_cfg) for _ in range(3)
        )
        sweep.append(
            {
                "chunk": int(k),
                "wall_s": round(w, 4),
                "per_token_ms": round(w * 1e3 / long_cfg.max_new, 3),
            }
        )
    # wall = b + (N/k)·C + N·t — least squares over the sweep for the
    # per-dispatch fixed cost C and marginal per-token cost t. The
    # intercept b absorbs the per-REQUEST constants (the prefill dispatch,
    # host scheduler setup): with N fixed across the sweep, omitting it
    # would fold those into t — the fixed-cost-diluted-into-the-marginal
    # artifact CLAUDE.md's TIMING TRAP 2 warns about.
    n_tok = long_cfg.max_new
    a = np.array([[1.0, n_tok / r["chunk"], n_tok] for r in sweep])
    y = np.array([r["wall_s"] for r in sweep])
    (req_b, fixed_c, marg_t), *_ = np.linalg.lstsq(a, y, rcond=None)

    k1 = next((r for r in sweep if r["chunk"] == 1), sweep[0])
    kbig = min(
        (r for r in sweep if r["chunk"] >= 32),
        key=lambda r: r["per_token_ms"],
        default=sweep[-1],
    )
    density = bench_paged_density(model_kw=model_kw)
    quantized = bench_quantized_density(model_kw=model_kw)
    weight_only = bench_weight_only_decode(model_kw=model_kw)
    speculation = bench_speculation(model_kw=model_kw)
    percentiles = bench_request_percentiles(
        model, params, n_requests=n_requests, max_new=max_new,
        slots=slots, chunk=chunk,
    )
    return {
        "device": jax.devices()[0].device_kind,
        "model": {
            "vocab": model.vocab_size,
            "model_dim": model.model_dim,
            "num_layers": model.num_layers,
            "max_len": model.max_len,
        },
        "workload": {
            "requests": n_requests,
            "max_new": max_new,
            "total_tokens": total_tokens,
        },
        "batched": {
            "slots": slots,
            "chunk": chunk,
            "wall_s": round(wall_batched, 4),
            "tokens_per_s": round(total_tokens / wall_batched, 1),
        },
        "sequential": {
            "slots": 1,
            "chunk": chunk,
            "wall_s": round(wall_seq, 4),
            "tokens_per_s": round(total_tokens / wall_seq, 1),
        },
        "batched_speedup": round(wall_seq / wall_batched, 2),
        "chunk_sweep": sweep,
        "chunk_speedup": round(
            k1["per_token_ms"] / kbig["per_token_ms"], 2
        ),
        "dispatch_fixed_ms": round(float(fixed_c) * 1e3, 3),
        "marginal_token_ms": round(float(marg_t) * 1e3, 3),
        "per_request_ms": round(float(req_b) * 1e3, 3),
        "paged_density": density,
        "quantized_density": quantized,
        "weight_only_decode": weight_only,
        "speculation": speculation,
        **(
            {"request_percentiles": percentiles}
            if percentiles is not None
            else {}
        ),
    }


# -- journal emission (round 10): the measured points as bench_point
# events, so BENCH artifacts, docs tables, and the event journal share
# one source (tools/perf_record.py --journal reads them back). ----------


def emit_bench_events(payload: dict, events_path: str) -> list[dict]:
    from distributed_tensorflow_tpu.observability.journal import EventJournal

    j = EventJournal(events_path, run_id="serve_bench")
    try:
        common = dict(tool="serve_bench", device=payload["device"])
        return [
            j.emit(
                "bench_point", name="batched_tokens_per_s",
                value=payload["batched"]["tokens_per_s"], unit="tokens/s",
                slots=payload["batched"]["slots"],
                chunk=payload["batched"]["chunk"], **common,
            ),
            j.emit(
                "bench_point", name="sequential_tokens_per_s",
                value=payload["sequential"]["tokens_per_s"],
                unit="tokens/s", **common,
            ),
            j.emit(
                "bench_point", name="batched_speedup",
                value=payload["batched_speedup"], unit="x", **common,
            ),
            j.emit(
                "bench_point", name="chunk_speedup",
                value=payload["chunk_speedup"], unit="x", **common,
            ),
            j.emit(
                "bench_point", name="dispatch_fixed_ms",
                value=payload["dispatch_fixed_ms"], unit="ms", **common,
            ),
            j.emit(
                "bench_point", name="marginal_token_ms",
                value=payload["marginal_token_ms"], unit="ms", **common,
            ),
        ] + (
            [
                j.emit(
                    "bench_point", name="paged_slot_density",
                    value=payload["paged_density"]["density_x"], unit="x",
                    kv_hbm_positions=payload["paged_density"][
                        "kv_hbm_positions"
                    ],
                    **common,
                )
            ]
            if "paged_density" in payload
            else []
        ) + (
            [
                j.emit(
                    "bench_point", name="slot_density_q",
                    value=payload["quantized_density"]["density_q_x"],
                    unit="x",  # unit-aware gate: "x" fails LOW
                    kv_dtype=payload["quantized_density"]["quantized"][
                        "kv_dtype"
                    ],
                    kv_hbm_bytes=payload["quantized_density"][
                        "kv_hbm_bytes"
                    ],
                    **common,
                ),
                j.emit(
                    "bench_point", name="quantized_positions_x",
                    value=payload["quantized_density"]["positions_x"],
                    unit="x", **common,
                ),
            ]
            if "quantized_density" in payload
            else []
        ) + (
            [
                j.emit(
                    "bench_point", name="wo_decode_speedup",
                    value=payload["weight_only_decode"]["speedup"],
                    unit="x",
                    dtype=payload["weight_only_decode"]["dtype"],
                    **common,
                )
            ]
            # Gate this series ON-CHIP ONLY: the CPU number is a
            # dequant-and-dot emulation the bench itself documents as
            # meaningless off-chip (≈0.6-1.0× run to run) — a fail-low
            # band over it would flag container noise, not regressions.
            # The md row still carries the CPU A/B as provenance.
            if "weight_only_decode" in payload
            and payload["device"] != "cpu"
            else []
        ) + (
            [
                j.emit(
                    "bench_point", name="spec_tokens_per_dispatch",
                    value=payload["speculation"]["tokens_per_dispatch"],
                    unit="tokens/dispatch",
                    acceptance_rate=payload["speculation"][
                        "acceptance_rate"
                    ],
                    **common,
                )
            ]
            if "speculation" in payload
            else []
        ) + (
            [
                j.emit(
                    "bench_point", name="ttft_p95_s",
                    value=payload["request_percentiles"]["ttft_s"]["p95"],
                    unit="s",
                    requests=payload["request_percentiles"]["requests"],
                    **common,
                ),
                j.emit(
                    "bench_point", name="latency_p95_s",
                    value=payload["request_percentiles"]["latency_s"][
                        "p95"
                    ],
                    unit="s",
                    requests=payload["request_percentiles"]["requests"],
                    **common,
                ),
            ]
            if "request_percentiles" in payload
            else []
        )
    finally:
        j.close()


def emit_fleet_events(payload: dict, events_path: str) -> list[dict]:
    """The fleet row's gate-covered bench_point series (round-12 gate:
    tokens/s fails LOW, the ttft ``s`` unit fails HIGH). The
    failed-request count rides along as a series too; its hard zero is
    pinned by the RUN_SLOW fault-injection test — the gate's band just
    keeps the trajectory on record."""
    from distributed_tensorflow_tpu.observability.journal import EventJournal

    fl = payload["fleet"]
    j = EventJournal(events_path, run_id="serve_bench")
    try:
        common = dict(
            tool="serve_bench", device=fl.get("device", "cpu"),
            replicas=fl["replicas"],
        )
        out = [
            j.emit(
                "bench_point", name="fleet_tokens_per_s",
                value=fl["tokens_per_s"], unit="tokens/s", **common,
            ),
            j.emit(
                "bench_point", name="fleet_failed_requests",
                value=fl["failed_requests"], unit="requests", **common,
            ),
        ]
        if fl.get("ttft_s"):
            out.append(
                j.emit(
                    "bench_point", name="fleet_ttft_p95_s",
                    value=fl["ttft_s"]["p95"], unit="s", **common,
                )
            )
        return out
    finally:
        j.close()


def emit_disagg_events(payload: dict, events_path: str) -> list[dict]:
    """The disagg A/B's gate-covered series (round 23):
    ``disagg_ttft_p95_s`` (unit ``s``, fails HIGH — the chat tail
    regrowing under the same mixed load means prefill isolation broke),
    ``disagg_tokens_per_s`` (fails LOW), and
    ``kv_migration_bytes_per_req`` (unit ``bytes/req``, fails HIGH —
    the handoff payload creeping up is a wire regression, round-17
    bytes/token precedent)."""
    from distributed_tensorflow_tpu.observability.journal import EventJournal

    dg = payload["disagg"]
    d = dg["disagg"]
    j = EventJournal(events_path, run_id="serve_bench")
    try:
        common = dict(
            tool="serve_bench", device=dg.get("device", "cpu"),
            replicas=dg["replicas"], seed=dg["seed"],
        )
        out = [
            j.emit(
                "bench_point", name="disagg_tokens_per_s",
                value=d["tokens_per_s"], unit="tokens/s", **common,
            ),
        ]
        if d.get("ttft_s"):
            out.append(
                j.emit(
                    "bench_point", name="disagg_ttft_p95_s",
                    value=d["ttft_s"]["p95"], unit="s", **common,
                )
            )
        if d.get("kv_migration_bytes_per_req") is not None:
            out.append(
                j.emit(
                    "bench_point", name="kv_migration_bytes_per_req",
                    value=d["kv_migration_bytes_per_req"],
                    unit="bytes/req", **common,
                )
            )
        return out
    finally:
        j.close()


def emit_load_gen_events(payload: dict, events_path: str) -> list[dict]:
    """The overload row's gate-covered per-class series (round 21):
    ``fleet_ttft_p95_p{k}_s`` (unit ``s``, fails HIGH — a scheduler
    regression shows up as interactive-tail inflation under the same
    load) and ``shed_rate_p{k}`` (unit ``shed_rate``, fails HIGH — more
    shedding at the same offered load is a capacity or scheduling
    regression; the regression_gate unit table lists it
    lower-is-better). Only the overload (priority_mix) scenario feeds
    the gate; the steady baseline is provenance in the md."""
    from distributed_tensorflow_tpu.observability.journal import EventJournal

    lg = payload["load_gen"]
    sc = lg["scenarios"]["priority_mix"]
    j = EventJournal(events_path, run_id="serve_bench")
    try:
        common = dict(
            tool="serve_bench", device=lg["device"],
            scenario="priority_mix", seed=lg["seed"],
        )
        out = []
        for prio, c in sorted(sc["classes"].items()):
            p95 = (c.get("ttft_s") or {}).get("p95")
            if p95 is not None:
                out.append(
                    j.emit(
                        "bench_point", name=f"fleet_ttft_p95_p{prio}_s",
                        value=p95, unit="s", priority=int(prio), **common,
                    )
                )
            out.append(
                j.emit(
                    "bench_point", name=f"shed_rate_p{prio}",
                    value=c["shed_rate"], unit="shed_rate",
                    priority=int(prio), **common,
                )
            )
        return out
    finally:
        j.close()


# -- rendering (offline: the staleness guard re-renders committed JSON) ----


def render(payload: dict) -> str:
    b, s = payload["batched"], payload["sequential"]
    lines = [
        "| mode | slots | chunk | wall (s) | tokens/s |",
        "|---|---|---|---|---|",
        f"| batched | {b['slots']} | {b['chunk']} | {b['wall_s']} "
        f"| {b['tokens_per_s']} |",
        f"| sequential | {s['slots']} | {s['chunk']} | {s['wall_s']} "
        f"| {s['tokens_per_s']} |",
        "",
        f"**Batched speedup: {payload['batched_speedup']}x** "
        f"({payload['workload']['requests']} requests x "
        f"{payload['workload']['max_new']} tokens).",
        "",
        "| chunk k | per-token (ms) |",
        "|---|---|",
    ]
    for r in payload["chunk_sweep"]:
        lines.append(f"| {r['chunk']} | {r['per_token_ms']} |")
    lines += [
        "",
        f"**Chunking speedup: {payload['chunk_speedup']}x** per-token vs "
        "one-dispatch-per-token; fit wall = b + (N/k)·C + N·t gives "
        f"C = {payload['dispatch_fixed_ms']} ms/dispatch, "
        f"t = {payload['marginal_token_ms']} ms/token, "
        f"b = {payload.get('per_request_ms', 0.0)} ms/request "
        "(prefill + scheduler constants, kept out of t).",
    ]
    d = payload.get("paged_density")
    if d:
        sl, pg = d["slab"], d["paged"]
        lines += [
            "",
            "## Paged vs slab cache: slot density at equal KV HBM "
            f"({d['kv_hbm_positions']} cached positions, "
            f"block size {d['block_size']})",
            "",
            "| cache | slots | peak concurrent | wall (s) | tokens/s |",
            "|---|---|---|---|---|",
            f"| slab | {sl['slots']} | {sl['peak_occupancy']} "
            f"| {sl['wall_s']} | {sl['tokens_per_s']} |",
            f"| paged | {pg['slots']} ({pg['kv_blocks']} blocks) "
            f"| {pg['peak_occupancy']} | {pg['wall_s']} "
            f"| {pg['tokens_per_s']} |",
            "",
            f"**Slot density: {d['density_x']}x** concurrent residents "
            f"in identical KV memory (throughput {d['throughput_x']}x) "
            f"on a short-request mix (prompts "
            f"{d['workload']['prompt_range'][0]}-"
            f"{d['workload']['prompt_range'][1]} + "
            f"{d['workload']['max_new']} new of max_len "
            f"{payload['model']['max_len']}): the slab reserves "
            "worst-case slabs, the paged pool reserves actual "
            "footprints.",
        ]
    q = payload.get("quantized_density")
    if q:
        bq, qq = q["bf16"], q["quantized"]
        dev = f" ({q['device']})" if q.get("device") else ""
        lines += [
            "",
            "## Quantized KV cache: slot density at equal KV HBM bytes "
            f"({q['kv_hbm_bytes']} B budget, block size {q['block_size']})",
            "",
            "| pool | blocks | positions | bytes/block | peak concurrent "
            "| wall (s) | tokens/s |",
            "|---|---|---|---|---|---|---|",
            f"| bf16 | {bq['kv_blocks']} | {bq['positions']} "
            f"| {bq['block_bytes']} | {bq['peak_occupancy']} "
            f"| {bq['wall_s']}{dev} | {bq['tokens_per_s']} |",
            f"| {qq['kv_dtype']} | {qq['kv_blocks']} | {qq['positions']} "
            f"| {qq['block_bytes']} | {qq['peak_occupancy']} "
            f"| {qq['wall_s']}{dev} | {qq['tokens_per_s']} |",
            "",
            f"**Quantized slot density: {q['density_q_x']}x** peak "
            f"concurrent residents in the SAME byte budget "
            f"({q['positions_x']}x the cached positions — int8 payload "
            "plus the f32 per-row scales, charged honestly; the extra "
            "density over the positions ratio is the byte-smaller "
            "blocks packing the bf16 pool's remainder) on a "
            "long-generation mix (prompts "
            f"{q['workload']['prompt_range'][0]}-"
            f"{q['workload']['prompt_range'][1]} + "
            f"{q['workload']['max_new']} new). Occupancy is "
            "admission-control arithmetic — the density column carries "
            "over to the chip as-is; the wall columns are device-tagged "
            "provenance.",
        ]
    wo = payload.get("weight_only_decode")
    if wo:
        dev = f" ({wo['device']})" if wo.get("device") else ""
        lines += [
            "",
            "## Weight-only quantized decode (`decode_matmul_dtype`)",
            "",
            "| weights | tokens/s | wall (s) |",
            "|---|---|---|",
            f"| full precision | {wo['baseline_tokens_per_s']} "
            f"| {wo['baseline_wall_s']}{dev} |",
            f"| {wo['dtype']} (wo_dot) | {wo['wo_tokens_per_s']} "
            f"| {wo['wo_wall_s']}{dev} |",
            "",
            f"**Decode A/B: {wo['speedup']}x wall** on this device. The "
            "weight-only win is HBM traffic (decode reads every "
            "projection weight per token), so the CPU dequant-and-dot "
            "emulation understates — or inverts — the chip number, "
            "which is not measured, like the round-13 int8 training "
            "row.",
        ]
    sp = payload.get("speculation")
    if sp:
        lines += [
            "",
            "## Speculative decoding (n-gram drafts, greedy-exact "
            "verify)",
            "",
            "| mode | decode dispatches | tokens/dispatch | wall (s) |",
            "|---|---|---|---|",
            f"| chunk=1 baseline | {sp['baseline_dispatches']} "
            f"| {sp['baseline_tokens_per_dispatch']} "
            f"| {sp['baseline_wall_s']} |",
            f"| spec draft={sp['draft']} | {sp['decode_dispatches']} "
            f"| {sp['tokens_per_dispatch']} | {sp['wall_s']} |",
            "",
            f"**Tokens/dispatch: {sp['tokens_per_dispatch']}** at a "
            f"measured acceptance rate of {sp['acceptance_rate']} "
            f"({sp['accepted']}/{sp['proposed']} drafted tokens "
            f"accepted), {sp['speedup']}x wall vs one-token-per-"
            "dispatch on the same pool (slots=1 so batching stays out "
            "of the quotient). Greedy-exact acceptance: the served "
            "stream is the pure greedy stream either way — a rejected "
            "draft costs wasted compute, never a changed token.",
        ]
    fl = payload.get("fleet")
    if fl:
        k = fl.get("kill") or {}
        ttft = fl.get("ttft_s") or {}
        lat = fl.get("latency_s") or {}
        lines += [
            "",
            "## Serving fleet: failover under SIGKILL "
            "(serve_fleet.py router)",
            "",
            "| replicas | slots x chunk | requests | killed | failed "
            "| failovers | wall (s) | tokens/s |",
            "|---|---|---|---|---|---|---|---|",
            f"| {fl['replicas']} | {fl['slots']} x {fl['chunk']} "
            f"| {fl['workload']['requests']} | {k.get('victim')} "
            f"(after {k.get('after_done')} done) "
            f"| **{fl['failed_requests']}** | {fl['failovers']} "
            f"| {fl['wall_s']} | {fl['tokens_per_s']} |",
            "",
            f"Fleet TTFT p50/p95 = {ttft.get('p50')}/{ttft.get('p95')} s, "
            f"latency p50/p95 = {lat.get('p50')}/{lat.get('p95')} s, from "
            "the merged router+replica journals (`obs_report --fleet` — "
            "router submit to serving-replica completion, queue wait and "
            "failover latency included). The busiest replica is SIGKILLed "
            "mid-decode; its in-flight requests re-admit to healthy "
            f"replicas ({fl['reroutes']} re-routes) and the dead one "
            "relaunches under the restart budget. **failed = "
            f"{fl['failed_requests']}** is the zero-loss contract measured "
            "(the RUN_SLOW fault-injection test additionally pins every "
            "stream — re-served ones included — token-identical to "
            "in-process decode). Replicas are CPU subprocesses regardless "
            "of the bench host: this row is a routing/failover property, "
            "not a model-speed claim.",
        ]
    dg = payload.get("disagg")
    if dg:
        d, h = dg["disagg"], dg["homogeneous"]
        dt = d.get("ttft_s") or {}
        ht = h.get("ttft_s") or {}
        lines += [
            "",
            "## Disaggregated prefill/decode fleet: equal-replica A/B "
            "(serve_fleet.py roles, round 23)",
            "",
            f"{dg['workload']['requests']} requests, mixed workload — "
            f"{dg['workload']['mix']} — over {dg['replicas']} replicas "
            f"(role-tuned slots={dg['slots']} vs homogeneous "
            f"{dg.get('homog_slots')}, chunk={dg['chunk']}, "
            f"arrival gap {dg.get('arrival_gap_s')} s, migrate_threshold="
            f"{dg.get('migrate_threshold')}, seed={dg['seed']}), same "
            "paged-KV geometry both sides.",
            "",
            "| fleet | roles | done | failed | migrated | TTFT p50/p95 "
            "(s) | latency p95 (s) | tokens/s | KV wire B/req |",
            "|---|---|---|---|---|---|---|---|---|",
            f"| disagg | 2 prefill + 2 decode | {d['done']} "
            f"| {d['failed_requests']} | {d['migrated']} "
            f"| {dt.get('p50')}/{dt.get('p95')} "
            f"| {(d.get('latency_s') or {}).get('p95')} "
            f"| {d['tokens_per_s']} "
            f"| {d.get('kv_migration_bytes_per_req')} |",
            f"| homogeneous | 4 both | {h['done']} "
            f"| {h['failed_requests']} | {h['migrated']} "
            f"| {ht.get('p50')}/{ht.get('p95')} "
            f"| {(h.get('latency_s') or {}).get('p95')} "
            f"| {h['tokens_per_s']} | - |",
            "",
            f"**TTFT p95 speedup {dg['ttft_p95_speedup']}x, tokens/s "
            f"speedup {dg['tokens_per_s_speedup']}x** for the role-split "
            "fleet at EQUAL total replicas: chat decoders never stall "
            "behind a stranger's long prefill, and decode batches stay "
            "dense. The workload is STREAMED — continuous arrivals are "
            "the scenario role-splitting exists for (a single burst "
            "admits in one wave and levels the field); the config is "
            "role-tuned (denser decode slots, short prompts skip "
            "migration via `migrate_threshold`), which roles make safe "
            "to do. Migrated-request TTFT is measured conservatively "
            "(decode-leg first continuation token — the prefill leg's "
            "true first token lands earlier), so the disagg win is "
            "understated. Replicas are CPU subprocesses: a "
            "routing-topology property, not a model-speed claim; "
            "one-chip replicas need per-replica device choice, which "
            "is not built.",
        ]
    lg = payload.get("load_gen")
    if lg:
        dev = lg.get("device", "?")
        lines += [
            "",
            "## Overload robustness (load_gen scenarios, round 21)",
            "",
            f"slots={lg['slots']}, chunk={lg['chunk']}, seed={lg['seed']}"
            f", measured on {dev}. TTFT = submit → admission (the "
            "scheduler observable).",
        ]
        for scenario, sc in sorted(lg["scenarios"].items()):
            lines += [
                "",
                f"### `{scenario}` — {sc['n']} requests at "
                f"{sc['rate_rps']} rps offered"
                + (
                    f", queue_limit={sc['queue_limit']}"
                    if sc.get("queue_limit")
                    else ""
                ),
                "",
                "| class | requests | done | shed | shed rate "
                "| TTFT p50/p95 (s) | latency p50/p95 (s) |",
                "|---|---|---|---|---|---|---|",
            ]
            for prio, c in sorted(
                sc["classes"].items(), key=lambda kv: int(kv[0])
            ):
                t, l = c.get("ttft_s") or {}, c.get("latency_s") or {}
                lines.append(
                    f"| p{prio} | {c['requests']} | {c['done']} "
                    f"| {c['shed']} | {c['shed_rate']} "
                    f"| {t.get('p50')}/{t.get('p95')} "
                    f"| {l.get('p50')}/{l.get('p95')} |"
                )
            lines += [
                "",
                f"wall {sc['wall_s']} s; {sc['rejected']} QueueFull "
                "rejections (round-16 backpressure on same-or-lower-"
                "class arrivals); **hi-class misses: "
                f"{sc['hi_class_misses']}** (must be 0); sheds on "
                "lowest class only: "
                f"**{sc['sheds_on_lowest_class_only']}**.",
            ]
        lines += [
            "",
            "Under ≥2x-capacity overload the deadline/priority scheduler "
            "(serve.py round 21) sheds ONLY the batch class — loudly, as "
            "terminal `RequestShed` with a `request_shed` journal event — "
            "while every deadline-capable interactive/standard request "
            "completes. The per-class `fleet_ttft_p95_p{k}_s` and "
            "`shed_rate_p{k}` series feed the regression gate (both fail "
            "HIGH).",
        ]
    pc = payload.get("request_percentiles")
    if pc:
        lines += [
            "",
            "## Per-request latency percentiles (SLO view, "
            f"slots={pc['slots']}, chunk={pc['chunk']})",
            "",
            "| percentile | TTFT (s) | latency (s) |",
            "|---|---|---|",
        ]
        for p in ("p50", "p95", "p99"):
            lines.append(
                f"| {p} | {pc['ttft_s'][p]} | {pc['latency_s'][p]} |"
            )
        lines += [
            "",
            f"Measured over {pc['requests']} requests via the journal's "
            "trace reconstruction (`obs_report --requests` on the run's "
            "events.jsonl — the same path an operator uses on a "
            "production journal), on a separate journal-attached run so "
            "the headline rows above keep their journal-free "
            "methodology. TTFT includes queue wait: at "
            f"slots={pc['slots']} a workload of "
            f"{payload['workload']['requests']} requests queues, so the "
            "tail percentiles are an admission-control observable, not "
            "a pure model-speed one.",
        ]
    return "\n".join(lines)


def _docs_root() -> str:
    return os.path.abspath(
        os.path.join(
            os.path.dirname(__file__), "..", "..", "docs", "benchmarks"
        )
    )


def write_docs(payload: dict, root: str | None = None) -> None:
    root = root or _docs_root()
    with open(os.path.join(root, "serving.json"), "w") as f:
        json.dump(payload, f, indent=1)
    with open(os.path.join(root, "serving.md"), "w") as f:
        f.write(
            "# LM serving engine (serve.py): measured record\n\n"
            "Generated by `python -m distributed_tensorflow_tpu.tools."
            f"serve_bench --write-docs` on **{payload['device']}** "
            "(rerun on the v5e chip to refresh the on-chip record; "
            "tests/test_serve.py fails if this file drifts from "
            "serving.json). Timing is wall-clock around "
            "served workloads; every chunk ends in a D2H token fetch, so "
            "the numbers are dispatch-inclusive and barrier-honest "
            "(utils/sync.py). Model: "
            f"d={payload['model']['model_dim']}, "
            f"{payload['model']['num_layers']} layers, vocab "
            f"{payload['model']['vocab']}.\n\n"
            + render(payload)
            + "\n\nReading it: chunking amortizes the per-dispatch fixed "
            "cost C (on CPU the ~2 ms dispatch+fetch overhead; on the "
            "chip not measured) over k tokens: "
            "per-token cost approaches the marginal t as k grows, with "
            "diminishing returns once C/(k·t) « 1. The scheduler admits "
            "at chunk boundaries, so k also bounds admission latency — "
            "pick the smallest k whose per-token cost sits on the flat "
            "part of the sweep. Batching rides the decode's "
            "parameter-read-bound step: on an accelerator 8 slots cost "
            "barely more HBM traffic per step than 1 (params dominate at "
            "serving widths), so 8 streams multiply tokens/s; a CPU run "
            "of this bench pays batch compute linearly and shows ~1x "
            "there — the slots lever is an accelerator phenomenon, the "
            "chunk lever shows everywhere.\n\n"
            "Provenance: every row "
            f"in this file was measured on **{payload['device']}**"
            + (
                " — i.e. NOT on the chip. The slot-density "
                "row is a geometry + admission-control property and "
                "carries over as-is; the batched-speedup, chunk and "
                "speculation wall-clock rows are CPU runs and say "
                "nothing about the chip (tokens/dispatch and the "
                "acceptance rate carry over; wall speedups do not): "
                "those are not measured. Rerun `python -m "
                "distributed_tensorflow_tpu.tools.serve_bench "
                "--write-docs` on the v5e to measure them."
                if payload["device"] == "cpu"
                else " (the chip of record)."
            )
            + "\n"
        )


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--requests", type=int, default=24)
    ap.add_argument("--max-new", type=int, default=96)
    ap.add_argument("--slots", type=int, default=8)
    ap.add_argument("--chunk", type=int, default=32)
    ap.add_argument("--write-docs", action="store_true")
    ap.add_argument(
        "--events",
        default=None,
        help="append the measured points as bench_point journal events "
        "(default with --write-docs: docs/benchmarks/events.jsonl)",
    )
    ap.add_argument(
        "--fleet",
        action="store_true",
        help="run ONLY the fleet failover bench (subprocess replicas + "
        "one SIGKILL) and merge its row into the committed serving.json "
        "— the other rows are untouched, so a fleet refresh needs no "
        "chip and no full rerun",
    )
    ap.add_argument(
        "--load-gen",
        action="store_true",
        help="run ONLY the overload load-generator scenarios "
        "(tools/load_gen.py against an in-process TextServer) and merge "
        "the section into the committed serving.json (the --fleet merge "
        "pattern) — per-class TTFT/shed-rate series feed the gate",
    )
    ap.add_argument(
        "--disagg",
        action="store_true",
        help="run ONLY the disaggregated prefill/decode A/B (role-split "
        "vs homogeneous subprocess fleets at equal total replicas on the "
        "same mixed workload) and merge its section into the committed "
        "serving.json (the --fleet merge pattern) — TTFT/tokens-per-s/"
        "migration-bytes series feed the gate",
    )
    args = ap.parse_args(argv)
    events_path = args.events
    if events_path is None and args.write_docs:
        events_path = os.path.join(_docs_root(), "events.jsonl")
    if args.load_gen:
        lg = bench_load_gen()
        with open(os.path.join(_docs_root(), "serving.json")) as f:
            payload = json.load(f)
        payload["load_gen"] = lg
        print(json.dumps(lg))
        if args.write_docs:
            write_docs(payload)
            print(f"wrote {_docs_root()}/serving.md and serving.json")
        else:
            print(render(payload))
        if events_path:
            n = len(emit_load_gen_events(payload, events_path))
            print(f"appended {n} bench_point events to {events_path}")
        return 0
    if args.disagg:
        dg = bench_disagg()
        with open(os.path.join(_docs_root(), "serving.json")) as f:
            payload = json.load(f)
        payload["disagg"] = dg
        print(json.dumps(dg))
        if args.write_docs:
            write_docs(payload)
            print(f"wrote {_docs_root()}/serving.md and serving.json")
        else:
            print(render(payload))
        if events_path:
            n = len(emit_disagg_events(payload, events_path))
            print(f"appended {n} bench_point events to {events_path}")
        return 0
    if args.fleet:
        fleet = bench_fleet()
        with open(os.path.join(_docs_root(), "serving.json")) as f:
            payload = json.load(f)
        payload["fleet"] = fleet
        print(json.dumps(fleet))
        if args.write_docs:
            write_docs(payload)
            print(f"wrote {_docs_root()}/serving.md and serving.json")
        else:
            print(render(payload))
        if events_path:
            n = len(emit_fleet_events(payload, events_path))
            print(f"appended {n} bench_point events to {events_path}")
        return 0
    payload = bench(
        n_requests=args.requests,
        max_new=args.max_new,
        slots=args.slots,
        chunk=args.chunk,
    )
    # A full rerun re-measures every engine row but not the sections
    # with an entry point of their own (--fleet, --load-gen, --disagg):
    # carry the committed sections forward instead of silently dropping
    # them.
    try:
        with open(os.path.join(_docs_root(), "serving.json")) as f:
            old = json.load(f)
        for key in ("fleet", "load_gen", "disagg"):
            if key in old:
                payload.setdefault(key, old[key])
    except (OSError, ValueError):
        pass
    print(json.dumps(payload))
    if args.write_docs:
        write_docs(payload)
        print(f"wrote {_docs_root()}/serving.md and serving.json")
    else:
        print(render(payload))
    if events_path:
        n = len(emit_bench_events(payload, events_path))
        print(f"appended {n} bench_point events to {events_path}")
    return 0


if __name__ == "__main__":
    import sys

    sys.exit(main())
