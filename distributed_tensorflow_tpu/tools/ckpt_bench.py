"""Save-boundary stall: synchronous vs async checkpoint pipeline.

The round-22 tentpole claim (docs/resilience.md §async-checkpoint) in
measured form: with ``async_checkpoint=True`` the training loop's pause
at a save boundary is the device→host snapshot cost, not the full
serialize+CRC+manifest+GC write — the writer thread pays that off the
hot path. This bench times exactly the boundary pause (the ``save()``
call itself) for the SAME state pytree under both modes and emits the
``ckpt_stall_ms_{sync,async}`` bench_point series (unit ``ms`` — the
regression gate fails HIGH, so an async path that quietly starts
blocking on the writer again fails the fast tier).

Methodology notes, in the repo's bench discipline:

- Each timed save is drained (``wait_pending``) BEFORE the next timing
  window opens, so every async point measures the snapshot handoff and
  never a queue-supersede fast path (which would flatter the number).
- The state is plain host-backed jax arrays on CPU — the honest
  BASELINE. On a real TPU the async path pays the device→host snapshot
  while the sync write pays it AND storage, so the win should grow
  with state size and storage latency (not measured on the chip —
  ROADMAP R7); CPU rows carry ``device: cpu``
  per the round-13 provenance convention.
- Median over ``--reps`` (default 5) after one warm save per mode (the
  warm save absorbs orbax's first-write setup and the directory
  creation).

Usage::

    python -m distributed_tensorflow_tpu.tools.ckpt_bench --events \
        docs/benchmarks/events.jsonl
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import statistics
import sys
import tempfile
import time


def _make_state(nparams: int):
    import jax
    import jax.numpy as jnp

    # A dict-of-arrays pytree shaped like a small trainer state: a few
    # large leaves (params-like) and a couple of scalars (step/opt
    # hyper-state) so the manifest walks a realistic file mix.
    keys = jax.random.split(jax.random.key(0), 4)
    quarter = nparams // 4
    return {
        f"w{i}": jax.random.normal(k, (quarter,), dtype=jnp.float32)
        for i, k in enumerate(keys)
    } | {
        "global_step": jnp.asarray(0, dtype=jnp.int32),
        "scale": jnp.asarray(1.0, dtype=jnp.float32),
    }


def _time_mode(state, *, async_checkpoint: bool, reps: int) -> dict:
    from distributed_tensorflow_tpu.train.supervisor import Supervisor

    tmp = tempfile.mkdtemp(prefix="ckpt_bench_")
    try:
        sup = Supervisor(
            checkpoint_dir=tmp, async_checkpoint=async_checkpoint
        )
        sup.save(state, 0)  # warm: orbax setup + dir creation
        sup.wait_pending()
        stalls_ms = []
        for r in range(reps):
            t0 = time.perf_counter()
            sup.save(state, r + 1)
            stalls_ms.append((time.perf_counter() - t0) * 1e3)
            # Drain OUTSIDE the timing window: each point measures a
            # boundary pause with an idle writer, never the supersede
            # fast path.
            sup.wait_pending()
        return {
            "mode": "async" if async_checkpoint else "sync",
            "stall_ms": round(statistics.median(stalls_ms), 3),
            "stalls_ms": [round(s, 3) for s in stalls_ms],
            "reps": reps,
        }
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def run(nparams: int = 2_000_000, reps: int = 5) -> list[dict]:
    import jax

    state = jax.tree.map(
        lambda x: jax.device_put(x).block_until_ready(),
        _make_state(nparams),
    )
    return [
        _time_mode(state, async_checkpoint=False, reps=reps),
        _time_mode(state, async_checkpoint=True, reps=reps),
    ]


def emit_bench_events(results: list[dict], events_path: str) -> int:
    from distributed_tensorflow_tpu.observability.journal import (
        EventJournal,
    )

    j = EventJournal(events_path)
    n = 0
    for r in results:
        j.emit(
            "bench_point",
            run="ckpt_bench",
            name=f"ckpt_stall_ms_{r['mode']}",
            value=float(r["stall_ms"]),
            unit="ms",
            tool="ckpt_bench",
            device="cpu",
            reps=r["reps"],
        )
        n += 1
    return n


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--nparams", type=int, default=2_000_000)
    p.add_argument("--reps", type=int, default=5)
    p.add_argument("--json", default=None, help="also write results here")
    p.add_argument(
        "--events",
        default=None,
        help="append ckpt_stall_ms_{sync,async} bench_point events to "
        "this events.jsonl (the gate-covered series)",
    )
    args = p.parse_args(argv)
    results = run(nparams=args.nparams, reps=args.reps)
    sync = next(r for r in results if r["mode"] == "sync")
    a = next(r for r in results if r["mode"] == "async")
    ratio = sync["stall_ms"] / max(a["stall_ms"], 1e-9)
    # The acceptance claim: async's boundary pause is MEASURABLY below
    # sync's — we assert a conservative 2x so jitter on a loaded
    # container never flakes the check (measured ~10-40x on CPU).
    check = "PASS" if ratio >= 2.0 else "FAIL"
    for r in results:
        print(json.dumps(r))
    print(
        f"{check}: async save-boundary stall {a['stall_ms']} ms vs sync "
        f"{sync['stall_ms']} ms ({ratio:.1f}x)",
        file=sys.stderr,
    )
    if args.json:
        with open(args.json, "w") as f:
            json.dump(results, f, indent=1)
    if args.events:
        n = emit_bench_events(results, args.events)
        print(
            f"appended {n} bench_point events to {args.events}",
            file=sys.stderr,
        )
    return 0 if check == "PASS" else 1


if __name__ == "__main__":
    sys.exit(main())
