"""Seed-swept chaos driver over the round-19 failpoint registry.

Every fault proof before this round was a bespoke integration script
(one SIGKILL, one throttle — tests/integration/test_fault_injection.py).
This tool is the robustness analogue of the regression gate: the fault
scenarios the docs claim to survive become a swept, repeatable matrix::

    python -m distributed_tensorflow_tpu.tools.chaos_sweep                # all
    python -m distributed_tensorflow_tpu.tools.chaos_sweep --seeds 0,1,2
    python -m distributed_tensorflow_tpu.tools.chaos_sweep \
        --schedules delta-torn,fleet-torn-result --json /tmp/chaos.json

Each SCHEDULE arms a deterministic failpoint spec (train/failpoints.py)
against one durability seam and asserts the invariants the docs already
claim — no data loss, recovery to the documented state, structured
``mailbox_corrupt``/``failpoint`` events, and the counters that make the
recovery observable. Each runs once per SEED; the seed deterministically
moves WHERE in the operation sequence the fault lands (``@N`` in the
spec), so a sweep covers a band of fault positions, not one anecdote.

Schedules (3 seams × 2 each):

- ``ckpt-torn-manifest`` — checkpoint corruption cascade: the newest one
  or two (seed parity) manifests torn at commit; restore must fall back
  to the newest VERIFYING step with the exact saved values.
- ``ckpt-kill-mid-save``  — a subprocess trainer SIGKILLed between its
  manifest tmp write and the atomic replace (``atomic.write.commit:
  kill@N``); the orbax payload is complete, so restore recovers the
  full step (unverified-trusted, the pre-manifest contract) and the
  only litter is a ``.tmp`` orphan the mailbox/manifest sweeps GC.
- ``delta-torn``          — a gang member's committed delta post torn;
  the peer's stale-weighted round proceeds WITHOUT it (skipped, never
  consumed, watermark advanced, ``mailbox_corrupt`` journaled) and the
  weighted mean over the surviving rounds is exact.
- ``delta-transient``     — ``delta.load:raise`` (FailpointError is an
  OSError): the unreadable post is retried next boundary with the
  watermark UNMOVED — the round's movement is consumed exactly once,
  late, never lost.
- ``fleet-torn-result``   — a replica's committed result file torn
  mid-failover; the router's poll quarantines it (never delivered,
  never re-read), the replica re-serves (the router re-admits anything
  without a result), and every trace id is delivered exactly once.
- ``fleet-garbage-json``  — raw garbage dropped into an outbox (storage
  corruption): quarantined once, valid results unaffected, second poll
  clean (the pre-round-19 infinite re-read is fixed).

Exit code 0 iff every (schedule, seed) cell passes; the one-line JSON
summary (bench.py idiom) carries the per-cell detail. The RUN_SLOW tier
runs one representative schedule per seam
(tests/integration/test_chaos_sweep.py).

Determinism: failpoints count hits, never clock or RNG; retry jitter in
any exercised path uses ``random.Random(seed)`` via the ``rng=`` knobs
(resilience.backoff_delay/retry/retry_io — the round-19 satellite), and
the sweep self-checks that the jittered delay sequence is reproducible.
"""

from __future__ import annotations

import argparse
import json
import os
import random
import subprocess
import sys
import tempfile
import time

import numpy as np

from distributed_tensorflow_tpu.train import failpoints, resilience

_REPO = os.path.abspath(
    os.path.join(os.path.dirname(__file__), "..", "..")
)


class _Recorder:
    """Minimal journal: record events, write nothing (jax-free)."""

    path = None

    def __init__(self):
        self.events: list[dict] = []

    def emit(self, kind, **fields):
        ev = {"kind": kind, **fields}
        self.events.append(ev)
        return ev

    def kinds(self):
        return [e["kind"] for e in self.events]

    def flush(self):
        pass

    def close(self):
        pass


SCENARIOS: dict = {}


def scenario(name):
    def deco(fn):
        SCENARIOS[name] = fn
        return fn

    return deco


# ---------------------------------------------------------------------------
# Checkpoint seam.
# ---------------------------------------------------------------------------


def _mk_state(v):
    import jax.numpy as jnp

    from distributed_tensorflow_tpu.parallel.strategy import TrainState

    return TrainState(
        {"w": jnp.full((4, 3), float(v)), "b": jnp.zeros((3,))},
        {"mu": jnp.ones((4, 3))},
        jnp.asarray(int(v), jnp.int32),
    )


@scenario("ckpt-torn-manifest")
def _ckpt_torn_manifest(seed, workdir):
    """Corruption cascade: tear the newest 1 (even seed) or 2 (odd seed)
    manifests; restore falls back to the newest verifying step with the
    exact saved values — a corrupt latest costs progress back to the
    last good save, never the run and never silent wrong data."""
    import warnings

    from distributed_tensorflow_tpu.train.supervisor import (
        Supervisor,
        latest_checkpoint_step,
    )

    d = os.path.join(workdir, "ck")
    sup = Supervisor(is_chief=True, checkpoint_dir=d)
    saves = 4
    torn = 1 + (seed % 2)  # newest 1 or 2 manifests torn
    spec = ",".join(
        f"ckpt.manifest:torn@{saves - i}" for i in range(torn)
    )
    failpoints.configure(spec)
    try:
        for s in range(1, saves + 1):
            sup.save(_mk_state(s), s)
    finally:
        failpoints.configure(None)
    expect = saves - torn
    assert latest_checkpoint_step(d, verify=True) == expect
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", RuntimeWarning)
        restored, step = Supervisor(
            is_chief=True, checkpoint_dir=d
        ).prepare_or_restore(_mk_state(0))
    assert step == expect, f"restored step_{step}, wanted step_{expect}"
    got = float(np.asarray(restored.params["w"])[0, 0])
    assert got == float(expect), f"state value {got} != {expect}"
    return {"torn_manifests": torn, "restored_step": step}


_KILL_WORKER = r"""
import os, sys
import jax
import jax.numpy as jnp
from distributed_tensorflow_tpu.parallel.strategy import TrainState
from distributed_tensorflow_tpu.train.supervisor import Supervisor

d = sys.argv[1]
sup = Supervisor(is_chief=True, checkpoint_dir=d)
for s in range(1, 6):
    sup.save(
        TrainState(
            {"w": jnp.full((4, 3), float(s)), "b": jnp.zeros((3,))},
            {"mu": jnp.ones((4, 3))},
            jnp.asarray(int(s), jnp.int32),
        ),
        s,
    )
print("UNREACHED" if os.environ.get("DTF_FAILPOINTS") else "DONE")
"""


@scenario("ckpt-kill-mid-save")
def _ckpt_kill_mid_save(seed, workdir):
    """Writer crash mid-commit: the subprocess saver is SIGKILLed between
    save N's manifest tmp write and the atomic replace. The orbax
    payload for step N is already complete, so restore recovers the FULL
    step (no manifest → unverified-trusted, the pre-round-6 contract);
    the only litter is a ``.tmp`` orphan, which the age-guarded sweep
    removes."""
    import warnings

    from distributed_tensorflow_tpu.train.supervisor import Supervisor

    d = os.path.join(workdir, "ck")
    os.makedirs(d)
    kill_at = 3 + (seed % 2)  # one atomic.write per save (the manifest)
    env = dict(os.environ)
    env["DTF_FAILPOINTS"] = f"atomic.write.commit:kill@{kill_at}"
    env["JAX_PLATFORMS"] = "cpu"  # a CPU child: the chip has one owner
    env["PYTHONPATH"] = _REPO + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run(
        [sys.executable, "-c", _KILL_WORKER, d],
        env=env,
        capture_output=True,
        text=True,
        timeout=300,
    )
    assert proc.returncode == -9, (
        f"rc={proc.returncode}, wanted SIGKILL (-9)\n{proc.stderr[-2000:]}"
    )
    assert "UNREACHED" not in proc.stdout
    # The kill landed mid-manifest-commit: step kill_at's payload is on
    # disk, its manifest is not, and the tmp orphan survives the crash.
    assert not os.path.exists(resilience.manifest_path(d, kill_at))
    orphans = [n for n in os.listdir(d) if ".tmp" in n]
    assert orphans, "writer crash should leave a .tmp orphan"
    swept = resilience.sweep_tmp_orphans(d, age_s=0.0)
    assert len(swept) == len(orphans)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", RuntimeWarning)
        restored, step = Supervisor(
            is_chief=True, checkpoint_dir=d
        ).prepare_or_restore(_mk_state(0))
    assert step == kill_at, f"restored step_{step}, wanted step_{kill_at}"
    got = float(np.asarray(restored.params["w"])[0, 0])
    assert got == float(kill_at)
    return {
        "killed_at_save": kill_at,
        "restored_step": step,
        "orphans_swept": len(swept),
    }


# ---------------------------------------------------------------------------
# Delta-exchange seam (numpy-only: delta_dtype=None never touches jax).
# ---------------------------------------------------------------------------


def _leaf(v):
    return np.full((5, 7), float(v), np.float32)


@scenario("delta-torn")
def _delta_torn(seed, workdir):
    """Mid-gang committed-post corruption: one of rank 0's posts is torn
    at commit; rank 1's stale-weighted round proceeds without it —
    skipped, never consumed, watermark advanced (later rounds still
    arrive), one ``mailbox_corrupt`` event, and the weighted mean over
    the survivors is exact."""
    from distributed_tensorflow_tpu.train.local_sgd import (
        DeltaExchange,
        staleness_weight,
    )

    d = os.path.join(workdir, "mail")
    rounds = 5
    torn_hit = 2 + (seed % 3)  # post() hit N ↔ round N-1
    rec = _Recorder()
    writer = DeltaExchange(d, 0, 2, stale_limit=rounds + 2)
    reader = DeltaExchange(d, 1, 2, stale_limit=rounds + 2, journal=rec)
    failpoints.configure(f"delta.post:torn@{torn_hit}")
    try:
        for r in range(rounds):
            writer.post(r, [_leaf(r + 1)])
    finally:
        failpoints.configure(None)
    own = [_leaf(100.0)]
    mean, total, contributors = reader.weighted_delta(rounds - 1, own)
    torn_round = torn_hit - 1
    survive = [r for r in range(rounds) if r != torn_round]
    assert reader.corrupt_posts == 1
    assert rec.kinds() == ["mailbox_corrupt"]
    assert rec.events[0]["round"] == torn_round
    assert [c[0] for c in contributors] == [1] + [0] * len(survive)
    # Exact weighted mean over the surviving rounds (own weight 1).
    w = [
        staleness_weight(rounds - 1 - r, reader.stale_limit)
        for r in survive
    ]
    want_total = 1.0 + sum(w)
    want = (100.0 + sum(wi * (r + 1) for wi, r in zip(w, survive))) / (
        want_total
    )
    assert abs(total - want_total) < 1e-6
    assert abs(float(mean[0][0, 0]) - want) < 1e-5, (
        f"mean {float(mean[0][0, 0])} != {want}"
    )
    return {"torn_round": torn_round, "survivors": len(survive)}


@scenario("delta-transient")
def _delta_transient(seed, workdir):
    """Transient unreadability: ``delta.load:raise`` makes the first
    peer read fail like a shared-fs hiccup (FailpointError IS an
    OSError). The watermark must NOT advance — the next boundary
    consumes the same round exactly once, one round later. Nothing
    lost, nothing double-applied."""
    from distributed_tensorflow_tpu.train.local_sgd import DeltaExchange

    d = os.path.join(workdir, "mail")
    writer = DeltaExchange(d, 0, 2, stale_limit=4)
    reader = DeltaExchange(d, 1, 2, stale_limit=4)
    val = float(1 + seed)
    writer.post(0, [_leaf(val)])
    failpoints.configure("delta.load:raise@1")
    try:
        got = reader.gather(0)
    finally:
        failpoints.configure(None)
    assert got == [] and reader._consumed == {}, (
        "transient failure must not consume or advance the watermark"
    )
    got = reader.gather(1)  # next boundary: same post, age 1, consumed
    assert len(got) == 1 and got[0][0] == 0 and got[0][1] == 1
    assert float(got[0][3][0][0, 0]) == val
    assert reader.gather(2) == [], "a post is consumed exactly once"
    assert reader.corrupt_posts == 0  # transient ≠ corrupt
    return {"retried_age": 1}


# ---------------------------------------------------------------------------
# Fleet-mailbox seam (jax-free).
# ---------------------------------------------------------------------------


@scenario("fleet-torn-result")
def _fleet_torn_result(seed, workdir):
    """Torn result mid-failover: of R committed results one is torn; the
    router's poll delivers the others and quarantines the torn file
    (never delivered, never re-read). The replica re-serves the one
    request the router still sees as in-flight — the round-16 zero-loss
    protocol: anything without a result re-admits — and every trace is
    delivered exactly once."""
    from distributed_tensorflow_tpu.serve_fleet import MailboxClient

    rec = _Recorder()
    box = MailboxClient(os.path.join(workdir, "r0"), journal=rec)
    n = 4
    torn_hit = 1 + (seed % n)
    traces = [f"t{i}" for i in range(n)]
    payloads = {t: {"trace": t, "out": [i, i + 1]} for i, t in enumerate(traces)}
    failpoints.configure(f"fleet.result:torn@{torn_hit}")
    try:
        for t in traces:
            box.put_result(payloads[t])
    finally:
        failpoints.configure(None)
    first = box.poll_results()
    got = {p["trace"] for p in first}
    torn_trace = traces[torn_hit - 1]
    assert got == set(traces) - {torn_trace}
    assert box.corrupt_files == 1
    assert rec.kinds() == ["mailbox_corrupt"]
    assert rec.events[0]["action"] == "quarantined"
    assert box.poll_results() == [], "quarantined file must not re-read"
    # Failover re-serve: the router re-admits the traceless request and
    # the (re)serving replica commits the identical deterministic result.
    box.put_result(payloads[torn_trace])
    second = box.poll_results()
    assert [p["trace"] for p in second] == [torn_trace]
    assert second[0] == payloads[torn_trace], "re-served result intact"
    delivered = [p["trace"] for p in first + second]
    assert sorted(delivered) == sorted(traces), "each trace exactly once"
    return {"torn_trace": torn_trace, "delivered": len(delivered)}


@scenario("fleet-garbage-json")
def _fleet_garbage_json(seed, workdir):
    """Storage corruption: raw garbage bytes appear as a committed
    ``.json`` in the outbox. The poll quarantines it once (counted,
    journaled), delivers the valid results untouched, and the next poll
    is clean — the pre-round-19 behavior re-read the garbage forever."""
    from distributed_tensorflow_tpu.serve_fleet import MailboxClient

    rec = _Recorder()
    box = MailboxClient(os.path.join(workdir, "r0"), journal=rec)
    box.put_result({"trace": "ok1", "out": [1]})
    rng = random.Random(seed)
    junk = bytes(rng.randrange(256) for _ in range(64))
    with open(os.path.join(box.outbox, "00000000-junk.json"), "wb") as f:
        f.write(junk)
    box.put_result({"trace": "ok2", "out": [2]})
    got = {p["trace"] for p in box.poll_results()}
    assert got == {"ok1", "ok2"}
    assert box.corrupt_files == 1
    assert box.poll_results() == [] and os.listdir(box.outbox) == []
    assert rec.events[0]["reason"] in ("json", "crc")
    return {"junk_bytes": len(junk)}


# ---------------------------------------------------------------------------
# Driver.
# ---------------------------------------------------------------------------


def _jitter_determinism(seed: int) -> bool:
    """Satellite pin, swept per seed: the jittered backoff sequence is a
    pure function of the seeded rng."""
    seq = [
        resilience.backoff_delay(
            a, backoff=0.25, jitter=0.5, rng=random.Random(seed)
        )
        for a in range(4)
    ]
    again = [
        resilience.backoff_delay(
            a, backoff=0.25, jitter=0.5, rng=random.Random(seed)
        )
        for a in range(4)
    ]
    return seq == again


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seeds", default="0,1", help="comma-separated ints")
    ap.add_argument(
        "--schedules",
        default="all",
        help=f"comma-separated from: {','.join(SCENARIOS)} (or 'all')",
    )
    ap.add_argument("--json", dest="json_out", default=None)
    args = ap.parse_args(argv)

    seeds = [int(s) for s in args.seeds.split(",") if s.strip()]
    names = (
        list(SCENARIOS)
        if args.schedules == "all"
        else [s.strip() for s in args.schedules.split(",") if s.strip()]
    )
    unknown = [n for n in names if n not in SCENARIOS]
    if unknown:
        ap.error(f"unknown schedule(s): {unknown}; have {list(SCENARIOS)}")

    cells = []
    failed = 0
    for name in names:
        for seed in seeds:
            failpoints.configure(None)
            t0 = time.perf_counter()
            cell = {"schedule": name, "seed": seed}
            with tempfile.TemporaryDirectory() as workdir:
                try:
                    detail = SCENARIOS[name](seed, workdir) or {}
                    cell.update(ok=True, **detail)
                except Exception as exc:  # noqa: BLE001 — cell verdicts
                    failed += 1
                    cell.update(
                        ok=False,
                        error=f"{type(exc).__name__}: {exc}",
                    )
            failpoints.configure(None)
            cell["wall_s"] = round(time.perf_counter() - t0, 3)
            cells.append(cell)
            status = "ok" if cell["ok"] else "FAIL"
            print(
                f"chaos {name} seed={seed}: {status} "
                f"({cell['wall_s']}s)",
                file=sys.stderr,
            )

    summary = {
        "tool": "chaos_sweep",
        "schedules": names,
        "seeds": seeds,
        "cells": cells,
        "failed": failed,
        "jitter_deterministic": all(_jitter_determinism(s) for s in seeds),
        "ok": failed == 0,
    }
    line = json.dumps(summary)
    print(line)
    if args.json_out:
        with open(args.json_out, "w", encoding="utf-8") as f:
            f.write(line + "\n")
    return 0 if summary["ok"] and summary["jitter_deterministic"] else 1


if __name__ == "__main__":
    raise SystemExit(main())
