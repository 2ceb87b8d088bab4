"""Serving-fleet fault injection (RUN_SLOW): SIGKILL a replica of a live
≥3-replica fleet mid-decode — zero failed requests, every served stream
(including the re-admitted ones) token-identical to in-process decode —
and the live weight swap: a fleet adopts a newer CRC-verified checkpoint
between chunk boundaries with no request dropped.

The serving twin of test_fault_injection.py, grounded in the paper's
async thesis: replicas fail and recover independently while the fleet
keeps serving, exactly as the reference's async PS workers did for
training (reference tfdist_between.py:83 re-attach semantics, upgraded
from "don't lose the PS state" to "don't lose a single request")."""

import os
import signal
import sys
import time

import numpy as np
import pytest

pytestmark = pytest.mark.skipif(
    not os.environ.get("RUN_SLOW"),
    reason="serving fleet fault injection (set RUN_SLOW=1)",
)

_REPO = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)

_MODEL_KW = dict(
    vocab_size=97,
    max_len=96,
    model_dim=32,
    num_heads=4,
    num_layers=2,
    compute_dtype="float32",  # bitwise-stable across processes
)


def _fleet_env():
    env = {
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=1",
        "PYTHONPATH": os.environ.get("PYTHONPATH", "")
        + os.pathsep
        + _REPO,
    }
    return env


def _model_and_params(seed):
    import jax.numpy as jnp

    from distributed_tensorflow_tpu.models.gpt import GPTLM

    kw = dict(_MODEL_KW)
    kw["compute_dtype"] = jnp.float32
    model = GPTLM(**kw)
    return model, model.init(seed)


def _workload(model, n, seed=0):
    from distributed_tensorflow_tpu.serve import GenerationConfig

    rng = np.random.default_rng(seed)
    prompts = [
        rng.integers(0, model.vocab_size, (int(s),)).astype(np.int32)
        for s in rng.integers(4, 17, n)
    ]
    configs = [
        GenerationConfig(max_new=24, greedy=True)
        if i % 3
        else GenerationConfig(
            max_new=24, greedy=False, temperature=0.8, top_p=0.9, seed=40 + i
        )
        for i in range(n)
    ]
    return prompts, configs


def _reference_stream(model, params, prompt, cfg):
    import jax
    import jax.numpy as jnp

    if cfg.greedy:
        ref = model.greedy_decode(params, jnp.asarray(prompt[None]), cfg.max_new)
    else:
        ref = model.sample_decode(
            params,
            jnp.asarray(prompt[None]),
            cfg.max_new,
            jax.random.key(cfg.seed),
            temperature=cfg.temperature,
            top_p=cfg.top_p,
        )
    return np.asarray(ref)[0, prompt.size:]


def test_fleet_survives_replica_sigkill_with_zero_loss_and_parity(tmp_path):
    """Acceptance (tentpole): 3 subprocess replicas serving a mixed
    greedy/sampled workload; one replica is SIGKILLed while it holds
    in-flight requests mid-decode. The router re-admits its in-flight to
    healthy replicas (same trace, full config), relaunches the dead one
    under the restart budget, and EVERY request completes with a stream
    token-identical to in-process decode — the round-9 parity contract
    through failover. The merged journals show one trace admitted on two
    replicas (obs_report --fleet), and the weight-swap phase then adopts
    a newer checkpoint with residents finishing on the old weights."""
    from distributed_tensorflow_tpu import serve_fleet
    from distributed_tensorflow_tpu.observability import aggregate
    from distributed_tensorflow_tpu.tools import obs_report

    model, params1 = _model_and_params(seed=3)
    ckpt = str(tmp_path / "ckpt")
    serve_fleet.publish_checkpoint(model, params1, ckpt, step=1)

    fleet_dir = str(tmp_path / "fleet")
    router = serve_fleet.local_fleet(
        _MODEL_KW,
        ckpt,
        fleet_dir,
        replicas=3,
        slots=2,
        chunk=4,
        queue_limit=64,
        buckets=(16,),
        env=_fleet_env(),
        min_replicas=1,
        max_restarts=2,
        backoff=0.5,
        jitter=0.25,
        probe_interval_s=0.25,
        poll_interval=0.02,
        print_fn=lambda *a: None,
    )
    n = 18
    prompts, configs = _workload(model, n, seed=1)
    try:
        rids = [
            router.submit(p, c) for p, c in zip(prompts, configs)
        ]
        # Tick until the fleet is mid-flight: at least one completion AND
        # some replica holding several in-flight requests mid-decode.
        killed = None
        deadline = time.time() + 600
        while router.step():
            st = router.stats()
            if killed is None and st["done"] >= 2:
                victim = max(
                    router.replicas.values(), key=lambda h: len(h.inflight)
                )
                if len(victim.inflight) >= 2 and victim.agent.handle is not None:
                    os.kill(victim.agent.handle.pid, signal.SIGKILL)
                    killed = victim.name
            assert time.time() < deadline, f"fleet stuck: {router.stats()}"
            time.sleep(0.02)
        assert killed is not None, "fleet finished before the kill staged"
        stats = router.stats()
        # Zero-loss: every request reached done (none cancelled, none lost).
        assert stats["done"] == n and stats["cancelled"] == 0, stats
        assert stats["failovers"] >= 1 and stats["reroutes"] >= 2, stats

        # Parity through failover: every stream — including the re-served
        # ones — equals the in-process decode of the checkpoint params.
        for p, c, rid in zip(prompts, configs, rids):
            out = np.asarray(router.result(rid), np.int32)
            ref = _reference_stream(model, params1, p, c)
            assert np.array_equal(out, ref), (c, p)

        # -- live weight swap (fleet-wide) -------------------------------
        # Phase B under params1, sized to the fleet's slot bank so every
        # request is RESIDENT (or already done) before the swap control is
        # sent — residents complete under old weights. Phase C routes
        # after the control; per-replica FIFO mailboxes guarantee the
        # worker processes swap before C, so C serves the new weights.
        _, params2 = _model_and_params(seed=9)
        prompts_b, configs_b = _workload(model, 6, seed=2)  # 3 replicas x 2 slots
        rids_b = [
            router.submit(p, c) for p, c in zip(prompts_b, configs_b)
        ]
        admit_deadline = time.time() + 300
        while time.time() < admit_deadline:
            router.step()
            busy = sum(
                int((h.health.probe() or {}).get("slots_busy") or 0)
                for h in router.replicas.values()
            )
            done_b = sum(router.done(r) for r in rids_b)
            if busy + done_b >= len(rids_b):
                break  # every B request is resident or finished
            time.sleep(0.02)
        else:
            raise AssertionError(f"phase B never admitted: {router.stats()}")
        serve_fleet.publish_checkpoint(model, params2, ckpt, step=2)
        router.swap_weights()
        prompts_c, configs_c = _workload(model, 6, seed=5)
        rids_c = [
            router.submit(p, c) for p, c in zip(prompts_c, configs_c)
        ]
        router.run_until_done(timeout_s=600)
        for p, c, rid in zip(prompts_b, configs_b, rids_b):
            out = np.asarray(router.result(rid), np.int32)
            assert np.array_equal(out, _reference_stream(model, params1, p, c))
        for p, c, rid in zip(prompts_c, configs_c, rids_c):
            out = np.asarray(router.result(rid), np.int32)
            assert np.array_equal(out, _reference_stream(model, params2, p, c))
    finally:
        router.shutdown()
        router.journal.close()

    # -- the journals tell the story (obs_report --fleet) ----------------
    merged = aggregate.merge(fleet_dir)
    records = obs_report.reconstruct_fleet_requests(merged)
    # rid is the ROUTER's: replica-local warmup requests reconstruct too
    # (rid None) but are not fleet traffic.
    done = [r for r in records if r["done"] and r["rid"] is not None]
    assert len(done) == n + 12, (len(done), len(records))
    spans = [r for r in records if len(set(r["replicas"])) > 1]
    assert spans, "no request shows admission on two replicas"
    assert all(r["failovers"] >= 1 for r in spans)
    kinds = {e.get("kind") for e in merged["events"]}
    assert {"replica_dead", "replica_relaunch", "weight_swap"} <= kinds
    # Every replica journaled at least one incarnation; the killed one
    # announced itself twice (worker_start per (re)launch).
    summary = aggregate.fleet_summary(merged)
    assert summary["worker_starts"][f"{killed}"] >= 2, summary


def test_fleet_deadline_and_backpressure_end_to_end(tmp_path):
    """Satellites over real replicas: a deadline-doomed request cancels
    (terminal — retries never resurrect it) while everything else
    completes token-identically, under a deliberately tiny replica
    queue_limit — saturation holds the overflow at the ROUTER (the
    /healthz queue_saturation signal doing its routing job) instead of
    growing any replica's queue without bound, and nothing is lost."""
    from distributed_tensorflow_tpu import serve_fleet

    model, params = _model_and_params(seed=4)
    ckpt = str(tmp_path / "ckpt")
    serve_fleet.publish_checkpoint(model, params, ckpt, step=1)
    fleet_dir = str(tmp_path / "fleet")
    router = serve_fleet.local_fleet(
        _MODEL_KW,
        ckpt,
        fleet_dir,
        replicas=2,
        slots=1,
        chunk=4,
        queue_limit=2,  # tiny: backpressure is reachable
        buckets=(16,),
        env=_fleet_env(),
        min_replicas=1,
        max_restarts=1,
        poll_interval=0.02,
        print_fn=lambda *a: None,
    )
    prompts, configs = _workload(model, 10, seed=7)
    try:
        rids = [router.submit(p, c) for p, c in zip(prompts, configs)]
        doomed = router.submit(
            prompts[0], configs[0], deadline_s=0.0
        )
        router.run_until_done(timeout_s=600)
        assert router.done(doomed)
        with pytest.raises(RuntimeError, match="cancelled"):
            router.result(doomed)
        for p, c, rid in zip(prompts, configs, rids):
            out = np.asarray(router.result(rid), np.int32)
            assert np.array_equal(out, _reference_stream(model, params, p, c))
    finally:
        router.shutdown()
        router.journal.close()
