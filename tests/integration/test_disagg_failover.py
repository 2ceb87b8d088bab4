"""Disaggregated-fleet fault injection (RUN_SLOW, round 23): a real
subprocess fleet with prefill/decode ROLES serves a mixed greedy/sampled
workload through the two-leg migration path; a DECODE replica is
SIGKILLed while it holds resumed requests mid-stream — the router
re-routes the decode legs with the SAME migration posts (it owns post
lifetime until terminal), zero requests are lost, and every stream is
token-identical to in-process decode: the round-9 parity contract
through a prefill→decode handoff AND a mid-decode failover.

The disaggregated twin of test_serve_fleet_failover.py, grounded in the
same async thesis: specialized workers fail independently while the
fleet keeps serving (reference tfdist_between.py:83 re-attach
semantics, upgraded to role-specialized replicas that hand requests
across the prefill/decode boundary without losing a token)."""

import os
import signal
import time

import numpy as np
import pytest

pytestmark = pytest.mark.skipif(
    not os.environ.get("RUN_SLOW"),
    reason="disaggregated fleet fault injection (set RUN_SLOW=1)",
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
    return {
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=1",
        "PYTHONPATH": os.environ.get("PYTHONPATH", "")
        + os.pathsep
        + _REPO,
    }


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
            max_new=24, greedy=False, temperature=0.8, top_p=0.9, seed=70 + i
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


def test_disagg_fleet_survives_decode_sigkill_with_zero_loss_and_parity(
    tmp_path,
):
    """Acceptance (ISSUE 20): 1 prefill + 2 decode subprocess replicas;
    every request runs leg 1 on the prefill replica, exports its paged
    KV, and finishes on a decode replica. One decode replica is
    SIGKILLed while it holds resumed requests mid-decode: its legs
    re-route to the surviving decode replica by re-importing the SAME
    posts, nothing is lost, and every stream — greedy and seeded-sampled
    — equals in-process decode. The merged journals then show the
    two-leg join: migrated records spanning two replicas, kv_migration
    post/import events, and per-role summaries."""
    from distributed_tensorflow_tpu import serve_fleet
    from distributed_tensorflow_tpu.observability import aggregate
    from distributed_tensorflow_tpu.tools import obs_report

    model, params = _model_and_params(seed=6)
    ckpt = str(tmp_path / "ckpt")
    serve_fleet.publish_checkpoint(model, params, ckpt, step=1)

    fleet_dir = str(tmp_path / "fleet")
    router = serve_fleet.local_fleet(
        _MODEL_KW,
        ckpt,
        fleet_dir,
        replicas=3,
        roles=["prefill", "decode", "decode"],
        slots=2,
        chunk=4,
        queue_limit=64,
        buckets=(16,),
        block_size=8,
        kv_blocks=48,
        env=_fleet_env(),
        min_replicas=1,
        max_restarts=2,
        backoff=0.5,
        jitter=0.25,
        probe_interval_s=0.25,
        poll_interval=0.02,
        print_fn=lambda *a: None,
    )
    n = 12
    prompts, configs = _workload(model, n, seed=11)
    decode_names = {
        h.name for h in router.replicas.values() if h.role == "decode"
    }
    try:
        rids = [router.submit(p, c) for p, c in zip(prompts, configs)]
        killed = None
        deadline = time.time() + 600
        while router.step():
            st = router.stats()
            if killed is None and st["done"] >= 2:
                # Kill the decode replica holding the most RESUMED legs.
                victims = [
                    h for h in router.replicas.values()
                    if h.name in decode_names and len(h.inflight) >= 1
                    and h.agent.handle is not None
                ]
                if victims:
                    victim = max(victims, key=lambda h: len(h.inflight))
                    os.kill(victim.agent.handle.pid, signal.SIGKILL)
                    killed = victim.name
            assert time.time() < deadline, f"fleet stuck: {router.stats()}"
            time.sleep(0.02)
        assert killed is not None, "fleet finished before the kill staged"
        stats = router.stats()
        assert stats["done"] == n and stats["cancelled"] == 0, stats
        assert stats["failovers"] >= 1, stats
        assert router.metrics.counter("fleet_migrations_total").value >= n

        # Parity: every stream (incl. the re-imported ones) == in-process
        # decode — the contract survives the handoff AND the failover.
        for p, c, rid in zip(prompts, configs, rids):
            out = np.asarray(router.result(rid), np.int32)
            ref = _reference_stream(model, params, p, c)
            assert np.array_equal(out, ref), (c, p)

        # Post lifetime: every request is terminal, so the router removed
        # every migration post — the store drains to empty.
        migrate_dir = os.path.join(fleet_dir, "migrate")
        leftovers = [
            f for f in os.listdir(migrate_dir) if f.endswith(".npz")
        ]
        assert leftovers == [], leftovers
    finally:
        router.shutdown()
        router.journal.close()

    # -- the journals tell the story (obs_report --fleet) ----------------
    merged = aggregate.merge(fleet_dir)
    records = obs_report.reconstruct_fleet_requests(merged)
    fleet = [r for r in records if r["rid"] is not None]
    done = [r for r in fleet if r["done"]]
    assert len(done) == n, (len(done), len(records))
    migrated = [r for r in fleet if r["migrated"]]
    assert len(migrated) == n, "every request crossed the handoff"
    summary = aggregate.fleet_summary(merged)
    prefill_names = {
        name for name, info in summary["ranks"].items()
        if info.get("role") == "prefill"
    }
    assert all(
        (r["migration"] or {}).get("from") in prefill_names
        for r in migrated
    ), migrated[0]
    # At least one migrated record spans two DECODE admissions (the
    # failover re-imported the same post on the survivor).
    spans = [
        r for r in migrated
        if len([x for x in r["replicas"] if x in decode_names]) >= 2
        or r["failovers"] >= 1
    ]
    assert spans, "no migrated request shows the decode-leg failover"
    kinds = {e.get("kind") for e in merged["events"]}
    assert {
        "fleet_roles", "request_migrated", "kv_migration", "replica_dead",
    } <= kinds
    posts = [
        e for e in merged["events"]
        if e.get("kind") == "kv_migration" and e.get("phase") == "post"
    ]
    imports = [
        e for e in merged["events"]
        if e.get("kind") == "kv_migration" and e.get("phase") == "import"
    ]
    assert len(posts) >= n and len(imports) >= n
    roles = {
        name: info.get("role")
        for name, info in summary["ranks"].items()
        if info.get("role")
    }
    assert sorted(roles.values()) == ["decode", "decode", "prefill"], roles
    txt = obs_report.render_fleet_requests(records)
    assert "done+migr" in txt and "kv migration:" in txt
