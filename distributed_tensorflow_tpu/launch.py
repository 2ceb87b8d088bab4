"""High-level launcher: config → model + strategy + trainer (L7).

This is where ``TrainConfig``'s mode knobs are honored:

- ``sync=True``  → :class:`SyncDataParallel` (or :class:`SingleDevice` on a
  1-chip mesh) — the ``tfdist_between_sync.py`` path;
- ``sync=False`` → :class:`AsyncDataParallel` with
  ``avg_every=async_avg_every`` — the ``tfdist_between.py`` path;
- ``compute_dtype`` → the model's MXU compute dtype;
- ``checkpoint_dir`` → a :class:`Supervisor` wired into the trainer;
- ``logs_path`` → the TensorBoard scalar writer (chief only, matching the
  reference where every worker wrote summaries but only the chief's mattered).

The reference's per-script wiring (build graph → Supervisor → loop,
reference tfdist_between.py:32-113) collapses into :func:`build_trainer`.
"""

from __future__ import annotations

from typing import TYPE_CHECKING

from distributed_tensorflow_tpu.config import ClusterConfig, TrainConfig

if TYPE_CHECKING:  # jax-backed types only; see the lazy imports below
    from distributed_tensorflow_tpu.cluster import ProcessContext
    from distributed_tensorflow_tpu.train.trainer import Trainer
    from distributed_tensorflow_tpu.utils.summary import SummaryWriter

# The jax-backed stack (strategies, models, data, Trainer) is imported
# inside build_strategy/build_trainer/run: the config surface of this
# module (config_from_env / cluster_from_env) is also the elastic
# driver's — a lean supervisor process, or a degraded container, must be
# able to parse the DTF_* env without a working jax (same rationale as
# the lazy train/__init__).


def config_from_env(base: TrainConfig | None = None) -> TrainConfig:
    """Apply environment overrides to a TrainConfig — the knob the reference
    lacked (its hyperparameters were module constants, SURVEY.md §5
    "Config/flag system"). Recognized: DTF_EPOCHS, DTF_BATCH_SIZE, DTF_LR,
    DTF_SCAN (=1 → scan_epoch), DTF_COMPILED (=1 → compiled_run: the whole
    run as one dispatch), DTF_LOGS (logs path, empty disables),
    DTF_MODEL (registry name: mlp | cnn | lstm | transformer), and the
    resilience knobs (train/resilience.py): DTF_CHECKPOINT (checkpoint
    dir — what a pod scheduler sets so a preempted run can resume),
    DTF_KEEP_LAST (checkpoint retention), DTF_MAX_ROLLBACKS (anomaly
    guard budget), and the elastic knobs (train/elastic.py):
    DTF_MAX_RESTARTS (gang-restart budget), DTF_STALL_TIMEOUT_MS
    (live-but-stalled detection window), DTF_MIN_WORKERS (shrink-to-fit
    floor, round 8; 0 disables resizing) and DTF_REJOIN_TIMEOUT_S
    (replacement-registration window before a resize), the round-13
    perf knobs: DTF_REMAT (0 | 1 | selective) and DTF_MATMUL_DTYPE
    (int8 | fp8, empty → off), and the DiLoCo outer-loop knobs
    (train/local_sgd.py): DTF_SYNC_EVERY (H inner steps per outer
    round), DTF_OUTER_LR (empty → the worker-count default),
    DTF_OUTER_MOMENTUM, and the round-17 streaming/compressed levers:
    DTF_DELTA_DTYPE (int8 | fp8, empty → full-precision deltas) and
    DTF_STALE_LIMIT (stale-tolerant gang window in outer rounds; 0 =
    same-round deltas only). Invalid values
    raise ValueError naming the knob — a scheduler typo must fail the
    launch, not silently train with defaults (TrainConfig.__post_init__
    validates the perf-knob values the same way)."""
    import os

    def _parse(var: str, conv):
        try:
            return conv(os.environ[var])
        except ValueError as exc:
            raise ValueError(
                f"invalid {var}={os.environ[var]!r}: {exc}"
            ) from None

    cfg = base or TrainConfig()
    kw = {}
    if "DTF_CHECKPOINT" in os.environ:
        kw["checkpoint_dir"] = os.environ["DTF_CHECKPOINT"] or None
    if "DTF_KEEP_LAST" in os.environ:
        kw["keep_last_n"] = _parse("DTF_KEEP_LAST", int) or None
    if "DTF_MAX_ROLLBACKS" in os.environ:
        kw["max_rollbacks"] = _parse("DTF_MAX_ROLLBACKS", int)
    if "DTF_MAX_RESTARTS" in os.environ:
        kw["max_restarts"] = _parse("DTF_MAX_RESTARTS", int)
    if "DTF_STALL_TIMEOUT_MS" in os.environ:
        kw["stall_timeout_ms"] = _parse("DTF_STALL_TIMEOUT_MS", int)
    if "DTF_MIN_WORKERS" in os.environ:
        kw["min_workers"] = _parse("DTF_MIN_WORKERS", int)
    if "DTF_REJOIN_TIMEOUT_S" in os.environ:
        kw["rejoin_timeout_s"] = _parse("DTF_REJOIN_TIMEOUT_S", float)
    if "DTF_MODEL" in os.environ:
        kw["model"] = os.environ["DTF_MODEL"]
    if "DTF_EPOCHS" in os.environ:
        kw["epochs"] = _parse("DTF_EPOCHS", int)
    if "DTF_BATCH_SIZE" in os.environ:
        kw["batch_size"] = _parse("DTF_BATCH_SIZE", int)
    if "DTF_LR" in os.environ:
        kw["learning_rate"] = _parse("DTF_LR", float)
    if "DTF_SCAN" in os.environ:
        kw["scan_epoch"] = os.environ["DTF_SCAN"] == "1"
    if "DTF_COMPILED" in os.environ:
        kw["compiled_run"] = os.environ["DTF_COMPILED"] == "1"
    if "DTF_LOGS" in os.environ:
        kw["logs_path"] = os.environ["DTF_LOGS"]
    if "DTF_SYNC_EVERY" in os.environ:
        kw["sync_every"] = _parse("DTF_SYNC_EVERY", int)
    if "DTF_OUTER_LR" in os.environ:
        # Empty = the worker-count default (the update_scale=N
        # convention), mirroring the other unset-style knobs.
        raw = os.environ["DTF_OUTER_LR"]
        kw["outer_lr"] = _parse("DTF_OUTER_LR", float) if raw else None
    if "DTF_OUTER_MOMENTUM" in os.environ:
        kw["outer_momentum"] = _parse("DTF_OUTER_MOMENTUM", float)
    if "DTF_DELTA_DTYPE" in os.environ:
        # Empty = full-precision deltas (the unset-style contract, like
        # DTF_MATMUL_DTYPE); bad names fail in TrainConfig.__post_init__.
        kw["delta_dtype"] = os.environ["DTF_DELTA_DTYPE"] or None
    if "DTF_STALE_LIMIT" in os.environ:
        kw["stale_limit"] = _parse("DTF_STALE_LIMIT", int)
    if "DTF_REMAT" in os.environ:
        raw = os.environ["DTF_REMAT"]
        # Empty/0/1 keep the boolean surface (empty = off, matching the
        # sibling knob's unset-style contract); "selective" is the
        # older spelling of 1 for the LM family; anything else fails in
        # TrainConfig.__post_init__.
        kw["remat"] = raw == "1" if raw in ("", "0", "1") else raw
    if "DTF_MATMUL_DTYPE" in os.environ:
        kw["matmul_dtype"] = os.environ["DTF_MATMUL_DTYPE"] or None
    return cfg.replace(**kw) if kw else cfg


def parse_worker_ranks(raw: str) -> tuple[int, ...]:
    """Parse a ``DTF_WORKER_RANKS`` value (comma-separated ORIGINAL
    ranks in new-rank order). THE one parser for the knob — the elastic
    driver writes it, :func:`cluster_from_env` resolves the resize
    topology from it, and ``cluster.bootstrap`` maps compact ranks back
    to original ids for per-rank journals; all three must agree on what
    is valid."""
    try:
        return tuple(int(r) for r in raw.split(","))
    except ValueError:
        raise ValueError(
            f"invalid DTF_WORKER_RANKS={raw!r}: must be comma-separated "
            "integers (original ranks in new-rank order)"
        ) from None


def cluster_from_env(base: ClusterConfig | None = None) -> ClusterConfig:
    """Apply environment overrides to a ClusterConfig — the detector half
    of the pod-scheduler surface (the trainer half is
    :func:`config_from_env`). Recognized: DTF_HEARTBEAT_PORT (UDP failure
    detector port; empty/0 disables), DTF_HEARTBEAT_TIMEOUT_MS (silence
    window), DTF_HEARTBEAT_HOST (set by an elastic agent —
    train/elastic.py — that hosts the detector out-of-band; every task
    then sends beats there instead of the chief hosting). ``launch.run``
    applies this, so a scheduler arms failure detection without code
    changes, mirroring DTF_CHECKPOINT/DTF_MAX_ROLLBACKS.

    Resize topology (round 8; set by the elastic driver on a relaunch at
    a non-original world size): DTF_WORKER_RANKS — comma-separated
    ORIGINAL ranks in new-rank order, resolved via
    ``ClusterConfig.subset`` (the worker re-bootstraps
    ``jax.distributed`` at ``len(ranks)`` processes with ``ranks[0]``'s
    host as coordinator); DTF_WORLD_SIZE — shorthand for the first-N
    prefix when the survivor set IS a prefix, and a cross-check
    (``len(ranks)`` must match) when both are set. Invalid values raise
    ValueError naming the knob."""
    import dataclasses
    import os

    def _parse(var: str, conv):
        try:
            return conv(os.environ[var])
        except ValueError as exc:
            raise ValueError(
                f"invalid {var}={os.environ[var]!r}: {exc}"
            ) from None

    cluster = base or ClusterConfig()
    kw = {}
    if "DTF_HEARTBEAT_PORT" in os.environ:
        raw = os.environ["DTF_HEARTBEAT_PORT"]
        kw["heartbeat_port"] = _parse("DTF_HEARTBEAT_PORT", int) if raw else None
        if kw["heartbeat_port"] == 0:
            kw["heartbeat_port"] = None
    if "DTF_HEARTBEAT_TIMEOUT_MS" in os.environ:
        kw["heartbeat_timeout_ms"] = _parse("DTF_HEARTBEAT_TIMEOUT_MS", int)
    if "DTF_HEARTBEAT_HOST" in os.environ:
        kw["heartbeat_host"] = os.environ["DTF_HEARTBEAT_HOST"] or None
    cluster = dataclasses.replace(cluster, **kw) if kw else cluster

    ranks = None
    if os.environ.get("DTF_WORKER_RANKS"):
        ranks = parse_worker_ranks(os.environ["DTF_WORKER_RANKS"])
    if os.environ.get("DTF_WORLD_SIZE"):
        raw = os.environ["DTF_WORLD_SIZE"]
        try:
            world = int(raw)
        except ValueError:
            raise ValueError(
                f"invalid DTF_WORLD_SIZE={raw!r}: must be an integer"
            ) from None
        if world < 1:
            raise ValueError(f"invalid DTF_WORLD_SIZE={world}: must be >= 1")
        if ranks is None:
            ranks = tuple(range(world))
        elif len(ranks) != world:
            raise ValueError(
                f"DTF_WORLD_SIZE={world} contradicts DTF_WORKER_RANKS="
                f"{ranks} (length {len(ranks)})"
            )
    if ranks is not None:
        if not cluster.worker_svrs:
            raise ValueError(
                "DTF_WORLD_SIZE/DTF_WORKER_RANKS set but the base "
                "ClusterConfig lists no worker_svrs to select from"
            )
        cluster = cluster.subset(ranks)
    return cluster


def build_strategy(config: TrainConfig, *, devices=None, mesh=None):
    if config.dp_mode not in ("replicated", "zero"):
        raise ValueError(
            f"unknown dp_mode {config.dp_mode!r} for the classifier path; "
            "use 'replicated' or 'zero' ('tp'/'ep'/'pp' are LM-trainer "
            "modes — train/lm_trainer.py)"
        )
    if config.dp_mode == "zero" and not config.sync:
        raise ValueError("dp_mode='zero' requires sync=True (async keeps per-chip copies)")
    import jax

    from distributed_tensorflow_tpu.parallel import (
        AsyncDataParallel,
        SingleDevice,
        SyncDataParallel,
        make_mesh,
    )

    devices = list(devices if devices is not None else jax.devices())
    if mesh is None and len(devices) == 1:
        return SingleDevice()
    mesh = mesh or make_mesh(devices=devices)
    if config.sync:
        if config.dp_mode == "zero":
            from distributed_tensorflow_tpu.parallel import ShardedDataParallel

            return ShardedDataParallel(mesh)
        return SyncDataParallel(mesh)
    return AsyncDataParallel(mesh, avg_every=config.async_avg_every)


class _RematAdapter:
    """Applies ``jax.checkpoint`` to the model forward: activations are
    recomputed during the backward pass instead of stored — the standard
    TPU trade of MXU FLOPs for HBM activation memory. Gradients are
    mathematically identical (tests/test_launch.py proves bitwise-close);
    only peak memory and backward-pass FLOPs change. No reference analog
    (TF1 stored everything)."""

    def __init__(self, model):
        import jax

        self._model = model
        self._apply = jax.checkpoint(model.apply)
        if hasattr(model, "apply_logits"):
            # Keep the stable-loss path remat'd too (loss="stable" wraps
            # apply_logits via _LogitsAdapter after this adapter).
            self.apply_logits = jax.checkpoint(model.apply_logits)

    def __getattr__(self, name):
        return getattr(self._model, name)

    def apply(self, params, x):
        return self._apply(params, x)


class _LogitsAdapter:
    """Presents ``apply_logits`` as ``apply`` so the logits-based stable
    loss composes with the strategy stack (accuracy argmax is unchanged)."""

    def __init__(self, model):
        self._model = model

    def __getattr__(self, name):
        return getattr(self._model, name)

    def apply(self, params, x):
        return self._model.apply_logits(params, x)


def build_trainer(
    config: TrainConfig | None = None,
    *,
    context: ProcessContext | None = None,
    model=None,
    datasets=None,
    strategy=None,
    optimizer=None,
    loss_fn=None,
    data_dir: str = "MNIST_data",
    summary_writer: SummaryWriter | None = None,
    print_fn=print,
) -> Trainer:
    import jax.numpy as jnp

    from distributed_tensorflow_tpu.data import read_data_sets
    from distributed_tensorflow_tpu.ops import optim as optim_lib
    from distributed_tensorflow_tpu.train.trainer import Trainer
    from distributed_tensorflow_tpu.utils.summary import SummaryWriter

    config = config or TrainConfig()
    # Pure config validation runs BEFORE any model/dataset construction.
    if getattr(config, "matmul_dtype", None):
        raise ValueError(
            "matmul_dtype is an LM-family knob (models/gpt.GPTLM / "
            "LMTrainer); the classifier models have no quantized path"
        )
    is_chief = context.is_chief if context is not None else True
    if model is None:
        from distributed_tensorflow_tpu.models import build_model

        model = build_model(
            config.model, compute_dtype=jnp.dtype(config.compute_dtype)
        )
    if config.remat:
        # Any truthy value — including "selective" — is plain
        # jax.checkpoint here: the classifier models carry no
        # checkpoint-name surface for a selective policy to save.
        model = _RematAdapter(model)
    datasets = datasets or read_data_sets(data_dir, one_hot=True)
    strategy = strategy or build_strategy(config)
    if optimizer is None:
        # The schedule count advances once per optimizer *apply*: trainer
        # epochs run num_examples // (batch_size × replicas) steps (global
        # batches; trainer.py), or // batch_size under per_worker_epoch, and
        # accumulation applies once every accumulate_steps micro-steps.
        denom = config.batch_size * (
            1 if config.per_worker_epoch else strategy.num_replicas
        )
        applies_per_epoch = max(1, datasets.train.num_examples // denom)
        total_applies = max(
            1, config.epochs * applies_per_epoch // config.accumulate_steps
        )
        lr = optim_lib.schedule(
            config.lr_schedule,
            config.learning_rate,
            total_applies,
            warmup_steps=config.warmup_steps,
        )
        optimizer = optim_lib.accumulate(
            optim_lib.clip(
                optim_lib.make(config.optimizer, lr), config.grad_clip_norm
            ),
            config.accumulate_steps,
        )
    if loss_fn is None:
        from distributed_tensorflow_tpu.ops import losses as losses_lib

        if config.loss == "stable":
            if not hasattr(model, "apply_logits"):
                raise ValueError(
                    f"loss='stable' needs apply_logits on {type(model).__name__}"
                )
            model = _LogitsAdapter(model)
            loss_fn = losses_lib.stable_cross_entropy
        elif config.loss == "naive":
            loss_fn = losses_lib.cross_entropy
        else:
            raise ValueError(f"unknown loss {config.loss!r}; use 'naive' or 'stable'")
    if summary_writer is None and is_chief and config.logs_path:
        summary_writer = SummaryWriter(config.logs_path)
    trainer = Trainer(
        model,
        datasets,
        config,
        strategy=strategy,
        optimizer=optimizer,
        loss_fn=loss_fn,
        summary_writer=summary_writer,
        is_chief=is_chief,
        print_fn=print_fn,
    )
    # Failure-reactive stop: a chief with an armed heartbeat coordinator
    # (cluster.bootstrap(heartbeat_port=...)) stops cleanly when a worker
    # dies — or, with stall_timeout_ms set, stalls — instead of hanging in
    # a collective (train/supervisor.py). In elastic mode
    # (heartbeat_host set) the detector lives in the agent and
    # context.heartbeat is a plain SENDER even on the chief — nothing to
    # attach, hence the coordinator-shape check.
    if context is not None:
        has_coordinator = context.heartbeat is not None and hasattr(
            context.heartbeat, "failed_count"
        )
        has_sender = any(
            h is not None and hasattr(h, "set_progress")
            for h in (context.heartbeat_sender, context.heartbeat)
        )
        if (has_coordinator and is_chief) or has_sender:
            if trainer.supervisor is None:
                from distributed_tensorflow_tpu.train import Supervisor

                trainer.supervisor = Supervisor(is_chief=is_chief)
            if has_coordinator and is_chief:
                trainer.supervisor.attach_heartbeat(
                    context.heartbeat,
                    stall_timeout_ms=config.stall_timeout_ms,
                )
            if has_sender:
                # Progress-aware health: the trainer bumps the counter at
                # epoch boundaries; the beats carry it to the detector.
                trainer.supervisor.attach_progress(context.report_progress)
    return trainer


def run(
    cluster: ClusterConfig | None = None,
    config: TrainConfig | None = None,
    argv=None,
    **kw,
) -> dict | None:
    """End-to-end entry: parse flags, bootstrap, train. Returns the final
    metrics dict (or None for a ps no-op process)."""
    from distributed_tensorflow_tpu.cluster import bootstrap_from_argv
    from distributed_tensorflow_tpu.utils.compile_cache import (
        configure_compile_cache,
    )

    configure_compile_cache()
    # Env overrides (pod-scheduler surface): heartbeat/elastic knobs ride
    # DTF_* like the resilience knobs; bootstrap_from_argv then threads the
    # cluster-level heartbeat settings into bootstrap, so the documented
    # launch.run(cluster) entry gets failure detection too.
    cluster = cluster_from_env(cluster or ClusterConfig())
    ctx = bootstrap_from_argv(cluster, argv)
    if ctx.should_exit:
        return None
    try:
        trainer = build_trainer(config_from_env(config), context=ctx, **kw)
        print("Ready to go")  # reference tfdist_between.py:76
        return trainer.run()  # honors compiled_run / scan_epoch internally
    finally:
        ctx.close()  # stop heartbeat threads (sv.stop() analog)
