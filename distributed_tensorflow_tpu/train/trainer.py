"""The training loop (component C14, SURVEY.md §2) — strategy-agnostic.

Reproduces the reference loop's contract (reference tfdist_between.py:86-111):
``epochs`` × ``num_train_examples // batch_size`` steps, one compiled train
step per batch, Step/Epoch/Batch/Cost/AvgTime logs every ``log_frequency``
batches, full-test-set accuracy + wall time per epoch, scalar summaries, and
a final-cost line.

TPU-first deltas from the reference loop:

- the step is fully compiled (jit/pjit/shard_map per strategy) — no
  per-batch Python→runtime graph feed;
- cost fetches are *lazy*: the returned device scalar is only synced on the
  host at log/summary cadence, so JAX's async dispatch keeps the device
  busy (the reference blocked on ``sess.run`` fetching cost every batch);
- summaries are buffered per epoch and flushed off the hot path.
"""

from __future__ import annotations

import time
from functools import partial
from typing import Callable

import jax

from distributed_tensorflow_tpu.config import TrainConfig
from distributed_tensorflow_tpu.observability import journal as obs_journal
from distributed_tensorflow_tpu.observability import names
from distributed_tensorflow_tpu.observability.metrics import MetricsRegistry
from distributed_tensorflow_tpu.observability.spans import SpanRecorder
from distributed_tensorflow_tpu.ops import losses as losses_lib
from distributed_tensorflow_tpu.ops import optim as optim_lib
from distributed_tensorflow_tpu.parallel.strategy import (
    AsyncDataParallel,
    SingleDevice,
    Strategy,
)
from distributed_tensorflow_tpu.train.supervisor import Supervisor
from distributed_tensorflow_tpu.utils.logging import StepLogger
from distributed_tensorflow_tpu.utils.summary import SummaryWriter, lifecycle_event


class Trainer:
    def __init__(
        self,
        model,
        datasets,
        config: TrainConfig | None = None,
        *,
        strategy: Strategy | None = None,
        loss_fn: Callable | None = None,
        optimizer=None,
        summary_writer: SummaryWriter | None = None,
        supervisor: "Supervisor | None" = None,
        is_chief: bool = True,
        print_fn=print,
        journal=None,
        metrics: MetricsRegistry | None = None,
    ):
        self.model = model
        self.datasets = datasets
        self.config = config or TrainConfig()
        self.strategy = strategy or SingleDevice()
        self.loss_fn = loss_fn or losses_lib.cross_entropy
        self.optimizer = optimizer or optim_lib.sgd(self.config.learning_rate)
        self.summary_writer = summary_writer
        self.is_chief = is_chief
        self.print_fn = print_fn
        # Telemetry (round 10, observability/): the journal defaults to the
        # process-wide one (a no-op NullJournal unless observability
        # .configure ran) — every structured line below is rendered FROM a
        # journal event, byte-identical to the pre-journal output.
        self.journal = journal if journal is not None else obs_journal.get_journal()
        self.metrics = metrics if metrics is not None else MetricsRegistry()
        self.spans = SpanRecorder(journal=self.journal)

        self.state = self.strategy.init_state(self.model, self.optimizer, self.config.seed)
        self.train_step = self.strategy.make_train_step(
            self.model, self.loss_fn, self.optimizer
        )
        self.eval_fn = self.strategy.make_eval_fn(self.model)
        self._exchange = None
        if isinstance(self.strategy, AsyncDataParallel) and self.strategy.avg_every:
            self._exchange = self.strategy.make_exchange_fn()
        # Global-batch policy (round 8): the effective global batch this
        # run consumes per optimizer step. Derived from the config
        # (reference convention: batch_size per worker × replicas), but a
        # restore across a WORLD-SIZE change adopts the checkpoint's
        # recorded global batch instead — the resized gang keeps the same
        # optimization trajectory (steps/epoch, effective batch), each
        # surviving replica's shard just grows. See _adopt_batch_policy.
        self.global_batch = self.config.batch_size * self.strategy.num_replicas
        # Completed-epoch counter, persisted in the layout sidecar: the
        # step counter alone cannot recover it once incarnations at
        # DIFFERENT world sizes mixed their per-batch increments (async
        # advances num_replicas per global batch), and the cross-world
        # permutation fast-forward needs the true epoch count.
        self.epochs_completed = 0

        # Supervisor duties (C13): restore-or-init against checkpoint_dir.
        self.supervisor = supervisor
        if self.supervisor is None and self.config.checkpoint_dir:
            self.supervisor = Supervisor(
                is_chief=is_chief,
                checkpoint_dir=self.config.checkpoint_dir,
                keep_last_n=self.config.keep_last_n,
                io_retries=self.config.checkpoint_retries,
                io_backoff=self.config.checkpoint_retry_backoff,
                async_checkpoint=self.config.async_checkpoint,
            )
        self.start_step = 0
        if self.supervisor is not None:
            self.supervisor.attach_observability(
                self.journal, self.metrics, self.spans
            )
            src = None
            # Newest step that is not known-corrupt (manifest-verified,
            # train/resilience.py) — a truncated/flipped latest checkpoint
            # must point the restore at the previous valid one, not at an
            # opaque orbax failure.
            step = self.supervisor.newest_restorable_step()
            if step is not None:
                src = self.supervisor.saved_layout(step)
            if src is not None and not self._layout_compatible(src):
                # Cross-topology restore (round 5, mirror of LMTrainer):
                # the checkpoint was written under a different strategy
                # layout (async's stacked copies, or a different replica
                # count) — restore in ITS shapes, fold to the canonical
                # dense form, re-stage into this strategy's layout.
                raw = self.supervisor.restore_raw(
                    step, self._abstract_for_layout(src)
                )
                self.state = self.strategy.from_canonical(
                    self._canonicalize_from(raw, src)
                )
                self.start_step = step
            else:
                # verified_step: the probe above already CRC-verified this
                # step's files — skip the redundant disk re-read.
                self.state, self.start_step = (
                    self.supervisor.prepare_or_restore(
                        self.state, verified_step=step
                    )
                )
            if src is not None:
                self._adopt_batch_policy(src)
            self._restore_src = src
            self.epochs_completed = self._epochs_from_restore(src)

        # Scanned-epoch fast path (config.scan_epoch): one dispatch per epoch.
        # config.scan_epoch=None resolves by backend: on an accelerator the
        # per-batch eager loop pays one host dispatch 550× per epoch for
        # microseconds of device work each (the round-1 gap between the
        # documented Trainer API and bench.py's scanned path), so non-CPU
        # backends default to the scanned path.
        self._scanned_fn = None
        self._indexed_fn = None
        self._scan_rng = None
        self._stage_cache: dict = {}
        scan_epoch = self.config.scan_epoch
        has_indexed = hasattr(self.strategy, "make_indexed_scanned_train_fn")
        if scan_epoch is None:
            scan_epoch = (
                jax.default_backend() != "cpu"
                and (has_indexed or hasattr(self.strategy, "make_scanned_train_fn"))
                and not (self.config.per_worker_epoch and not has_indexed)
                and not getattr(self.strategy, "explicit", False)
            )
        if scan_epoch:
            if not (has_indexed or hasattr(self.strategy, "make_scanned_train_fn")):
                raise ValueError(
                    f"scan_epoch unsupported for {type(self.strategy).__name__}"
                )
            if self.config.per_worker_epoch and not has_indexed:
                # The reference's epoch convention (each worker passes over
                # the full dataset, reference tfdist_between.py:87) needs the
                # indexed scan's wrap-around index stream.
                raise ValueError(
                    "per_worker_epoch scanning requires an indexed scan path"
                )
            # Indexed variant when available: train arrays stay device-
            # resident across epochs; only [steps, batch] int32 indices are
            # uploaded per epoch (train/scan.py).
            if hasattr(self.strategy, "make_indexed_scanned_train_fn"):
                self._indexed_fn = self.strategy.make_indexed_scanned_train_fn(
                    self.model, self.loss_fn, self.optimizer
                )
            else:
                self._scanned_fn = self.strategy.make_scanned_train_fn(
                    self.model, self.loss_fn, self.optimizer
                )
            import numpy as _np

            self._scan_rng = _np.random.default_rng(self.config.seed)
            if (
                self.start_step
                and getattr(self, "_world_changed", False)
                and not self.config.per_worker_epoch
            ):
                self._fast_forward_permutations(self._restore_src or {})

        self.last_cost: jax.Array | None = None
        self._epoch_costs = None  # per-step costs of the last scanned epoch
        self.history: list[dict] = []
        self._graph_written = False
        self._compiled_run_fns: dict = {}

        if self.config.log_placement and self.is_chief:
            from distributed_tensorflow_tpu.utils import placement

            placement.describe(self.state.params, print_fn=self.print_fn)

    # -- cross-topology restore (round 5; LMTrainer carries the LM-mode
    # analog — see its _state_{to,from}_canonical) ------------------------

    def _layout_compatible(self, src: dict) -> bool:
        """True when the saved state's SHAPES match this strategy's (the
        ordinary bitwise prepare_or_restore applies). All sync-family
        strategies share the canonical dense shapes; async matches only
        async at the same replica count. Compared on the sidecar's SHAPE
        keys only (supervisor.layout_shape): round-8 policy keys
        (world/global_batch) ride the same sidecar but must not force a
        same-layout resume onto the cross-topology path."""
        from distributed_tensorflow_tpu.train.supervisor import layout_shape

        mine = self.strategy.layout_meta()
        if mine["mode"] != "async":
            return src.get("mode") != "async"
        return layout_shape(src) == layout_shape(mine)

    def _layout_meta(self) -> dict:
        """The checkpoint layout sidecar: the strategy's shape topology
        plus the round-8 restore policy — the world size and effective
        global batch this run trained with, which a resized gang's
        restore preserves (_adopt_batch_policy)."""
        meta = dict(self.strategy.layout_meta())
        meta["world"] = int(self.strategy.num_replicas)
        meta["global_batch"] = int(self.global_batch)
        meta["epochs"] = int(self.epochs_completed)
        return meta

    def _epochs_from_restore(self, src: dict | None) -> int:
        """Completed epochs at the restored step. The round-8 sidecar
        records it exactly; older sidecars fall back to deriving it from
        the step counter — correct for a single-world history, but a
        counter spanning incarnations at different ASYNC replica counts
        mixes increments, which is precisely why the sidecar now carries
        the count."""
        if not self.start_step:
            return 0
        if src is not None and src.get("epochs") is not None:
            return int(src["epochs"])
        spe = self.datasets.train.num_examples // max(1, self.global_batch)
        incr = 1
        if src is not None and src.get("mode") == "async":
            incr = int(src.get("replicas", src.get("world", 1)))
        return self.start_step // max(1, spe * incr)

    def _adopt_batch_policy(self, src: dict) -> None:
        """Global-batch policy across an elastic resize (round 8,
        docs/resilience.md): the checkpoint records the run's effective
        global batch; a restore onto a DIFFERENT world size keeps it —
        same steps/epoch, same effective batch, same optimization
        trajectory — by growing each surviving replica's shard, rather
        than silently shrinking the global batch with the gang (which
        would change what the remaining epochs optimize). Asserted
        shardable; the reference's per-worker epoch convention ties batch
        to worker count by definition, so it refuses a world change
        loudly instead."""
        saved = src.get("global_batch")
        saved_world = src.get("world")
        self._world_changed = (
            saved_world is not None
            and int(saved_world) != self.strategy.num_replicas
        )
        if saved is None or int(saved) == self.global_batch:
            return
        saved = int(saved)
        n = self.strategy.num_replicas
        if self.config.per_worker_epoch:
            raise ValueError(
                f"checkpoint was written with global_batch={saved} "
                f"(world={saved_world}) but per_worker_epoch ties the "
                f"effective batch to the worker count (now {n}); the "
                "reference convention cannot preserve the global batch "
                "across a resize — resume with per_worker_epoch=False or "
                "restore onto the original world size"
            )
        if saved % n:
            raise ValueError(
                f"checkpoint global_batch={saved} does not shard over "
                f"{n} replicas; resume on a world size dividing it (or "
                "accept a new trajectory by clearing the sidecar)"
            )
        if self.is_chief:
            # Structured, greppable — the trainer-side half of the gang's
            # Resize: line (rendered from the journal event).
            lifecycle_event(
                "restore",
                print_fn=self.print_fn,
                journal=self.journal,
                global_batch=saved,
                from_world=saved_world,
                world=n,
                config_batch=self.config.batch_size,
                config_global=self.global_batch,
                per_replica=saved // n,
            )
        self.global_batch = saved

    def _fast_forward_permutations(self, src: dict) -> None:
        """Replay the host permutation stream up to the restored epoch so
        a resumed-after-resize run draws the batches the uninterrupted
        run would have (the classifier analog of LMTrainer's
        next_indices fast-forward; with the global batch preserved,
        steps/epoch — and therefore the step→epoch mapping — is
        world-invariant). Only runs on a cross-world restore: same-world
        resumes keep their round-5 pinned behavior unchanged. The epoch
        count comes from the sidecar (``_epochs_from_restore``) — the
        step counter alone cannot recover it across mixed-world async
        histories."""
        train = self.datasets.train
        spe = train.num_examples // self.global_batch
        need = spe * self.global_batch
        draws_per_epoch = max(1, -(-need // train.num_examples))
        for _ in range(self.epochs_completed * draws_per_epoch):
            self._scan_rng.permutation(train.num_examples)

    def _abstract_for_layout(self, src: dict):
        """ShapeDtypeStructs of a checkpoint written under layout ``src``
        (this model + optimizer)."""
        import jax.numpy as jnp

        from distributed_tensorflow_tpu.parallel.strategy import TrainState

        params = jax.eval_shape(lambda: self.model.init(self.config.seed))
        opt = jax.eval_shape(self.optimizer.init, params)
        if src.get("mode") == "async":
            n = int(src["replicas"])
            stack = lambda t: jax.tree.map(  # noqa: E731
                lambda s: jax.ShapeDtypeStruct((n,) + s.shape, s.dtype), t
            )
            return TrainState(
                stack(params), stack(opt), jax.ShapeDtypeStruct((n,), jnp.int32)
            )
        return TrainState(params, opt, jax.ShapeDtypeStruct((), jnp.int32))

    def _canonicalize_from(self, state, src: dict):
        """Source-layout state → the canonical dense form (async merges
        its copies at the mean — its own effective_params — and sums the
        per-chip step vector; sync layouts only need the step fold).
        Integer leaves (e.g. adam's int32 count) take replica 0's value
        instead of mean-then-cast — the float mean is only exact below
        2^24 (parallel/strategy.py::merge_replica_leaf)."""
        import jax.numpy as jnp

        from distributed_tensorflow_tpu.parallel.strategy import (
            TrainState,
            merge_replica_leaf,
        )

        step = jnp.asarray(jnp.sum(state.step), jnp.int32)
        if src.get("mode") == "async":
            merge = lambda t: jax.tree.map(merge_replica_leaf, t)  # noqa: E731
            return TrainState(merge(state.params), merge(state.opt_state), step)
        return TrainState(state.params, state.opt_state, step)

    # -- pieces -----------------------------------------------------------

    def _stage_cached(self, name: str, arr) -> jax.Array:
        """Device-resident staging cache: full train/test arrays are placed
        once (replicated on the mesh when the strategy defines a replicated
        sharding) and reused across epochs and run_compiled calls. Round 1
        re-shipped ~170 MB from the host every epoch — a transfer that
        dwarfed the epoch's compute."""
        # The cache value keeps the host array alive and identity-checked:
        # keying by id() alone would go stale if a freed array's id were
        # reused by a different dataset.
        hit = self._stage_cache.get(name)
        if hit is None or hit[0] is not arr:
            sharding = getattr(self.strategy, "replicated_sharding", None)
            staged = self._place_replicated(arr, sharding)
            self._stage_cache[name] = hit = (arr, staged)
        return hit[1]

    @staticmethod
    def _place_replicated(a, sharding) -> jax.Array:
        """Place host data ``a`` replicated under ``sharding``. Takes the
        host array directly — an eager ``asarray`` first would commit it to
        the local default device and force an extra round trip through the
        device link before re-placement. In a multi-process mesh a plain
        device_put is not globally addressable; every process holds the
        identical full array (deterministic loaders), so assembly goes
        through make_array_from_process_local_data."""
        if sharding is None:
            return jax.numpy.asarray(a)
        if jax.process_count() > 1:
            import numpy as _np

            a = _np.asarray(a)
            return jax.make_array_from_process_local_data(sharding, a, a.shape)
        return jax.device_put(a, sharding)

    def evaluate(self) -> float:
        test = self.datasets.test
        return float(
            self.eval_fn(
                self.state,
                self._stage_cached("test_x", test.images),
                self._stage_cached("test_y", test.labels),
            )
        )

    def run_epoch(self, epoch: int, logger: StepLogger) -> None:
        self._epoch_costs = None  # eager path: guard judges last_cost only
        if self._scanned_fn is not None or self._indexed_fn is not None:
            return self._run_epoch_scanned(epoch, logger)
        cfg = self.config
        train = self.datasets.train
        # Global batch: the reference gave each of N workers a batch of 100
        # (reference tfdist_between.py:19,91), so N replicas consume N×100 —
        # unless a resize-restore adopted the checkpoint's recorded value
        # (self.global_batch, _adopt_batch_policy).
        global_batch = self.global_batch
        if cfg.per_worker_epoch:
            # Reference convention: each worker passes over the full dataset
            # per epoch; next_batch wraps across the shuffled permutations.
            batch_count = train.num_examples // cfg.batch_size
        else:
            batch_count = train.num_examples // global_batch
        summaries: list[tuple[int, jax.Array]] = []
        step_before = self.strategy.global_step(self.state)
        logger.reset_window()
        t_epoch = time.time()
        if cfg.prefetch:
            from distributed_tensorflow_tpu.data.prefetch import prefetch_batches

            batches = prefetch_batches(
                train.next_batch,
                global_batch,
                batch_count,
                self.strategy.prepare_batch,
                depth=cfg.prefetch,
            )
        else:
            batches = (
                self.strategy.prepare_batch(*train.next_batch(global_batch))
                for _ in range(batch_count)
            )
        for i, (bx, by) in enumerate(batches):
            self.state, cost = self.train_step(self.state, bx, by)
            self.last_cost = cost
            if self._exchange is not None and (i + 1) % self.strategy.avg_every == 0:
                self.state = self._exchange(self.state)
            if self.summary_writer is not None and self.is_chief:
                summaries.append((i, cost))
            # Only sync the host when a log line is due (async dispatch).
            if logger.is_due(i + 1, batch_count):
                logger.maybe_log_step(
                    step=self.strategy.global_step(self.state),
                    epoch=epoch,
                    batch=i,
                    batch_count=batch_count,
                    cost=self.strategy.cost_scalar(cost),
                )
        self._observe_step_time(
            (time.time() - t_epoch) * 1000 / max(batch_count, 1)
        )
        if self.summary_writer is not None and self.is_chief:
            incr = self._step_incr(step_before, batch_count)
            for i, cost in summaries:
                self.summary_writer.add_scalar(
                    "cost", self.strategy.cost_scalar(cost), step_before + (i + 1) * incr
                )

    def _run_epoch_scanned(self, epoch: int, logger: StepLogger) -> None:
        """One compiled dispatch for the whole epoch (train/scan.py). Update
        semantics match the eager loop exactly; log lines are emitted at the
        reference cadence afterwards from the returned per-step costs.

        Preferred path: the indexed scan — train arrays device-resident via
        ``_stage_cached``, per-epoch upload is only the [steps, batch] int32
        permutation (same host-RNG draw ``stage_epoch`` makes, so the batch
        stream is unchanged). Fallback (strategies without the indexed fn):
        stage the shuffled epoch and ship it whole."""
        cfg = self.config
        train = self.datasets.train
        global_batch = self.global_batch
        if self._indexed_fn is not None:
            import numpy as _np

            xs = self._stage_cached("train_x", train.images)
            ys = self._stage_cached("train_y", train.labels)
            if cfg.per_worker_epoch:
                # Reference convention (tfdist_between.py:87): each worker
                # runs num_examples/batch_size steps per epoch, wrapping
                # across reshuffles — i.e. the batch stream is successive
                # permutations concatenated (DataSet.next_batch tail-carry).
                steps = train.num_examples // cfg.batch_size
            else:
                steps = train.num_examples // global_batch
            need = steps * global_batch
            chunks, total = [], 0
            while total < need:
                p = self._scan_rng.permutation(train.num_examples)
                chunks.append(p)
                total += p.size
            perm = _np.concatenate(chunks)[:need] if len(chunks) > 1 else chunks[0][:need]
            # Replicated like xs/ys: on a multi-process mesh the jitted
            # computation takes only globally-addressable inputs — and every
            # process draws the identical permutation (same seed-derived
            # _scan_rng stream), so replication is consistent.
            idxs = self._place_replicated(
                perm.reshape(steps, global_batch).astype(_np.int32),
                getattr(self.strategy, "replicated_sharding", None),
            )
            dispatch = partial(self._indexed_fn, self.state, xs, ys, idxs)
        else:
            from distributed_tensorflow_tpu.train.scan import stage_epoch

            xs_np, ys_np = stage_epoch(
                train.images, train.labels, global_batch, rng=self._scan_rng
            )
            sharding = self.strategy.stage_sharding
            xs = jax.device_put(xs_np, sharding) if sharding else jax.numpy.asarray(xs_np)
            ys = jax.device_put(ys_np, sharding) if sharding else jax.numpy.asarray(ys_np)
            dispatch = partial(self._scanned_fn, self.state, xs, ys)
        step_before = self.strategy.global_step(self.state)
        # The fetch IS the execution barrier (CLAUDE.md timing trap), and
        # the span records the honest dispatch→D2H window.
        with self.spans.dispatch(
            names.SPAN_EPOCH_SCAN, epoch=int(epoch)
        ) as sp:
            t0 = time.time()
            self.state, costs = dispatch()
            costs = sp.fetch(costs)
        elapsed = time.time() - t0
        self.last_cost = costs[-1]
        self._epoch_costs = costs  # anomaly guard sees every step's cost
        batch_count = costs.shape[0]
        avg_ms = elapsed * 1000 / batch_count  # uniform: one dispatch ran them all
        self._observe_step_time(avg_ms)
        self._emit_step_logs(
            costs,
            epoch,
            step_before,
            avg_ms,
            logger,
            step_incr=self._step_incr(step_before, batch_count),
        )

    def run_compiled(
        self,
        epochs: int | None = None,
        *,
        epoch_offset: int = 0,
        finalize: bool = True,
    ) -> dict:
        """Trace-scoped entry for :meth:`_run_compiled` (the whole-run
        fast path — full contract on the implementation just below): one
        trace id per run, reusing run()'s when chunked dispatches arrive
        inside it."""
        from distributed_tensorflow_tpu.observability import tracing

        with tracing.trace(tracing.current_trace()):
            try:
                return self._run_compiled(
                    epochs, epoch_offset=epoch_offset, finalize=finalize
                )
            finally:
                if finalize and self.supervisor is not None:
                    self.supervisor.wait_pending()

    def _run_compiled(
        self,
        epochs: int | None = None,
        *,
        epoch_offset: int = 0,
        finalize: bool = True,
    ) -> dict:
        """Whole-run fast path (train/compiled_run.py): every epoch, shuffle,
        and test eval compiled into ONE dispatch. Observable surface matches
        ``run()`` — same log lines (uniform AvgTime, as in the scanned path),
        same summaries, same return dict — with per-epoch granularity
        reconstructed post-hoc from the returned ``[epochs, steps]`` costs
        and ``[epochs]`` accuracies. The epoch shuffle runs on-device
        (distributionally equivalent to the host shuffle; see the module
        docstring of train/compiled_run.py for the exact semantics).
        ``epoch_offset`` shifts the printed/recorded epoch numbers — the
        k-epochs-per-dispatch middle tier (``config.epochs_per_dispatch``)
        calls this once per chunk."""
        cfg = self.config
        epochs = cfg.epochs if epochs is None else epochs
        if not hasattr(self.strategy, "make_compiled_run_fn"):
            raise ValueError(
                f"compiled run unsupported for {type(self.strategy).__name__}"
            )
        train, test = self.datasets.train, self.datasets.test
        global_batch = self.global_batch
        # per_worker_epoch (reference convention, tfdist_between.py:87): each
        # worker runs num_examples/batch_size steps per epoch; the compiled
        # program wraps its index stream across fresh permutations.
        steps_per_epoch = (
            train.num_examples // cfg.batch_size if cfg.per_worker_epoch else None
        )
        use_pallas = cfg.engine == "pallas"
        if use_pallas:
            # Probe once per trainer: the check issues eager dispatches
            # that warm repeated calls must not re-pay.
            # Model/optimizer/loss are fixed at __init__.
            # (A previous flat elif chain made the SECOND pallas call fall
            # through to the unknown-engine raise — the already-checked
            # case must be a no-op, not an error.)
            if not getattr(self, "_pallas_checked", False):
                self._check_pallas_engine()
                self._pallas_checked = True
        elif cfg.engine != "xla":
            raise ValueError(f"unknown engine {cfg.engine!r} (xla|pallas)")
        # Cache per (engine, epochs, batch, steps): each make_*_run_fn call
        # builds a fresh jit closure, so without the cache a repeated
        # run_compiled — resume, epoch-at-a-time, benchmark warm runs —
        # would re-trace and recompile the whole program every call.
        key = (cfg.engine, epochs, global_batch, steps_per_epoch)
        run_fn = self._compiled_run_fns.get(key)
        if run_fn is None:
            if use_pallas:
                from distributed_tensorflow_tpu.ops.pallas_mlp import (
                    make_fused_compiled_run_fn,
                )

                run_fn = make_fused_compiled_run_fn(
                    batch_size=global_batch,
                    epochs=epochs,
                    in_dim=self.model.in_dim,
                    hidden_dim=self.model.hidden_dim,
                    out_dim=self.model.out_dim,
                    learning_rate=cfg.learning_rate,
                    steps_per_epoch=steps_per_epoch,
                )
            else:
                run_fn = self.strategy.make_compiled_run_fn(
                    self.model,
                    self.loss_fn,
                    self.optimizer,
                    batch_size=global_batch,
                    epochs=epochs,
                    steps_per_epoch=steps_per_epoch,
                )
            self._compiled_run_fns[key] = run_fn
        if self.summary_writer is not None and self.is_chief and not self._graph_written:
            self.write_graph()
            self._graph_written = True
        logger = StepLogger(
            freq=cfg.log_frequency, print_fn=self.print_fn,
            journal=self.journal,
        )
        # Stage replicated (per-step batches are random gathers, and in a
        # multi-process mesh the inputs must be globally addressable), cached
        # across calls: a repeated/resumed run re-dispatches without
        # re-shipping the train+test arrays through the device link.
        stage = self._stage_cached
        step_before = self.strategy.global_step(self.state)
        # Fold the global step into the shuffle key so a resumed or repeated
        # compiled run draws fresh epoch permutations instead of replaying
        # the first run's (the eager path's host RNG advances across runs).
        shuffle_key = jax.random.fold_in(jax.random.key(cfg.seed), step_before)
        t0 = time.time()
        staged_args = (
            stage("train_x", train.images),
            stage("train_y", train.labels),
            stage("test_x", test.images),
            stage("test_y", test.labels),
            shuffle_key,
        )
        with self.spans.dispatch(
            names.SPAN_COMPILED_RUN, epochs=int(epochs), engine=cfg.engine
        ) as sp:
            if use_pallas:
                from distributed_tensorflow_tpu.ops.pallas_mlp import (
                    from_fused,
                    to_fused,
                )
                from distributed_tensorflow_tpu.parallel.strategy import (
                    TrainState,
                )

                fused, metrics = run_fn(
                    to_fused(self.state.params), *staged_args
                )
                n_steps = int(
                    metrics["costs"].shape[0] * metrics["costs"].shape[1]
                )
                self.state = TrainState(
                    from_fused(fused), self.state.opt_state,
                    self.state.step + n_steps,
                )
            else:
                self.state, metrics = run_fn(self.state, *staged_args)
            # D2H fetches double as the execution barrier (CLAUDE.md
            # timing trap) and close the honest dispatch span.
            costs = sp.fetch(metrics["costs"])
        accs = jax.device_get(metrics["accuracy"])
        elapsed = time.time() - t0
        batch_count = costs.shape[1]
        if costs.size:
            self.last_cost = costs[-1, -1]
        avg_ms = elapsed * 1000 / max(epochs * batch_count, 1)
        self._observe_step_time(avg_ms)
        # Per-batch global-step advance (num_replicas under async, 1 under
        # sync) — derived from the counter over the whole dispatch.
        incr = self._step_incr(step_before, epochs * batch_count)
        accuracy = 0.0
        for epoch in range(epochs):
            self._emit_step_logs(
                costs[epoch],
                epoch_offset + epoch,
                step_before + epoch * batch_count * incr,
                avg_ms,
                logger,
                step_incr=incr,
            )
            if self.is_chief:
                accuracy = float(accs[epoch])
                logger.log_epoch(test_accuracy=accuracy)
                step_now = step_before + (epoch + 1) * batch_count * incr
                if self.summary_writer is not None:
                    self.summary_writer.add_scalar("accuracy", accuracy, step_now)
                self.history.append(
                    {
                        "epoch": epoch_offset + epoch + 1,
                        "accuracy": accuracy,
                        "step": step_now,
                    }
                )
        self.epochs_completed += epochs
        if self.supervisor is not None:
            import numpy as _np

            self.supervisor.report_progress(self.strategy.global_step(self.state))
            if cfg.max_rollbacks and costs.size and not _np.isfinite(costs).all():
                # A single compiled dispatch cannot roll back mid-program;
                # the anomaly guard's durability half still holds — never
                # commit a poisoned state over the last good checkpoint
                # (the per-epoch run() path does the full restore+retry).
                if self.is_chief:
                    lifecycle_event(
                        "rollback_compiled",
                        print_fn=self.print_fn,
                        journal=self.journal,
                    )
            else:
                self.supervisor.save(
                    self.state,
                    self.strategy.global_step(self.state),
                    layout=self._layout_meta(),
                )
        final_cost = float(costs[-1, -1]) if costs.size else float("nan")
        if finalize and self.is_chief:
            logger.log_final(cost=final_cost)
            if self.summary_writer is not None:
                self.summary_writer.flush()
            self.metrics.flush_to(self.journal, component="trainer")
            self.journal.flush()
        return {
            "accuracy": float(accs[-1]) if accs.size else 0.0,
            "final_cost": final_cost,
            "global_step": self.strategy.global_step(self.state),
        }

    def _run_chunked(self, epochs: int) -> dict:
        """The k-epochs-per-dispatch middle tier
        (``config.epochs_per_dispatch``): the whole-run compiled program
        dispatched a chunk at a time — per-epoch logs/eval/summaries come
        from each chunk's fetched history, a checkpoint lands after every
        dispatch, and ``should_stop`` is honored at chunk boundaries. The
        lifecycle surface of ``run()`` at near-``run_compiled`` throughput
        (docs/benchmarks/tpu_single.md, the ``single-k*`` rows)."""
        import math

        from distributed_tensorflow_tpu.train.resilience import AnomalyGuard

        k = self.config.epochs_per_dispatch
        guard = AnomalyGuard.from_config(self.config)
        res = {
            "accuracy": 0.0,
            "final_cost": float("nan"),
            "global_step": self.strategy.global_step(self.state),
        }
        done = 0
        while done < epochs:
            n = min(k, epochs - done)
            last = done + n >= epochs
            step_before = self.strategy.global_step(self.state)
            res = self.run_compiled(n, epoch_offset=done, finalize=last)
            if (
                guard is not None
                and not math.isfinite(res["final_cost"])
                and res["global_step"] > step_before
            ):
                # A chunk went NaN mid-dispatch: run_compiled already
                # skipped its save; this host boundary is where the
                # restore can run — roll back and retry the chunk
                # (NaN-only here: the spike baseline needs the per-epoch
                # history the per-epoch run() path keeps). The
                # global_step guard keeps an empty dispatch's nan
                # placeholder from reading as an anomaly. The poisoned
                # chunk's epochs never landed in a checkpoint — uncount
                # them (run_compiled counted before skipping its save).
                self.epochs_completed = max(0, self.epochs_completed - n)
                self._anomaly_rollback(guard, "nan", done)
                continue
            done += n
            if self.supervisor is not None and self.supervisor.should_stop:
                if not last and self.is_chief:
                    StepLogger(
                        freq=self.config.log_frequency,
                        print_fn=self.print_fn,
                        journal=self.journal,
                    ).log_final(cost=res["final_cost"])
                    if self.summary_writer is not None:
                        self.summary_writer.flush()
                break
        return res

    def _check_pallas_engine(self) -> None:
        """engine="pallas" runs the fused whole-epoch grid kernel, which
        hard-codes the reference workload's math (MLP sigmoid/softmax, naive
        CE, plain constant-lr SGD, single device). Anything else must use
        the generic XLA engine — raise rather than silently change math."""
        from distributed_tensorflow_tpu.models.mlp import MLP

        cfg = self.config
        problems = []
        if not isinstance(self.model, MLP):
            problems.append(f"model {type(self.model).__name__} (need MLP)")
        if not isinstance(self.strategy, SingleDevice):
            problems.append(
                f"strategy {type(self.strategy).__name__} (need SingleDevice; "
                "use ops.pallas_mlp.make_fused_async_epoch_fn for DP)"
            )
        if (
            cfg.optimizer != "sgd"
            or cfg.lr_schedule not in (None, "constant")  # optim.py treats both as constant
            or cfg.warmup_steps
        ):
            problems.append("optimizer config (need plain constant-lr sgd)")
        if cfg.loss != "naive":
            problems.append("loss config (need the reference's naive CE)")
        if cfg.accumulate_steps != 1 or cfg.grad_clip_norm:
            problems.append("accumulation/clipping (unsupported in the kernel)")
        # Semantic probes on top of the config strings: optimizer=/loss_fn=
        # can be passed to Trainer directly (build_trainer always does), so
        # the actual objects must also behave as plain sgd(lr) + naive CE.
        # Two applies expose momentum/adam/schedules/accumulation (all of
        # which match plain SGD on a single first step).
        import jax.numpy as jnp

        probe = jnp.asarray([[0.5, -1.5], [2.0, 0.25]])

        def two_updates(opt):
            s = opt.init(probe)
            u1, s = opt.update(probe, s, probe)
            u2, _ = opt.update(probe * 0.5, s, probe + u1)
            return jnp.concatenate([u1, u2])

        try:
            opt_ok = bool(
                jnp.allclose(
                    two_updates(self.optimizer),
                    two_updates(optim_lib.sgd(cfg.learning_rate)),
                )
            )
        except Exception:
            opt_ok = False
        if not opt_ok:
            problems.append(
                "optimizer (need plain constant-lr sgd semantics: no "
                "momentum/adam, schedule, warmup, clipping, or accumulation)"
            )
        y_probe = jnp.asarray([[1.0, 0.0], [0.0, 1.0]])
        p_probe = jnp.asarray([[0.7, 0.3], [0.2, 0.8]])
        try:
            loss_ok = bool(
                jnp.allclose(
                    self.loss_fn(p_probe, y_probe),
                    losses_lib.cross_entropy(p_probe, y_probe),
                )
            )
        except Exception:
            loss_ok = False
        if not loss_ok:
            problems.append("loss (need the reference's naive CE)")
        if problems:
            raise ValueError(
                "engine='pallas' requires the reference workload shape; got "
                + "; ".join(problems)
            )

    def _observe_step_time(self, avg_ms: float) -> None:
        """Per-epoch average step time into the metrics registry (the
        trainer-side slice of the telemetry layer; edges span the µs
        Pallas steps through multi-second cold dispatches)."""
        from distributed_tensorflow_tpu.observability.metrics import (
            TIME_MS_EDGES,
        )

        self.metrics.histogram("step_time_ms", edges=TIME_MS_EDGES).observe(
            float(avg_ms)
        )

    def _step_incr(self, step_before: int, batch_count: int) -> int:
        """Global-step advance per batch of the epoch just run — derived
        from the counter itself (num_replicas under async, 1 under sync)."""
        return (self.strategy.global_step(self.state) - step_before) // max(
            batch_count, 1
        )

    def _emit_step_logs(
        self,
        costs,
        epoch: int,
        step_offset: int,
        avg_ms: float,
        logger: StepLogger,
        step_incr: int = 1,
    ) -> None:
        """Post-hoc reference-cadence step lines + cost scalars from a
        compiled dispatch's returned per-step costs (shared by the scanned
        and whole-run fast paths). ``step_incr`` is the global-step advance
        per batch (num_replicas under async, 1 under sync)."""
        batch_count = len(costs)
        for i in range(batch_count):
            if logger.is_due(i + 1, batch_count):
                logger.log_step_line(
                    step=step_offset + (i + 1) * step_incr,
                    epoch=epoch,
                    batch=i,
                    batch_count=batch_count,
                    cost=float(costs[i]),
                    avg_ms=avg_ms,
                )
        if self.summary_writer is not None and self.is_chief:
            for i in range(batch_count):
                self.summary_writer.add_scalar(
                    "cost", float(costs[i]), step_offset + (i + 1) * step_incr
                )

    def write_graph(self) -> None:
        """Dump the train step's jaxpr as the TensorBoard graph — the
        reference passed its TF graph to the FileWriter (reference
        tfsingle.py:69, tfdist_between.py:83-84). Traced on a zeros batch so
        the training data stream is not advanced."""
        import numpy as np

        train = self.datasets.train
        global_batch = self.global_batch
        bx, by = self.strategy.prepare_batch(
            np.zeros((global_batch,) + train.images.shape[1:], np.float32),
            np.zeros((global_batch,) + train.labels.shape[1:], np.float32),
        )
        self.summary_writer.add_graph(self.train_step, self.state, bx, by)

    # -- resilience (round 6: train/resilience.py) ------------------------

    def _anomaly_rollback(self, guard, kind: str, epoch: int) -> None:
        """Restore the newest valid checkpoint after an anomalous epoch
        (NaN/inf or spike) and leave the host data stream where it is —
        the offending epoch's draws are consumed, never replayed, so the
        retry trains on the NEXT data window (the PaLM spike protocol:
        restore + skip the offending batches). With no checkpoint yet,
        the rollback target is the deterministic seed re-init. Raises
        AnomalyError once ``max_rollbacks`` is spent — training on a
        poisoned state must be loud, never silent."""
        from distributed_tensorflow_tpu.train.resilience import AnomalyError

        detected_step = self.strategy.global_step(self.state)
        if self.supervisor is None or guard.exhausted:
            raise AnomalyError(
                f"anomalous cost (kind={kind}) at epoch {epoch} step "
                f"{detected_step} with no rollback budget left "
                f"({guard.rollbacks}/{guard.max_rollbacks} used"
                + ("" if self.supervisor else "; no supervisor") + ")"
            )
        guard.rollbacks += 1
        self.metrics.counter("rollbacks_total").inc()
        fresh = self.strategy.init_state(
            self.model, self.optimizer, self.config.seed
        )
        self.state, restored_step = self.supervisor.prepare_or_restore(fresh)
        self.last_cost = None
        # Resync the completed-epoch counter with the state we restored to
        # (a fallback restore can land more than one epoch back).
        try:
            side = self.supervisor.saved_layout(restored_step)
        except ValueError:
            side = None
        if side is not None and side.get("epochs") is not None:
            self.epochs_completed = int(side["epochs"])
        if self.is_chief:
            # Structured, greppable — same key=value shape as Preemption:.
            # One lifecycle_event fans out to stdout + journal + tfevents.
            lifecycle_event(
                "rollback",
                print_fn=self.print_fn,
                journal=self.journal,
                writer=self.summary_writer,
                scalar=("rollback", float(restored_step), detected_step),
                anomaly=kind,
                epoch=epoch,
                detected_step=detected_step,
                restored_step=restored_step,
                rollback=guard.rollbacks,
                max_rollbacks=guard.max_rollbacks,
            )

    # -- the loop ---------------------------------------------------------

    def run(self, epochs: int | None = None) -> dict:
        """Public entry: the whole run under the preemption contract —
        SIGTERM/SIGINT requests a stop, the loop exits at the next epoch
        (or dispatch-chunk) boundary with a final save, and the process
        can exit 0 (train/resilience.py)."""
        from distributed_tensorflow_tpu.observability import tracing
        from distributed_tensorflow_tpu.train.resilience import preemption_guard

        # Ambient trace (round 12): every journal event of this run —
        # steps, epochs, checkpoint saves, spans, rollbacks — carries one
        # trace id, so obs_report can separate interleaved runs sharing a
        # journal. Reuses an enclosing trace (a resumed run staying in
        # its caller's scope) instead of splitting it.
        from distributed_tensorflow_tpu.train.resilience import arm_stall_dump

        arm_stall_dump()  # $DTF_STALL_DUMP (elastic launcher) or no-op
        with tracing.trace(tracing.current_trace()), preemption_guard(
            self.supervisor,
            enabled=self.config.handle_preemption,
            print_fn=self.print_fn,
            journal=self.journal,
        ):
            try:
                return self._run(epochs)
            finally:
                # Async-checkpoint drain (round 22): run() returns only
                # once every submitted save is durable on disk — callers
                # (and tests) probe checkpoints right after.
                if self.supervisor is not None:
                    self.supervisor.wait_pending()

    def _run(self, epochs: int | None = None) -> dict:
        cfg = self.config
        if cfg.compiled_run:
            return self.run_compiled(epochs)
        epochs = cfg.epochs if epochs is None else epochs
        if cfg.epochs_per_dispatch:
            return self._run_chunked(epochs)
        if self.summary_writer is not None and self.is_chief and not self._graph_written:
            # Once per trainer: TensorBoard expects at most one graph per run,
            # and run() may be called repeatedly (resume, epoch-at-a-time).
            self.write_graph()
            self._graph_written = True
        logger = StepLogger(
            freq=cfg.log_frequency, print_fn=self.print_fn,
            journal=self.journal,
        )
        from distributed_tensorflow_tpu.train.resilience import AnomalyGuard

        guard = AnomalyGuard.from_config(cfg)
        accuracy = 0.0
        epoch, profiled = 0, False
        while epoch < epochs:
            if epoch == 0 and cfg.profile_dir and not profiled:
                from distributed_tensorflow_tpu.utils import profiler

                profiled = True
                with profiler.trace(cfg.profile_dir):
                    self.run_epoch(epoch, logger)
            else:
                self.run_epoch(epoch, logger)
            if guard is not None:
                # Judge the epoch BEFORE eval/save: an anomalous state
                # must neither reach the checkpoint directory nor count
                # as a good epoch. Every process computes the identical
                # verdict (deterministic costs), so multi-process runs
                # branch together.
                cost = self.strategy.cost_scalar(self.last_cost)
                kind = guard.classify(cost, costs=self._epoch_costs)
                if kind is not None:
                    self._anomaly_rollback(guard, kind, epoch)
                    continue  # retry this epoch index on the next window
                guard.record(cost)
            self.epochs_completed += 1  # a good epoch: the sidecar's count
            self.metrics.counter("epochs_total").inc()
            # EVERY process runs the eval — it is a global-mesh computation
            # (sharded-param strategies gather over collectives), so a
            # chief-only dispatch would hang or die once non-chief
            # processes move on (the multi-host LM smoke caught exactly
            # this in lm_trainer.py); only the chief logs and records it.
            accuracy = self.evaluate()
            if self.is_chief:
                logger.log_epoch(test_accuracy=accuracy)
                if self.summary_writer is not None:
                    self.summary_writer.add_scalar(
                        "accuracy", accuracy, self.strategy.global_step(self.state)
                    )
                self.history.append(
                    {
                        "epoch": epoch + 1,
                        "accuracy": accuracy,
                        "step": self.strategy.global_step(self.state),
                    }
                )
            if self.supervisor is not None:
                # Epoch boundary = demonstrable progress: bump the heartbeat
                # progress counter BEFORE the save (the save itself can be
                # slow; the work it persists is already done), so the
                # elastic agent's stall clock resets on real forward motion.
                self.supervisor.report_progress(
                    self.strategy.global_step(self.state)
                )
                self.supervisor.save(
                    self.state,
                    self.strategy.global_step(self.state),
                    layout=self._layout_meta(),
                )
                if self.supervisor.should_stop:
                    break
            epoch += 1
        final_cost = (
            self.strategy.cost_scalar(self.last_cost)
            if self.last_cost is not None
            else float("nan")
        )
        if self.is_chief:
            logger.log_final(cost=final_cost)
            if self.summary_writer is not None:
                self.summary_writer.flush()
            self.metrics.flush_to(self.journal, component="trainer")
            self.journal.flush()
        return {
            "accuracy": accuracy,
            "final_cost": final_cost,
            "global_step": self.strategy.global_step(self.state),
        }
