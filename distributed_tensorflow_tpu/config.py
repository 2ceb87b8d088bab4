"""Cluster topology + hyperparameter configuration.

Reference parity (component C1, SURVEY.md §2): the reference declares its
cluster as two host:port lists in ``settings.py:3-4``::

    ps_svrs     = [...]
    worker_svrs = [...]

This module keeps that exact configuration surface — a user of the reference
can drop in their ``settings.py`` unchanged — but resolves it TPU-natively:
the ``ps`` list is accepted and ignored (parameters are GSPMD-replicated on
chips; there is no parameter-server role), and the ``worker`` list defines the
set of *processes* (hosts) in a ``jax.distributed`` coordination group, i.e.
the process axis of the device mesh.

Hyperparameters mirror the reference's module constants
(batch_size=100, lr=0.001, epochs=100 — reference tfdist_between.py:19-21)
but are overridable per-run, and carry TPU-specific extras (dtype, mesh shape).
"""

from __future__ import annotations

import dataclasses
import importlib
from typing import Any, Sequence


@dataclasses.dataclass(frozen=True)
class ClusterConfig:
    """Topology of a training job.

    ``ps_svrs`` is retained for drop-in compatibility with the reference's
    ``settings.py`` but plays no runtime role: the PS star is replaced by
    all-reduce over ICI (SURVEY.md §2a). ``worker_svrs`` host:port entries
    map 1:1 to ``jax.distributed`` processes; entry 0 is the coordinator
    (and the chief, matching the reference's ``is_chief=(task_index==0)``,
    reference tfdist_between.py:78).
    """

    worker_svrs: tuple[str, ...] = ()
    ps_svrs: tuple[str, ...] = ()  # accepted, ignored (no PS role on TPU)
    # -- failure detection (round 7: cluster-level so launch.run(cluster)
    # arms it without the caller pre-building a ProcessContext) ----------
    # UDP port of the native heartbeat detector (runtime/csrc). None
    # disables. By default the chief hosts the coordinator; when
    # heartbeat_host is set, the detector lives THERE instead (an elastic
    # agent out-of-band of the job — train/elastic.py) and every task,
    # chief included, is a plain sender to it.
    heartbeat_port: int | None = None
    heartbeat_timeout_ms: int = 10_000
    heartbeat_host: str | None = None
    # Bounded jax.distributed.initialize (cluster.bounded_initialize): a
    # restarting gang whose coordinator isn't up yet gets timeout + retry
    # with backoff instead of an indefinite hang. The per-attempt window
    # deliberately matches jax's own initialization_timeout default
    # (300 s): a slow-assembling pod that worked under the raw call keeps
    # working; tighten it for fast local gangs where 300 s per attempt is
    # an eternity.
    connect_timeout_s: int = 300
    connect_attempts: int = 3

    @property
    def num_processes(self) -> int:
        return max(1, len(self.worker_svrs))

    @property
    def coordinator_address(self) -> str | None:
        return self.worker_svrs[0] if self.worker_svrs else None

    def is_chief(self, task_index: int) -> bool:
        return task_index == 0

    @classmethod
    def from_settings_module(cls, module: Any | str = "settings") -> "ClusterConfig":
        """Load a reference-style ``settings.py`` (C1 parity)."""
        if isinstance(module, str):
            module = importlib.import_module(module)
        return cls(
            worker_svrs=tuple(getattr(module, "worker_svrs", ())),
            ps_svrs=tuple(getattr(module, "ps_svrs", ())),
        )

    @classmethod
    def from_lists(
        cls, worker_svrs: Sequence[str], ps_svrs: Sequence[str] = ()
    ) -> "ClusterConfig":
        return cls(worker_svrs=tuple(worker_svrs), ps_svrs=tuple(ps_svrs))

    def subset(self, ranks: Sequence[int]) -> "ClusterConfig":
        """The surviving sub-cluster after an elastic resize
        (train/elastic.py, round 8): new rank ``r`` is served by the host
        that held original rank ``ranks[r]``, and ``ranks[0]``'s address
        becomes the coordinator. The full ``worker_svrs`` list stays the
        roster of POTENTIAL hosts (a regrown gang selects a superset);
        everything else (heartbeat, bootstrap bounds) carries over."""
        ranks = tuple(int(r) for r in ranks)
        if not ranks:
            raise ValueError("subset needs at least one rank")
        if len(set(ranks)) != len(ranks):
            raise ValueError(f"subset ranks must be unique, got {ranks}")
        bad = [r for r in ranks if not 0 <= r < len(self.worker_svrs)]
        if bad:
            raise ValueError(
                f"subset ranks {bad} out of range for "
                f"{len(self.worker_svrs)} worker_svrs entries"
            )
        return dataclasses.replace(
            self, worker_svrs=tuple(self.worker_svrs[r] for r in ranks)
        )


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    """Hyperparameters. Defaults reproduce the reference exactly
    (reference tfsingle.py:8-10, tfdist_between.py:19-21) plus TPU knobs."""

    batch_size: int = 100
    learning_rate: float = 0.001
    epochs: int = 100
    log_frequency: int = 100  # print every N batches (reference `freq`, :81)
    seed: int = 1  # reference tf.set_random_seed(1), tfsingle.py:17

    # TPU-first knobs (no reference analog)
    compute_dtype: str = "bfloat16"  # MXU-friendly activations dtype
    param_dtype: str = "float32"
    # Model family from the registry (models/__init__.py): mlp (reference
    # parity) | cnn | lstm | transformer. The reference picked its model by
    # picking which script to run; here it is one config knob.
    model: str = "mlp"
    # Optimizer surface (ops/optim.py). Defaults reproduce the reference's
    # constant-lr SGD exactly; everything else is framework surface.
    optimizer: str = "sgd"  # sgd | momentum | adam | adamw
    lr_schedule: str | None = None  # None/constant | cosine | linear | exponential
    warmup_steps: int = 0  # linear lr ramp before the schedule
    # Average grads over N micro-steps, apply once. Note: global_step counts
    # micro-steps (one per train_step call), not applies, when N > 1.
    accumulate_steps: int = 1
    # Global-norm gradient clipping; 0 disables (reference parity — the
    # reference's naive loss has no gradient guard and can diverge).
    grad_clip_norm: float = 0.0
    # Rematerialization (jax.checkpoint on the model forward): replay
    # activations in the backward pass instead of storing them — trades
    # FLOPs for HBM activation memory. Gradients unchanged. For the LM
    # family True (and its older spelling "selective") is a checkpoint a
    # layer that keeps what costs more to replay than to hold: the flash
    # kernel's output and log-sum-exp where the kernel is engaged and,
    # under dp_mode="tp", the residual stream after the attention's
    # output was summed across the `model` axis (GPTLM.__init__ has the
    # list and this chip's numbers: +4% tokens/s on one chip, +8% on
    # four under tp, PERF.md section 6, PR 31). It reaches every
    # dp_mode through LMTrainer. A model built with
    # GPTLM(remat=jax.checkpoint_policies.nothing_saveable) holds the
    # least (one [B, L, d] a layer). The classifier path treats any
    # truthy value as the plain checkpoint (its models name no value).
    remat: bool | str = False
    # Opt-in low-precision projection matmuls for the LM family
    # (models/gpt.GPTLM(matmul_dtype=), ops/quantized.py): None | "int8"
    # | "fp8". int8 is the v5e MXU's native double-rate regime; forward
    # quantized with dynamic symmetric scales, backward straight-through
    # at full precision, loss-parity-guarded (tests/test_quantized.py).
    # The classifier path rejects it (no quantized surface there).
    matmul_dtype: str | None = None
    # "naive" = reference parity (CE over softmax probabilities, NaN-guarded,
    # reference tfsingle.py:44-45); "stable" = logits-based log-softmax CE.
    loss: str = "naive"
    logs_path: str = "./logs"  # reference logs_path, tfdist_between.py:22
    checkpoint_dir: str | None = None  # deliberate upgrade: orbax checkpointing
    # -- resilience layer (train/resilience.py; no reference analog — the
    # reference configured no saver at all, SURVEY.md §5) -----------------
    # Checkpoint retention: keep the newest N step_N checkpoints, GC the
    # rest after each save (the newest VALID one is never GC'd). None/0
    # keeps everything (the old behavior).
    keep_last_n: int | None = None
    # Bounded retry-with-backoff around checkpoint save/restore I/O.
    checkpoint_retries: int = 3
    checkpoint_retry_backoff: float = 0.25
    # Async checkpoint pipeline (round 22, train/resilience.py
    # AsyncCheckpointWriter): the save boundary pays only the device→host
    # snapshot; serialize + CRC + manifest + retention GC run on a
    # bounded background writer through the SAME write sequence, so the
    # artifacts are byte-identical to the synchronous path (test-pinned)
    # and a crash mid-async-write is indistinguishable from today's torn
    # write (newest→oldest fallback covers both). At most one write in
    # flight; a newer snapshot supersedes a queued one; trainers drain at
    # run() exit and before every restore. False = the round-6
    # synchronous path, kept as the escape hatch.
    async_checkpoint: bool = True
    # Preemption contract: run() installs a SIGTERM/SIGINT handler that
    # flips Supervisor.request_stop, so the loop exits at the next epoch/
    # dispatch boundary with a final save (TPU-pod preemption semantics).
    # Only active when a supervisor exists and run() is on the main thread.
    handle_preemption: bool = True
    # Anomaly guard (PaLM-style spike/NaN rollback): watch per-epoch cost;
    # on NaN/inf — or a spike beyond spike_threshold x the median of the
    # trailing anomaly_window good epochs — restore the last valid
    # checkpoint, keep the (already advanced) host data stream so the
    # offending window is skipped, and retry, at most max_rollbacks times
    # per run. max_rollbacks=0 disables the guard; spike_threshold=0
    # keeps only the NaN/inf check.
    max_rollbacks: int = 0
    anomaly_window: int = 8
    spike_threshold: float = 3.0
    # Elastic gang-restart budget (train/elastic.py): how many times the
    # supervising agent may kill + rendezvous + relaunch the gang after a
    # worker dies or stalls, with exponential backoff between attempts.
    # 0 (default) preserves fail-stop: the first failure ends the job.
    # Consumed OUTSIDE the trainer (the agent supervises the process): the
    # elastic driver reads it via DTF_MAX_RESTARTS (tools/launch_local's
    # --max-restarts default); this knob keeps config_from_env's surface
    # the single source of truth for config-driven deployments.
    max_restarts: int = 0
    # A worker whose heartbeat keeps arriving but whose progress counter
    # has not moved for this long is classified STALLED and recovered the
    # same way as a dead one (a rank hung in a collective beats forever —
    # silence timeouts alone never fire). 0 disables stall detection.
    # Size it above the worst-case epoch + first-compile latency.
    stall_timeout_ms: int = 0
    # Shrink-to-fit floor (round 8, train/elastic.py): when a gang member
    # dies and no replacement registers within rejoin_timeout_s, the
    # elastic agent relaunches only the survivors at the reduced world
    # size — down to this floor; below it the gang fail-stops (round 6
    # semantics). 0 (default) disables resizing entirely: round 7's
    # fixed-size gang restart. Like max_restarts, consumed OUTSIDE the
    # trainer by the elastic driver (DTF_MIN_WORKERS →
    # tools/launch_local --min-workers); kept on TrainConfig so
    # config_from_env stays the single config surface.
    min_workers: int = 0
    # How long a failed member's slot may stay vacant before the gang
    # gives up on a replacement and resizes without it (only meaningful
    # with min_workers > 0). 0 decides from one availability probe.
    rejoin_timeout_s: float = 30.0
    sync: bool = True  # sync DP (pmean all-reduce) vs async emulation
    async_avg_every: int = 0  # async mode: average params every N steps (0 = never)
    # -- local-SGD / DiLoCo outer loop (train/local_sgd.py; LM family,
    # dp_mode="diloco") — the paper's async thesis in its modern
    # communication-reducing form: each worker runs sync_every = H inner
    # steps with the inner optimizer, then the gang applies ONE outer
    # update from the pseudo-gradient Δ = θ_start − mean_w(θ_w) through
    # Nesterov momentum — H× fewer all-reduce rounds per token than sync
    # dp. The DEFAULTS are the paper-parity convention, momentum-free:
    # outer_lr=None resolves to N (the worker count), the same
    # update_scale=N sequential-apply semantics as the async modes, and
    # outer_momentum=0 keeps that step un-compounded (N× PLUS momentum
    # is a regime no reference sanctions and it measurably overshoots).
    # DiLoCo-paper settings are the explicit opt-in: sync_every>=8,
    # outer_lr≈0.7-1.0, outer_momentum=0.9 — what the convergence
    # record (docs/benchmarks/diloco.md) uses. outer_momentum=0 +
    # outer_lr=1 + sync_every=1 degenerates to the per-step parameter
    # mean (the sync-dp anchor, test-pinned).
    sync_every: int = 1
    outer_lr: float | None = None
    outer_momentum: float = 0.0
    # Mesh-free diloco gang width: with dp_mode="diloco" and NO mesh, the
    # LMTrainer runs the SAME gang as one vmapped single-device program
    # over this many emulated workers (the bench/degraded-container
    # engine — tools/diloco_bench.py; mathematically the mesh gang with
    # parallel execution replaced by vectorization). 0 (default) means
    # dp_mode="diloco" requires a mesh.
    diloco_workers: int = 0
    # -- streaming/compressed DiLoCo levers (round 17, train/local_sgd.py;
    # all default-off: the round-14 outer loop stays bitwise) ------------
    # Quantize the outer pseudo-gradient Δ = θ_start − mean_w(θ_w) before
    # it crosses the wire: None (full precision) | "int8" | "fp8" —
    # per-TENSOR symmetric scales (ops/quantized.quantize_tensor) with an
    # error-feedback residual carried in DiLoCoState, so compression
    # error is re-injected into the next round's delta instead of lost.
    # ~4× fewer comm bytes per round on top of the H× round reduction
    # (one byte per element + one f32 scale per tensor).
    delta_dtype: str | None = None
    # Streaming-DiLoCo overlap: the outer delta computed at a round
    # boundary is treated as IN FLIGHT during the next H inner steps and
    # the completed outer update applies one round late — in a real gang
    # the all-reduce has the whole next round of compute to hide behind
    # (the layer-wise partition schedule lives in
    # local_sgd.streaming_schedule). The in-flight delta rides
    # DiLoCoState (world-invariant, resize-safe like θ_start/momentum).
    delta_overlap: bool = False
    # Stale-tolerant gang (LMTrainer delta_exchange=, the host-mailbox
    # outer exchange): a member that misses a round boundary contributes
    # its delta at the next one with a staleness-discounted weight
    # (1/(1+age), local_sgd.staleness_weight) instead of stalling the
    # round; deltas older than this many rounds are dropped entirely.
    # 0 = only same-round deltas participate.
    stale_limit: int = 0
    # Sync parameter layout: "replicated" (params on every chip, gradient
    # all-reduce — the reference-parity mode) or "zero" (ZeRO-3/FSDP: params
    # and optimizer state sharded over 'data', all-gather fwd/bwd +
    # reduce-scatter grads — parallel/fsdp.py); identical update semantics.
    # The LM trainer additionally accepts "tp" (Megatron tensor parallel,
    # composes with a data axis → dp×tp), "ep" (expert parallel, MoE
    # models, → dp×ep), "pp" (GPipe pipeline, → dp×pp), and "sp"
    # (sequence parallel over the causal ring / Ulysses, → dp×sp) — see
    # train/lm_trainer.py; the classifier path rejects these.
    dp_mode: str = "replicated"
    # Compile each epoch as one lax.scan dispatch (train/scan.py): identical
    # update semantics, ~100x less host overhead. Log lines are emitted from
    # the returned per-step costs after the dispatch. Supported by the
    # single-device, sync-DP (GSPMD), and async strategies. None (default)
    # resolves by backend: True on accelerators (where per-batch dispatches
    # pay the device-link latency 550x per epoch), False on CPU — set an
    # explicit bool to override.
    scan_epoch: bool | None = None
    # Compile the WHOLE run — every epoch, on-device shuffle, and per-epoch
    # test eval — into one dispatch (train/compiled_run.py). Same observable
    # surface as the eager loop; the shuffle moves from host numpy to the
    # on-device PRNG (distributionally equivalent). Wins whenever dispatch
    # latency matters. Supported by the single-device, sync-DP (GSPMD), and
    # async strategies (the async variant compiles every chip's local
    # stream, the exchanges, and the mean-params evals into the program).
    compiled_run: bool = False
    # Whole-run engine for compiled_run. "xla" (default): the generic
    # train/compiled_run.py program, any model/optimizer/strategy. "pallas":
    # the whole-epoch Pallas grid kernel inside the epoch scan
    # (ops/pallas_mlp.py make_fused_compiled_run_fn) — bench.py's fastest
    # engine behind the Trainer API; requires the reference workload shape
    # (MLP + plain sgd + naive loss + SingleDevice) and raises otherwise.
    engine: str = "xla"
    # Middle tier between the per-epoch scanned path and the all-or-nothing
    # compiled_run (round 5): run() dispatches k epochs at a time through
    # the whole-run compiled program (in-graph per-epoch eval), prints the
    # same per-epoch lines from the fetched k-epoch history, and
    # checkpoints + honors should_stop BETWEEN dispatches — the documented
    # lifecycle API at near-compiled_run throughput, with a bounded
    # resume/stop granularity of k epochs instead of the whole run.
    # None/0 disables. Ignored when compiled_run=True (strictly coarser).
    # Picking k: per-epoch cost is t + C/k (t = whole-run compute, C = the
    # per-dispatch fixed cost — benchmark_suite's `single-k*` sweep fits
    # both; docs/benchmarks/tpu_single.md), so choose the smallest k with
    # C/(k·t) at your tolerable overhead (C is not measured on a directly
    # attached chip yet — ROADMAP S2); smaller k buys nothing but a finer
    # checkpoint/stop boundary.
    epochs_per_dispatch: int | None = None
    # Keep N device-placed batches in flight in the eager per-batch loop
    # (data/prefetch.py): batch i+1's host→device transfer overlaps step i's
    # compute. 0 disables (reference-parity synchronous feed).
    prefetch: int = 0
    profile_dir: str | None = None  # capture a jax.profiler trace of epoch 0
    # Print each parameter's sharding at startup — the TPU analog of the
    # reference's log_device_placement=True (C4, tfdist_between.py:15).
    log_placement: bool = False
    # Epoch definition. False (default): one pass over the data per epoch
    # globally (modern convention; N replicas split the 550 batches). True:
    # the reference's convention — EACH worker runs num_examples/batch_size
    # steps per epoch (reference tfdist_between.py:87), so N sync replicas
    # make 550 aggregated applies/epoch at effective batch N*100, which is
    # what makes the reference's sync accuracy equal single-device at equal
    # epochs (README.md:148-150).
    per_worker_epoch: bool = False

    def __post_init__(self):
        # Fail fast at construction: None/0 disables the middle tier; a
        # negative value would otherwise reach run() and loop forever.
        if self.epochs_per_dispatch is not None and self.epochs_per_dispatch < 0:
            raise ValueError(
                "epochs_per_dispatch must be >= 1 (or None/0 to disable), "
                f"got {self.epochs_per_dispatch}"
            )
        if self.max_rollbacks < 0:
            raise ValueError(
                f"max_rollbacks must be >= 0 (0 disables), got {self.max_rollbacks}"
            )
        if not (
            isinstance(self.remat, bool) or self.remat == "selective"
        ):
            raise ValueError(
                f"remat must be False, True, or 'selective'; got "
                f"{self.remat!r} (callable policies go directly on the "
                "model: GPTLM(remat=policy))"
            )
        if self.matmul_dtype not in (None, "int8", "fp8"):
            raise ValueError(
                f"matmul_dtype must be None, 'int8', or 'fp8'; got "
                f"{self.matmul_dtype!r}"
            )
        if self.keep_last_n is not None and self.keep_last_n < 0:
            raise ValueError(
                "keep_last_n must be >= 1 (or None/0 to keep everything), "
                f"got {self.keep_last_n}"
            )
        if self.anomaly_window < 1:
            raise ValueError(
                f"anomaly_window must be >= 1, got {self.anomaly_window}"
            )
        if self.max_restarts < 0:
            raise ValueError(
                f"max_restarts must be >= 0 (0 = fail-stop), got {self.max_restarts}"
            )
        if self.stall_timeout_ms < 0:
            raise ValueError(
                f"stall_timeout_ms must be >= 0 (0 disables), got {self.stall_timeout_ms}"
            )
        if self.min_workers < 0:
            raise ValueError(
                f"min_workers must be >= 0 (0 disables resizing), "
                f"got {self.min_workers}"
            )
        if self.rejoin_timeout_s < 0:
            raise ValueError(
                f"rejoin_timeout_s must be >= 0, got {self.rejoin_timeout_s}"
            )
        if self.sync_every < 1:
            raise ValueError(
                f"sync_every must be >= 1 (1 = exchange every step), "
                f"got {self.sync_every}"
            )
        if self.outer_lr is not None and not self.outer_lr > 0:
            raise ValueError(
                f"outer_lr must be > 0 (or None for the worker-count "
                f"default), got {self.outer_lr}"
            )
        if not 0 <= self.outer_momentum < 1:
            raise ValueError(
                f"outer_momentum must be in [0, 1), got {self.outer_momentum}"
            )
        if self.diloco_workers < 0:
            raise ValueError(
                f"diloco_workers must be >= 0 (0 = diloco needs a mesh), "
                f"got {self.diloco_workers}"
            )
        if self.delta_dtype not in (None, "int8", "fp8"):
            raise ValueError(
                f"delta_dtype must be None, 'int8', or 'fp8'; got "
                f"{self.delta_dtype!r}"
            )
        if self.stale_limit < 0:
            raise ValueError(
                f"stale_limit must be >= 0 (0 = same-round deltas only), "
                f"got {self.stale_limit}"
            )
        if (
            self.delta_dtype or self.delta_overlap or self.stale_limit
        ) and self.dp_mode != "diloco":
            # Loud-failure contract (launch.py config_from_env): a
            # scheduler exporting DTF_DELTA_DTYPE/DTF_STALE_LIMIT at a
            # non-diloco job must fail the launch, not silently train
            # full-precision/sync with the lever ignored.
            raise ValueError(
                "delta_dtype/delta_overlap/stale_limit are diloco "
                "outer-loop levers (train/local_sgd.py) and would be "
                f"silently ignored under dp_mode={self.dp_mode!r}; set "
                "dp_mode='diloco'"
            )

    def replace(self, **kw) -> "TrainConfig":
        return dataclasses.replace(self, **kw)
