"""LM training lifecycle — the reference loop contract over token batches.

Round 2 left the GPT family training through bare step factories and a
hand-rolled loop (VERDICT round-2 missing #2); this module applies the full
reference contract (reference tfdist_between.py:86-111) to the LM family,
exactly as ``train/trainer.py`` does for the classifiers:

- epochs × ``num_train // batch_size`` steps over a
  :class:`~data.tokens.TokenDataset` (``next_batch`` semantics, C6);
- ``Step/Epoch/Batch/Cost/AvgTime`` lines at ``log_frequency`` cadence and
  a per-epoch held-out metric — **perplexity** (exp mean next-token CE),
  the LM's analog of the reference's per-epoch ``Test-Accuracy``
  (reference tfdist_between.py:101-110);
- scalar summaries (``cost`` per step, ``perplexity`` per epoch) through
  the same dependency-free tfevents writer (C15);
- Supervisor checkpointing: restore-or-init at construction, save per
  epoch, heartbeat-reactive stop (C13);
- a **scanned-epoch fast path** (default on accelerators, like the
  classifier Trainer): token data staged device-resident once, one
  ``lax.scan`` dispatch per epoch gathering batches on device from an
  uploaded [steps, batch] index permutation — drawn from the SAME
  ``next_indices`` stream as the eager loop, so the two paths see
  identical batch sequences.

Data-parallel: pass ``mesh`` — the eager path uses
``make_lm_train_step(mesh=...)`` (shard_map + pmean), the scanned path
shards each gathered batch over ``data`` via a sharding constraint and
lets GSPMD insert the gradient all-reduce; both equal the single-device
math on the global batch. Ragged corpora (datasets with ``lengths``) train
through the masked loss end to end.

**Mode matrix** (round 4 — the same selection surface the classifier
Trainer gets from ``TrainConfig``; the reference picked its mode by
picking which script to launch, reference README.md:90-121):

- ``mesh=None`` → **single** device;
- ``mesh`` + ``config.sync=True`` + ``dp_mode="replicated"`` → **dp**
  (gradient all-reduce, the reference's sync mode);
- ``mesh`` + ``config.sync=True`` + ``dp_mode="zero"`` → **zero**
  (ZeRO: params AND optimizer slots sharded over ``data`` via
  ``parallel/fsdp.fsdp_specs``, all-gather fwd/bwd + reduce-scatter
  grads — identical update semantics to dp);
- ``mesh`` + ``config.sync=False`` → **async** local-SGD
  (``models/gpt.make_lm_async_parts``: per-device parameter copies,
  exchange to the mean every ``config.async_avg_every`` steps, the
  reference's HOGWILD table emulated as in ``AsyncDataParallel``;
  held-out perplexity is evaluated at the mean of the copies, and
  ``update_scale`` defaults to N like every async API here);
- ``mesh`` + ``dp_mode="tp"`` → **tp** (Megatron tensor parallelism over
  ``tp_axis`` via ``GPTLM.partition_specs``, params AND optimizer slots
  column/row-sharded, ONE GSPMD program; composes with a ``data`` axis
  on the same mesh → dp×tp, identical math to the single-device step);
- ``mesh`` + ``dp_mode="ep"`` → **ep** (MoE models: expert-parallel
  all-to-all training over ``expert_axis`` via
  ``models/gpt.make_lm_ep_parts`` — one expert's FFN weights + slots per
  device; composes with a ``data`` axis → dp×ep; ragged corpora mask
  routing per shard);
- ``mesh`` + ``dp_mode="pp"`` → **pp** (GPipe pipeline training over
  ``stage_axis`` via ``models/gpt.make_lm_pp_parts`` — stage-owned layer
  groups + slots, backward as the tick-scan transpose; composes with a
  ``data`` axis → dp×pp; ``pp_microbatches`` microbatches);
- ``mesh`` + ``dp_mode="sp"`` → **sp** (sequence-parallel training over
  ``seq_axis`` via ``models/gpt.make_lm_sp_parts`` — L/n tokens of
  activations per device, KV on the causal ring (or Ulysses all-to-all,
  ``sp_attention=``), the EXACT global masked CE assembled from psum'd
  shard sums with the boundary target over one ppermute hop; params
  replicated; composes with a ``data`` axis → dp×sp);
- ``dp_mode="diloco"`` → **diloco** (round 14: local-SGD/DiLoCo outer
  loop, ``train/local_sgd.py`` — per-worker copies run
  ``config.sync_every`` = H inner steps each, then ONE outer
  Nesterov-momentum update from the pseudo-gradient
  Δ = θ_start − mean_w(θ_w): H× fewer all-reduce rounds per token than
  dp, the paper's async-over-sync thesis in its communication-reducing
  modern form. Gang = the ``data`` mesh axis, or — with no mesh —
  ``config.diloco_workers`` emulated workers vmapped into one
  single-device program (same math, bench/degraded-container engine).
  Outer state (θ_start anchor + momentum buffer) lives in the
  optimizer-state slot as a ``DiLoCoState`` and is world-size-invariant,
  so an elastic resize carries it across a world change).

Every mode runs the FULL lifecycle: log lines, per-epoch perplexity,
tfevents, Supervisor save/restore (async checkpoints the stacked copies;
zero/tp/ep/pp checkpoint sharded arrays — pp in the staged layout; sp
params are replicated), the scanned epoch, and run_compiled. Held-out
perplexity is defined at the model's dense forward everywhere (async
folds the copies to their mean; pp merges the staged layer groups back;
ep reads the dense forward, == the EP forward in the no-drop regime —
``drop_fraction`` is the guard; sp == dense exactly).
"""

from __future__ import annotations

import copy
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax

from distributed_tensorflow_tpu.config import TrainConfig
from distributed_tensorflow_tpu.models.gpt import GPTLM, make_lm_train_step
from distributed_tensorflow_tpu.observability import journal as obs_journal
from distributed_tensorflow_tpu.observability import names
from distributed_tensorflow_tpu.observability.metrics import MetricsRegistry
from distributed_tensorflow_tpu.observability.spans import SpanRecorder
from distributed_tensorflow_tpu.ops import optim as optim_lib
from distributed_tensorflow_tpu.parallel.strategy import TrainState
from distributed_tensorflow_tpu.train.supervisor import Supervisor
from distributed_tensorflow_tpu.utils.logging import StepLogger
from distributed_tensorflow_tpu.utils.summary import SummaryWriter, lifecycle_event


class LMTrainer:
    def __init__(
        self,
        model: GPTLM,
        datasets,
        config: TrainConfig | None = None,
        *,
        optimizer=None,
        mesh=None,
        data_axis: str = "data",
        summary_writer: SummaryWriter | None = None,
        supervisor: Supervisor | None = None,
        is_chief: bool = True,
        eval_batch: int = 256,
        print_fn=print,
        async_update_scale: float | None = None,
        tp_axis: str = "model",
        expert_axis: str = "expert",
        stage_axis: str = "stage",
        pp_microbatches: int = 4,
        seq_axis: str = "seq",
        sp_attention: str | None = None,
        tokenizer=None,
        journal=None,
        metrics: MetricsRegistry | None = None,
        delta_exchange=None,
    ):
        self.datasets = datasets
        self.config = config or TrainConfig()
        # Config-driven perf knobs (round 13): TrainConfig is the single
        # config surface (config_from_env deployments), so a remat policy
        # (True | "selective") or low-precision matmul request set there
        # lands on the model — every dp_mode routes through the model's
        # forward, which is what makes the knob reach all of them. A knob
        # the caller already set on the model itself wins on conflict
        # (TrainConfig validates its values in __post_init__). The knobs
        # land on a trainer-local SHALLOW COPY: mutating the caller's
        # instance would leak one trainer's config into every other user
        # of the same model object (a second trainer, an eval harness).
        apply_remat = self.config.remat and not model.remat
        apply_mm = self.config.matmul_dtype and model.matmul_dtype is None
        if apply_remat or apply_mm:
            model = copy.copy(model)
            if apply_remat:
                model.remat = self.config.remat
            if apply_mm:
                model.matmul_dtype = self.config.matmul_dtype
        self.model = model
        self.optimizer = optimizer or optim_lib.make(
            self.config.optimizer, self.config.learning_rate
        )
        self.mesh = mesh
        self.data_axis = data_axis
        self.summary_writer = summary_writer
        self.is_chief = is_chief
        self.eval_batch = eval_batch
        self.print_fn = print_fn
        self.async_update_scale = async_update_scale
        self.tp_axis = tp_axis
        self.expert_axis = expert_axis
        self.stage_axis = stage_axis
        self.pp_microbatches = pp_microbatches
        self.seq_axis = seq_axis
        self.sp_attention = sp_attention
        # Telemetry (round 10, observability/): journal defaults to the
        # process-wide one (no-op NullJournal unless configured); the
        # structured lines below render FROM journal events.
        self.journal = journal if journal is not None else obs_journal.get_journal()
        self.metrics = metrics if metrics is not None else MetricsRegistry()
        self.spans = SpanRecorder(journal=self.journal)
        self._ragged = datasets.train.lengths is not None
        # Stale-tolerant mailbox gang (round 17, local_sgd.DeltaExchange):
        # one member per process, outer rounds exchanged host-side with
        # staleness-weighted peer deltas. The exchange's own knobs must
        # agree with the config's (config_from_env is the single config
        # surface — a drifted pair would compress with one dtype and
        # decode with another).
        self.delta_exchange = delta_exchange
        if delta_exchange is not None:
            if delta_exchange.delta_dtype != self.config.delta_dtype:
                raise ValueError(
                    f"delta_exchange.delta_dtype="
                    f"{delta_exchange.delta_dtype!r} disagrees with "
                    f"config.delta_dtype={self.config.delta_dtype!r}"
                )
            if delta_exchange.stale_limit != self.config.stale_limit:
                raise ValueError(
                    f"delta_exchange.stale_limit="
                    f"{delta_exchange.stale_limit} disagrees with "
                    f"config.stale_limit={self.config.stale_limit}"
                )
            # The exchange's mailbox_corrupt events (round 19) ride this
            # trainer's journal unless the caller wired its own; same for
            # the corruption counter (round 21 — exporter-visible).
            if getattr(delta_exchange, "journal", None) is None:
                delta_exchange.journal = self.journal
            if getattr(delta_exchange, "metrics", None) is None:
                delta_exchange.metrics = self.metrics
        self.mode = self._resolve_mode()
        if self.mode == "tp" or (
            self.mode in ("dp", "zero") and model.attention_impl == "flash"
        ):
            # These modes run the model's forward as ONE GSPMD program,
            # and the chip's compiler cannot partition a Pallas kernel:
            # tell this trainer's copy of the model how its batch and
            # heads are laid out, so the flash call maps itself per
            # device (GPTLM._flash_attend). Under tp, whatever the
            # attention, the head axis also tells the layer's checkpoint
            # that the attention's output is a sum across chips, worth
            # keeping (GPTLM._block).
            model = self.model = copy.copy(model)
            model.attention_shard = (
                mesh, self._dp_axis(),
                tp_axis if self.mode == "tp" else None,
            )

        self.state = self._init_state(model.init(seed=self.config.seed))
        self._eager_step = None  # built lazily (scanned path may not need it)
        self._scanned_fn = None
        self._eval_chunk = None
        self._stage_cache: dict = {}

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
        self.tokenizer = tokenizer
        if (
            tokenizer is not None
            and self.supervisor is not None
            and self.supervisor.checkpoint_dir
            and self.supervisor.is_chief
            and hasattr(tokenizer, "save")
        ):
            # The vocab ships WITH the checkpoint: a restored model is
            # useless without the exact merges that produced its token ids
            # (reference analog: none — its data pipeline had no learned
            # state; this is part of the deliberate checkpoint upgrade).
            # Supervisor only creates the directory when orbax is present,
            # so make sure it exists before writing the vocab.
            os.makedirs(self.supervisor.checkpoint_dir, exist_ok=True)
            self._write_tokenizer(tokenizer)
        self.start_step = 0
        if self.supervisor is not None:
            self.supervisor.attach_observability(
                self.journal, self.metrics, self.spans
            )
            # Newest step that is not known-corrupt (manifest-verified,
            # train/resilience.py): a truncated latest checkpoint points
            # the restore at the previous valid one.
            step = self.supervisor.newest_restorable_step()
            src = (
                self.supervisor.saved_layout(step)
                if step is not None
                else None
            )
            if step is not None and src is not None and not (
                self._layout_compatible(src)
            ):
                # Cross-topology restore (round 5): the checkpoint was
                # written by a DIFFERENT mode layout (pp's staged blocks,
                # async's stacked copies, or a different stage/replica
                # count). Restore it in ITS shapes, canonicalize to the
                # dense single-device layout, then re-stage into this
                # trainer's layout — elasticity the reference's
                # Supervisor (topology-pinned re-attach) never had.
                raw = self.supervisor.restore_raw(
                    step, self._abstract_state_for(src)
                )
                restored = self._state_from_canonical(
                    self._state_to_canonical(raw, src)
                )
                if src.get("mode") == "diloco" and self.mode == "diloco":
                    # Elastic resize within the diloco family: the outer
                    # state (θ_start anchor + Nesterov momentum) carries
                    # DENSE shapes, so it survives the world change
                    # verbatim — the next outer round's pseudo-gradient
                    # is computed against the SAVED anchor over the
                    # survivor gang ("the outer update proceeds over
                    # survivors", docs/parallelism.md §local-SGD). The
                    # round-17 lever state (EF residual, in-flight
                    # delta) is world-invariant too and carries the same
                    # way — when both sides run the lever; a lever
                    # flipped across the resize keeps the target's fresh
                    # zeros (residual) / drops the saved one (the
                    # compression error it deferred is lost once, not
                    # corrupted).
                    carry = dict(
                        theta=raw.opt_state.theta,
                        momentum=raw.opt_state.momentum,
                    )
                    if self.config.delta_dtype and src.get(
                        "delta_dtype"
                    ) == self.config.delta_dtype:
                        carry["residual"] = raw.opt_state.residual
                    if self.config.delta_overlap and src.get("overlap"):
                        carry["inflight"] = raw.opt_state.inflight
                    restored = restored._replace(
                        opt_state=restored.opt_state._replace(**carry)
                    )
                self.state = self._place_state(restored)
                self.start_step = step
            else:
                # verified_step: the probe above already CRC-verified this
                # step's files — skip the redundant disk re-read.
                self.state, self.start_step = (
                    self.supervisor.prepare_or_restore(
                        self.state, verified_step=step
                    )
                )
                self.state = self._place_state(self.state)
            # Global-batch policy across an elastic resize (round 8,
            # docs/resilience.md): the LM batch_size IS the global batch,
            # so a world-size change needs no adoption — each shard just
            # grows — but the CONFIG must carry the same value, or the
            # step→data-stream mapping (and the trajectory) silently
            # changes. Asserted, not adopted: the divisibility checks in
            # _resolve_mode already ran against config.batch_size.
            if src is not None and src.get("global_batch") is not None:
                saved_gb = int(src["global_batch"])
                if saved_gb != int(self.config.batch_size):
                    raise ValueError(
                        f"checkpoint was trained with global batch "
                        f"{saved_gb} (world={src.get('world')}) but this "
                        f"config says batch_size={self.config.batch_size}"
                        "; the LM batch is GLOBAL — resume with the same "
                        "batch_size (the per-shard batch grows with the "
                        "smaller mesh) to preserve the trajectory and "
                        "data-stream position"
                    )
            # Fast-forward the host-side index stream so a resumed run
            # draws exactly the batches the uninterrupted run would (the
            # reference resumed against live PS state; the TPU-native
            # analog restores the state pytree and replays the
            # deterministic data stream up to it — proven bitwise in
            # test_lm_trainer.py::test_supervisor_resume_bitwise; the
            # draw is world-invariant because batch_size is global, so
            # the position is preserved across a resize too).
            for _ in range(self.start_step):
                datasets.train.next_indices(self.config.batch_size)

        scan_epoch = self.config.scan_epoch
        if scan_epoch is None:
            # Same backend default as the classifier Trainer: on an
            # accelerator the per-batch eager loop pays one host dispatch
            # per step; scan the epoch.
            scan_epoch = jax.default_backend() != "cpu"
        self._scan = bool(scan_epoch)
        if self.delta_exchange is not None:
            # The mailbox round is a HOST decision point every
            # sync_every steps (post + gather + apply) — it cannot ride
            # inside a scanned-epoch dispatch.
            self._scan = False

        self.last_cost = None
        self._epoch_costs = None  # per-step costs of the last scanned epoch
        self.history: list[dict] = []

    def _write_tokenizer(self, tokenizer) -> None:
        """Write ``tokenizer.json`` into checkpoint_dir — unless one is
        already there. An existing record is the vocab that produced the
        CHECKPOINT's token ids: matching merges make the write a no-op,
        mismatched merges refuse loudly instead of silently replacing the
        record the restored weights depend on (ADVICE round 5)."""
        path = os.path.join(self.supervisor.checkpoint_dir, "tokenizer.json")
        if os.path.exists(path):
            from distributed_tensorflow_tpu.data.text import BPETokenizer

            try:
                existing = BPETokenizer.load(path)
            except Exception as exc:
                raise ValueError(
                    f"checkpoint_dir already holds an unreadable {path} "
                    f"({type(exc).__name__}: {exc}); refusing to overwrite "
                    "the vocab record the checkpoint's token ids depend on"
                ) from exc
            if getattr(tokenizer, "merges", None) != existing.merges:
                raise ValueError(
                    f"tokenizer mismatch: {path} holds "
                    f"{len(existing.merges)} merges that differ from this "
                    f"tokenizer's {len(getattr(tokenizer, 'merges', []))}; "
                    "refusing to overwrite the vocab that matches the "
                    "checkpoint's token ids (use a fresh checkpoint_dir "
                    "to train with a new vocab)"
                )
            return  # identical vocab: nothing to do
        tokenizer.save(path)

    # -- modes -------------------------------------------------------------

    def _resolve_mode(self) -> str:
        cfg = self.config
        if cfg.dp_mode not in (
            "replicated", "zero", "tp", "ep", "pp", "sp", "diloco"
        ):
            raise ValueError(
                f"unknown dp_mode {cfg.dp_mode!r}; "
                "replicated|zero|tp|ep|pp|sp|diloco"
            )
        if cfg.dp_mode != "diloco" and (
            self.delta_exchange is not None
        ):
            raise ValueError(
                "delta_exchange is the diloco mailbox gang: it requires "
                f"dp_mode='diloco', got {cfg.dp_mode!r}"
            )
        if cfg.dp_mode == "diloco":
            if not cfg.sync:
                raise ValueError(
                    "dp_mode='diloco' does not compose with sync=False: "
                    "the outer loop IS the (reduced) synchronization; "
                    "use sync=False + async_avg_every for the HOGWILD "
                    "emulation instead"
                )
            if self.delta_exchange is not None:
                # Mailbox gang: one member per PROCESS — the gang is the
                # set of processes sharing the exchange directory, not a
                # mesh axis or an in-process emulation.
                if self.mesh is not None:
                    raise ValueError(
                        "delta_exchange runs one gang member per process "
                        "(the outer round is a host decision point): "
                        "pass mesh=None with diloco_workers=1"
                    )
                if cfg.diloco_workers != 1:
                    raise ValueError(
                        "delta_exchange needs diloco_workers=1 (each "
                        f"process is ONE member), got {cfg.diloco_workers}"
                    )
                if cfg.delta_overlap:
                    raise ValueError(
                        "delta_overlap does not compose with "
                        "delta_exchange: the mailbox gang never waits on "
                        "the exchange — staleness tolerance IS its "
                        "overlap"
                    )
                if cfg.epochs_per_dispatch:
                    raise ValueError(
                        "epochs_per_dispatch does not compose with "
                        "delta_exchange: the outer round is a host "
                        "decision point inside every epoch"
                    )
                return "diloco"
            if self.mesh is not None:
                if self.data_axis not in self.mesh.shape:
                    raise ValueError(
                        f"dp_mode='diloco' needs a {self.data_axis!r} "
                        f"mesh axis (the gang): {dict(self.mesh.shape)}"
                    )
                n = self.mesh.shape[self.data_axis]
            elif cfg.diloco_workers >= 1:
                n = cfg.diloco_workers
            else:
                raise ValueError(
                    "dp_mode='diloco' needs a mesh (the gang is the "
                    f"{self.data_axis!r} axis) or diloco_workers >= 1 "
                    "(the vmapped single-device gang emulation)"
                )
            if cfg.batch_size % n:
                raise ValueError(
                    f"dp_mode='diloco' shards the batch over {n} "
                    f"workers: batch_size {cfg.batch_size} must divide"
                )
            return "diloco"
        if self.mesh is None:
            return "single"
        if not cfg.sync:
            if cfg.dp_mode != "replicated":
                # Fail loudly rather than silently train full replicated
                # per-chip copies under a config that asked for a sharded
                # layout: the async copies are per-chip by construction.
                raise ValueError(
                    f"dp_mode={cfg.dp_mode!r} does not compose with "
                    "sync=False: the async copies are per-chip by "
                    "construction; pick one"
                )
            if cfg.batch_size % self.mesh.shape[self.data_axis]:
                raise ValueError(
                    f"async mode shards the batch over {self.data_axis!r}: "
                    f"batch_size {cfg.batch_size} must be divisible by the "
                    f"axis size {self.mesh.shape[self.data_axis]}"
                )
            return "async"
        if cfg.dp_mode == "tp":
            if self.tp_axis not in self.mesh.shape:
                raise ValueError(
                    f"dp_mode='tp' needs a {self.tp_axis!r} mesh axis: "
                    f"{dict(self.mesh.shape)}"
                )
            if self.model.moe_experts is not None:
                raise ValueError(
                    "dp_mode='tp' is not defined for MoE blocks; use "
                    "dp_mode='ep' (expert parallelism)"
                )
            return "tp"
        if cfg.dp_mode == "ep":
            if self.model.moe_experts is None:
                raise ValueError(
                    "dp_mode='ep' requires a MoE model (moe_experts=E)"
                )
            if self.expert_axis not in self.mesh.shape:
                raise ValueError(
                    f"dp_mode='ep' needs a {self.expert_axis!r} mesh axis: "
                    f"{dict(self.mesh.shape)}"
                )
            shards = self.mesh.shape.get(self.expert_axis, 1) * (
                self.mesh.shape.get(self.data_axis, 1)
                if self._dp_axis() is not None
                else 1
            )
            if cfg.batch_size % shards:
                raise ValueError(
                    f"dp_mode='ep' shards the batch {shards} ways: "
                    f"batch_size {cfg.batch_size} must divide"
                )
            return "ep"
        if cfg.dp_mode == "pp":
            if self.stage_axis not in self.mesh.shape:
                raise ValueError(
                    f"dp_mode='pp' needs a {self.stage_axis!r} mesh axis: "
                    f"{dict(self.mesh.shape)}"
                )
            m = self.pp_microbatches
            if cfg.batch_size % m:
                raise ValueError(
                    f"dp_mode='pp' splits the batch into {m} microbatches: "
                    f"batch_size {cfg.batch_size} must be divisible"
                )
            d = self.mesh.shape.get(self.data_axis, 1)
            if self._dp_axis() is not None and (cfg.batch_size // m) % d:
                raise ValueError(
                    f"dp×pp shards each {cfg.batch_size // m}-row "
                    f"microbatch over the {d}-way {self.data_axis!r} axis: "
                    "sizes must divide"
                )
            return "pp"
        if cfg.dp_mode == "sp":
            if self.seq_axis not in self.mesh.shape:
                raise ValueError(
                    f"dp_mode='sp' needs a {self.seq_axis!r} mesh axis: "
                    f"{dict(self.mesh.shape)}"
                )
            if self.model.moe_experts is not None:
                raise ValueError(
                    "dp_mode='sp' is not defined for MoE blocks; use "
                    "dp_mode='ep' (expert parallelism)"
                )
            s = self.mesh.shape[self.seq_axis]
            seq_len = self.datasets.train.tokens.shape[1]
            if seq_len % s:
                raise ValueError(
                    f"dp_mode='sp' shards the {seq_len}-token sequence "
                    f"over the {s}-way {self.seq_axis!r} axis: must divide"
                )
            d = self.mesh.shape.get(self.data_axis, 1)
            if self._dp_axis() is not None and cfg.batch_size % d:
                raise ValueError(
                    f"dp×sp shards the batch over the {d}-way "
                    f"{self.data_axis!r} axis: batch_size {cfg.batch_size} "
                    "must divide"
                )
            return "sp"
        if cfg.dp_mode == "zero":
            return "zero"
        return "dp"

    def _dp_axis(self) -> str | None:
        """The data axis to compose on top of tp/ep/pp — present on the
        mesh or None (pure tp / ep / pp meshes are legal)."""
        return self.data_axis if self.data_axis in self.mesh.shape else None

    def _init_state(self, params) -> TrainState:
        if self.mode == "pp":
            # Parts first (their validations), then restage the params so
            # the optimizer slots are born in the staged layout.
            from distributed_tensorflow_tpu.models.gpt import (
                make_lm_pp_parts,
                pipeline_stage_params,
            )

            specs, opt_specs, self._pp_loss = make_lm_pp_parts(
                self.model,
                self.optimizer,
                self.mesh,
                axis=self.stage_axis,
                num_microbatches=self.pp_microbatches,
                data_axis=self._dp_axis(),
            )
            params = pipeline_stage_params(
                self.model, params, self.mesh.shape[self.stage_axis]
            )
            return self._sharded_init(params, specs, opt_specs=opt_specs)
        opt_state = self.optimizer.init(params)
        if self.mode == "zero":
            from distributed_tensorflow_tpu.parallel import fsdp_specs

            pshape = jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), params
            )
            pspecs = fsdp_specs(pshape, self.mesh, axis=self.data_axis)
            return self._sharded_init(params, pspecs, opt_state=opt_state)
        if self.mode == "tp":
            return self._sharded_init(
                params,
                self.model.partition_specs(self.tp_axis),
                opt_state=opt_state,
            )
        if self.mode == "ep":
            from distributed_tensorflow_tpu.models.gpt import make_lm_ep_parts

            specs, opt_specs, self._mapped_update = make_lm_ep_parts(
                self.model,
                self.optimizer,
                self.mesh,
                self.expert_axis,
                data_axis=self._dp_axis(),
                ragged=self._ragged,
            )
            return self._sharded_init(
                params, specs, opt_specs=opt_specs, opt_state=opt_state
            )
        if self.mode == "sp":
            from distributed_tensorflow_tpu.models.gpt import make_lm_sp_parts

            self._mapped_update = make_lm_sp_parts(
                self.model,
                self.optimizer,
                self.mesh,
                self.seq_axis,
                data_axis=self._dp_axis(),
                attention=self.sp_attention,
                ragged=self._ragged,
            )
            # Params stay replicated (sp shards activations, not weights):
            # the plain TrainState below is already the right layout.
        if self.mode == "diloco":
            from distributed_tensorflow_tpu.train.local_sgd import (
                make_lm_diloco_parts,
                make_lm_diloco_vmapped,
            )

            kw = dict(
                # Mailbox gang: the in-graph exchange must never fire —
                # the boundary is a host decision point (an unreachable
                # period, the async avg_every=0 trick); the engine still
                # allocates the EF residual (it checkpoints with the
                # state), which the host round updates.
                sync_every=(
                    (1 << 30)
                    if self.delta_exchange is not None
                    else self.config.sync_every
                ),
                outer_lr=self.config.outer_lr,
                outer_momentum=self.config.outer_momentum,
                ragged=self._ragged,
                delta_dtype=self.config.delta_dtype,
                overlap=self.config.delta_overlap,
            )
            if self.mesh is not None:
                init_state, self._diloco_mapped = make_lm_diloco_parts(
                    self.model,
                    self.optimizer,
                    self.mesh,
                    axis=self.data_axis,
                    **kw,
                )
            else:
                init_state, self._diloco_mapped = make_lm_diloco_vmapped(
                    self.model,
                    self.optimizer,
                    self.config.diloco_workers,
                    **kw,
                )
            stacked_p, dstate, count = init_state(params, opt_state)
            return TrainState(stacked_p, dstate, count)
        if self.mode == "async":
            from distributed_tensorflow_tpu.models.gpt import (
                make_lm_async_parts,
            )

            init_state, self._async_mapped = make_lm_async_parts(
                self.model,
                self.optimizer,
                self.mesh,
                axis=self.data_axis,
                # async_avg_every=0 means "never exchange" (classifier
                # convention) — key the cond on an unreachable period.
                avg_every=self.config.async_avg_every or (1 << 30),
                update_scale=self.async_update_scale,
                ragged=self._ragged,
            )
            stacked_p, stacked_o, count = init_state(params, opt_state)
            return TrainState(stacked_p, stacked_o, count)
        return TrainState(params, opt_state, jnp.zeros((), jnp.int32))

    def _sharded_init(
        self, params, pspecs, *, opt_specs=None, opt_state=None
    ) -> TrainState:
        """Shared state construction for every GSPMD-sharded-layout mode
        (zero / tp / ep / pp): record the param + optimizer-slot shardings
        and place both pytrees under them."""
        from distributed_tensorflow_tpu.parallel import as_shardings, slot_specs

        if opt_specs is None:
            pshape = jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), params
            )
            opt_specs = slot_specs(self.optimizer, pshape, pspecs)
        self._param_shardings = as_shardings(self.mesh, pspecs)
        self._opt_shardings = as_shardings(self.mesh, opt_specs)
        if opt_state is None:
            opt_state = self.optimizer.init(params)
        return TrainState(
            jax.device_put(params, self._param_shardings),
            jax.device_put(opt_state, self._opt_shardings),
            jnp.zeros((), jnp.int32),
        )

    def _place_state(self, state: TrainState) -> TrainState:
        """Re-place a state pytree into the mode's device layout. Needed
        after Supervisor restore: orbax hands back arrays committed to the
        default device, and a committed single-device leaf conflicts with
        the mesh-placed staging arrays under jit ("incompatible devices").
        Idempotent for already-placed states."""
        if self.mesh is None:
            return state
        from jax.sharding import NamedSharding, PartitionSpec as P

        repl = NamedSharding(self.mesh, P())
        if self.mode in ("zero", "tp", "ep", "pp"):
            return TrainState(
                jax.device_put(state.params, self._param_shardings),
                jax.device_put(state.opt_state, self._opt_shardings),
                jax.device_put(state.step, repl),
            )
        if self.mode == "async":
            stacked = NamedSharding(self.mesh, P(self.data_axis))
            return TrainState(
                jax.device_put(state.params, stacked),
                jax.device_put(state.opt_state, stacked),
                jax.device_put(state.step, repl),
            )
        if self.mode == "diloco":
            # Worker copies + inner opt slots stacked over the gang; the
            # outer state (θ_start, momentum, and the round-17 EF
            # residual / in-flight delta when present) replicated — each
            # is ONE gang-level quantity, not per-worker.
            stacked = NamedSharding(self.mesh, P(self.data_axis))
            d = state.opt_state
            put_repl = lambda t: (  # noqa: E731 — None = lever off
                None if t is None else jax.device_put(t, repl)
            )
            return TrainState(
                jax.device_put(state.params, stacked),
                d._replace(
                    inner=jax.device_put(d.inner, stacked),
                    theta=jax.device_put(d.theta, repl),
                    momentum=jax.device_put(d.momentum, repl),
                    residual=put_repl(d.residual),
                    inflight=put_repl(d.inflight),
                ),
                jax.device_put(state.step, repl),
            )
        return TrainState(
            jax.device_put(state.params, repl),
            jax.device_put(state.opt_state, repl),
            jax.device_put(state.step, repl),
        )

    def _eval_params(self, params):
        """Parameters the held-out metric is computed at: async evaluates
        the mean of the per-chip copies (strategy.py convention), pp
        merges the staged layer groups back to the [num_layers, ...]
        stack (pure reshape — the dense forward then reads the same
        weights the pipeline trains), every other mode the parameters
        themselves. Works traced (the compiled run folds in-graph) and
        concrete alike. DiLoCo evaluates where async does — at the mean
        of the worker copies (== θ_start exactly on round boundaries,
        and the natural mid-round point between them)."""
        if self.mode in ("async", "diloco"):
            return jax.tree.map(lambda x: jnp.mean(x, axis=0), params)
        if self.mode == "pp":
            return params._replace(
                blocks=jax.tree.map(
                    lambda a: a.reshape((-1,) + a.shape[2:]), params.blocks
                )
            )
        return params

    # -- cross-topology checkpoint restore (round 5) -----------------------
    #
    # Every mode's state is a re-layout of ONE canonical form — the dense
    # single-device (params, opt_state, step): {single, dp, zero, tp, ep,
    # sp} share its shapes outright (only GSPMD placement differs), pp
    # stages the block stack ([L] → [S, L/S]), async stacks N per-replica
    # copies. A checkpoint therefore restores into ANY mode: restore in
    # the source layout's shapes, canonicalize (pp unstages; async merges
    # at the mean — the same parameters async evaluates at), then re-stage
    # into the target layout. Same-layout resume keeps the old bitwise
    # path (async replicas keep their individual copies). The reference's
    # Supervisor could only re-attach to the same topology (reference
    # tfdist_between.py:78,83) — this is the elasticity upgrade SURVEY §5
    # flagged as the deliberate next axis.

    # Modes whose state shapes ARE the canonical shapes.
    _DENSE_LAYOUTS = frozenset({"single", "dp", "zero", "tp", "ep", "sp"})

    def _layout_meta(self) -> dict:
        """Topology descriptor saved alongside each checkpoint — shape
        keys (mode/stages/replicas) plus the round-8 restore policy: the
        world size (device count) and the GLOBAL batch, which a resized
        gang's restore must find unchanged (the LM ``batch_size`` is
        already global — docs/resilience.md, batch policy)."""
        meta: dict = {"mode": self.mode}
        if self.mode == "pp":
            meta["stages"] = int(self.mesh.shape[self.stage_axis])
        if self.mode == "async":
            meta["replicas"] = int(self.mesh.shape[self.data_axis])
        if self.mode == "diloco":
            # replicas = the LOCAL stacked width (what the saved arrays'
            # leading axis is): the mailbox gang stacks ONE member per
            # process regardless of how many peers share the exchange.
            meta["replicas"] = (
                1
                if self.delta_exchange is not None
                else int(self._gang_size())
            )
            # POLICY key (like world/global_batch): the outer-round
            # length is a schedule knob, not a shape — layout_shape
            # ignores it, so resuming under a different H keeps the
            # bitwise same-layout path.
            meta["sync_every"] = int(self.config.sync_every)
            # Round-17 lever keys, present only when ON (lever-off metas
            # stay byte-identical to round 14). These ARE shape keys
            # (supervisor.LAYOUT_SHAPE_KEYS): the EF residual and the
            # in-flight delta are extra DiLoCoState nodes, so flipping a
            # lever between save and resume must route through the
            # cross-topology path, never the bitwise one.
            if self.config.delta_dtype:
                meta["delta_dtype"] = self.config.delta_dtype
            if self.config.delta_overlap:
                meta["overlap"] = True
        meta["world"] = int(
            1 if self.mesh is None else self.mesh.size
        )
        meta["global_batch"] = int(self.config.batch_size)
        return meta

    def _gang_size(self) -> int:
        """Workers in the data-parallel gang (1 when there is none):
        the data-axis size, or the emulated diloco gang width."""
        if self.mesh is not None and self.data_axis in self.mesh.shape:
            return int(self.mesh.shape[self.data_axis])
        if self.mode == "diloco":
            if self.delta_exchange is not None:
                return int(self.delta_exchange.world)
            return int(self.config.diloco_workers)
        return 1

    def _layout_compatible(self, src: dict) -> bool:
        """True when the saved state's SHAPES match this trainer's (the
        bitwise same-layout resume path applies). Compared on the shape
        keys only (supervisor.layout_shape): the round-8 policy keys
        (world/global_batch) ride the same sidecar but a world-size
        change alone is a pure re-shard for every dense-family mode."""
        from distributed_tensorflow_tpu.train.supervisor import layout_shape

        m = src.get("mode")
        if self.mode in self._DENSE_LAYOUTS:
            return m in self._DENSE_LAYOUTS
        return m == self.mode and layout_shape(src) == layout_shape(
            self._layout_meta()
        )

    def _map_params_like(self, fn, tree_):
        """Apply ``fn`` to every GPTLMParams node in a pytree — the
        optimizer state mirrors the parameter structure (adam's mu/nu ARE
        GPTLMParams), so one traversal re-layouts params and slots alike;
        non-params leaves (e.g. adam's count) pass through."""
        from distributed_tensorflow_tpu.models.gpt import GPTLMParams

        return jax.tree.map(
            lambda node: fn(node) if isinstance(node, GPTLMParams) else node,
            tree_,
            is_leaf=lambda x: isinstance(x, GPTLMParams),
        )

    def _abstract_state_for(self, src: dict) -> TrainState:
        """ShapeDtypeStructs of a checkpoint written under layout ``src``
        (this model + optimizer; cross-OPTIMIZER restore is out of scope —
        orbax fails loudly on a structure mismatch). Leaves are pinned to
        the default LOCAL device: eval_shape structs carry sharding=None,
        under which orbax restores each leaf with the sharding its writer
        recorded (the source topology's mesh, as in serve.py's
        canonical_lm_params) — and it must be ``local_devices`` because
        every rank of a multi-process gang restores
        (``jax.devices()[0]`` is non-addressable on rank > 0)."""
        dev = jax.sharding.SingleDeviceSharding(jax.local_devices()[0])
        return jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=dev),
            self._abstract_state_shapes(src),
        )

    def _abstract_state_shapes(self, src: dict) -> TrainState:
        params = jax.eval_shape(lambda: self.model.init(seed=0))
        if src["mode"] == "pp":
            from distributed_tensorflow_tpu.models.gpt import (
                pipeline_stage_params,
            )

            params = jax.eval_shape(
                lambda p: pipeline_stage_params(
                    self.model, p, src["stages"]
                ),
                params,
            )
        opt = jax.eval_shape(self.optimizer.init, params)
        step = jax.ShapeDtypeStruct((), jnp.int32)
        if src["mode"] in ("async", "diloco"):
            n = src["replicas"]
            stack = lambda t: jax.tree.map(  # noqa: E731
                lambda s: jax.ShapeDtypeStruct((n,) + s.shape, s.dtype), t
            )
            if src["mode"] == "diloco":
                from distributed_tensorflow_tpu.train.local_sgd import (
                    DiLoCoState,
                )

                # Outer anchor + momentum carry DENSE parameter shapes
                # regardless of the gang size (world-invariant) — and so
                # do the round-17 EF residual / in-flight delta, present
                # exactly when the saving config had the lever on (the
                # sidecar's shape keys say so).
                return TrainState(
                    stack(params),
                    DiLoCoState(
                        stack(opt),
                        params,
                        params,
                        params if src.get("delta_dtype") else None,
                        {"delta": params, "landing": params}
                        if src.get("overlap")
                        else None,
                    ),
                    step,
                )
            return TrainState(stack(params), stack(opt), step)
        return TrainState(params, opt, step)

    def _state_to_canonical(self, state: TrainState, src: dict) -> TrainState:
        """Source-layout state → dense single-device layout."""
        mode = src["mode"]
        if mode == "async":
            # Merge the replicas at the mean — exactly the parameters the
            # async mode itself evaluates at (_eval_params). Integer
            # leaves (adam count) take replica 0's value outright
            # (strategy.merge_replica_leaf): the float mean is exact only
            # below 2^24, past which mean-then-cast silently corrupts the
            # count the copies share (ADVICE round 5).
            from distributed_tensorflow_tpu.parallel.strategy import (
                merge_replica_leaf,
            )

            merge = lambda t: jax.tree.map(merge_replica_leaf, t)  # noqa: E731
            return TrainState(
                merge(state.params), merge(state.opt_state), state.step
            )
        if mode == "diloco":
            # Same merge-at-the-mean as async for the worker copies and
            # inner slots (merge_replica_leaf keeps integer leaves exact);
            # the OUTER state (θ_start, momentum) has no canonical slot —
            # the diloco→diloco resize path carries it verbatim instead
            # (__init__), every other destination starts a fresh outer
            # round from the merged parameters.
            from distributed_tensorflow_tpu.parallel.strategy import (
                merge_replica_leaf,
            )

            merge = lambda t: jax.tree.map(merge_replica_leaf, t)  # noqa: E731
            return TrainState(
                merge(state.params),
                merge(state.opt_state.inner),
                state.step,
            )
        if mode == "pp":
            unstage = lambda p: p._replace(  # noqa: E731
                blocks=jax.tree.map(
                    lambda a: a.reshape((-1,) + a.shape[2:]), p.blocks
                )
            )
            return TrainState(
                self._map_params_like(unstage, state.params),
                self._map_params_like(unstage, state.opt_state),
                state.step,
            )
        return state

    def _state_from_canonical(self, c: TrainState) -> TrainState:
        """Dense single-device layout → this trainer's layout (placement
        itself happens in _place_state)."""
        if self.mode == "pp":
            from distributed_tensorflow_tpu.models.gpt import (
                pipeline_stage_params,
            )

            stages = int(self.mesh.shape[self.stage_axis])
            stage = lambda p: pipeline_stage_params(  # noqa: E731
                self.model, p, stages
            )
            return TrainState(
                self._map_params_like(stage, c.params),
                self._map_params_like(stage, c.opt_state),
                c.step,
            )
        if self.mode == "async":
            n = int(self.mesh.shape[self.data_axis])
            bcast = lambda t: jax.tree.map(  # noqa: E731
                lambda x: jnp.broadcast_to(x[None], (n,) + x.shape), t
            )
            return TrainState(bcast(c.params), bcast(c.opt_state), c.step)
        if self.mode == "diloco":
            from distributed_tensorflow_tpu.train.local_sgd import (
                DiLoCoState,
            )

            # Mailbox gangs stack ONE member per process regardless of
            # the gang's world size.
            n = 1 if self.delta_exchange is not None else self._gang_size()
            bcast = lambda t: jax.tree.map(  # noqa: E731
                lambda x: jnp.broadcast_to(x[None], (n,) + x.shape), t
            )
            zeros = lambda: jax.tree.map(  # noqa: E731
                jnp.zeros_like, c.params
            )
            # Fresh outer round from the canonical point: anchor at the
            # restored params, zero momentum — and zero EF residual /
            # in-flight delta when this trainer's levers are on (a dense
            # source has none to carry; the diloco→diloco resize
            # overwrites all of them with the saved outer state —
            # __init__).
            return TrainState(
                bcast(c.params),
                DiLoCoState(
                    bcast(c.opt_state),
                    c.params,
                    zeros(),
                    zeros() if self.config.delta_dtype else None,
                    # Nothing in flight; every copy lands on the
                    # restored point (a copy — aliasing theta would
                    # donate the same buffer twice under the scan).
                    {"delta": zeros(), "landing": jax.tree.map(jnp.copy, c.params)}
                    if self.config.delta_overlap
                    else None,
                ),
                c.step,
            )
        return c

    # -- compiled pieces ---------------------------------------------------

    @property
    def global_step(self) -> int:
        return int(self.state.step)

    def _replicated(self, a):
        """Host array → device, replicated over the mesh when present."""
        if self.mesh is None:
            return jnp.asarray(a)
        from jax.sharding import NamedSharding, PartitionSpec as P

        return jax.device_put(np.asarray(a), NamedSharding(self.mesh, P()))

    def _stage(self, name: str, arr):
        """Device-resident staging cache (same contract as
        Trainer._stage_cached): token arrays placed once, reused across
        epochs/evals — per-epoch upload is only the int32 index block."""
        hit = self._stage_cache.get(name)
        if hit is None or hit[0] is not arr:
            self._stage_cache[name] = hit = (arr, self._replicated(arr))
        return hit[1]

    def _train_lens(self):
        """Staged train lengths — real when ragged, else a once-staged zero
        placeholder (the compiled bodies statically ignore it; staging
        avoids a per-epoch upload)."""
        train = self.datasets.train
        if self._ragged:
            return self._stage("train_lengths", train.lengths)
        if not hasattr(self, "_zero_lens"):
            self._zero_lens = np.zeros((train.num_examples,), np.int32)
        return self._stage("zero_lengths", self._zero_lens)

    def _shard_batch(self, toks):
        if self.mesh is None:
            return toks
        from jax.sharding import NamedSharding, PartitionSpec as P

        # Pure-tp / pure-pp meshes have no data axis: the batch stays
        # replicated (the sharded dimension is the model, not the batch).
        spec = P(self.data_axis) if self._dp_axis() is not None else P()
        return jax.lax.with_sharding_constraint(
            toks, NamedSharding(self.mesh, spec)
        )

    def _loss(self, params, toks, lens):
        if lens is None:
            return self.model.loss(params, toks)
        return self.model.loss(params, toks, lens)

    def _build_eager_step(self):
        """One per-batch jitted step, uniform across modes:
        ``step(params, opt_state, count, toks, lens) -> (params, opt_state,
        loss)`` (``count`` drives the async exchange cadence; the sync
        modes ignore it)."""
        if self.mode in ("async", "diloco"):
            mapped = (
                self._async_mapped
                if self.mode == "async"
                else self._diloco_mapped
            )
            ragged = self._ragged

            @jax.jit
            def astep(params, opt_state, count, toks, lens):
                return mapped(
                    params, opt_state, toks, lens if ragged else None, count
                )

            return astep
        if self.mode in ("ep", "sp"):
            mapped = self._mapped_update
            ragged = self._ragged

            @jax.jit
            def estep(params, opt_state, count, toks, lens):
                return mapped(
                    params, opt_state, toks, lens if ragged else None
                )

            return estep
        if self.mode in ("zero", "tp", "pp"):
            from distributed_tensorflow_tpu.parallel import pinned_update

            opt = self.optimizer
            loss_fn = (
                self._pp_loss if self.mode == "pp" else self.model.loss
            )
            shardings = self._param_shardings
            opt_shardings = self._opt_shardings
            shard = self._shard_batch

            @jax.jit
            def zstep(params, opt_state, count, toks, lens):
                toks = shard(toks)
                loss, grads = jax.value_and_grad(loss_fn)(
                    params, toks, lens
                )
                # Owner layout (zero: the batch-sum over 'data' lowers to
                # a reduce-scatter; tp: Megatron column/row shards; pp:
                # stage-owned layer groups) — the update stays local to
                # each chip's slice.
                with jax.named_scope(names.OPTIMIZER):
                    params, opt_state = pinned_update(
                        opt, params, opt_state, grads, shardings,
                        opt_shardings,
                    )
                return params, opt_state, loss

            return zstep
        if self._ragged:
            # make_lm_train_step has no lengths slot; build the equivalent
            # jitted step over (tokens, lengths) with the masked loss.
            model, opt = self.model, self.optimizer

            @jax.jit
            def step(params, opt_state, count, toks, lens):
                loss, grads = jax.value_and_grad(model.loss)(
                    params, toks, lens
                )
                with jax.named_scope(names.OPTIMIZER):
                    updates, opt_state = opt.update(grads, opt_state, params)
                    params = optax.apply_updates(params, updates)
                return params, opt_state, loss

            return step
        plain = make_lm_train_step(self.model, self.optimizer, mesh=self.mesh)

        def step(params, opt_state, count, toks, lens):
            return plain(params, opt_state, toks)

        return step

    def _make_step_body(self, toks_all, lens_all, with_counters=False):
        """The ONE compiled step body per mode, shared by the scanned-epoch
        and whole-run paths (a divergence here would silently break their
        proven equality): gather the batch by index from the staged
        arrays, shard it over the mesh, masked loss when ragged; the async
        body is the shard-mapped local-SGD update keyed on the carried
        step count, the zero body pins grads/params/slots to the FSDP
        layout so the carry stays sharded across the whole scan."""
        model, opt = self.model, self.optimizer
        ragged = self._ragged
        shard = self._shard_batch
        if self.mode in ("async", "diloco"):
            mapped = (
                self._async_mapped
                if self.mode == "async"
                else self._diloco_mapped
            )

            def abody(carry, idx):
                params, opt_state, step = carry
                toks = toks_all[idx]
                lens = lens_all[idx] if ragged else None
                params, opt_state, loss = mapped(
                    params, opt_state, toks, lens, step
                )
                return (params, opt_state, step + 1), loss

            return abody
        if self.mode in ("ep", "sp"):
            mapped = self._mapped_update

            def ebody(carry, idx):
                params, opt_state, step = carry
                toks = toks_all[idx]
                lens = lens_all[idx] if ragged else None
                params, opt_state, loss = mapped(
                    params, opt_state, toks, lens
                )
                return (params, opt_state, step + 1), loss

            return ebody
        pinned = self.mode in ("zero", "tp", "pp")
        loss_fn = self._pp_loss if self.mode == "pp" else model.loss
        if pinned:
            from distributed_tensorflow_tpu.parallel import pinned_update
        # A model that counts what its step did (``loss_and_counters``:
        # the rows each held expert was sent) hands the counters back
        # beside the loss; they leave the scan with the step costs.
        counted = (
            getattr(model, "loss_and_counters", None)
            if with_counters and not pinned
            else None
        )

        def body(carry, idx):
            params, opt_state, step = carry
            toks = shard(toks_all[idx])
            lens = lens_all[idx] if ragged else None
            # With counters, ``loss`` is the pair (loss, counters).
            loss, grads = jax.value_and_grad(
                counted or loss_fn, has_aux=counted is not None
            )(params, toks, lens)
            with jax.named_scope(names.OPTIMIZER):
                if pinned:
                    params, opt_state = pinned_update(
                        opt, params, opt_state, grads,
                        self._param_shardings, self._opt_shardings,
                    )
                else:
                    updates, opt_state = opt.update(grads, opt_state, params)
                    params = optax.apply_updates(params, updates)
            return (params, opt_state, step + 1), loss

        return body

    def _build_scanned_fn(self):
        def epoch(state, toks_all, lens_all, idxs):
            body = self._make_step_body(toks_all, lens_all, with_counters=True)
            carry = (state.params, state.opt_state, state.step)
            (p, o, s), losses = jax.lax.scan(body, carry, idxs)
            return TrainState(p, o, s), losses

        return jax.jit(epoch, donate_argnums=0)

    def _ce_count(self, params, toks, lens):
        """(CE · target-count, target-count) for one token block — the ONE
        eval arithmetic shared by the host-side :meth:`evaluate` chunks and
        the compiled run's in-graph eval (a divergence here would silently
        break their proven equality, same rationale as
        :meth:`_make_step_body`); masked when ragged."""
        l = toks.shape[1]
        if self._ragged:
            ce = self.model.loss(params, toks, lens)
            count = jnp.sum(jnp.maximum(lens - 1, 0))
        else:
            ce = self.model.loss(params, toks)
            count = jnp.asarray(toks.shape[0] * (l - 1), jnp.int32)
        return ce * count, count

    def _in_graph_perplexity(self, params, val_toks, val_lens):
        """Per-epoch eval inside the compiled run: chunked over
        ``eval_batch``-row blocks (trimmed to a chunk multiple), exact
        CE·count aggregation via :meth:`_ce_count`."""
        ragged = self._ragged
        n, l = val_toks.shape
        b = min(self.eval_batch, n)
        k = n // b
        vt = val_toks[: k * b].reshape(k, b, l)
        vl = val_lens[: k * b].reshape(k, b) if ragged else None

        def chunk(args):
            toks, lens = args
            return self._ce_count(params, toks, lens if ragged else None)

        sums, counts = jax.lax.map(
            chunk, (vt, vl if ragged else jnp.zeros((k, b), jnp.int32))
        )
        return jnp.exp(jnp.sum(sums) / jnp.maximum(jnp.sum(counts), 1))

    def _build_compiled_run_fn(self):
        """The LM analog of ``train/compiled_run.py``: EVERY epoch's steps
        AND its held-out perplexity eval compiled into ONE dispatch — an
        outer scan over epochs, an inner scan over that epoch's gathered
        batches (the SAME step body as the scanned path), and an in-graph
        chunked eval over the staged validation tokens. Identical update
        math to the scanned path: the [epochs, steps, batch] index block is
        drawn from the dataset's own ``next_indices`` stream (proven
        bitwise in test_lm_trainer.py)."""

        def run(state, toks_all, lens_all, idxs, val_toks, val_lens):
            step_body = self._make_step_body(toks_all, lens_all)

            def epoch_body(carry, epoch_idxs):
                carry, losses = jax.lax.scan(step_body, carry, epoch_idxs)
                ppl = self._in_graph_perplexity(
                    self._eval_params(carry[0]), val_toks, val_lens
                )
                return carry, (losses, ppl)

            carry = (state.params, state.opt_state, state.step)
            (p, o, s), (losses, ppls) = jax.lax.scan(
                epoch_body, carry, idxs
            )
            return TrainState(p, o, s), losses, ppls

        return jax.jit(run, donate_argnums=0)

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

        if self.delta_exchange is not None:
            raise ValueError(
                "run_compiled does not compose with delta_exchange: the "
                "mailbox round is a host decision point inside every "
                "epoch; use run()"
            )
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
        """Whole-run fast path: all epochs + per-epoch in-graph perplexity
        as ONE dispatch. Log lines (uniform AvgTime), summaries, and
        history match :meth:`run`; the in-graph perplexity covers the
        validation split trimmed to an ``eval_batch`` multiple (equal to
        :meth:`evaluate` whenever ``eval_batch`` divides the split; the
        final returned perplexity always comes from the exact full-split
        :meth:`evaluate`). Supervisor semantics differ BY DESIGN from
        run(): one checkpoint save after the dispatch and no mid-run
        heartbeat-reactive stop — a single compiled program cannot be
        interrupted at epoch boundaries; use run() when those matter, or
        ``config.epochs_per_dispatch`` for the middle tier (k epochs per
        dispatch with checkpoints + stop checks between dispatches —
        ``epoch_offset``/``finalize`` are its chunk plumbing)."""
        cfg = self.config
        epochs = cfg.epochs if epochs is None else epochs
        train = self.datasets.train
        val = self.datasets.validation
        steps = train.num_examples // cfg.batch_size
        logger = StepLogger(
            freq=cfg.log_frequency, print_fn=self.print_fn,
            journal=self.journal,
        )
        if epochs * steps == 0:
            # Nothing to dispatch (epochs=0, or dataset smaller than one
            # batch) — mirror run()'s no-op semantics instead of crashing
            # on an empty index stack.
            perplexity = self.evaluate("validation")  # all processes (global mesh)
            if self.is_chief:
                logger.log_final(cost=float("nan"))
            return {
                "perplexity": perplexity,
                "final_cost": float("nan"),
                "global_step": self.global_step,
            }

        # One jitted whole-run program, built once: it closes over nothing
        # shape-specific, so jax.jit's own shape-keyed cache handles varying
        # (epochs, steps) without re-tracing a rebuilt wrapper.
        if not hasattr(self, "_compiled_run_fn"):
            self._compiled_run_fn = self._build_compiled_run_fn()
        run_fn = self._compiled_run_fn
        toks = self._stage("train_tokens", train.tokens)
        lens = self._train_lens()
        if self._ragged:
            val_lens = self._stage("validation_lengths", val.lengths)
        else:
            val_lens = None
        val_toks = self._stage("validation_tokens", val.tokens)
        idxs = self._replicated(
            np.stack(
                [
                    self._epoch_indices(steps, cfg.batch_size)
                    for _ in range(epochs)
                ]
            )
        )
        step_before = self.global_step
        with self.spans.dispatch(
            names.SPAN_LM_COMPILED_RUN, epochs=int(epochs)
        ) as sp:
            t0 = time.time()
            self.state, costs, ppls = run_fn(
                self.state, toks, lens, idxs, val_toks, val_lens
            )
            # D2H fetch = execution barrier (CLAUDE.md timing trap): it
            # closes the honest dispatch span and its dtf: annotation.
            costs = sp.fetch(costs)
        ppls = jax.device_get(ppls)
        elapsed = time.time() - t0
        avg_ms = elapsed * 1000 / max(epochs * steps, 1)
        self._observe_step_time(avg_ms)
        self.last_cost = float(costs[-1, -1])
        for epoch in range(epochs):
            for i in range(steps):
                if logger.is_due(i + 1, steps):
                    logger.log_step_line(
                        step=step_before + epoch * steps + i + 1,
                        epoch=epoch_offset + epoch,
                        batch=i,
                        batch_count=steps,
                        cost=float(costs[epoch, i]),
                        avg_ms=avg_ms,
                    )
            self._emit_comm_stats(
                epoch=epoch_offset + epoch,
                steps=steps,
                count_before=step_before + epoch * steps,
            )
            if self.is_chief:
                ppl = float(ppls[epoch])
                logger.log_epoch_metric("Test-Perplexity", ppl)
                step_now = step_before + (epoch + 1) * steps
                if self.summary_writer is not None:
                    for i in range(steps):
                        self.summary_writer.add_scalar(
                            "cost",
                            float(costs[epoch, i]),
                            step_before + epoch * steps + i + 1,
                        )
                    self.summary_writer.add_scalar("perplexity", ppl, step_now)
                self.history.append(
                    {
                        "epoch": epoch_offset + epoch + 1,
                        "perplexity": ppl,
                        "step": step_now,
                    }
                )
        if self.supervisor is not None:
            self.supervisor.report_progress(self.global_step)
            if cfg.max_rollbacks and costs.size and not np.isfinite(costs).all():
                # One compiled dispatch cannot roll back mid-program; the
                # guard's durability half still holds — never commit a
                # poisoned state over the last good checkpoint (the
                # per-epoch run() path does the full restore+retry).
                if self.is_chief:
                    lifecycle_event(
                        "rollback_compiled",
                        print_fn=self.print_fn,
                        journal=self.journal,
                    )
            else:
                self.supervisor.save(
                    self.state, self.global_step, layout=self._layout_meta()
                )
        if not finalize:
            return {
                "perplexity": float(ppls[-1]),
                "final_cost": self.last_cost,
                "global_step": self.global_step,
            }
        perplexity = self.evaluate("validation")  # all processes (global mesh)
        if self.is_chief:
            logger.log_final(cost=self.last_cost)
            if self.summary_writer is not None:
                self.summary_writer.flush()
            self.metrics.flush_to(self.journal, component="lm_trainer")
            self.journal.flush()
        return {
            "perplexity": perplexity,
            "final_cost": self.last_cost,
            "global_step": self.global_step,
        }

    def _run_chunked(self, epochs: int) -> dict:
        """k-epochs-per-dispatch middle tier (``config.epochs_per_dispatch``,
        mirror of Trainer._run_chunked): the compiled whole-run program
        dispatched a chunk at a time — per-epoch logs + in-graph perplexity
        from each chunk's fetched history, checkpoint per dispatch,
        ``should_stop`` honored at chunk boundaries."""
        import math

        from distributed_tensorflow_tpu.train.resilience import AnomalyGuard

        k = self.config.epochs_per_dispatch
        guard = AnomalyGuard.from_config(self.config)
        res = {
            "perplexity": float("nan"),
            "final_cost": float("nan"),
            "global_step": self.global_step,
        }
        done = 0
        while done < epochs:
            n = min(k, epochs - done)
            last = done + n >= epochs
            step_before = self.global_step
            res = self.run_compiled(n, epoch_offset=done, finalize=last)
            if (
                guard is not None
                and not math.isfinite(res["final_cost"])
                and res["global_step"] > step_before
            ):
                # Chunk went NaN mid-dispatch (its save was skipped): roll
                # back at this host boundary and retry — the retried chunk
                # draws the NEXT next_indices window, so the offending
                # data is skipped, not replayed (NaN-only; see
                # Trainer._run_chunked). Empty dispatches (nan
                # placeholder, no step advance) are not anomalies.
                self._anomaly_rollback(guard, "nan", done)
                continue
            done += n
            if self.supervisor is not None and self.supervisor.should_stop:
                if not last:
                    res["perplexity"] = self.evaluate("validation")
                    if self.is_chief:
                        StepLogger(
                            freq=self.config.log_frequency,
                            print_fn=self.print_fn,
                            journal=self.journal,
                        ).log_final(cost=res["final_cost"])
                        if self.summary_writer is not None:
                            self.summary_writer.flush()
                break
        return res

    def _build_eval_chunk(self):
        @jax.jit
        def chunk(params, toks, lens):
            return self._ce_count(params, toks, lens)

        return chunk

    def evaluate(self, split: str = "validation") -> float:
        """Held-out perplexity = exp(total next-token CE / total targets)."""
        if self._eval_chunk is None:
            self._eval_chunk = self._build_eval_chunk()
        params = self.state.params
        if self.mode in ("async", "diloco", "pp"):
            # Fold to the eval layout ONCE per evaluate call (not per
            # chunk): async takes the mean of the stacked copies, pp
            # merges the staged layer groups — the parameters the metric
            # is defined at.
            if not hasattr(self, "_fold_fn"):
                self._fold_fn = jax.jit(self._eval_params)
            params = self._fold_fn(params)
        ds = getattr(self.datasets, split)
        toks = self._stage(f"{split}_tokens", ds.tokens)
        lens = (
            self._stage(f"{split}_lengths", ds.lengths)
            if self._ragged
            else None
        )
        total, count = 0.0, 0
        b = min(self.eval_batch, ds.num_examples)
        # Full split coverage: the tail chunk runs at its own (smaller)
        # shape — one extra compile, zero dropped examples.
        for lo in range(0, ds.num_examples, b):
            hi = min(lo + b, ds.num_examples)
            t = jax.lax.slice_in_dim(toks, lo, hi)
            ln = jax.lax.slice_in_dim(lens, lo, hi) if self._ragged else None
            s, c = self._eval_chunk(params, t, ln)
            total += float(s)
            count += int(c)
        return float(np.exp(total / max(count, 1)))

    # -- the loop ----------------------------------------------------------

    def _epoch_indices(self, steps: int, batch: int) -> np.ndarray:
        """[steps, batch] int32 drawn from the dataset's OWN index stream,
        so the scanned epoch sees exactly the batches the eager loop would
        (including tail-carry across reshuffles)."""
        train = self.datasets.train
        return np.stack(
            [train.next_indices(batch) for _ in range(steps)]
        ).astype(np.int32)

    def run_epoch(self, epoch: int, logger: StepLogger) -> None:
        cfg = self.config
        train = self.datasets.train
        steps = train.num_examples // cfg.batch_size
        summaries: list[tuple[int, float]] = []
        step_before = self.global_step
        self._epoch_costs = None  # eager path: guard judges last_cost only
        if self._scan:
            if self._scanned_fn is None:
                self._scanned_fn = self._build_scanned_fn()
            toks = self._stage("train_tokens", train.tokens)
            lens = self._train_lens()
            idxs = self._replicated(self._epoch_indices(steps, cfg.batch_size))
            with self.spans.dispatch(
                names.SPAN_LM_EPOCH_SCAN, epoch=int(epoch)
            ) as sp:
                t0 = time.time()
                self.state, costs = self._scanned_fn(
                    self.state, toks, lens, idxs
                )
                # D2H fetch = execution barrier (+ the honest dispatch span).
                costs = sp.fetch(costs)
            if isinstance(costs, tuple):
                # (step costs, the model's counters): one transfer.
                costs, counters = costs
                self._set_step_gauges(counters)
            avg_ms = (time.time() - t0) * 1000 / steps
            self._observe_step_time(avg_ms)
            self.last_cost = float(costs[-1])
            self._epoch_costs = costs  # anomaly guard sees every step's cost
            for i in range(steps):
                if logger.is_due(i + 1, steps):
                    logger.log_step_line(
                        step=step_before + i + 1,
                        epoch=epoch,
                        batch=i,
                        batch_count=steps,
                        cost=float(costs[i]),
                        avg_ms=avg_ms,
                    )
                if self.summary_writer is not None and self.is_chief:
                    summaries.append((step_before + i + 1, float(costs[i])))
        else:
            if self._eager_step is None:
                self._eager_step = self._build_eager_step()
            logger.reset_window()
            t_epoch = time.time()
            for i in range(steps):
                batch = train.next_batch(cfg.batch_size)
                toks, lens = batch if self._ragged else (batch, None)
                params, opt_state, cost = self._eager_step(
                    self.state.params,
                    self.state.opt_state,
                    self.state.step,
                    jnp.asarray(toks),
                    None if lens is None else jnp.asarray(lens),
                )
                self.state = TrainState(
                    params, opt_state, self.state.step + 1
                )
                if self.delta_exchange is not None:
                    # Host-side count: the device scalar would cost a
                    # blocking D2H fetch per inner step.
                    self._maybe_mailbox_round(step_before + i + 1)
                self.last_cost = cost
                if self.summary_writer is not None and self.is_chief:
                    summaries.append((step_before + i + 1, cost))
                if logger.is_due(i + 1, steps):
                    logger.maybe_log_step(
                        step=step_before + i + 1,
                        epoch=epoch,
                        batch=i,
                        batch_count=steps,
                        cost=float(cost),
                    )
            self.last_cost = float(self.last_cost)
            self._observe_step_time(
                (time.time() - t_epoch) * 1000 / max(steps, 1)
            )
        if self.summary_writer is not None and self.is_chief:
            for step, cost in summaries:
                self.summary_writer.add_scalar("cost", float(cost), step)
        self._emit_comm_stats(
            epoch=epoch, steps=steps, count_before=step_before
        )

    def _set_step_gauges(self, counters: dict) -> None:
        """Gauges from the counters a model's steps returned with their
        costs (leaves lead with the dispatch's steps). ``moe_expert_rows``
        [steps, expert layers, experts held]: the (token, choice) pairs
        that landed on each held expert."""
        rows = counters.get("moe_expert_rows")
        if rows is not None and rows.size:
            rows = np.asarray(rows, np.float64)
            self.metrics.gauge("moe_rows_per_step").set(
                float(rows.sum(axis=(1, 2)).mean())
            )
            self.metrics.gauge("moe_expert_rows_max").set(float(rows.max()))
            self.metrics.gauge("moe_expert_rows_mean").set(float(rows.mean()))

    def _emit_comm_stats(
        self, *, epoch: int, steps: int, count_before: int
    ) -> None:
        """Per-epoch communication accounting (round 14) — MEASURED
        counters, not claims: how many gang-level sync rounds this
        epoch's steps fired and the bytes they all-reduced (one round
        moves one dense parameter set: dp's per-step gradient all-reduce
        and diloco's per-H-steps parameter mean carry the same payload,
        so the round ratio IS the traffic ratio). Journal ``comm_stats``
        events feed ``obs_report``'s comm/compute section; the counters
        land in the metrics registry. Modes whose traffic is not a
        param-sized all-reduce per round (zero/tp/ep/pp/sp collectives)
        are out of scope."""
        if self.mode not in ("dp", "diloco") or steps <= 0:
            return
        if self.mode == "diloco":
            from distributed_tensorflow_tpu.train.local_sgd import (
                sync_rounds_between,
            )

            h = self.config.sync_every
            rounds = sync_rounds_between(
                count_before, count_before + steps, h
            )
        else:
            h = 1
            rounds = steps
        if not hasattr(self, "_dense_param_nbytes"):
            from distributed_tensorflow_tpu.train.local_sgd import (
                delta_payload_nbytes,
                params_nbytes,
            )

            shapes = jax.eval_shape(lambda: self.model.init(seed=0))
            self._dense_param_nbytes = params_nbytes(shapes)
            # What ONE round actually puts on the wire (round 17): the
            # dense payload, or its per-tensor-quantized form under
            # delta_dtype. dp always moves dense gradients.
            self._delta_payload_nbytes = delta_payload_nbytes(
                shapes,
                self.config.delta_dtype if self.mode == "diloco" else None,
            )
        nbytes = rounds * self._dense_param_nbytes
        payload = rounds * self._delta_payload_nbytes
        self.journal.emit(
            "comm_stats",
            epoch=int(epoch),
            mode=self.mode,
            steps=int(steps),
            sync_every=int(h),
            sync_rounds=int(rounds),
            allreduce_bytes=int(nbytes),
            payload_bytes=int(payload),
            delta_dtype=(
                self.config.delta_dtype if self.mode == "diloco" else None
            ),
            overlap=bool(
                self.mode == "diloco" and self.config.delta_overlap
            ),
            workers=int(self._gang_size()),
        )
        self.metrics.counter("sync_rounds_total").inc(int(rounds))
        self.metrics.counter("allreduce_bytes_total").inc(int(nbytes))
        self.metrics.counter("payload_bytes_total").inc(int(payload))

    def _maybe_mailbox_round(self, count: int) -> None:
        """Host-side outer round of the stale-tolerant mailbox gang
        (round 17; ``local_sgd.DeltaExchange``), fired on the same
        cadence as the in-graph exchange (step ``t`` fires iff ``(t+1) %
        sync_every == 0`` — ``count`` is the HOST-side post-step counter:
        fetching ``int(self.state.step)`` here would block on a device
        scalar every inner step — pure synchronization, and the end of
        any overlap between host and device). Post this member's
        (EF-compressed)
        pseudo-gradient, assemble the staleness-weighted mean from
        whatever peers have posted — NEVER waiting — and apply the outer
        update locally; ``outer_lr=None`` scales by the round's ACTUAL
        total contributor weight (the variable-gang form of the η=N
        convention — see ``DeltaExchange.weighted_delta``). A
        ``delta_exchange`` journal event records the contributors and
        their ages; the on-disk payload size is the measured wire
        cost."""
        h = self.config.sync_every
        if h < 1 or count % h:
            return
        from distributed_tensorflow_tpu.train.local_sgd import (
            DiLoCoState,
            outer_apply,
            resolve_outer_lr,
        )

        t0 = time.perf_counter()
        round_idx = count // h - 1  # rounds are 0-based
        d: DiLoCoState = self.state.opt_state
        p = jax.tree.map(lambda x: x[0], self.state.params)
        delta = jax.tree.map(lambda t, q: t - q, d.theta, p)
        leaves, treedef = jax.tree.flatten(delta)
        np_leaves = [np.asarray(jax.device_get(x)) for x in leaves]
        residual = d.residual
        if self.config.delta_dtype is not None:
            r_leaves = [
                np.asarray(jax.device_get(x))
                for x in jax.tree.leaves(residual)
            ]
            corr = [a + b for a, b in zip(np_leaves, r_leaves)]
            # post() returns the DEQUANTIZED wire values — the residual
            # must see what peers read, not what we meant to send.
            own = self.delta_exchange.post(round_idx, corr)
            residual = jax.tree.unflatten(
                treedef,
                [
                    jnp.asarray(a - b)
                    for a, b in zip(corr, own)
                ],
            )
        else:
            own = self.delta_exchange.post(round_idx, np_leaves)
        mean, total_weight, contributors = (
            self.delta_exchange.weighted_delta(round_idx, own)
        )
        mean_delta = jax.tree.unflatten(
            treedef, [jnp.asarray(x) for x in mean]
        )
        # outer_lr=None → the round's actual total contributor weight,
        # NOT the fixed world size: η=N compensates an exact 1/N mean of
        # N contributions; a member alone in the mailbox applies its own
        # delta exactly once (weighted_delta docstring).
        eta = (
            float(total_weight)
            if self.config.outer_lr is None
            else resolve_outer_lr(self.config.outer_lr, self._gang_size())
        )
        theta2, m2 = outer_apply(
            d.theta,
            mean_delta,
            d.momentum,
            outer_lr=eta,
            outer_momentum=self.config.outer_momentum,
        )
        new_p = jax.tree.map(
            lambda x: jnp.broadcast_to(x[None], (1,) + x.shape), theta2
        )
        self.state = TrainState(
            new_p,
            d._replace(theta=theta2, momentum=m2, residual=residual),
            self.state.step,
        )
        stale = [c for c in contributors if c[1] > 0]
        self.journal.emit(
            "delta_exchange",
            round=int(round_idx),
            rank=int(self.delta_exchange.rank),
            world=int(self.delta_exchange.world),
            contributors=[
                [int(r), int(age), float(w)] for r, age, w in contributors
            ],
            total_weight=float(total_weight),
            outer_lr=float(eta),
            stale_contributions=len(stale),
            delta_dtype=self.config.delta_dtype,
            payload_nbytes=self.delta_exchange.payload_nbytes(round_idx),
            # Host cost of the whole boundary (post + gather + apply) —
            # the gang bench's outer-round wall share reads THIS: the
            # mailbox never waits on a peer, so this is the entire
            # non-overlapped cost of an outer round.
            wall_ms=round((time.perf_counter() - t0) * 1000, 3),
        )
        self.metrics.counter("mailbox_rounds_total").inc()
        if stale:
            self.metrics.counter("stale_contributions_total").inc(
                len(stale)
            )

    def _observe_step_time(self, avg_ms: float) -> None:
        """Per-epoch average step time into the metrics registry (mirror
        of Trainer._observe_step_time)."""
        from distributed_tensorflow_tpu.observability.metrics import (
            TIME_MS_EDGES,
        )

        self.metrics.histogram("step_time_ms", edges=TIME_MS_EDGES).observe(
            float(avg_ms)
        )

    def _anomaly_rollback(self, guard, kind: str, epoch: int) -> None:
        """LM analog of Trainer._anomaly_rollback: restore the newest
        valid checkpoint (re-placed into this mode's device layout), keep
        the host index stream where it is — the offending epoch's
        ``next_indices`` draws are consumed, never replayed, so the retry
        trains on the next data window (the PaLM spike protocol). With no
        checkpoint yet the target is the deterministic seed re-init.
        Raises AnomalyError once ``max_rollbacks`` is spent."""
        from distributed_tensorflow_tpu.train.resilience import AnomalyError

        detected_step = self.global_step
        if self.supervisor is None or guard.exhausted:
            raise AnomalyError(
                f"anomalous cost (kind={kind}) at epoch {epoch} step "
                f"{detected_step} with no rollback budget left "
                f"({guard.rollbacks}/{guard.max_rollbacks} used"
                + ("" if self.supervisor else "; no supervisor") + ")"
            )
        guard.rollbacks += 1
        self.metrics.counter("rollbacks_total").inc()
        fresh = self._init_state(self.model.init(seed=self.config.seed))
        restored, restored_step = self.supervisor.prepare_or_restore(fresh)
        self.state = self._place_state(restored)
        self.last_cost = None
        if self.is_chief:
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

    def run(self, epochs: int | None = None) -> dict:
        """Public entry: the whole run under the preemption contract —
        SIGTERM/SIGINT requests a stop, the loop exits at the next epoch
        (or dispatch-chunk) boundary with a final save, and the process
        can exit 0 (train/resilience.py)."""
        from distributed_tensorflow_tpu.observability import tracing
        from distributed_tensorflow_tpu.train.resilience import preemption_guard

        # Ambient trace (round 12): one id across every journal event of
        # this run — see Trainer.run. Reuses an enclosing trace.
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
                # once every submitted save is durable on disk.
                if self.supervisor is not None:
                    self.supervisor.wait_pending()

    def _run(self, epochs: int | None = None) -> dict:
        cfg = self.config
        epochs = cfg.epochs if epochs is None else epochs
        if cfg.epochs_per_dispatch:
            return self._run_chunked(epochs)
        logger = StepLogger(
            freq=cfg.log_frequency, print_fn=self.print_fn,
            journal=self.journal,
        )
        from distributed_tensorflow_tpu.train.resilience import AnomalyGuard

        guard = AnomalyGuard.from_config(cfg)
        perplexity = float("nan")
        epoch = 0
        while epoch < epochs:
            self.run_epoch(epoch, logger)
            if guard is not None:
                # Judge BEFORE eval/save: an anomalous state must neither
                # reach the checkpoint directory nor count as a good
                # epoch; all processes compute the identical verdict.
                cost = (
                    float(self.last_cost)
                    if self.last_cost is not None
                    else float("nan")
                )
                kind = guard.classify(cost, costs=self._epoch_costs)
                if kind is not None:
                    self._anomaly_rollback(guard, kind, epoch)
                    continue  # retry this epoch index on the next window
                guard.record(cost)
            self.metrics.counter("epochs_total").inc()
            # EVERY process runs the eval — it is a global-mesh computation
            # (GSPMD may partition it with collectives), so a chief-only
            # dispatch would hang or die once non-chief processes move on
            # (cost a real multi-host debugging cycle); only the chief
            # logs and records it.
            perplexity = self.evaluate("validation")
            if self.is_chief:
                logger.log_epoch_metric("Test-Perplexity", perplexity)
                if self.summary_writer is not None:
                    self.summary_writer.add_scalar(
                        "perplexity", perplexity, self.global_step
                    )
                self.history.append(
                    {
                        "epoch": epoch + 1,
                        "perplexity": perplexity,
                        "step": self.global_step,
                    }
                )
            if self.supervisor is not None:
                # Epoch boundary = demonstrable progress: bump the heartbeat
                # progress counter before the (possibly slow) save so the
                # elastic agent's stall clock resets on real forward motion.
                self.supervisor.report_progress(self.global_step)
                self.supervisor.save(
                    self.state, self.global_step, layout=self._layout_meta()
                )
                if self.supervisor.should_stop:
                    break
            epoch += 1
        final_cost = (
            float(self.last_cost) if self.last_cost is not None else float("nan")
        )
        if self.is_chief:
            logger.log_final(cost=final_cost)
            if self.summary_writer is not None:
                self.summary_writer.flush()
            self.metrics.flush_to(self.journal, component="lm_trainer")
            self.journal.flush()
        return {
            "perplexity": perplexity,
            "final_cost": final_cost,
            "global_step": self.global_step,
        }
