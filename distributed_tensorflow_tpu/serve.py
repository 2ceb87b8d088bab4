"""Batched LM serving: compiled prefill+decode with continuous batching.

The reference's only "inference" was the in-loop eval fetch
(reference tfsingle.py:94); the classifier side of this framework got
``inference.py::Predictor`` (fixed-shape compiled prediction). This module
is the LM analog — text in, text out, from a checkpoint directory — built
from the pieces rounds 5-8 left on the table: the cross-topology canonical
restore (``step_N.layout.json`` sidecars), the ``tokenizer.json`` the
LMTrainer ships into ``checkpoint_dir``, and the unrolled-layer KV-cache
decode step (the paged one updates the stacked pool where it lies, and
every program that returns the server's state takes it donated, so the
cache is never held twice nor moved: PERF.md §6, PR 27). Three
serving-engine ideas, on one chip whose every
dispatch-and-fetch has a fixed host cost (not measured on a directly
attached chip yet — ROADMAP S2):

- **Bucketed prefill** (vLLM-style fixed shapes): prompts are padded to a
  small set of length buckets and prefilled BATCHED across the server's
  fixed request slots with ragged ``kv_lens`` masking
  (``GPTLM.prefill_slots``), so the compile count is ``len(buckets)``, not
  one per prompt length.
- **Multi-token decode chunks**: ``chunk`` decode steps — including the
  sampling — run as ONE ``lax.scan`` dispatch (``GPTLM.decode_slots`` per
  step, in-graph greedy/temperature/nucleus picks, per-slot EOS/budget
  tracking), so the host's dispatch and token fetch are paid once per
  ``chunk`` tokens instead of once per token.
- **Continuous batching** (Orca-style): a slot scheduler admits queued
  requests into freed slots at chunk boundaries — each slot is an
  independent request at its own position (``SlotKVCache`` carries per-slot
  lengths), so throughput never drains to the longest request in a static
  batch.

Parity contract (pinned in tests/test_serve.py): for every request, the
served token stream equals the in-process single-prompt
``GPTLM.greedy_decode`` / ``sample_decode(key=jax.random.key(seed))``
stream token for token — generation is batch-invariant, so a request's
output does not depend on what shared the batch with it.
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import math
import os
import time
from collections import deque
from typing import NamedTuple

import numpy as np

import jax
import jax.numpy as jnp

from distributed_tensorflow_tpu.models.gpt import GPTLM, GPTLMParams
from distributed_tensorflow_tpu.observability import journal as obs_journal
from distributed_tensorflow_tpu.observability import names, tracing
from distributed_tensorflow_tpu.observability.exporter import MetricsExporter
from distributed_tensorflow_tpu.observability.metrics import MetricsRegistry
from distributed_tensorflow_tpu.observability.spans import SpanRecorder
from distributed_tensorflow_tpu.ops import paged_attention
from distributed_tensorflow_tpu import serve_pool
from distributed_tensorflow_tpu.serve_pool import (
    BlockAllocator,
    PrefixCache,
    QueueFull,
    RequestCancelled,
    RequestShed,
    blocks_for,
    lookup_draft,
)

__all__ = [  # noqa: F822 — QueueFull/RequestCancelled/RequestShed re-exported
    "GenerationConfig", "QueueFull", "RequestCancelled", "RequestShed",
    "TextServer", "canonical_lm_params", "load_tokenizer",
]


@dataclasses.dataclass(frozen=True)
class GenerationConfig:
    """Per-request decoding knobs. ``greedy=True`` (default) reproduces
    ``GPTLM.greedy_decode``; ``greedy=False`` reproduces
    ``sample_decode(key=jax.random.key(seed), temperature=, top_p=)``
    (nucleus sampling; ``top_p=1.0`` keeps the whole distribution).
    ``eos_id`` stops a request early once emitted (the EOS token itself is
    included in the output); None generates exactly ``max_new`` tokens."""

    max_new: int = 64
    greedy: bool = True
    temperature: float = 1.0
    top_p: float = 1.0
    seed: int = 0
    eos_id: int | None = None

    def validate(self, vocab_size: int) -> None:
        if self.max_new < 1:
            raise ValueError(f"max_new must be >= 1, got {self.max_new}")
        if self.temperature <= 0:
            raise ValueError(
                f"temperature must be > 0, got {self.temperature}"
            )
        if not 0.0 < self.top_p <= 1.0:
            raise ValueError(f"top_p must be in (0, 1], got {self.top_p}")
        if self.eos_id is not None and not 0 <= self.eos_id < vocab_size:
            raise ValueError(
                f"eos_id must be in [0, {vocab_size}), got {self.eos_id}"
            )


# -- checkpoint loading (the round-5 canonical layer, params-only) ---------


def canonical_lm_params(
    model: GPTLM, checkpoint_dir: str, *, optimizer=None
) -> tuple[GPTLMParams, int]:
    """Restore the newest valid checkpoint under ``checkpoint_dir`` written
    by :class:`~train.lm_trainer.LMTrainer` in ANY mode layout, and return
    ``(dense canonical params, step)`` — the serving-side half of the
    round-5 cross-topology contract: the ``step_N.layout.json`` sidecar
    names the source layout, pipeline checkpoints unstage their
    [S, L/S, ...] block stacks back to [L, ...], async checkpoints merge
    their per-replica copies at the mean (integer leaves take replica 0 —
    ``merge_replica_leaf``), and the dense family restores as-is.

    ``optimizer`` must match the training optimizer (the checkpoint stores
    its slots; orbax fails loudly on a structure mismatch); defaults to
    the reference SGD whose slot state is empty."""
    from distributed_tensorflow_tpu.ops import optim as optim_lib
    from distributed_tensorflow_tpu.parallel.strategy import TrainState
    from distributed_tensorflow_tpu.train import supervisor as _sup

    probe = _sup.latest_checkpoint_step(checkpoint_dir)
    if probe is None:
        raise FileNotFoundError(f"no checkpoint under {checkpoint_dir}")
    if not _sup._HAVE_ORBAX:
        raise RuntimeError(
            f"checkpoint found under {checkpoint_dir} but orbax is not"
            " importable; cannot restore"
        )
    sup = _sup.Supervisor(checkpoint_dir=checkpoint_dir)
    step = sup.newest_restorable_step()
    if step is None:
        raise RuntimeError(
            f"no restorable checkpoint under {checkpoint_dir} (all steps "
            "fail manifest verification)"
        )
    optimizer = optimizer or optim_lib.sgd(0.001)
    meta = sup.saved_layout(step) or {}
    mode = meta.get("mode", "single")

    params = jax.eval_shape(lambda: model.init(seed=0))
    if mode == "pp":
        from distributed_tensorflow_tpu.models.gpt import (
            pipeline_stage_params,
        )

        params = jax.eval_shape(
            lambda p: pipeline_stage_params(model, p, meta["stages"]), params
        )
    opt = jax.eval_shape(optimizer.init, params)
    step_leaf = jax.ShapeDtypeStruct((), jnp.int32)
    if mode == "async":
        n = int(meta["replicas"])
        stack = lambda t: jax.tree.map(  # noqa: E731
            lambda s: jax.ShapeDtypeStruct((n,) + s.shape, s.dtype), t
        )
        abstract = TrainState(stack(params), stack(opt), step_leaf)
    else:
        abstract = TrainState(params, opt, step_leaf)
    # eval_shape structs carry sharding=None, and orbax then restores each
    # leaf under the sharding its WRITER recorded (another topology's
    # mesh) — pin every leaf to the default device explicitly.
    dev = jax.sharding.SingleDeviceSharding(jax.devices()[0])
    abstract = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=dev),
        abstract,
    )
    state = sup.restore_raw(step, abstract)

    if mode == "async":
        from distributed_tensorflow_tpu.parallel.strategy import (
            merge_replica_leaf,
        )

        served = jax.tree.map(merge_replica_leaf, state.params)
    elif mode == "pp":
        served = state.params._replace(
            blocks=jax.tree.map(
                lambda a: a.reshape((-1,) + a.shape[2:]), state.params.blocks
            )
        )
    else:
        served = state.params
    return served, step


def load_tokenizer(checkpoint_dir: str):
    """The vocab that produced the checkpoint's token ids:
    ``tokenizer.json`` (the record LMTrainer ships) when present, else the
    byte-level identity tokenizer (trainings that never passed one)."""
    from distributed_tensorflow_tpu.data.text import (
        BPETokenizer,
        ByteTokenizer,
    )

    path = os.path.join(checkpoint_dir, "tokenizer.json")
    if os.path.exists(path):
        return BPETokenizer.load(path)
    return ByteTokenizer()


# -- the engine ------------------------------------------------------------


class _DecodeState(NamedTuple):
    """Device-resident per-slot serving state, one pytree so every
    prefill/chunk dispatch carries it whole. PRNG keys ride as raw
    ``key_data`` (uint32) — jnp.where composes on those."""

    k: jax.Array  # [layers, S, C, Hkv, Dh]
    v: jax.Array
    lengths: jax.Array  # [S] i32 — tokens written into each slot's cache
    last_tok: jax.Array  # [S] i32 — most recent token (next decode input)
    key: jax.Array  # [S, ...] u32 — per-slot PRNG key data
    emitted: jax.Array  # [S] i32 — generated tokens so far
    budget: jax.Array  # [S] i32 — max_new for the resident request
    finished: jax.Array  # [S] bool — True: slot idle (done or vacant)
    greedy: jax.Array  # [S] bool
    temp: jax.Array  # [S] f32
    top_p: jax.Array  # [S] f32
    eos: jax.Array  # [S] i32 — -1: no EOS stop
    # Quantized-cache scale side tensors (round 15; None on the bf16
    # default — the pytree simply has no leaves there).
    k_scale: jax.Array | None = None  # [layers, S, C, Hkv] f32
    v_scale: jax.Array | None = None


class _PagedState(NamedTuple):
    """:class:`_DecodeState` for the paged engine: the slab rows become
    the shared block pool plus per-slot block tables (same scheduler
    fields otherwise, so the host loop is mode-agnostic)."""

    k: jax.Array  # [layers, num_blocks, block_size, Hkv, Dh]
    v: jax.Array
    block_tables: jax.Array  # [S, max_blocks] i32
    lengths: jax.Array  # [S] i32 — tokens written into each slot's cache
    last_tok: jax.Array  # [S] i32 — most recent token (next decode input)
    key: jax.Array  # [S, ...] u32 — per-slot PRNG key data
    emitted: jax.Array  # [S] i32 — generated tokens so far
    budget: jax.Array  # [S] i32 — max_new for the resident request
    finished: jax.Array  # [S] bool — True: slot idle (done or vacant)
    greedy: jax.Array  # [S] bool
    temp: jax.Array  # [S] f32
    top_p: jax.Array  # [S] f32
    eos: jax.Array  # [S] i32 — -1: no EOS stop
    k_scale: jax.Array | None = None  # [layers, NB, bs, Hkv] f32
    v_scale: jax.Array | None = None


class _Request:
    __slots__ = (
        "rid", "tokens", "config", "out", "done", "trace", "cancelled",
        "shed", "priority", "deadline", "t_submit", "t_admit", "t_first",
        "prefill_only", "resume", "export", "migrated",
    )

    def __init__(
        self, rid, tokens, config, *, trace=None, deadline_s=None, priority=0
    ):
        self.rid = rid
        self.tokens = tokens
        self.config = config
        self.out: list[int] = []
        self.done = False
        self.cancelled = False
        # Disaggregated-fleet handoff state (round 23, docs/serving.md
        # §disaggregation): prefill_only requests stop after the
        # prefill's first token and EXPORT their paged KV + sampling
        # state (``export`` holds the payload until take_export);
        # ``resume`` carries an imported payload — admission skips
        # prefill and continues the chunk scan from it.
        self.prefill_only = False
        self.resume = None
        self.export = None
        self.migrated = False
        # Shed (round 21): dropped by the scheduler WITHOUT spending a
        # dispatch — terminal like cancelled, but typed RequestShed.
        self.shed = False
        self.priority = priority  # int >= 0; higher = more important
        # Trace id (round 12, observability/tracing.py): joins every
        # journal event of this request's life — request_submit →
        # admission → prefill/decode spans (by rid) → completion — so
        # obs_report --requests rebuilds the per-request timeline from
        # the journal alone. A caller-supplied trace (the fleet router)
        # wins, so one logical request keeps ONE id across replicas.
        self.trace = trace if trace else tracing.new_trace_id()
        self.t_submit = time.perf_counter()
        # Absolute deadline on the submit clock; None = no deadline. An
        # overdue request is cancelled at the next chunk boundary.
        self.deadline = (
            None if deadline_s is None else self.t_submit + float(deadline_s)
        )
        self.t_admit = None  # set at slot admission
        self.t_first = None  # set when the first token lands (TTFT)


class TextServer:
    """Continuous-batching text server over a fixed bank of request slots.

    Construct from live params or :meth:`from_checkpoint`; submit requests
    (:meth:`submit` / :meth:`generate` / :meth:`serve_text`) and drive the
    engine with :meth:`step` (one admission round + one compiled
    ``chunk``-token decode dispatch) until :meth:`idle`.

    Compiled shapes: one prefill executable per length bucket (shared
    jitted function, shape-keyed) and ONE decode-chunk executable serving
    every occupancy pattern — finished/vacant slots ride along masked, so
    admission order and slot churn never recompile anything."""

    def __init__(
        self,
        model: GPTLM,
        params: GPTLMParams,
        tokenizer=None,
        *,
        slots: int = 8,
        buckets: tuple[int, ...] | None = None,
        chunk: int = 32,
        paged: bool = False,
        block_size: int = 16,
        kv_blocks: int | None = None,
        kv_hbm_bytes: int | None = None,
        kv_dtype: str = "bf16",
        decode_matmul_dtype: str | None = None,
        decode_engine: str | None = None,
        prefix_caching: bool = True,
        spec_draft: int = 0,
        spec_ngram: int = 2,
        queue_limit: int | None = None,
        journal=None,
        metrics: MetricsRegistry | None = None,
        metrics_port: int | None = None,
    ):
        from distributed_tensorflow_tpu.ops.quantized import (
            KV_DTYPES,
            MATMUL_DTYPES,
            kv_elem_bytes,
        )

        if slots < 1:
            raise ValueError(f"slots must be >= 1, got {slots}")
        if chunk < 1:
            raise ValueError(f"chunk must be >= 1, got {chunk}")
        if kv_dtype not in KV_DTYPES:
            raise ValueError(
                f"unknown kv_dtype {kv_dtype!r}; one of {KV_DTYPES}"
            )
        if decode_matmul_dtype is not None and (
            decode_matmul_dtype not in MATMUL_DTYPES
        ):
            raise ValueError(
                f"unknown decode_matmul_dtype {decode_matmul_dtype!r}; "
                f"None or one of {MATMUL_DTYPES}"
            )
        if kv_hbm_bytes is not None and not paged:
            raise ValueError(
                "kv_hbm_bytes sizes the paged block pool; pass paged=True"
            )
        if kv_hbm_bytes is not None and kv_blocks is not None:
            raise ValueError(
                "pass kv_blocks or kv_hbm_bytes, not both (kv_hbm_bytes "
                "derives kv_blocks from the element size)"
            )
        if spec_draft < 0:
            raise ValueError(f"spec_draft must be >= 0, got {spec_draft}")
        if spec_ngram < 1:
            raise ValueError(f"spec_ngram must be >= 1, got {spec_ngram}")
        if spec_draft and not paged:
            raise ValueError(
                "speculative decoding requires the paged cache "
                "(paged=True): the verify pass extends through block "
                "tables"
            )
        if queue_limit is not None and queue_limit < 1:
            raise ValueError(
                f"queue_limit must be >= 1 (or None), got {queue_limit}"
            )
        self.model = model
        # Bounded admission queue (round 16): submit() raises QueueFull
        # past this depth instead of growing without bound; /healthz
        # surfaces the saturation so a router can route around a
        # backed-up replica. None = unbounded (the round-9 behavior).
        self.queue_limit = queue_limit
        # Drain / live-weight-swap state (round 16, docs/serving.md
        # §fleet): draining closes admission permanently (residents
        # finish); a pending swap pauses admission until the last
        # old-weight resident completes, then the whole param tree is
        # replaced between dispatches — params are runtime args of every
        # compiled graph, so a swap recompiles NOTHING.
        self._draining = False
        self._pending_swap: tuple | None = None
        # Provenance of the served weights (set by from_checkpoint; swap
        # staleness checks compare against checkpoint_step).
        self.checkpoint_dir: str | None = None
        self.checkpoint_step: int | None = None
        self._restore_optimizer = None
        # Weight-only quantized decode projections (round 15): quantize
        # ONCE at construction (the restore-time artifact
        # GPTLM.decode_weights documents) and serve the quantized tree
        # through EVERY compiled graph — prefill, chunk decode, and the
        # speculative verify all see one consistent set of weights, so
        # served streams are exactly the greedy/sampled streams of the
        # weight-quantized model (the parity tests pin this: weight-only
        # quantization does not relax batch-invariance, only the values).
        self.decode_matmul_dtype = decode_matmul_dtype
        if decode_matmul_dtype is not None and params is not None:
            params = model.decode_weights(params, decode_matmul_dtype)
        self.params = params
        # ``decode_engine`` selected the fused Pallas decode tier, removed
        # in PR 30; the keyword is still accepted because
        # benchmark/traffic/*.json pass "auto" straight into this
        # constructor (ROADMAP D14 drops it there, then it goes here).
        if decode_engine not in (None, "auto", "xla"):
            raise ValueError(
                f"decode_engine={decode_engine!r}: the fused Pallas decode "
                "tier was removed; there is one decode engine (leave the "
                "keyword out)"
            )
        self.tokenizer = tokenizer
        self.slots = slots
        self.chunk = chunk
        self.kv_dtype = kv_dtype
        # Element-size-aware cache accounting (serve_pool helpers): what
        # one position / one block actually costs, scale side tensors
        # included — the quantized pool's capacity gain is exactly this
        # quotient, and obs_report renders it so a quantized pool reads
        # as "smaller bytes", not "bigger chip".
        self.kv_position_bytes = serve_pool.kv_position_bytes(
            model.num_layers,
            model.num_kv_heads,
            model.head_dim,
            kv_elem_bytes(kv_dtype, model.compute_dtype),
            scale_bytes=0 if kv_dtype == "bf16" else 4,
        )
        # Paged mode (round 11): KV lives in a shared pool of
        # `kv_blocks` blocks of `block_size` positions; slots map
        # logical positions through block tables, admission is gated on
        # FREE BLOCKS (a request reserves ceil((prompt+max_new)/bs)
        # blocks, minus prefix-cache hits), and an oversized request
        # queues without blocking shorter ones behind it. Default pool
        # = slots × ceil(max_len/bs) — the slab footprint for full-
        # context models, so paged=True alone changes layout, not
        # capacity; density comes from shrinking kv_blocks below that
        # (or raising slots above it) for short-request mixes. CAVEAT:
        # windowed models keep FULL history in the paged layout
        # (absolute-position addressing; the slab's rolling buffer is
        # only min(window, max_len) rows), so for window << max_len the
        # default pool is ~max_len/window times the slab's KV HBM —
        # size kv_blocks explicitly there.
        self.paged = paged
        self.block_size = int(block_size)
        self.spec_draft = int(spec_draft)
        self.spec_ngram = int(spec_ngram)
        self._alloc: BlockAllocator | None = None
        self._prefix: PrefixCache | None = None
        self.kv_block_bytes = self.kv_position_bytes * self.block_size
        if paged:
            nb_slot = model.paged_blocks_per_slot(self.block_size)
            if kv_hbm_bytes is not None:
                # Byte-budget sizing (round 15): blocks-per-budget from
                # the ELEMENT SIZE, so an int8/fp8 pool under the same
                # budget holds ~2×/~2× the blocks — admission capacity
                # actually grows instead of the dtype silently changing
                # only the array layout.
                self.kv_blocks = serve_pool.blocks_for_hbm_bytes(
                    kv_hbm_bytes,
                    self.block_size,
                    num_layers=model.num_layers,
                    kv_heads=model.num_kv_heads,
                    head_dim=model.head_dim,
                    elem_bytes=kv_elem_bytes(kv_dtype, model.compute_dtype),
                    scale_bytes=0 if kv_dtype == "bf16" else 4,
                )
            else:
                self.kv_blocks = (
                    int(kv_blocks)
                    if kv_blocks is not None
                    else slots * nb_slot
                )
            if self.kv_blocks < 1:
                raise ValueError(
                    f"kv_blocks must be >= 1, got {self.kv_blocks}"
                )
            self._alloc = BlockAllocator(self.kv_blocks)
            # self._prefix (initialized above) is constructed after the
            # journal resolves, so the radix can journal its evictions.
            # Host-authoritative block tables (the device copy is an
            # input of every prefill dispatch) + per-slot held blocks
            # for release at completion.
            self._host_tables = np.zeros((slots, nb_slot), np.int32)
            self._slot_blocks: list[list[int] | None] = [None] * slots
        else:
            self.kv_blocks = 0
        # Serving telemetry (round 10, observability/): admissions and
        # completions as journal events (rid, TTFT, latency, tokens),
        # queue/occupancy gauges + latency histograms in the registry,
        # and every prefill/chunk dispatch as a host span closed by the
        # scheduler's own D2H token fetch. Defaults are no-ops.
        self.journal = journal if journal is not None else obs_journal.get_journal()
        self.metrics = metrics if metrics is not None else MetricsRegistry()
        self.spans = SpanRecorder(journal=self.journal)
        if paged and prefix_caching:
            # Constructed here (not in the paged block above) so the
            # radix can journal its eviction-under-pressure events.
            self._prefix = PrefixCache(
                self._alloc, self.block_size, journal=self.journal
            )
        # Cache-geometry record (round 15): dtype + honest byte
        # accounting as ONE journal event at construction, so
        # obs_report's serving-cache section can say "int8 pool,
        # N bytes/slot" — without it a quantized pool's higher
        # occupancy is indistinguishable from a bigger chip.
        self.kv_slot_bytes = (
            self.model.paged_blocks_per_slot(self.block_size)
            * self.kv_block_bytes
            if paged
            else self.model.cache_len * self.kv_position_bytes
        )
        self.journal.emit(
            "serving_cache_config",
            kv_dtype=self.kv_dtype,
            decode_matmul_dtype=self.decode_matmul_dtype,
            paged=bool(paged),
            block_size=int(self.block_size) if paged else None,
            kv_blocks=int(self.kv_blocks) if paged else None,
            position_bytes=int(self.kv_position_bytes),
            block_bytes=int(self.kv_block_bytes) if paged else None,
            pool_bytes=int(
                self.kv_blocks * self.kv_block_bytes
                if paged
                else self.slots * self.kv_slot_bytes
            ),
            slot_bytes=int(self.kv_slot_bytes),
        )
        if buckets is None:
            # Doubling buckets up to max_len-1 (a prompt always leaves at
            # least one position of generation room): 16, 32, ... — small
            # enough a handful of executables covers everything.
            buckets, b = [], 16
            while b < model.max_len:
                buckets.append(min(b, model.max_len - 1))
                b *= 2
            if not buckets or buckets[-1] != model.max_len - 1:
                buckets.append(model.max_len - 1)
        buckets = tuple(sorted(set(int(b) for b in buckets)))
        if buckets[0] < 1 or buckets[-1] > model.max_len:
            raise ValueError(
                f"buckets must lie in [1, max_len={model.max_len}]: {buckets}"
            )
        self.buckets = buckets
        self._queue: deque[_Request] = deque()
        self._slot_req: list[_Request | None] = [None] * slots
        self._next_rid = 0
        self._results: dict[int, _Request] = {}
        # Measured per-token decode seconds (EWMA over chunk dispatches,
        # round 21): the "provably cannot finish" shed predicate's only
        # evidence. None until the first measured chunk — the scheduler
        # never sheds on a guess, only on expiry, before then.
        self._tok_ewma: float | None = None
        # The first decode dispatch carries the chunk-scan COMPILE —
        # seconds/token of one-time cost. Feeding it to the EWMA made a
        # freshly-warmed replica shed its first deadline-bearing traffic
        # as "hopeless" within microseconds (the round-21 chaos schedule
        # caught this live); that measurement is discarded instead.
        self._tok_first_dispatch = True
        self._state = self._init_state()
        # One rule for every program that returns the state: it takes the
        # state DONATED, so the KV cache is updated where it lies and the
        # device never holds it twice (:meth:`_state_lent` is the other
        # half: what a dispatch that fails owes the server).
        self._prefill_jit = jax.jit(
            self._paged_prefill_graph if paged else self._prefill_graph,
            donate_argnums=1,
        )
        self._chunk_jit = jax.jit(self._chunk_graph, donate_argnums=1)
        self._verify_jit = (
            jax.jit(self._verify_graph, donate_argnums=1)
            if spec_draft else None
        )
        if paged:
            self.metrics.gauge("kv_blocks_total").set(self.kv_blocks)
            self.metrics.gauge("kv_blocks_used").set(0)
            # Byte-honest pool size (round 15): block count × what a
            # block actually costs at this kv_dtype, scales included.
            self.metrics.gauge("kv_pool_bytes").set(
                self.kv_blocks * self.kv_block_bytes
            )
        # Live scrape surface (round 12, observability/exporter.py):
        # /metrics = the registry's Prometheus text, /healthz = engine
        # heartbeat (seconds since the last step() tick) + occupancy.
        # Opt-in: None/0 leaves nothing listening; port 0 is reserved
        # for "off" so production wiring stays explicit — pass a real
        # port (tests bind an ephemeral one via MetricsExporter
        # directly). Started LAST: a constructor failure above must not
        # leave a bound port + daemon thread with no handle to stop.
        self._last_tick = time.time()
        self.exporter: MetricsExporter | None = None
        if metrics_port:
            self.exporter = MetricsExporter(
                self.metrics, port=int(metrics_port), health_fn=self.health
            )
            self.exporter.start()

    # -- constructors ------------------------------------------------------

    @classmethod
    def from_checkpoint(
        cls,
        model: GPTLM,
        checkpoint_dir: str,
        *,
        optimizer=None,
        tokenizer=None,
        **kw,
    ) -> "TextServer":
        """Serve the newest valid checkpoint in ``checkpoint_dir`` — any
        mode layout (:func:`canonical_lm_params`), with the shipped
        ``tokenizer.json`` unless an explicit tokenizer is passed. The
        restored step and directory are recorded so
        :meth:`swap_from_checkpoint` can later adopt a NEWER step from
        the same directory (the live-weight-swap half of the
        train→publish→serve loop)."""
        params, step = canonical_lm_params(
            model, checkpoint_dir, optimizer=optimizer
        )
        tok = tokenizer if tokenizer is not None else load_tokenizer(
            checkpoint_dir
        )
        srv = cls(model, params, tok, **kw)
        srv.checkpoint_dir = checkpoint_dir
        srv.checkpoint_step = int(step)
        srv._restore_optimizer = optimizer
        return srv

    # -- compiled graphs ---------------------------------------------------

    def _init_state(self):
        s = self.slots
        kd = jax.random.key_data(jax.random.split(jax.random.key(0), s))
        common = dict(
            last_tok=jnp.zeros((s,), jnp.int32),
            key=kd,
            emitted=jnp.zeros((s,), jnp.int32),
            budget=jnp.zeros((s,), jnp.int32),
            finished=jnp.ones((s,), bool),  # vacant == finished
            greedy=jnp.ones((s,), bool),
            temp=jnp.ones((s,), jnp.float32),
            top_p=jnp.ones((s,), jnp.float32),
            eos=jnp.full((s,), -1, jnp.int32),
        )
        if self.paged:
            cache = self.model.empty_paged_cache(
                s, self.kv_blocks, self.block_size, self.kv_dtype
            )
            return _PagedState(
                k=cache.k,
                v=cache.v,
                block_tables=cache.block_tables,
                lengths=cache.lengths,
                k_scale=cache.k_scale,
                v_scale=cache.v_scale,
                **common,
            )
        cache = self.model.empty_slot_cache(s, self.kv_dtype)
        return _DecodeState(
            k=cache.k,
            v=cache.v,
            lengths=cache.lengths,
            k_scale=cache.k_scale,
            v_scale=cache.v_scale,
            **common,
        )

    @contextlib.contextmanager
    def _state_lent(self):
        """The stretch of a dispatch, fetch included, in which a program
        owns the server's state (the three jitted programs take it
        donated). One that raises before it runs — tracing, compiling, an
        argument refused — has taken nothing, and the server stands as it
        stood. One that fails holding the buffers leaves no state to go
        back to and every resident's KV is lost with it: the residents
        return to the head of the queue, to be served again from their
        prompts on a vacant state, and the error goes on to the caller."""
        lent = self._state
        try:
            yield
        except BaseException:
            if isinstance(lent.k, jax.Array) and lent.k.is_deleted():
                self._restart_residents()
            raise

    def _restart_residents(self) -> None:
        residents = [r for r in self._slot_req if r is not None]
        for req in residents:
            # A migrated-in request keeps the tokens its first leg
            # emitted: its payload is imported again at admission.
            kept = 0 if req.resume is None else req.resume["meta"]["emitted"]
            del req.out[int(kept):]
            req.t_admit = req.t_first = None
        for slot in range(self.slots):  # planned blocks too, resident or not
            self._release_slot(slot)
        if self._prefix is not None:
            # The radix names blocks of a pool that is gone; with no
            # resident left every one of them is cache-only.
            self._prefix.evict(self._prefix.evictable_blocks())
        self._queue.extendleft(reversed(residents))
        self.metrics.gauge("queue_depth").set(len(self._queue))
        self._state = self._init_state()

    def _pick(self, logits, key_data, greedy, temp, top_p):
        """Per-slot next-token pick, the exact arithmetic of
        ``GPTLM.{greedy,sample}_decode``'s pick closures (greedy: argmax of
        the raw logits; sampled: f32/temperature, nucleus keep-mask by
        EXCLUSIVE cumulative probability, categorical) — vmapped per row
        with per-slot knobs. ``top_p=1.0`` keeps every token, making the
        nucleus branch the identity, and the categorical runs at [1, V] so
        its noise bits match the in-process B=1 call exactly (the parity
        contract)."""

        with jax.named_scope(names.PICK):
            amax = jnp.argmax(logits, axis=-1).astype(jnp.int32)

            def row(lg, kd, t, p):
                lt = lg.astype(jnp.float32) / t
                order = jnp.argsort(lt)[::-1]
                sorted_l = lt[order]
                probs = jax.nn.softmax(sorted_l)
                keep_sorted = jnp.cumsum(probs) - probs < p
                keep = jnp.zeros(lt.shape, bool).at[order].set(keep_sorted)
                lt = jnp.where(keep, lt, -jnp.inf)
                return jax.random.categorical(
                    jax.random.wrap_key_data(kd), lt[None, :], axis=-1
                )[0].astype(jnp.int32)

            def mixed(_):
                sampled = jax.vmap(row)(logits, key_data, temp, top_p)
                return jnp.where(greedy, amax, sampled)

            # Greedy-only banks (the default config) skip the full-vocab
            # sort/softmax/gumbel machinery entirely — it is O(V log V) per
            # slot per token in the hot chunk graph, and jnp.where alone
            # would still evaluate it.
            return jax.lax.cond(jnp.all(greedy), lambda _: amax, mixed, None)

    def _split_keys(self, key_data):
        """Per-slot ``key, sub = jax.random.split(key)`` on key-data rows —
        the exact chain ``GPTLM._decode_loop`` advances per request."""

        def row(kd):
            nxt = jax.random.split(jax.random.wrap_key_data(kd))
            return (
                jax.random.key_data(nxt[0]),
                jax.random.key_data(nxt[1]),
            )

        carried, sub = jax.vmap(row)(key_data)
        return carried, sub

    def _cache(self, st):
        from distributed_tensorflow_tpu.models.gpt import (
            PagedKVCache,
            SlotKVCache,
        )

        if self.paged:
            return PagedKVCache(
                k=st.k,
                v=st.v,
                block_tables=st.block_tables,
                lengths=st.lengths,
                k_scale=st.k_scale,
                v_scale=st.v_scale,
            )
        return SlotKVCache(
            k=st.k,
            v=st.v,
            lengths=st.lengths,
            k_scale=st.k_scale,
            v_scale=st.v_scale,
        )

    def _prefill_graph(
        self, params, st, tokens, plens, admit, key, budget, greedy, temp,
        top_p, eos,
    ):
        """One admission round: ragged batched prefill into admitted slots
        + the first sampled token per admitted request (the pick
        ``_decode_loop`` makes from the prefill logits), all in-graph."""
        logits, cache = self.model.prefill_slots(
            params, self._cache(st), tokens, plens, admit
        )
        keys = jnp.where(admit[:, None], key, st.key)
        carried, sub = self._split_keys(keys)
        first = self._pick(logits, sub, greedy, temp, top_p)
        sel = lambda n, o: jnp.where(admit, n, o)  # noqa: E731
        eos_eff = sel(eos, st.eos)
        fin = sel(
            (first == eos_eff) | (budget <= 1), st.finished
        )
        return st._replace(
            k=cache.k,
            v=cache.v,
            k_scale=cache.k_scale,
            v_scale=cache.v_scale,
            lengths=cache.lengths,
            last_tok=sel(first, st.last_tok),
            key=jnp.where(admit[:, None], carried, st.key),
            emitted=sel(jnp.ones_like(st.emitted), st.emitted),
            budget=sel(budget, st.budget),
            finished=fin,
            greedy=sel(greedy, st.greedy),
            temp=jnp.where(admit, temp, st.temp),
            top_p=jnp.where(admit, top_p, st.top_p),
            eos=eos_eff,
        )

    def _paged_prefill_graph(
        self, params, st, tokens, suffix_lens, prefix_lens, admit,
        block_tables, key, budget, greedy, temp, top_p, eos,
    ):
        """Paged admission round: ragged batched EXTEND through the
        block tables (prefix-cache hits arrive as nonzero
        ``prefix_lens`` — those blocks are read, not recomputed; the
        host strips the cached prefix, so ``tokens`` is only each
        request's suffix padded to its bucket) + the first pick from
        each row's last real suffix position. ``block_tables`` [S, NB]
        is the host-authoritative table snapshot (non-admitted rows
        unchanged by construction)."""
        cache = self._cache(st)._replace(block_tables=block_tables)
        logits, cache = self.model.extend_paged(
            params, cache, tokens, suffix_lens, prefix_lens, admit
        )
        last_lg = jnp.take_along_axis(
            logits,
            jnp.maximum(suffix_lens - 1, 0)[:, None, None],
            axis=1,
        )[:, 0]  # [S, vocab]
        keys = jnp.where(admit[:, None], key, st.key)
        carried, sub = self._split_keys(keys)
        first = self._pick(last_lg, sub, greedy, temp, top_p)
        sel = lambda n, o: jnp.where(admit, n, o)  # noqa: E731
        eos_eff = sel(eos, st.eos)
        fin = sel((first == eos_eff) | (budget <= 1), st.finished)
        return st._replace(
            k=cache.k,
            v=cache.v,
            k_scale=cache.k_scale,
            v_scale=cache.v_scale,
            block_tables=block_tables,
            lengths=sel(prefix_lens + suffix_lens, st.lengths),
            last_tok=sel(first, st.last_tok),
            key=jnp.where(admit[:, None], carried, st.key),
            emitted=sel(jnp.ones_like(st.emitted), st.emitted),
            budget=sel(budget, st.budget),
            finished=fin,
            greedy=sel(greedy, st.greedy),
            temp=jnp.where(admit, temp, st.temp),
            top_p=jnp.where(admit, top_p, st.top_p),
            eos=eos_eff,
        )

    def _verify_graph(self, params, st, suffix, suffix_lens):
        """One speculative verify round (the paged engine's decode tick
        when ``spec_draft > 0``): per active slot the host sent
        ``suffix = [last_tok, d_1..d_k]`` (k = that slot's draft length,
        0 for sampled slots — speculation is greedy-only) — ONE batched
        extend scores every draft position, then GREEDY-EXACT
        acceptance in-graph: target ``tgt[i] = argmax(logits[i])``
        (position 0 through :meth:`_pick`, so sampled slots keep their
        PRNG chain), draft ``d_i`` is accepted iff it equals
        ``tgt[i-1]`` and every earlier draft was accepted, and the
        emitted run is ``tgt[0..n_acc]`` — each accepted position's
        target IS the draft token, plus the first-mismatch correction,
        so the stream is the pure greedy stream by construction (the
        parity contract survives speculation; a bad draft costs wasted
        compute, never a changed token). EOS/budget truncate the run
        exactly as the chunk scan would token by token; ``lengths``
        advance only by tokens actually emitted — rejected drafts' K/V
        stay past ``lengths`` as unreachable garbage, overwritten by
        the next write at those positions. Returns
        ``(state, tokens [D+1, S], valid [D+1, S])`` — the chunk
        graph's host contract, so the scheduler loop is shared."""
        max_len = self.model.max_len
        act = ~st.finished & (st.lengths < max_len)
        logits, cache = self.model.extend_paged(
            params, self._cache(st), suffix, suffix_lens, st.lengths, act
        )
        s, d1 = suffix.shape
        amax = jnp.argmax(logits, axis=-1).astype(jnp.int32)  # [S, D+1]
        carried, sub = self._split_keys(st.key)
        t0 = self._pick(logits[:, 0], sub, st.greedy, st.temp, st.top_p)
        tgt = amax.at[:, 0].set(t0)
        pos = jnp.arange(d1)
        # Leading accepted-draft run: d_i == tgt_{i-1}, all-prior rule.
        ok = (suffix[:, 1:] == tgt[:, :-1]) & (
            pos[None, 1:] < suffix_lens[:, None]
        )
        n_acc = jnp.cumprod(ok.astype(jnp.int32), axis=1).sum(1)  # [S]
        eos_hit = tgt == st.eos[:, None]
        prev_eos = (
            jnp.cumsum(eos_hit.astype(jnp.int32), axis=1)
            - eos_hit.astype(jnp.int32)
        ) > 0
        valid = (
            act[:, None]
            & (pos[None] <= n_acc[:, None])
            & (pos[None] < (st.budget - st.emitted)[:, None])
            & ~prev_eos
        )
        n_emit = valid.sum(1).astype(jnp.int32)  # >= 1 for active slots
        emitted = st.emitted + n_emit
        last = jnp.take_along_axis(
            tgt, jnp.maximum(n_emit - 1, 0)[:, None], axis=1
        )[:, 0]
        fin = st.finished | (
            act & ((eos_hit & valid).any(1) | (emitted >= st.budget))
        )
        st = st._replace(
            k=cache.k,
            v=cache.v,
            k_scale=cache.k_scale,
            v_scale=cache.v_scale,
            lengths=st.lengths + n_emit,
            last_tok=jnp.where(act, last, st.last_tok),
            key=jnp.where(act[:, None], carried, st.key),
            emitted=emitted,
            finished=fin,
        )
        return st, tgt.T, valid.T

    def _chunk_graph(self, params, st):
        """``chunk`` decode steps as one ``lax.scan``: per step every
        unfinished slot advances one token (decode + in-graph pick),
        finished/vacant slots ride along masked. Returns the new state
        plus the [chunk, S] token block and its validity mask — the only
        per-chunk host traffic. One body for both cache layouts: the
        paged step differs only in how the cache row is addressed
        (:meth:`GPTLM.decode_paged` vs :meth:`GPTLM.decode_slots`).

        The paged step reads the pool through a list of the blocks that
        are resident, made here once for the whole chunk
        (every block a slot active now can reach by the chunk's end);
        the token block then carries two more rows, the list's real
        entries and the entries a step walks (that count rounded up to
        whole tiles), each repeated over the slots: they reach the host
        in the fetch that brings the tokens (:meth:`_account_delivery`)."""
        max_len = self.model.max_len
        decode = (
            self.model.decode_paged if self.paged else self.model.decode_slots
        )
        # The paged step updates the pool where it lies, and the scan
        # carries it with each position's [Hkv, Dh] row FLAT:
        # the chip tiles an array's two minor axes (8 × 128 words), so a
        # carry ending in [20, 64] is padded 3.2 times over, where
        # [.., 1280] is not and a block is one contiguous tile to gather.
        # Two reshapes a chunk, none a step.
        pool_shape = st.k.shape
        walked = None
        if self.paged:
            flat = pool_shape[:3] + (-1,)
            st = st._replace(k=st.k.reshape(flat), v=st.v.reshape(flat))
            live = paged_attention.live_block_list(
                st.block_tables, st.lengths,
                ~st.finished & (st.lengths < max_len), self.chunk,
                pool_shape[1], pool_shape[2],
            )
            decode = functools.partial(decode, live=live)
            walked = jnp.stack(
                [live.live, paged_attention.blocks_walked(live, pool_shape[2])]
            )

        def body(st, _):
            act = ~st.finished & (st.lengths < max_len)
            logits, cache = decode(
                params, st.last_tok, self._cache(st), active=act
            )
            carried, sub = self._split_keys(st.key)
            nxt = self._pick(logits, sub, st.greedy, st.temp, st.top_p)
            nxt = jnp.where(act, nxt, st.last_tok)
            emitted = st.emitted + act.astype(jnp.int32)
            fin = st.finished | (
                act
                & (
                    (nxt == st.eos)
                    | (emitted >= st.budget)
                    | (cache.lengths >= max_len)
                )
            )
            st = st._replace(
                k=cache.k,
                v=cache.v,
                k_scale=cache.k_scale,
                v_scale=cache.v_scale,
                lengths=cache.lengths,
                last_tok=nxt,
                key=jnp.where(act[:, None], carried, st.key),
                emitted=emitted,
                finished=fin,
            )
            return st, (nxt, act)

        st, (toks, valid) = jax.lax.scan(
            body, st, None, length=self.chunk
        )
        if self.paged:
            st = st._replace(
                k=st.k.reshape(pool_shape), v=st.v.reshape(pool_shape)
            )
            toks = jnp.concatenate(
                [toks, jnp.broadcast_to(walked[:, None], (2, self.slots))]
            )
        return st, toks, valid

    # -- the scheduler (host side) -----------------------------------------

    def submit(
        self,
        tokens,
        config: GenerationConfig | None = None,
        *,
        deadline_s: float | None = None,
        priority: int = 0,
        trace: str | None = None,
        prefill_only: bool = False,
        resume: dict | None = None,
        emitted_tokens=None,
    ) -> int:
        """Queue one request (prompt as a 1-D int token array). Returns a
        request id for :meth:`result`. Validates against the bucket/cache
        geometry up front: the prompt must fit a bucket and
        ``len + max_new`` must fit ``max_len`` (the KV cache is the slot's
        whole memory — vLLM's fixed-slot discipline).

        ``deadline_s`` (round 16, shed semantics round 21): wall-clock
        budget from NOW. A RESIDENT request past its deadline is
        cancelled at the next chunk boundary (slot/blocks freed,
        ``request_cancelled`` event, :meth:`result` raises
        :class:`RequestCancelled`). A QUEUED request past its deadline —
        or whose remaining budget provably cannot finish inside it at
        the measured per-token rate — is SHED before any prefill
        dispatch (``request_shed`` event, :class:`RequestShed`); one
        that arrives already dead (``deadline_s <= 0``) is shed AT
        SUBMIT and never occupies queue_limit budget.

        ``priority`` (round 21): int >= 0, higher = more important.
        Admission picks by (priority class, earliest deadline first);
        with every queued request at priority 0 and no deadline the
        order is EXACTLY the round-16 FIFO. Under saturation a
        higher-priority submit sheds the lowest class's most deferrable
        request instead of bouncing QueueFull.

        ``trace`` overrides the generated trace id so a fleet router's
        retries keep one id across replicas. Raises :class:`QueueFull`
        when the queue is at ``queue_limit`` with no lower class to
        shed, and RuntimeError once :meth:`drain` closed admission.

        Disaggregated handoff (round 23, docs/serving.md
        §disaggregation; both knobs require ``paged=True`` — block
        tables are what make the cache relocatable):

        - ``prefill_only=True``: run prefill + the first token, then
          EXPORT the request's written KV blocks + sampling state
          (:meth:`take_export`) and free the slot — the prefill leg of
          a two-leg fleet request. A request that FINISHES at prefill
          (budget 1 / immediate EOS) completes normally instead.
        - ``resume=payload``: admit a mid-flight request — the decode
          leg. The payload (an export from a prefill replica, same
          model geometry) is imported into freshly reserved blocks and
          the chunk scan continues token-identically.
          ``emitted_tokens`` seeds the output with leg 1's tokens so
          :meth:`result` returns the complete stream."""
        config = config or GenerationConfig()
        priority = int(priority)
        if priority < 0:
            raise ValueError(f"priority must be >= 0, got {priority}")
        config.validate(self.model.vocab_size)
        tokens = np.asarray(tokens, np.int32).reshape(-1)
        if tokens.size < 1:
            raise ValueError("empty prompt")
        if tokens.size > self.buckets[-1]:
            raise ValueError(
                f"prompt length {tokens.size} exceeds the largest bucket "
                f"{self.buckets[-1]}"
            )
        if tokens.size + config.max_new > self.model.max_len:
            raise ValueError(
                f"prompt {tokens.size} + max_new {config.max_new} exceeds "
                f"max_len {self.model.max_len}"
            )
        if self.paged:
            need = blocks_for(
                tokens.size + config.max_new, self.block_size
            )
            if need > self.kv_blocks:
                raise ValueError(
                    f"request needs {need} KV blocks but the pool holds "
                    f"{self.kv_blocks}; raise kv_blocks or shrink the "
                    "request"
                )
        if (prefill_only or resume is not None) and not self.paged:
            raise ValueError(
                "KV migration requires paged=True (block tables are what "
                "make the cache relocatable across replicas)"
            )
        if prefill_only and resume is not None:
            raise ValueError(
                "prefill_only and resume are the two LEGS of one request "
                "— a submit is at most one of them"
            )
        if resume is not None:
            self._validate_resume(resume, tokens, config)
        if self._draining:
            raise RuntimeError(
                "server is draining: admission is closed (residents are "
                "being finished; route new requests to another replica)"
            )
        if deadline_s is not None and float(deadline_s) <= 0.0:
            # Arrived dead: terminal RequestShed at submit — it must
            # never occupy queue_limit budget or displace live work
            # (satellite, round 21). The birth event still fires so the
            # per-request timeline reconstruction sees one lifecycle.
            rid = self._next_rid
            self._next_rid += 1
            req = _Request(
                rid, tokens, config,
                trace=trace, deadline_s=deadline_s, priority=priority,
            )
            self._results[rid] = req
            self.metrics.counter("requests_submitted_total").inc()
            self._emit_submit(req)
            self._shed(req, reason="expired_at_submit")
            return rid
        if (
            self.queue_limit is not None
            and len(self._queue) >= self.queue_limit
        ):
            victim = self._shed_victim(priority)
            if victim is None:
                self.metrics.counter("queue_rejections_total").inc()
                self.journal.emit(
                    "queue_reject",
                    prompt_len=int(tokens.size),
                    queue_depth=len(self._queue),
                    queue_limit=int(self.queue_limit),
                    **({"trace": trace} if trace else {}),
                )
                raise QueueFull(
                    f"admission queue is at queue_limit={self.queue_limit}; "
                    "retry later or route to another replica"
                )
            # Saturation shed (round 21): the newcomer outranks the
            # lowest queued class — shed that class's most deferrable
            # member (no deadline first, then latest deadline; never out
            # of deadline order within the class) instead of bouncing
            # the higher-priority request.
            self._queue.remove(victim)
            self._shed(victim, reason="preempted")
        rid = self._next_rid
        self._next_rid += 1
        req = _Request(
            rid, tokens, config,
            trace=trace, deadline_s=deadline_s, priority=priority,
        )
        req.prefill_only = bool(prefill_only)
        if resume is not None:
            req.resume = resume
            if emitted_tokens is not None:
                req.out = [int(t) for t in np.asarray(emitted_tokens)]
            if len(req.out) != int(resume["meta"]["emitted"]):
                raise ValueError(
                    f"resume payload says {resume['meta']['emitted']} "
                    f"tokens were emitted on leg 1 but emitted_tokens "
                    f"carries {len(req.out)}"
                )
        self._queue.append(req)
        self._results[rid] = req
        self.metrics.counter("requests_submitted_total").inc()
        self.metrics.gauge("queue_depth").set(len(self._queue))
        self._emit_submit(req)
        return rid

    def _emit_submit(self, req: _Request) -> None:
        # The trace's birth event: everything downstream (admission,
        # spans, completion/shed) joins to it by trace/rid. ``priority``
        # rides only when non-default — the round-16 event bytes are
        # preserved on the default path.
        self.journal.emit(
            "request_submit",
            rid=req.rid,
            trace=req.trace,
            prompt_len=int(req.tokens.size),
            max_new=int(req.config.max_new),
            greedy=bool(req.config.greedy),
            **({"priority": req.priority} if req.priority else {}),
        )

    def _validate_resume(self, resume: dict, tokens, config) -> None:
        """Refuse a migration payload that cannot continue here — wrong
        model geometry, wrong cache dtype, or inconsistent with the
        request it claims to resume. Raises ValueError (a PERMANENT
        rejection in the fleet protocol: the router falls back to
        re-prefill, it does not retry the import)."""
        meta = resume.get("meta") or {}
        arrays = resume.get("arrays") or {}
        want = {
            "kv_dtype": self.kv_dtype,
            "block_size": self.block_size,
            "num_layers": self.model.num_layers,
            "num_kv_heads": self.model.num_kv_heads,
            "head_dim": self.model.head_dim,
        }
        for k, w in want.items():
            if meta.get(k) != w:
                raise ValueError(
                    f"resume payload geometry mismatch: {k}="
                    f"{meta.get(k)!r} but this replica serves {w!r}"
                )
        if int(meta.get("length", -1)) != int(tokens.size):
            raise ValueError(
                f"resume payload covers {meta.get('length')} positions "
                f"but the prompt has {tokens.size}"
            )
        if int(meta.get("emitted", 0)) < 1:
            raise ValueError("resume payload emitted no leg-1 token")
        need = {"k", "v", "key"}
        if self.kv_dtype != "bf16":
            need |= {"k_scale", "v_scale"}
        missing = need - set(arrays)
        if missing:
            raise ValueError(
                f"resume payload missing arrays: {sorted(missing)}"
            )
        n_src = int(meta.get("blocks", 0))
        if n_src != blocks_for(int(tokens.size), self.block_size):
            raise ValueError(
                f"resume payload carries {n_src} blocks; "
                f"{blocks_for(int(tokens.size), self.block_size)} cover "
                "the prompt"
            )

    def _shed_victim(self, priority: int) -> _Request | None:
        """Under a full queue: the request a ``priority``-class submit may
        displace — a member of the strictly LOWEST queued class when that
        class ranks below the newcomer; within the class the most
        deferrable one (no deadline, then latest deadline, then newest).
        All-default traffic (priority 0 everywhere) finds no victim and
        keeps the round-16 QueueFull contract."""
        if priority <= 0 or not self._queue:
            return None
        low = min(r.priority for r in self._queue)
        if low >= priority:
            return None
        return max(
            (r for r in self._queue if r.priority == low),
            key=lambda r: (
                math.inf if r.deadline is None else r.deadline, r.rid,
            ),
        )

    def _shed(self, req: _Request, *, reason: str) -> None:
        """Terminal drop WITHOUT spending a dispatch: the loud record
        (``request_shed`` event + ``sheds_total``) a router or load
        generator keys on. Distinct from :meth:`_cancel` — no slot or
        blocks exist to free, and :meth:`result` raises
        :class:`RequestShed`."""
        req.shed = True
        self.metrics.counter("sheds_total").inc()
        self.journal.emit(
            "request_shed",
            rid=req.rid,
            trace=req.trace,
            priority=req.priority,
            reason=reason,
            age_s=round(time.perf_counter() - req.t_submit, 6),
        )

    def bucket_for(self, length: int) -> int:
        """Smallest bucket holding a ``length``-token prompt."""
        for b in self.buckets:
            if length <= b:
                return b
        raise ValueError(
            f"prompt length {length} exceeds the largest bucket "
            f"{self.buckets[-1]}"
        )

    def _free_slots(self) -> list[int]:
        return [i for i, r in enumerate(self._slot_req) if r is None]

    def _admit(self) -> None:
        """Move queued requests into free slots; one prefill dispatch per
        length bucket among this round's admissions. Paged mode admits by
        free BLOCKS (worst-case reservation minus prefix-cache hits) with
        no head-of-line blocking; slab mode by free slots alone."""
        if self.paged:
            self._admit_paged()
        else:
            self._admit_slab()

    def _plan_admission(self, req: _Request):
        """Block reservation for one request: prefix-cache match
        (matched blocks retained IMMEDIATELY, so this round's own
        evictions cannot free them out from under the plan), worst-case
        new-block reservation for ``prompt + max_new`` (admission never
        overcommits, so generation never OOMs mid-flight), LRU eviction
        of cache-only blocks under pressure. Returns None — releasing
        any retains — when the request does not fit right now."""
        bs = self.block_size
        total = blocks_for(int(req.tokens.size) + req.config.max_new, bs)
        matched: list[int] = []
        if self._prefix is not None:
            matched = self._prefix.match(req.tokens)
            for b in matched:
                self._alloc.retain(b)
        n_new = total - len(matched)
        if not self._alloc.can_alloc(n_new) and self._prefix is not None:
            deficit = n_new - self._alloc.free_blocks
            # Evict only when eviction can actually make this request
            # fit — a hopeless flush would trade the warm prefix cache
            # for nothing and the request would still be skipped.
            if self._prefix.evictable_blocks() >= deficit:
                self._prefix.evict(deficit)
        if not self._alloc.can_alloc(n_new):
            for b in matched:
                self._alloc.release(b)
            return None
        return {
            "table": matched + self._alloc.alloc(n_new),
            "matched": len(matched),
            "new": n_new,
        }

    def _plan_import(self, req: _Request):
        """Block reservation for a migration import: ``prompt + max_new``
        FRESH blocks, no prefix-cache match — the payload's blocks are
        the authoritative prompt KV (round-15 storage-dtype values), and
        splicing locally cached prefix blocks under an imported stream
        would trade a bitwise guarantee for a recomputed one. Same
        eviction-under-pressure rule as :meth:`_plan_admission`."""
        total = blocks_for(
            int(req.tokens.size) + req.config.max_new, self.block_size
        )
        if not self._alloc.can_alloc(total) and self._prefix is not None:
            deficit = total - self._alloc.free_blocks
            if self._prefix.evictable_blocks() >= deficit:
                self._prefix.evict(deficit)
        if not self._alloc.can_alloc(total):
            return None
        return {"table": self._alloc.alloc(total), "matched": 0,
                "new": total}

    def _import_resume(self, slot: int, req: _Request, plan: dict) -> None:
        """Admit a mid-flight request from a migration payload: write the
        exported blocks into this pool (:func:`import_kv_blocks` — the
        sentinel=``num_blocks`` scatter rule), restore the per-slot
        sampling/progress rows EXACTLY as the prefill dispatch left them
        on the source replica, and let the ordinary chunk scan continue.
        Token parity is by construction: the blocks carry the exact
        storage-dtype values (round-15 uniform rule) and the PRNG row is
        the carried key after leg 1's single split."""
        from distributed_tensorflow_tpu.models.gpt import import_kv_blocks

        t0 = time.perf_counter()
        payload = req.resume
        meta = payload["meta"]
        arrays = payload["arrays"]
        table = plan["table"]
        row = self._host_tables[slot]
        row[:] = 0
        row[: len(table)] = table
        self._slot_blocks[slot] = list(table)
        n_src = int(meta["blocks"])
        blocks = {
            k: arrays[k]
            for k in ("k", "v", "k_scale", "v_scale")
            if k in arrays
        }
        # Pad every import to ONE canonical block count (sentinel ids
        # drop their zero rows): the eager scatter otherwise compiles a
        # fresh executable per distinct payload size — a ~1 s XLA
        # compile per prompt-length class, which the disagg bench
        # measured as the dominant cost of the whole migration path.
        pool = self._cache(self._state)
        cap = blocks_for(self.model.max_len, self.block_size)
        ids = list(int(b) for b in table[:n_src])
        ids += [int(pool.k.shape[1])] * (cap - n_src)
        if cap > n_src:
            blocks = {
                k: np.concatenate(
                    [
                        np.asarray(a),
                        np.zeros(
                            (a.shape[0], cap - n_src) + a.shape[2:],
                            np.asarray(a).dtype,
                        ),
                    ],
                    axis=1,
                )
                for k, a in (
                    (k, np.asarray(a)) for k, a in blocks.items()
                )
            }
        cache = import_kv_blocks(pool, ids, blocks)
        st = self._state

        def put_row(field, value):
            a = np.asarray(getattr(st, field)).copy()
            a[slot] = value
            return self._commit_row(a)

        c = req.config
        self._state = st._replace(
            k=cache.k,
            v=cache.v,
            k_scale=cache.k_scale,
            v_scale=cache.v_scale,
            block_tables=put_row("block_tables", row),
            lengths=put_row("lengths", int(meta["length"])),
            last_tok=put_row("last_tok", int(meta["last_tok"])),
            key=put_row("key", np.asarray(arrays["key"])),
            emitted=put_row("emitted", int(meta["emitted"])),
            budget=put_row("budget", c.max_new),
            finished=put_row("finished", False),
            greedy=put_row("greedy", c.greedy),
            temp=put_row("temp", c.temperature),
            top_p=put_row("top_p", c.top_p),
            eos=put_row("eos", -1 if c.eos_id is None else c.eos_id),
        )
        self._slot_req[slot] = req
        req.t_admit = time.perf_counter()
        nbytes = sum(
            np.asarray(a).nbytes for a in arrays.values()
        )
        self.metrics.counter("admissions_total").inc()
        self.metrics.counter("migrations_imported_total").inc()
        self.journal.emit(
            "admission",
            rid=req.rid,
            trace=req.trace,
            slot=int(slot),
            bucket=0,
            prompt_len=int(req.tokens.size),
            imported_blocks=n_src,
            new_blocks=int(plan["new"]),
            migrated=True,
            queue_wait_s=round(req.t_admit - req.t_submit, 6),
        )
        self.journal.emit(
            "kv_migration",
            phase="import",
            rid=req.rid,
            trace=req.trace,
            slot=int(slot),
            blocks=n_src,
            nbytes=int(nbytes),
            wall_ms=round((time.perf_counter() - t0) * 1e3, 3),
        )

    def _admit_member_row(
        self, slot, req, lb, key, budget, greedy, temp, top_p, eos,
        journal_extra=None,
    ) -> None:
        """Per-member sampling/budget row + admission telemetry shared by
        BOTH engine modes — a ``GenerationConfig`` field wired here
        reaches the slab and paged admission paths together (they must
        never drift: the parity contract spans both)."""
        c = req.config
        key[slot] = np.asarray(
            jax.random.key_data(jax.random.key(c.seed))
        )
        budget[slot] = c.max_new
        greedy[slot] = c.greedy
        temp[slot] = c.temperature
        top_p[slot] = c.top_p
        eos[slot] = -1 if c.eos_id is None else c.eos_id
        self._slot_req[slot] = req
        req.t_admit = time.perf_counter()
        self.metrics.counter("admissions_total").inc()
        self.journal.emit(
            "admission",
            rid=req.rid,
            trace=req.trace,
            slot=int(slot),
            bucket=int(lb),
            prompt_len=int(req.tokens.size),
            **(journal_extra or {}),
            queue_wait_s=round(req.t_admit - req.t_submit, 6),
        )

    def _record_first_token(self, slot, req, first, fin, t_first) -> None:
        """Post-prefill bookkeeping shared by both engine modes: TTFT,
        the admission's first generated token, early EOS/budget finish."""
        req.t_first = t_first
        self.metrics.histogram("ttft_s").observe(t_first - req.t_submit)
        req.out.append(int(first[slot]))
        if fin[slot]:
            # A prefill_only request that FINISHES at prefill (budget 1,
            # immediate EOS) completes normally — nothing to migrate.
            self._finish(slot)
        elif req.prefill_only:
            self._export_request(slot, req)

    def _commit_row(self, a):
        """Host-edited state rows must re-enter the jit as arrays
        COMMITTED to the same device as the graph outputs they replace:
        a raw numpy leaf keys the executable cache under unspecified
        sharding, and the NEXT prefill/chunk dispatch silently
        recompiles its multi-second program (same trace, different
        executable — the round-23 disagg A/B surfaced this as a full
        recompile after every export/import/cancel)."""
        sharding = getattr(self._state.k, "sharding", None)
        return jax.device_put(a, sharding)

    def _export_request(self, slot: int, req: _Request) -> None:
        """The prefill leg's terminal act: fetch the request's WRITTEN
        KV blocks (``ceil(prompt/block_size)`` — the first generated
        token's KV is written by the first decode step, which runs on
        the importing replica) + the per-slot sampling/progress rows,
        stash them as the migration payload (:meth:`take_export`), and
        free the slot. The request is terminal HERE; the radix keeps the
        prompt's prefix blocks warm for future prefills."""
        from distributed_tensorflow_tpu.models.gpt import export_kv_blocks

        t0 = time.perf_counter()
        st = self._state
        length = int(np.asarray(st.lengths[slot]))
        n_src = blocks_for(length, self.block_size)
        ids = self._slot_blocks[slot][:n_src]
        # Gather at the ONE canonical block count every export shares
        # (pad with repeats of a real id — export has no sentinel), then
        # trim on the host: the eager gather's executable is keyed on
        # len(ids), so per-prompt-length shapes would compile a fresh
        # XLA program per length class at serving time. Wire bytes stay
        # the trimmed n_src blocks.
        cap = blocks_for(self.model.max_len, self.block_size)
        padded = list(ids) + [int(ids[0])] * (cap - n_src)
        arrays = {
            k: np.asarray(v)[:, :n_src]
            for k, v in export_kv_blocks(self._cache(st), padded).items()
        }
        arrays["key"] = np.asarray(st.key[slot])
        meta = {
            "kv_dtype": self.kv_dtype,
            "block_size": self.block_size,
            "num_layers": self.model.num_layers,
            "num_kv_heads": self.model.num_kv_heads,
            "head_dim": self.model.head_dim,
            "length": length,
            "blocks": n_src,
            "last_tok": int(np.asarray(st.last_tok[slot])),
            "emitted": int(np.asarray(st.emitted[slot])),
            "max_new": int(req.config.max_new),
        }
        req.export = {"arrays": arrays, "meta": meta}
        req.migrated = True
        req.done = True
        fin = np.asarray(st.finished).copy()
        fin[slot] = True
        self._state = self._state._replace(finished=self._commit_row(fin))
        self._release_slot(slot)
        nbytes = sum(a.nbytes for a in arrays.values())
        self.metrics.counter("migrations_exported_total").inc()
        self.journal.emit(
            "kv_migration",
            phase="export",
            rid=req.rid,
            trace=req.trace,
            slot=int(slot),
            blocks=n_src,
            nbytes=int(nbytes),
            wall_ms=round((time.perf_counter() - t0) * 1e3, 3),
            ttft_s=round(
                (req.t_first if req.t_first is not None else t0)
                - req.t_submit,
                6,
            ),
        )

    def warm_import(self) -> None:
        """Compile BOTH migration executables ahead of traffic: one
        all-sentinel import against the live pool (every row drops, so
        the pool values are untouched) plus one canonical-shape export
        gather. `_import_resume` pads every real payload and
        `_export_request` pads every gather to this single shape, so
        these two programs are the only ones migration ever dispatches —
        first-request TTFT on either leg's replica must not be an XLA
        compile measurement (the ``--warm`` contract)."""
        if not self.paged:
            return
        from distributed_tensorflow_tpu.models.gpt import (
            export_kv_blocks,
            import_kv_blocks,
        )

        pool = self._cache(self._state)
        cap = blocks_for(self.model.max_len, self.block_size)

        def zeros(p):
            return np.zeros((p.shape[0], cap) + tuple(p.shape[2:]), p.dtype)

        blocks = {"k": zeros(pool.k), "v": zeros(pool.v)}
        if pool.k_scale is not None:
            blocks["k_scale"] = zeros(pool.k_scale)
            blocks["v_scale"] = zeros(pool.v_scale)
        import_kv_blocks(pool, [int(pool.k.shape[1])] * cap, blocks)
        jax.block_until_ready(
            list(export_kv_blocks(pool, [0] * cap).values())
        )

    def take_export(self, rid: int) -> dict | None:
        """Consume a migrated request's payload: the KV-block arrays +
        state meta :meth:`_export_request` stashed, plus leg 1's emitted
        tokens. Returns None when the request completed without
        migrating (finished at prefill) — the caller then treats
        :meth:`result` as the terminal read. A consumed or unknown rid
        also returns None (idempotent, like a second ``result`` read is
        not): the worker loop probes every done rid through here."""
        req = self._results.get(rid)
        if req is None or not req.migrated:
            return None
        del self._results[rid]
        return {
            "arrays": req.export["arrays"],
            "meta": req.export["meta"],
            "tokens": list(req.out),
            "trace": req.trace,
        }

    def _admit_paged(self) -> None:
        free = self._free_slots()
        if not free or not self._queue:
            return
        batch: list[tuple[int, _Request, dict, int]] = []
        skipped: deque[_Request] = deque()
        # Same-round cold-prefix serialization (round 14): block id →
        # the admission WAVE whose prefill writes its K/V this round.
        pending: dict[int, int] = {}
        bs = self.block_size
        imports: list[tuple[int, _Request, dict]] = []
        while free and self._queue:
            req = self._queue.popleft()
            plan = (
                self._plan_import(req) if req.resume is not None
                else self._plan_admission(req)
            )
            if plan is None:
                # No head-of-line blocking: a request the pool cannot
                # hold yet waits WITHOUT starving shorter requests
                # behind it (relative FIFO order is preserved both among
                # the admitted and among the skipped).
                skipped.append(req)
                continue
            if req.resume is not None:
                # Migration import (round 23): the payload's device
                # writes land synchronously below, BEFORE any of this
                # round's prefill waves dispatch — so the radix entries
                # registered here are valid for every same-round reader
                # without joining the wave dependency graph.
                if self._prefix is not None:
                    self._prefix.insert(
                        req.tokens, plan["table"],
                        int(req.tokens.size) // bs,
                    )
                imports.append((free.pop(0), req, plan))
                continue
            # Register the planned full PROMPT blocks in the radix NOW —
            # round 11 registered post-prefill, so N cold requests
            # sharing a prefix admitted in ONE round all missed and
            # prefilled it N times (the GOTCHA that needed staggered
            # test choreography). A match against a block whose K/V
            # this round has not yet written is sound only when the
            # reader dispatches AFTER the writer, so each member lands
            # in a wave one past its deepest pending dependency and
            # waves dispatch in order below. Refcounts make the early
            # registration safe: the writer's slot holds every pending
            # block until its prefill ran, so eviction (cache-only,
            # refcount 1) can never reclaim one, and an early finisher
            # only drops the slot references — the radix keeps its own.
            wave = 0
            if self._prefix is not None:
                matched_ids = plan["table"][: plan["matched"]]
                deps = [pending[b] for b in matched_ids if b in pending]
                if deps:
                    wave = max(deps) + 1
                n_full = int(req.tokens.size) // bs
                self._prefix.insert(req.tokens, plan["table"], n_full)
                for b in plan["table"][plan["matched"]: n_full]:
                    pending[b] = wave
            batch.append((free.pop(0), req, plan, wave))
        skipped.extend(self._queue)
        self._queue = skipped
        self.metrics.gauge("queue_depth").set(len(self._queue))
        for slot, req, plan in imports:
            self._import_resume(slot, req, plan)
        if not batch:
            self.metrics.gauge("kv_blocks_used").set(
                self._alloc.used_blocks
            )
            return
        for slot, req, plan, wave in batch:
            row = self._host_tables[slot]
            row[:] = 0
            row[: len(plan["table"])] = plan["table"]
            self._slot_blocks[slot] = list(plan["table"])
        for wave in sorted({w for _, _, _, w in batch}):
            self._prefill_wave(
                [m for m in batch if m[3] == wave], wave
            )
        self.metrics.gauge("kv_blocks_used").set(self._alloc.used_blocks)

    def _prefill_wave(self, members_w, wave: int) -> None:
        """One admission wave's prefill dispatches (one per length
        bucket among the wave's members)."""
        s = self.slots
        by_bucket: dict[int, list] = {}
        for slot, req, plan, _ in members_w:
            prefix_len = plan["matched"] * self.block_size
            suffix = req.tokens[prefix_len:]
            by_bucket.setdefault(self.bucket_for(suffix.size), []).append(
                (slot, req, plan, prefix_len, suffix)
            )
        for lb, members in sorted(by_bucket.items()):
            tokens = np.zeros((s, lb), np.int32)
            slens = np.ones((s,), np.int32)  # suffix lens must be >= 1
            plens = np.zeros((s,), np.int32)  # cached-prefix lens
            admit = np.zeros((s,), bool)
            key = np.array(self._state.key)  # writable host copy
            budget = np.zeros((s,), np.int32)
            greedy = np.ones((s,), bool)
            temp = np.ones((s,), np.float32)
            top_p = np.ones((s,), np.float32)
            eos = np.full((s,), -1, np.int32)
            for slot, req, plan, prefix_len, suffix in members:
                tokens[slot, : suffix.size] = suffix
                slens[slot] = suffix.size
                plens[slot] = prefix_len
                admit[slot] = True
                miss = 0
                if self._prefix is not None:
                    miss = (
                        self._prefix.matchable_blocks(int(req.tokens.size))
                        - plan["matched"]
                    )
                    self.metrics.counter("prefix_cache_hits").inc(
                        plan["matched"]
                    )
                    self.metrics.counter("prefix_cache_misses").inc(miss)
                self._admit_member_row(
                    slot, req, lb, key, budget, greedy, temp, top_p, eos,
                    journal_extra=dict(
                        prefix_len=int(prefix_len),
                        prefix_hit_blocks=int(plan["matched"]),
                        prefix_miss_blocks=int(miss),
                        new_blocks=int(plan["new"]),
                        wave=int(wave),
                    ),
                )
            with self._state_lent(), self.spans.dispatch(
                names.SPAN_PREFILL, bucket=int(lb), admitted=len(members),
                rids=[int(m[1].rid) for m in members],
            ) as sp:
                self._state = self._prefill_jit(
                    self.params,
                    self._state,
                    jnp.asarray(tokens),
                    jnp.asarray(slens),
                    jnp.asarray(plens),
                    jnp.asarray(admit),
                    jnp.asarray(self._host_tables),
                    jnp.asarray(key),
                    jnp.asarray(budget),
                    jnp.asarray(greedy),
                    jnp.asarray(temp),
                    jnp.asarray(top_p),
                    jnp.asarray(eos),
                )
                first = sp.fetch(self._state.last_tok)
            fin = np.asarray(self._state.finished)
            t_first = time.perf_counter()
            for slot, req, plan, prefix_len, suffix in members:
                # Prompt blocks were registered in the radix at
                # admission-plan time (wave scheduling above); their K/V
                # is valid as of this dispatch.
                self._record_first_token(slot, req, first, fin, t_first)

    def _admit_slab(self) -> None:
        free = self._free_slots()
        if not free or not self._queue:
            return
        batch: list[tuple[int, _Request]] = []
        while free and self._queue:
            batch.append((free.pop(0), self._queue.popleft()))
        by_bucket: dict[int, list[tuple[int, _Request]]] = {}
        for slot, req in batch:
            by_bucket.setdefault(
                self.bucket_for(req.tokens.size), []
            ).append((slot, req))
        s = self.slots
        for lb, members in sorted(by_bucket.items()):
            tokens = np.zeros((s, lb), np.int32)
            plens = np.ones((s,), np.int32)  # kv_lens must be >= 1
            admit = np.zeros((s,), bool)
            key = np.array(self._state.key)  # writable host copy
            budget = np.zeros((s,), np.int32)
            greedy = np.ones((s,), bool)
            temp = np.ones((s,), np.float32)
            top_p = np.ones((s,), np.float32)
            eos = np.full((s,), -1, np.int32)
            for slot, req in members:
                tokens[slot, : req.tokens.size] = req.tokens
                plens[slot] = req.tokens.size
                admit[slot] = True
                self._admit_member_row(
                    slot, req, lb, key, budget, greedy, temp, top_p, eos
                )
            with self._state_lent(), self.spans.dispatch(
                names.SPAN_PREFILL, bucket=int(lb), admitted=len(members),
                rids=[int(r.rid) for _, r in members],
            ) as sp:
                self._state = self._prefill_jit(
                    self.params,
                    self._state,
                    jnp.asarray(tokens),
                    jnp.asarray(plens),
                    jnp.asarray(admit),
                    jnp.asarray(key),
                    jnp.asarray(budget),
                    jnp.asarray(greedy),
                    jnp.asarray(temp),
                    jnp.asarray(top_p),
                    jnp.asarray(eos),
                )
                # The admission's first tokens come back with this fetch —
                # a real D2H value read, so it is also the execution
                # barrier (and what lets the dispatch span close).
                first = sp.fetch(self._state.last_tok)
            fin = np.asarray(self._state.finished)
            t_first = time.perf_counter()
            for slot, req in members:
                self._record_first_token(slot, req, first, fin, t_first)
        self.metrics.gauge("queue_depth").set(len(self._queue))

    def _release_slot(self, slot: int) -> None:
        """Return a slot (and, paged, its block references) to the free
        pool — the shared half of completion AND cancellation. Prefix-
        cached blocks keep the radix's own reference and stay resident
        for future hits."""
        self._slot_req[slot] = None
        if self.paged and self._slot_blocks[slot] is not None:
            for b in self._slot_blocks[slot]:
                self._alloc.release(b)
            self._slot_blocks[slot] = None
            self.metrics.gauge("kv_blocks_used").set(
                self._alloc.used_blocks
            )

    def _cancel(self, req: _Request, *, slot: int | None = None) -> None:
        """Cancel one overdue request at a chunk boundary. Resident
        requests free their slot/blocks (the device-side ``finished``
        flag masks the slot out of the next dispatch exactly as a normal
        completion would); queued requests just leave the queue. The
        structured ``request_cancelled`` event + counter is the record a
        router keys on — a cancelled request must never be resurrected
        by a failover retry."""
        req.cancelled = True
        if slot is not None:
            fin = np.asarray(self._state.finished).copy()
            fin[slot] = True
            self._state = self._state._replace(
                finished=self._commit_row(fin)
            )
            self._release_slot(slot)
        self.metrics.counter("cancellations_total").inc()
        self.journal.emit(
            "request_cancelled",
            rid=req.rid,
            trace=req.trace,
            resident=slot is not None,
            slot=None if slot is None else int(slot),
            tokens=len(req.out),
            age_s=round(time.perf_counter() - req.t_submit, 6),
        )

    def _hopeless(self, req: _Request, now: float) -> bool:
        """True when the request provably cannot finish: full remaining
        budget × the measured per-token EWMA exceeds the deadline slack.
        Conservative by construction — no measurement yet (or no
        deadline) never sheds, and the estimate ignores queue wait ahead
        of the request, so only truly unreachable deadlines trip it."""
        if req.deadline is None or self._tok_ewma is None:
            return False
        # Remaining budget, not max_new: a resumed decode leg already
        # carries leg 1's tokens (round 23) — its remaining work is
        # what the deadline must cover.
        remaining = req.config.max_new - len(req.out)
        return remaining * self._tok_ewma > req.deadline - now

    def _shed_overdue(self) -> None:
        """Queued-side deadline enforcement at the chunk boundary (round
        21): a queued request past its deadline — or provably unable to
        finish inside it — is SHED before any prefill dispatch is spent
        on it. Residents are the :meth:`_cancel_overdue` half."""
        now = time.perf_counter()
        if not any(
            r.deadline is not None
            and (now > r.deadline or self._hopeless(r, now))
            for r in self._queue
        ):
            return
        keep: deque[_Request] = deque()
        for req in self._queue:
            if req.deadline is not None and now > req.deadline:
                self._shed(req, reason="expired")
            elif self._hopeless(req, now):
                self._shed(req, reason="hopeless")
            else:
                keep.append(req)
        self._queue = keep
        self.metrics.gauge("queue_depth").set(len(self._queue))

    def _cancel_overdue(self) -> None:
        """Deadline enforcement at the chunk boundary: cancel RESIDENT
        requests whose ``deadline_s`` budget elapsed mid-generation
        (queued ones are shed instead — :meth:`_shed_overdue`)."""
        now = time.perf_counter()
        for slot, req in enumerate(self._slot_req):
            if req is not None and req.deadline is not None and now > req.deadline:
                self._cancel(req, slot=slot)

    def _schedule(self) -> None:
        """Admission order (round 21): (priority class desc, earliest
        deadline first, submission order). When every queued request is
        priority 0 with no deadline the sort is skipped entirely — the
        queue stays the round-16 FIFO deque, untouched."""
        if all(r.priority == 0 and r.deadline is None for r in self._queue):
            return
        self._queue = deque(sorted(
            self._queue,
            key=lambda r: (
                -r.priority,
                math.inf if r.deadline is None else r.deadline,
                r.rid,
            ),
        ))

    def _finish(self, slot: int) -> None:
        req = self._slot_req[slot]
        if req is not None:
            req.done = True
            # Completion IS the block eviction: every reference this
            # request held returns before the next chunk boundary's
            # admissions.
            self._release_slot(slot)
            now = time.perf_counter()
            latency = now - req.t_submit
            self.metrics.counter("completions_total").inc()
            self.metrics.counter("tokens_generated_total").inc(len(req.out))
            self.metrics.histogram("request_latency_s").observe(latency)
            self.journal.emit(
                "completion",
                rid=req.rid,
                trace=req.trace,
                slot=int(slot),
                tokens=len(req.out),
                latency_s=round(latency, 6),
                ttft_s=round(
                    (req.t_first if req.t_first is not None else now)
                    - req.t_submit,
                    6,
                ),
            )

    def _account_delivery(
        self, sp, valid, occupied: int, steps: int, walked=()
    ) -> None:
        """What a decode dispatch delivered, written into its span after
        the fetch and before the span closes: ``emitted``, the tokens it
        delivered to each resident request (one count per entry of the
        span's ``rids``, same order), and ``slot_steps``, the slot-steps
        it ran (``active`` × the steps of the program). A slot that
        finishes inside a chunk rides masked to the chunk's end: 1 − Σ
        emitted ÷ Σ slot_steps is that tail (under speculation, the
        rejected drafts' share). ``walked``, where the chunk read the
        pool through a live-block list, is the two counts the program
        sent along with its tokens: ``kv_blocks_live``, the list's real
        entries, and ``kv_blocks_read``, the entries one step of one
        layer walks (the first rounded up to whole tiles); the second ÷
        (``slots`` × a table's blocks) is the share of a whole-table
        read that is left."""
        if len(walked):
            sp.args["kv_blocks_live"] = int(walked[0])
            sp.args["kv_blocks_read"] = int(walked[1])
        sp.args["emitted"] = [
            int(valid[:, slot].sum())
            for slot, req in enumerate(self._slot_req) if req is not None
        ]
        sp.args["slot_steps"] = int(occupied) * int(steps)
        self.metrics.counter("decode_slot_steps_total").inc(
            sp.args["slot_steps"]
        )

    def _spec_dispatch(self, occupied: int):
        """One speculative decode tick (replaces the chunk scan when
        ``spec_draft > 0``): host-side prompt-lookup drafts per GREEDY
        slot (``serve_pool.lookup_draft`` over the request's own
        prompt + generated stream — no draft model), then ONE batched
        verify dispatch (:meth:`_verify_graph`) that scores every draft
        position and emits ``accepted + 1`` tokens per slot. Sampled
        slots ride along at draft length 0 (one ordinary pick — their
        PRNG chain is untouchable by speculation). Draft length is
        capped at remaining budget MINUS ONE — a verify round emits at
        most ``accepted + 1`` tokens, so the last position of a
        full-budget draft could never be consumed — which also keeps
        verify writes inside the blocks reserved at admission.

        NOTE: on greedy ticks this replaces the chunk scan, so
        tokens/dispatch is bounded by ``spec_draft + 1`` — where the
        fixed dispatch cost dominates (small models)
        a large ``chunk`` can beat speculation outright; measure both
        (docs/serving.md §speculation)."""
        s, d1 = self.slots, self.spec_draft + 1
        suffix = np.zeros((s, d1), np.int32)
        slens = np.ones((s,), np.int32)
        proposed = 0
        for slot, req in enumerate(self._slot_req):
            if req is None:
                continue
            suffix[slot, 0] = req.out[-1]
            if req.config.greedy:
                cap = min(
                    self.spec_draft, req.config.max_new - len(req.out) - 1
                )
                if cap <= 0:
                    continue  # last budgeted token: drafting is wasted work
                ctx = np.concatenate(
                    [req.tokens, np.asarray(req.out, np.int32)]
                )
                d = lookup_draft(ctx, cap, self.spec_ngram)
                if d:
                    suffix[slot, 1 : 1 + len(d)] = d
                    slens[slot] = 1 + len(d)
                    proposed += len(d)
        with self._state_lent(), self.spans.dispatch(
            names.SPAN_SPEC_VERIFY, draft=self.spec_draft,
            active=int(occupied),
            rids=[int(r.rid) for r in self._slot_req if r is not None],
        ) as sp:
            self._state, toks, valid = self._verify_jit(
                self.params,
                self._state,
                jnp.asarray(suffix),
                jnp.asarray(slens),
            )
            # D2H fetch = execution barrier (closes the span).
            toks = sp.fetch(toks)
            valid = np.asarray(valid)
            self._account_delivery(sp, valid, occupied, d1)
        accepted = int(valid.sum()) - int(occupied)
        self.metrics.counter("spec_tokens_proposed").inc(proposed)
        self.metrics.counter("spec_tokens_accepted").inc(accepted)
        self.journal.emit(
            "spec_verify",
            proposed=int(proposed),
            accepted=int(accepted),
            emitted=int(valid.sum()),
            active=int(occupied),
        )
        return np.asarray(toks), valid

    def step(self) -> bool:
        """One engine tick: admit queued requests into free slots (per-
        bucket prefill dispatches), then — if any slot is mid-generation —
        ONE compiled ``chunk``-token decode dispatch, then collect
        finished requests so their slots free for the next tick's
        admissions. Returns True while there is work left.

        Chunk boundaries are also where the lifecycle levers act (round
        16): overdue requests are cancelled first (freeing their slots),
        a pending weight swap applies once the last old-weight resident
        has finished, and admission is skipped while draining or while a
        swap is pending — so residents ALWAYS complete under the weights
        they were admitted with (the parity contract is per-admission)."""
        self._last_tick = time.time()  # /healthz heartbeat: engine ticking
        self._shed_overdue()
        self._cancel_overdue()
        self._maybe_apply_swap()
        if not self._draining and self._pending_swap is None:
            self._schedule()
            self._admit()
        occupied = sum(r is not None for r in self._slot_req)
        self.metrics.gauge("slots_busy").set(occupied)
        if occupied:
            # Speculate only when a greedy slot is resident: sampled
            # slots ride verify dispatches at draft 0 (one token each),
            # so an all-sampled tick through the verify graph would pay
            # one dispatch PER TOKEN — fall back to the chunk scan and
            # keep its chunk-way amortization instead.
            spec = self.spec_draft and any(
                r is not None and r.config.greedy for r in self._slot_req
            )
            t_dispatch = time.perf_counter()
            if spec:
                toks, valid = self._spec_dispatch(occupied)
            else:
                with self._state_lent(), self.spans.dispatch(
                    names.SPAN_DECODE_CHUNK, chunk=self.chunk,
                    active=int(occupied),
                    rids=[
                        int(r.rid) for r in self._slot_req if r is not None
                    ],
                ) as sp:
                    self._state, toks, valid = self._chunk_jit(
                        self.params, self._state
                    )
                    # D2H fetch = execution barrier (closes the span).
                    toks = sp.fetch(toks)
                    valid = np.asarray(valid)
                    # rows past the chunk: the live-block list's counts
                    toks, walked = toks[: self.chunk], toks[self.chunk:, 0]
                    self._account_delivery(
                        sp, valid, occupied, self.chunk, walked
                    )
            fin = np.asarray(self._state.finished)
            emitted = 0
            for slot, req in enumerate(self._slot_req):
                if req is None:
                    continue
                picked = [int(t) for t in toks[valid[:, slot], slot]]
                req.out.extend(picked)
                emitted += len(picked)
                if fin[slot]:
                    self._finish(slot)
            # Per-token EWMA (round 21): one decode dispatch's wall time
            # over the tokens it emitted — the evidence the hopeless-shed
            # predicate runs on. EWMA (not last-sample) so one slow tick
            # (GC pause, cold path) cannot trigger a shed storm.
            if emitted:
                if self._tok_first_dispatch:
                    # Compile-bearing measurement: discard (see __init__).
                    self._tok_first_dispatch = False
                else:
                    inst = (time.perf_counter() - t_dispatch) / emitted
                    self._tok_ewma = (
                        inst if self._tok_ewma is None
                        else 0.8 * self._tok_ewma + 0.2 * inst
                    )
            # Re-read after _finish frees slots: the tick that completes
            # the last request must leave the gauge at 0 (an idle server
            # must not scrape as busy forever).
            self.metrics.gauge("slots_busy").set(
                sum(r is not None for r in self._slot_req)
            )
        return not self.idle()

    def idle(self) -> bool:
        return not self._queue and all(r is None for r in self._slot_req)

    # -- drain + live weight swap (round 16, docs/serving.md §fleet) -------

    def drain(self) -> None:
        """Graceful stop: close admission (``submit()`` raises from now
        on; queued-but-unadmitted requests stay queued for the caller to
        re-route) and run the engine until every RESIDENT request has
        finished. Idempotent — a second call returns immediately once
        the slots are empty. This is the graceful half of both failover
        (a replica told to retire finishes what it holds, loses nothing)
        and weight swap."""
        if not self._draining:
            self._draining = True
            self.journal.emit(
                "serve_drain",
                residents=sum(r is not None for r in self._slot_req),
                queued=len(self._queue),
            )
        while any(r is not None for r in self._slot_req):
            self.step()

    @property
    def draining(self) -> bool:
        return self._draining

    def request_swap(self, params, *, step=None, source=None) -> None:
        """Arm a live weight swap: ``params`` replaces the served tree at
        the first chunk boundary with NO residents (admission pauses
        until then, so every request completes under the weights it was
        admitted with — the parity contract is per-admission). Nothing
        recompiles: params are runtime arguments of every compiled
        graph. ``decode_matmul_dtype`` re-quantizes the incoming tree,
        keeping the weight-only discipline across swaps."""
        if self.decode_matmul_dtype is not None:
            params = self.model.decode_weights(
                params, self.decode_matmul_dtype
            )
        self._pending_swap = (params, step, source)
        self.journal.emit(
            "weight_swap_requested",
            step=None if step is None else int(step),
            source=source,
        )
        self._maybe_apply_swap()  # an idle server swaps immediately

    def swap_from_checkpoint(
        self, checkpoint_dir: str | None = None, *, optimizer=None
    ) -> int | None:
        """Adopt the newest CRC-verified checkpoint under
        ``checkpoint_dir`` (default: the directory this server restored
        from) if it is NEWER than the served step — the serving end of
        the train→publish→serve loop (a DiLoCo trainer keeps
        checkpointing; replicas pick the steps up without dropping a
        single resident). Returns the adopted step, or None when there
        is nothing newer (no swap armed). Restores through
        :func:`canonical_lm_params`, so any training layout publishes."""
        d = checkpoint_dir or self.checkpoint_dir
        if d is None:
            raise ValueError(
                "no checkpoint_dir: construct via from_checkpoint or pass "
                "one explicitly"
            )
        opt = optimizer if optimizer is not None else self._restore_optimizer
        params, step = canonical_lm_params(self.model, d, optimizer=opt)
        if self.checkpoint_step is not None and step <= self.checkpoint_step:
            return None
        self.checkpoint_dir = d
        self.request_swap(params, step=int(step), source=d)
        return int(step)

    def _maybe_apply_swap(self) -> None:
        if self._pending_swap is None:
            return
        if any(r is not None for r in self._slot_req):
            return  # old-weight residents still decoding: wait
        params, step, source = self._pending_swap
        self._pending_swap = None
        old = self.checkpoint_step
        self.params = params
        if self._prefix is not None:
            # The radix caches K/V computed under the OLD weights; a
            # post-swap prefix hit would splice stale keys into a
            # new-weights stream and silently break the parity contract.
            # No residents exist here, so every cached block is
            # cache-only (refcount 1) and evictable — flush them all.
            self._prefix.evict(self._prefix.evictable_blocks())
            self.metrics.gauge("kv_blocks_used").set(
                self._alloc.used_blocks
            )
        if step is not None:
            self.checkpoint_step = int(step)
        self.metrics.counter("weight_swaps_total").inc()
        self.journal.emit(
            "weight_swap",
            step=None if step is None else int(step),
            from_step=old,
            source=source,
        )

    def health(self) -> dict:
        """The /healthz payload: engine heartbeat age (seconds since the
        last ``step()`` tick — an idle-but-alive server reads old, a
        wedged one reads ancient; the scraper applies the SLO), the
        occupancy the admission controller sees, and the round-16
        routing signals (queue saturation, draining, swap state, served
        checkpoint step)."""
        return {
            "heartbeat_age_s": round(time.time() - self._last_tick, 3),
            "slots_busy": sum(r is not None for r in self._slot_req),
            "slots": self.slots,
            "queue_depth": len(self._queue),
            "queue_limit": self.queue_limit,
            "queue_saturation": (
                round(len(self._queue) / self.queue_limit, 3)
                if self.queue_limit
                else 0.0
            ),
            "draining": self._draining,
            "swap_pending": self._pending_swap is not None,
            "checkpoint_step": self.checkpoint_step,
            "kv_blocks_free": (
                self._alloc.free_blocks if self._alloc is not None else None
            ),
        }

    def shutdown(self) -> None:
        """Graceful stop: :meth:`drain` (admission closed, residents
        finished — nothing in flight is dropped), then stop the live
        exporter (if armed). The engine itself holds no threads — jit
        caches and device state die with the object."""
        self.drain()
        if self.exporter is not None:
            self.exporter.stop()
            self.exporter = None

    def done(self, rid: int) -> bool:
        """True once the request reached a terminal state (finished,
        cancelled, or shed) — the poll half of the submit/step/result
        cycle a replica worker loop drives."""
        req = self._results[rid]
        return req.done or req.cancelled or req.shed

    def result(self, rid: int) -> np.ndarray:
        """Generated tokens of a finished request (prompt excluded).
        Consumes the record — a second read raises — so a long-lived
        server does not accumulate every request it ever served. A
        deadline-cancelled request raises :class:`RequestCancelled`, a
        shed one :class:`RequestShed` (record consumed either way)."""
        req = self._results[rid]
        if req.shed:
            del self._results[rid]
            raise RequestShed(
                f"request {rid} was shed before prefill (deadline "
                "unreachable or displaced under saturation)"
            )
        if req.cancelled:
            del self._results[rid]
            raise RequestCancelled(
                f"request {rid} was cancelled at a chunk boundary "
                "(deadline exceeded)"
            )
        if req.migrated:
            # NOT consumed: take_export() owns this record — result()
            # must not destroy the payload a confused caller probed.
            raise RuntimeError(
                f"request {rid} migrated — take_export() owns its "
                "payload; the decode leg's result is the stream"
            )
        if not req.done:
            raise RuntimeError(f"request {rid} is not finished")
        del self._results[rid]
        return np.asarray(req.out, np.int32)

    # -- convenience entries ----------------------------------------------

    def generate(
        self, prompts, configs: GenerationConfig | list | None = None
    ) -> list[np.ndarray]:
        """Serve a batch of token prompts to completion; returns each
        request's generated tokens in submission order."""
        if configs is None or isinstance(configs, GenerationConfig):
            configs = [configs] * len(prompts)
        rids = [
            self.submit(p, c) for p, c in zip(prompts, configs, strict=True)
        ]
        while self.step():
            pass
        return [self.result(r) for r in rids]

    def serve_text(self, texts: list[str], **gen_kwargs) -> list[str]:
        """Text in → text out: encode with the served tokenizer, generate,
        decode (EOS and padding drop out in ``tokenizer.decode``). By
        default requests stop at the tokenizer's EOS id."""
        if self.tokenizer is None:
            raise ValueError("no tokenizer attached (pass one, or use "
                             "from_checkpoint with a shipped tokenizer.json)")
        gen_kwargs.setdefault("eos_id", self.tokenizer.eos_id)
        cfg = GenerationConfig(**gen_kwargs)
        prompts = [self.tokenizer.encode(t) for t in texts]
        return self.tokenizer.decode_batch(self.generate(prompts, cfg))
