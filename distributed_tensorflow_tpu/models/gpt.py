"""Autoregressive LM family: GPT-style causal decoder with KV-cache decoding.

The reference has no sequence models and no generative path at all (its one
model is the fixed-feature MLP classifier, SURVEY.md §2 C8; its only
"inference" is the in-loop accuracy fetch, reference tfsingle.py:94). This
family completes the framework's long-context story on the *generation*
side: the training forward is the same causal-attention machinery the
transformer classifier proves (dense or Pallas flash), and decoding is the
idiomatic TPU inference shape —

- **static shapes everywhere**: the KV cache is allocated at ``max_len`` up
  front and written with ``dynamic_update_slice``; the growing sequence
  never changes a compiled shape, so one executable serves every step;
- **layers as a scanned stack**: block parameters carry a leading
  ``num_layers`` axis and the forward is one ``lax.scan`` over it — one
  trace and one HLO body regardless of depth (no Python-unrolled layers);
- **decode loop as ``lax.scan``**: greedy generation compiles into a single
  dispatch, token round-trips never touch the host.

Architecture: token embed → +learned positions → N pre-LN blocks
(causal attention + GELU MLP, residuals) → final LN → logits through the
tied embedding (lm_head = embedᵀ). All matmuls in ``compute_dtype`` with
f32 accumulation; layernorm/softmax/loss f32.
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from distributed_tensorflow_tpu.models.base import layernorm as _layernorm
from distributed_tensorflow_tpu.observability import names
from distributed_tensorflow_tpu.ops.collectives import to_varying
from distributed_tensorflow_tpu.ops.quantized import (
    QuantizedLinear,
    dequantize_kv,
    kv_storage_dtype,
    quantize_kv,
    wo_dot,
)
from distributed_tensorflow_tpu.ops.ring_attention import dense_attention


def _rope(x, positions, base: float = 10000.0):
    """Rotary position embedding on [B, L, H, Dh] at absolute ``positions``
    [L] (shared across the batch) or [B, L] (per-row — the slot-decode
    path, where every serving slot sits at its own sequence position):
    pairs (x_i, x_{i+Dh/2}) rotate by pos·base^(−2i/Dh). Computed in
    f32, cast back — relative-position attention without any learned table,
    the modern LM default (absent from the reference, which has no sequence
    models at all)."""
    b, l, h, dh = x.shape
    half = dh // 2
    freqs = base ** (-jnp.arange(half, dtype=jnp.float32) / half)
    # [L, half] or [B, L, half]; the head axis slots in before `half`, and
    # leading-batch broadcasting aligns both layouts against [B, L, H, half].
    ang = positions.astype(jnp.float32)[..., :, None] * freqs
    cos = jnp.expand_dims(jnp.cos(ang), -2)
    sin = jnp.expand_dims(jnp.sin(ang), -2)
    x32 = x.astype(jnp.float32)
    x1, x2 = x32[..., :half], x32[..., half:]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1
    ).astype(x.dtype)


class GPTBlockParams(NamedTuple):
    """One decoder block; every leaf carries a leading [num_layers] axis in
    ``GPTLMParams.blocks`` so the forward can scan over the stack."""

    ln1_scale: jax.Array
    ln1_bias: jax.Array
    wq: jax.Array  # [d, d]
    wk: jax.Array
    wv: jax.Array
    wo: jax.Array
    ln2_scale: jax.Array
    ln2_bias: jax.Array
    w_up: jax.Array  # [d, 4d]
    b_up: jax.Array
    w_down: jax.Array  # [4d, d]
    b_down: jax.Array


class GPTMoEBlockParams(NamedTuple):
    """Decoder block whose FFN is a Switch-style top-1 MoE
    (ops/moe.py): attention fields as in :class:`GPTBlockParams`, FFN
    weights stacked over experts (axis 1; axis 0 remains num_layers)."""

    ln1_scale: jax.Array
    ln1_bias: jax.Array
    wq: jax.Array
    wk: jax.Array
    wv: jax.Array
    wo: jax.Array
    ln2_scale: jax.Array
    ln2_bias: jax.Array
    wg: jax.Array  # [n, d, E] gate
    w_up: jax.Array  # [n, E, d, 4d]
    b_up: jax.Array  # [n, E, 4d]
    w_down: jax.Array  # [n, E, 4d, d]
    b_down: jax.Array  # [n, E, d]


class GPTLMParams(NamedTuple):
    embed: jax.Array  # [vocab, d] (also the tied LM head)
    pos: jax.Array  # [max_len, d]
    blocks: GPTBlockParams  # leaves stacked over num_layers
    lnf_scale: jax.Array
    lnf_bias: jax.Array


class SlotKVCache(NamedTuple):
    """Serving-side decode state over a fixed bank of request SLOTS: like
    :class:`KVCache` but with a PER-SLOT length — every batch row is an
    independent request at its own sequence position, which is what
    continuous batching needs (slots free and refill at different times;
    a shared scalar length would drain the whole bank to the longest
    request). Written by :meth:`GPTLM.prefill_slots` /
    :meth:`GPTLM.decode_slots`; the text layer on top is ``serve.py``.

    ``kv_dtype="int8"|"fp8"`` (round 15) stores the payload in 1-byte
    elements with the per-row symmetric scales riding as the
    ``k_scale``/``v_scale`` side tensors (``ops/quantized.quantize_kv``
    granularity: one f32 per written position per KV head). Quantization
    happens ON WRITE and dequantization ON READ inside the attention
    math, so the contract stays "same math, fewer bytes" up to the
    committed rounding; ``kv_dtype="bf16"`` (the default) keeps scales
    ``None`` and is bitwise the round-9/11 layout."""

    k: jax.Array  # [num_layers, S, cache_len, Hkv, Dh]
    v: jax.Array  # [num_layers, S, cache_len, Hkv, Dh]
    lengths: jax.Array  # [S] int32 — tokens written into each slot's cache
    k_scale: jax.Array | None = None  # [num_layers, S, cache_len, Hkv] f32
    v_scale: jax.Array | None = None


class PagedKVCache(NamedTuple):
    """Serving-side decode state over a shared BLOCK POOL (vLLM's
    PagedAttention layout): K/V for every slot live in one pool of
    fixed-size blocks, and each slot maps logical position ``p`` to
    ``pool[block_tables[s, p // bs], p % bs]``. Occupancy scales with
    blocks actually held, not ``slots × max_len`` slabs — the paged
    engine (``serve.py paged=True``) admits by free blocks, and two
    slots may map the SAME physical block for a shared prompt prefix
    (copy-on-write via the host-side refcounts in ``serve_pool.py``;
    shared blocks are immutable full prompt blocks, so no copy ever
    happens). Written by :meth:`GPTLM.extend_paged` /
    :meth:`GPTLM.decode_paged`; device primitives in
    ``ops/paged_attention.py``. Unused table entries read garbage that
    the validity masks keep out of every softmax (the stale-bytes-
    unreachable stance of :class:`SlotKVCache`).

    ``kv_dtype="int8"|"fp8"`` (round 15): payload blocks shrink to
    1-byte elements and the per-row scales ride as ``k_scale``/
    ``v_scale`` side pools indexed by the SAME (block, position, head)
    coordinates — the block-table gather/scatter index math applies to
    them unchanged, and COW prefix sharing shares a block's scales with
    the block (one refcount covers both; scales are never packed into
    the payload). ``kv_dtype="bf16"`` keeps scales ``None``: the
    round-11 bitwise path.

    A model whose layers keep state of their own per slot
    (:class:`~.hybrid.HybridLM`: a window layer's ring of K/V blocks, a
    delta-rule layer's recurrent and convolution states) holds it in
    ``slot_state``, a dict of arrays (or tuples of them) that the model
    declares in its ``empty_paged_cache`` and alone reads; the pool and
    its tables then hold the full-attention layers alone. An extend over
    fewer rows than there are slots names each row's slot in
    ``slot_of_row`` (None: row ``s`` is slot ``s``)."""

    k: jax.Array  # [num_layers, num_blocks, block_size, Hkv, Dh]
    v: jax.Array  # [num_layers, num_blocks, block_size, Hkv, Dh]
    block_tables: jax.Array  # [S, max_blocks] int32 — physical block ids
    lengths: jax.Array  # [S] int32 — tokens written for each slot
    k_scale: jax.Array | None = None  # [num_layers, num_blocks, bs, Hkv] f32
    v_scale: jax.Array | None = None
    slot_state: dict | None = None  # the model's per-slot state, by name
    slot_of_row: jax.Array | None = None  # [rows] int32


class KVCache(NamedTuple):
    """Decode state: per-layer keys/values at a static cache length, plus
    the number of tokens decoded so far (``length`` is ABSOLUTE — it keeps
    counting past the cache size on the rolling path).

    Cache length is ``max_len`` for full-attention models; for windowed
    models it is only ``min(window, max_len)`` — slots are written mod W
    (a rolling buffer), because a sliding-window query can never attend
    anything older. Decode memory and per-step attention are O(W), not
    O(max_len)."""

    k: jax.Array  # [num_layers, B, cache_len, Hkv, Dh]
    v: jax.Array  # [num_layers, B, cache_len, Hkv, Dh]
    length: jax.Array  # scalar int32


class GPTLM:
    """tokens [B, L] int32 → next-token logits [B, L, vocab].

    ``remat=True`` (``"selective"`` means the same) checkpoints each
    block and keeps what costs more to replay than to hold: the flash
    kernel's output and log-sum-exp where the kernel is engaged and,
    under tensor parallelism, the residual stream after the attention's
    output was summed across chips. That is two ``[B, L, d]`` values a
    layer, three under ``tp``, where a checkpoint that keeps nothing
    holds one: a run at long context that wants the least memory passes
    ``remat=jax.checkpoint_policies.nothing_saveable`` (any callable is
    handed to ``jax.checkpoint`` as its policy). The constructor's
    comment has the list and the measured gain."""

    def __init__(
        self,
        vocab_size: int = 256,
        max_len: int = 128,
        model_dim: int = 64,
        num_heads: int = 4,
        num_kv_heads: int | None = None,
        num_layers: int = 2,
        compute_dtype: jnp.dtype = jnp.bfloat16,
        attention_impl: str = "xla",
        window: int | None = None,
        moe_experts: int | None = None,
        moe_capacity_factor: float = 2.0,
        moe_balance_coef: float = 1e-2,
        moe_z_coef: float = 1e-3,
        moe_top_k: int = 1,
        pos_embedding: str = "learned",
        remat: bool | str = False,
        flash_min_len: int | None = None,
        matmul_dtype: str | None = None,
    ):
        assert model_dim % num_heads == 0
        if attention_impl not in ("xla", "flash"):
            raise ValueError(
                f"unknown attention_impl {attention_impl!r}; xla|flash"
            )
        if window is not None and window < 1:
            raise ValueError(f"window must be >= 1, got {window}")
        if moe_experts is not None and moe_experts < 2:
            raise ValueError(f"moe_experts must be >= 2, got {moe_experts}")
        if moe_top_k < 1 or (
            moe_experts is not None and moe_top_k > moe_experts
        ):
            raise ValueError(
                f"moe_top_k {moe_top_k} must be in [1, moe_experts"
                f"={moe_experts}]"
            )
        if moe_top_k > 1 and moe_experts is None:
            raise ValueError(
                f"moe_top_k={moe_top_k} requires a MoE model "
                "(set moe_experts)"
            )
        if pos_embedding not in ("learned", "rope"):
            raise ValueError(
                f"unknown pos_embedding {pos_embedding!r}; learned|rope"
            )
        if pos_embedding == "rope" and (model_dim // num_heads) % 2:
            raise ValueError(
                f"rope needs an even head_dim, got {model_dim // num_heads}"
            )
        if num_kv_heads is None:
            num_kv_heads = num_heads
        if num_kv_heads < 1:
            raise ValueError(f"num_kv_heads must be >= 1, got {num_kv_heads}")
        if num_heads % num_kv_heads:
            raise ValueError(
                f"num_heads {num_heads} must be a multiple of num_kv_heads "
                f"{num_kv_heads}"
            )
        self.vocab_size = vocab_size
        self.max_len = max_len
        self.model_dim = model_dim
        self.num_heads = num_heads
        self.num_kv_heads = num_kv_heads
        self.head_dim = model_dim // num_heads
        self.num_layers = num_layers
        self.compute_dtype = compute_dtype
        self.attention_impl = attention_impl
        self.window = window
        self.moe_experts = moe_experts
        self.moe_capacity_factor = moe_capacity_factor
        # Top-k routing width (ops/moe._route): 1 = Switch (raw-prob
        # combine), ≥2 = standard top-k (probs renormalized over the
        # chosen experts, GShard choice-major capacity priority).
        self.moe_top_k = moe_top_k
        # Switch load-balance + ST-MoE router-z coefficients (ops/moe.MoEAux);
        # both enter the training loss via loss_and_metrics. The defaults are
        # the papers' standard settings (1e-2 balance, 1e-3 z).
        self.moe_balance_coef = moe_balance_coef
        self.moe_z_coef = moe_z_coef
        self.pos_embedding = pos_embedding
        # attention_impl="flash" applies the kernel only at
        # L >= flash_min_len and falls back to the mathematically
        # identical dense path below. None → the ONE measured crossover
        # shared by every model (ops/pallas_attention.FLASH_MIN_LEN — its
        # comment has the numbers and the re-measure tool), resolved
        # LAZILY at forward time (models/base.resolve_flash_min_len) so
        # xla models never import Pallas; 0 forces the kernel at every
        # length (tests do, to exercise it at toy L).
        self.flash_min_len = flash_min_len
        # (mesh, batch axis | None, head axis | None) when this model's
        # forward runs inside a GSPMD-sharded program: the flash kernel
        # is then mapped per device (_flash_attend), and a head axis
        # says the block's row-split products are summed across chips
        # (_tensor_parallel). Not a constructor knob — the trainer that
        # owns the mesh sets it on its own copy.
        self.attention_shard = None
        # The data axis over which this copy's backward sums the layer
        # stack's weight gradients itself, one layer late
        # (_stack_summed_late, value_and_grad_summed); None: the plain
        # scan. Not a constructor knob either — the tp trainer sets it on
        # its own copy when its mesh has more than one data member.
        self.grad_sum_axis = None
        # jax.checkpoint around each scanned block: the backward replays a
        # block from its input instead of holding every activation of
        # every layer. What a layer's checkpoint KEEPS is what costs more
        # to replay than to hold (_remat_policy):
        #   True, "selective" — the flash kernel's output and its
        #                 log-sum-exp ([B, L, d] + [B, L, H] a layer),
        #                 tagged only where the kernel is engaged, so the
        #                 dense fallback and lengths under flash_min_len
        #                 keep nothing; and, where the trainer runs this
        #                 model under a tensor-parallel axis
        #                 (attention_shard names a head axis), the
        #                 residual stream after the attention's row-split
        #                 product was summed over that axis ([B, L, d]),
        #                 so the replay is ln1, q/k/v, ln2, the
        #                 up-projection and the GELU: no flash forward,
        #                 no `wo` product, no second all-reduce. On one
        #                 chip nothing is exchanged and the `wo` product
        #                 replays at the MXU's rate, so that value is
        #                 not kept there. Grad-identical to remat=False
        #                 (tests/test_gpt.py, tests/test_lm_trainer.py).
        #   callable    — passed straight to jax.checkpoint(policy=...);
        #                 jax.checkpoint_policies.nothing_saveable holds
        #                 the least a checkpoint can (one [B, L, d] a
        #                 layer: the meaning of True before PR 31) and
        #                 replays the kernel and the exchange.
        # MEASURED (TPU v5e, 1,024-token rows, flash engaged; PERF.md
        # section 6, PR 31), against nothing_saveable: gpt2-medium on one
        # chip at batch 8, +4% tokens/s for +0.8 GB (9.05 GB of 16: the
        # replayed kernel was 24 ms of a 283 ms step, and copying what is
        # kept into and out of the layers' stack gives 12 of them back);
        # gpt2-large under a 2x2 data x model mesh at batch 16, +8%
        # tokens/s for +2.3 GB a chip (five exposed all-reduces a layer,
        # not six). At toy widths or on the dense fallback nothing is
        # tagged, so nothing is kept and nothing is lost.
        # Every forward path (scanned stack, sp/ep bodies, pipeline
        # stages) routes through _remat_wrap, so the policy reaches every
        # dp_mode. The shard_map sp ring does not thread the save names:
        # there every mode is nothing_saveable (correct, no savings).
        if not (
            isinstance(remat, bool)
            or remat == "selective"
            or callable(remat)
        ):
            raise ValueError(
                f"remat must be False, True, 'selective', or a "
                f"jax.checkpoint policy callable; got {remat!r}"
            )
        self.remat = remat
        # Opt-in low-precision projection matmuls (ops/quantized.py):
        # None | "int8" | "fp8". Covers the block QKV/out projections and
        # the dense FFN pair wherever the model runs (training forward,
        # prefill, decode) — NOT the logits head (tied embedding, kept at
        # compute_dtype) and NOT MoE expert matmuls (ops/moe keeps its
        # own dtype discipline). Forward in the reduced dtype with
        # dynamic symmetric scales, backward straight-through at full
        # precision; the contract is the synthetic-corpus loss-parity
        # guard in tests/test_quantized.py. Not measured on the chip:
        # int8 is the v5e MXU's native double-rate regime.
        if matmul_dtype is not None:
            from distributed_tensorflow_tpu.ops.quantized import (
                MATMUL_DTYPES,
            )

            if matmul_dtype not in MATMUL_DTYPES:
                raise ValueError(
                    f"unknown matmul_dtype {matmul_dtype!r}; None or one "
                    f"of {MATMUL_DTYPES}"
                )
        self.matmul_dtype = matmul_dtype

    # -- init --------------------------------------------------------------

    def init(self, seed: int = 1) -> GPTLMParams:
        d = self.model_dim
        n = self.num_layers
        keys = jax.random.split(jax.random.key(seed), 7)

        def dense_init(key, shape):
            # fan-in scaled; leading num_layers axis gets independent draws
            return jax.random.normal(key, shape, jnp.float32) / jnp.sqrt(
                shape[-2]
            )

        attn = dict(
            ln1_scale=jnp.ones((n, d), jnp.float32),
            ln1_bias=jnp.zeros((n, d), jnp.float32),
            wq=dense_init(keys[2], (n, d, d)),
            # GQA: k/v project to num_kv_heads·head_dim (≤ d); query head
            # groups share KV heads in the attention kernels, and the
            # decode cache shrinks by the same factor.
            wk=dense_init(keys[3], (n, d, self.num_kv_heads * self.head_dim)),
            wv=dense_init(keys[4], (n, d, self.num_kv_heads * self.head_dim)),
            # residual-path projections start at zero: the depth-N stack
            # begins as the identity, a stable start at any depth.
            wo=jnp.zeros((n, d, d), jnp.float32),
            ln2_scale=jnp.ones((n, d), jnp.float32),
            ln2_bias=jnp.zeros((n, d), jnp.float32),
        )
        if self.moe_experts is None:
            blocks = GPTBlockParams(
                **attn,
                w_up=dense_init(keys[5], (n, d, 4 * d)),
                b_up=jnp.zeros((n, 4 * d), jnp.float32),
                w_down=jnp.zeros((n, 4 * d, d), jnp.float32),
                b_down=jnp.zeros((n, d), jnp.float32),
            )
        else:
            e = self.moe_experts
            blocks = GPTMoEBlockParams(
                **attn,
                wg=dense_init(keys[6], (n, d, e)),
                w_up=dense_init(keys[5], (n, e, d, 4 * d)),
                b_up=jnp.zeros((n, e, 4 * d), jnp.float32),
                w_down=jnp.zeros((n, e, 4 * d, d), jnp.float32),
                b_down=jnp.zeros((n, e, d), jnp.float32),
            )
        return GPTLMParams(
            embed=0.02
            * jax.random.normal(keys[0], (self.vocab_size, d), jnp.float32),
            # under rope the table is unused (kept zero so the params
            # pytree, TP specs, and checkpoints are layout-identical
            # across both position schemes)
            pos=(
                0.02
                * jax.random.normal(keys[1], (self.max_len, d), jnp.float32)
                if self.pos_embedding == "learned"
                else jnp.zeros((self.max_len, d), jnp.float32)
            ),
            blocks=blocks,
            lnf_scale=jnp.ones((d,), jnp.float32),
            lnf_bias=jnp.zeros((d,), jnp.float32),
        )

    def partition_specs(self, model_axis: str = "model") -> GPTLMParams:
        """Megatron-style tensor-parallel layout over ``model_axis`` (same
        convention as ``MLP.partition_specs``; every block leaf keeps its
        leading num_layers axis unsharded).

        Attention: wq/wk/wv column-split on their output dim — the split
        lands on whole heads as long as the axis size divides num_heads
        (and, under GQA, num_kv_heads: wk/wv only have num_kv_heads·head_dim
        columns; a mid-KV-head split stays numerically correct under GSPMD
        but loses the whole-head one-all-reduce layout) —
        and wo row-split, so attention computes on local head groups with
        one all-reduce after the output projection. MLP: w_up column-split,
        w_down row-split (all-reduce after). Embeddings, positions, norms,
        and biases on the residual stream stay replicated. Apply by placing
        params with ``NamedSharding(mesh, spec)`` and calling the ordinary
        jitted step — GSPMD inserts the collectives."""
        if self.moe_experts is not None:
            raise NotImplementedError(
                "tensor parallelism is not defined for the MoE blocks; "
                "use expert parallelism (apply_expert_parallel)"
            )
        from jax.sharding import PartitionSpec as P

        return GPTLMParams(
            embed=P(),
            pos=P(),
            blocks=GPTBlockParams(
                ln1_scale=P(),
                ln1_bias=P(),
                wq=P(None, None, model_axis),
                wk=P(None, None, model_axis),
                wv=P(None, None, model_axis),
                wo=P(None, model_axis, None),
                ln2_scale=P(),
                ln2_bias=P(),
                w_up=P(None, None, model_axis),
                b_up=P(None, model_axis),
                w_down=P(None, model_axis, None),
                b_down=P(),
            ),
            lnf_scale=P(),
            lnf_bias=P(),
        )

    # -- shared pieces -----------------------------------------------------

    def _dot_full(self, x, w):
        """compute_dtype matmul with f32 accumulation — the always-full-
        precision dot (the logits/tied-embedding head, and every
        projection when ``matmul_dtype`` is unset)."""
        cd = self.compute_dtype
        return jnp.dot(
            x.astype(cd), w.astype(cd), preferred_element_type=jnp.float32
        )

    def _dot(self, x, w):
        """Block-projection matmul (QKV/out and the dense-FFN pair,
        training AND decode): ``matmul_dtype`` reroutes it through
        :func:`~ops.quantized.quantized_dot` — int8/fp8 forward on the
        MXU's native low-precision path, exact full-precision backward
        (straight-through). The logits head stays on :meth:`_dot_full`
        (quantizing the tied-embedding head measurably hurts loss), and
        MoE expert matmuls stay at compute_dtype (``_moe_block_ffn``
        routes through ops/moe, which the ``matmul_dtype`` contract
        deliberately excludes — see __init__).

        Round 15: a :class:`~ops.quantized.QuantizedLinear` leaf (the
        pre-quantized weight-only serving params from
        :meth:`decode_weights`) routes through
        :func:`~ops.quantized.wo_dot` instead — full-precision
        activations against 1-byte weights, forward-only, the same
        exclusion rule (logits head and MoE experts never carry
        QuantizedLinear leaves)."""
        if isinstance(w, QuantizedLinear):
            return wo_dot(x, w.qw, w.scale, self.compute_dtype)
        if self.matmul_dtype is None:
            return self._dot_full(x, w)
        from distributed_tensorflow_tpu.ops.quantized import quantized_dot

        return quantized_dot(self.matmul_dtype, x, w)

    def decode_weights(self, params: GPTLMParams, dtype: str) -> GPTLMParams:
        """Pre-quantize the decode projection weights ONCE (at restore):
        the block QKV/out projections and — for dense blocks — the FFN
        pair become :class:`~ops.quantized.QuantizedLinear` leaves
        (int8/fp8 payload + per-output-column f32 scales), which
        :meth:`_dot` routes through ``wo_dot`` wherever the returned
        params run. The round-13 exclusion rule holds: the logits head
        (tied embedding) and MoE expert matmuls stay full-precision —
        MoE blocks quantize only their attention projections. Decode
        reads every projection weight per token, so this halves (int8)
        the weight half of decode's HBM traffic; the returned tree is a
        SERVING artifact — it is not trainable (``wo_dot`` is
        forward-only) and not checkpoint-compatible (quantize at restore
        from the full-precision checkpoint, never persist)."""
        from distributed_tensorflow_tpu.ops.quantized import (
            MATMUL_DTYPES,
            quantize_linear_columns,
        )

        if dtype not in MATMUL_DTYPES:
            raise ValueError(
                f"unknown decode weight dtype {dtype!r}; one of "
                f"{MATMUL_DTYPES}"
            )
        names = ("wq", "wk", "wv", "wo")
        if self.moe_experts is None:
            names += ("w_up", "w_down")
        repl = {
            nm: quantize_linear_columns(getattr(params.blocks, nm), dtype)
            for nm in names
        }
        return params._replace(blocks=params.blocks._replace(**repl))

    def _kv_quant_dtype(self, cache) -> str | None:
        """The serving cache's quantized-dtype name ("int8"/"fp8"), or
        None for the bf16 identity layout — derived from the cache
        itself (payload dtype + scale presence), so one model instance
        serves every layout and the default path stays byte-identical
        to round 11."""
        if getattr(cache, "k_scale", None) is None:
            return None
        return "int8" if cache.k.dtype == jnp.int8 else "fp8"

    @property
    def _tensor_parallel(self) -> bool:
        """Whether this copy's forward runs under a tensor-parallel mesh
        axis (the trainer said so through ``attention_shard``)."""
        return (
            self.attention_shard is not None
            and self.attention_shard[2] is not None
        )

    def _remat_policy(self):
        """The jax.checkpoint policy for the current ``remat`` value: a
        callable as given, else keep the values this model names — the
        flash kernel's output and log-sum-exp (``_flash_attend``) and,
        under tensor parallelism, the attention's summed output
        (``_block``). A name no one tagged keeps nothing."""
        if callable(self.remat):
            return self.remat
        from distributed_tensorflow_tpu.ops.pallas_attention import (
            REMAT_SAVE_NAMES,
            REMAT_SAVE_TP_SUM,
        )

        return jax.checkpoint_policies.save_only_these_names(
            *REMAT_SAVE_NAMES, REMAT_SAVE_TP_SUM
        )

    def _remat_wrap(self, body):
        """``jax.checkpoint`` around a scanned-block (or pipeline-stage)
        body per the ``remat`` knob — the ONE wrapper every forward path
        uses, so a policy mode reaches dense/sp/ep/pp identically."""
        if not self.remat:
            return body
        return jax.checkpoint(body, policy=self._remat_policy())

    def _attend(self, q, k, v, kv_lens=None):
        from distributed_tensorflow_tpu.models.base import (
            resolve_flash_min_len,
        )

        if self.attention_impl == "flash" and q.shape[1] >= (
            resolve_flash_min_len(self.flash_min_len)
        ):
            return self._flash_attend(q, k, v, kv_lens)
        return dense_attention(
            q, k, v, causal=True, window=self.window, kv_lens=kv_lens
        )

    def _flash_attend(self, q, k, v, kv_lens):
        """The Pallas attention kernel on [B, L, H, Dh]. The chip's
        compiler cannot partition a kernel ("Mosaic kernels cannot be
        automatically partitioned"), so under a GSPMD-sharded program
        (``attention_shard`` set by the trainer that owns the mesh) the
        call is mapped per device over the batch and head axes; inside
        an enclosing ``shard_map`` the outputs are typed with the
        inputs' varying axes.

        The kernel runs its products in its operands' type, so q, k, v
        are handed over in ``compute_dtype`` like every other product of
        the step (they leave ``_dot`` as float32 sums); the output and
        dq, dk, dv come back in it, the log-sum-exp in float32."""
        from distributed_tensorflow_tpu.ops.pallas_attention import (
            REMAT_SAVE_NAMES,
            flash_attention,
        )

        cd = self.compute_dtype
        q, k, v = q.astype(cd), k.astype(cd), v.astype(cd)

        # Under remat, name out+lse so the layer's checkpoint keeps them
        # and the backward's replay skips the O(L²)-work forward kernel
        # (the rebuild composition — see flash_attention_with_lse). A
        # policy that does not list the names replays it as before.
        names = REMAT_SAVE_NAMES if self.remat else None

        def kernel(q, k, v, *lens):
            return flash_attention(
                q, k, v, causal=True, window=self.window,
                kv_lens=lens[0] if lens else None, save_names=names,
                vma=tuple(jax.typeof(q).vma) or None,
            )

        lens = () if kv_lens is None else (kv_lens,)
        if self.attention_shard is None:
            return kernel(q, k, v, *lens)
        from jax.sharding import PartitionSpec as P

        mesh, batch_axis, head_axis = self.attention_shard
        manual = set(jax.sharding.get_abstract_mesh().manual_axes)
        auto = set(mesh.axis_names) - manual
        if not auto:  # wholly inside an enclosing shard_map already
            return kernel(q, k, v, *lens)

        def split(axis, *dims):
            # An axis maps a dimension only where it divides it (a tail
            # eval chunk, odd head counts); otherwise every device of
            # that axis runs the whole dimension — replicated, correct.
            ok = axis in auto and not any(d % mesh.shape[axis] for d in dims)
            return axis if ok else None

        qkv = P(
            split(batch_axis, q.shape[0]), None,
            split(head_axis, q.shape[2], k.shape[2]), None,
        )
        return jax.shard_map(
            # Inside a region manual over some axes (the tp step's `data`,
            # value_and_grad_summed) the map takes the context's mesh.
            kernel, mesh=jax.sharding.get_abstract_mesh() if manual else mesh,
            axis_names=auto,
            in_specs=(qkv, qkv, qkv) + (P(qkv[0]),) * len(lens),
            out_specs=qkv, check_vma=False,
        )(q, k, v, *lens)

    def _embed_tokens(self, params, tokens, positions):
        """Token embedding, plus the learned position table when that
        scheme is active (rope instead rotates q/k inside the blocks).
        Over-length sequences fail loudly here: jnp.take clamps by default,
        which would silently reuse the last table row (the SP path's guard
        comment depends on the dense path raising)."""
        if tokens.ndim > 1 and tokens.shape[1] > self.max_len:
            raise ValueError(
                f"sequence length {tokens.shape[1]} exceeds max_len "
                f"{self.max_len}"
            )
        with jax.named_scope(names.EMBED):
            h = params.embed[tokens]
            if self.pos_embedding == "learned":
                h = h + jnp.take(params.pos, positions, axis=0)
        return h

    def _moe_capacity(self, tokens: int) -> int:
        """Static per-expert capacity for a call with ``tokens`` routable
        tokens (GShard convention: factor × k × tokens/experts, min 1 —
        top-k routes k·tokens dispatches, so capacity scales with k to
        keep ``moe_capacity_factor`` meaning the same headroom at any k)."""
        import math

        return max(
            1,
            math.ceil(
                self.moe_capacity_factor
                * self.moe_top_k
                * tokens
                / self.moe_experts
            ),
        )

    def _moe_block_ffn(self, blk, hn2, moe_call, token_mask=None):
        """Shared MoE-FFN scaffold for the dense and expert-parallel paths:
        token flattening, compute_dtype casting (expert matmuls ride the
        MXU at one bf16 pass like every other matmul here; the gate
        *weights* stay f32 — the activations it sees are compute_dtype like
        everywhere else), and the capacity policy. ``moe_call(mp, x2d, capacity)`` is the only difference
        between the two paths — keeping ep==dense pinned by construction.
        Returns ``(out, aux)`` with the router's :class:`~ops.moe.MoEAux`.

        Capacity: training applies the Switch convention
        (``moe_capacity_factor`` × tokens/experts, drops beyond). Single-
        token calls (the KV-cache decode step, L==1) never drop — capacity
        drops are a training-time load-balancing device, and a decode-time
        drop would make generation diverge from the training forward at the
        default factor (B tokens routed per step vs B·L in training)."""
        cd = self.compute_dtype
        from distributed_tensorflow_tpu.ops.moe import MoEParams

        b, l, d = hn2.shape
        t = b * l
        capacity = t if l == 1 else self._moe_capacity(t)
        mp = MoEParams(
            blk.wg,
            blk.w_up.astype(cd),
            blk.b_up.astype(cd),
            blk.w_down.astype(cd),
            blk.b_down.astype(cd),
        )
        flat_mask = None if token_mask is None else token_mask.reshape(t)
        out, aux = moe_call(
            mp, hn2.reshape(t, d).astype(cd), capacity, flat_mask
        )
        return out.astype(jnp.float32).reshape(b, l, d), aux

    def _ffn(self, blk, hn2, token_mask=None):
        """Dense-FFN or (for MoE blocks) locally-computed switch MoE on
        [B, L, d]; includes the output bias. Returns ``(out, aux)`` —
        aux is the router's MoEAux for MoE blocks, zeros for dense ones
        (so the layer scan carries a uniform pytree either way).
        ``token_mask`` [B, L] bool (ragged batches): pad tokens are
        excluded from MoE routing, capacity, and aux statistics."""
        from distributed_tensorflow_tpu.ops.moe import MoEAux

        if isinstance(blk, GPTMoEBlockParams):
            # moe_ffn_local: E·capacity token-FFNs (the sparse cost MoE
            # exists for); moe_ffn_dense would compute all E experts on all
            # T tokens. Same semantics, proven in tests/test_moe.py.
            from distributed_tensorflow_tpu.ops.moe import moe_ffn_local

            return self._moe_block_ffn(
                blk,
                hn2,
                lambda mp, x, c, m: moe_ffn_local(
                    mp, x, capacity=c, with_aux=True, token_mask=m,
                    k=self.moe_top_k,
                ),
                token_mask,
            )
        out = (
            self._dot(
                jax.nn.gelu(self._dot(hn2, blk.w_up) + blk.b_up), blk.w_down
            )
            + blk.b_down
        )
        return out, MoEAux.zero()

    def _block(self, blk, h, attend=None, ffn=None, positions=None,
               token_mask=None):
        """Block forward; also returns this block's k/v for cache prefill
        and the FFN's router aux (zeros for dense blocks).
        h: [B, L, d]. ``attend``/``ffn`` swap the attention algorithm (the
        sequence-parallel path passes the ring) or the FFN (the
        expert-parallel path passes the all-to-all MoE) without duplicating
        the surrounding layernorm/projection/residual math — one source of
        truth for the block, so sp==dense and ep==dense stay pinned by
        construction."""
        b, l, d = h.shape
        with jax.named_scope(names.ATTN_QKV):
            hn = _layernorm(h, blk.ln1_scale, blk.ln1_bias)
            kv_shape = (b, l, self.num_kv_heads, self.head_dim)
            q = self._dot(hn, blk.wq).reshape(
                b, l, self.num_heads, self.head_dim
            )
            k = self._dot(hn, blk.wk).reshape(kv_shape)
            v = self._dot(hn, blk.wv).reshape(kv_shape)
            if self.pos_embedding == "rope":
                q = _rope(q, positions)
                k = _rope(k, positions)
        with jax.named_scope(names.ATTN_CORE):
            attn = (attend or self._attend)(q, k, v)
        with jax.named_scope(names.ATTN_OUT):
            h = h + self._dot(attn.reshape(b, l, d), blk.wo)
            if self.remat and self._tensor_parallel:
                # `wo` is row-split: the product above ends in an
                # all-reduce over the model axis. Name its result, so the
                # layer's checkpoint keeps it and the replay pays neither.
                from jax.ad_checkpoint import checkpoint_name

                from distributed_tensorflow_tpu.ops.pallas_attention import (
                    REMAT_SAVE_TP_SUM,
                )

                h = checkpoint_name(h, REMAT_SAVE_TP_SUM)
        with jax.named_scope(names.MLP):
            hn2 = _layernorm(h, blk.ln2_scale, blk.ln2_bias)
            if ffn is not None:
                ffn_out, aux = ffn(blk, hn2)
            else:
                ffn_out, aux = self._ffn(blk, hn2, token_mask)
            h = h + ffn_out
        return h, (k, v), aux

    def _logits(self, p: GPTLMParams, h):
        with jax.named_scope(names.LM_HEAD):
            hf = _layernorm(h, p.lnf_scale, p.lnf_bias)
            return self._dot_full(hf, p.embed.T)

    # -- training forward --------------------------------------------------

    def apply(self, params: GPTLMParams, tokens: jax.Array) -> jax.Array:
        """tokens [B, L] int32 → logits [B, L, vocab], causal."""
        return self.apply_with_aux(params, tokens)[0]

    def apply_with_aux(
        self,
        params: GPTLMParams,
        tokens: jax.Array,
        lengths: jax.Array | None = None,
    ):
        """:meth:`apply` that also returns the per-layer router statistics
        (:class:`~ops.moe.MoEAux` with [num_layers] leaves; all zeros for
        dense models) — the observability surface the training loss and the
        drop-rate metric are built from. ``lengths`` [B] int32 (ragged
        right-padded batches) keeps pad tokens out of MoE routing/capacity
        and the aux statistics, making the MoE forward at real positions —
        and therefore the masked loss — exactly pad-content-independent."""
        l = tokens.shape[1]
        positions = jnp.arange(l)
        token_mask = (
            None
            if lengths is None
            else positions[None, :] < lengths[:, None]  # [B, L]
        )
        h = self._embed_tokens(params, tokens, positions)
        if self.grad_sum_axis is not None:  # dense blocks: no router aux
            h = self._stack_summed_late(positions, self.grad_sum_axis)(
                params.blocks, h
            )
            return self._logits(params, h), None

        def body(h, blk):
            h, _, aux = self._block(
                blk, h, positions=positions, token_mask=token_mask
            )
            return h, aux

        body = self._remat_wrap(body)
        h, auxs = lax.scan(body, h, params.blocks)
        return self._logits(params, h), auxs

    def apply_sequence_parallel(
        self,
        params: GPTLMParams,
        tokens: jax.Array,
        axis_name: str = "seq",
        *,
        attention: str | None = None,
    ) -> jax.Array:
        """Sequence-parallel causal forward *body*: call inside
        ``jax.shard_map`` with tokens sharded [B, L/n] per device and params
        replicated; returns this device's logits shard [B, L/n, vocab] —
        identical to the matching slice of :meth:`apply` on the gathered
        sequence. ``attention`` is ``"ring"``, ``"ring_flash"`` or
        ``"ulysses"`` (default follows ``attention_impl``, like the
        transformer classifier — whose SP menu this matches; the flash
        variant needs ``check_vma=False`` in the enclosing shard_map
        off-TPU). This is how the LM trains past one device's activation
        memory: L/n tokens of activations per device, KV blocks riding the
        ring — at ``num_kv_heads`` width under GQA (the repeat to Hq never
        crosses a device), and for windowed models only
        ``ceil((W−1)/L_loc)+1`` hops of it (out-of-band blocks never
        move). ``"ulysses"`` instead trades sequence shards for head
        shards in one all-to-all and runs full-sequence attention locally
        per head group (windowed models apply the band mask there); it
        needs the axis size to divide ``num_heads`` AND
        ``num_kv_heads``."""
        if self.moe_experts is not None:
            # Per-shard capacity/routing order would silently diverge from
            # the dense forward under drops (window+SP, by contrast, is
            # implemented exactly — the bounded ring); expert parallelism
            # is the MoE sharding.
            raise NotImplementedError(
                "MoE blocks are not supported on the sequence-parallel "
                "path; use apply_expert_parallel"
            )
        from distributed_tensorflow_tpu.ops.ring_attention import (
            ring_attention,
            ring_flash_attention,
            ulysses_attention,
        )

        if attention is not None and attention not in (
            "ring", "ring_flash", "ulysses"
        ):
            raise ValueError(
                f"unknown attention {attention!r}; ring|ring_flash|ulysses"
            )

        n = lax.axis_size(axis_name)
        my = lax.axis_index(axis_name)
        b, l_loc = tokens.shape
        if attention is None:
            # Default follows attention_impl, honoring the flash_min_len
            # crossover at the PER-SHARD length (the flash ring runs the
            # kernel on l_loc-sized blocks each hop, so l_loc is the
            # length that decides kernel-vs-dense — an explicit
            # attention="ring_flash" still forces the kernel).
            from distributed_tensorflow_tpu.models.base import (
                resolve_flash_min_len,
            )

            attention = (
                "ring_flash"
                if self.attention_impl == "flash"
                and l_loc >= resolve_flash_min_len(self.flash_min_len)
                else "ring"
            )
        if n * l_loc > self.max_len:
            # dynamic_slice would silently CLAMP the positional slice for
            # the last devices (duplicating other shards' positions) where
            # the dense path fails loudly — so fail loudly here too.
            raise ValueError(
                f"global sequence {n * l_loc} exceeds max_len {self.max_len}"
            )
        if attention == "ulysses" and (
            self.num_heads % n or self.num_kv_heads % n
        ):
            raise ValueError(
                f"ulysses needs heads ({self.num_heads}) and kv heads "
                f"({self.num_kv_heads}) divisible by the axis size {n}"
            )
        positions = my * l_loc + jnp.arange(l_loc)  # absolute, so rope and
        h = self._embed_tokens(params, tokens, positions)  # learned agree

        if attention == "ulysses":

            def sp_attend(q, k, v):
                return ulysses_attention(
                    q, k, v, axis_name, causal=True, window=self.window
                )

        else:
            ring = (
                ring_attention if attention == "ring" else ring_flash_attention
            )

            def sp_attend(q, k, v):
                # KV circulates at num_kv_heads width; the ring repeats
                # (XLA ring) or grid-maps (flash ring) locally after each
                # receive.
                return ring(
                    q, k, v, axis_name, causal=True, window=self.window
                )

        def body(h, blk):
            h, _, _ = self._block(blk, h, attend=sp_attend, positions=positions)
            return h, None

        body = self._remat_wrap(body)
        h, _ = lax.scan(body, h, params.blocks)
        return self._logits(params, h)

    def apply_expert_parallel(
        self,
        params: GPTLMParams,
        tokens: jax.Array,
        axis_name: str = "expert",
        *,
        with_aux: bool = False,
        lengths: jax.Array | None = None,
    ) -> jax.Array:
        """Expert-parallel causal forward *body* (MoE models): call inside
        ``jax.shard_map`` with tokens sharded on the BATCH dim [B/n, L] and
        the blocks' expert dims sharded over ``axis_name`` (one expert's
        FFN weights per device; gate and attention weights replicated).
        Attention runs locally on the batch shard; each block's FFN is the
        all-to-all token exchange (``ops/moe.moe_ffn``). Routing (top-1)
        is identical to :meth:`apply`; capacity is applied per
        (expert, source device) here vs per expert globally there, so the
        two are exactly equal whenever no token overflows capacity (ample
        ``moe_capacity_factor``) and may drop different tokens under
        overflow — drops are a training-time load-balancing device, not a
        semantic guarantee. ``with_aux=True`` also returns per-layer
        :class:`~ops.moe.MoEAux` over this device's local tokens — its
        ``drop_fraction`` is the observable guard on the no-drop-regime
        claim above (pmean it over ``axis_name`` for the global rate).
        ``lengths`` [B/n] int32 (this shard's rows of a ragged right-padded
        batch) keeps pad tokens out of MoE routing/capacity and the aux
        statistics, exactly as :meth:`apply_with_aux` does in the dense
        path — EP ragged training is pad-content-independent too."""
        if self.moe_experts is None:
            raise ValueError("apply_expert_parallel requires moe_experts")
        n = lax.axis_size(axis_name)
        if n != self.moe_experts:
            raise ValueError(
                f"{axis_name!r} axis size {n} != moe_experts "
                f"{self.moe_experts}"
            )
        from distributed_tensorflow_tpu.ops.moe import moe_ffn

        l = tokens.shape[1]
        positions = jnp.arange(l)
        token_mask = (
            None
            if lengths is None
            else positions[None, :] < lengths[:, None]  # [B/n, L]
        )

        def ep_ffn(blk, hn2):
            return self._moe_block_ffn(
                blk,
                hn2,
                lambda mp, x, c, m: moe_ffn(
                    mp, x, axis_name, capacity=c, with_aux=True,
                    token_mask=m, k=self.moe_top_k,
                ),
                token_mask,
            )

        h = self._embed_tokens(params, tokens, positions)

        def body(h, blk):
            h, _, aux = self._block(blk, h, ffn=ep_ffn, positions=positions)
            return h, aux

        body = self._remat_wrap(body)
        h, auxs = lax.scan(body, h, params.blocks)
        logits = self._logits(params, h)
        return (logits, auxs) if with_aux else logits

    def pipeline_stage_blocks(self, blocks, num_stages: int):
        """Reshape the scanned [num_layers, ...] block stack into
        [num_stages, layers_per_stage, ...] for stage-sharding (leading dim
        over the ``stage`` mesh axis) — the layout
        :meth:`apply_pipeline_parallel` consumes."""
        if self.num_layers % num_stages:
            raise ValueError(
                f"num_layers {self.num_layers} not divisible by "
                f"num_stages {num_stages}"
            )
        lps = self.num_layers // num_stages
        return jax.tree.map(
            lambda a: a.reshape((num_stages, lps) + a.shape[1:]), blocks
        )

    def _pp_stage_fn(self):
        """One pipeline stage's forward — the ONE stage body shared by
        :meth:`apply_pipeline_parallel` and :func:`make_lm_pp_train_step`
        (a divergence would silently break their proven forward equality):
        the stage's contiguous layer group ([1, layers_per_stage, ...]
        leaves) scanned exactly like :meth:`apply`, ``jax.checkpoint``-ed
        when ``remat`` (backward recomputes one stage group per tick
        instead of stashing every tick's activations)."""

        def stage_fn(blk_stack, x):
            positions = jnp.arange(x.shape[1])

            def body(h, blk):
                h, _, _ = self._block(blk, h, positions=positions)
                return h, None

            h, _ = lax.scan(body, x, jax.tree.map(lambda a: a[0], blk_stack))
            return h

        return self._remat_wrap(stage_fn)

    def apply_pipeline_parallel(
        self,
        params: GPTLMParams,
        tokens: jax.Array,
        axis_name: str = "stage",
        *,
        num_microbatches: int = 4,
    ) -> jax.Array:
        """Pipeline-parallel causal forward *body*: call inside
        ``jax.shard_map`` over the ``stage`` axis with ``params.blocks`` in
        :meth:`pipeline_stage_blocks` layout sharded on its leading dim
        (each device holds one stage's contiguous layer group [1, n/S, ...])
        and everything else — embed/pos/lnf and tokens [B, L] — replicated.
        Embedding and the LM head are computed on every stage (cheap,
        replicated); the block stack runs as a GPipe-microbatched pipeline
        (``parallel/pipeline.py``): activations flow stage-to-stage over
        single ppermute hops, ``num_microbatches`` microbatches keep all
        stages busy after the fill. Returns logits [B, L, vocab], identical
        to :meth:`apply` — the flagship-model composition PARITY.md §2b's
        PP row promises (the reference has no stages at all, SURVEY.md
        §2b)."""
        if self.moe_experts is not None:
            raise NotImplementedError(
                "pipeline parallelism is not defined for MoE blocks; use "
                "expert parallelism (apply_expert_parallel)"
            )
        from distributed_tensorflow_tpu.parallel.pipeline import (
            microbatch,
            pipeline_apply,
        )

        b, l = tokens.shape
        h = self._embed_tokens(params, tokens, jnp.arange(l))
        hm = microbatch(h, num_microbatches)  # [M, B/M, L, d]
        out = pipeline_apply(self._pp_stage_fn(), params.blocks, hm, axis_name)
        return self._logits(params, out.reshape(b, l, -1))

    def loss(
        self,
        params: GPTLMParams,
        tokens: jax.Array,
        lengths: jax.Array | None = None,
    ) -> jax.Array:
        """Training loss: mean next-token cross-entropy (positions 0..L-2
        predict 1..L-1, f32 log-softmax), plus — for MoE models — the
        Switch load-balance and router-z auxiliary terms behind
        ``moe_balance_coef`` / ``moe_z_coef``. Dense models: exactly CE.

        ``lengths`` [B] int32 (each ≥ 1) makes the CE a *masked* mean for
        right-padded ragged batches: only targets at positions < lengths[b]
        count. Causal attention keeps pad tokens out of real positions'
        logits, and ``lengths`` is also threaded into MoE routing (pads
        never consume expert capacity or enter the aux statistics) — so
        ragged-batch training is exactly pad-content-independent for dense
        AND MoE models (proven in test_gpt.py); the attention ops
        additionally accept ``kv_lens`` for non-causal uses."""
        return self.loss_and_metrics(params, tokens, lengths)[0]

    def loss_and_metrics(
        self,
        params: GPTLMParams,
        tokens: jax.Array,
        lengths: jax.Array | None = None,
    ) -> tuple[jax.Array, dict]:
        """(total loss, metrics dict). Metrics always include ``ce``; MoE
        models add ``balance_loss`` / ``z_loss`` (layer means entering the
        total) and ``drop_fraction`` (pure metric, NOT in the loss — the
        observable no-drop-regime guard)."""
        logits, auxs = self.apply_with_aux(params, tokens, lengths)
        ce = _ce_from_logits(logits, tokens, lengths)
        metrics = {"ce": ce}
        if self.moe_experts is None:
            return ce, metrics
        balance = jnp.mean(auxs.balance_loss)
        z = jnp.mean(auxs.z_loss)
        metrics.update(
            balance_loss=balance,
            z_loss=z,
            drop_fraction=jnp.mean(auxs.drop_fraction),
            # [E]: dispatch distribution averaged over layers — the direct
            # utilization readout (uniform = 1/E everywhere).
            expert_fraction=jnp.mean(auxs.expert_fraction, axis=0),
        )
        total = ce + self.moe_balance_coef * balance + self.moe_z_coef * z
        return total, metrics

    # -- gradient summed over a data axis by the model itself -------------

    def value_and_grad_summed(
        self,
        params: GPTLMParams,
        tokens: jax.Array,
        lengths: jax.Array | None = None,
    ):
        """``(loss, grads)`` of the whole batch, the same on every member
        of ``grad_sum_axis``: call inside a ``shard_map`` manual over that
        axis, each member holding its rows of ``tokens``, on the tp
        trainer's copy (dense blocks, ``attention_shard`` naming the
        `model` axis).

        :meth:`loss` runs the layer stack as :meth:`_stack_summed_late`,
        whose backward sums the stack's weight gradients over the axis one
        layer late; the embedding, positions and final norm are summed by
        ``psum`` at the end. Each member's loss is weighted by its share
        of the targets, so the sum is the global mean's gradient (``1/n``
        when not ragged)."""
        axis = self.grad_sum_axis
        if lengths is None:
            share = 1.0 / lax.axis_size(axis)
        else:
            count = jnp.sum(jnp.maximum(lengths - 1, 0)).astype(jnp.float32)
            share = count / jnp.maximum(lax.psum(count, axis), 1.0)
        loss, grads = jax.value_and_grad(
            lambda p: self.loss(p, tokens, lengths) * share
        )(params)
        rest = lax.psum(grads._replace(blocks=None), axis)
        return lax.psum(loss, axis), rest._replace(blocks=grads.blocks)

    def _block_operands(self, blk: GPTBlockParams) -> GPTBlockParams:
        """A dense block's weights in the type its products take them
        (``_dot`` casts to ``compute_dtype``; quantized products take the
        float32 weights), so their gradients are exchanged at that width."""
        if self.matmul_dtype is not None:
            return blk
        cd = self.compute_dtype
        return blk._replace(**{
            k: getattr(blk, k).astype(cd)
            for k in ("wq", "wk", "wv", "wo", "w_up", "w_down")
        })

    def _grad_packing(self, shapes):
        """``(pack, unpack)`` between one layer's gradient tree (``shapes``)
        and one flat buffer per dtype, under the tp layout. The chip's
        compiler starts a dozen per-leaf exchanges after the layer's
        backward, but keeps one exchange of a whole buffer in flight
        across it. Each device packs its own shards over the `model` axis:
        flattening a sharded leaf through GSPMD would gather it first."""
        from jax.sharding import PartitionSpec as P

        leaves, tree = jax.tree.flatten(shapes)
        dtypes = sorted({s.dtype for s in leaves}, key=str)

        def pack(g):
            gl = jax.tree.leaves(g)
            return tuple(
                jnp.concatenate([x.ravel() for x in gl if x.dtype == dt])
                for dt in dtypes
            )

        def unpack(flat, local):
            out, at = [], dict.fromkeys(dtypes, 0)
            for s in local:
                n = int(np.prod(s.shape))
                buf = flat[dtypes.index(s.dtype)]
                out.append(buf[at[s.dtype]:at[s.dtype] + n].reshape(s.shape))
                at[s.dtype] += n
            return jax.tree.unflatten(tree, out)

        axis = self.attention_shard[2]
        mesh = jax.sharding.get_abstract_mesh()
        specs = jax.tree.map(
            lambda s: P(*s[1:]), self.partition_specs(axis).blocks,
            is_leaf=lambda s: isinstance(s, P),
        )
        local = [
            jax.ShapeDtypeStruct(tuple(
                d // mesh.shape[axis] if i < len(sp) and sp[i] == axis else d
                for i, d in enumerate(s.shape)
            ), s.dtype)
            for s, sp in zip(leaves, jax.tree.leaves(
                specs, is_leaf=lambda s: isinstance(s, P)
            ))
        ]
        kw = dict(mesh=mesh, axis_names={axis}, check_vma=False)
        return (
            jax.shard_map(pack, in_specs=(specs,), out_specs=P(axis), **kw),
            jax.shard_map(
                lambda flat: unpack(flat, local),
                in_specs=(P(axis),), out_specs=specs, **kw,
            ),
        )

    def _stack_summed_late(self, positions, axis_name: str):
        """The scanned block stack as a ``custom_vjp`` whose backward sums
        the weight gradients over ``axis_name`` one layer late.

        Forward: each layer under ``jax.vjp`` of its checkpointed body (the
        same ``_remat_wrap`` policy as :meth:`apply_with_aux`), keeping the
        pullback's residuals stacked — less the layer's weights, which the
        backward takes from the stack again. Backward: a reverse scan whose
        carry holds the previous layer's local gradient; each step runs its
        layer's pullback and, independent of it, sums the carried gradient
        (:func:`~ops.collectives.sum_in_member_order`), so the exchange
        runs behind the layer's products instead of stopping the core. The
        last layer's sum closes after the loop. Outside a region manual over
        the axis (an eval's forward, or GSPMD's own gradient) nothing is
        exchanged: there the pullback's gradient is the batch's already."""
        from distributed_tensorflow_tpu.ops.collectives import (
            sum_in_member_order,
        )

        operands = self._block_operands
        layer = self._remat_wrap(
            lambda h, w: self._block(w, h, positions=positions)[0]
        )
        # Where each leaf of a layer's pullback comes from: ("w", k) the
        # k-th weight leaf, ("r", j) the j-th kept residual. Set when the
        # forward is traced, read when the backward is.
        layout = {}

        def run(blocks, h):
            return lax.scan(lambda h, blk: (layer(h, operands(blk)), None),
                            h, blocks)[0]

        def fwd(blocks, h):
            def body(h, blk):
                ops = operands(blk)
                w = jax.tree.leaves(ops)
                h, pullback = jax.vjp(layer, h, ops)
                leaves, layout["tree"] = jax.tree.flatten(pullback)
                kept, where = [], []
                for leaf in leaves:
                    src = [k for k, x in enumerate(w) if x is leaf]
                    if src:
                        where.append(("w", src[0]))
                        continue
                    have = [j for j, x in enumerate(kept) if x is leaf]
                    where.append(("r", have[0] if have else len(kept)))
                    if not have:
                        kept.append(leaf)
                layout["where"] = where
                return h, kept

            h, kept = lax.scan(body, h, blocks)
            return h, (kept, blocks)

        def bwd(res, dh):
            kept, blocks = res
            n = jax.tree.leaves(blocks)[0].shape[0]
            shapes = jax.eval_shape(
                operands, jax.tree.map(lambda a: a[0], blocks)
            )
            if axis_name in jax.sharding.get_abstract_mesh().manual_axes:
                pack, unpack = self._grad_packing(shapes)

                def summed(late):
                    return unpack(tuple(
                        sum_in_member_order(x, axis_name) for x in late
                    ))
            else:
                pack = summed = lambda g: g  # noqa: E731

            def body(carry, xs):
                dh, late, grads = carry
                i, kept_i, blk = xs
                wl = jax.tree.leaves(operands(blk))
                pullback = jax.tree.unflatten(layout["tree"], [
                    wl[k] if src == "w" else kept_i[k]
                    for src, k in layout["where"]
                ])
                dh, g = pullback(dh)
                # Layer i+1's sum; at the top layer a sum of zeros, written
                # into slot n-1 and overwritten one step later.
                grads = jax.tree.map(
                    lambda buf, s: lax.dynamic_update_index_in_dim(
                        buf, s.astype(buf.dtype), jnp.minimum(i + 1, n - 1), 0
                    ),
                    grads, summed(late),
                )
                return (dh, pack(g), grads), None

            zeros = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), shapes)
            carry = (dh, pack(zeros), jax.tree.map(jnp.zeros_like, blocks))
            (dh, late, grads), _ = lax.scan(
                body, carry, (jnp.arange(n), kept, blocks), reverse=True
            )
            grads = jax.tree.map(
                lambda buf, s: buf.at[0].set(s.astype(buf.dtype)),
                grads, summed(late),
            )
            return grads, dh

        stack = jax.custom_vjp(run)
        stack.defvjp(fwd, bwd)
        return stack

    # -- KV-cache decoding -------------------------------------------------

    def _commit_slot_rows(
        self, ck0, cv0, ks0, vs0, kq, vq, ksc, vsc, lengths, act
    ):
        """The slab fresh-row commit of ``_decode_block_slots`` (per-row
        scatter at ``lengths % C`` / ``lengths``; inactive rows write
        their old value back — a no-op). ``kq``/``vq`` [S, Hkv, Dh]
        storage-dtype rows, ``ksc``/``vsc`` [S, Hkv] f32 scales or None
        (bf16 layout). Returns (ck, cv, nks, nvs)."""
        rows = jnp.arange(ck0.shape[0])
        c = self.cache_len
        slot = lengths % c if self.window is not None else lengths
        with jax.named_scope(names.KV_WRITE):
            kw = jnp.where(act[:, None, None], kq, ck0[rows, slot])
            vw = jnp.where(act[:, None, None], vq, cv0[rows, slot])
            ck = ck0.at[rows, slot].set(kw)
            cv = cv0.at[rows, slot].set(vw)
            if ks0 is None:
                return ck, cv, None, None
            nks = ks0.at[rows, slot].set(
                jnp.where(act[:, None], ksc, ks0[rows, slot])
            )
            nvs = vs0.at[rows, slot].set(
                jnp.where(act[:, None], vsc, vs0[rows, slot])
            )
        return ck, cv, nks, nvs

    @property
    def cache_len(self) -> int:
        """Static KV-cache length per layer: ``min(window, max_len)`` for
        windowed models (rolling buffer — older keys are unreachable by the
        sliding-window mask), else ``max_len``."""
        if self.window is not None:
            return min(self.window, self.max_len)
        return self.max_len

    def prefill(self, params: GPTLMParams, tokens: jax.Array):
        """Run the prompt once, returning (last-position logits [B, vocab],
        cache holding every layer's prompt k/v). Windowed models keep only
        the last ``cache_len`` prompt positions, each at slot ``pos mod
        cache_len`` — the rolling layout :meth:`decode_step` writes."""
        b, l = tokens.shape
        positions = jnp.arange(l)
        h = self._embed_tokens(params, tokens, positions)

        def body(h, blk):
            h, kv, _ = self._block(blk, h, positions=positions)
            return h, kv

        h, (ks, vs) = lax.scan(body, h, params.blocks)
        c = self.cache_len
        with jax.named_scope(names.KV_WRITE):
            ks = ks.astype(self.compute_dtype)
            vs = vs.astype(self.compute_dtype)
            if l <= c:
                pad = [(0, 0), (0, 0), (0, c - l), (0, 0), (0, 0)]
                # Positions land at slot pos % c = pos (l <= c): plain pad.
                ck, cv = jnp.pad(ks, pad), jnp.pad(vs, pad)
            else:
                # Rolling: keep the last c positions at slots pos % c
                # (static index arrays — l and c are compile-time).
                ps = np.arange(l - c, l)
                slots = ps % c
                shape = ks.shape[:2] + (c,) + ks.shape[3:]
                ck = jnp.zeros(shape, ks.dtype).at[:, :, slots].set(
                    ks[:, :, ps]
                )
                cv = jnp.zeros(shape, vs.dtype).at[:, :, slots].set(
                    vs[:, :, ps]
                )
        cache = KVCache(k=ck, v=cv, length=jnp.asarray(l, jnp.int32))
        return self._logits(params, h)[:, -1], cache

    def _decode_block(self, blk: GPTBlockParams, h, ck, cv, length):
        """Single-token block step. h: [B, 1, d]; ck/cv: [B, cache_len, Hkv,
        Dh] (this layer's cache). Returns (h, updated ck, updated cv)."""
        b = h.shape[0]
        c = self.cache_len
        with jax.named_scope(names.ATTN_QKV):
            hn = _layernorm(h, blk.ln1_scale, blk.ln1_bias)
            kv_shape = (b, 1, self.num_kv_heads, self.head_dim)
            q = self._dot(hn, blk.wq).reshape(
                b, 1, self.num_heads, self.head_dim
            )
            k = self._dot(hn, blk.wk).reshape(kv_shape)
            v = self._dot(hn, blk.wv).reshape(kv_shape)
            if self.pos_embedding == "rope":
                pos1 = jnp.reshape(length, (1,))
                q = _rope(q, pos1)
                k = _rope(k, pos1)
        slot = length % c if self.window is not None else length
        with jax.named_scope(names.KV_WRITE):
            k = k.astype(ck.dtype)
            v = v.astype(cv.dtype)
            ck = lax.dynamic_update_slice(ck, k, (0, slot, 0, 0))
            cv = lax.dynamic_update_slice(cv, v, (0, slot, 0, 0))
        # Attend the one query against the whole static-length cache,
        # masking invalid slots. GQA runs WITHOUT materializing the head
        # repeat: q groups to [B, Hkv, g, Dh] (group_query_heads — the one
        # canonical q-head→KV-head mapping, shared with repeat_kv and the
        # flash grid maps) and both einsums contract against the Hkv-head
        # cache directly — per-step temporaries stay at KV width, the same
        # factor the cache itself saves (round-2 weak spot: the old path
        # repeated the cache to Hq every step).
        from distributed_tensorflow_tpu.ops.ring_attention import (
            group_query_heads,
        )

        idx = jnp.arange(c)
        if self.window is not None:
            # Rolling buffer: slot i holds absolute position
            # length − ((slot − i) mod c) ∈ (length − c, length] — by
            # construction exactly the window (self included), so the only
            # invalid slots are the not-yet-written ones (negative
            # position). No ≤ length or > length − W test needed.
            slot_pos = length - jnp.mod(slot - idx, c)
            valid = slot_pos >= 0
        else:
            valid = idx <= length  # [cache_len]
        attn = self._decode_attend(
            "bhgd,bkhd->bhgk", "bhgk,bkhd->bhgd",
            group_query_heads(q[:, 0], self.num_kv_heads), ck, cv,
            valid[None, None, None, :],
        )
        return self._decode_block_tail(blk, h, attn), ck, cv

    def _decode_attend(self, qk, wv, qg, ck, cv, valid):
        """One query per row against a whole static-length cache, invalid
        slots masked: the core every single-token decode path shares
        (``qk``/``wv`` are the two einsum specs — the batch letter is all
        that differs). f32 softmax; the value product runs at the cache's
        dtype."""
        with jax.named_scope(names.ATTN_CORE):
            scores = jnp.einsum(
                qk, qg, ck, preferred_element_type=jnp.float32
            ) / jnp.sqrt(jnp.asarray(self.head_dim, jnp.float32))
            scores = jnp.where(valid, scores, -1e30)
            w = jax.nn.softmax(scores, axis=-1)
            return jnp.einsum(
                wv, w.astype(cv.dtype), cv,
                preferred_element_type=jnp.float32,
            )

    def _decode_block_tail(self, blk, h, attn):
        """Attention-out projection and FFN of a single-token block step
        (``attn`` [rows, Hkv, g, Dh]); returns the new residual."""
        rows = h.shape[0]
        with jax.named_scope(names.ATTN_OUT):
            h = h + self._dot(attn.reshape(rows, 1, self.model_dim), blk.wo)
        with jax.named_scope(names.MLP):
            hn2 = _layernorm(h, blk.ln2_scale, blk.ln2_bias)
            ffn_out, _ = self._ffn(blk, hn2)  # aux unused: decode never drops
            return h + ffn_out

    def decode_step(
        self,
        params: GPTLMParams,
        token: jax.Array,
        cache: KVCache,
    ):
        """Append one token [B] int32; returns (logits [B, vocab], cache).

        The cache is full at ``length == max_len``; stepping past it would
        silently clamp (``dynamic_update_slice`` semantics) and corrupt the
        last slot, so eager calls raise instead. Under a trace the length is
        abstract — loop drivers must bound their own trip count the way
        :meth:`greedy_decode` does.

        The layer loop is UNROLLED, not a ``lax.scan``: with the stacked
        cache as scan xs/ys, XLA double-buffers the whole cache every
        token instead of updating one slot in place. Decode graphs are
        tiny (~20 ops/layer, forward-only), so unrolling costs no
        meaningful compile time; :meth:`prefill` and training keep their
        scans."""
        if not isinstance(cache.length, jax.core.Tracer):
            if int(cache.length) >= self.max_len:
                raise ValueError(
                    f"KV cache full: length {int(cache.length)} == max_len "
                    f"{self.max_len}; increase max_len"
                )
        h = self._embed_tokens(
            params, token[:, None], jnp.reshape(cache.length, (1,))
        )
        nks, nvs = [], []
        for i in range(self.num_layers):
            blk = jax.tree.map(lambda x: x[i], params.blocks)
            ck0, cv0, _, _ = _cache_layer(cache, i)
            h, ck, cv = self._decode_block(blk, h, ck0, cv0, cache.length)
            nks.append(ck)
            nvs.append(cv)
        nk, nv, _, _ = _restack(nks, nvs)
        new_cache = KVCache(k=nk, v=nv, length=cache.length + 1)
        return self._logits(params, h)[:, 0], new_cache

    # -- slot-wise decoding (the serving surface, serve.py) ----------------

    def empty_slot_cache(
        self, slots: int, kv_dtype: str = "bf16"
    ) -> SlotKVCache:
        """A vacant ``slots``-row :class:`SlotKVCache` (lengths all zero —
        a zero-length slot is FREE; the decode mask treats only written
        positions as attendable, so vacant rows compute well-defined
        garbage that the scheduler never reads). ``kv_dtype`` picks the
        storage layout: "bf16" stores compute_dtype with no scales (the
        default, bitwise round-9); int8/fp8 store 1-byte payloads plus
        the per-row scale side tensors."""
        if slots < 1:
            raise ValueError(f"slots must be >= 1, got {slots}")
        shape = (
            self.num_layers,
            slots,
            self.cache_len,
            self.num_kv_heads,
            self.head_dim,
        )
        # One buffer each: a server donates its cache to the programs
        # that return it, and a buffer can be given away once.
        dt = kv_storage_dtype(kv_dtype, self.compute_dtype)
        scaled = kv_dtype != "bf16"
        return SlotKVCache(
            k=jnp.zeros(shape, dt),
            v=jnp.zeros(shape, dt),
            lengths=jnp.zeros((slots,), jnp.int32),
            k_scale=jnp.zeros(shape[:-1], jnp.float32) if scaled else None,
            v_scale=jnp.zeros(shape[:-1], jnp.float32) if scaled else None,
        )

    def reset_slots(self, cache: SlotKVCache, free: jax.Array) -> SlotKVCache:
        """Mark slots FREE (``free`` [S] bool): their lengths drop to 0.
        K/V content is left in place — stale bytes are unreachable because
        the decode validity mask ignores everything past ``lengths``, and
        a :meth:`prefill_slots` admit overwrites the row wholesale.
        ``serve.py``'s scheduler tracks vacancy host-side (its ``finished``
        flag) and re-arms through the admit merge alone; this is the
        explicit in-graph vacancy op for external schedulers that keep
        slot state on device (pinned content-independent in
        tests/test_serve.py)."""
        return cache._replace(
            lengths=jnp.where(free, 0, cache.lengths)
        )

    def prefill_slots(
        self,
        params: GPTLMParams,
        cache: SlotKVCache,
        tokens: jax.Array,
        lengths: jax.Array,
        admit: jax.Array,
    ):
        """Batched ragged prefill INTO slots: run the prompt block [S, L]
        (right-padded rows, real lengths in ``lengths`` [S]) once, and for
        every row with ``admit[s]`` True replace slot s's cache with the
        prompt's K/V and its length — rows with ``admit`` False keep their
        existing state bit-for-bit (they are mid-generation in other
        slots' requests). Returns (per-row logits at each row's LAST REAL
        position [S, vocab], updated cache).

        Pad positions are kept out of everything that could leak into real
        rows: attention masks keys ≥ lengths (``kv_lens``, both attention
        impls), MoE routing/capacity sees only real tokens (``lengths``
        threading, as in :meth:`apply_with_aux`), and the returned logits
        are gathered at ``lengths-1``. For a prompt at exactly L the masks
        are no-ops and the math is :meth:`prefill`'s — the serving parity
        contract (pinned in tests/test_serve.py). One compiled executable
        per (S, L) shape: serve.py pads prompts to a small set of length
        BUCKETS so the compile count stays bounded."""
        s, l = tokens.shape
        c = self.cache_len
        positions = jnp.arange(l)
        token_mask = positions[None, :] < lengths[:, None]  # [S, L]
        qd = self._kv_quant_dtype(cache)

        def attend(q, k, v):
            if qd is not None:
                # Uniform quantized-cache rule (see extend_paged): the
                # prompt's own K/V are round-tripped before the softmax
                # so the prefill scores over exactly the values the
                # cache write below stores — decode re-reading these
                # positions sees the same math this pick saw.
                k = dequantize_kv(*quantize_kv(k, qd), self.compute_dtype)
                v = dequantize_kv(*quantize_kv(v, qd), self.compute_dtype)
            return self._attend(q, k, v, kv_lens=lengths)

        h = self._embed_tokens(params, tokens, positions)

        def body(h, blk):
            h, kv, _ = self._block(
                blk,
                h,
                attend=attend,
                positions=positions,
                token_mask=token_mask,
            )
            return h, kv

        h, (ks, vs) = lax.scan(body, h, params.blocks)
        with jax.named_scope(names.KV_WRITE):
            if qd is None:
                ks = ks.astype(self.compute_dtype)  # [n, S, L, Hkv, Dh]
                vs = vs.astype(self.compute_dtype)
                ksc = vsc = None
            else:
                # Quantize-on-write (round 15): payload rows plus the per-
                # (position, head) scale side tensors, which follow the same
                # pad/rolling relayout minus the lane axis.
                ks, ksc = quantize_kv(ks, qd)  # [n,S,L,Hkv,Dh] + [n,S,L,Hkv]
                vs, vsc = quantize_kv(vs, qd)
            if l <= c:
                # Every prompt position p < lengths[s] <= c lands at slot
                # p % c = p: plain pad (the same layout prefill() writes).
                pad = [(0, 0), (0, 0), (0, c - l), (0, 0), (0, 0)]
                nk, nv = jnp.pad(ks, pad), jnp.pad(vs, pad)
                if qd is not None:
                    nksc = jnp.pad(ksc, pad[:-1])
                    nvsc = jnp.pad(vsc, pad[:-1])
            else:
                # Rolling window (c < L): per ROW, keep that row's last
                # min(c, len) real positions at slots p % c. Cache slot j
                # holds the largest prompt position p < len with p ≡ j
                # (mod c): p = j + c·⌊(len−1−j)/c⌋ — per-row dynamic, unlike
                # prefill()'s static arrays, because each row has its own len.
                idx = jnp.arange(c)[None, :]  # [1, c]
                p = idx + c * ((lengths[:, None] - 1 - idx) // c)  # [S, c]
                gather = jnp.clip(p, 0, l - 1)[None, :, :, None, None]
                nk = jnp.take_along_axis(ks, gather, axis=2)
                nv = jnp.take_along_axis(vs, gather, axis=2)
                if qd is not None:
                    nksc = jnp.take_along_axis(ksc, gather[..., 0], axis=2)
                    nvsc = jnp.take_along_axis(vsc, gather[..., 0], axis=2)
                # p < 0 rows (len <= j and no earlier wrap) hold garbage —
                # unreachable: the decode mask derives validity from lengths.
            m = admit[None, :, None, None, None]
            new_cache = SlotKVCache(
                k=jnp.where(m, nk, cache.k),
                v=jnp.where(m, nv, cache.v),
                lengths=jnp.where(admit, lengths, cache.lengths),
                k_scale=(
                    None
                    if qd is None
                    else jnp.where(m[..., 0], nksc, cache.k_scale)
                ),
                v_scale=(
                    None
                    if qd is None
                    else jnp.where(m[..., 0], nvsc, cache.v_scale)
                ),
            )
        h_last = jnp.take_along_axis(
            h, jnp.maximum(lengths - 1, 0)[:, None, None], axis=1
        )  # [S, 1, d]
        return self._logits(params, h_last)[:, 0], new_cache

    def _decode_block_step(self, blk, h, lengths, attend):
        """Shared per-slot single-token block math (layernorm / QKV /
        rope / attention / FFN) for BOTH single-token decode cache
        layouts. ``attend(q, k, v)`` owns everything layout-specific:
        given the query ([S, 1, Hq, Dh]) and the fresh K/V row
        ([S, 1, Hkv, Dh]) it returns the attention output
        ([S, Hkv, G, Dh]) and the cache state threaded back to the
        caller — the slab commits the row and attends the slot's whole
        static-length row (:meth:`_decode_attend`), the paged pool is
        read through its live-block list. Keeping the rest in ONE body
        is what keeps the slab and paged paths in lockstep (their token
        streams are pinned equal by test_gpt.py / test_serve.py)."""
        s = h.shape[0]
        with jax.named_scope(names.ATTN_QKV):
            hn = _layernorm(h, blk.ln1_scale, blk.ln1_bias)
            kv_shape = (s, 1, self.num_kv_heads, self.head_dim)
            q = self._dot(hn, blk.wq).reshape(
                s, 1, self.num_heads, self.head_dim
            )
            k = self._dot(hn, blk.wk).reshape(kv_shape)
            v = self._dot(hn, blk.wv).reshape(kv_shape)
            if self.pos_embedding == "rope":
                pos = lengths[:, None]  # [S, 1] — per-row absolute position
                q = _rope(q, pos)
                k = _rope(k, pos)
        attn, state = attend(q, k, v)
        return self._decode_block_tail(blk, h, attn), state

    def _decode_block_slots(
        self, blk, h, ck0, cv0, lengths, act, ks0=None, vs0=None, qd=None
    ):
        """Per-slot single-token block step — :meth:`_decode_block` with a
        VECTOR of positions: h [S, 1, d], ck0/cv0 [S, cache_len, Hkv, Dh],
        ``lengths`` [S] (each row's write position), ``act`` [S] bool
        (inactive rows write their old K/V back — a no-op — and their
        outputs are garbage the caller discards). Row-wise math is
        _decode_block's exactly (pinned by test_serve.py's token-parity
        tests); the scalar ``dynamic_update_slice`` becomes a per-row
        scatter and the validity mask broadcasts per row. Quantized
        caches (``qd`` + ks0/vs0 scale rows) quantize the fresh row on
        write and attend the dequantized view — same math, fewer bytes
        resident."""
        from distributed_tensorflow_tpu.ops.ring_attention import (
            group_query_heads,
        )

        c = self.cache_len

        def attend(q, k, v):
            slot = lengths % c if self.window is not None else lengths
            if qd is None:
                kq, vq = k.astype(ck0.dtype)[:, 0], v.astype(cv0.dtype)[:, 0]
                ksc = vsc = None
            else:
                kq, ksc = quantize_kv(k[:, 0], qd)  # [S,Hkv,Dh] + [S,Hkv]
                vq, vsc = quantize_kv(v[:, 0], qd)
            # Per-row scatter, inactive rows writing their old value back.
            ck, cv, nks, nvs = self._commit_slot_rows(
                ck0, cv0, ks0, vs0, kq, vq, ksc, vsc, lengths, act
            )
            state = (ck, cv, nks, nvs)
            if qd is None:
                ck_att, cv_att = ck, cv
            else:
                # Dequantize to compute_dtype, NOT f32: a f32 view would
                # double the compute-side intermediate and push the MXU
                # onto its multi-pass f32 path — the bandwidth win this
                # cache exists for (int8's |q| ≤ 127 and every e4m3
                # value upcast to bf16 exactly, so the pow2 equality
                # oracles survive the narrower view).
                ck_att = dequantize_kv(ck, nks, self.compute_dtype)
                cv_att = dequantize_kv(cv, nvs, self.compute_dtype)
            idx = jnp.arange(c)[None, :]  # [1, c]
            if self.window is not None:
                # Same rolling-buffer identity as _decode_block, per row.
                slot_pos = lengths[:, None] - jnp.mod(slot[:, None] - idx, c)
                valid = slot_pos >= 0  # [S, c]
            else:
                valid = idx <= lengths[:, None]  # [S, c]
            attn = self._decode_attend(
                "shgd,skhd->shgk", "shgk,skhd->shgd",
                group_query_heads(q[:, 0], self.num_kv_heads),
                ck_att, cv_att, valid[:, None, None, :],
            )
            return attn, state

        return self._decode_block_step(blk, h, lengths, attend)

    def decode_slots(
        self,
        params: GPTLMParams,
        token: jax.Array,
        cache: SlotKVCache,
        active: jax.Array | None = None,
    ):
        """Append one token per SLOT: token [S] int32 at each slot's own
        position. Returns (logits [S, vocab], cache with ``lengths``
        advanced where active). ``active`` [S] bool masks rows out of the
        update entirely (their cache row and length are untouched and
        their logits are garbage to discard) — finished/vacant slots ride
        along at full batch shape, which is what keeps ONE compiled
        executable serving every occupancy pattern. Layer loop UNROLLED
        for the same cache-double-buffering reason as :meth:`decode_step`.

        Stepping an ACTIVE row past ``max_len`` would corrupt its newest
        cache slot (scatter clamp semantics), so eager calls raise, as in
        :meth:`decode_step`; traced callers bound their own trip count
        (serve.py budgets every admit so prompt+generation fits)."""
        act = (
            jnp.ones((token.shape[0],), bool) if active is None else active
        )
        if not isinstance(cache.lengths, jax.core.Tracer) and not isinstance(
            act, jax.core.Tracer
        ):
            worst = int(jnp.max(jnp.where(act, cache.lengths, 0)))
            if bool(jnp.any(act)) and worst >= self.max_len:
                raise ValueError(
                    f"KV cache full: an active slot is at length {worst} == "
                    f"max_len {self.max_len}; increase max_len"
                )
        h = self._embed_tokens(
            params, token[:, None], cache.lengths[:, None]
        )
        qd = self._kv_quant_dtype(cache)
        nks, nvs, nksc, nvsc = [], [], [], []
        for i in range(self.num_layers):
            blk = jax.tree.map(lambda x: x[i], params.blocks)
            ck0, cv0, ks0, vs0 = _cache_layer(cache, i)
            h, (ck, cv, ksc, vsc) = self._decode_block_slots(
                blk, h, ck0, cv0, cache.lengths, act, ks0, vs0, qd
            )
            nks.append(ck)
            nvs.append(cv)
            nksc.append(ksc)
            nvsc.append(vsc)
        nk, nv, nks, nvs = _restack(nks, nvs, nksc, nvsc)
        new_cache = SlotKVCache(
            k=nk, v=nv, k_scale=nks, v_scale=nvs,
            lengths=cache.lengths + act.astype(jnp.int32),
        )
        return self._logits(params, h)[:, 0], new_cache

    # -- paged decoding (block-table cache, serve.py paged=True) -----------

    def paged_blocks_per_slot(self, block_size: int) -> int:
        """Static block-table width: blocks to address ``max_len``
        positions (the table is sized for the worst request; the POOL is
        what paging shrinks)."""
        if block_size < 1:
            raise ValueError(f"block_size must be >= 1, got {block_size}")
        return -(-self.max_len // block_size)

    def empty_paged_cache(
        self,
        slots: int,
        num_blocks: int,
        block_size: int = 16,
        kv_dtype: str = "bf16",
    ) -> PagedKVCache:
        """A vacant :class:`PagedKVCache`: ``num_blocks`` pool blocks of
        ``block_size`` positions each (the HBM actually reserved —
        compare the slab's ``slots × cache_len``), all-zero block tables
        (garbage mappings, unreachable while lengths are 0). Windowed
        models keep FULL history here — the paged layout addresses
        absolutely and windows by mask, trading the rolling buffer's
        O(W) bound for block sharing (``serve_pool.PrefixCache``).
        ``kv_dtype="int8"|"fp8"`` shrinks every pool block to 1-byte
        elements with per-row scale side pools — the serving engine
        derives MORE blocks from the same HBM budget
        (``serve_pool.blocks_for_hbm_bytes``)."""
        if slots < 1:
            raise ValueError(f"slots must be >= 1, got {slots}")
        if num_blocks < 1:
            raise ValueError(f"num_blocks must be >= 1, got {num_blocks}")
        nb_slot = self.paged_blocks_per_slot(block_size)
        shape = (
            self.num_layers,
            num_blocks,
            block_size,
            self.num_kv_heads,
            self.head_dim,
        )
        # One buffer each, as in :meth:`empty_slot_cache`.
        dt = kv_storage_dtype(kv_dtype, self.compute_dtype)
        scaled = kv_dtype != "bf16"
        return PagedKVCache(
            k=jnp.zeros(shape, dt),
            v=jnp.zeros(shape, dt),
            block_tables=jnp.zeros((slots, nb_slot), jnp.int32),
            lengths=jnp.zeros((slots,), jnp.int32),
            k_scale=jnp.zeros(shape[:-1], jnp.float32) if scaled else None,
            v_scale=jnp.zeros(shape[:-1], jnp.float32) if scaled else None,
        )

    def extend_paged(
        self,
        params: GPTLMParams,
        cache: PagedKVCache,
        tokens: jax.Array,
        suffix_lens: jax.Array,
        prefix_lens: jax.Array,
        admit: jax.Array,
    ):
        """Batched ragged EXTEND through the block tables: run suffix
        block ``tokens`` [S, L] (right-padded rows, real lengths
        ``suffix_lens`` [S]) at absolute positions
        ``prefix_lens[s] + 0..L-1``, attending each suffix query over the
        slot's cached prefix (read through its block table) plus the
        suffix itself causally, and scatter the suffix K/V into the pool
        where ``admit``. Returns (per-position logits [S, L, vocab],
        cache with K/V written). ``lengths``/``block_tables`` are NOT
        touched — the caller owns commit semantics, because the two
        callers commit differently: admission prefill commits
        ``prefix + suffix`` wholesale, the speculative verify graph
        commits only ``accepted + 1`` tokens (rejected drafts' K/V stay
        as unreachable garbage past ``lengths`` and are overwritten by
        the next write at that position).

        ``prefix_lens = 0`` is plain ragged prefill (the paged analog of
        :meth:`prefill_slots`); block-aligned nonzero prefixes are the
        prefix-cache hit path — the shared system prompt's K/V is read,
        never recomputed. The caller guarantees every written position
        ``< prefix + suffix ≤`` the slot's reserved table extent (the
        engine budgets ``prompt + max_new`` blocks at admission)."""
        from distributed_tensorflow_tpu.ops import paged_attention as paged

        s, l = tokens.shape
        positions = prefix_lens[:, None] + jnp.arange(l)[None, :]  # [S, L]
        token_mask = jnp.arange(l)[None, :] < suffix_lens[:, None]
        h = self._embed_tokens(params, tokens, positions)
        qd = self._kv_quant_dtype(cache)

        def make_attend(pk, pv, pks, pvs):
            def attend(q, k, v):
                kview = paged.gather_block_view(pk, cache.block_tables)
                vview = paged.gather_block_view(pv, cache.block_tables)
                if qd is not None:
                    # Dequantize-on-read: the scale side pools gather
                    # through the SAME tables (identical index math,
                    # one fewer axis), so cached-prefix K/V arrive as
                    # values. The suffix's own fresh k/v are ROUND-
                    # TRIPPED through the same quantizer before the
                    # softmax — attention must see exactly the values
                    # the scatter below will store, or a token scored
                    # here (the speculative verify, a prefill pick)
                    # could differ from the same position re-scored by
                    # decode_paged reading the cache; the uniform rule
                    # "a quantized cache attends quantized values
                    # EVERYWHERE" is what keeps spec == non-spec and
                    # paged == slab token-identical.
                    kview = dequantize_kv(
                        kview,
                        paged.gather_block_view(pks, cache.block_tables),
                        self.compute_dtype,
                    )
                    vview = dequantize_kv(
                        vview,
                        paged.gather_block_view(pvs, cache.block_tables),
                        self.compute_dtype,
                    )
                    k = dequantize_kv(*quantize_kv(k, qd), self.compute_dtype)
                    v = dequantize_kv(*quantize_kv(v, qd), self.compute_dtype)
                return paged.paged_extend_attention(
                    q, k, v, kview, vview, positions, prefix_lens,
                    suffix_lens, window=self.window,
                )

            return attend

        def body(h, xs):
            blk, pk, pv = xs[0], xs[1], xs[2]
            pks, pvs = (xs[3], xs[4]) if qd is not None else (None, None)
            h, kv, _ = self._block(
                blk, h, attend=make_attend(pk, pv, pks, pvs),
                positions=positions, token_mask=token_mask,
            )
            return h, kv

        xs_all = (params.blocks, cache.k, cache.v)
        if qd is not None:
            xs_all += (cache.k_scale, cache.v_scale)
        h, (ks, vs) = lax.scan(body, h, xs_all)
        valid = token_mask & admit[:, None]
        if qd is None:
            ks = ks.astype(cache.k.dtype)  # [n, S, L, Hkv, Dh]
            vs = vs.astype(cache.v.dtype)
            nksc, nvsc = cache.k_scale, cache.v_scale
        else:
            ks, ksc = quantize_kv(ks, qd)  # + [n, S, L, Hkv] scales
            vs, vsc = quantize_kv(vs, qd)
            nksc = paged.scatter_token_kv_all_layers(
                cache.k_scale, ksc, cache.block_tables, positions, valid
            )
            nvsc = paged.scatter_token_kv_all_layers(
                cache.v_scale, vsc, cache.block_tables, positions, valid
            )
        nk = paged.scatter_token_kv_all_layers(
            cache.k, ks, cache.block_tables, positions, valid
        )
        nv = paged.scatter_token_kv_all_layers(
            cache.v, vs, cache.block_tables, positions, valid
        )
        return self._logits(params, h), cache._replace(
            k=nk, v=nv, k_scale=nksc, v_scale=nvsc
        )

    def _decode_block_paged(self, blk, h, cache, layer, live, qd=None):
        """Per-slot single-token block step against the BLOCK POOL. The
        layer-stacked pool is READ-ONLY here: attention walks the
        live-block list ``live`` through layer ``layer`` of the stack
        (``ops/paged_attention.paged_decode_attention``: a tile of one
        slot's resident blocks a turn, folded into that slot's running
        softmax)
        and meets the row this step writes as one more key, so no view
        of a slot's whole table is ever built. A pool position is valid
        below ``lengths[s]``; windowed models band by mask
        (``> lengths − W``) — absolute addressing, no rolling arithmetic.
        Quantized pools (``qd``) quantize the fresh row and attend it
        dequantized again, as it will be read back; the tiles are
        dequantized with the scale side pools read through the same
        list. Returns ``(h, (kq, vq, ksc, vsc))``: the rows
        (``[S, Hkv, Dh]``, scales ``[S, Hkv]`` or None) that
        :meth:`decode_paged` commits for all layers at once."""
        from distributed_tensorflow_tpu.ops import paged_attention as paged

        def attend(q, k, v):
            if qd is None:
                kq = k.astype(cache.k.dtype)[:, 0]
                vq = v.astype(cache.v.dtype)[:, 0]
                ksc = vsc = None
                k_row, v_row = kq, vq
            else:
                kq, ksc = quantize_kv(k[:, 0], qd)  # [S,Hkv,Dh] + [S,Hkv]
                vq, vsc = quantize_kv(v[:, 0], qd)
                # compute_dtype rows, not f32 (see _decode_block_slots).
                k_row = dequantize_kv(kq, ksc, self.compute_dtype)
                v_row = dequantize_kv(vq, vsc, self.compute_dtype)
            attn = paged.paged_decode_attention(
                q[:, 0], k_row, v_row, cache.k, cache.v, layer, live,
                cache.lengths, window=self.window,
                k_scale=cache.k_scale if qd else None,
                v_scale=cache.v_scale if qd else None,
            )
            return attn, (kq, vq, ksc, vsc)

        return self._decode_block_step(blk, h, cache.lengths, attend)

    def decode_paged(
        self,
        params: GPTLMParams,
        token: jax.Array,
        cache: PagedKVCache,
        active: jax.Array | None = None,
        *,
        live=None,
    ):
        """Append one token per slot through the block tables — the
        paged counterpart of :meth:`decode_slots` (same masking
        contract: inactive rows untouched, garbage logits to discard).
        The caller guarantees each active slot's table covers position
        ``lengths[s]`` (the engine reserves ``prompt + max_new`` blocks
        at admission, so generation never outgrows the table).

        The step never moves the pool and reads of it only what is
        resident: the layer loop (UNROLLED, as in :meth:`decode_step`)
        walks the live-block list ``live``
        (``ops/paged_attention.live_block_list``) through each layer of
        the one stacked ``[layers, blocks, block_size, Hkv, Dh]`` array.
        A caller that steps many times makes the list once, for that
        many steps, and hands it in (the server's chunk scan); a call
        that brings none gets one made here for its single step. ONE
        update for K and one for V (and one per scale pool) commits
        every layer's fresh row after it
        (``ops/paged_attention.commit_token_rows``: the places and the
        sentinel-drop of the commit :meth:`extend_paged` makes after its
        layer scan). The result is the argument changed at
        ``slots × layers`` rows, which XLA does in place on a loop carry
        and on a donated argument (``TextServer`` gives it both). The
        pools may also come with each position's ``[Hkv, Dh]`` row flat,
        ``[layers, blocks, block_size, Hkv·Dh]``, as the server's chunk
        scan carries them (the chip tiles an array by its two minor
        axes); they go back as they came."""
        act = (
            jnp.ones((token.shape[0],), bool) if active is None else active
        )
        if not isinstance(cache.lengths, jax.core.Tracer) and not isinstance(
            act, jax.core.Tracer
        ):
            worst = int(jnp.max(jnp.where(act, cache.lengths, 0)))
            if bool(jnp.any(act)) and worst >= self.max_len:
                raise ValueError(
                    f"KV cache full: an active slot is at length {worst} == "
                    f"max_len {self.max_len}; increase max_len"
                )
        h = self._embed_tokens(
            params, token[:, None], cache.lengths[:, None]
        )
        qd = self._kv_quant_dtype(cache)
        from distributed_tensorflow_tpu.ops import paged_attention as paged

        if live is None:
            live = paged.live_block_list(
                cache.block_tables, cache.lengths, act, 1,
                cache.k.shape[1], cache.k.shape[2],
            )
        fresh = []
        for i in range(self.num_layers):
            blk = jax.tree.map(lambda x: x[i], params.blocks)
            h, rows = self._decode_block_paged(blk, h, cache, i, live, qd)
            fresh.append(rows)

        def commit(pool, rows):
            return paged.commit_token_rows(
                pool, jnp.stack(rows), cache.block_tables, cache.lengths, act
            )

        kq, vq, ksc, vsc = zip(*fresh)
        new_cache = cache._replace(
            k=commit(cache.k, kq),
            v=commit(cache.v, vq),
            k_scale=None if qd is None else commit(cache.k_scale, ksc),
            v_scale=None if qd is None else commit(cache.v_scale, vsc),
            lengths=cache.lengths + act.astype(jnp.int32),
        )
        return self._logits(params, h)[:, 0], new_cache

    def _check_decode_bounds(self, prompt, max_new):
        """Shared generation-length validation (every decode entry point:
        greedy / sampled / beam)."""
        if max_new < 1:
            raise ValueError(f"max_new must be >= 1, got {max_new}")
        if prompt.shape[1] + max_new > self.max_len:
            raise ValueError(
                f"prompt {prompt.shape[1]} + max_new {max_new} exceeds "
                f"max_len {self.max_len}"
            )

    def _decode_loop(self, params, prompt, max_new, pick, key):
        """Shared generation scaffold: prefill, then one ``lax.scan`` of
        decode steps, each choosing the next token via ``pick(logits, key)``
        (greedy ignores the key). Returns [B, L0 + max_new]."""
        self._check_decode_bounds(prompt, max_new)
        logits, cache = self.prefill(params, prompt)
        key, sub = jax.random.split(key)
        first = pick(logits, sub)

        def body(carry, _):
            tok, cache, key = carry
            logits, cache = self.decode_step(params, tok, cache)
            key, sub = jax.random.split(key)
            nxt = pick(logits, sub)
            return (nxt, cache, key), nxt

        if max_new > 1:
            _, rest = lax.scan(
                body, (first, cache, key), None, length=max_new - 1
            )
            generated = jnp.concatenate([first[None], rest], axis=0).swapaxes(
                0, 1
            )
        else:
            generated = first[:, None]
        return jnp.concatenate([prompt, generated], axis=1)

    def greedy_decode(
        self, params: GPTLMParams, prompt: jax.Array, max_new: int
    ) -> jax.Array:
        """[B, L0] prompt → [B, L0 + max_new] (``max_new`` ≥ 1); the whole
        generation loop is one ``lax.scan`` (jit it once, no host
        round-trips per token)."""

        def pick(logits, _key):
            return jnp.argmax(logits, axis=-1).astype(prompt.dtype)

        return self._decode_loop(
            params, prompt, max_new, pick, jax.random.key(0)
        )

    def sample_decode(
        self,
        params: GPTLMParams,
        prompt: jax.Array,
        max_new: int,
        key: jax.Array,
        *,
        temperature: float = 1.0,
        top_k: int | None = None,
        top_p: float | None = None,
    ) -> jax.Array:
        """Stochastic counterpart of :meth:`greedy_decode`: categorical
        sampling from ``logits/temperature``, optionally truncated to the
        ``top_k`` highest-probability tokens and/or the ``top_p`` nucleus
        (smallest prefix of the probability-sorted vocabulary whose mass
        reaches p — Holtzman et al.'s nucleus sampling; applied after
        ``top_k`` when both are set, the usual composition). Same
        one-``lax.scan`` shape — the PRNG key rides the carry, so
        generation stays fully on-device and reproducible per key.
        ``top_k=1`` is exactly greedy; ``top_p=1.0`` keeps everything."""
        if temperature <= 0:
            raise ValueError(f"temperature must be > 0, got {temperature}")
        if top_k is not None and not 1 <= top_k <= self.vocab_size:
            raise ValueError(
                f"top_k must be in [1, {self.vocab_size}], got {top_k}"
            )
        if top_p is not None and not 0.0 < top_p <= 1.0:
            raise ValueError(f"top_p must be in (0, 1], got {top_p}")

        def pick(logits, k):
            logits = logits.astype(jnp.float32) / temperature
            if top_k is not None:
                # Scatter the top_k entries onto a -inf canvas: exactly
                # top_k candidates survive even on exact logit ties (a
                # >= kth threshold would keep every token tied with the
                # k-th — plausible at low-entropy bf16 logits).
                vals, idx = lax.top_k(logits, top_k)
                rows = jnp.arange(logits.shape[0])[:, None]
                logits = jnp.full_like(logits, -jnp.inf).at[rows, idx].set(vals)
            if top_p is not None and top_p < 1.0:
                # Keep tokens whose EXCLUSIVE cumulative probability (mass
                # strictly ahead of them in sorted order) is < p: the
                # smallest prefix reaching p mass, never empty (the top
                # token's exclusive mass is 0), and the boundary token
                # that crosses p is kept — the standard nucleus rule.
                # Scatter the keep mask back through the sort order (not a
                # >=-threshold test, which would re-admit tokens exactly
                # tied with the boundary — the same tie hazard the top_k
                # scatter above avoids).
                order = jnp.argsort(logits, axis=-1)[..., ::-1]
                sorted_l = jnp.take_along_axis(logits, order, axis=-1)
                probs = jax.nn.softmax(sorted_l, axis=-1)
                keep_sorted = jnp.cumsum(probs, axis=-1) - probs < top_p
                rows = jnp.arange(logits.shape[0])[:, None]
                keep = (
                    jnp.zeros(logits.shape, bool).at[rows, order]
                    .set(keep_sorted)
                )
                logits = jnp.where(keep, logits, -jnp.inf)
            return jax.random.categorical(k, logits, axis=-1).astype(
                prompt.dtype
            )

        return self._decode_loop(params, prompt, max_new, pick, key)

    def beam_decode(
        self,
        params: GPTLMParams,
        prompt: jax.Array,
        max_new: int,
        beam_size: int,
        *,
        eos_id: int | None = None,
        length_penalty: float = 0.0,
    ) -> jax.Array:
        """Beam search over the KV cache: keep the ``beam_size`` highest
        log-probability continuations at every step, all beams advancing
        in ONE batched decode (the cache runs at batch B·K; beam
        reordering is a gather on its batch dim), the whole search one
        ``lax.scan`` like the samplers. Returns the best beam per row,
        [B, L0 + max_new].

        ``eos_id``: a beam that emits it is finished — it only extends
        with further ``eos_id`` tokens at zero cost (its score freezes),
        so the returned row is the sequence followed by EOS padding.
        ``length_penalty`` α ranks final beams by ``score / len_gen**α``
        (α=0 — the default — is pure summed log-probability; α>0 favors
        longer finished sequences, the usual normalization); ``len_gen``
        counts generated tokens up to and including the first EOS.

        ``beam_size=1`` is exactly :meth:`greedy_decode`. The first
        expansion seeds at most ``vocab_size`` distinct beams (top-k of
        one distribution), so ``beam_size`` must be ≤ ``vocab_size``."""
        b, l0 = prompt.shape
        kbeams = beam_size
        self._check_decode_bounds(prompt, max_new)
        if not 1 <= kbeams <= self.vocab_size:
            raise ValueError(
                f"beam_size must be in [1, {self.vocab_size}], got {kbeams}"
            )
        if eos_id is not None and not 0 <= eos_id < self.vocab_size:
            raise ValueError(
                f"eos_id must be in [0, {self.vocab_size}), got {eos_id}"
            )
        v = self.vocab_size

        logits, cache = self.prefill(params, prompt)
        logp0 = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        scores, tok = lax.top_k(logp0, kbeams)  # [B, K]
        tok = tok.astype(prompt.dtype)
        cache = KVCache(
            k=jnp.repeat(cache.k, kbeams, axis=1),
            v=jnp.repeat(cache.v, kbeams, axis=1),
            length=cache.length,
        )
        seqs = jnp.zeros((b, kbeams, max_new), prompt.dtype)
        seqs = seqs.at[:, :, 0].set(tok)
        finished = (
            tok == eos_id
            if eos_id is not None
            else jnp.zeros((b, kbeams), bool)
        )

        def body(carry, t):
            seqs, scores, finished, cache, tok = carry
            step_logits, cache = self.decode_step(
                params, tok.reshape(b * kbeams), cache
            )
            logp = jax.nn.log_softmax(
                step_logits.astype(jnp.float32), axis=-1
            ).reshape(b, kbeams, v)
            if eos_id is not None:
                # Finished beams extend only with EOS, at zero cost.
                only_eos = jnp.full((v,), -jnp.inf).at[eos_id].set(0.0)
                logp = jnp.where(finished[..., None], only_eos, logp)
            flat = (scores[..., None] + logp).reshape(b, kbeams * v)
            scores, idx = lax.top_k(flat, kbeams)
            parent = idx // v  # [B, K] — which beam each winner extends
            tok = (idx % v).astype(prompt.dtype)
            flat_parent = (
                jnp.arange(b)[:, None] * kbeams + parent
            ).reshape(b * kbeams)
            cache = KVCache(
                k=jnp.take(cache.k, flat_parent, axis=1),
                v=jnp.take(cache.v, flat_parent, axis=1),
                length=cache.length,
            )
            seqs = jnp.take_along_axis(seqs, parent[..., None], axis=1)
            seqs = lax.dynamic_update_slice(seqs, tok[..., None], (0, 0, t))
            finished = jnp.take_along_axis(finished, parent, axis=1)
            if eos_id is not None:
                finished = finished | (tok == eos_id)
            return (seqs, scores, finished, cache, tok), None

        if max_new > 1:
            (seqs, scores, finished, _, _), _ = lax.scan(
                body,
                (seqs, scores, finished, cache, tok),
                jnp.arange(1, max_new),
            )
        # Rank beams: generated length = up to and including first EOS.
        if eos_id is not None and length_penalty != 0.0:
            is_eos = seqs == eos_id
            first_eos = jnp.argmax(is_eos, axis=-1)  # 0 when none
            has_eos = jnp.any(is_eos, axis=-1)
            gen_len = jnp.where(has_eos, first_eos + 1, max_new)
        else:
            gen_len = jnp.full((b, kbeams), max_new)
        ranked = scores / jnp.maximum(
            gen_len.astype(jnp.float32), 1.0
        ) ** jnp.float32(length_penalty)
        best = jnp.argmax(ranked, axis=-1)  # [B]
        best_seq = jnp.take_along_axis(
            seqs, best[:, None, None], axis=1
        )[:, 0]
        return jnp.concatenate([prompt, best_seq], axis=1)


def _cache_layer(cache, i: int):
    """Layer ``i``'s ``(k, v, k_scale, v_scale)`` out of a layer-stacked
    cache (scales None on a bf16 cache, and on a :class:`KVCache`, which
    has none). With :func:`_restack` this is the per-step restack of the
    whole cache that the unrolled loops of ``decode_step`` and
    ``decode_slots`` pay — scoped, so a trace shows what it costs
    (``decode_paged`` reads the stack in place instead: the restack was
    most of its step at gpt2-large's size, PERF.md §6, PR 27)."""
    with jax.named_scope(names.KV_RESTACK):
        ks = getattr(cache, "k_scale", None)
        vs = getattr(cache, "v_scale", None)
        return (
            cache.k[i], cache.v[i],
            None if ks is None else ks[i],
            None if vs is None else vs[i],
        )


def _restack(nks, nvs, nksc=(), nvsc=()):
    """The per-layer results of an unrolled decode loop back into the
    layer-stacked layout (scale lists of Nones, or empty, give None):
    the other half of :func:`_cache_layer`, for the same callers."""
    with jax.named_scope(names.KV_RESTACK):
        scaled = bool(nksc) and nksc[0] is not None
        return (
            jnp.stack(nks), jnp.stack(nvs),
            jnp.stack(nksc) if scaled else None,
            jnp.stack(nvsc) if scaled else None,
        )


def export_kv_blocks(cache: PagedKVCache, block_ids) -> dict:
    """Lift the named pool blocks out of a :class:`PagedKVCache` as host
    arrays — the wire half of the round-23 prefill→decode handoff. The
    payload carries the EXACT storage-dtype bytes (bf16, or the int8/fp8
    1-byte elements plus their per-row f32 scale side tensors at the
    same block coordinates), so an import followed by attention
    reproduces the source replica's dequantized values bit-for-bit (the
    round-15 uniform rule is what makes the migrated stream
    token-identical). ``block_ids`` must be valid pool indices — export
    has no sentinel (you cannot export a block you never wrote).

    Returns ``{"k", "v"[, "k_scale", "v_scale"]}`` with payload shape
    ``[num_layers, n, block_size, Hkv, Dh]`` (scales one axis fewer)."""
    ids = jnp.asarray(block_ids, jnp.int32)
    if ids.ndim != 1:
        raise ValueError(f"block_ids must be 1-D, got shape {ids.shape}")
    out = {"k": cache.k[:, ids], "v": cache.v[:, ids]}
    if cache.k_scale is not None:
        out["k_scale"] = cache.k_scale[:, ids]
        out["v_scale"] = cache.v_scale[:, ids]
    return out


def import_kv_blocks(cache: PagedKVCache, block_ids, blocks: dict) -> PagedKVCache:
    """Write exported block payloads into this pool at ``block_ids`` —
    the receiving half of :func:`export_kv_blocks`. Values land verbatim
    in storage dtype (scale side pools ride the same index math, one
    fewer axis), so export→import round-trips bit-exactly.

    Sentinel rule (round 11): an id equal to ``num_blocks`` DROPS that
    payload row instead of writing it — never ``-1``, which JAX wraps to
    the last real block and corrupts it silently. Implemented the way
    the runtime scatters do: the pool is extended by one garbage block
    at index ``num_blocks`` that the final slice discards."""
    ids = jnp.asarray(block_ids, jnp.int32)
    nb = cache.k.shape[1]
    if bool(jnp.any((ids < 0) | (ids > nb))):
        raise ValueError(
            f"block id out of range [0, {nb}] (sentinel={nb} drops; -1 "
            "would wrap and corrupt the last block)"
        )

    def put(pool, payload):
        if payload.shape[1:] != (ids.shape[0],) + pool.shape[2:]:
            raise ValueError(
                f"payload shape {payload.shape} does not match pool "
                f"{pool.shape} over {ids.shape[0]} blocks"
            )
        ext = jnp.concatenate([pool, jnp.zeros_like(pool[:, :1])], axis=1)
        ext = ext.at[:, ids].set(jnp.asarray(payload).astype(pool.dtype))
        return ext[:, :nb]

    has_scale = cache.k_scale is not None
    if has_scale != ("k_scale" in blocks):
        raise ValueError(
            "scale side tensors must travel with a quantized pool and "
            "only with one (pool has scales: %s, payload has: %s)"
            % (has_scale, "k_scale" in blocks)
        )
    return cache._replace(
        k=put(cache.k, blocks["k"]),
        v=put(cache.v, blocks["v"]),
        k_scale=put(cache.k_scale, blocks["k_scale"]) if has_scale else None,
        v_scale=put(cache.v_scale, blocks["v_scale"]) if has_scale else None,
    )


def _picked_nll(logits32, targets):
    """Per-position negative log-likelihood ``logsumexp(x) − x[target]``
    with the pick as a fused compare-and-reduce over the vocab axis, NOT
    a ``take_along_axis`` gather: TPU scalar gathers along the tiled
    minor (vocab) dimension are catastrophically slow — at gpt-l shapes
    ([8, 1023, 8192]) the gather formulation measured 25.2 ms per step
    vs 1.1 ms for this one (23×; the whole full-vocab ``log_softmax``
    materialization also disappears). Same values: the gathered
    log-softmax IS ``x[t] − lse``."""
    lse = jax.scipy.special.logsumexp(logits32, axis=-1)
    vocab = jnp.arange(logits32.shape[-1])
    picked = jnp.sum(
        jnp.where(vocab == targets[..., None], logits32, 0.0), axis=-1
    )
    return lse - picked


def _ce_from_logits(logits, tokens, lengths=None):
    """Mean next-token cross-entropy (positions 0..L-2 predict 1..L-1, f32
    ``logsumexp − picked``), masked over ``lengths`` when given — the ONE
    CE arithmetic shared by :meth:`GPTLM.loss_and_metrics` and every
    parallel train-step factory below (a divergence here would silently
    break their proven equality with the single-device step)."""
    with jax.named_scope(names.LOSS):
        nll = _picked_nll(logits[:, :-1].astype(jnp.float32), tokens[:, 1:])
        if lengths is None:
            return jnp.mean(nll)
        # Target at position i is token i+1 → valid iff i+1 < lengths[b].
        w = (
            jnp.arange(tokens.shape[1] - 1)[None, :] < (lengths[:, None] - 1)
        ).astype(jnp.float32)
        return jnp.sum(nll * w) / jnp.maximum(jnp.sum(w), 1.0)


def expert_parallel_specs(model: GPTLM, axis_name: str = "expert"):
    """PartitionSpec layout for expert parallelism: every leaf replicated
    except the MoE blocks' expert-stacked FFN weights, sharded on their
    expert dim (axis 1 — axis 0 is num_layers). The layout
    ``apply_expert_parallel`` / ``make_lm_ep_train_step`` consume."""
    from jax.sharding import PartitionSpec as P

    if model.moe_experts is None:
        raise ValueError("expert_parallel_specs requires moe_experts")
    return GPTLMParams(
        embed=P(),
        pos=P(),
        blocks=GPTMoEBlockParams(
            ln1_scale=P(), ln1_bias=P(), wq=P(), wk=P(), wv=P(), wo=P(),
            ln2_scale=P(), ln2_bias=P(), wg=P(),
            w_up=P(None, axis_name),
            b_up=P(None, axis_name),
            w_down=P(None, axis_name),
            b_down=P(None, axis_name),
        ),
        lnf_scale=P(),
        lnf_bias=P(),
    )


# Generic layout utilities, shared with the LM trainer's ZeRO mode and the
# rest of the parallel surface (parallel/specs.py is their home).
from distributed_tensorflow_tpu.parallel.specs import (  # noqa: E402
    as_shardings as _as_shardings,
    pinned_update as _pinned_update,
    slot_specs as _slot_specs,
)


def make_lm_ep_train_step(
    model: GPTLM,
    optimizer,
    mesh,
    axis: str = "expert",
    *,
    data_axis: str | None = None,
):
    """Expert-parallel TRAINING step for the MoE LM: one expert's FFN
    weights (and their optimizer slots) live on each device of ``axis``,
    tokens are sharded on the batch dim, every block's FFN is the
    all-to-all exchange (``ops/moe.moe_ffn``), and gradients flow back
    through the collectives. ``step(params, opt_state, tokens) ->
    (params, opt_state, loss)``, jitted, with params laid out per
    :func:`expert_parallel_specs` (place them with ``jax.device_put``
    before the first call, or let shard_map reshard).

    ``data_axis`` composes data parallelism on top — real MoE training is
    dp×ep on a 2-D ``(data, expert)`` mesh (the reference's only
    composition story is multi-ps × multi-worker, reference README.md:
    166-254; this is its modern form). The batch dim is sharded over BOTH
    axes (data-major), expert weights stay sharded over ``axis`` only
    (replicated across ``data``), and each data row runs its own expert
    all-to-all over ``axis``. The ``axis`` size must still equal
    ``moe_experts`` (that equality is the all-to-all's layout); the data
    axis is free, so the device count scales past the expert count.

    The differentiated loss is the cross-device ``pmean`` (over both axes
    when dp is on) of the local masked CE plus the router aux terms (the
    same total ``loss_and_metrics`` builds): differentiating the *global*
    mean makes shard_map's automatic psum of replicated-leaf cotangents
    produce exactly the global gradient — no manual rescaling — while each
    expert's sharded weights receive their data-summed local gradient
    through the all-to-all transpose.

    Semantics vs the dense step: the CE term equals the dense global-batch
    CE exactly in the no-drop regime (capacity is per source shard, like
    the forward); the aux terms are *per-shard* balance/z-losses averaged
    over shards — standard EP practice (each device regularizes its own
    router view), differing from the dense global-batch aux by the
    product-of-averages gap. tests/test_gpt.py pins the exact semantics
    against a shard-wise dense reference, for 1-D ep and 2-D dp×ep."""
    specs, opt_specs, mapped = make_lm_ep_parts(
        model, optimizer, mesh, axis, data_axis=data_axis
    )

    @jax.jit
    def step(params, opt_state, tokens):
        return mapped(params, opt_state, tokens, None)

    return step


def make_lm_ep_parts(
    model: GPTLM,
    optimizer,
    mesh,
    axis: str = "expert",
    *,
    data_axis: str | None = None,
    ragged: bool = False,
):
    """Building blocks behind :func:`make_lm_ep_train_step`, exposed (like
    :func:`make_lm_async_parts`) so the LM trainer can embed the
    expert-parallel update inside its scanned-epoch / whole-run-compiled
    bodies. Returns ``(specs, opt_specs, mapped)``:

    - ``specs`` / ``opt_specs`` — PartitionSpec pytrees for the params and
      their optimizer slots (:func:`expert_parallel_specs` + slot
      matching); place states with ``NamedSharding(mesh, spec)``;
    - ``mapped(params, opt_state, tokens, lengths) -> (params, opt_state,
      loss)`` — NOT jitted (call inside your own jit/scan); tokens [B, L]
      sharded on the batch dim over ``(data_axis?, axis)``, ``lengths``
      [B] for ragged corpora (masked CE + masked routing per shard, the
      same pad-independence the dense path proves) or None (``ragged`` is
      a factory-time choice — it shapes the shard_map signature).

    Ragged loss convention: the differentiated loss is the pmean of each
    shard's *masked mean* CE — shards weight equally regardless of their
    valid-token counts (the same convention as ``make_lm_async_parts``'s
    per-copy masked CE), equal to the global masked mean exactly when the
    per-shard valid counts are equal."""
    import optax
    from jax.sharding import PartitionSpec as P

    if model.moe_experts is None:
        raise ValueError("make_lm_ep_train_step requires moe_experts")
    n = mesh.shape[axis]
    if n != model.moe_experts:
        raise ValueError(
            f"{axis!r} axis size {n} != moe_experts {model.moe_experts}"
        )
    if data_axis is not None and data_axis not in mesh.shape:
        raise ValueError(f"mesh has no {data_axis!r} axis: {dict(mesh.shape)}")
    if data_axis == axis:
        raise ValueError(
            f"data_axis must differ from the expert axis {axis!r}"
        )
    axes = (axis,) if data_axis is None else (data_axis, axis)
    batch_spec = P(axis) if data_axis is None else P((data_axis, axis))
    specs = expert_parallel_specs(model, axis)
    params_shape = jax.eval_shape(model.init, 1)
    opt_specs = _slot_specs(optimizer, params_shape, specs)

    def ep_loss(params, tokens, lens):
        logits, auxs = model.apply_expert_parallel(
            params, tokens, axis, with_aux=True, lengths=lens
        )
        ce = lax.pmean(_ce_from_logits(logits, tokens, lens), axes)
        balance = lax.pmean(jnp.mean(auxs.balance_loss), axes)
        z = lax.pmean(jnp.mean(auxs.z_loss), axes)
        return (
            ce
            + model.moe_balance_coef * balance
            + model.moe_z_coef * z
        )

    def local(params, opt_state, tokens, lens):
        loss, grads = jax.value_and_grad(ep_loss)(
            params, tokens, lens if ragged else None
        )
        updates, opt_state = optimizer.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        return params, opt_state, loss

    lens_spec = batch_spec if ragged else P()
    inner = jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(specs, opt_specs, batch_spec, lens_spec),
        out_specs=(specs, opt_specs, P()),
    )

    def mapped(params, opt_state, tokens, lens):
        if lens is None:
            lens = _default_lens(tokens, ragged)
        return inner(params, opt_state, tokens, lens)

    return specs, opt_specs, mapped


def _default_lens(tokens, ragged: bool):
    """Placeholder for a factory's ``lens=None`` call. Non-ragged: the
    local body ignores lens and a rank-0 zero matches the P() spec.
    Ragged: the lens spec is rank-1 over the batch axis, so a rank-0
    placeholder would die in shard_map with a confusing spec/operand
    mismatch — synthesize full lengths instead (every position real ==
    the non-ragged loss). Shared by the ep/sp/async factories (advisor
    r4: the original rank-0 bug existed in all three copies at once)."""
    if ragged:
        return jnp.full((tokens.shape[0],), tokens.shape[1], jnp.int32)
    return jnp.zeros((), jnp.int32)


def pipeline_parallel_specs(model: GPTLM, axis_name: str = "stage"):
    """PartitionSpec layout for pipeline parallelism over the
    :meth:`GPTLM.pipeline_stage_blocks` layout: every staged block leaf
    sharded on its leading ``num_stages`` dim (one contiguous layer group
    per device of ``axis_name``); embed/pos/lnf replicated — exactly the
    placement :func:`make_lm_pp_train_step` trains under."""
    from jax.sharding import PartitionSpec as P

    if model.moe_experts is not None:
        raise NotImplementedError(
            "pipeline parallelism is not defined for MoE blocks; use "
            "expert parallelism (make_lm_ep_train_step)"
        )
    params_shape = jax.eval_shape(model.init, 1)
    return GPTLMParams(
        embed=P(),
        pos=P(),
        blocks=jax.tree.map(lambda _: P(axis_name), params_shape.blocks),
        lnf_scale=P(),
        lnf_bias=P(),
    )


def pipeline_stage_params(
    model: GPTLM, params: GPTLMParams, num_stages: int
) -> GPTLMParams:
    """Full params → pipeline layout: blocks reshaped to
    [num_stages, layers_per_stage, ...] (:meth:`GPTLM.pipeline_stage_blocks`),
    everything else untouched. Inverse: merge the two leading block dims."""
    return params._replace(
        blocks=model.pipeline_stage_blocks(params.blocks, num_stages)
    )


def make_lm_pp_train_step(
    model: GPTLM,
    optimizer,
    mesh,
    *,
    axis: str = "stage",
    num_microbatches: int = 4,
    data_axis: str | None = None,
):
    """Pipeline-parallel TRAINING step: the GPipe backward as the scan
    transpose. The reference has no pipeline stages at all (SURVEY.md §2b
    — one tiny MLP per worker); this completes the parallelism matrix on
    the *training* side, the reason GPipe exists.

    Layout: params in :func:`pipeline_stage_params` form — each device of
    ``axis`` owns one contiguous layer group [1, n/S, ...] AND that group's
    optimizer slots (:func:`pipeline_parallel_specs` + slot matching);
    embed/pos/lnf and tokens replicated. The forward is the GPipe
    microbatched pipeline (``parallel/pipeline.py``): M microbatches flow
    stage-to-stage over ``ppermute`` hops, M + S − 1 ticks. The backward is
    **not hand-scheduled**: reverse-mode AD through the tick scan replays
    the ticks in reverse with the transposed hops (``ppermute`` with the
    inverse permutation) — exactly the GPipe backward schedule, derived by
    the compiler rather than written out. Each stage's parameter gradient
    accumulates across its microbatch ticks inside the scan transpose; the
    embedding/head gradients flow once (embed + LM head run under GSPMD
    outside the stage loop, so nothing is double-counted across stages).

    ``model.remat=True`` composes: each stage's layer-group forward is
    ``jax.checkpoint``-ed, so the backward recomputes one stage group per
    tick instead of stashing all M·(M+S−1) tick activations.

    ``data_axis`` composes data parallelism on top — dp×pp on a 2-D
    ``(data, stage)`` mesh: each microbatch's rows are sharded over
    ``data_axis`` (every data row runs the same GPipe schedule on its
    shard of every microbatch), embed/head/CE run under GSPMD on the
    data-sharded batch, and the stage-owned layer groups (replicated
    across ``data``) receive their data-summed gradients through
    shard_map's auto-psum — the same composition form as dp×ep.

    Returns a jitted ``step(params, opt_state, tokens) -> (params,
    opt_state, loss)``; place params/slots with ``jax.device_put`` under
    the :func:`pipeline_parallel_specs` layout first (or let GSPMD
    reshard on the first call). Proven grad-identical to the sequential
    single-device step in tests/test_gpt.py on 4- and 8-stage meshes
    (and 2×4 dp×pp)."""
    specs, opt_specs, pp_loss = make_lm_pp_parts(
        model,
        optimizer,
        mesh,
        axis=axis,
        num_microbatches=num_microbatches,
        data_axis=data_axis,
    )
    shardings = _as_shardings(mesh, specs)
    opt_shardings = _as_shardings(mesh, opt_specs)

    @jax.jit
    def step(params, opt_state, tokens):
        loss, grads = jax.value_and_grad(pp_loss)(params, tokens)
        # Pin to the stage-owner layout: the update stays local to each
        # device's layer group.
        params, opt_state = _pinned_update(
            optimizer, params, opt_state, grads, shardings, opt_shardings
        )
        return params, opt_state, loss

    return step


def make_lm_pp_parts(
    model: GPTLM,
    optimizer,
    mesh,
    *,
    axis: str = "stage",
    num_microbatches: int = 4,
    data_axis: str | None = None,
):
    """Building blocks behind :func:`make_lm_pp_train_step`, exposed (like
    :func:`make_lm_ep_parts`) so the LM trainer can embed the pipeline
    step inside its scanned-epoch / whole-run-compiled bodies. Returns
    ``(specs, opt_specs, pp_loss)``:

    - ``specs`` / ``opt_specs`` — PartitionSpec pytrees for params in
      :func:`pipeline_stage_params` layout and their optimizer slots;
    - ``pp_loss(params, tokens, lengths=None) -> loss`` — differentiable
      GPipe forward + next-token CE (masked when ``lengths`` [B] is given:
      ragged right-padded batches train exactly as in :meth:`GPTLM.loss` —
      causal attention already isolates pads, only the CE needs masking
      for dense blocks). Call inside jit; differentiate for the GPipe
      backward (the tick-scan transpose)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from distributed_tensorflow_tpu.parallel.pipeline import (
        microbatch,
        pipeline_apply,
    )

    s = mesh.shape[axis]
    if model.num_layers % s:
        raise ValueError(
            f"num_layers {model.num_layers} not divisible by {axis!r} axis "
            f"size {s}"
        )
    if data_axis is not None and data_axis not in mesh.shape:
        raise ValueError(f"mesh has no {data_axis!r} axis: {dict(mesh.shape)}")
    if data_axis == axis:
        raise ValueError(
            f"data_axis must differ from the stage axis {axis!r}"
        )
    specs = pipeline_parallel_specs(model, axis)  # raises for MoE blocks
    staged_shape = jax.eval_shape(
        lambda: pipeline_stage_params(model, model.init(1), s)
    )
    opt_specs = _slot_specs(optimizer, staged_shape, specs)
    mb_spec = P() if data_axis is None else P(None, data_axis)

    stage_fn = model._pp_stage_fn()
    pp_body = jax.shard_map(
        lambda blocks, hm: pipeline_apply(stage_fn, blocks, hm, axis),
        mesh=mesh,
        in_specs=(specs.blocks, mb_spec),
        out_specs=mb_spec,
    )

    def pp_loss(params, tokens, lengths=None):
        b, l = tokens.shape
        if data_axis is not None:
            tokens = lax.with_sharding_constraint(
                tokens, NamedSharding(mesh, P(data_axis))
            )
        positions = jnp.arange(l)
        h = model._embed_tokens(params, tokens, positions)
        hm = microbatch(h, num_microbatches)  # [M, B/M, L, d]
        out = pp_body(params.blocks, hm)
        logits = model._logits(params, out.reshape(b, l, -1))
        return _ce_from_logits(logits, tokens, lengths)

    return specs, opt_specs, pp_loss


def make_lm_sp_train_step(
    model: GPTLM,
    optimizer,
    mesh,
    *,
    axis: str = "seq",
    data_axis: str | None = None,
    attention: str | None = None,
):
    """Sequence-parallel TRAINING step: the LM trains past one device's
    activation memory — L/n tokens of activations per device, KV riding
    the causal ring (or the Ulysses all-to-all) exactly as in
    :meth:`GPTLM.apply_sequence_parallel`, gradients back through the
    collectives. ``step(params, opt_state, tokens) -> (params, opt_state,
    loss)``, jitted; tokens [B, L] with L divisible by the ``axis`` size,
    params replicated (no layout to place). ``data_axis`` composes data
    parallelism → dp×sp on a ``('data','seq')`` mesh. Proven equal to the
    single-device step in tests/test_gpt.py."""
    mapped = make_lm_sp_parts(
        model, optimizer, mesh, axis,
        data_axis=data_axis, attention=attention,
    )

    @jax.jit
    def step(params, opt_state, tokens):
        return mapped(params, opt_state, tokens, None)

    return step


def make_lm_sp_parts(
    model: GPTLM,
    optimizer,
    mesh,
    axis: str = "seq",
    *,
    data_axis: str | None = None,
    attention: str | None = None,
    ragged: bool = False,
):
    """Building blocks behind :func:`make_lm_sp_train_step`, exposed (like
    the ep/pp parts) so the LM trainer can embed the sequence-parallel
    update inside its scanned-epoch / whole-run-compiled bodies. Returns
    ``mapped(params, opt_state, tokens, lengths) -> (params, opt_state,
    loss)`` — NOT jitted; tokens [B, L] sharded on the SEQUENCE dim over
    ``axis`` (and the batch dim over ``data_axis`` when given), params
    and optimizer slots replicated.

    The loss is the EXACT global (masked) next-token CE — not a per-shard
    mean: each device scores its l_loc positions, the shard-boundary
    target (position s+l_loc−1 predicts the NEXT shard's first token)
    arrives over one ``ppermute`` hop, and CE·count sums are
    ``psum``-aggregated over all axes before the division. Equal to
    :func:`_ce_from_logits` on the gathered sequence by construction,
    ragged or not — so sp training is bitwise-tolerant equal to the
    single-device step (grads of the replicated params arrive through
    shard_map's auto-psum, already globally summed; no rescaling).

    ``attention`` follows :meth:`GPTLM.apply_sequence_parallel` (ring /
    ring_flash / ulysses; ring_flash needs a TPU or check_vma=False)."""
    import optax
    from jax.sharding import PartitionSpec as P

    if model.moe_experts is not None:
        raise NotImplementedError(
            "MoE blocks are not supported on the sequence-parallel path; "
            "use expert parallelism (make_lm_ep_parts)"
        )
    n = mesh.shape[axis]
    if data_axis is not None and data_axis not in mesh.shape:
        raise ValueError(f"mesh has no {data_axis!r} axis: {dict(mesh.shape)}")
    if data_axis == axis:
        raise ValueError(f"data_axis must differ from the seq axis {axis!r}")
    axes = (axis,) if data_axis is None else (data_axis, axis)
    batch_spec = P(data_axis, axis)  # data_axis=None → replicated batch dim
    lens_spec = P(data_axis)
    # Shard i receives shard (i+1)'s first token — the boundary target.
    perm = [(j, (j - 1) % n) for j in range(n)]

    def sp_loss(params, toks, lens):
        l_loc = toks.shape[1]
        my = lax.axis_index(axis)
        logits = model.apply_sequence_parallel(
            params, toks, axis, attention=attention
        )
        nxt = lax.ppermute(toks[:, 0], axis, perm)
        targets = jnp.concatenate([toks[:, 1:], nxt[:, None]], axis=1)
        nll = _picked_nll(logits.astype(jnp.float32), targets)
        # Absolute index of each local position's target token.
        tpos = my * l_loc + jnp.arange(l_loc) + 1
        valid = tpos[None, :] < n * l_loc  # the last global position has
        if lens is not None:  # no target (wrapped garbage masked here)
            valid = valid & (tpos[None, :] < lens[:, None])
        # Broadcast to [B, l_loc] BEFORE counting: the non-ragged mask is
        # per-position only and the count must include the batch factor.
        w = jnp.broadcast_to(valid, nll.shape).astype(jnp.float32)
        # pvary to the full psum axes first: non-ragged w only varies over
        # the seq axis, and psum rejects axes the operand is invariant of.
        ce = lax.psum(to_varying(jnp.sum(nll * w), axes), axes)
        cnt = lax.psum(to_varying(jnp.sum(w), axes), axes)
        return ce / jnp.maximum(cnt, 1.0)

    def local(params, opt_state, toks, lens):
        loss, grads = jax.value_and_grad(sp_loss)(
            params, toks, lens if ragged else None
        )
        updates, opt_state = optimizer.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        return params, opt_state, loss

    inner = jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(P(), P(), batch_spec, lens_spec if ragged else P()),
        out_specs=(P(), P(), P()),
    )

    def mapped(params, opt_state, tokens, lens):
        if lens is None:
            lens = _default_lens(tokens, ragged)
        return inner(params, opt_state, tokens, lens)

    return mapped


def make_lm_async_train_step(
    model: GPTLM,
    optimizer,
    mesh,
    *,
    axis: str = "data",
    avg_every: int = 1,
    update_scale: float | None = None,
):
    """Async local-SGD for the LM — the reference's signature training mode
    (HOGWILD applies to PS variables, reference tfdist_between.py:64-66),
    emulated the way ``AsyncDataParallel`` does for the classifiers: each
    device owns a private (params, opt_state) copy advancing on its own
    token stream, and every ``avg_every`` steps all copies jump to the
    cross-device parameter mean (one all-reduce; zero traffic between
    exchanges).

    Returns ``(init_state, step)``:

    - ``init_state(params, opt_state) -> state`` stacks per-device copies
      ([n, ...] leaves, sharded over ``axis``) plus a step counter;
    - ``step(state, tokens) -> (state, loss)`` with tokens [n·B, L] sharded
      on the batch dim; loss is the cross-device mean of the local losses.

    ``update_scale`` defaults to **N (the replica count)** — the ONE
    convention both async APIs share (``AsyncDataParallel``,
    strategy.py): the reference PS applied all N workers' updates
    sequentially, so reproducing its async-table behavior needs N× the
    per-exchange step; parameter averaging alone gives sync-like
    dynamics (tools/parity_converged.py). Pass ``update_scale=1.0``
    explicitly for pure local-SGD averaging — with plain SGD and
    ``avg_every=1`` that is *exactly* the sync data-parallel step (mean of
    independent SGD updates from a common point = update by the mean
    gradient — SGD is linear in the gradient), which the tests assert
    bitwise-tolerant; with momentum/adam or ``avg_every>1`` it is
    genuinely async (copies diverge between exchanges, the modeled
    race)."""
    init_state, mapped = make_lm_async_parts(
        model,
        optimizer,
        mesh,
        axis=axis,
        avg_every=avg_every,
        update_scale=update_scale,
    )

    @partial(jax.jit, donate_argnums=0)
    def step(state, tokens):
        params, opt_state, count = state
        params, opt_state, loss = mapped(
            params, opt_state, tokens, None, count
        )
        return (params, opt_state, count + 1), loss

    return init_state, step


def make_lm_async_parts(
    model: GPTLM,
    optimizer,
    mesh,
    *,
    axis: str = "data",
    avg_every: int = 1,
    update_scale: float | None = None,
    ragged: bool = False,
):
    """Building blocks behind :func:`make_lm_async_train_step`, exposed so
    the :class:`~train.lm_trainer.LMTrainer` can embed the async local-SGD
    update inside its scanned-epoch / whole-run-compiled bodies (one
    ``lax.scan`` over many async steps) instead of paying a dispatch per
    step. Returns ``(init_state, mapped)``:

    - ``init_state(params, opt_state) -> (stacked_params, stacked_opt,
      count)`` — per-device copies ([n, ...] leaves sharded over ``axis``)
      plus the step counter the ``avg_every`` exchange keys on;
    - ``mapped(stacked_params, stacked_opt, tokens, lengths, count) ->
      (stacked_params, stacked_opt, loss)`` — NOT jitted (call it inside
      your own jit/scan); tokens [n·B, L] sharded on the batch dim,
      ``lengths`` [n·B] for ragged corpora (masked CE per copy) or None
      (``ragged`` is a factory-time choice — it shapes the shard_map
      signature); loss is the cross-device mean of the local losses.
    """
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    if avg_every < 1:
        raise ValueError(f"avg_every must be >= 1, got {avg_every}")
    n = mesh.shape[axis]
    if update_scale is None:
        update_scale = float(n)

    def init_state(params, opt_state):
        stacked = jax.tree.map(
            lambda x: jnp.broadcast_to(x[None], (n,) + x.shape),
            (params, opt_state),
        )
        stacked = jax.device_put(
            stacked, NamedSharding(mesh, P(axis))
        )
        return (*stacked, jnp.zeros((), jnp.int32))

    def local(params, opt_state, tokens, lens, count):
        p = jax.tree.map(lambda x: x[0], params)
        o = jax.tree.map(lambda x: x[0], opt_state)
        loss_fn = (
            (lambda q: model.loss(q, tokens, lens))
            if ragged
            else (lambda q: model.loss(q, tokens))
        )
        loss, grads = jax.value_and_grad(loss_fn)(p)
        updates, o = optimizer.update(grads, o, p)
        if update_scale != 1.0:
            updates = jax.tree.map(lambda u: u * update_scale, updates)
        p = optax.apply_updates(p, updates)
        # lax.cond, not jnp.where: where evaluates both branches, so the
        # all-reduce would fire on EVERY step and void avg_every's traffic
        # bound. The predicate derives from the replicated count, so all
        # devices agree and the collective is uniform.
        # pmean outputs are typed invariant; cast back to varying so both
        # cond branches agree under check_vma (same pattern as the ring's
        # skip branch, ops/collectives.to_varying).
        pvary = partial(to_varying, axis_name=(axis,))
        p = lax.cond(
            (count + 1) % avg_every == 0,
            lambda p: jax.tree.map(lambda x: pvary(lax.pmean(x, axis)), p),
            lambda p: p,
            p,
        )
        return (
            jax.tree.map(lambda x: x[None], p),
            jax.tree.map(lambda x: x[None], o),
            lax.pmean(loss, axis),
        )

    lens_spec = (P(axis),) if ragged else (P(),)
    inner = jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(P(axis), P(axis), P(axis)) + lens_spec + (P(),),
        out_specs=(P(axis), P(axis), P()),
    )

    def mapped(params, opt_state, tokens, lens, count):
        if lens is None:
            lens = _default_lens(tokens, ragged)
        return inner(params, opt_state, tokens, lens, count)

    return init_state, mapped


def make_lm_train_step(
    model: GPTLM,
    optimizer,
    mesh=None,
    axis: str = "data",
    *,
    tp_axis: str | None = None,
    seq_axis: str | None = None,
):
    """``step(params, opt_state, tokens) -> (params, opt_state, loss)``,
    jitted, for any optax ``GradientTransformation`` (ops/optim.make).

    With ``mesh`` the step runs data-parallel over its ``axis``: tokens
    sharded on the batch dim, params/opt-state replicated, gradients
    all-reduced — the LM analog of ``SyncDataParallel``'s compiled
    collective (the reference's sync mode, tfdist_between_sync.py:66-68,
    minus the parameter server). Identical math to the single-device step on
    the same global batch for dense models; MoE models compute switch
    capacity from the LOCAL batch shard (standard practice), so dp equals
    single-device exactly only in the no-drop regime. Under ``shard_map`` AD auto-inserts a psum for
    grads of the replicated params, so the local grads are *summed* — the
    code divides by the axis size rather than pmean-ing (CLAUDE.md).

    ``tp_axis`` switches to the 2-D dp×tp form: params (and optimizer
    slots) laid out per :meth:`GPTLM.partition_specs` over ``tp_axis``,
    batch sharded over ``axis``, and the whole step expressed as ONE
    GSPMD program — XLA inserts the Megatron collectives (all-reduce
    after attention-out/MLP-down) and the gradient all-reduce over
    ``axis``. The math is the single-device step verbatim (GSPMD
    partitioning preserves semantics), proven in tests/test_gpt.py.
    Place params with ``jax.device_put`` under the returned layout or let
    GSPMD reshard on first call; dense models only (MoE → EP).

    ``seq_axis`` (round 9) composes GSPMD sequence sharding on top of the
    tp form — the 3-D **dp×tp×sp** mesh real pods run: tokens constrained
    ``P(axis, seq_axis)`` (batch over ``axis``, the SEQUENCE dim over
    ``seq_axis``), params still per :meth:`partition_specs`, one GSPMD
    program for the whole 3-D composition — XLA inserts the sequence
    gathers the causal attention needs next to the Megatron collectives.
    Still the single-device math verbatim; equality on the 2x2x2 mesh is
    pinned in tests/test_gpt.py. GSPMD triples compose freely this way
    because every axis is a layout annotation on one program; the
    shard_map modes (explicit sp/ep/pp) instead compose with exactly one
    data axis — docs/parallelism.md has the triple-composition menu."""
    import optax

    if seq_axis is not None and tp_axis is None:
        raise ValueError(
            "seq_axis composes on the GSPMD tp path; pass tp_axis too "
            "(for shard_map sequence parallelism use make_lm_sp_parts)"
        )
    if tp_axis is not None:
        from jax.sharding import NamedSharding, PartitionSpec as P

        if mesh is None:
            raise ValueError("tp_axis requires a mesh")
        if seq_axis is not None and seq_axis not in mesh.shape:
            raise ValueError(
                f"mesh has no {seq_axis!r} axis: {dict(mesh.shape)}"
            )
        specs = model.partition_specs(tp_axis)  # raises for MoE blocks
        opt_specs = _slot_specs(
            optimizer, jax.eval_shape(model.init, 1), specs
        )
        shardings = _as_shardings(mesh, specs)
        opt_shardings = _as_shardings(mesh, opt_specs)
        batch_sharding = NamedSharding(mesh, P(axis, seq_axis))

        @jax.jit
        def step(params, opt_state, tokens):
            tokens = lax.with_sharding_constraint(tokens, batch_sharding)
            loss, grads = jax.value_and_grad(model.loss)(params, tokens)
            # Pin to the TP layout: the update stays local to each
            # device's weight shard.
            with jax.named_scope(names.OPTIMIZER):
                params, opt_state = _pinned_update(
                    optimizer, params, opt_state, grads, shardings,
                    opt_shardings,
                )
            return params, opt_state, loss

        return step

    if mesh is None:

        @jax.jit
        def step(params, opt_state, tokens):
            loss, grads = jax.value_and_grad(model.loss)(params, tokens)
            with jax.named_scope(names.OPTIMIZER):
                updates, opt_state = optimizer.update(grads, opt_state, params)
                params = optax.apply_updates(params, updates)
            return params, opt_state, loss

        return step

    from jax.sharding import PartitionSpec as P

    n = mesh.shape[axis]

    def local(params, opt_state, tokens):
        loss, grads = jax.value_and_grad(model.loss)(params, tokens)
        # AD's auto-psum summed the per-device grads of the replicated
        # params; the global-mean loss needs their mean.
        grads = jax.tree.map(lambda g: g / n, grads)
        with jax.named_scope(names.OPTIMIZER):
            updates, opt_state = optimizer.update(grads, opt_state, params)
            params = optax.apply_updates(params, updates)
        return params, opt_state, lax.pmean(loss, axis)

    mapped = jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(P(), P(), P(axis)),
        out_specs=(P(), P(), P()),
    )
    return jax.jit(mapped)
