"""A language model whose layer pattern is data: state-space, sparse-expert
and attention layers in one stack (the ``nemotron_h`` block design).

The residual stream ``h`` runs through ``len(pattern)`` layers, each ONE
mixer behind one pre-norm — ``h <- h + mixer(RMSNorm(h))``; there is no
attention + feed-forward pair — then a final RMSNorm and an untied head.
The pattern is a string over three letters:

- ``M`` — a Mamba-2 mixer: ``[z | xBC | dt] = in_proj(u)``; ``xBC`` through
  a causal depthwise convolution (with bias) and silu; split into ``x``
  (heads x head size), ``B`` and ``C`` (groups x state; a head reads the
  group ``head // (heads / groups)``); ``dt = softplus(dt + dt_bias)``,
  ``A = -exp(A_log)`` per head; the float32 recurrence of
  :mod:`~distributed_tensorflow_tpu.ops.ssd` plus the skip ``D * x``; an
  RMSNorm over each group of ``y * silu(z)``; ``out_proj``.
- ``E`` — sparse experts (:func:`~distributed_tensorflow_tpu.ops.moe.moe_ffn_held`):
  float32 sigmoid scores over all ``num_experts``, the ``experts_per_token``
  largest ``score + bias`` chosen, weighed by their normalised scores times
  ``routed_scale``; experts are ``w_down relu(w_up u)^2`` (not gated); one
  shared expert of the same form is added for every token. The layer HOLDS
  ``experts_held = (first, count)`` of the routed experts (all of them by
  default): it routes over all, computes the choices that landed on the
  held ones and adds nothing for the rest — one chip's share of an
  expert-parallel layer; the shared expert is computed whole.
- ``*`` — causal softmax attention, grouped-query, ``head_dim`` free of
  ``model_dim / num_heads``, no bias, no position embedding of any kind
  (the state-space layers carry the order).

Parameters are stacked per kind (``mamba[n_M]``, ``moe[n_E]``,
``attn[n_*]``) and the layers run unrolled in pattern order, each taking
its kind's next slice. Matmul operands are ``compute_dtype`` with float32
accumulation; norms, the router, the convolution, the recurrence and the
loss are float32. ``remat`` checkpoints each layer through the same policy
surface as :class:`~distributed_tensorflow_tpu.models.gpt.GPTLM`.

The duck type :class:`~distributed_tensorflow_tpu.train.LMTrainer` trains:
``init``, ``loss``, ``remat``, ``matmul_dtype``, ``attention_impl``,
``moe_experts`` — plus :meth:`loss_and_counters`, whose counters (the
pairs that chose each expert, per ``E`` layer) the trainer fetches with the
step costs. The routers' correction bias is a buffer: no gradient reaches
it. With ``balance_rounds`` (0 or more) the choice does not read the
buffer: every sequence's tokens are dealt evenly over the experts by a
bias found anew from the sequence's own logits, its experts' marks and
that many rounds of an auction
(:func:`~distributed_tensorflow_tpu.ops.moe.balancing_bias`): the balancing
a training recipe holds the load even with. Without it (None, the
default) the buffer is used as it stands.
"""

from __future__ import annotations

import math
from typing import NamedTuple

import jax
import jax.numpy as jnp

from distributed_tensorflow_tpu.models.gpt import GPTLM, _ce_from_logits
from distributed_tensorflow_tpu.observability import names
from distributed_tensorflow_tpu.ops.moe import moe_ffn_held
from distributed_tensorflow_tpu.ops.ring_attention import dense_attention
from distributed_tensorflow_tpu.ops.ssd import ssd_chunked

KINDS = ("M", "E", "*")


class MambaParams(NamedTuple):
    norm: jax.Array  # [n, d]
    in_proj: jax.Array  # [n, d, 2*inner + 2*groups*state + heads]
    conv_w: jax.Array  # [n, kernel, inner + 2*groups*state]; last row = now
    conv_b: jax.Array  # [n, inner + 2*groups*state]
    dt_bias: jax.Array  # [n, heads]
    a_log: jax.Array  # [n, heads]
    d_skip: jax.Array  # [n, heads]
    gate_norm: jax.Array  # [n, inner]
    out_proj: jax.Array  # [n, inner, d]


class ExpertParams(NamedTuple):
    norm: jax.Array  # [n, d]
    router: jax.Array  # [n, d, num_experts] over ALL experts
    router_bias: jax.Array  # [n, num_experts] the choice's correction: a buffer
    w_up: jax.Array  # [n, held, d, expert_dim]
    w_down: jax.Array  # [n, held, expert_dim, d]
    shared_up: jax.Array  # [n, d, shared_dim]
    shared_down: jax.Array  # [n, shared_dim, d]


class AttnParams(NamedTuple):
    norm: jax.Array  # [n, d]
    wq: jax.Array  # [n, d, heads*head_dim]
    wk: jax.Array  # [n, d, kv_heads*head_dim]
    wv: jax.Array  # [n, d, kv_heads*head_dim]
    wo: jax.Array  # [n, heads*head_dim, d]


class HybridLMParams(NamedTuple):
    embed: jax.Array  # [vocab, d]
    mamba: MambaParams
    moe: ExpertParams
    attn: AttnParams
    norm_f: jax.Array  # [d]
    head: jax.Array  # [d, vocab]


def rmsnorm(x, weight, eps):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * weight


@jax.custom_vjp
def _unstack(x):
    """A stacked leaf as its per-layer slices; the transpose is ONE stack
    (the sum of zero-padded slices that plain indexing transposes to
    would hold a full-size copy per layer)."""
    return tuple(x[i] for i in range(x.shape[0]))


_unstack.defvjp(lambda x: (_unstack(x), None),
                lambda _, g: (jnp.stack(g),))


def _layers_of(stack):
    """A per-kind stack as a list of per-layer parameter tuples."""
    n = stack[0].shape[0]
    if not n:
        return []
    leaves = [_unstack(leaf) for leaf in stack]
    return [type(stack)(*(leaf[i] for leaf in leaves)) for i in range(n)]


class HybridLM:
    def __init__(
        self,
        vocab_size: int,
        model_dim: int,
        pattern: str,
        *,
        ssm_heads: int = 0,
        ssm_head_dim: int = 0,
        ssm_state: int = 0,
        ssm_groups: int = 1,
        conv_kernel: int = 4,
        chunk_size: int = 128,
        num_experts: int = 0,
        experts_per_token: int = 0,
        expert_dim: int = 0,
        shared_dim: int = 0,
        routed_scale: float = 1.0,
        experts_held: tuple[int, int] | None = None,
        balance_rounds: int | None = None,
        num_heads: int = 0,
        num_kv_heads: int | None = None,
        head_dim: int = 0,
        norm_eps: float = 1e-5,
        init_std: float = 0.02,
        depth_for_init: int | None = None,
        dt_init: tuple[float, float, float] = (1e-3, 1e-1, 1e-4),
        compute_dtype: jnp.dtype = jnp.bfloat16,
        attention_impl: str = "xla",
        flash_min_len: int | None = None,
        remat: bool | str = False,
        matmul_dtype: str | None = None,
    ):
        if not pattern or set(pattern) - set(KINDS):
            raise ValueError(
                f"pattern {pattern!r} must be a non-empty string over {KINDS}")
        if attention_impl not in ("xla", "flash"):
            raise ValueError(
                f"unknown attention_impl {attention_impl!r}; xla|flash")
        self.vocab_size, self.model_dim, self.pattern = (
            vocab_size, model_dim, pattern)
        self.counts = {kind: pattern.count(kind) for kind in KINDS}
        if self.counts["M"]:
            if min(ssm_heads, ssm_head_dim, ssm_state, ssm_groups) < 1 or (
                    ssm_heads % ssm_groups):
                raise ValueError(
                    "an M layer needs ssm_heads (a multiple of ssm_groups), "
                    "ssm_head_dim and ssm_state")
        if self.counts["E"]:
            if min(num_experts, expert_dim, shared_dim) < 1 or not (
                    1 <= experts_per_token <= num_experts):
                raise ValueError(
                    "an E layer needs num_experts, expert_dim, shared_dim and "
                    "1 <= experts_per_token <= num_experts")
        if self.counts["*"]:
            if min(num_heads, head_dim) < 1 or num_heads % (
                    num_kv_heads or num_heads):
                raise ValueError(
                    "a * layer needs num_heads (a multiple of num_kv_heads) "
                    "and head_dim")
        self.ssm_heads, self.ssm_head_dim = ssm_heads, ssm_head_dim
        self.ssm_state, self.ssm_groups = ssm_state, ssm_groups
        self.conv_kernel, self.chunk_size = conv_kernel, chunk_size
        self.num_experts, self.experts_per_token = num_experts, experts_per_token
        self.expert_dim, self.shared_dim = expert_dim, shared_dim
        self.routed_scale = float(routed_scale)
        first, count = experts_held or (0, num_experts)
        if self.counts["E"] and not (
                0 <= first and count >= 1 and first + count <= num_experts):
            raise ValueError(
                f"experts_held {(first, count)} is not a range of the "
                f"{num_experts} experts")
        self.experts_held = (first, count)
        self.balance_rounds = balance_rounds
        self.num_heads = num_heads
        self.num_kv_heads = num_kv_heads or num_heads
        self.head_dim = head_dim
        self.norm_eps = norm_eps
        self.init_std = init_std
        # rescale_prenorm_residual: the projections that write to the
        # residual stream start 1/sqrt(depth) smaller. A share of a deeper
        # model keeps the deeper model's depth.
        self.depth_for_init = depth_for_init or len(pattern)
        self.dt_init = dt_init
        self.compute_dtype = compute_dtype
        self.attention_impl = attention_impl
        self.flash_min_len = flash_min_len
        self.remat = remat
        self.matmul_dtype = matmul_dtype
        # What LMTrainer's mode checks read: tp and sp refuse a model with
        # experts, as they refuse GPTLM's.
        self.moe_experts = num_experts if self.counts["E"] else None

    # -- sizes ---------------------------------------------------------------

    @property
    def ssm_inner(self) -> int:
        return self.ssm_heads * self.ssm_head_dim

    @property
    def conv_dim(self) -> int:
        return self.ssm_inner + 2 * self.ssm_groups * self.ssm_state

    # -- init ----------------------------------------------------------------

    def init(self, seed: int = 1) -> HybridLMParams:
        d, std = self.model_dim, self.init_std
        resid = std / math.sqrt(self.depth_for_init)
        keys = iter(jax.random.split(jax.random.key(seed), 16))
        f32 = jnp.float32

        def normal(shape, s=std):
            return s * jax.random.normal(next(keys), shape, f32)

        nm, ne, na = (self.counts[kind] for kind in KINDS)
        h, inner, cdim = self.ssm_heads, self.ssm_inner, self.conv_dim
        lo, hi, floor = self.dt_init
        dt = jnp.exp(jax.random.uniform(next(keys), (nm, h), f32)
                     * (math.log(hi) - math.log(lo)) + math.log(lo))
        dt = jnp.maximum(dt, floor)
        mamba = MambaParams(
            norm=jnp.ones((nm, d), f32),
            in_proj=normal((nm, d, inner + cdim + h)),
            # a depthwise convolution's usual start: U(+-1/sqrt(kernel))
            conv_w=jax.random.uniform(
                next(keys), (nm, self.conv_kernel, cdim), f32, -1.0, 1.0
            ) / math.sqrt(self.conv_kernel),
            conv_b=jnp.zeros((nm, cdim), f32),
            dt_bias=dt + jnp.log(-jnp.expm1(-dt)),  # softplus's inverse
            a_log=jnp.log(jax.random.uniform(next(keys), (nm, h), f32, 1.0, 16.0)),
            d_skip=jnp.ones((nm, h), f32),
            gate_norm=jnp.ones((nm, inner), f32),
            out_proj=normal((nm, inner, d), resid),
        )
        held = self.experts_held[1]
        moe = ExpertParams(
            norm=jnp.ones((ne, d), f32),
            router=normal((ne, d, self.num_experts)),
            router_bias=jnp.zeros((ne, self.num_experts), f32),
            w_up=normal((ne, held, d, self.expert_dim)),
            w_down=normal((ne, held, self.expert_dim, d), resid),
            shared_up=normal((ne, d, self.shared_dim)),
            shared_down=normal((ne, self.shared_dim, d), resid),
        )
        hq, hkv = self.num_heads * self.head_dim, self.num_kv_heads * self.head_dim
        attn = AttnParams(
            norm=jnp.ones((na, d), f32),
            wq=normal((na, d, hq)), wk=normal((na, d, hkv)),
            wv=normal((na, d, hkv)), wo=normal((na, hq, d), resid),
        )
        return HybridLMParams(
            embed=normal((self.vocab_size, d)), mamba=mamba, moe=moe,
            attn=attn, norm_f=jnp.ones((d,), f32),
            head=normal((d, self.vocab_size)),
        )

    # -- pieces --------------------------------------------------------------

    def _dot(self, x, w):
        if self.matmul_dtype is not None:
            raise NotImplementedError(
                "HybridLM has no low-precision matmul path "
                f"(matmul_dtype={self.matmul_dtype!r})")
        cd = self.compute_dtype
        return jnp.dot(x.astype(cd), w.astype(cd),
                       preferred_element_type=jnp.float32)

    def _mamba(self, p: MambaParams, h):
        b, l, _ = h.shape
        nh, hd, g, n = (self.ssm_heads, self.ssm_head_dim, self.ssm_groups,
                        self.ssm_state)
        inner, cdim = self.ssm_inner, self.conv_dim
        with jax.named_scope(names.SSM_PROJ):
            proj = self._dot(rmsnorm(h, p.norm, self.norm_eps), p.in_proj)
            z, xbc, dt = jnp.split(proj, [inner, inner + cdim], axis=-1)
        with jax.named_scope(names.SSM_CONV):
            k = self.conv_kernel
            padded = jnp.pad(xbc, ((0, 0), (k - 1, 0), (0, 0)))
            xbc = p.conv_b + sum(
                padded[:, j:j + l] * p.conv_w[j] for j in range(k))
            xbc = jax.nn.silu(xbc)
        with jax.named_scope(names.SSM_SCAN):
            x, bm, cm = jnp.split(xbc, [inner, inner + g * n], axis=-1)
            x = x.reshape(b, l, nh, hd)
            # The recurrence is float32: on a TPU a float32 product at the
            # default precision rounds its operands to bfloat16.
            y = ssd_chunked(
                x, jax.nn.softplus(dt + p.dt_bias), -jnp.exp(p.a_log),
                bm.reshape(b, l, g, n), cm.reshape(b, l, g, n),
                chunk=self.chunk_size, precision=jax.lax.Precision.HIGHEST)
            y = y + p.d_skip[:, None] * x
        with jax.named_scope(names.SSM_PROJ):
            y = y.reshape(b, l, inner) * jax.nn.silu(z)
            y = rmsnorm(y.reshape(b, l, g, inner // g), 1.0, self.norm_eps)
            y = y.reshape(b, l, inner) * p.gate_norm
            return self._dot(y, p.out_proj)

    def _experts(self, p: ExpertParams, h):
        b, l, d = h.shape
        with jax.named_scope(names.MOE_ROUTE):
            u = rmsnorm(h, p.norm, self.norm_eps).reshape(b * l, d)
        routed, load = moe_ffn_held(
            u, p.router, p.router_bias, p.w_up, p.w_down,
            first=self.experts_held[0], k=self.experts_per_token,
            scale=self.routed_scale, compute_dtype=self.compute_dtype,
            balance=None if self.balance_rounds is None
            else (l, self.balance_rounds))
        with jax.named_scope(names.MOE_SHARED):
            act = jnp.square(jax.nn.relu(self._dot(u, p.shared_up)))
            shared = self._dot(act, p.shared_down)
        return (routed + shared).reshape(b, l, d), load

    def _attend(self, q, k, v):
        from distributed_tensorflow_tpu.models.base import resolve_flash_min_len

        if self.attention_impl == "flash" and q.shape[1] >= (
                resolve_flash_min_len(self.flash_min_len)):
            from distributed_tensorflow_tpu.ops.pallas_attention import (
                REMAT_SAVE_NAMES, flash_attention)

            return flash_attention(
                q, k, v, causal=True,
                save_names=REMAT_SAVE_NAMES if self._policy_remat else None)
        return dense_attention(q, k, v, causal=True)

    def _attention(self, p: AttnParams, h):
        b, l, _ = h.shape
        cd = self.compute_dtype
        with jax.named_scope(names.ATTN_QKV):
            u = rmsnorm(h, p.norm, self.norm_eps)
            q = self._dot(u, p.wq).reshape(b, l, self.num_heads, self.head_dim)
            kv = (b, l, self.num_kv_heads, self.head_dim)
            k = self._dot(u, p.wk).reshape(kv)
            v = self._dot(u, p.wv).reshape(kv)
        with jax.named_scope(names.ATTN_CORE):
            a = self._attend(q.astype(cd), k.astype(cd), v.astype(cd))
        with jax.named_scope(names.ATTN_OUT):
            return self._dot(a.reshape(b, l, -1), p.wo)

    # remat, applied per layer: GPTLM's values (False | True | "selective"
    # | a jax.checkpoint policy), and its code for all but True. Here True
    # keeps nothing: this stack's cell trains at 94.7% of its chip's
    # memory (PERF.md section 4), where one attention layer's kept output
    # buys under 1% of the step and may not load.
    @property
    def _policy_remat(self) -> bool:
        return bool(self.remat) and self.remat is not True

    def _remat_policy(self):
        return GPTLM._remat_policy(self) if self._policy_remat else None

    _remat_wrap = GPTLM._remat_wrap

    # -- forward -------------------------------------------------------------

    def apply_with_counters(self, params: HybridLMParams, tokens):
        """tokens [B, L] int32 -> (logits [B, L, vocab] float32, counters:
        ``moe_expert_load`` int32 [E layers, experts] — the (token, choice)
        pairs that chose each expert — and its held part
        ``moe_expert_rows`` [E layers, experts held])."""
        with jax.named_scope(names.EMBED):
            h = params.embed[tokens].astype(jnp.float32)
        layers = {"M": iter(_layers_of(params.mamba)),
                  "E": iter(_layers_of(params.moe)),
                  "*": iter(_layers_of(params.attn))}
        rows = []
        for kind in self.pattern:
            p = next(layers[kind])
            if kind == "M":
                h = h + self._remat_wrap(self._mamba)(p, h)
            elif kind == "*":
                h = h + self._remat_wrap(self._attention)(p, h)
            else:
                out, landed = self._remat_wrap(self._experts)(p, h)
                h = h + out
                rows.append(landed)
        with jax.named_scope(names.LM_HEAD):
            logits = self._dot(rmsnorm(h, params.norm_f, self.norm_eps),
                               params.head)
        first, held = self.experts_held
        load = (jnp.stack(rows) if rows
                else jnp.zeros((0, self.num_experts), jnp.int32))
        counters = {"moe_expert_load": load,
                    "moe_expert_rows": load[:, first:first + held]}
        return logits, counters

    def apply(self, params: HybridLMParams, tokens):
        return self.apply_with_counters(params, tokens)[0]

    def loss_and_counters(self, params, tokens, lengths=None):
        """(mean next-token cross-entropy, float32; what a step hands
        back with its cost: ``moe_expert_rows`` of the forward's counters).
        ``lengths`` [B] masks the loss of a right-padded batch; the mixers
        are causal, so pads reach no real position's logits, but they are
        routed like any token and the counters count them."""
        logits, counters = self.apply_with_counters(params, tokens)
        return _ce_from_logits(logits, tokens, lengths), {
            "moe_expert_rows": counters["moe_expert_rows"]}

    def loss(self, params, tokens, lengths=None):
        return self.loss_and_counters(params, tokens, lengths)[0]
