"""A language model whose layer pattern is data: state-space, delta-rule,
sparse-expert, feed-forward and attention layers in one stack (the
``nemotron_h`` block design; a ``kimi_linear`` layer is two letters).

The residual stream ``h`` runs through ``len(pattern)`` layers, each ONE
mixer behind one pre-norm — ``h <- h + mixer(RMSNorm(h))``; a block that
pairs a token mixer with a feed-forward is two letters, there is no pair
in the code — then a final RMSNorm and an untied head.
The pattern is a string over six letters:

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
  With ``expert_form="silu_gated"`` every expert, the shared one too, is
  ``w_down (silu(w_gate u) * w_up u)`` (a third matrix each).
- ``*`` — causal softmax attention, grouped-query, ``head_dim`` free of
  ``model_dim / num_heads``, no bias, no position embedding of any kind
  (the state-space layers carry the order).
- ``K`` — a gated delta-rule mixer with a per-channel decay ("KDA"):
  ``[q | k | v | f | z | b] = in_proj(u)``; ``q, k, v`` through a causal
  depthwise convolution (no bias) and silu; ``q`` and ``k`` L2-normalised
  per head, ``q`` times ``head_dim^-1/2``; the log-decay ``g = -exp(A_log)
  * softplus(decay_up(f) + dt_bias)`` per head and key channel (``f`` the
  low-rank gate's ``kda_gate_rank`` channels), ``beta = sigmoid(b)`` per
  head; the float32 recurrence of
  :mod:`~distributed_tensorflow_tpu.ops.kda`; an RMSNorm over each head
  with one learned weight, times ``sigmoid(gate_up(z))``; ``out_proj``.
- ``L`` — latent attention ("MLA") trained in its expanded form, no
  positions: ``q = wq(u)`` per head, ``qk_nope_dim + qk_shared_dim`` wide;
  ``[c | k_s] = w_dkv(u)``, ``c <- RMSNorm(c)`` (``kv_lora_rank`` wide),
  ``[k_n | v] = w_ukv(c)`` per head; ``k = [k_n | k_s]`` with the
  ``qk_shared_dim`` part shared by the heads and NOT rotated; causal
  softmax of ``q.k / sqrt(width of q)`` over values ``v_head_dim`` wide
  (the flash kernels take the two head sizes as they are); ``wo``.
- ``D`` — a dense gated feed-forward: ``w_down (silu(w_gate u) * w_up u)``,
  ``dense_dim`` wide.

Parameters are stacked per kind (``mamba[n_M]``, ``moe[n_E]``,
``attn[n_*]``, ``kda[n_K]``, ``mla[n_L]``, ``dense[n_D]``) and the layers
run unrolled in pattern order, each taking its kind's next slice. A
pattern over ``M``, ``E``, ``*`` alone keeps the tree it always had
(:class:`HybridLMParams`); one that uses ``K``, ``L`` or ``D`` has
:class:`StackLMParams`. Matmul operands are ``compute_dtype`` with float32
accumulation; norms, the router, the convolutions, both recurrences (their
decays, states and solves) and the loss are float32. ``remat`` checkpoints
each layer through the same policy surface as
:class:`~distributed_tensorflow_tpu.models.gpt.GPTLM`.

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
from distributed_tensorflow_tpu.ops.kda import kda_chunked
from distributed_tensorflow_tpu.ops.moe import moe_ffn_held
from distributed_tensorflow_tpu.ops.ring_attention import dense_attention
from distributed_tensorflow_tpu.ops.ssd import ssd_chunked

KINDS = ("M", "E", "*")
WIDE_KINDS = ("K", "L", "D")  # a pattern that uses one has StackLMParams
GROUP_OF = {"M": "mamba", "E": "moe", "*": "attn",
            "K": "kda", "L": "mla", "D": "dense"}
EXPERT_FORMS = ("relu2", "silu_gated")


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


class GatedExpertParams(NamedTuple):
    """``E`` with ``expert_form="silu_gated"``: a third matrix an expert."""

    norm: jax.Array  # [n, d]
    router: jax.Array  # [n, d, num_experts] over ALL experts
    router_bias: jax.Array  # [n, num_experts] the choice's correction: a buffer
    w_gate: jax.Array  # [n, held, d, expert_dim]
    w_up: jax.Array  # [n, held, d, expert_dim]
    w_down: jax.Array  # [n, held, expert_dim, d]
    shared_gate: jax.Array  # [n, d, shared_dim]
    shared_up: jax.Array  # [n, d, shared_dim]
    shared_down: jax.Array  # [n, shared_dim, d]


class KdaParams(NamedTuple):
    norm: jax.Array  # [n, d]
    in_proj: jax.Array  # [n, d, 3*inner + 2*rank + heads]: q, k, v, f, z, b
    conv_w: jax.Array  # [n, kernel, 3*inner]; last row = now; no bias
    decay_up: jax.Array  # [n, rank, inner]
    dt_bias: jax.Array  # [n, inner]: per head and key channel
    a_log: jax.Array  # [n, heads]
    gate_up: jax.Array  # [n, rank, inner]
    out_norm: jax.Array  # [n, head_dim]: one weight for every head
    out_proj: jax.Array  # [n, inner, d]


class MlaParams(NamedTuple):
    norm: jax.Array  # [n, d]
    wq: jax.Array  # [n, d, heads*(nope + shared)]
    w_dkv: jax.Array  # [n, d, kv_lora_rank + shared]
    kv_norm: jax.Array  # [n, kv_lora_rank]
    w_ukv: jax.Array  # [n, kv_lora_rank, heads*(nope + v_head_dim)]
    wo: jax.Array  # [n, heads*v_head_dim, d]


class DenseParams(NamedTuple):
    norm: jax.Array  # [n, d]
    w_gate: jax.Array  # [n, d, dense_dim]
    w_up: jax.Array  # [n, d, dense_dim]
    w_down: jax.Array  # [n, dense_dim, d]


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


class StackLMParams(NamedTuple):
    """The tree of a pattern that uses ``K``, ``L`` or ``D``: a stack for
    each of the six kinds (``moe`` in the form the model was built with),
    None for a kind the pattern lacks."""

    embed: jax.Array  # [vocab, d]
    mamba: MambaParams | None
    moe: ExpertParams | GatedExpertParams | None
    attn: AttnParams | None
    kda: KdaParams | None
    mla: MlaParams | None
    dense: DenseParams | None
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
    """A per-kind stack as a list of per-layer parameter tuples (none for
    None, or for ``HybridLMParams``'s stack of no layers)."""
    if stack is None or not stack[0].shape[0]:
        return []
    n = stack[0].shape[0]
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
        expert_form: str = "relu2",
        kda_heads: int = 0,
        kda_head_dim: int = 0,
        kda_gate_rank: int | None = None,
        kv_lora_rank: int = 0,
        qk_nope_dim: int = 0,
        qk_shared_dim: int = 0,
        v_head_dim: int = 0,
        dense_dim: int = 0,
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
        if not pattern or set(pattern) - set(GROUP_OF):
            raise ValueError(
                f"pattern {pattern!r} must be a non-empty string over "
                f"{tuple(GROUP_OF)}")
        if attention_impl not in ("xla", "flash"):
            raise ValueError(
                f"unknown attention_impl {attention_impl!r}; xla|flash")
        self.vocab_size, self.model_dim, self.pattern = (
            vocab_size, model_dim, pattern)
        self.counts = {kind: pattern.count(kind) for kind in GROUP_OF}
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
        if expert_form not in EXPERT_FORMS:
            raise ValueError(
                f"unknown expert_form {expert_form!r}; one of {EXPERT_FORMS}")
        if self.counts["K"] and min(kda_heads, kda_head_dim) < 1:
            raise ValueError("a K layer needs kda_heads and kda_head_dim")
        if self.counts["L"] and min(
                num_heads, kv_lora_rank, qk_nope_dim, v_head_dim) < 1:
            raise ValueError(
                "an L layer needs num_heads, kv_lora_rank, qk_nope_dim and "
                "v_head_dim")
        if self.counts["D"] and dense_dim < 1:
            raise ValueError("a D layer needs dense_dim")
        self.expert_form = expert_form
        self.kda_heads, self.kda_head_dim = kda_heads, kda_head_dim
        self.kda_gate_rank = kda_gate_rank or kda_head_dim
        self.kv_lora_rank, self.v_head_dim = kv_lora_rank, v_head_dim
        self.qk_nope_dim, self.qk_shared_dim = qk_nope_dim, qk_shared_dim
        self.dense_dim = dense_dim
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

    @property
    def kda_inner(self) -> int:
        return self.kda_heads * self.kda_head_dim

    # -- init ----------------------------------------------------------------

    def init(self, seed: int = 1) -> HybridLMParams | StackLMParams:
        d, std = self.model_dim, self.init_std
        resid = std / math.sqrt(self.depth_for_init)
        keys = iter(jax.random.split(jax.random.key(seed), 16))
        f32 = jnp.float32

        def normal(shape, s=std):
            return s * jax.random.normal(next(keys), shape, f32)

        def uniform(shape, lo, hi):
            return jax.random.uniform(next(keys), shape, f32, lo, hi)

        def dt_bias(shape):
            """softplus's inverse of a log-uniform draw in ``dt_init``."""
            lo, hi, floor = self.dt_init
            dt = jnp.exp(jax.random.uniform(next(keys), shape, f32)
                         * (math.log(hi) - math.log(lo)) + math.log(lo))
            dt = jnp.maximum(dt, floor)
            return dt + jnp.log(-jnp.expm1(-dt))

        nm, ne, na = (self.counts[kind] for kind in KINDS)
        h, inner, cdim = self.ssm_heads, self.ssm_inner, self.conv_dim
        ssm_dt_bias = dt_bias((nm, h))
        mamba = MambaParams(
            norm=jnp.ones((nm, d), f32),
            in_proj=normal((nm, d, inner + cdim + h)),
            # a depthwise convolution's usual start: U(+-1/sqrt(kernel))
            conv_w=uniform((nm, self.conv_kernel, cdim), -1.0, 1.0)
            / math.sqrt(self.conv_kernel),
            conv_b=jnp.zeros((nm, cdim), f32),
            dt_bias=ssm_dt_bias,
            a_log=jnp.log(uniform((nm, h), 1.0, 16.0)),
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
        ends = dict(embed=normal((self.vocab_size, d)),
                    norm_f=jnp.ones((d,), f32),
                    head=normal((d, self.vocab_size)))
        if not set(self.pattern) & set(WIDE_KINDS):
            return HybridLMParams(mamba=mamba, moe=moe, attn=attn, **ends)
        # The kinds that came later draw from a stream of their own, so the
        # three above start as they always did.
        keys = iter(jax.random.split(
            jax.random.fold_in(jax.random.key(seed), 1), 24))
        if self.expert_form == "silu_gated":
            moe = GatedExpertParams(
                norm=moe.norm, router=moe.router, router_bias=moe.router_bias,
                w_gate=normal(moe.w_up.shape), w_up=moe.w_up,
                w_down=moe.w_down, shared_gate=normal(moe.shared_up.shape),
                shared_up=moe.shared_up, shared_down=moe.shared_down)
        nk, nl, nd = (self.counts[kind] for kind in WIDE_KINDS)
        kh, khd, rank = self.kda_heads, self.kda_head_dim, self.kda_gate_rank
        kin = self.kda_inner
        kda = KdaParams(
            norm=jnp.ones((nk, d), f32),
            in_proj=normal((nk, d, 3 * kin + 2 * rank + kh)),
            conv_w=uniform((nk, self.conv_kernel, 3 * kin), -1.0, 1.0)
            / math.sqrt(self.conv_kernel),
            decay_up=normal((nk, rank, kin)),
            dt_bias=dt_bias((nk, kin)),
            a_log=jnp.log(uniform((nk, kh), 1.0, 16.0)),
            gate_up=normal((nk, rank, kin)),
            out_norm=jnp.ones((nk, khd), f32),
            out_proj=normal((nk, kin, d), resid),
        )
        qk = self.qk_nope_dim + self.qk_shared_dim
        mla = MlaParams(
            norm=jnp.ones((nl, d), f32),
            wq=normal((nl, d, self.num_heads * qk)),
            w_dkv=normal((nl, d, self.kv_lora_rank + self.qk_shared_dim)),
            kv_norm=jnp.ones((nl, self.kv_lora_rank), f32),
            w_ukv=normal((nl, self.kv_lora_rank, self.num_heads * (
                self.qk_nope_dim + self.v_head_dim))),
            wo=normal((nl, self.num_heads * self.v_head_dim, d), resid),
        )
        dense = DenseParams(
            norm=jnp.ones((nd, d), f32),
            w_gate=normal((nd, d, self.dense_dim)),
            w_up=normal((nd, d, self.dense_dim)),
            w_down=normal((nd, self.dense_dim, d), resid),
        )
        stacks = dict(mamba=mamba, moe=moe, attn=attn, kda=kda, mla=mla,
                      dense=dense)
        return StackLMParams(**ends, **{
            group: stacks[group] if self.counts[kind] else None
            for kind, group in GROUP_OF.items()})

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
            xbc = self._causal_conv_silu(xbc, p.conv_w, p.conv_b)
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
        gated = self.expert_form == "silu_gated"
        routed, load = moe_ffn_held(
            u, p.router, p.router_bias, p.w_up, p.w_down,
            first=self.experts_held[0], k=self.experts_per_token,
            scale=self.routed_scale, w_gate=p.w_gate if gated else None,
            compute_dtype=self.compute_dtype,
            balance=None if self.balance_rounds is None
            else (l, self.balance_rounds))
        with jax.named_scope(names.MOE_SHARED):
            if gated:
                shared = self._gated_ffn(
                    u, p.shared_gate, p.shared_up, p.shared_down)
            else:
                act = jnp.square(jax.nn.relu(self._dot(u, p.shared_up)))
                shared = self._dot(act, p.shared_down)
        return (routed + shared).reshape(b, l, d), load

    def _gated_ffn(self, u, w_gate, w_up, w_down):
        act = jax.nn.silu(self._dot(u, w_gate)) * self._dot(u, w_up)
        return self._dot(act, w_down)

    def _dense(self, p: DenseParams, h):
        with jax.named_scope(names.MLP):
            return self._gated_ffn(rmsnorm(h, p.norm, self.norm_eps),
                                   p.w_gate, p.w_up, p.w_down)

    def _causal_conv_silu(self, x, conv_w, bias=0.0):
        """Causal depthwise convolution (tap j sees the token kernel-1-j
        back), then silu. x [B, L, C], conv_w [kernel, C]."""
        k, l = self.conv_kernel, x.shape[1]
        padded = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
        return jax.nn.silu(bias + sum(
            padded[:, j:j + l] * conv_w[j] for j in range(k)))

    def _kda(self, p: KdaParams, h):
        b, l, _ = h.shape
        nh, hd, rank, inner = (self.kda_heads, self.kda_head_dim,
                               self.kda_gate_rank, self.kda_inner)
        heads = (b, l, nh, hd)
        with jax.named_scope(names.KDA_PROJ):
            proj = self._dot(rmsnorm(h, p.norm, self.norm_eps), p.in_proj)
            qkv, f, z, beta = jnp.split(
                proj, [3 * inner, 3 * inner + rank, 3 * inner + 2 * rank],
                axis=-1)
            f = self._dot(f, p.decay_up).reshape(heads)
            z = self._dot(z, p.gate_up).reshape(heads)
        with jax.named_scope(names.KDA_CONV):
            qkv = self._causal_conv_silu(qkv, p.conv_w)
        with jax.named_scope(names.KDA_GATE):
            q, k, v = (t.reshape(heads) for t in jnp.split(qkv, 3, axis=-1))
            unit = lambda t: t * jax.lax.rsqrt(  # noqa: E731
                jnp.sum(t * t, -1, keepdims=True) + 1e-6)
            q, k = unit(q) * hd ** -0.5, unit(k)
            g = -jnp.exp(p.a_log)[:, None] * jax.nn.softplus(
                f + p.dt_bias.reshape(nh, hd))
            beta = jax.nn.sigmoid(beta)
        with jax.named_scope(names.KDA_SCAN):
            # The recurrence is float32: on a TPU a float32 product at the
            # default precision rounds its operands to bfloat16.
            o = kda_chunked(q, k, v, g, beta,
                            precision=jax.lax.Precision.HIGHEST)
        with jax.named_scope(names.KDA_GATE):
            o = rmsnorm(o, p.out_norm, self.norm_eps) * jax.nn.sigmoid(z)
        with jax.named_scope(names.KDA_PROJ):
            return self._dot(o.reshape(b, l, inner), p.out_proj)

    def _mla(self, p: MlaParams, h):
        b, l, _ = h.shape
        cd, nh = self.compute_dtype, self.num_heads
        nope, shared, rank = self.qk_nope_dim, self.qk_shared_dim, self.kv_lora_rank
        with jax.named_scope(names.ATTN_QKV):
            u = rmsnorm(h, p.norm, self.norm_eps)
            q = self._dot(u, p.wq).reshape(b, l, nh, nope + shared)
            c, k_s = jnp.split(self._dot(u, p.w_dkv), [rank], axis=-1)
            kv = self._dot(rmsnorm(c, p.kv_norm, self.norm_eps), p.w_ukv)
            k_n, v = jnp.split(
                kv.reshape(b, l, nh, nope + self.v_head_dim), [nope], axis=-1)
            k = jnp.concatenate([k_n, jnp.broadcast_to(
                k_s[:, :, None, :], (b, l, nh, shared))], axis=-1)
        with jax.named_scope(names.ATTN_CORE):
            a = self._attend(q.astype(cd), k.astype(cd), v.astype(cd))
        with jax.named_scope(names.ATTN_OUT):
            return self._dot(a.reshape(b, l, -1), p.wo)

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

    def apply_with_counters(self, params, tokens):
        """tokens [B, L] int32 -> (logits [B, L, vocab] float32, counters:
        ``moe_expert_load`` int32 [E layers, experts] — the (token, choice)
        pairs that chose each expert — and its held part
        ``moe_expert_rows`` [E layers, experts held])."""
        with jax.named_scope(names.EMBED):
            h = params.embed[tokens].astype(jnp.float32)
        layers = {kind: iter(_layers_of(getattr(params, group, None)))
                  for kind, group in GROUP_OF.items()}
        mixers = {"M": self._mamba, "*": self._attention, "K": self._kda,
                  "L": self._mla, "D": self._dense}
        rows = []
        for kind in self.pattern:
            p = next(layers[kind])
            if kind == "E":
                out, landed = self._remat_wrap(self._experts)(p, h)
                h = h + out
                rows.append(landed)
            else:
                h = h + self._remat_wrap(mixers[kind])(p, h)
        with jax.named_scope(names.LM_HEAD):
            logits = self._dot(rmsnorm(h, params.norm_f, self.norm_eps),
                               params.head)
        first, held = self.experts_held
        load = (jnp.stack(rows) if rows
                else jnp.zeros((0, self.num_experts), jnp.int32))
        counters = {"moe_expert_load": load,
                    "moe_expert_rows": load[:, first:first + held]}
        return logits, counters

    def apply(self, params, tokens):
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
