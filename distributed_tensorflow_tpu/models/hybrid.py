"""A language model whose layer pattern is data: state-space, delta-rule,
sparse-expert, feed-forward and attention layers in one stack (the
``nemotron_h`` block design; a ``kimi_linear`` layer is two letters).

The residual stream ``h`` runs through ``len(pattern)`` layers, each ONE
mixer behind one pre-norm — ``h <- h + mixer(RMSNorm(h))``; a block that
pairs a token mixer with a feed-forward is two letters, there is no pair
in the code — then a final RMSNorm and an untied head.
The pattern is a string over nine letters:

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
- ``G`` and ``W`` — gated attention over positions, full (``G``) or in a
  window of the last ``window`` keys (``W``): ``full_heads`` or
  ``window_heads`` query heads over the shared ``num_kv_heads`` x
  ``head_dim``, q and k turned by the kind's :class:`~..ops.rotary.Rotary`
  (``full_rotary``, ``window_rotary``: a base, the leading dims that turn,
  YaRN where given), scores scaled by ``head_dim^-1/2``, causal; each
  head's output times ``sigmoid(u W_gate)`` of that head (``u`` the
  layer's normed input) before ``wo``. No bias, no norm on q or k. A
  ``G`` layer may instead (``full_gate="element"``) gate each element of
  each head's output by its own column of ``W_gate`` (``heads x head_dim``
  wide), and (``full_qk_norm``) RMS-normalise each head of q and of k over
  ``head_dim`` by a learned weight before the rotation.
- ``N`` — a gated delta-rule mixer with ONE decay per head ("Gated
  DeltaNet"): ``[q | k | v | z] = u W_qkvz`` and ``[b | a] = u W_ba``;
  ``q, k, v`` through a causal depthwise convolution (no bias) and silu;
  ``gdn_key_heads`` heads of q and k (``gdn_key_dim`` wide), L2-normalised,
  ``q`` times ``gdn_key_dim^-1/2``; ``gdn_value_heads`` heads of v
  (``gdn_value_dim``), value head ``j`` reading key head ``j // (value
  heads / key heads)``; ``beta = sigmoid(b)`` and the log-decay ``g =
  -exp(A_log) * softplus(a + dt_bias)``, one each per value head and
  token; the float32 recurrence of :mod:`~distributed_tensorflow_tpu.ops.kda`
  with ``g`` the same for every key channel; an RMSNorm over each head
  with one learned weight, times ``silu(z)``; ``out_proj``.

With ``moe_router="softmax"`` an ``E`` layer routes by float32 softmax
probabilities over all experts, the ``experts_per_token`` most probable
weighed by their probabilities renormalised over the chosen (no bias, no
scale), and its shared expert's output is multiplied by ``sigmoid(u
w_shared)`` (``shared_router``, one column). With ``norm_offset`` 1 every
RMSNorm of the stack but a mixer's inner ones (the kinds' output norms,
latent attention's) is zero-centred: ``x / rms(x) * (1 + w)``.

Parameters are stacked per kind (``mamba[n_M]``, ``moe[n_E]``,
``attn[n_*]``, ``kda[n_K]``, ``mla[n_L]``, ``dense[n_D]``, ``full[n_G]``,
``window[n_W]``, ``gdn[n_N]``) and the layers run unrolled in pattern
order, each taking its kind's next slice. A pattern over ``M``, ``E``,
``*`` alone keeps the tree it always had (:class:`HybridLMParams`); one
that uses ``K``, ``L``, ``D``, ``G``, ``W`` or ``N`` has
:class:`StackLMParams`. Matmul operands are
``compute_dtype`` with float32 accumulation; norms, the router, the convolutions, both recurrences (their
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

A pattern over ``G``, ``W``, ``N``, ``D`` and ``E`` is also served by
:class:`~distributed_tensorflow_tpu.serve.TextServer` through the paged
interface :class:`~..gpt.GPTLM` gives it (``empty_paged_cache``,
``extend_paged``, ``decode_paged``, ``paged_blocks_per_slot``,
``max_len``, ``num_layers``, ``num_kv_heads``, ``head_dim``): the ``G``
layers' K/V in the server's block pool; per slot, in the cache's
``slot_state``, the ``W`` layers' K/V in a ring of ``ring_blocks`` blocks
(``ops/paged_attention``) and an ``N`` layer's recurrent state ``[value
heads, key dim, value dim]`` float32 and its last ``conv_kernel - 1``
rows before the convolution, written whole by the prefill at each row's
real length and advanced a token a decode step. The prefill reads no
cached prefix and returns the logits of each row's last position only;
the experts hold their share and drop nothing.
"""

from __future__ import annotations

import math
from typing import NamedTuple

import jax
import jax.numpy as jnp

from distributed_tensorflow_tpu.models.gpt import (
    GPTLM, PagedKVCache, _ce_from_logits)
from distributed_tensorflow_tpu.observability import names
from distributed_tensorflow_tpu.ops import paged_attention as paged
from distributed_tensorflow_tpu.ops import rotary
from distributed_tensorflow_tpu.ops.kda import kda_chunked, kda_step
from distributed_tensorflow_tpu.ops.moe import (
    moe_ffn_held, route_sigmoid_topk, route_softmax_topk,
)
from distributed_tensorflow_tpu.ops.ring_attention import dense_attention
from distributed_tensorflow_tpu.ops.ssd import ssd_chunked

KINDS = ("M", "E", "*")
# a pattern that uses one of these has StackLMParams
WIDE_KINDS = ("K", "L", "D", "G", "W", "N")
GROUP_OF = {"M": "mamba", "E": "moe", "*": "attn",
            "K": "kda", "L": "mla", "D": "dense", "G": "full", "W": "window",
            "N": "gdn"}
EXPERT_FORMS = ("relu2", "silu_gated")
GATE_FORMS = ("head", "element")  # a G layer's output gate
ROUTERS = ("sigmoid", "softmax")  # an E layer's routing
SERVED_KINDS = ("G", "W", "N", "D", "E")  # what the paged serving path runs


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


class SoftmaxExpertParams(NamedTuple):
    """``E`` with ``moe_router="softmax"`` (silu-gated experts): no
    correction bias, and the shared expert's gate."""

    norm: jax.Array  # [n, d]
    router: jax.Array  # [n, d, num_experts] over ALL experts
    w_gate: jax.Array  # [n, held, d, expert_dim]
    w_up: jax.Array  # [n, held, d, expert_dim]
    w_down: jax.Array  # [n, held, expert_dim, d]
    shared_gate: jax.Array  # [n, d, shared_dim]
    shared_up: jax.Array  # [n, d, shared_dim]
    shared_down: jax.Array  # [n, shared_dim, d]
    shared_router: jax.Array  # [n, d, 1]: the shared expert's sigmoid gate


class GdnParams(NamedTuple):
    """``N``: widths ``kd = key heads x key dim``, ``vd = value heads x
    value dim``, ``hv`` value heads."""

    norm: jax.Array  # [n, d]
    in_qkvz: jax.Array  # [n, d, 2*kd + 2*vd]: q, k, v, z
    in_ba: jax.Array  # [n, d, 2*hv]: b (write strength), a (decay)
    conv_w: jax.Array  # [n, kernel, 2*kd + vd]; last row = now; no bias
    dt_bias: jax.Array  # [n, hv]
    a_log: jax.Array  # [n, hv]
    out_norm: jax.Array  # [n, value dim]: one weight for every head
    out_proj: jax.Array  # [n, vd, d]


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


class GatedAttnParams(NamedTuple):
    """``G`` and ``W``: grouped-query attention whose heads are each
    scaled by a gate of the layer's input before ``wo``."""

    norm: jax.Array  # [n, d]
    wq: jax.Array  # [n, d, heads*head_dim]
    wk: jax.Array  # [n, d, kv_heads*head_dim]
    wv: jax.Array  # [n, d, kv_heads*head_dim]
    w_gate: jax.Array  # [n, d, heads] (a gate per element: heads*head_dim)
    wo: jax.Array  # [n, heads*head_dim, d]


class NormedGatedAttnParams(NamedTuple):
    """``G`` with ``full_qk_norm``: a norm weight over a head of q and
    one of k."""

    norm: jax.Array  # [n, d]
    wq: jax.Array  # [n, d, heads*head_dim]
    wk: jax.Array  # [n, d, kv_heads*head_dim]
    wv: jax.Array  # [n, d, kv_heads*head_dim]
    w_gate: jax.Array  # [n, d, heads] (a gate per element: heads*head_dim)
    wo: jax.Array  # [n, heads*head_dim, d]
    q_norm: jax.Array  # [n, head_dim]
    k_norm: jax.Array  # [n, head_dim]


class HybridLMParams(NamedTuple):
    embed: jax.Array  # [vocab, d]
    mamba: MambaParams
    moe: ExpertParams
    attn: AttnParams
    norm_f: jax.Array  # [d]
    head: jax.Array  # [d, vocab]


class StackLMParams(NamedTuple):
    """The tree of a pattern that uses ``K``, ``L``, ``D``, ``G``, ``W``
    or ``N``: a stack for each kind (``moe`` in the form the model was
    built with), None for a kind the pattern lacks."""

    embed: jax.Array  # [vocab, d]
    mamba: MambaParams | None
    moe: ExpertParams | GatedExpertParams | SoftmaxExpertParams | None
    attn: AttnParams | None
    kda: KdaParams | None
    mla: MlaParams | None
    dense: DenseParams | None
    norm_f: jax.Array  # [d]
    head: jax.Array  # [d, vocab]
    full: GatedAttnParams | NormedGatedAttnParams | None = None
    window: GatedAttnParams | None = None
    gdn: GdnParams | None = None


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
    None, or for ``HybridLMParams``'s stack of no layers). A leaf may also
    come as a tuple of per-layer arrays, as a server holds weights it must
    not copy (a compiled loop that slices a stacked leaf copies the slice:
    an expert layer's matrices are 1.6 GB at 256 experts)."""
    if stack is None or not len(stack[0]):
        return []
    n = len(stack[0])
    leaves = [leaf if isinstance(leaf, tuple) else _unstack(leaf)
              for leaf in stack]
    return [type(stack)(*(leaf[i] for leaf in leaves)) for i in range(n)]


def _layer_iters(params):
    """Each kind's layers, in order, as an iterator a pass takes from."""
    return {kind: iter(_layers_of(getattr(params, group, None)))
            for kind, group in GROUP_OF.items()}


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
        full_heads: int = 0,
        window_heads: int = 0,
        window: int | None = None,
        full_rotary: rotary.Rotary | None = None,
        window_rotary: rotary.Rotary | None = None,
        full_gate: str = "head",
        full_qk_norm: bool = False,
        gdn_key_heads: int = 0,
        gdn_key_dim: int = 0,
        gdn_value_heads: int = 0,
        gdn_value_dim: int = 0,
        moe_router: str = "sigmoid",
        norm_offset: float = 0.0,
        max_len: int = 0,
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
        for kind, heads, rot in (("G", full_heads, full_rotary),
                                 ("W", window_heads, window_rotary)):
            if self.counts[kind] and (
                    min(heads, head_dim, num_kv_heads or 0) < 1
                    or heads % num_kv_heads or rot is None
                    or not 0 < rot.dims <= head_dim or rot.dims % 2):
                raise ValueError(
                    f"a {kind} layer needs its query heads (a multiple of "
                    "num_kv_heads), head_dim and a Rotary over an even "
                    "number of a head's dims")
        if self.counts["W"] and (window is None or window < 1):
            raise ValueError("a W layer needs window >= 1")
        if full_gate not in GATE_FORMS:
            raise ValueError(
                f"unknown full_gate {full_gate!r}; one of {GATE_FORMS}")
        if self.counts["N"] and (
                min(gdn_key_heads, gdn_key_dim, gdn_value_heads,
                    gdn_value_dim) < 1 or gdn_value_heads % gdn_key_heads):
            raise ValueError(
                "an N layer needs gdn_key_heads, gdn_key_dim, "
                "gdn_value_heads (a multiple of the key heads) and "
                "gdn_value_dim")
        if moe_router not in ROUTERS or (
                moe_router == "softmax" and expert_form != "silu_gated"):
            raise ValueError(
                f"moe_router={moe_router!r}: one of {ROUTERS}; softmax "
                "routing is built with silu_gated experts")
        self.full_gate, self.full_qk_norm = full_gate, full_qk_norm
        self.gdn_key_heads, self.gdn_key_dim = gdn_key_heads, gdn_key_dim
        self.gdn_value_heads, self.gdn_value_dim = gdn_value_heads, gdn_value_dim
        self.moe_router = moe_router
        self.norm_offset = float(norm_offset)
        self.full_heads, self.window_heads = full_heads, window_heads
        self.window = window
        self.full_rotary, self.window_rotary = full_rotary, window_rotary
        self.max_len = max_len
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

    @property
    def gdn_widths(self) -> tuple[int, int]:
        """(q and k's width each, v and z's width each) of an ``N`` layer."""
        return (self.gdn_key_heads * self.gdn_key_dim,
                self.gdn_value_heads * self.gdn_value_dim)

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
        nk, nl, nd = (self.counts[kind] for kind in ("K", "L", "D"))
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
        # The gated attention kinds draw from a stream of their own too.
        keys = iter(jax.random.split(
            jax.random.fold_in(jax.random.key(seed), 2), 12))
        kv = self.num_kv_heads * self.head_dim

        def gated(n, heads, gate=1):
            return GatedAttnParams(
                norm=jnp.ones((n, d), f32),
                wq=normal((n, d, heads * self.head_dim)),
                wk=normal((n, d, kv)), wv=normal((n, d, kv)),
                w_gate=normal((n, d, heads * gate)),
                wo=normal((n, heads * self.head_dim, d), resid))

        full = gated(self.counts["G"], self.full_heads,
                     self.head_dim if self.full_gate == "element" else 1)
        window = gated(self.counts["W"], self.window_heads)
        # The kinds and options of the delta rule with a decay per head
        # draw from a stream of their own; a zero-centred norm starts at 0.
        keys = iter(jax.random.split(
            jax.random.fold_in(jax.random.key(seed), 3), 8))
        norm_w = 1.0 - self.norm_offset
        if self.full_qk_norm:
            qk = jnp.full((self.counts["G"], self.head_dim), norm_w, f32)
            full = NormedGatedAttnParams(*full, q_norm=qk, k_norm=qk)
        if self.moe_router == "softmax":
            moe = SoftmaxExpertParams(
                **{k: v for k, v in moe._asdict().items()
                   if k != "router_bias"},
                shared_router=normal((ne, d, 1)))
        nn = self.counts["N"]
        kd, vd = self.gdn_widths
        hv = self.gdn_value_heads
        gdn = GdnParams(
            norm=jnp.ones((nn, d), f32),
            in_qkvz=normal((nn, d, 2 * kd + 2 * vd)),
            in_ba=normal((nn, d, 2 * hv)),
            conv_w=uniform((nn, self.conv_kernel, 2 * kd + vd), -1.0, 1.0)
            / math.sqrt(self.conv_kernel),
            dt_bias=jnp.ones((nn, hv), f32),
            a_log=jnp.log(uniform((nn, hv), 0.0, 16.0)),
            out_norm=jnp.ones((nn, self.gdn_value_dim), f32),
            out_proj=normal((nn, vd, d), resid),
        )
        stacks = dict(mamba=mamba, moe=moe, attn=attn, kda=kda, mla=mla,
                      dense=dense, full=full, window=window, gdn=gdn)
        if self.norm_offset:  # every layer's norm and the final one
            stacks = {g: t._replace(norm=jnp.full_like(t.norm, norm_w))
                      for g, t in stacks.items()}
            ends["norm_f"] = jnp.full_like(ends["norm_f"], norm_w)
        return StackLMParams(**ends, **{
            group: stacks[group] if self.counts[kind] else None
            for kind, group in GROUP_OF.items()})

    # -- pieces --------------------------------------------------------------

    def _norm(self, x, weight):
        """The stack's RMSNorm: ``weight`` as it stands, or ``1 + weight``
        where the norms are zero-centred (``norm_offset``)."""
        if self.norm_offset:
            weight = weight.astype(jnp.float32) + self.norm_offset
        return rmsnorm(x, weight, self.norm_eps)

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
            proj = self._dot(self._norm(h, p.norm), p.in_proj)
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

    def _experts(self, p: ExpertParams, h, token_mask=None):
        """``token_mask`` [B, L] (None: every token) keeps the tokens it
        is False for out of the routed experts and their load."""
        b, l, d = h.shape
        gated = self.expert_form == "silu_gated"
        softmax = self.moe_router == "softmax"
        k = self.experts_per_token
        with jax.named_scope(names.MOE_ROUTE):
            u = self._norm(h, p.norm).reshape(b * l, d)
            mask = None if token_mask is None else token_mask.reshape(b * l)
            idx, w = (route_softmax_topk(u, p.router, k) if softmax
                      else route_sigmoid_topk(
                          u, p.router, p.router_bias, k, self.routed_scale,
                          None if self.balance_rounds is None
                          else (l, self.balance_rounds)))
        routed, load = moe_ffn_held(
            u, idx, w, p.w_up, p.w_down, experts=p.router.shape[1],
            first=self.experts_held[0], w_gate=p.w_gate if gated else None,
            compute_dtype=self.compute_dtype, token_mask=mask)
        with jax.named_scope(names.MOE_SHARED):
            if gated:
                shared = self._gated_ffn(
                    u, p.shared_gate, p.shared_up, p.shared_down)
            else:
                act = jnp.square(jax.nn.relu(self._dot(u, p.shared_up)))
                shared = self._dot(act, p.shared_down)
            if softmax:
                shared = shared * jax.nn.sigmoid(self._dot(u, p.shared_router))
        return (routed + shared).reshape(b, l, d), load

    def _gated_ffn(self, u, w_gate, w_up, w_down):
        act = jax.nn.silu(self._dot(u, w_gate)) * self._dot(u, w_up)
        return self._dot(act, w_down)

    def _dense(self, p: DenseParams, h):
        with jax.named_scope(names.MLP):
            return self._gated_ffn(self._norm(h, p.norm),
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
            proj = self._dot(self._norm(h, p.norm), p.in_proj)
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

    def _gdn_in(self, p: GdnParams, h):
        """h [B, L, d] -> (q|k|v before the convolution [B, L, 2kd + vd],
        z [B, L, vd], b and a [B, L, hv]), float32."""
        kd, vd = self.gdn_widths
        with jax.named_scope(names.GDN_PROJ):
            u = self._norm(h, p.norm)
            qkv, z = jnp.split(self._dot(u, p.in_qkvz), [2 * kd + vd], axis=-1)
            b, a = jnp.split(self._dot(u, p.in_ba), 2, axis=-1)
        return qkv, z, b, a

    def _gdn_heads(self, p: GdnParams, qkv, b, a):
        """The recurrence's inputs from the convolved ``qkv`` [..., 2kd +
        vd] and ``b``, ``a`` [..., hv]: q and k L2-normalised and repeated
        to the value heads [..., hv, dk], v [..., hv, dv], the log-decay
        and the write strength [..., hv] (one per value head)."""
        kd, _ = self.gdn_widths
        hk, dk = self.gdn_key_heads, self.gdn_key_dim
        hv, dv = self.gdn_value_heads, self.gdn_value_dim
        lead = qkv.shape[:-1]
        q, k, v = jnp.split(qkv, [kd, 2 * kd], axis=-1)
        unit = lambda t: t * jax.lax.rsqrt(  # noqa: E731
            jnp.sum(t * t, -1, keepdims=True) + 1e-6)
        q = unit(q.reshape(*lead, hk, dk)) * dk ** -0.5
        k = unit(k.reshape(*lead, hk, dk))
        q, k = (jnp.repeat(t, hv // hk, axis=-2) for t in (q, k))
        g = -jnp.exp(p.a_log) * jax.nn.softplus(a + p.dt_bias)
        return q, k, v.reshape(*lead, hv, dv), g, jax.nn.sigmoid(b)

    def _gdn_out(self, p: GdnParams, o, z):
        """o [..., hv, dv], z [..., vd] -> the layer's output [..., d]."""
        with jax.named_scope(names.GDN_PROJ):
            o = rmsnorm(o, p.out_norm, self.norm_eps) * jax.nn.silu(
                z.reshape(o.shape))
            return self._dot(o.reshape(*o.shape[:-2], -1), p.out_proj)

    def _gdn_scan(self, p: GdnParams, h, valid=None):
        """An ``N`` layer over whole sequences from a zero state: (its
        output [B, L, d], the state after the last position [B, hv, dk,
        dv] float32, q|k|v before the convolution). ``valid`` [B, L]
        (None: every position) leaves the state as it stands at the
        positions it is False for (a right-padded row's tail)."""
        qkv, z, b, a = self._gdn_in(p, h)
        with jax.named_scope(names.GDN_CONV):
            conv = self._causal_conv_silu(qkv, p.conv_w)
        with jax.named_scope(names.GDN_SCAN):
            q, k, v, g, beta = self._gdn_heads(p, conv, b, a)
            if valid is not None:
                g = jnp.where(valid[..., None], g, 0.0)
                beta = jnp.where(valid[..., None], beta, 0.0)
            o, state = kda_chunked(
                q, k, v, jnp.broadcast_to(g[..., None], k.shape), beta,
                precision=jax.lax.Precision.HIGHEST, final_state=True)
        return self._gdn_out(p, o, z), state, qkv

    def _gdn(self, p: GdnParams, h):
        return self._gdn_scan(p, h)[0]

    def _gdn_decode(self, p: GdnParams, h, state, conv, act):
        """One token per slot: h [S, 1, d], the slots' state [S, hv, dk,
        dv] and last ``conv_kernel - 1`` rows before the convolution
        [S, kernel - 1, 2kd + vd] -> (output [S, 1, d], the state and
        rows advanced where ``act``, as they were elsewhere)."""
        qkv, z, b, a = self._gdn_in(p, h)
        with jax.named_scope(names.GDN_CONV):
            rows = jnp.concatenate([conv, qkv], axis=1)  # [S, kernel, C]
            x = jax.nn.silu(sum(rows[:, j] * p.conv_w[j]
                                for j in range(self.conv_kernel)))
            conv = jnp.where(act[:, None, None], rows[:, 1:], conv)
        with jax.named_scope(names.GDN_STATE):
            q, k, v, g, beta = self._gdn_heads(p, x, b[:, 0], a[:, 0])
            o, new = kda_step(state, q, k, v, g, beta)
            state = jnp.where(act[:, None, None, None], new, state)
        return self._gdn_out(p, o[:, None], z), state, conv

    def _mla(self, p: MlaParams, h):
        b, l, _ = h.shape
        cd, nh = self.compute_dtype, self.num_heads
        nope, shared, rank = self.qk_nope_dim, self.qk_shared_dim, self.kv_lora_rank
        with jax.named_scope(names.ATTN_QKV):
            u = self._norm(h, p.norm)
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

    def _attend(self, q, k, v, window=None):
        from distributed_tensorflow_tpu.models.base import resolve_flash_min_len

        if self.attention_impl == "flash" and q.shape[1] >= (
                resolve_flash_min_len(self.flash_min_len)):
            from distributed_tensorflow_tpu.ops.pallas_attention import (
                REMAT_SAVE_NAMES, flash_attention)

            return flash_attention(
                q, k, v, causal=True, window=window,
                save_names=REMAT_SAVE_NAMES if self._policy_remat else None)
        return dense_attention(q, k, v, causal=True, window=window)

    def _kind(self, kind: str):
        """(query heads, Rotary, window, core scope) of ``G`` or ``W``."""
        if kind == "G":
            return self.full_heads, self.full_rotary, None, names.ATTN_FULL
        return (self.window_heads, self.window_rotary, self.window,
                names.ATTN_WINDOW)

    def _gated_qkv(self, p: GatedAttnParams, h, kind: str, positions):
        """h [B, L, d] at ``positions`` ([L] or [B, L]) -> q [B, L, Hq,
        Dh], k, v [B, L, Hkv, Dh] (turned, ``compute_dtype``) and the
        gates float32: the heads' [B, L, Hq], or each element's [B, L,
        Hq, Dh] (``full_gate="element"``)."""
        b, l, _ = h.shape
        heads, rot, _, _ = self._kind(kind)
        cd, hd = self.compute_dtype, self.head_dim
        with jax.named_scope(names.ATTN_QKV):
            u = self._norm(h, p.norm)
            q = self._dot(u, p.wq).reshape(b, l, heads, hd)
            kv = (b, l, self.num_kv_heads, hd)
            k = self._dot(u, p.wk).reshape(kv)
            v = self._dot(u, p.wv).reshape(kv)
            if kind == "G" and self.full_qk_norm:
                q, k = self._norm(q, p.q_norm), self._norm(k, p.k_norm)
            q = rotary.apply(q, positions, rot)
            k = rotary.apply(k, positions, rot)
            gate = jax.nn.sigmoid(self._dot(u, p.w_gate))
            if kind == "G" and self.full_gate == "element":
                gate = gate.reshape(b, l, heads, hd)
        return q.astype(cd), k.astype(cd), v.astype(cd), gate

    def _gated_out(self, p: GatedAttnParams, a, gate):
        """a [B, L, Hq, Dh] -> the layer's output [B, L, d]."""
        b, l = a.shape[:2]
        with jax.named_scope(names.ATTN_OUT):
            # a gate per head, or one per element
            a = a.astype(jnp.float32) * (
                gate[..., None] if gate.ndim < a.ndim else gate)
            return self._dot(a.reshape(b, l, -1), p.wo)

    def _gated_attention(self, p: GatedAttnParams, h, kind: str):
        q, k, v, gate = self._gated_qkv(
            p, h, kind, jnp.arange(h.shape[1], dtype=jnp.int32))
        _, _, window, scope = self._kind(kind)
        with jax.named_scope(scope):
            a = self._attend(q, k, v, window=window)
        return self._gated_out(p, a, gate)

    def _attention(self, p: AttnParams, h):
        b, l, _ = h.shape
        cd = self.compute_dtype
        with jax.named_scope(names.ATTN_QKV):
            u = self._norm(h, p.norm)
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
        layers = _layer_iters(params)
        mixers = {"M": self._mamba, "*": self._attention, "K": self._kda,
                  "L": self._mla, "D": self._dense, "N": self._gdn,
                  "G": lambda p, h: self._gated_attention(p, h, "G"),
                  "W": lambda p, h: self._gated_attention(p, h, "W")}
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
            logits = self._dot(self._norm(h, params.norm_f),
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

    # -- paged serving (TextServer) ------------------------------------------

    # extend_paged gives each row's last position's logits, not every
    # position's (at 16k positions every position's would be GBs)
    extend_returns_last = True
    # The cache carries per-slot state (``PagedKVCache.slot_state``) that a
    # block pool's sharing, relocation and quantization do not cover, and
    # the prefill reads no cached prefix: the server refuses what needs them.
    per_slot_state = True

    @property
    def num_layers(self) -> int:
        """Layers whose K/V the server's block pool holds: the ``G``
        layers (a ``W`` layer keeps a ring, the other kinds no cache)."""
        return self.counts["G"]

    def ring_blocks(self, block_size: int) -> int:
        """Blocks of a ``W`` layer's ring per slot: ``ceil(window / bs) +
        1`` (0 where the pattern has no ``W``)."""
        if not self.counts["W"]:
            return 0
        return -(-self.window // block_size) + 1

    def paged_blocks_per_slot(self, block_size: int) -> int:
        """Block-table width: blocks to address ``max_len`` positions."""
        if block_size < 1:
            raise ValueError(f"block_size must be >= 1, got {block_size}")
        return -(-self.max_len // block_size)

    def _check_served(self) -> None:
        missing = {"M": "a state-space layer's recurrent state (ROADMAP "
                        "R-state)",
                   "K": "a delta-rule layer's recurrent state with a decay "
                        "per key channel (ROADMAP R-state)",
                   "L": "latent attention's compressed cache (ROADMAP "
                        "R-latent)",
                   "*": "attention without positions"}
        for kind in sorted(set(self.pattern) - set(SERVED_KINDS)):
            raise NotImplementedError(
                f"pattern {self.pattern!r}: kind {kind!r} has no serving "
                f"path; the paged cache keeps nothing for {missing[kind]} "
                f"(served kinds: {SERVED_KINDS})")
        if self.max_len < 2:
            raise ValueError("serving needs max_len >= 2 (the positions a "
                             "slot may address)")

    def empty_paged_cache(self, slots: int, num_blocks: int,
                          block_size: int = 16,
                          kv_dtype: str = "bf16") -> PagedKVCache:
        """A vacant cache: ``num_blocks`` pool blocks for the ``G`` layers,
        tables of :meth:`paged_blocks_per_slot` entries, and per slot, in
        ``slot_state``: for the ``W`` layers a ring of :meth:`ring_blocks`
        blocks (``ring_k``, ``ring_v``: [W layers, slots * ring, bs, Hkv,
        Dh]); for each ``N`` layer its recurrent state (``gdn_state``, a
        tuple of [slots, hv, dk, dv] float32) and its last ``conv_kernel -
        1`` rows before the convolution (``gdn_conv``, a tuple of [slots,
        kernel - 1, 2kd + vd] float32). All zero (unreachable while
        lengths are 0; a prefill writes a slot's state whole). K/V are
        ``compute_dtype``."""
        self._check_served()
        if kv_dtype != "bf16":
            raise ValueError(
                f"kv_dtype={kv_dtype!r}: a HybridLM cache is held in its "
                "compute_dtype (quantized rings: ROADMAP R-kinds)")
        if slots < 1 or num_blocks < 1:
            raise ValueError("slots and num_blocks must be >= 1")
        row = (block_size, self.num_kv_heads, self.head_dim)
        pool = (self.counts["G"], num_blocks) + row
        cd, f32 = self.compute_dtype, jnp.float32
        state = {}
        if self.counts["W"]:
            ring = (self.counts["W"], slots * self.ring_blocks(block_size)) + row
            state.update(ring_k=jnp.zeros(ring, cd), ring_v=jnp.zeros(ring, cd))
        if self.counts["N"]:
            kd, vd = self.gdn_widths
            n = self.counts["N"]
            state.update(
                gdn_state=tuple(jnp.zeros(
                    (slots, self.gdn_value_heads, self.gdn_key_dim,
                     self.gdn_value_dim), f32) for _ in range(n)),
                gdn_conv=tuple(jnp.zeros(
                    (slots, self.conv_kernel - 1, 2 * kd + vd), f32)
                    for _ in range(n)))
        return PagedKVCache(
            k=jnp.zeros(pool, cd), v=jnp.zeros(pool, cd),
            block_tables=jnp.zeros(
                (slots, self.paged_blocks_per_slot(block_size)), jnp.int32),
            lengths=jnp.zeros((slots,), jnp.int32),
            slot_state=state or None)

    def _head_rows(self, params, h):
        """h [S, d] -> logits [S, vocab] float32."""
        with jax.named_scope(names.LM_HEAD):
            return self._dot(self._norm(h, params.norm_f), params.head)

    def extend_paged(self, params, cache: PagedKVCache, tokens, suffix_lens,
                     prefix_lens, admit):
        """Prefill rows ``tokens`` [B, L] (right-padded, real lengths
        ``suffix_lens`` [B]) from position 0, through the training-shaped
        layers (the flash kernels, a ``W`` layer's by its band, an ``N``
        layer's chunked scan), and write each row's state where ``admit``,
        into the rows' slots (``cache.slot_of_row``; None: row ``s`` is
        slot ``s``): the ``G`` layers' K/V through the rows' block tables,
        the ``W`` layers' last ``window`` positions into the slots' rings,
        an ``N`` layer's state at the row's real length (its pads neither
        decay nor write) and its last rows before the convolution, zero
        before position 0. No cached prefix is read: ``prefix_lens`` is the
        interface's and must be 0 (the server refuses prefix reuse with
        this cache). Returns (logits [B, vocab] float32 of each row's last
        real position, the cache; its lengths and tables untouched)."""
        del prefix_lens
        b, l = tokens.shape
        positions = jnp.broadcast_to(jnp.arange(l, dtype=jnp.int32), (b, l))
        valid = (jnp.arange(l)[None, :] < suffix_lens[:, None]) & admit[:, None]
        with jax.named_scope(names.EMBED):
            h = params.embed[tokens].astype(jnp.float32)
        layers = _layer_iters(params)
        rows = {"G": [], "W": [], "N": []}
        for kind in self.pattern:
            p = next(layers[kind])
            if kind in ("G", "W"):
                q, k, v, gate = self._gated_qkv(p, h, kind, positions[0])
                _, _, window, scope = self._kind(kind)
                with jax.named_scope(scope):
                    a = self._attend(q, k, v, window=window)
                h = h + self._gated_out(p, a, gate)
                rows[kind].append((k, v))
            elif kind == "N":
                out, state, qkv = self._gdn_scan(p, h, valid)
                h = h + out
                with jax.named_scope(names.GDN_CONV):
                    back = suffix_lens[:, None] + jnp.arange(
                        1 - self.conv_kernel, 0)  # [B, kernel - 1]
                    conv = jnp.take_along_axis(
                        qkv, jnp.maximum(back, 0)[..., None], axis=1)
                    conv = jnp.where(back[..., None] >= 0, conv, 0.0)
                rows["N"].append((state, conv))
            elif kind == "E":
                h = h + self._experts(p, h, token_mask=valid)[0]
            else:
                h = h + self._dense(p, h)
        last = jnp.take_along_axis(
            h, jnp.maximum(suffix_lens - 1, 0)[:, None, None], axis=1)[:, 0]
        logits = self._head_rows(params, last)
        new = {}
        if rows["G"]:
            for name, pool, got in zip(("k", "v"), (cache.k, cache.v),
                                       zip(*rows["G"])):
                new[name] = paged.scatter_token_kv_all_layers(
                    pool, jnp.stack(got).astype(pool.dtype),
                    cache.block_tables, positions, valid)
        slot_of_row = (jnp.arange(b, dtype=jnp.int32)
                       if cache.slot_of_row is None else cache.slot_of_row)
        state = dict(cache.slot_state or {})
        if rows["W"]:
            ring = self.ring_blocks(state["ring_k"].shape[2])
            for name, got in zip(("ring_k", "ring_v"), zip(*rows["W"])):
                pool = state[name]
                state[name] = paged.scatter_ring_rows(
                    pool, jnp.stack(got).astype(pool.dtype), slot_of_row,
                    positions, valid, ring=ring, window=self.window)
        if rows["N"]:
            # a row that admits nothing writes nothing
            at = jnp.where(admit, slot_of_row, cache.lengths.shape[0])
            for name, got in zip(("gdn_state", "gdn_conv"), zip(*rows["N"])):
                state[name] = tuple(
                    old.at[at].set(row, mode="drop")
                    for old, row in zip(state[name], got))
        if state:
            new["slot_state"] = state
        return logits, cache._replace(**new)

    def decode_paged(self, params, token, cache: PagedKVCache, active=None,
                     *, live=None, counters: bool = False):
        """One token per slot at position ``lengths``: a ``G`` layer reads
        the pool through the live-block list ``live``
        (``paged_attention.live_block_list``; made here for one step where
        none is given), a ``W`` layer its slot's ring, each meeting the
        row it writes as one more key; every layer's fresh row is
        committed after the last layer (pool rows through the tables,
        ring rows at ``(lengths // bs) % ring``); an ``N`` layer advances
        its slot's state and convolution rows by the token. Inactive slots
        are untouched and keep out of the experts. Returns (logits [S,
        vocab], cache with lengths advanced where active), and with
        ``counters`` also the experts held here that the active slots'
        tokens chose this step, as many distinct ones per ``E`` layer
        (int32 [n_E])."""
        s = token.shape[0]
        act = jnp.ones((s,), bool) if active is None else active
        lengths = cache.lengths
        state = dict(cache.slot_state or {})
        with jax.named_scope(names.EMBED):
            h = params.embed[token[:, None]].astype(jnp.float32)
        if live is None and self.counts["G"]:
            live = paged.live_block_list(
                cache.block_tables, lengths, act, 1, cache.k.shape[1],
                cache.k.shape[2])
        layers = _layer_iters(params)
        rows = {"G": [], "W": [], "N": []}
        touched = []
        for kind in self.pattern:
            p = next(layers[kind])
            if kind in ("G", "W"):
                q, k, v, gate = self._gated_qkv(p, h, kind, lengths[:, None])
                heads, _, _, scope = self._kind(kind)
                k_row, v_row = k[:, 0], v[:, 0]
                if kind == "G":
                    a = paged.paged_decode_attention(
                        q[:, 0], k_row, v_row, cache.k, cache.v,
                        len(rows["G"]), live, lengths, scopes=(scope, scope))
                else:
                    a = paged.ring_decode_attention(
                        q[:, 0], k_row, v_row, state["ring_k"],
                        state["ring_v"], len(rows["W"]), lengths,
                        window=self.window, scope=scope)
                rows[kind].append((k_row, v_row))
                h = h + self._gated_out(
                    p, a.reshape(s, 1, heads, self.head_dim), gate)
            elif kind == "N":
                i = len(rows["N"])
                out, st, conv = self._gdn_decode(
                    p, h, state["gdn_state"][i], state["gdn_conv"][i], act)
                h = h + out
                rows["N"].append((st, conv))
            elif kind == "E":
                out, load = self._experts(p, h, token_mask=act[:, None])
                h = h + out
                first, held = self.experts_held
                if held < self.num_experts:  # the experts held here
                    load = load[first:first + held]
                touched.append(jnp.sum(load > 0, dtype=jnp.int32))
            else:
                h = h + self._dense(p, h)
        logits = self._head_rows(params, h[:, 0])
        new = {"lengths": lengths + act.astype(jnp.int32)}
        if rows["G"]:
            for name, pool, got in zip(("k", "v"), (cache.k, cache.v),
                                       zip(*rows["G"])):
                new[name] = paged.commit_token_rows(
                    pool, jnp.stack(got).astype(pool.dtype),
                    cache.block_tables, lengths, act)
        if rows["W"]:
            for name, got in zip(("ring_k", "ring_v"), zip(*rows["W"])):
                pool = state[name]
                state[name] = paged.commit_ring_rows(
                    pool, jnp.stack(got).astype(pool.dtype), lengths, act)
        if rows["N"]:
            state["gdn_state"], state["gdn_conv"] = (
                tuple(t) for t in zip(*rows["N"]))
        if state:
            new["slot_state"] = state
        cache = cache._replace(**new)
        if not counters:
            return logits, cache
        return logits, cache, (jnp.stack(touched) if touched
                               else jnp.zeros((0,), jnp.int32))
