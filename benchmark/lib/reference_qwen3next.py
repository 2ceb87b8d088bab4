"""The plain reference of a ``qwen3_next`` stack: one forward pass over a
whole sequence in straightforward ``jax.numpy``, float32, ``highest``
matmul precision, nothing imported from the program.

No cache, no kernels, no paging, no chunks. Per layer ``h += Mixer(N(h));
h += MoE(N(h))``, then ``N`` and the head, ``N`` the zero-centred RMSNorm
``x rsqrt(mean x^2 + eps) (1 + w)``.

- Gated DeltaNet: ``[q | k | v | z] = u W_qkvz``, ``[b | a] = u W_ba``;
  ``q|k|v`` through the causal depthwise convolution of 4 taps and silu;
  q and k L2-normalised per head (``x rsqrt(sum x^2 + 1e-6)``), q times
  ``key_dim^-1/2``, value head ``j`` reading key head ``j // 2``; ``beta =
  sigmoid(b)``, ``g = -exp(A_log) softplus(a + dt_bias)``; the delta rule
  TOKEN BY TOKEN (a scan over blocks of 64 tokens, each under
  ``jax.checkpoint`` as the kimi reference keeps it, of a scan over the
  block's tokens) on a float32 state ``[key, value]`` per value head from
  zero: ``S <- exp(g) S; S <- S + k (beta (v - S^T k))^T; o = S^T q``, the
  sums over the key channels as elementwise products summed; then
  ``RMSNorm(o) w_o * silu(z)`` and ``out_proj``.
- Gated full attention: q and k by their zero-centred norms over each
  head's 256 dims, rotate-half pairs over the first 64 dims (theta from
  the configuration, no scaling), causal dense softmax over
  ``sqrt(head_dim)`` (``reference_laguna``'s, in blocks of queries), the
  output times ``sigmoid(u W_gate)`` per element, ``o_proj``.
- Experts: float32 softmax over ALL the router's experts, the ``k`` most
  probable renormalised; EVERY held expert computed on EVERY row, its
  output weighed by what the row gave it (0 where it was not chosen); the
  shared expert times ``sigmoid(u w_sg)``.

Departures, each the published description's: ``in_proj_qkvz`` and
``in_proj_ba`` as contiguous ``[q | k | v | z]`` and ``[b | a]`` column
blocks, and ``q_proj``'s query and gate halves as two matrices (the public
code groups the columns per head: a permutation that seeded weights do not
see); the multi-token-prediction module is left out. Weights come in
bfloat16 (``lib/weights_qwen3next.py``) and are widened here.

``precision``: ``"float32"`` is the reference proper; ``"int8"`` is the
control: both operands of every projection, expert and head product
rounded to int8 (weights per output channel, activations per row); the
recurrence, norms, router and softmax stay float32. ``"state_bf16"`` is
the second control, the nearest precision below the configuration's for
the recurrence: the delta rule's state rounded to bfloat16 after every
token (its arithmetic float32, every product float32 as in the reference
proper). ``fault`` plants one of :data:`FAULTS`, to show that the
comparison sees it; ``prompt_len`` is where the served decode began (the
first served token's position), read by the first two:

- ``zero_state``: every GDN layer enters that position from a zero state
  instead of the prompt's;
- ``no_conv_state``: from that position the convolution sees no row
  before it (the prefill's last rows dropped);
- ``no_decay``: ``g = 0`` (the state never decays);
- ``decay_doubled``: the decay applied twice a token (``exp(2 g)``);
- ``decay_after_write``: a token's write made before the decay, so that
  the decay also scales the token's own write;
- ``no_gate``: the attention's per-element output gate left out;
- ``no_shared_gate``: the shared expert's sigmoid gate left out;
- ``no_routed``: the routed experts' part left out.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from benchmark.lib.reference_laguna import _full_attention, _mm, _rotate, head_scan

HI = lax.Precision.HIGHEST
F32 = jnp.float32
FAULTS = ("zero_state", "no_conv_state", "no_decay", "decay_doubled",
          "decay_after_write", "no_gate", "no_shared_gate", "no_routed")
PRECISIONS = ("float32", "int8", "state_bf16")
BLOCK = 64  # tokens of the recurrence a checkpointed block holds

__all__ = ["FAULTS", "PRECISIONS", "final_hidden", "head_scan"]


def _znorm(x, w, eps):
    """The zero-centred RMSNorm over the last axis."""
    return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * (
        1.0 + w.astype(F32))


def _rms(x, w, eps):
    return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w.astype(F32)


def _unit(x):
    return x * lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)


def _delta_rule(q, k, v, g, beta, reset_at, write_first=False,
                state_bf16=False):
    """q, k [L, H, K], v [L, H, V], g and beta [L, H] -> o [L, H, V], one
    token at a time from a zero state; the state is zeroed again before
    position ``reset_at`` (-1: never). ``write_first``: the fault
    ``decay_after_write``; ``state_bf16``: the state rounded to bfloat16
    after every token."""
    l, h, width = q.shape

    def token(s, t):
        pos, qt, kt, vt, gt, bt = t
        s = jnp.where(pos == reset_at, 0.0, s)
        decay = jnp.exp(gt)[:, None, None]
        if not write_first:
            s = decay * s
        answered = jnp.sum(s * kt[:, :, None], axis=1)  # [H, V]
        s = s + (bt[:, None] * kt)[:, :, None] * (vt - answered)[:, None, :]
        if write_first:
            s = decay * s
        if state_bf16:
            # not a convert pair: a compiler that allows excess precision
            # may fold that away
            s = lax.reduce_precision(s, exponent_bits=8, mantissa_bits=7)
        return s, jnp.sum(s * qt[:, :, None], axis=1)

    @jax.checkpoint
    def block(s, ts):
        return lax.scan(token, s, ts)

    blk = BLOCK if l % BLOCK == 0 else l
    ts = (jnp.arange(l), q, k, v, g, beta)
    by_block = [t.reshape(l // blk, blk, *t.shape[1:]) for t in ts]
    _, o = lax.scan(block, jnp.zeros((h, width, v.shape[-1]), F32), by_block)
    return o.reshape(l, h, v.shape[-1])


def _conv(x, w, start):
    """Causal depthwise convolution then silu: x [L, C], w [taps, C]; tap
    ``j`` sees the row ``taps - 1 - j`` back. Where ``start`` >= 0, a
    position at or past it sees no row before it."""
    l, taps = x.shape[0], w.shape[0]
    pos = jnp.arange(l)
    out = 0.0
    for j in range(taps):
        back = taps - 1 - j
        row = jnp.concatenate([jnp.zeros((back, x.shape[1]), F32), x])[:l]
        cut = (start >= 0) & (pos >= start) & (pos - back < start)
        out = out + jnp.where(cut[:, None], 0.0, row) * w[j].astype(F32)
    return jax.nn.silu(out)


@functools.partial(jax.jit, static_argnums=(3, 4, 5))
def _gdn_layer(p, h, prompt_len, z_items, precision, fault):
    z = dict(z_items)
    l = h.shape[0]
    hk, dk, hv, dv = z["key_heads"], z["key_dim"], z["value_heads"], z["value_dim"]
    kd, vd = hk * dk, hv * dv
    u = _znorm(h, p["norm"], z["eps"])
    qkvz = _mm(u, p["in_qkvz"], precision)
    qkv, gate = qkvz[:, :2 * kd + vd], qkvz[:, 2 * kd + vd:]
    ba = _mm(u, p["in_ba"], precision)
    b, a = ba[:, :hv], ba[:, hv:]
    x = _conv(qkv, p["conv_w"],
              prompt_len if fault == "no_conv_state" else jnp.int32(-1))
    q = _unit(x[:, :kd].reshape(l, hk, dk)) * dk ** -0.5
    k = _unit(x[:, kd:2 * kd].reshape(l, hk, dk))
    q, k = jnp.repeat(q, hv // hk, axis=1), jnp.repeat(k, hv // hk, axis=1)
    v = x[:, 2 * kd:].reshape(l, hv, dv)
    g = -jnp.exp(p["a_log"].astype(F32)) * jax.nn.softplus(
        a + p["dt_bias"].astype(F32))
    if fault == "no_decay":
        g = jnp.zeros_like(g)
    elif fault == "decay_doubled":
        g = 2.0 * g
    o = _delta_rule(q, k, v, g, jax.nn.sigmoid(b),
                    prompt_len if fault == "zero_state" else jnp.int32(-1),
                    write_first=fault == "decay_after_write",
                    state_bf16=precision == "state_bf16")
    o = _rms(o, p["out_norm"], z["eps"]) * jax.nn.silu(gate.reshape(l, hv, dv))
    return h + _mm(o.reshape(l, vd), p["out_proj"], precision)


@functools.partial(jax.jit, static_argnums=(2, 3, 4))
def _attention_layer(p, h, z_items, precision, fault):
    z = dict(z_items)
    l, hd, heads, kv = h.shape[0], z["head_dim"], z["heads"], z["kv_heads"]
    rope = dict(z["rope"])
    u = _znorm(h, p["norm"], z["eps"])
    q = _znorm(_mm(u, p["wq"], precision).reshape(l, heads, hd), p["q_norm"],
               z["eps"])
    k = _znorm(_mm(u, p["wk"], precision).reshape(l, kv, hd), p["k_norm"],
               z["eps"])
    v = _mm(u, p["wv"], precision).reshape(l, kv, hd)
    a = _full_attention(_rotate(q, rope), _rotate(k, rope), v)
    gate = jax.nn.sigmoid(_mm(u, p["w_gate"], precision))
    if fault == "no_gate":
        gate = jnp.ones_like(gate)
    return h + _mm(a.reshape(l, heads * hd) * gate, p["wo"], precision)


def _ffn(x, wg, wu, wd, precision):
    return _mm(jax.nn.silu(_mm(x, wg, precision)) * _mm(x, wu, precision),
               wd, precision)


@functools.partial(jax.jit, static_argnums=(2, 3, 4))
def _experts_layer(p, h, z_items, precision, fault):
    z = dict(z_items)
    u = _znorm(h, p["norm"], z["eps"])
    probs = jax.nn.softmax(
        jnp.matmul(u, p["router"].astype(F32), precision=HI), -1)
    top, idx = lax.top_k(probs, z["top_k"])
    w = top / jnp.sum(top, -1, keepdims=True)
    # what each row gives each held expert: 0 where it did not choose it
    given = jnp.sum(jnp.where(idx[..., None] == jnp.arange(z["held"]),
                              w[..., None], 0.0), axis=1)  # [L, held]
    shared = _ffn(u, p["shared_gate"], p["shared_up"], p["shared_down"],
                  precision)
    if fault != "no_shared_gate":
        shared = shared * jax.nn.sigmoid(_mm(u, p["shared_router"], precision))
    out = h + shared
    if fault == "no_routed":
        return out

    def one(e, acc):
        y = _ffn(u, p["w_gate"][e], p["w_up"][e], p["w_down"][e], precision)
        return acc + given[:, e, None] * y

    return lax.fori_loop(0, z["held"], one, out)


def final_hidden(weights, tokens, z, precision="float32", fault=None,
                 prompt_len=None):
    """tokens [L] (L a multiple of the blocks; every layer is causal, so
    pads after the real tokens reach none of them) -> the final normed
    hidden states [L, d] float32. ``prompt_len``: where the served decode
    began, read by the faults ``zero_state`` and ``no_conv_state``."""
    if fault is not None and fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}; one of {FAULTS}")
    if precision not in PRECISIONS:
        raise ValueError(
            f"unknown precision {precision!r}; one of {PRECISIONS}")
    items = tuple(sorted(
        (k, tuple(sorted(v.items())) if isinstance(v, dict) else v)
        for k, v in z.items()))
    h = weights["embed"][jnp.asarray(tokens)].astype(F32)
    start = jnp.int32(-1 if prompt_len is None else prompt_len)
    at = {"N": 0, "G": 0, "E": 0}
    group = {"N": "gdn", "G": "full", "E": "moe"}
    for kind in z["pattern"]:
        # one layer's leaves (a stacked leaf's slice, or a list's element)
        p = {k: v[at[kind]] for k, v in weights[group[kind]].items()}
        at[kind] += 1
        if kind == "N":
            h = _gdn_layer(p, h, start, items, precision, fault)
        elif kind == "G":
            h = _attention_layer(p, h, items, precision, fault)
        else:
            h = _experts_layer(p, h, items, precision, fault)
    return _znorm(h, weights["norm_f"], z["eps"])
