"""The plain reference: GPT-2's forward pass, loss, gradients and AdamW
in straightforward ``jax.numpy``, float32, ``highest`` matmul precision.

No kernels, no cache, no batching tricks, nothing imported from the
program. It follows the published block (pre-LN, learned positions,
``gelu_new``, tied head) with one departure, the program's: the
attention projections carry no bias.

``precision`` selects the arithmetic of the matmuls:

- ``"float32"``: the reference proper;
- ``"int8"``: the control of "How correct is decided" for a bfloat16
  configuration: both operands of every block matmul rounded to int8
  (per output channel for weights, per row for activations) before a
  float32 product, in the forward and in the backward products alike.

Layers run under ``lax.scan`` with ``jax.checkpoint`` so that one
layer's activations live at a time; rows run in blocks.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

HI = lax.Precision.HIGHEST
LN_EPS = 1e-5


def _int8(x, axis):
    """``x`` rounded to 255 levels along ``axis`` (symmetric, per slice)."""
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    scale = jnp.where(scale == 0, 1.0, scale)
    return jnp.round(x / scale) * scale


@jax.custom_vjp
def _mm_int8(x, w):
    """x [..., k] @ w [k, n] with both operands in int8: activations per
    row, weights per output channel. The backward products take their
    operands in int8 too, as a step computed in int8 would."""
    return jnp.matmul(_int8(x, -1), _int8(w, 0), precision=HI)


def _mm_int8_fwd(x, w):
    return _mm_int8(x, w), (x, w)


def _mm_int8_bwd(res, g):
    x, w = res
    gq = _int8(g, -1)
    dx = jnp.matmul(gq, _int8(w, 1).T, precision=HI)
    x2 = x.reshape(-1, x.shape[-1])
    g2 = g.reshape(-1, g.shape[-1])
    dw = jnp.matmul(_int8(x2, 0).T, _int8(g2, 0), precision=HI)
    return dx, dw


_mm_int8.defvjp(_mm_int8_fwd, _mm_int8_bwd)


def _mm(x, w, precision):
    if precision == "int8":
        return _mm_int8(x, w)
    return jnp.matmul(x, w, precision=HI)


def _ln(x, scale, bias):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) * lax.rsqrt(var + LN_EPS) * scale + bias


def _mm_part(x, w, precision, parts):
    """The product with only the first of ``parts`` slices of the
    contraction: what a row-split matmul gives when the sum over chips
    (the exchange) is left out. ``parts == 1`` is the whole product."""
    k = w.shape[0] // parts
    return _mm(x[..., :k], w[:k], precision)


def _block(h, blk, n_head, precision, parts=1):
    b, l, d = h.shape
    x = _ln(h, blk["ln1_scale"], blk["ln1_bias"])
    split = lambda t: t.reshape(b, l, n_head, d // n_head)  # noqa: E731
    q, k, v = (split(_mm(x, blk[w], precision)) for w in ("wq", "wk", "wv"))
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=HI) / jnp.sqrt(
        jnp.float32(d // n_head))
    causal = jnp.tril(jnp.ones((l, l), bool))
    p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
    a = jnp.einsum("bhqk,bkhd->bqhd", p, v, precision=HI).reshape(b, l, d)
    h = h + _mm_part(a, blk["wo"], precision, parts)
    x = _ln(h, blk["ln2_scale"], blk["ln2_bias"])
    up = jax.nn.gelu(_mm(x, blk["w_up"], precision) + blk["b_up"],
                     approximate=True)
    return h + _mm_part(up, blk["w_down"], precision, parts) + blk["b_down"]


def hidden(params, tokens, n_head, precision="float32", parts=1):
    """tokens [B, L] -> final-layer-normed hidden states [B, L, d]."""
    l = tokens.shape[1]
    h = params["embed"][tokens] + params["pos"][:l]

    @jax.checkpoint
    def body(h, blk):
        return _block(h, blk, n_head, precision, parts), None

    h, _ = lax.scan(body, h, params["blocks"])
    return _ln(h, params["lnf_scale"], params["lnf_bias"])


def logits(params, tokens, n_head, precision="float32", parts=1):
    return jnp.matmul(hidden(params, tokens, n_head, precision, parts),
                      params["embed"].T, precision=HI)


def loss(params, tokens, n_head, precision="float32", parts=1):
    """Mean next-token cross-entropy over positions 0..L-2."""
    lg = logits(params, tokens[:, :-1], n_head, precision, parts)
    lse = jax.scipy.special.logsumexp(lg, axis=-1)
    picked = jnp.take_along_axis(lg, tokens[:, 1:, None], axis=-1)[..., 0]
    return jnp.mean(lse - picked)


@functools.partial(jax.jit, static_argnums=(2, 3, 4, 5))
def loss_and_grad(params, tokens, n_head, rows, precision="float32", parts=1):
    """Loss and gradient of the mean over all rows of ``tokens``, taken
    ``rows`` rows at a time so that the activations fit."""
    blocks = tokens.reshape(-1, rows, tokens.shape[-1])
    zero = jax.tree.map(jnp.zeros_like, params)

    def body(acc, blk):
        l, g = jax.value_and_grad(loss)(params, blk, n_head, precision, parts)
        return (acc[0] + l, jax.tree.map(jnp.add, acc[1], g)), None

    (l, g), _ = lax.scan(body, (jnp.float32(0), zero), blocks)
    n = blocks.shape[0]
    return l / n, jax.tree.map(lambda x: x / n, g)


@functools.partial(jax.jit, static_argnums=(4,), donate_argnums=(0, 1, 2))
def adamw(params, mu, nu, grads, hyper, step):
    """One AdamW update as optax.adamw computes it. ``hyper`` is
    (lr, b1, b2, eps, weight_decay); ``step`` counts from 1."""
    lr, b1, b2, eps, wd = hyper
    mu = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, mu, grads)
    nu = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, nu, grads)
    c1 = 1 - b1 ** step
    c2 = 1 - b2 ** step
    params = jax.tree.map(
        lambda p, m, v: p - lr * ((m / c1) / (jnp.sqrt(v / c2) + eps) + wd * p),
        params, mu, nu,
    )
    return params, mu, nu


@functools.partial(jax.jit, static_argnums=(3, 4))
def token_gaps(params, tokens, targets, n_head, precision="float32"):
    """For every position of ``tokens`` [B, L]: how far the reference's
    logit of a token lies below the reference's best. The token is
    ``targets`` (the served one) for ``"float32"``; for a lower
    precision it is the token which that precision puts first at the
    same position (the control: it need not decode)."""
    lg = logits(params, tokens, n_head)
    if precision != "float32":
        targets = jnp.argmax(logits(params, tokens, n_head, precision), axis=-1)
    picked = jnp.take_along_axis(lg, targets[..., None], axis=-1)[..., 0]
    return lg.max(-1) - picked
