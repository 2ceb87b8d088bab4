"""The gated delta rule with a per-channel decay (a "KDA" layer's
recurrence), computed in chunks.

Per head (state ``S`` is ``[K, V]``: key channels by value channels, all
float32, from zero):

    S' = Diag(exp(g_t)) S_{t-1}
    S_t = S' + beta_t k_t (v_t - S'^T k_t)^T
    o_t = S_t^T q_t

with ``g_t <= 0`` one log-decay per head, token and KEY CHANNEL, and
``beta_t`` in [0, 1] one write strength per head and token. The second
line is the delta rule: what the state already answers for ``k_t`` is
taken off ``v_t`` before the rest is written.

:func:`kda_chunked` is the chunked form. Inside a chunk of ``C`` tokens
with cumulative log-decay ``G`` and entering state ``S_0`` the writes
``u_t = beta_t (v_t - S'^T k_t)`` solve one unit lower-triangular system
(the WY / UT form of a product of Householder-like factors):

    (I + tril(Diag(beta) A, -1)) [W | U'] = Diag(beta) [K * e^G | V]
    A[t, j] = sum_c k[t, c] k[j, c] exp(G[t, c] - G[j, c])
    U = U' - W S_0
    O = (Q * e^G) S_0 + tril(P) U        P[t, j] = the same sum with q[t, c]
    S_C = Diag(e^{G_C}) S_0 + (K * e^{G_C - G})^T U

The correction ``W S_0`` reads the state, so the chunks' states cannot be
mixed in one product as :mod:`~distributed_tensorflow_tpu.ops.ssd` mixes
them: the carry is a ``lax.scan``. The system is solved by its own
inverse: ``tril(., -1)`` is nilpotent, so ``(I + N)^-1`` is the finite
product ``(I - N)(I + N^2)(I + N^4)...`` — matrix products only.

**No exponent that is formed is positive.** ``A`` and ``P`` as one product
``(x * e^G)(k * e^-G)^T`` overflow float32 once a channel's log-decay
inside a chunk passes -88, which a start of ``A = 16`` reaches in four
tokens. So a chunk is cut into sub-blocks of :data:`SUB` tokens: a pair of
tokens in DIFFERENT sub-blocks is measured against the first token of the
later one (``exp(G_t - G_ref) * exp(G_ref - G_j)``, both factors <= 1, so
still one product a sub-block), a pair inside ONE sub-block by its own
difference under the causal mask (as ``ops/ssd._decay``). A factor that
underflows to 0 stands for a product that is 0 in float32 too.

Memory: the scan's body takes :data:`CHUNKS_PER_STEP` chunks (their
triangular systems in one batch, then their carries one after the other)
and is checkpointed, so the backward pass holds one entering state a step
and one step's intermediates. Plain ``jax.numpy``; ``jax.grad``
differentiates it; no kernel.

:func:`kda_sequential` is the three lines above token by token, for tests.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

SUB = 16  # a sub-block's tokens: what keeps every exponent <= 0
CHUNKS_PER_STEP = 8  # chunks a checkpointed step of the carry's scan takes


def _decayed_scores(x, k, cum, sub: int, precision):
    """``M[..., t, j] = sum_c x[..., t, c] k[..., j, c] exp(cum[..., t, c]
    - cum[..., j, c])`` for ``j <= t`` inside a chunk, 0 above the
    diagonal. x [..., C, K] (leading axes may be more than ``k``'s), k and
    cum [..., C, K], cum non-increasing along C -> [..., C, C]."""
    *lead, c, width = k.shape
    ns = c // sub
    blocks = lambda t: t.reshape(*t.shape[:-2], ns, sub, width)  # noqa: E731
    xb, kb, cb = blocks(x), blocks(k), blocks(cum)
    ref = cb[..., :1, :]  # [..., ns, 1, K]: each sub-block's first token
    # Pairs in different sub-blocks: one product a sub-block, both factors
    # measured against the later block's first token.
    later = xb * jnp.exp(cb - ref)
    before = jnp.arange(c)[None, :] < (jnp.arange(ns) * sub)[:, None]  # [ns, C]
    earlier = k[..., None, :, :] * jnp.exp(jnp.where(
        before[..., None], ref - cum[..., None, :, :], -jnp.inf))
    across = jnp.einsum("...ntc,...njc->...ntj", later, earlier,
                        precision=precision)  # [..., ns, sub, C]
    across = across.reshape(*across.shape[:-3], c, c)
    # Pairs inside one sub-block: their own difference under the mask.
    tri = jnp.tril(jnp.ones((sub, sub), bool))
    decay = jnp.exp(jnp.where(
        tri[..., None], cb[..., :, None, :] - cb[..., None, :, :], -jnp.inf))
    inside = jnp.sum(xb[..., :, None, :] * kb[..., None, :, :] * decay, axis=-1)
    inside = jnp.einsum("...ntj,nm->...ntmj", inside,
                        jnp.eye(ns, dtype=inside.dtype))
    return across + inside.reshape(*inside.shape[:-4], c, c)


def _unit_lower_inverse(n, precision):
    """``(I + n)^-1`` for strictly lower-triangular ``n`` [..., C, C]: the
    Neumann series ends (``n^C = 0``), summed by doubling."""
    c = n.shape[-1]
    mm = lambda a, b: jnp.matmul(a, b, precision=precision)  # noqa: E731
    inv = jnp.eye(c, dtype=n.dtype) - n
    power, terms = mm(n, n), 2
    while terms < c:
        inv = inv + mm(inv, power)
        terms *= 2
        if terms < c:
            power = mm(power, power)
    return inv


def _within_chunks(q, k, v, g, beta, sub, precision):
    """What a chunk's carry reads, for any number of chunks at once.
    q, k, g [..., C, K], v [..., C, V], beta [..., C] -> W [..., C, K],
    U' [..., C, V], P [..., C, C], q * e^G [..., C, K], k * e^{G_C - G}
    [..., C, K], e^{G_C} [..., K]."""
    c = q.shape[-2]
    mm = lambda a, b: jnp.matmul(a, b, precision=precision)  # noqa: E731
    cum = jnp.cumsum(g, axis=-2)
    a, p = _decayed_scores(jnp.stack([k, q]), k, cum, sub, precision)
    strict = jnp.tril(jnp.ones((c, c), bool), -1)
    solve = _unit_lower_inverse(
        jnp.where(strict, beta[..., :, None] * a, 0.0), precision
    ) * beta[..., None, :]
    end = cum[..., -1:, :]
    return (mm(solve, k * jnp.exp(cum)), mm(solve, v), p, q * jnp.exp(cum),
            k * jnp.exp(end - cum), jnp.exp(end[..., 0, :]))


def kda_chunked(q, k, v, g, beta, *, chunk: int = 64, precision=None):
    """q, k, g [B, L, H, K], v [B, L, H, V], beta [B, L, H] -> o [B, L, H,
    V] (float32), from a zero state. ``L`` need not be a multiple of
    ``chunk``: the tail is padded with tokens that neither decay nor write
    (``g = 0``, ``beta = 0``). A chunk longer than :data:`SUB` is a
    multiple of it. ``precision`` is the products' (``lax.Precision`` or
    None)."""
    bsz, l, h, _ = q.shape
    sub = min(SUB, chunk)
    if chunk % sub:
        raise ValueError(f"sub-block {sub} does not divide chunk {chunk}")
    f32 = jnp.float32
    per = min(CHUNKS_PER_STEP, -(-l // chunk))
    span = chunk * per
    pad = -l % span
    steps = (l + pad) // span

    def by_step(t):
        """[B, L, H, ...] -> [steps, per, B, H, chunk, ...]."""
        t = jnp.pad(t.astype(f32), ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
        t = t.reshape(bsz, steps, per, chunk, h, *t.shape[3:])
        return jnp.moveaxis(jnp.moveaxis(t, 4, 3), 0, 2)

    beta_s = jnp.moveaxis(
        jnp.pad(beta.astype(f32), ((0, 0), (0, pad), (0, 0))).reshape(
            bsz, steps, per, chunk, h), (0, 4), (2, 3))
    mm = lambda a, b: jnp.matmul(a, b, precision=precision)  # noqa: E731

    def one_chunk(s, parts):
        w, u, p, q_in, k_out, keep = parts
        u = u - mm(w, s)
        o = mm(q_in, s) + mm(p, u)
        s = keep[..., None] * s + mm(jnp.swapaxes(k_out, -1, -2), u)
        return s, o

    @jax.checkpoint
    def one_step(s, xs):
        qs, ks, vs, gs, bs = xs  # [per, B, H, chunk, ...]
        return lax.scan(
            one_chunk, s, _within_chunks(qs, ks, vs, gs, bs, sub, precision))

    s0 = jnp.zeros((bsz, h, q.shape[-1], v.shape[-1]), f32)
    _, o = lax.scan(one_step, s0, (by_step(q), by_step(k), by_step(v),
                                   by_step(g), beta_s))
    # [steps, per, B, H, chunk, V] -> [B, L, H, V]
    o = jnp.moveaxis(jnp.moveaxis(o, 2, 0), 3, 4)
    return o.reshape(bsz, l + pad, h, v.shape[-1])[:, :l]


def kda_sequential(q, k, v, g, beta, *, precision=None):
    """The recurrence token by token (same arguments and result as
    :func:`kda_chunked`)."""
    bsz, _, h, width = q.shape
    f32 = jnp.float32
    q, k, v, g, beta = (t.astype(f32) for t in (q, k, v, g, beta))

    def step(s, t):
        qt, kt, vt, gt, bt = t  # [B, H, K] x2, [B, H, V], [B, H, K], [B, H]
        s = jnp.exp(gt)[..., None] * s
        answered = jnp.einsum("zhkv,zhk->zhv", s, kt, precision=precision)
        s = s + (bt[..., None] * kt)[..., None] * (vt - answered)[:, :, None, :]
        return s, jnp.einsum("zhkv,zhk->zhv", s, qt, precision=precision)

    s0 = jnp.zeros((bsz, h, width, v.shape[-1]), f32)
    _, o = lax.scan(step, s0, tuple(
        jnp.moveaxis(t, 1, 0) for t in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1)
