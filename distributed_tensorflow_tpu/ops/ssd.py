"""The state-space recurrence of a Mamba-2 layer, computed in chunks.

Per head (state ``S`` is ``[P, N]``, all float32):

    S_t = exp(dt_t * a) * S_{t-1} + dt_t * x_t (outer) b_t
    y_t = S_t c_t

with ``a < 0`` one scalar per head, ``dt_t > 0`` one scalar per head and
token, and ``b_t`` / ``c_t`` shared by the ``H / G`` heads of a group. The
skip term ``D * x_t`` is the caller's.

:func:`ssd_chunked` is the "state-space duality" form: within a chunk of
``Q`` tokens the recurrence is a masked ``[Q, Q]`` decay matrix applied as
a product (token ``s`` reaches token ``t >= s`` with weight
``exp(sum_{s<r<=t} dt_r a) * dt_s * (c_t . b_s)``), across chunks the state
is carried: each chunk's contribution to the state at its end is one
product, the states entering every chunk are a ``[chunks, chunks]`` decay
mix of those, and their effect on the chunk's outputs is one more product.
Plain ``jax.numpy``: ``jax.grad`` differentiates it; no kernel.

:func:`ssd_sequential` is the recurrence itself, token by token, for
tests.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax


def _decay(cs_t, cs_s, mask):
    """exp(cs_t - cs_s) where ``mask``, else 0 (the masked entries would
    overflow: the exponent is positive there)."""
    return jnp.exp(jnp.where(mask, cs_t - cs_s, -jnp.inf))


def ssd_chunked(x, dt, a, b, c, *, chunk: int, precision=None):
    """x [B, L, H, P], dt [B, L, H], a [H], b and c [B, L, G, N] -> y
    [B, L, H, P] (float32), from a zero state. ``L`` need not be a
    multiple of ``chunk``: the tail is padded with ``dt = 0`` tokens, which
    neither decay nor feed the state. ``precision`` is the products'
    (``lax.Precision`` or None)."""
    bsz, l, h, p = x.shape
    g, n = b.shape[2], b.shape[3]
    if h % g:
        raise ValueError(f"{h} heads do not divide into {g} groups")
    r = h // g
    f32 = jnp.float32
    x, dt, b, c = (t.astype(f32) for t in (x, dt, b, c))
    pad = -l % chunk
    if pad:
        x, dt, b, c = (
            jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
            for t in (x, dt, b, c))
    nc, q = (l + pad) // chunk, chunk
    # [B, nc, G, R, Q, ...]: heads by group, tokens of a chunk minor.
    xr = x.reshape(bsz, nc, q, g, r, p).transpose(0, 1, 3, 4, 2, 5)
    dtr = dt.reshape(bsz, nc, q, g, r).transpose(0, 1, 3, 4, 2)
    br = b.reshape(bsz, nc, q, g, n).transpose(0, 1, 3, 2, 4)  # [B,nc,G,Q,N]
    cr = c.reshape(bsz, nc, q, g, n).transpose(0, 1, 3, 2, 4)
    cs = jnp.cumsum(dtr * a.astype(f32).reshape(g, r, 1), axis=-1)  # [B,nc,G,R,Q]
    xdt = xr * dtr[..., None]  # [B,nc,G,R,Q,P]

    # Inside each chunk: y_t += sum_{s<=t} decay(t, s) (c_t . b_s) dt_s x_s
    tri = jnp.tril(jnp.ones((q, q), bool))
    cb = jnp.einsum("zcgtn,zcgsn->zcgts", cr, br, precision=precision)
    m = cb[:, :, :, None] * _decay(cs[..., :, None], cs[..., None, :], tri)
    y = jnp.einsum("zcgrts,zcgrsp->zcgrtp", m, xdt, precision=precision)

    # Each chunk's own contribution to the state at its end.
    to_end = jnp.exp(cs[..., -1:] - cs)  # [B,nc,G,R,Q]
    states = jnp.einsum(
        "zcgrsp,zcgsn->zcgrpn", xdt * to_end[..., None], br,
        precision=precision)

    # The state entering chunk k: sum_{j<k} (decay of chunks j+1..k-1)
    # states_j.
    ccs = jnp.cumsum(cs[..., -1], axis=1)  # log-decay up to each chunk's end
    before = jnp.concatenate([jnp.zeros_like(ccs[:, :1]), ccs[:, :-1]], axis=1)
    strict = jnp.tril(jnp.ones((nc, nc), bool), -1)  # [k, j]: j < k
    mix = _decay(
        before[:, :, None], ccs[:, None, :],
        strict[None, :, :, None, None])  # [B, nc, nc, G, R]
    entering = jnp.einsum(
        "zkjgr,zjgrpn->zkgrpn", mix, states, precision=precision)
    y = y + jnp.einsum(
        "zcgtn,zcgrpn->zcgrtp", cr, entering, precision=precision,
    ) * jnp.exp(cs)[..., None]
    return y.transpose(0, 1, 4, 2, 3, 5).reshape(bsz, nc * q, h, p)[:, :l]


def ssd_sequential(x, dt, a, b, c, *, precision=None):
    """The recurrence token by token (same arguments and result as
    :func:`ssd_chunked`)."""
    bsz, l, h, p = x.shape
    g, n = b.shape[2], b.shape[3]
    f32 = jnp.float32
    x, dt, b, c = (t.astype(f32) for t in (x, dt, b, c))
    b, c = (jnp.repeat(t, h // g, axis=2) for t in (b, c))  # [B,L,H,N]
    s0 = jnp.zeros((bsz, h, p, n), f32)

    def step(s, t):
        xt, dtt, bt, ct = t
        s = (jnp.exp(dtt * a)[..., None, None] * s
             + (dtt[..., None] * xt)[..., None] * bt[:, :, None, :])
        return s, jnp.einsum("zhpn,zhn->zhp", s, ct, precision=precision)

    _, y = lax.scan(step, s0, tuple(
        jnp.moveaxis(t, 1, 0) for t in (x, dt, b, c)))
    return jnp.moveaxis(y, 0, 1)
