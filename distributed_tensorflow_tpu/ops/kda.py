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
and one step's intermediates.

**The decayed scores ``A`` and ``P`` are a Pallas kernel pair** where the
shapes tile (float32, a channel width that is a multiple of 128: every
published width), ``kda_scores_fwd`` and, behind a ``jax.custom_vjp``,
``kda_scores_bwd``. Written as ``jax.numpy`` the pairs inside a sub-block
make a ``[..., sub, sub, K]`` decay tensor that is written to HBM, read
back, and differentiated by six more passes over tensors of its size: half
of the scan's time for a few exponentials an element. The kernels keep a
pair's decay in registers, form it again in the backward pass (what is
kept for it is ``x``, ``k`` and ``cum``) and return ``dx``, ``dk`` and,
through the decay, ``dcum = sum_x x * dx - k * dk``; both follow the two
rules above, so neither forms a positive exponent. They are exact float32
arithmetic on the vector unit, which is what ``precision=HIGHEST`` asks of
the matrix unit. Any other shape (the tests' narrow heads) takes
:func:`_decayed_scores`, the ``jax.numpy`` form and the kernels' oracle; no
argument chooses between the two. Everything else here is plain
``jax.numpy`` that ``jax.grad`` differentiates.

:func:`kda_step` is the three lines above for one token from a given state
(a served decode step), with the decay per key channel or one per head
broadcast over the channels (a "Gated DeltaNet" layer's form);
:func:`kda_sequential` is them token by token, for tests.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from distributed_tensorflow_tpu.observability import names
from distributed_tensorflow_tpu.ops.pallas_mode import resolve_interpret

SUB = 16  # a sub-block's tokens: what keeps every exponent <= 0
CHUNKS_PER_STEP = 8  # chunks a checkpointed step of the carry's scan takes
LANES = 128  # (chunk, batch, head) units a grid step of the kernels takes


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


# -- the same scores as a kernel pair ---------------------------------------
# The kernels put the (chunk, batch, head) units along the 128 lanes and a
# token's channels along the sublanes: a token of a grid step is one
# [K, LANES] tile, a pair of tokens is elementwise work on two tiles with
# nothing to broadcast, and the sum over channels is adds between vector
# registers and one sublane reduce. The decay of a pair lives in
# registers; nothing with a t, a j and a channel axis is written anywhere.


ROWS = 8  # a vector register's sublanes: the j's one aligned store takes


def _total(tile):
    """[K, LANES] -> [1, LANES]: the sum over channels, as a tree of adds
    between registers and one sublane reduce."""
    while tile.shape[0] > ROWS:
        half = tile.shape[0] // 2
        tile = tile[:half] + tile[half:]
    return jnp.sum(tile, axis=0, keepdims=True)


def _stack_rows(rows):
    """ROWS rows [1, LANES] -> [ROWS, LANES]."""
    at = lax.broadcasted_iota(jnp.int32, (ROWS, LANES), 0)
    out = jnp.broadcast_to(rows[0], (ROWS, LANES))
    for r in range(1, ROWS):
        out = jnp.where(at == r, rows[r], out)
    return out


def _scores_fwd_kernel(x_ref, k_ref, g_ref, o_ref, ek_ref, *, sub):
    """x_ref [n, C, K, LANES], k_ref and g_ref (the cumulative log-decay)
    [C, K, LANES] -> o_ref [n, C, C, LANES]; ek_ref [C - sub, K, LANES] is
    scratch: the earlier tokens' keys against the current sub-block's first
    token. The j's are taken ROWS at a time, one aligned store a row of
    the result."""
    n, c = x_ref.shape[:2]
    o_ref[...] = jnp.zeros(o_ref.shape, o_ref.dtype)
    for base in range(0, c, sub):
        first = g_ref[base]

        def fill(j, _, first=first):
            ek_ref[j] = k_ref[j] * jnp.exp(first - g_ref[j])

        lax.fori_loop(0, base, fill, None)

        def token(i, _, base=base, first=first):
            t = base + i
            gt = g_ref[t]
            xs = [x_ref[m, t] for m in range(n)]
            later = jnp.exp(gt - first)
            lx = [x * later for x in xs]

            def across(block, _):
                j0 = pl.multiple_of(block * ROWS, ROWS)
                eks = [ek_ref[j0 + r] for r in range(ROWS)]
                for m in range(n):
                    o_ref[m, t, pl.ds(j0, ROWS), :] = _stack_rows(
                        [_total(lx[m] * ek) for ek in eks])

            lax.fori_loop(0, base // ROWS, across, None)

            def inside(block, _):
                j0 = pl.multiple_of(base + block * ROWS, ROWS)
                # a j after t is no pair: its exponent is held at 0 and
                # its row left out
                zs = [k_ref[j0 + r] * jnp.exp(jnp.minimum(
                    gt - g_ref[j0 + r], 0.0)) for r in range(ROWS)]
                pair = j0 + lax.broadcasted_iota(
                    jnp.int32, (ROWS, LANES), 0) <= t
                for m in range(n):
                    o_ref[m, t, pl.ds(j0, ROWS), :] = jnp.where(
                        pair, _stack_rows([_total(xs[m] * z) for z in zs]),
                        0.0)

            lax.fori_loop(0, i // ROWS + 1, inside, None)

        lax.fori_loop(0, sub, token, None)


def _scores_bwd_kernel(x_ref, k_ref, g_ref, do_ref, dx_ref, dk_ref, dg_ref,
                       ek_ref, dek_ref, *, sub):
    """The forward's arguments and do_ref [n, C, C, LANES] -> dx_ref [n, C,
    K, LANES], dk_ref, dg_ref [C, K, LANES]. The decay is formed again, by
    the forward's two rules; ek_ref as there, dek_ref its cotangent. A
    pair's two products go to two sums over the other token, so the pairs
    are taken one at a time and only j <= t is visited."""
    n, c = x_ref.shape[:2]
    dk_ref[...] = jnp.zeros(dk_ref.shape, dk_ref.dtype)
    for base in range(0, c, sub):
        first = g_ref[base]

        def fill(j, _, first=first):
            ek_ref[j] = k_ref[j] * jnp.exp(first - g_ref[j])
            dek_ref[j] = jnp.zeros(dek_ref.shape[1:], dek_ref.dtype)

        lax.fori_loop(0, base, fill, None)

        def token(i, _, base=base, first=first):
            t = base + i
            gt = g_ref[t]
            xs = [x_ref[m, t] for m in range(n)]
            later = jnp.exp(gt - first)
            lx = [x * later for x in xs]
            weights = lambda j: [  # noqa: E731
                do_ref[m, t, pl.ds(j, 1), :] for m in range(n)]

            def across(j, dlx):
                ek, w = ek_ref[j], weights(j)
                dek_ref[j] += sum(w[m] * lx[m] for m in range(n))
                return [dlx[m] + w[m] * ek for m in range(n)]

            dlx = lax.fori_loop(
                0, base, across, [jnp.zeros_like(x) for x in xs])

            def inside(j, dxs):
                decay, w = jnp.exp(gt - g_ref[j]), weights(j)
                z = k_ref[j] * decay
                dk_ref[j] += decay * sum(w[m] * xs[m] for m in range(n))
                return [dxs[m] + w[m] * z for m in range(n)]

            dxs = lax.fori_loop(base, t + 1, inside, [d * later for d in dlx])
            for m in range(n):
                dx_ref[m, t] = dxs[m]

        lax.fori_loop(0, sub, token, None)

        def fold(j, _, first=first):
            dk_ref[j] += dek_ref[j] * jnp.exp(first - g_ref[j])

        lax.fori_loop(0, base, fold, None)

    def through_the_decay(t, _):
        # d M / d cum[t] = +x k E, d M / d cum[j] = -x k E: the whole of it
        dg_ref[t] = sum(
            x_ref[m, t] * dx_ref[m, t] for m in range(n)
        ) - k_ref[t] * dk_ref[t]

    lax.fori_loop(0, c, through_the_decay, None)


def _to_lanes(t, lead: tuple):
    """[..., *lead, C, X] -> [..., C, X, units]: the unit axes flattened,
    put last and padded to whole grid steps (a padded unit is all zeros:
    it decays nothing and scores nothing)."""
    front = t.shape[:t.ndim - len(lead) - 2]
    t = jnp.moveaxis(t.reshape(*front, -1, *t.shape[-2:]), -3, -1)
    return jnp.pad(t, ((0, 0),) * (t.ndim - 1) + ((0, -t.shape[-1] % LANES),))


def _from_lanes(t, lead: tuple):
    """:func:`_to_lanes` undone."""
    t = jnp.moveaxis(t, -1, -3)[..., :math.prod(lead), :, :]
    return t.reshape(*t.shape[:-3], *lead, *t.shape[-2:])


def _scores_call(kernel, name, ins, outs, scratch, sub):
    """One of the two kernels over [..., units] arrays, LANES units a grid
    step, every other axis whole; ``outs`` and ``scratch`` are float32
    shapes."""
    outs = [jax.ShapeDtypeStruct(s, jnp.float32) for s in outs]
    spec = lambda a: pl.BlockSpec(  # noqa: E731
        (*a.shape[:-1], LANES), lambda i: (*(0,) * (a.ndim - 1), i))
    # every block twice (the pipeline's two buffers) and the scratch
    words = 2 * LANES * sum(math.prod(a.shape[:-1]) for a in (*ins, *outs))
    words += sum(math.prod(s) for s in scratch)
    return pl.pallas_call(
        partial(kernel, sub=sub),
        grid=(ins[0].shape[-1] // LANES,),
        in_specs=[spec(a) for a in ins],
        out_specs=[spec(a) for a in outs],
        out_shape=outs,
        scratch_shapes=[pltpu.VMEM(s, jnp.float32) for s in scratch],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=4 * words + (8 << 20)),
        interpret=resolve_interpret(None),
        name=name,
    )(*ins)


@partial(jax.custom_vjp, nondiff_argnums=(3,))
def _decayed_scores_kernels(x, k, cum, sub: int):
    """:func:`_decayed_scores` for x [n, ..., C, K] beside k, cum [..., C,
    K], all float32, K a multiple of 128: ``kda_scores_fwd``, and
    ``kda_scores_bwd`` behind it."""
    return _scores_fwd(x, k, cum, sub)[0]


def _scores_fwd(x, k, cum, sub):
    lead, (c, width) = k.shape[:-2], k.shape[-2:]
    xl, kl, gl = (_to_lanes(t, lead) for t in (x, k, cum))
    (out,) = _scores_call(
        _scores_fwd_kernel, names.KERNEL_KDA_SCORES_FWD, (xl, kl, gl),
        [(*xl.shape[:2], c, xl.shape[-1])],
        [(max(c - sub, 1), width, LANES)], sub)
    return _from_lanes(out, lead), (xl, kl, gl)


def _scores_bwd(sub, res, dout):
    xl, kl, gl = res
    c, width = kl.shape[:2]
    lead = dout.shape[xl.ndim - 3:-2]
    dx, dk, dg = _scores_call(
        _scores_bwd_kernel, names.KERNEL_KDA_SCORES_BWD,
        (xl, kl, gl, _to_lanes(dout, lead)), [xl.shape, kl.shape, gl.shape],
        [(max(c - sub, 1), width, LANES)] * 2, sub)
    return tuple(_from_lanes(d, lead) for d in (dx, dk, dg))


_decayed_scores_kernels.defvjp(_scores_fwd, _scores_bwd)


def _scores(x, k, cum, sub, precision):
    """The decayed scores by the kernel pair where it tiles (float32, a
    channel width that is a multiple of the 128 lanes: every published
    width), by :func:`_decayed_scores` elsewhere."""
    tiled = (x.ndim == k.ndim + 1 and k.shape[-1] % 128 == 0
             and sub % ROWS == 0
             and {x.dtype, k.dtype, cum.dtype} == {jnp.dtype(jnp.float32)})
    if tiled:
        return _decayed_scores_kernels(x, k, cum, sub)
    return _decayed_scores(x, k, cum, sub, precision)


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
    a, p = _scores(jnp.stack([k, q]), k, cum, sub, precision)
    strict = jnp.tril(jnp.ones((c, c), bool), -1)
    solve = _unit_lower_inverse(
        jnp.where(strict, beta[..., :, None] * a, 0.0), precision
    ) * beta[..., None, :]
    end = cum[..., -1:, :]
    return (mm(solve, k * jnp.exp(cum)), mm(solve, v), p, q * jnp.exp(cum),
            k * jnp.exp(end - cum), jnp.exp(end[..., 0, :]))


def kda_chunked(q, k, v, g, beta, *, chunk: int = 64, precision=None,
                final_state: bool = False):
    """q, k, g [B, L, H, K], v [B, L, H, V], beta [B, L, H] -> o [B, L, H,
    V] (float32), from a zero state. ``L`` need not be a multiple of
    ``chunk``: the tail is padded with tokens that neither decay nor write
    (``g = 0``, ``beta = 0``), so a caller's own padding passes the state
    through unchanged where it gives them the same. A chunk longer than
    :data:`SUB` is a multiple of it. ``precision`` is the products'
    (``lax.Precision`` or None). With ``final_state`` the result is
    ``(o, S)``: ``S`` [B, H, K, V] float32, the state after the last
    token (the carry of the scan over chunks)."""
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
    s, o = lax.scan(one_step, s0, (by_step(q), by_step(k), by_step(v),
                                   by_step(g), beta_s))
    # [steps, per, B, H, chunk, V] -> [B, L, H, V]
    o = jnp.moveaxis(jnp.moveaxis(o, 2, 0), 3, 4)
    o = o.reshape(bsz, l + pad, h, v.shape[-1])[:, :l]
    return (o, s) if final_state else o


def kda_step(s, q, k, v, g, beta):
    """One token of the recurrence for every row: s [B, H, K, V], q, k
    [B, H, K], v [B, H, V], beta [B, H] and the log-decay ``g`` either per
    key channel [B, H, K] or one per head [B, H] -> (o [B, H, V], the new
    state), all float32. The sums over ``K`` are elementwise products
    summed (exact float32, as ``precision=HIGHEST`` asks of a product):
    one token's update reads and writes the state once, a product's
    passes would buy nothing."""
    f32 = jnp.float32
    s, q, k, v, g, beta = (t.astype(f32) for t in (s, q, k, v, g, beta))
    decay = jnp.exp(g)[..., None] if g.ndim == k.ndim else jnp.exp(g)[
        ..., None, None]
    s = decay * s
    answered = jnp.sum(s * k[..., None], axis=-2)  # S'^T k  [B, H, V]
    s = s + (beta[..., None] * k)[..., None] * (v - answered)[..., None, :]
    return jnp.sum(s * q[..., None], axis=-2), s


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
