"""Paged KV-cache primitives: block-table gather/scatter + extend attention.

The reference has no attention — and no serving — at all (SURVEY.md
§2b: its model is a fixed MLP and its only "inference" is the in-loop
eval fetch, reference tfsingle.py:94); this module is new capability on
round-2's attention surface, with masking semantics matching
``ops/pallas_attention.py`` (causal + optional sliding window + ragged
``kv_lens``) re-addressed through block tables.

The device half of the paged serving cache (host half:
``serve_pool.py``; model plumbing: ``GPTLM.extend_paged`` /
``decode_paged``). K/V live in one shared pool of fixed-size blocks
``[num_blocks, block_size, Hkv, Dh]`` per layer; each serving slot maps
its logical positions through a block table ``[S, max_blocks]`` —
position ``p`` of slot ``s`` lives at
``pool[table[s, p // bs], p % bs]``. The two readers go through the
tables differently. The EXTEND step (prefill, speculative verify)
gathers each slot's table into a per-slot contiguous view and runs the
dense math over it (``gather_block_view`` +
``paged_extend_attention``): its queries are many and its prefix short.
The DECODE step never builds that rectangle: it walks a compacted list
of the blocks that are resident (``live_block_list``, made once per
chunk from the tables, the lengths and the active mask), a tile of
blocks at a time. Every tile holds the blocks of one slot (each slot's
entries are padded up to whole tiles), so a turn is two matrix products
of that slot's queries with the tile and one update of that slot's
running softmax (``paged_decode_attention``): what a step reads follows
what the pool holds, plus each slot's padding to a whole tile, and not
``slots × max_blocks``. Correctness lives in the masks, not the layout,
on both paths.

Out-of-range discipline: unused table entries and masked (pad /
non-admitted) writes are routed to a sentinel block index ``num_blocks``
(one PAST the pool) and dropped via scatter ``mode="drop"`` — never
``-1``, which JAX index arithmetic would wrap to the pool's last block
and silently corrupt it. Gathers of garbage table entries are fine:
their positions are masked out of every softmax by the validity masks
below (same stale-bytes-unreachable stance as ``SlotKVCache``).
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from distributed_tensorflow_tpu.observability import names
from distributed_tensorflow_tpu.ops.quantized import dequantize_kv
from distributed_tensorflow_tpu.ops.ring_attention import group_query_heads

_NEG_INF = -1e30


def gather_block_view(pool: jax.Array, block_tables: jax.Array):
    """One layer's per-slot contiguous K (or V) view through the block
    tables: ``[num_blocks, bs, Hkv, Dh]`` + ``[S, NB]`` →
    ``[S, NB*bs, Hkv, Dh]``, where view position ``p`` is logical
    position ``p`` of the slot (the extend step's read; the decode step
    walks ``live_block_list`` instead). Unused table entries gather
    garbage that the caller's validity mask must keep out of the
    softmax."""
    s, tabs = block_tables.shape
    with jax.named_scope(names.KV_GATHER):
        view = jnp.take(pool, block_tables, axis=0)  # [S,NB,bs,H,D]
        return view.reshape(s, tabs * view.shape[2], *view.shape[3:])


def _table_index(block_tables, positions, valid, num_blocks, block_size):
    """``(block, offset)`` of logical ``positions`` [S, L] through the
    tables, masked writes routed to the sentinel block ``num_blocks``:
    the one place the out-of-range discipline's arithmetic lives."""
    bidx = jnp.take_along_axis(block_tables, positions // block_size, axis=1)
    return jnp.where(valid, bidx, num_blocks), positions % block_size


def scatter_token_kv_all_layers(
    pool: jax.Array,
    kvs: jax.Array,
    block_tables: jax.Array,
    positions: jax.Array,
    valid: jax.Array,
):
    """Write per-slot K (or V) rows into every layer's pool through the
    block tables (the extend path scatters once after its layer scan):
    ``pool`` [n, NB, bs, Hkv, Dh]; ``kvs`` [n, S, L, Hkv, Dh] holds the
    rows for logical ``positions`` [S, L] (absolute per slot); ``valid``
    [S, L] masks pad positions and non-admitted slots — their writes
    drop at the sentinel block. Distinct live slots never map the same
    WRITABLE block (the allocator shares only immutable full prompt
    blocks, and writes land past the prompt), so the scatter rows are
    disjoint by construction."""
    n, nb, bs = pool.shape[0], pool.shape[1], pool.shape[2]
    s, l = positions.shape
    with jax.named_scope(names.KV_WRITE):
        bidx, off = _table_index(block_tables, positions, valid, nb, bs)
        flat = kvs.reshape(n, s * l, *kvs.shape[3:])
        return pool.at[:, bidx.reshape(-1), off.reshape(-1)].set(
            flat, mode="drop"
        )


def commit_token_rows(
    pool: jax.Array,
    rows: jax.Array,
    block_tables: jax.Array,
    lengths: jax.Array,
    active: jax.Array,
):
    """The decode step's commit: ``rows`` [n, S, ...] holds one fresh row
    per layer and slot, written at position ``lengths[s]`` of slot ``s``
    where ``active``, dropped at the sentinel where not — the values and
    the places of ``scatter_token_kv_all_layers`` with ``L = 1``. The
    update is indexed by ``(layer, block, offset)`` triples, so its
    window is a row's own trailing axes and nothing else: XLA:TPU then
    leaves the pool in the layout the step's gathers read and updates it
    where it lies. With the window over the layer axis (the extend
    path's form, right for its ``S × L`` rows) it wants that axis minor
    and copies the whole pool there and back on every step (compiled
    for a v5e at gpt2-large's size, PERF.md §6, PR 27)."""
    n, nb, bs = pool.shape[0], pool.shape[1], pool.shape[2]
    with jax.named_scope(names.KV_WRITE):
        bidx, off = _table_index(
            block_tables, lengths[:, None], active[:, None], nb, bs
        )
        return pool.at[jnp.arange(n)[:, None], bidx.T, off.T].set(
            rows.reshape(rows.shape[:2] + pool.shape[3:]), mode="drop"
        )


# Positions one tile of the live-block list holds: a turn of the decode
# step's loop gathers ``TILE_POSITIONS // block_size`` blocks of one slot.
# Large enough that a turn's fixed cost is small beside its reads, small
# enough that rounding each slot's entries up to whole tiles reads little
# that is vacant.
TILE_POSITIONS = 512


class LiveBlocks(NamedTuple):
    """The blocks a decode chunk can read, compacted, one slot a tile:
    entry ``e`` is pool block ``block[e]``, held by slot ``slot[e]``,
    whose first row is logical position ``start[e]`` of that slot. Each
    slot's entries fill whole tiles, the first ``walked`` entries: those
    past its last block (padding to the tile's end) name the sentinel
    block and start past every length, so the position mask alone keeps
    them out of the softmax, as it keeps out the entries past
    ``walked``. ``slot[t · tile]`` is the slot of tile ``t``. ``live``
    counts the real entries. A block two slots share (a cached prefix)
    has one entry per slot."""

    block: jax.Array  # [E] i32
    slot: jax.Array  # [E] i32
    start: jax.Array  # [E] i32
    live: jax.Array  # [] i32
    walked: jax.Array  # [] i32


def _tile_blocks(block_size: int, table_blocks: int) -> int:
    return max(1, min(TILE_POSITIONS // block_size, table_blocks))


def live_block_list(
    block_tables: jax.Array,
    lengths: jax.Array,
    active: jax.Array,
    steps: int,
    num_blocks: int,
    block_size: int,
) -> LiveBlocks:
    """The list :func:`paged_decode_attention` walks, good for the next
    ``steps`` decode steps: every table entry of an ``active`` slot that
    holds a position a step can read out of the pool — positions below
    ``lengths + steps − 1``; the row a step writes reaches its own
    softmax as the fresh key — in slot order, then block order, each
    slot's entries padded up to whole tiles of ``min(TILE_POSITIONS //
    block_size, NB)`` blocks. Cumulative sums and three small scatters on
    the device; ``E`` is every slot's table rounded up to whole tiles. A
    table entry still at the sentinel (past the blocks reserved at
    admission: the request ends before it gets there) is left out."""
    s, nb = block_tables.shape
    tile = _tile_blocks(block_size, nb)
    cap = s * -(-nb // tile) * tile
    reach = jnp.minimum(lengths + steps - 1, nb * block_size)
    need = jnp.where(active, -(-reach // block_size), 0)  # [S] blocks
    col = jnp.arange(nb, dtype=jnp.int32)
    held = (col[None, :] < need[:, None]) & (block_tables < num_blocks)
    padded = -(-held.sum(axis=1, dtype=jnp.int32) // tile) * tile  # [S]
    ends = jnp.cumsum(padded)
    dest = jnp.where(
        held, (ends - padded)[:, None] + jnp.cumsum(held, axis=1) - 1, cap
    ).reshape(-1)

    def compact(values, fill):
        return jnp.full((cap,), fill, jnp.int32).at[dest].set(
            jnp.broadcast_to(values, (s, nb)).reshape(-1), mode="drop"
        )

    return LiveBlocks(
        block=compact(block_tables, num_blocks),
        # the slot whose tiles hold the entry; past the walk, S
        slot=(jnp.arange(cap)[:, None] >= ends[None, :]).sum(
            axis=1, dtype=jnp.int32),
        start=compact(col[None, :] * block_size, nb * block_size),
        live=held.sum(dtype=jnp.int32),
        walked=ends[-1],
    )


def paged_decode_attention(
    q: jax.Array,
    k_row: jax.Array,
    v_row: jax.Array,
    k_pool: jax.Array,
    v_pool: jax.Array,
    layer: int,
    live: LiveBlocks,
    lengths: jax.Array,
    *,
    window: int | None = None,
    k_scale: jax.Array | None = None,
    v_scale: jax.Array | None = None,
    scopes: tuple[str, str] = (names.ATTN_CORE, names.KV_GATHER),
):
    """Attention of a DECODE step: one query per slot over the slot's
    resident positions, read out of the layer-stacked pool through the
    live-block list, plus the slot's own fresh row.

    q [S, Hq, Dh] (float32, not narrowed); k_row/v_row [S, Hkv, Dh] are
    the rows this step writes at ``lengths``, as the pool will hold them
    (for a quantized pool: dequantized again, so a row is attended as it
    will be read back); k_pool/v_pool ``[n, NB, bs, Hkv, Dh]``, or with
    each position's row flat, ``[n, NB, bs, Hkv·Dh]``; k_scale/v_scale
    the quantized pool's ``[n, NB, bs, Hkv]`` side pools, read through
    the same list. Returns ``[S, Hkv, G, Dh]`` float32.

    The loop runs ``walked / tile`` turns — a number the device computes,
    so a chunk with two residents and one with eight are one program. A
    turn gathers one tile of blocks out of layer ``layer`` (a block of a
    flat-rowed pool is one contiguous piece), all of one slot ``s``,
    masks by ``position < lengths[s]`` (and the band ``> lengths[s] − W``
    under a window) and folds the tile into slot ``s``'s running max /
    sum / weighted value, float32; no other slot's state is touched. A
    turn is two matrix products over the tile's rows kept flat
    (``[P, Hkv·Dh]``: the chip tiles an array by its two minor axes, and
    ``[.., Hkv, Dh]`` would be padded there): the scores, of the slot's
    queries laid block-diagonally (``[Hkv·G, Hkv·Dh]``, each query in its
    KV head's lanes) against the tile, and ``P · V``, whose diagonal
    blocks are each query's weighted value. The scores are exact against
    the float32 query: at the highest precision, or, where the tile's K
    is bfloat16, as the sum of three bfloat16 products of the query split
    in three (each product of two bfloat16 numbers is exact in float32).
    The softmax weights are narrowed to V's dtype before ``P · V``, as
    the dense decode paths do; products accumulate in float32. The
    running state starts from the fresh row, which every slot sees, so
    no row of the softmax is ever empty. ``scopes`` names the arithmetic
    and the reads of the pool (a caller with attention kinds of its own
    names them by kind)."""
    core, gather = scopes
    s, hq, dh = q.shape
    hkv = k_row.shape[1]
    g, hd = hq // hkv, hkv * dh
    bs = k_pool.shape[2]
    # E is every slot's table in whole tiles
    tile = _tile_blocks(bs, live.block.shape[0] // s)
    f32, bf16 = jnp.float32, jnp.bfloat16
    hi = lax.Precision.HIGHEST
    k_dtype = k_pool.dtype if k_scale is None else k_row.dtype
    # lane c of a flat row belongs to KV head c // Dh: [Hkv, 1, Hkv·Dh]
    mine = (jnp.arange(hkv)[:, None] == jnp.arange(hd)[None, :] // dh)[
        :, None, :]

    def lanes(x):  # [.., Hkv·G] (head-major) -> [.., G, Hkv·Dh]
        x = x.reshape(x.shape[:-1] + (hkv, g))
        return jnp.repeat(jnp.swapaxes(x, -1, -2), dh, axis=-1)

    with jax.named_scope(core):
        qg = group_query_heads(q.astype(f32), hkv) / jnp.sqrt(f32(dh))
        m0 = jnp.einsum(  # [S, Hkv·G]: the fresh key's score
            "shgd,shd->shg", qg, k_row.astype(f32), precision=hi
        ).reshape(s, hkv * g)
        l0 = jnp.ones_like(m0)
        acc0 = jnp.broadcast_to(  # [S, G·Hkv·Dh]: a slot's G rows, flat
            v_row.reshape(s, 1, hd).astype(f32), (s, g, hd)
        ).reshape(s, g * hd)
        qf = jnp.swapaxes(qg, 1, 2).reshape(s, g, hd)  # [S, G, Hkv·Dh]
        if k_dtype == bf16:  # q = q1 + q2 + q3 exactly, each bfloat16
            q1 = qf.astype(bf16)
            q2 = (qf - q1.astype(f32)).astype(bf16)
            q3 = (qf - q1.astype(f32) - q2.astype(f32)).astype(bf16)
            parts = jnp.stack([q1, q2, q3], axis=1)  # [S, 3, G, Hkv·Dh]
        else:
            parts = qf[:, None]

    def read(pool, scales, ids):
        with jax.named_scope(gather):
            got = pool.at[layer, ids].get(mode="clip")  # [T, bs, ...]
            if scales is not None:
                got = dequantize_kv(
                    got.reshape(tile, bs, hkv, dh),
                    scales.at[layer, ids].get(mode="clip"),
                    k_row.dtype,
                )
            return got.reshape(tile * bs, hd)

    def turn(t, carry):
        m, l, acc = carry
        at = t * tile
        ids = lax.dynamic_slice(live.block, (at,), (tile,))
        start = lax.dynamic_slice(live.start, (at,), (tile,))
        slot = lax.dynamic_index_in_dim(live.slot, at, keepdims=False)
        kt = read(k_pool, k_scale, ids)  # [P, Hkv·Dh]
        vt = read(v_pool, v_scale, ids)

        def row(x):  # slot's row of a per-slot array
            return lax.dynamic_index_in_dim(x, slot, keepdims=False)

        def put(x, new):
            return lax.dynamic_update_index_in_dim(x, new, slot, 0)

        with jax.named_scope(core):
            upto = row(lengths)
            pos = (start[:, None] + jnp.arange(bs)[None, :]).reshape(-1)
            valid = pos < upto
            if window is not None:
                valid &= pos > upto - window
            qs = row(parts)  # [parts, G, Hkv·Dh]
            bd = jnp.where(mine, qs[:, None], 0).reshape(-1, hd)
            scores = jnp.einsum(
                "qc,pc->qp", bd, kt.astype(bd.dtype), precision=hi,
                preferred_element_type=f32,
            ).reshape(qs.shape[0], hkv * g, -1).sum(axis=0)  # [Hkv·G, P]
            scores = jnp.where(valid[None, :], scores, _NEG_INF)
            m_old = row(m)
            m_new = jnp.maximum(m_old, scores.max(axis=1))
            shrink = jnp.exp(m_old - m_new)
            w = jnp.exp(scores - m_new[:, None])  # 0 where masked
            pv = jnp.einsum(
                "qp,pc->qc", w.astype(vt.dtype), vt, precision=hi,
                preferred_element_type=f32,
            ).reshape(hkv, g, hd)
            pv = jnp.where(mine, pv, 0.0).sum(axis=0)  # [G, Hkv·Dh]
            l = put(l, row(l) * shrink + w.sum(axis=1))
            acc = put(acc, (
                row(acc).reshape(g, hd) * lanes(shrink) + pv).reshape(-1))
        return put(m, m_new), l, acc

    m, l, acc = lax.fori_loop(0, live.walked // tile, turn, (m0, l0, acc0))
    with jax.named_scope(core):
        out = (acc.reshape(s, g, hd) / lanes(l)).reshape(s, g, hkv, dh)
        return jnp.swapaxes(out, 1, 2)  # [S, Hkv, G, Dh]


def paged_extend_attention(
    q: jax.Array,
    k_new: jax.Array,
    v_new: jax.Array,
    k_view: jax.Array,
    v_view: jax.Array,
    q_positions: jax.Array,
    prefix_lens: jax.Array,
    suffix_lens: jax.Array,
    window: int | None = None,
):
    """Attention for an EXTEND step: suffix queries over (cached prefix
    read through the block tables) ++ (the suffix's own fresh K/V),
    causal by ABSOLUTE position.

    q [S, L, Hq, Dh] at absolute ``q_positions`` [S, L]
    (= prefix + 0..L-1 per slot); k_new/v_new [S, L, Hkv, Dh] are the
    suffix's keys/values (same positions); k_view/v_view [S, C, Hkv, Dh]
    are the gathered pool views, where view index j IS absolute position
    j. Validity: view keys need ``j < prefix_lens`` STRICTLY — the view
    also covers the suffix's (not yet scattered) positions, which hold
    garbage here and arrive via the fresh half instead; fresh keys need
    the in-suffix causal triangle and ``< suffix_lens`` (pad rows).
    ``window=W`` adds the sliding band ``key_pos > q_pos − W`` on both
    halves (the paged cache addresses absolutely, so the band is a mask,
    not a rolling layout). GQA contracts grouped queries against
    Hkv-width keys directly (``group_query_heads`` — no materialized
    repeat), f32 scores like every attention here."""
    s, l, hq, dh = q.shape
    hkv = k_new.shape[2]
    c = k_view.shape[1]
    qg = group_query_heads(q, hkv)  # [S, L, Hkv, G, Dh]
    scale = 1.0 / jnp.sqrt(jnp.asarray(dh, jnp.float32))

    kcat = jnp.concatenate(
        [k_view.astype(jnp.float32), k_new.astype(jnp.float32)], axis=1
    )  # [S, C+L, Hkv, Dh]
    vcat = jnp.concatenate(
        [v_view.astype(jnp.float32), v_new.astype(jnp.float32)], axis=1
    )
    scores = (
        jnp.einsum(
            "slhgd,skhd->shglk",
            qg.astype(jnp.float32),
            kcat,
            preferred_element_type=jnp.float32,
        )
        * scale
    )  # [S, Hkv, G, L, C+L]

    kpos = jnp.concatenate(
        [
            jnp.broadcast_to(jnp.arange(c)[None, :], (s, c)),
            q_positions,
        ],
        axis=1,
    )  # [S, C+L] absolute key positions
    real = jnp.concatenate(
        [
            jnp.arange(c)[None, :] < prefix_lens[:, None],
            jnp.arange(l)[None, :] < suffix_lens[:, None],
        ],
        axis=1,
    )  # [S, C+L]
    mask = real[:, None, :] & (kpos[:, None, :] <= q_positions[:, :, None])
    if window is not None:
        mask &= kpos[:, None, :] > q_positions[:, :, None] - window
    # [S, L, C+L] → broadcast over (Hkv, G)
    scores = jnp.where(mask[:, None, None, :, :], scores, _NEG_INF)
    w = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum(
        "shglk,skhd->slhgd", w, vcat, preferred_element_type=jnp.float32
    )
    return out.reshape(s, l, hq, dh).astype(q.dtype)


# -- the window layers' rings --------------------------------------------------
#
# A window layer (one whose queries see their last ``W`` keys) keeps no
# blocks of the pool: each slot holds a RING of ``R = ceil(W / bs) + 1``
# blocks of its own, allocated once, ``[n, S*R, bs, Hkv, Dh]`` for the
# stack's ``n`` window layers, ring block ``j`` of slot ``s`` at row
# ``s*R + j``. Logical position ``p`` lives in ring block ``(p // bs) % R``
# at offset ``p % bs``. A query at ``p`` reads keys ``p-W+1 .. p``: their
# blocks are at most ``R - 1`` apart, and the block that ``p`` starts
# overwrites holds positions at least ``R*bs > W`` back, so the ring holds
# every key a step reads. Which position a ring row holds is arithmetic on
# the slot's length, never a label stored with it: a row is read only
# where that arithmetic puts it inside the band, and every such row was
# written by the slot's present request (its prefill wrote the prompt's
# last ``W`` positions), so a refilled slot reads nothing of the last one.


def ring_positions(lengths: jax.Array, ring: int, block_size: int):
    """[S, ring*bs]: the logical position each ring row of each slot holds
    once ``lengths[s]`` positions are written (negative: none yet)."""
    cur = (lengths - 1) // block_size  # the block of the newest position
    j = jnp.arange(ring)
    blk = cur[:, None] - ((cur[:, None] - j[None, :]) % ring)  # [S, R]
    pos = blk[:, :, None] * block_size + jnp.arange(block_size)
    return pos.reshape(lengths.shape[0], ring * block_size)


def ring_decode_attention(
    q: jax.Array,
    k_row: jax.Array,
    v_row: jax.Array,
    ring_k: jax.Array,
    ring_v: jax.Array,
    layer: int,
    lengths: jax.Array,
    *,
    window: int,
    scope: str = names.ATTN_CORE,
):
    """Attention of a DECODE step of a window layer: one query per slot
    over its ring's rows inside the band ``(lengths - W, lengths)`` plus
    the fresh row this step writes at ``lengths``. q [S, Hq, Dh];
    k_row/v_row [S, Hkv, Dh]; ring_k/ring_v ``[n, S*R, bs, Hkv, Dh]``.
    Returns ``[S, Hkv, G, Dh]`` float32. Every slot's whole ring is read
    (``R*bs`` rows: the band and one block), float32 products at the
    highest precision."""
    s, hq, dh = q.shape
    hkv = k_row.shape[1]
    sr, bs = ring_k.shape[1], ring_k.shape[2]
    ring = sr // s
    f32 = jnp.float32
    hi = lax.Precision.HIGHEST
    with jax.named_scope(scope):
        kr = ring_k[layer].reshape(s, ring * bs, hkv, dh).astype(f32)
        vr = ring_v[layer].reshape(s, ring * bs, hkv, dh).astype(f32)
        pos = ring_positions(lengths, ring, bs)
        upto = lengths[:, None]
        valid = (pos >= 0) & (pos < upto) & (pos > upto - window)
        qg = group_query_heads(q.astype(f32), hkv) / jnp.sqrt(f32(dh))
        scores = jnp.einsum("shgd,sphd->shgp", qg, kr, precision=hi)
        scores = jnp.where(valid[:, None, None, :], scores, _NEG_INF)
        fresh = jnp.einsum(
            "shgd,shd->shg", qg, k_row.astype(f32), precision=hi)
        w = jax.nn.softmax(
            jnp.concatenate([scores, fresh[..., None]], axis=-1), axis=-1)
        out = jnp.einsum("shgp,sphd->shgd", w[..., :-1], vr, precision=hi)
        return out + w[..., -1:] * v_row.astype(f32)[:, :, None, :]


def _ring_index(slot, positions, ring: int, block_size: int, valid, rows: int):
    """``(ring row, offset)`` of ``positions`` of ``slot`` (broadcast
    alike), masked writes routed to the sentinel row ``rows``."""
    row = slot * ring + (positions // block_size) % ring
    return jnp.where(valid, row, rows), positions % block_size


def commit_ring_rows(ring_pool, rows, lengths, active):
    """The decode step's commit to the rings: ``rows`` [n, S, Hkv, Dh],
    one fresh row per window layer and slot, written at position
    ``lengths[s]`` of slot ``s`` where ``active`` (the sentinel drops the
    rest): :func:`commit_token_rows` for the rings."""
    n, sr, bs = ring_pool.shape[0], ring_pool.shape[1], ring_pool.shape[2]
    s = lengths.shape[0]
    with jax.named_scope(names.KV_WRITE):
        idx, off = _ring_index(
            jnp.arange(s), lengths, sr // s, bs, active, sr)
        return ring_pool.at[jnp.arange(n)[:, None], idx[None, :],
                            off[None, :]].set(
            rows.reshape((n, s) + ring_pool.shape[3:]), mode="drop")


def scatter_ring_rows(ring_pool, kvs, slot_of_row, positions, valid, *,
                      ring: int, window: int):
    """A prefill's write to the rings of ``ring`` blocks a slot: ``kvs``
    [n, B, L, Hkv, Dh] holds the rows of logical ``positions`` [B, L] of
    the slots ``slot_of_row`` [B]; of each row's ``valid`` positions only
    the last ``window`` are written (those a later query can read; older
    ones would land on the same ring rows)."""
    n, sr, bs = ring_pool.shape[0], ring_pool.shape[1], ring_pool.shape[2]
    b, l = positions.shape
    last = jnp.max(jnp.where(valid, positions, -1), axis=1, keepdims=True)
    keep = valid & (positions > last - window)
    with jax.named_scope(names.KV_WRITE):
        idx, off = _ring_index(
            slot_of_row[:, None], positions, ring, bs, keep, sr)
        return ring_pool.at[:, idx.reshape(-1), off.reshape(-1)].set(
            kvs.reshape((n, b * l) + kvs.shape[3:]), mode="drop")
