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
blocks at a time, and folds each tile into a per-slot running softmax
(``paged_decode_attention``), so that what a step reads follows what
the pool holds and not ``slots × max_blocks``. Correctness lives in the
masks, not the layout, on both paths.

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
# step's loop gathers ``TILE_POSITIONS // block_size`` blocks. Large
# enough that a turn's fixed cost is small beside its reads, small enough
# that rounding the list up to whole tiles reads little that is vacant.
TILE_POSITIONS = 512


class LiveBlocks(NamedTuple):
    """The blocks a decode chunk can read, compacted: entry ``e < live``
    is pool block ``block[e]``, held by slot ``slot[e]``, whose first
    row is logical position ``start[e]`` of that slot. Entries past
    ``live`` name the sentinel block and start past every length, so the
    position mask alone keeps them out of every softmax. A block two
    slots share (a cached prefix) has one entry per slot."""

    block: jax.Array  # [E] i32
    slot: jax.Array  # [E] i32
    start: jax.Array  # [E] i32
    live: jax.Array  # [] i32


def _tile_blocks(block_size: int, entries: int) -> int:
    return max(1, min(TILE_POSITIONS // block_size, entries))


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
    softmax as the fresh key — in slot order, then block order. One
    cumulative sum and three small scatters on the device; ``E`` is the
    tables' size rounded up to whole tiles. A table entry still at the
    sentinel (past the blocks reserved at admission: the request ends
    before it gets there) is left out."""
    s, nb = block_tables.shape
    tile = _tile_blocks(block_size, s * nb)
    cap = -(-s * nb // tile) * tile
    reach = jnp.minimum(lengths + steps - 1, nb * block_size)
    need = jnp.where(active, -(-reach // block_size), 0)  # [S] blocks
    col = jnp.arange(nb, dtype=jnp.int32)
    held = (col[None, :] < need[:, None]) & (block_tables < num_blocks)
    held = held.reshape(-1)
    dest = jnp.where(held, jnp.cumsum(held) - 1, cap)

    def compact(values, fill):
        return jnp.full((cap,), fill, jnp.int32).at[dest].set(
            jnp.broadcast_to(values, (s, nb)).reshape(-1), mode="drop"
        )

    return LiveBlocks(
        block=compact(block_tables, num_blocks),
        slot=compact(jnp.arange(s, dtype=jnp.int32)[:, None], 0),
        start=compact(col[None, :] * block_size, nb * block_size),
        live=held.sum(dtype=jnp.int32),
    )


def blocks_walked(live: LiveBlocks, block_size: int) -> jax.Array:
    """List entries one step of one layer reads: ``live.live`` rounded up
    to whole tiles."""
    tile = _tile_blocks(block_size, live.block.shape[0])
    return -(-live.live // tile) * tile  # the loop's turns × a tile


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

    The loop runs ``ceil(live / tile)`` turns — a number the device
    computes, so a chunk with two residents and one with eight are one
    program. A turn gathers one tile of blocks out of layer ``layer``
    (a block of a flat-rowed pool is one contiguous piece), scores each
    position against the query of the block's slot, masks by
    ``position < lengths[slot]`` (and the band ``> lengths[slot] − W``
    under a window) and folds the tile into the slots' running
    max / sum / weighted value, float32 throughout; K and V are promoted
    from the pool's dtype where they are multiplied and the softmax
    weights narrowed to it, as the dense decode paths do. The running
    state starts from the fresh row, which every slot sees, so no row of
    the softmax is ever empty. Everything keeps a row flat
    (``[.., Hkv·Dh]``) and sums or spreads over a head's ``Dh`` lanes by
    a product with the 0/1 head-membership matrix: the chip tiles an
    array by its two minor axes, and ``[.., Hkv, Dh]`` would be padded
    there."""
    s, hq, dh = q.shape
    hkv = k_row.shape[1]
    g, hd = hq // hkv, hkv * dh
    bs = k_pool.shape[2]
    tile = _tile_blocks(bs, live.block.shape[0])
    f32 = jnp.float32
    scale = 1.0 / jnp.sqrt(jnp.asarray(dh, f32))
    # head-membership: lane c of a flat row belongs to KV head c // Dh
    member = (jnp.arange(hd)[:, None] // dh == jnp.arange(hkv)[None, :])

    def per_head(x):  # [.., Hkv·Dh] -> [.., Hkv], summed over Dh
        return jnp.einsum(
            "...c,ch->...h", x, member.astype(f32),
            precision=lax.Precision.HIGHEST, preferred_element_type=f32,
        )

    def per_lane(x):  # [.., Hkv] -> [.., Hkv·Dh], each value Dh times
        return jnp.einsum(
            "...h,ch->...c", x, member.astype(x.dtype),
            precision=lax.Precision.HIGHEST, preferred_element_type=x.dtype,
        )

    with jax.named_scope(names.ATTN_CORE):
        # [S, G, Hkv·Dh]: group member g of every KV head, rows flat
        qf = jnp.swapaxes(group_query_heads(q, hkv), 1, 2).reshape(s, g, hd)
        qf = qf.astype(f32) * scale
        kf = k_row.reshape(s, 1, hd).astype(f32)
        m0 = per_head(qf * kf)  # [S, G, Hkv]: the fresh key's score
        l0 = jnp.ones_like(m0)
        acc0 = jnp.broadcast_to(
            v_row.reshape(s, 1, hd).astype(f32), (s, g, hd)
        )

    def read(pool, scales, ids):
        with jax.named_scope(names.KV_GATHER):
            got = pool.at[layer, ids].get(mode="clip")  # [T, bs, ...]
            if scales is not None:
                got = dequantize_kv(
                    got.reshape(tile, bs, hkv, dh),
                    scales.at[layer, ids].get(mode="clip"),
                    k_row.dtype,
                )
            return got.reshape(tile, bs, hd)

    def turn(t, carry):
        m, l, acc = carry
        at = (t * tile,)
        ids = lax.dynamic_slice(live.block, at, (tile,))
        slot = lax.dynamic_slice(live.slot, at, (tile,))
        start = lax.dynamic_slice(live.start, at, (tile,))
        kt = read(k_pool, k_scale, ids)
        vt = read(v_pool, v_scale, ids)
        with jax.named_scope(names.ATTN_CORE):
            upto = lengths[slot]  # [T]
            pos = start[:, None] + jnp.arange(bs)[None, :]  # [T, bs]
            valid = pos < upto[:, None]
            if window is not None:
                valid &= pos > upto[:, None] - window
            valid = valid[:, :, None, None]
            scores = per_head(
                kt.astype(f32)[:, :, None, :] * qf[slot][:, None]
            )  # [T, bs, G, Hkv]
            scores = jnp.where(valid, scores, _NEG_INF)
            mine = slot[None, :] == jnp.arange(s)[:, None]  # [S, T]
            mine = mine[:, :, None, None]
            m_new = jnp.maximum(m, jnp.max(
                jnp.where(mine, scores.max(axis=1)[None], _NEG_INF), axis=1
            ))
            shrink = jnp.exp(m - m_new)
            w = jnp.where(
                valid, jnp.exp(scores - m_new[slot][:, None]), 0.0
            )  # [T, bs, G, Hkv]
            wv = (
                per_lane(w.astype(vt.dtype)).astype(f32)
                * vt.astype(f32)[:, :, None, :]
            ).sum(axis=1)  # [T, G, Hkv·Dh]: each block's weighted value
            l = l * shrink + jnp.where(mine, w.sum(axis=1)[None], 0.0).sum(1)
            acc = acc * per_lane(shrink) + jnp.where(
                mine, wv[None], 0.0
            ).sum(axis=1)
        return m_new, l, acc

    m, l, acc = lax.fori_loop(
        0, blocks_walked(live, bs) // tile, turn, (m0, l0, acc0)
    )
    with jax.named_scope(names.ATTN_CORE):
        out = (acc / per_lane(l)).reshape(s, g, hkv, dh)
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
