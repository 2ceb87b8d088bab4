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
``pool[table[s, p // bs], p % bs]``. Attention reads K/V through the
table with a GATHER into a per-slot contiguous view (the vLLM dense
path): correctness lives in the masks, not the layout, so the flash
kernel is off the critical path — a contiguous gathered view feeds the
same dense math the slab cache used, and a Pallas kernel that walks the
table natively can slot in later without touching the engine.

Out-of-range discipline: unused table entries and masked (pad /
non-admitted) writes are routed to a sentinel block index ``num_blocks``
(one PAST the pool) and dropped via scatter ``mode="drop"`` — never
``-1``, which JAX index arithmetic would wrap to the pool's last block
and silently corrupt it. Gathers of garbage table entries are fine:
their positions are masked out of every softmax by the validity masks
below (same stale-bytes-unreachable stance as ``SlotKVCache``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from distributed_tensorflow_tpu.observability import names
from distributed_tensorflow_tpu.ops.ring_attention import group_query_heads

_NEG_INF = -1e30


def gather_block_view(
    pool: jax.Array, block_tables: jax.Array, layer: int | None = None
):
    """One layer's per-slot contiguous K (or V) view through the block
    tables: ``[num_blocks, bs, Hkv, Dh]`` + ``[S, NB]`` →
    ``[S, NB*bs, Hkv, Dh]``, where view position ``p`` is logical
    position ``p`` of the slot. With ``layer`` given, ``pool`` is the
    layer-STACKED pool ``[n, num_blocks, bs, Hkv, Dh]`` and the view is
    read through ``(layer, block_tables)`` in one gather, so no layer's
    slice of the pool is materialized on the way (the decode step, which
    must leave the stacked pool where it lies). Unused table entries
    gather garbage that the caller's validity mask must keep out of the
    softmax."""
    s, tabs = block_tables.shape
    with jax.named_scope(names.KV_GATHER):
        if layer is None:
            view = jnp.take(pool, block_tables, axis=0)  # [S,NB,bs,H,D]
        else:
            view = pool[layer, block_tables]
        return view.reshape(s, tabs * view.shape[2], *view.shape[3:])


def scatter_token_kv(
    pool_layer: jax.Array,
    kv: jax.Array,
    block_tables: jax.Array,
    positions: jax.Array,
    valid: jax.Array,
):
    """Write per-slot K (or V) rows into one layer's pool through the
    block tables. ``kv`` [S, L, Hkv, Dh] holds the rows for logical
    ``positions`` [S, L] (absolute per slot); ``valid`` [S, L] masks pad
    positions and non-admitted slots — their writes drop at the sentinel
    block. Distinct live slots never map the same WRITABLE block (the
    allocator shares only immutable full prompt blocks, and writes land
    past the prompt), so the scatter rows are disjoint by construction.

    Delegates to :func:`scatter_token_kv_all_layers` with a 1-layer pool
    so the sentinel/index arithmetic lives in exactly one place."""
    return scatter_token_kv_all_layers(
        pool_layer[None], kv[None], block_tables, positions, valid
    )[0]


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
    """All-layer variant (the extend path scatters once after its layer
    scan): ``pool`` [n, NB, bs, Hkv, Dh], ``kvs`` [n, S, L, Hkv, Dh]."""
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
