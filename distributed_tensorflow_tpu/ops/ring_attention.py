"""Ring attention: sequence-parallel attention over a mesh axis.

The reference workload has no attention and no sequence dimension
(SURVEY.md §2b "Sequence/context parallel: ABSENT — model is a fixed-784-
feature MLP"), but long-context capability is first-class in this framework:
the mesh design reserves a sequence axis and this module provides the
canonical long-context primitive — blockwise attention with the KV blocks
rotating around the device ring (one ``lax.ppermute`` hop per step), online-
softmax accumulation, O(L_local) memory per device.

Mechanics (flash-attention-style streaming):

- each device holds local blocks q, k, v of shape [B, L/n, H, D] for an
  L-token sequence sharded over the ``seq`` axis of n devices;
- n ring steps: attend local q against the currently-held KV block while a
  ``ppermute`` forwards the block to the ring neighbor; a running
  (max, sum, accumulator) triple makes the streamed softmax exact;
- causal masking uses global positions reconstructed from the ring step and
  the device's axis index, so the sharded result equals dense causal
  attention on the unsharded sequence.

Also here: ``all_to_all_seq_to_heads`` / ``heads_to_seq`` — the
Ulysses-style alternative that reshards sequence↔heads around attention so
each device computes full-sequence attention for a head subset — and
``ring_flash_attention``, the same KV ring with each hop's local attend
running the Pallas flash kernel (``ops/pallas_attention``) and hops
combined by per-row logsumexp, making memory O(block) end to end.

Call these inside ``jax.shard_map`` over the sequence axis.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

from distributed_tensorflow_tpu.ops.collectives import _ring_perm, to_varying

_NEG_INF = -1e30


def _block_scores(q, k, *, scale, mask=None):
    """Pre-softmax scores for one block: q [B,Lq,H,D] x k [B,Lk,H,D] →
    [B,H,Lq,Lk] (f32), with optional mask applied as -inf."""
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32)
    scores = scores * scale
    if mask is not None:
        scores = jnp.where(mask, scores, _NEG_INF)
    return scores


def _rotate_unless_last(kv, step, n, *, axis_name, perm):
    """Forward the KV pair one ring hop — except on the final step, whose
    rotated result the loop would discard (XLA cannot DCE inside a while
    loop, so an unconditional permute would pay one dead cross-device hop
    per attention call). The predicate is device-invariant, so all devices
    agree on whether the collective runs."""
    return lax.cond(
        step < n - 1,
        lambda kv: jax.tree.map(
            lambda x: lax.ppermute(x, axis_name, perm), kv
        ),
        lambda kv: kv,
        kv,
    )


def _window_hops(window: int | None, l_loc: int, n: int) -> int:
    """Ring steps actually needed under a sliding window: local queries
    span [my·L, (my+1)·L); the farthest-back key any of them sees is
    my·L − W + 1, i.e. ceil((W−1)/L) blocks behind — plus the diagonal.
    Hops beyond that hold KV wholly outside every band and never happen:
    THIS is sliding-window SP's traffic win (for W ≪ global L most of the
    ring is skipped), not just masked-out compute."""
    if window is None:
        return n
    return min(n, -(-(window - 1) // l_loc) + 1)


def ring_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    axis_name: str,
    *,
    causal: bool = False,
    window: int | None = None,
    kv_lens: jax.Array | None = None,
) -> jax.Array:
    """Exact attention over a sequence sharded on ``axis_name``.

    q is a local block [B, L_local, Hq, D]; k/v are local blocks with
    ``Hkv ≤ Hq`` heads (grouped-query attention: ONLY the KV heads ride the
    ring — the group factor is reclaimed as cross-device bandwidth, the one
    place GQA's saving matters most; the repeat to Hq happens locally after
    each receive). Returns the local output block [B, L_local, Hq, D] —
    equivalent to ``dense_attention`` (optionally causal / windowed) over
    the full gathered sequence.

    ``window=W`` (requires ``causal``) restricts each query to its last W
    keys; the ring then runs only ``ceil((W−1)/L_local)+1`` hops (see
    :func:`_window_hops`). ``kv_lens`` [B] int32 is the key-padding mask in
    right-padded form, in GLOBAL positions (replicated across the seq
    axis): keys at global position ≥ kv_lens[b] are masked — exactly
    ``dense_attention(kv_lens=...)`` on the gathered sequence.
    """
    if window is not None:
        if not causal:
            raise ValueError("window requires causal=True")
        if window < 1:
            raise ValueError(f"window must be >= 1, got {window}")
    n = lax.axis_size(axis_name)
    my = lax.axis_index(axis_name)
    b, l_loc, h, d = q.shape
    scale = 1.0 / jnp.sqrt(jnp.array(d, jnp.float32))
    perm = _ring_perm(n)
    hops = _window_hops(window, l_loc, n)

    q32 = q.astype(jnp.float32)
    # pvary: the zero-init carries are device-invariant but the loop body
    # makes them device-varying; shard_map's vma typing requires the carry
    # types to match up front. Derived from q (q*0, not fresh constants)
    # so they also inherit any OTHER varying axes — under a 2-D dp×sp
    # shard_map the batch is varying over 'data' and the carries must be
    # too (same pattern as parallel/pipeline.py).
    pvary = partial(to_varying, axis_name=(axis_name,))
    # stop_gradient keeps the init off the AD path (a q*0 cotangent route
    # would put pcast's psum transpose on paths check_vma=False can't
    # type) while preserving q's vma on the zeros.
    zeros = jnp.moveaxis(lax.stop_gradient(q32) * 0, 1, 2)  # [b,h,l_loc,d]
    m = pvary(zeros[..., :1] + _NEG_INF)
    s = pvary(zeros[..., :1])
    o = pvary(zeros)

    q_pos = my * l_loc + jnp.arange(l_loc)  # global positions of local q rows

    def body(step, carry):
        m, s, o, kv = carry
        k_blk, v_blk = kv

        def attend(m, s, o):
            # The block held at `step` originated `step` positions behind us.
            src = (my - step) % n
            # GQA: the block circulated at Hkv heads; repeat locally (a
            # transient — never on the wire).
            k_rep, v_rep = repeat_kv(k_blk, v_blk, h)
            mask = None
            k_pos = src * l_loc + jnp.arange(l_loc)
            if causal:
                diff = q_pos[:, None] - k_pos[None, :]  # [Lq, Lk]
                mask = diff >= 0
                if window is not None:
                    mask &= diff < window
                mask = mask[None, None]  # broadcast over B, H
            if kv_lens is not None:
                valid_k = k_pos[None, :] < kv_lens[:, None]  # [B, Lk]
                valid_k = valid_k[:, None, None, :]  # over H, Lq
                mask = valid_k if mask is None else mask & valid_k
            scores = _block_scores(
                q32, k_rep.astype(jnp.float32), scale=scale, mask=mask
            )
            blk_max = jnp.max(scores, axis=-1, keepdims=True)
            m_new = jnp.maximum(m, blk_max)
            # Guard fully-masked rows (every score -inf): exp(-inf - -inf).
            m_safe = jnp.where(m_new == _NEG_INF, 0.0, m_new)
            corr = jnp.exp(m - m_safe)
            p = jnp.exp(scores - m_safe)
            if mask is not None:
                p = jnp.where(mask, p, 0.0)
            s_new = s * corr + jnp.sum(p, axis=-1, keepdims=True)
            pv = jnp.einsum(
                "bhqk,bkhd->bhqd", p, v_rep.astype(jnp.float32),
                preferred_element_type=jnp.float32,
            )
            return m_new, s_new, o * corr + pv

        if causal:
            # Blocks strictly ahead of every local q row are fully masked:
            # skip their einsums entirely (devices early in the ring would
            # otherwise burn ~half the attention FLOPs on zeroed work).
            src = (my - step) % n
            m, s, o = lax.cond(src > my, lambda m, s, o: (m, s, o), attend, m, s, o)
        else:
            m, s, o = attend(m, s, o)
        kv = _rotate_unless_last(
            (k_blk, v_blk), step, hops, axis_name=axis_name, perm=perm
        )
        return m, s, o, kv

    m, s, o, _ = lax.fori_loop(0, hops, body, (m, s, o, (k, v)))
    out = o / jnp.maximum(s, 1e-30)
    return jnp.einsum("bhqd->bqhd", out).astype(q.dtype)


def ring_flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    axis_name: str,
    *,
    causal: bool = False,
    window: int | None = None,
    kv_lens: jax.Array | None = None,
    block_q: int | None = None,
    block_k: int | None = None,
) -> jax.Array:
    """:func:`ring_attention` with the within-device attend replaced by the
    Pallas flash kernel (``ops/pallas_attention``): the cross-device KV ring
    is unchanged, but each hop's local block-pair runs blockwise in VMEM, so
    per-device memory is O(block) end to end — no [L_local, L_local] score
    matrix either. Exact (not approximate): each hop returns (partial out,
    per-row logsumexp) over its KV chunk and the running result is the
    lse-weighted combination, which telescopes to the full softmax.

    Causal masking decomposes per hop: the KV block held at hop ``step``
    originated ``step`` positions behind this device, so it is entirely in
    the past (plain full attention), the diagonal (standard causal flash —
    offsets coincide), or entirely in the future (skipped; its weight in the
    combine is exactly zero via lse = -inf). Differentiation rides the flash
    kernel's custom VJP — the lse cotangent folds into its delta term.

    ``kv_lens`` [B] int32: key-padding in right-padded form, GLOBAL
    positions (replicated across the seq axis) — same semantics as
    :func:`ring_attention`. Each hop passes the kernel its block-relative
    remainder ``clip(kv_lens − src·L_loc, 0, L_loc)``; a fully-padded hop
    contributes weight exp(lse≈−inf) = 0 in the combine.

    Grouped-query attention: k/v may carry fewer heads (Hkv ≤ Hq). Like
    :func:`ring_attention`, only the Hkv-head blocks ride the ring; the
    flash kernel maps query-head groups onto KV heads via its grid index
    maps, so there is no materialized repeat at all on this path.

    ``window=W`` (requires ``causal``): the ring runs only
    ``ceil((W−1)/L_loc)+1`` statically-unrolled hops (the traffic win —
    out-of-band blocks never move), the diagonal hop runs causal+windowed
    flash, and each past hop runs the kernel with a static position
    ``offset`` of ``step·L_loc`` — the shifted band.
    """
    from distributed_tensorflow_tpu.ops.pallas_attention import (
        flash_attention_with_lse,
    )

    if window is not None and not causal:
        raise ValueError("window requires causal=True")
    n = lax.axis_size(axis_name)
    my = lax.axis_index(axis_name)
    b, l_loc, h, d = q.shape
    perm = _ring_perm(n)
    # The kernel's declared output vma must match ALL axes the inputs vary
    # over — under a 2-D dp×sp shard_map that is {data, seq}, not just the
    # ring axis (jax.typeof reads the tracer's vma; a plain jit gives the
    # empty set plus the ring axis).
    vma = jax.typeof(q).vma | {axis_name}
    kw = dict(block_q=block_q, block_k=block_k, vma=tuple(vma))

    pvary = partial(to_varying, axis_name=(axis_name,))
    # Zero/-inf init carries and skip-branch constants derived from q
    # (stop_gradient(q)*0 — off the AD path) so they inherit q's full vma
    # (see ring_attention above).
    _q0 = lax.stop_gradient(q) * 0
    _zo = lambda dt=jnp.float32: _q0.astype(dt)  # noqa: E731
    _zlse = _q0[..., 0].astype(jnp.float32) + _NEG_INF  # [b, l_loc, h]

    def _hop_lens(src):
        # Block-relative key-padding for the block held this hop (its keys
        # cover global positions [src·L_loc, (src+1)·L_loc)).
        if kv_lens is None:
            return None
        return jnp.clip(kv_lens - src * l_loc, 0, l_loc)

    def _skip(q, kb, vb, lens):
        # Constants, but typed varying to match the flash branches' outputs
        # under check_vma (all lax.switch/cond branches must agree).
        return pvary(_zo(q.dtype)), pvary(_zlse)

    def _combine(o, lse, o_i, lse_i):
        new_lse = jnp.logaddexp(lse, lse_i)
        # Weights sum to exactly 1; fully-masked rows keep lse ~ -inf and
        # contribute 0 (exp of a huge negative), never NaN.
        w_prev = jnp.exp(lse - new_lse)
        w_new = jnp.exp(lse_i - new_lse)
        o = o * w_prev[..., None] + o_i.astype(jnp.float32) * w_new[..., None]
        return o, new_lse

    if window is not None:
        # Statically-unrolled bounded ring: hop count and each hop's kernel
        # offset are compile-time constants (the kernel's masks are static).
        hops = _window_hops(window, l_loc, n)
        o = pvary(_zo())
        lse = pvary(_zlse)
        kv = (k, v)
        for step in range(hops):
            k_blk, v_blk = kv
            src = (my - step) % n
            lens = _hop_lens(src)
            if step == 0:
                # src == my always: the diagonal hop.
                o_i, lse_i = flash_attention_with_lse(
                    q, k_blk, v_blk,
                    causal=True, window=window, kv_lens=lens, **kw,
                )
            else:
                o_i, lse_i = lax.cond(
                    src > my,  # wrapped around: a future block
                    _skip,
                    lambda q, kb, vb, lens, _off=step * l_loc: (
                        flash_attention_with_lse(
                            q, kb, vb,
                            causal=True, window=window, offset=_off,
                            kv_lens=lens, **kw,
                        )
                    ),
                    q, k_blk, v_blk, lens,  # lens=None is an empty pytree
                )
            o, lse = _combine(o, lse, o_i, lse_i)
            if step < hops - 1:
                kv = jax.tree.map(
                    lambda x: lax.ppermute(x, axis_name, perm), kv
                )
        return o.astype(q.dtype)

    o = pvary(_zo())
    lse = pvary(_zlse)

    def _full(q, kb, vb, lens):
        return flash_attention_with_lse(
            q, kb, vb, causal=False, kv_lens=lens, **kw
        )

    def _diag(q, kb, vb, lens):
        return flash_attention_with_lse(
            q, kb, vb, causal=True, kv_lens=lens, **kw
        )

    def body(step, carry):
        o, lse, (k_blk, v_blk) = carry
        src = (my - step) % n
        lens = _hop_lens(src)
        if causal:
            idx = jnp.where(src > my, 2, jnp.where(src == my, 1, 0))
            o_i, lse_i = lax.switch(
                idx, (_full, _diag, _skip), q, k_blk, v_blk, lens
            )
        else:
            o_i, lse_i = _full(q, k_blk, v_blk, lens)
        o, lse = _combine(o, lse, o_i, lse_i)
        kv = _rotate_unless_last(
            (k_blk, v_blk), step, n, axis_name=axis_name, perm=perm
        )
        return o, lse, kv

    o, lse, _ = lax.fori_loop(0, n, body, (o, lse, (k, v)))
    return o.astype(q.dtype)


def group_query_heads(q: jax.Array, num_kv_heads: int) -> jax.Array:
    """[..., Hq, D] → [..., Hkv, G, D]: the NON-materializing side of the
    GQA contract — query head h belongs to KV head ``h // (Hq/Hkv)``,
    exactly the mapping :func:`repeat_kv` expands (and the flash kernel's
    grid index maps implement). Callers that contract grouped queries
    against Hkv-width keys/values (the decode path) go through this helper
    so the mapping lives in one place."""
    *lead, hq, d = q.shape
    if hq % num_kv_heads:
        raise ValueError(
            f"query heads {hq} must be a multiple of KV heads {num_kv_heads}"
        )
    return q.reshape(*lead, num_kv_heads, hq // num_kv_heads, d)


def repeat_kv(k, v, num_q_heads: int):
    """Repeat k/v heads up to ``num_q_heads`` (GQA semantics as one helper
    so the dense reference, the LM's ring/decode paths, and any future
    caller can't silently diverge from the flash kernel's group mapping)."""
    hkv = k.shape[2]
    if hkv == num_q_heads:
        return k, v
    if num_q_heads % hkv:
        raise ValueError(
            f"query heads {num_q_heads} must be a multiple of KV heads {hkv}"
        )
    g = num_q_heads // hkv
    return jnp.repeat(k, g, axis=2), jnp.repeat(v, g, axis=2)


def dense_attention(
    q,
    k,
    v,
    *,
    causal: bool = False,
    window: int | None = None,
    kv_lens: jax.Array | None = None,
) -> jax.Array:
    """Reference dense attention on unsharded [B, L, H, D] (for tests and
    single-device use). ``window=W`` (requires ``causal``) restricts each
    query to its last W keys, self included — the sliding-window mask.
    ``kv_lens`` [B] int32 is the key-padding mask in right-padded form:
    keys at positions ≥ kv_lens[b] are masked out for every query (each
    length must be ≥ 1; queries at padded positions produce well-defined
    garbage — mask them in the loss, e.g. ``GPTLM.loss(lengths=...)``).
    Grouped-query attention: k/v with fewer heads are repeated up to the
    query head count (the semantics the flash kernel implements without the
    materialized repeat)."""
    if window is not None:
        if not causal:
            raise ValueError("window requires causal=True")
        if window < 1:
            raise ValueError(f"window must be >= 1, got {window}")
    k, v = repeat_kv(k, v, q.shape[2])
    d = q.shape[-1]
    scale = 1.0 / jnp.sqrt(jnp.array(d, jnp.float32))
    scores = jnp.einsum(
        "bqhd,bkhd->bhqk", q.astype(jnp.float32), k.astype(jnp.float32),
        preferred_element_type=jnp.float32,
    ) * scale
    if causal:
        l_q, l_k = scores.shape[-2], scores.shape[-1]
        diff = jnp.arange(l_q)[:, None] - jnp.arange(l_k)[None, :]
        mask = diff >= 0
        if window is not None:
            mask &= diff < window
        scores = jnp.where(mask[None, None], scores, _NEG_INF)
    if kv_lens is not None:
        l_k = scores.shape[-1]
        valid_k = jnp.arange(l_k)[None, :] < kv_lens[:, None]  # [B, Lk]
        scores = jnp.where(valid_k[:, None, None, :], scores, _NEG_INF)
    w = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum(
        "bhqk,bkhd->bhqd", w, v.astype(jnp.float32),
        preferred_element_type=jnp.float32,
    )
    return jnp.einsum("bhqd->bqhd", out).astype(q.dtype)


# ---------------------------------------------------------------------------
# Ulysses-style alternative: all-to-all resharding seq <-> heads
# ---------------------------------------------------------------------------


def all_to_all_seq_to_heads(x: jax.Array, axis_name: str) -> jax.Array:
    """[B, L/n, H, D] seq-sharded → [B, L, H/n, D] head-sharded: each device
    trades sequence shards for a head subset (one all-to-all), after which
    plain full-sequence attention runs locally per head group."""
    n = lax.axis_size(axis_name)
    b, l_loc, h, d = x.shape
    x = x.reshape(b, l_loc, n, h // n, d)
    x = lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1, tiled=False)
    # all_to_all with these axes yields [B, n, l_loc, h//n, d] → merge seq.
    return x.reshape(b, n * l_loc, h // n, d)


def all_to_all_heads_to_seq(x: jax.Array, axis_name: str) -> jax.Array:
    """Inverse of :func:`all_to_all_seq_to_heads`."""
    n = lax.axis_size(axis_name)
    b, l, h_loc, d = x.shape
    x = x.reshape(b, n, l // n, h_loc, d)
    x = lax.all_to_all(x, axis_name, split_axis=1, concat_axis=3, tiled=False)
    # yields [B, l//n, h_loc, n, d]; the received axis (3) indexes the head
    # *group*, which is the major part of the head index — transpose it in
    # front of h_loc before merging, or heads come back interleaved.
    x = jnp.einsum("blhnd->blnhd", x)
    return x.reshape(b, l // n, n * h_loc, d)


def ulysses_attention(
    q, k, v, axis_name: str, *, causal: bool = False,
    window: int | None = None,
):
    """Sequence-parallel attention via all-to-all (Ulysses): reshard to
    head-parallel, run dense attention on the full sequence locally, reshard
    back. Requires H divisible by the axis size — under GQA, BOTH head
    counts (k/v trade their own Hkv heads, and the n-chunking of q heads
    aligns with the kv chunks exactly when n | Hkv: local q head j maps to
    local kv head j//g, which is ``repeat_kv``'s convention, so the local
    dense attention needs no cross-device head traffic). ``window`` is the
    sliding-window mask, applied by the full-sequence local attention (no
    hop-skipping to reason about — the ring's banding trick has no analog
    here; Ulysses moves heads, not KV blocks)."""
    q2 = all_to_all_seq_to_heads(q, axis_name)
    k2 = all_to_all_seq_to_heads(k, axis_name)
    v2 = all_to_all_seq_to_heads(v, axis_name)
    out = dense_attention(q2, k2, v2, causal=causal, window=window)
    return all_to_all_heads_to_seq(out, axis_name)
