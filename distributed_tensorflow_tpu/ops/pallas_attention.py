"""Pallas TPU kernel: blockwise fused (flash) attention, forward + backward.

The reference has no attention at all (SURVEY.md §2b: the model is a fixed
784-feature MLP) — long-context support is one of this framework's
first-class upgrades. ``ops/ring_attention.py`` supplies the cross-device
algorithms (ring / Ulysses); this module supplies the *within-device* hot
op: exact softmax attention computed block-by-block in VMEM so the [L, L]
score matrix is never materialized in HBM.

Forward (online softmax, one grid step per (batch·head, q-block, k-block)):

    s    = q·kᵀ·scale                     (bq, bk) f32 on the MXU
    m'   = max(m, rowmax(s)); corr = exp(m - m')
    p    = exp(s - m')
    l    = l·corr + rowsum(p)
    acc  = acc·corr + p·v
    out  = acc / l;  lse = m + log(l)     (written at the last k-block)

Backward re-derives p from the saved row-wise log-sum-exp instead of
storing it:

    p  = exp(s - lse)                      (exact, no second softmax pass)
    dv += pᵀ·do
    ds = p·(do·vᵀ - delta)·scale           delta = rowsum(do·out)
    dk += dsᵀ·q
    dq += ds·k

The default backward (round 13) is ONE fused k-major kernel: a single
pass over KV blocks computes s/p/dp/ds once and produces all three
gradients — dk/dv accumulate in VMEM scratch exactly as before, while
each grid step writes its dq *partial* to its own block of a
[nk, B·H, L, D] output that one XLA sum reduces afterwards (TPU grids
may only revisit output blocks in consecutive iterations, so cross-k
in-kernel dq accumulation is illegal; the partial-sum layout is the
same one jax's splash-attention fused backward uses). ``fused=False``
restores the classic two-kernel split (q-major dq kernel + k-major dkv
kernel), which computes the score-space work twice — kept as the escape
hatch and the parity oracle for the fused path.

Accumulators live in VMEM scratch that persists across the innermost grid
dimension (TPU grids run sequentially, minor-most fastest); causal masking
skips fully-masked blocks entirely via ``pl.when`` — past-diagonal work is
never issued, so causal runs ~2× faster than masked-dense. All per-row
statistics (m, l, lse, delta) are carried as [rows, 1] 2-D columns — 1-D
vectors trip Mosaic relayout bugs (CLAUDE.md).

Layout: public API takes [B, L, H, D] (matching ``dense_attention`` /
``ring_attention``); kernels run on [B·H, L, D]. Precision: every product
runs in its operands' type with float32 accumulation (bfloat16 q, k, v:
one MXU pass, and o, dq, dk, dv leave in bfloat16; float32 operands:
float32 products), p and ds are cast to that type for their products, and
the softmax's statistics (m, l, lse, delta), ``exp``, the accumulators and
the dq partials are float32 whatever comes in. ``interpret=None``
auto-selects the Pallas interpreter off-TPU, the Mosaic compiler on TPU
(same convention as ops/pallas_mlp.py).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from distributed_tensorflow_tpu.observability import names
from distributed_tensorflow_tpu.ops.pallas_mode import resolve_interpret

_NEG_INF = -1e30

# Measured dense/flash crossover (tools/attention_bench.py, two-point
# timing — docs/benchmarks/attention_tpu.md): below L≈1024 XLA's fused
# dense attention beats the Pallas kernel even at its best block size
# (L=512 fwd+bwd: dense 0.013 ms vs flash 0.097; L=1024 is the first
# length where flash's fwd+bwd wins, 1.34x), above it the gap widens
# (4.7x at 2048). The ONE shared default for every model's
# ``flash_min_len`` knob — re-measure with the tool before changing it.
FLASH_MIN_LEN = 1024


def _pick_block(l: int, requested: int | None) -> int:
    """Largest MXU-friendly block that divides ``l`` (512 below L=4096,
    1024 from there up), or ``l`` itself for short/odd sequences (Mosaic pads
    non-tile-multiple shapes). A long sequence with no small divisor would
    silently degenerate to one whole-sequence block — an O(L²) VMEM score
    tile, exactly what this kernel exists to avoid — so that case is an
    error, not a fallback.

    The caps are MEASURED, not guessed (tools/attention_bench.py with the
    round-4 two-point discipline — the round-3 cap of 128 cost flash its
    wins exactly where users run it, VERDICT round-3 weak #3): fwd+bwd
    per call at L=2048 is 3.53 ms at block 128 vs 0.87 ms at block 512
    (vs dense 3.38 ms) — the 128-block grid is 16x more grid steps, each
    too small to keep the MXU busy while Mosaic's pipeline turns over.
    Block 1024 loses slightly at L=2048 (0.96 vs 0.89 ms) but wins from
    L=4096 up (2.89 vs 3.62 ms; L=8192 11.0 vs 14.6, and windowed
    likewise — W=1024: 4.97 vs 6.36), hence the length-dependent cap;
    2048 fails to compile (VMEM). A 1024² f32 score tile is 4 MB —
    fine."""
    if requested is not None:
        if l % requested:
            raise ValueError(f"block {requested} must divide sequence {l}")
        return requested
    cands = (1024, 512, 256, 128, 64, 32, 16, 8)
    if l < 4096:
        cands = cands[1:]
    for cand in cands:
        if l % cand == 0:
            return cand
    if l > 512:
        raise ValueError(
            f"sequence length {l} has no power-of-two block divisor (tried"
            f" down from {cands[0]}); pad the sequence or pass an explicit"
            f" block_q/block_k that divides it"
        )
    return l


def _causal_mask(iq, ik, bq, bk, window=None, offset=0):
    """[bq, bk] bool: global q position >= global k position (and, with
    ``window=W``, within the last W keys). ``offset`` shifts every q
    position forward — the ring composition's past hops, where the held KV
    block originated ``offset`` positions behind the local queries. 2-D
    broadcasted_iota — plain ``jnp.arange`` is 1-D and TPU rejects it."""
    q_pos = iq * bq + lax.broadcasted_iota(jnp.int32, (bq, bk), 0) + offset
    k_pos = ik * bk + lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    diff = q_pos - k_pos
    mask = diff >= 0
    if window is not None:
        mask &= diff < window
    return mask


def _block_needed(iq, ik, bq, bk, window, offset=0):
    """Whether any (q, k) pair in this block pair survives the causal(+
    window) mask: max diff >= 0 (not fully above the diagonal) and, with a
    window, min diff < W (not fully fallen out of it)."""
    needed = (iq + 1) * bq - 1 + offset >= ik * bk
    if window is not None:
        needed &= iq * bq + offset - (ik + 1) * bk + 1 < window
    return needed


def _kvlen_valid(ik, bq, bk, kvlen_ref, by_row: bool):
    """[bq, bk] bool key-padding validity for one score block: keys at
    global position >= this grid row's kv_len are invalid — one definition
    shared by the forward and both backward kernels.

    Two static layouts (``by_row``): on Mosaic the whole [rows, 1] int32
    array sits in SMEM (full-array blocks are the only sub-(8,128) shapes
    the TPU lowering accepts) and the row is selected by grid position; the
    CPU interpreter instead gets a per-row (1, 1) block (it cannot lower
    ``program_id`` through the whole-array path)."""
    kl = kvlen_ref[pl.program_id(0), 0] if by_row else kvlen_ref[0, 0]
    k_pos = ik * bk + lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    return k_pos < kl


def _use_banding(window, l) -> bool:
    """Banded (clamped) index maps defeat Mosaic's affine prefetch analysis,
    which costs more than the saved DMA until the band is much smaller than
    the row: measured on v5e (block 512, W=1024), banding LOSES below
    L≈4·W (18.8 vs 11.4 ms at L=2048) and wins above (13.2 vs 15.6 ms at
    L=8192, 17.1 vs 29.9 at 16384 — docs/performance.md). Below the
    crossover the plain affine walk with in-kernel masking is used; the
    math is identical either way."""
    return window is not None and 4 * window <= l


def _kv_row(hq: int, hkv: int):
    """Grid-row mapping for grouped-query attention: q grid row
    ``b = batch·Hq + hq_head`` reads the KV row of its head *group*
    (``Hq/Hkv`` query heads share one KV head). Identity when Hq == Hkv."""
    g = hq // hkv
    if g == 1:
        return lambda b: b
    return lambda b: (b // hq) * hkv + (b % hq) // g


def _banded_k_index(window, bq, bk, row=lambda b: b):
    """Index-map factory clamping the k-block index into the causal window
    band of its q block (and routing through the GQA ``row`` mapping).
    Out-of-band grid steps re-reference an in-band (already-resident)
    block, so they cost no DMA — their compute is skipped by
    ``_block_needed`` anyway. Purely an index-map change: the kernels
    never see the clamped index (they recompute the true one from
    ``pl.program_id``)."""

    def index_map(b, iq, ik):
        lo = jnp.maximum((iq * bq - window + 1) // bk, 0)
        hi = ((iq + 1) * bq - 1) // bk
        return (row(b), jnp.clip(ik, lo, hi), 0)

    return index_map


def _banded_q_index(window, bq, bk, nq):
    """Transposed band for the k-major (dkv) kernel: clamp the q-block
    index into [first q attending this k, last q within the window]."""

    def index_map(b, ik, iq):
        lo = (ik * bk) // bq
        hi = jnp.minimum(((ik + 1) * bk - 2 + window) // bq, nq - 1)
        return (b, jnp.clip(iq, lo, hi), 0)

    return index_map


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _fwd_kernel(
    q_ref, k_ref, v_ref, *rest,
    scale: float, causal: bool, window: int | None, nk: int, has_lens: bool,
    offset: int = 0, lens_by_row: bool = True,
):
    if has_lens:
        kvlen_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr = rest
    else:
        o_ref, lse_ref, m_scr, l_scr, acc_scr = rest
        kvlen_ref = None
    iq = pl.program_id(1)
    ik = pl.program_id(2)
    bq = q_ref.shape[1]
    bk = k_ref.shape[1]

    @pl.when(ik == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    def _accumulate():
        # Matmuls run in the input dtype with f32 accumulation — one MXU
        # pass for bf16 inputs, matching XLA's DEFAULT precision. Softmax
        # statistics stay f32 regardless.
        q = q_ref[0]
        k = k_ref[0]
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
        if causal:
            s = jnp.where(_causal_mask(iq, ik, bq, bk, window, offset), s, _NEG_INF)
        if has_lens:
            s = jnp.where(_kvlen_valid(ik, bq, bk, kvlen_ref, lens_by_row), s, _NEG_INF)
        m_prev = m_scr[:]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        # A still-empty row (everything masked so far) has m_new == -inf;
        # exp(s - -inf) would be exp(+inf). Causal rows always include the
        # diagonal eventually, but guard the not-yet-reached iterations.
        m_safe = jnp.where(m_new == _NEG_INF, 0.0, m_new)
        corr = jnp.exp(m_prev - m_safe)
        p = jnp.exp(s - m_safe)
        l_scr[:] = l_scr[:] * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[:] = acc_scr[:] * corr + jnp.dot(
            p.astype(v_ref.dtype), v_ref[0], preferred_element_type=jnp.float32
        )
        m_scr[:] = m_new

    if causal:
        # Skip blocks whose every score is masked: strictly above the
        # diagonal, or (windowed) entirely fallen out of the window.
        pl.when(_block_needed(iq, ik, bq, bk, window, offset))(_accumulate)
    else:
        _accumulate()

    @pl.when(ik == nk - 1)
    def _finalize():
        l = jnp.maximum(l_scr[:], 1e-30)
        o_ref[0] = (acc_scr[:] / l).astype(o_ref.dtype)
        lse_ref[0] = m_scr[:] + jnp.log(l)


def _fwd_call(
    q, k, v, kv_lens, *, causal, window, offset, bq, bk, scale, interpret,
    vma, hq, hkv
):
    """q [B·Hq, L, D], k [B·Hkv, L, D], v [B·Hkv, L, Dv] → (out [B·Hq, L,
    Dv], lse [B·Hq, L, 1]); the values' head size is free of the keys'.
    ``kv_lens`` is None or [B] int32 (right-padded key-padding; expanded
    per query head here). ``vma`` marks the outputs as varying over those
    mesh axes — required under a ``check_vma=True`` shard_map (the ring
    composition)."""
    sds = partial(jax.ShapeDtypeStruct, vma=vma) if vma else jax.ShapeDtypeStruct
    bh, l, d = q.shape
    dv = v.shape[-1]
    nq, nk = l // bq, l // bk
    row = _kv_row(hq, hkv)
    kmap = (
        _banded_k_index(window, bq, bk, row)
        if offset == 0 and _use_banding(window, l)
        else (lambda b, iq, ik: (row(b), ik, 0))
    )
    has_lens = kv_lens is not None
    lens_spec = _lens_blockspec(interpret)
    in_specs = [
        pl.BlockSpec((1, bq, d), lambda b, iq, ik: (b, iq, 0)),
        pl.BlockSpec((1, bk, d), kmap),
        pl.BlockSpec((1, bk, dv), kmap),
    ]
    inputs = [q, k, v]
    if has_lens:
        in_specs.append(lens_spec)
        inputs.append(jnp.repeat(kv_lens.astype(jnp.int32), hq)[:, None])
    return pl.pallas_call(
        partial(
            _fwd_kernel,
            scale=scale, causal=causal, window=window, nk=nk,
            has_lens=has_lens, offset=offset, lens_by_row=not interpret,
        ),
        grid=(bh, nq, nk),
        in_specs=in_specs,
        out_specs=(
            pl.BlockSpec((1, bq, dv), lambda b, iq, ik: (b, iq, 0)),
            pl.BlockSpec((1, bq, 1), lambda b, iq, ik: (b, iq, 0)),
        ),
        out_shape=(
            sds((bh, l, dv), q.dtype),
            sds((bh, l, 1), jnp.float32),
        ),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, dv), jnp.float32),
        ],
        interpret=interpret,
        name=names.KERNEL_FLASH_FWD,
    )(*inputs)


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------


def _dq_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *rest,
    scale: float, causal: bool, window: int | None, nk: int, has_lens: bool,
    offset: int = 0, lens_by_row: bool = True,
):
    if has_lens:
        kvlen_ref, dq_ref, dq_scr = rest
    else:
        dq_ref, dq_scr = rest
        kvlen_ref = None
    iq = pl.program_id(1)
    ik = pl.program_id(2)
    bq = q_ref.shape[1]
    bk = k_ref.shape[1]

    @pl.when(ik == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    def _accumulate():
        q = q_ref[0]
        k = k_ref[0]
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
        # p must be masked EXPLICITLY here, not via -1e30 underflow: a
        # fully-masked row (offset past the window, or window+padding)
        # saved lse ~= -1e30 too, so exp(s - lse) would be exp(0) = 1 and
        # the row would inject garbage into every gradient.
        mask = None
        if causal:
            mask = _causal_mask(iq, ik, bq, bk, window, offset)
        if has_lens:
            lm = _kvlen_valid(ik, bq, bk, kvlen_ref, lens_by_row)
            mask = lm if mask is None else mask & lm
        if mask is not None:
            s = jnp.where(mask, s, _NEG_INF)
        p = jnp.exp(s - lse_ref[0])
        if mask is not None:
            p = jnp.where(mask, p, 0.0)
        dp = jnp.dot(do_ref[0], v_ref[0].T, preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[0]) * scale
        dq_scr[:] += jnp.dot(
            ds.astype(k.dtype), k, preferred_element_type=jnp.float32
        )

    if causal:
        pl.when(_block_needed(iq, ik, bq, bk, window, offset))(_accumulate)
    else:
        _accumulate()

    @pl.when(ik == nk - 1)
    def _finalize():
        dq_ref[0] = dq_scr[:].astype(dq_ref.dtype)


def _dkv_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *rest,
    scale: float, causal: bool, window: int | None, nq: int, total: int,
    has_lens: bool, offset: int = 0, lens_by_row: bool = True,
):
    if has_lens:
        kvlen_ref, dk_ref, dv_ref, dk_scr, dv_scr = rest
    else:
        dk_ref, dv_ref, dk_scr, dv_scr = rest
        kvlen_ref = None
    ik = pl.program_id(1)
    j = pl.program_id(2)
    iq = j % nq  # positional q block; j // nq is the GQA head in the group
    bq = q_ref.shape[1]
    bk = k_ref.shape[1]

    @pl.when(j == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    def _accumulate():
        q = q_ref[0]
        k = k_ref[0]
        do = do_ref[0]
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
        # Explicit p masking — see _dq_kernel (fully-masked rows saved
        # lse ~= -1e30; underflow alone would give p = 1 there).
        mask = None
        if causal:
            mask = _causal_mask(iq, ik, bq, bk, window, offset)
        if has_lens:
            lm = _kvlen_valid(ik, bq, bk, kvlen_ref, lens_by_row)
            mask = lm if mask is None else mask & lm
        if mask is not None:
            s = jnp.where(mask, s, _NEG_INF)
        p = jnp.exp(s - lse_ref[0])
        if mask is not None:
            p = jnp.where(mask, p, 0.0)
        dv_scr[:] += jnp.dot(
            p.astype(do.dtype).T, do, preferred_element_type=jnp.float32
        )
        dp = jnp.dot(do, v_ref[0].T, preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[0]) * scale
        dk_scr[:] += jnp.dot(
            ds.astype(q.dtype).T, q, preferred_element_type=jnp.float32
        )

    if causal:
        pl.when(_block_needed(iq, ik, bq, bk, window, offset))(_accumulate)
    else:
        _accumulate()

    @pl.when(j == total - 1)
    def _finalize():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


def _fused_bwd_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *rest,
    scale: float, causal: bool, window: int | None, nq: int, total: int,
    has_lens: bool, offset: int = 0, lens_by_row: bool = True,
):
    """One pass over the KV stream producing ALL THREE gradients: the
    k-major ``_dkv_kernel`` grid, with the score-space work (s, p, dp,
    ds) computed ONCE per block pair — the two-kernel split computes it
    twice. dk/dv accumulate in VMEM scratch exactly as in
    ``_dkv_kernel``; dq cannot accumulate the same way (its q-block is
    revisited at every non-consecutive k step, which TPU output
    semantics forbid), so each grid step writes its dq *partial* to its
    own block of a [nk, B·H, L, D] f32 output and ``_bwd_call`` sums
    the leading axis in XLA — the splash-attention fused-backward
    layout. Skipped (fully-masked) block pairs still own a block, which
    is zeroed up front so the sum sees no garbage."""
    if has_lens:
        kvlen_ref, dqp_ref, dk_ref, dv_ref, dk_scr, dv_scr = rest
    else:
        dqp_ref, dk_ref, dv_ref, dk_scr, dv_scr = rest
        kvlen_ref = None
    ik = pl.program_id(1)
    j = pl.program_id(2)
    iq = j % nq  # positional q block; j // nq is the GQA head in the group
    bq = q_ref.shape[1]
    bk = k_ref.shape[1]

    @pl.when(j == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    # Every grid step owns exactly one dq-partial block: zero it first so
    # block pairs the causal/window predicate skips contribute zero.
    dqp_ref[0, 0] = jnp.zeros_like(dqp_ref[0, 0])

    def _accumulate():
        q = q_ref[0]
        k = k_ref[0]
        do = do_ref[0]
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
        # Explicit p masking — see _dq_kernel (fully-masked rows saved
        # lse ~= -1e30; underflow alone would give p = 1 there).
        mask = None
        if causal:
            mask = _causal_mask(iq, ik, bq, bk, window, offset)
        if has_lens:
            lm = _kvlen_valid(ik, bq, bk, kvlen_ref, lens_by_row)
            mask = lm if mask is None else mask & lm
        if mask is not None:
            s = jnp.where(mask, s, _NEG_INF)
        p = jnp.exp(s - lse_ref[0])
        if mask is not None:
            p = jnp.where(mask, p, 0.0)
        dv_scr[:] += jnp.dot(
            p.astype(do.dtype).T, do, preferred_element_type=jnp.float32
        )
        dp = jnp.dot(do, v_ref[0].T, preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[0]) * scale
        dk_scr[:] += jnp.dot(
            ds.astype(q.dtype).T, q, preferred_element_type=jnp.float32
        )
        dqp_ref[0, 0] = jnp.dot(
            ds.astype(k.dtype), k, preferred_element_type=jnp.float32
        )

    if causal:
        pl.when(_block_needed(iq, ik, bq, bk, window, offset))(_accumulate)
    else:
        _accumulate()

    @pl.when(j == total - 1)
    def _finalize():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


def _lens_blockspec(interpret):
    """Key-padding lengths spec shared by every kernel launch (forward,
    dq, dkv, fused — see ``_kvlen_valid`` for the two layouts): the
    whole [rows, 1] array in SMEM on Mosaic, a per-row (1, 1) block on
    the CPU interpreter."""
    return (
        pl.BlockSpec((1, 1), lambda b, i, j: (b, 0))
        if interpret
        else pl.BlockSpec(memory_space=pltpu.SMEM)  # whole array
    )


def _qrow_specs(bq, d, qmap):
    """[1, bq, d] q/do blocks and the matching [1, bq, 1] lse/delta
    row-statistic blocks walking one shared index map — the ONE builder
    for every backward launch (q-major dq, k-major dkv, fused), so the
    row-spec layout cannot drift between consumers."""
    return pl.BlockSpec((1, bq, d), qmap), pl.BlockSpec((1, bq, 1), qmap)


def _bwd_call(
    q, k, v, o, lse, do, delta, kv_lens,
    *, causal, window, offset, bq, bk, scale, interpret, vma, hq, hkv,
    fused,
):
    sds = partial(jax.ShapeDtypeStruct, vma=vma) if vma else jax.ShapeDtypeStruct
    bh, l, d = q.shape
    dv = v.shape[-1]  # do, o and dv share the values' head size
    bhkv = k.shape[0]
    g = hq // hkv
    nq, nk = l // bq, l // bk
    row = _kv_row(hq, hkv)
    has_lens = kv_lens is not None
    lens_spec = _lens_blockspec(interpret)
    banded = offset == 0 and _use_banding(window, l)

    # k-major layout (dkv and fused launches): q/do/lse/delta blocks walk
    # the innermost dim, which under GQA spans all g query heads sharing
    # this KV head (j = head·nq + jq) — dk/dv accumulate over the whole
    # group in one scratch pass.
    def qrow(b, j):
        return (b // hkv) * hq + (b % hkv) * g + j // nq

    if banded:
        _band = _banded_q_index(window, bq, bk, nq)

        def qmap2(b, i, j):
            _, jq, _ = _band(b, i, j % nq)
            return (qrow(b, j), jq, 0)

    else:

        def qmap2(b, i, j):
            return (qrow(b, j), j % nq, 0)

    qspec2, rowspec2 = _qrow_specs(bq, d, qmap2)
    dospec2, _ = _qrow_specs(bq, dv, qmap2)
    kspec2 = pl.BlockSpec((1, bk, d), lambda b, i, j: (b, i, 0))
    vspec2 = pl.BlockSpec((1, bk, dv), lambda b, i, j: (b, i, 0))
    kv_inputs = [q, k, v, do, lse, delta]
    kv_specs = [qspec2, kspec2, vspec2, dospec2, rowspec2, rowspec2]
    if has_lens:
        # k-major grid: b indexes B·Hkv rows.
        kv_inputs.append(jnp.repeat(kv_lens.astype(jnp.int32), hkv)[:, None])
        kv_specs.append(lens_spec)

    if fused:
        # dq partials: each grid step's own block (index map UNclamped —
        # banding only redirects the resident input blocks), reduced in
        # XLA. f32 partials + f32 sum match the two-kernel path's f32
        # scratch accumulation.
        dqp_spec = pl.BlockSpec(
            (1, 1, bq, d), lambda b, i, j: (i, qrow(b, j), j % nq, 0)
        )
        dqp, dk, dv = pl.pallas_call(
            partial(
                _fused_bwd_kernel,
                scale=scale, causal=causal, window=window, nq=nq,
                total=nq * g, has_lens=has_lens, offset=offset,
                lens_by_row=not interpret,
            ),
            grid=(bhkv, nk, nq * g),
            in_specs=kv_specs,
            out_specs=(dqp_spec, kspec2, vspec2),
            out_shape=(
                sds((nk, bh, l, d), jnp.float32),
                sds((bhkv, l, d), k.dtype),
                sds((bhkv, l, dv), v.dtype),
            ),
            scratch_shapes=[
                pltpu.VMEM((bk, d), jnp.float32),
                pltpu.VMEM((bk, dv), jnp.float32),
            ],
            interpret=interpret,
            name=names.KERNEL_FLASH_BWD_FUSED,
        )(*kv_inputs)
        return jnp.sum(dqp, axis=0).astype(q.dtype), dk, dv

    # Two-kernel escape hatch (fused=False): q-major dq kernel + k-major
    # dkv kernel, each re-deriving p — the parity oracle for the fused
    # path and the fallback if a Mosaic regression ever hits it.
    kmap = (
        _banded_k_index(window, bq, bk, row)
        if banded
        else (lambda b, i, j: (row(b), j, 0))
    )
    qspec, rowspec = _qrow_specs(bq, d, lambda b, i, j: (b, i, 0))
    dospec, _ = _qrow_specs(bq, dv, lambda b, i, j: (b, i, 0))
    kspec = pl.BlockSpec((1, bk, d), kmap)
    vspec = pl.BlockSpec((1, bk, dv), kmap)
    dq_inputs = [q, k, v, do, lse, delta]
    dq_specs = [qspec, kspec, vspec, dospec, rowspec, rowspec]
    if has_lens:
        dq_inputs.append(jnp.repeat(kv_lens.astype(jnp.int32), hq)[:, None])
        dq_specs.append(lens_spec)
    dq = pl.pallas_call(
        partial(
            _dq_kernel,
            scale=scale, causal=causal, window=window, nk=nk,
            has_lens=has_lens, offset=offset, lens_by_row=not interpret,
        ),
        grid=(bh, nq, nk),
        in_specs=dq_specs,
        out_specs=qspec,
        out_shape=sds((bh, l, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        interpret=interpret,
        name=names.KERNEL_FLASH_BWD_DQ,
    )(*dq_inputs)

    dk, dv = pl.pallas_call(
        partial(
            _dkv_kernel,
            scale=scale, causal=causal, window=window, nq=nq, total=nq * g,
            has_lens=has_lens, offset=offset, lens_by_row=not interpret,
        ),
        grid=(bhkv, nk, nq * g),
        in_specs=kv_specs,
        out_specs=(kspec2, vspec2),
        out_shape=(
            sds((bhkv, l, d), k.dtype),
            sds((bhkv, l, dv), v.dtype),
        ),
        scratch_shapes=[
            pltpu.VMEM((bk, d), jnp.float32),
            pltpu.VMEM((bk, dv), jnp.float32),
        ],
        interpret=interpret,
        name=names.KERNEL_FLASH_BWD_DKV,
    )(*kv_inputs)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# custom-vjp wrapper and public API
# ---------------------------------------------------------------------------


def _to_bh(x):
    """[B, L, H, D] → [B·H, L, D]."""
    b, l, h, d = x.shape
    return jnp.einsum("blhd->bhld", x).reshape(b * h, l, d)


def _from_bh(x, b, h):
    bh, l, d = x.shape
    return jnp.einsum("bhld->blhd", x.reshape(b, h, l, d))


@partial(jax.custom_vjp, nondiff_argnums=tuple(range(10)))
def _flash(
    causal, window, offset, bq, bk, interpret, vma, hq, hkv, fused,
    q, k, v, kv_lens,
):
    """Primal returns (out, lse) — both differentiable. The lse output is
    what makes blockwise *composition* (ring attention) differentiable: a
    cotangent on lse folds into the backward's delta term, since
    ∂lse_i/∂s_ij = p_ij means ds = p·(dp − (delta − g_lse))·scale.
    ``kv_lens`` (None or [B] int32) is an integer side input — its
    "gradient" is None. ``fused`` picks the backward implementation
    (one-pass fused kernel vs the two-kernel split); the primal ignores
    it."""
    scale = 1.0 / (q.shape[-1] ** 0.5)
    return _fwd_call(
        q, k, v, kv_lens,
        causal=causal, window=window, offset=offset, bq=bq, bk=bk,
        scale=scale, interpret=interpret, vma=vma, hq=hq, hkv=hkv,
    )


def _flash_fwd(
    causal, window, offset, bq, bk, interpret, vma, hq, hkv, fused,
    q, k, v, kv_lens,
):
    o, lse = _flash(
        causal, window, offset, bq, bk, interpret, vma, hq, hkv, fused,
        q, k, v, kv_lens,
    )
    return (o, lse), (q, k, v, o, lse, kv_lens)


def _flash_bwd_impl(
    causal, window, offset, bq, bk, interpret, vma, hq, hkv, fused, res, g
):
    """(dq, dk, dv) from the saved residuals — shared by ``_flash``'s vjp
    and the selective-remat rebuild (``_flash_rebuild``), whose residual
    tuples are identical by construction."""
    q, k, v, o, lse, kv_lens = res
    do, dlse = g
    scale = 1.0 / (q.shape[-1] ** 0.5)
    # delta_i = rowsum(do ⊙ out) − g_lse: tiny elementwise reduce, XLA fuses
    # it into the surrounding graph — not worth a kernel. g_lse is symbolic
    # zero (materialized as zeros) when the caller discards lse.
    delta = jnp.sum(
        do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1, keepdims=True
    ) - dlse.astype(jnp.float32)
    return _bwd_call(
        q, k, v, o, lse, do, delta, kv_lens,
        causal=causal, window=window, offset=offset, bq=bq, bk=bk,
        scale=scale, interpret=interpret, vma=vma, hq=hq, hkv=hkv,
        fused=fused,
    )


def _flash_bwd(
    causal, window, offset, bq, bk, interpret, vma, hq, hkv, fused, res, g
):
    dq, dk, dv = _flash_bwd_impl(
        causal, window, offset, bq, bk, interpret, vma, hq, hkv, fused,
        res, g,
    )
    return dq, dk, dv, None


_flash.defvjp(_flash_fwd, _flash_bwd)


# The checkpoint_name labels under which a layer's checkpoint keeps the
# attention forward (models/gpt.py: remat=True or "selective" builds
# jax.checkpoint_policies.save_only_these_names over them).
REMAT_SAVE_NAMES = ("flash_out", "flash_lse")
# Kept beside them, and tagged under tensor parallelism only
# (GPTLM._block): the residual stream once the attention's row-split
# product has been summed over the model axis, so that the replay pays
# neither the product nor its all-reduce a second time.
REMAT_SAVE_TP_SUM = "attn_out_sum"
_LANES = 128  # the minor dimension of the chip's (8, 128) float32 tile

# Auto-fusion cap: the fused backward's dq-partial buffer is
# nk · (B·H·L·D) f32 in HBM — (L/bk) full gradient copies. The default
# (fused=None) picks the fused kernel only while that buffer stays under
# this cap, so extreme-length configs (L=16k attention-bench rows and
# beyond) silently keep the two-kernel split instead of OOMing a 16 GB
# v5e that is already carrying the xl activation stash. 1 GiB keeps the
# primary target (gpt-xl-L2048: ~536 MB of partials) fused. PROVISIONAL
# until the chip rerun measures where the fused win stops paying for the
# extra HBM traffic — an explicit fused=True/False always wins.
_FUSED_DQ_CAP_BYTES = 1 << 30


def _resolve_fused(
    fused: bool | None, bh: int, l: int, d: int, bk: int,
    window: int | None = None,
) -> bool:
    """fused=None → auto: fuse unless (a) the [nk, B·H, L, D] f32
    dq-partial output would exceed ``_FUSED_DQ_CAP_BYTES``, or (b) the
    call is in the BANDED-window regime (``_use_banding``) — there the
    fused kernel would write and re-read mostly structurally-zero
    partial planes (only in-band k-blocks contribute to a q-block's dq,
    but every plane exists), multiplying dq HBM traffic by ~nk against
    the split path's single VMEM-accumulated dq. Both rules are
    PROVISIONAL pending the chip rerun; an explicit bool always wins."""
    if fused is not None:
        return fused
    if _use_banding(window, l):
        return False
    return (l // bk) * bh * l * d * 4 <= _FUSED_DQ_CAP_BYTES


@partial(jax.custom_vjp, nondiff_argnums=tuple(range(10)))
def _flash_rebuild(
    causal, window, offset, bq, bk, interpret, vma, hq, hkv, fused,
    q, k, v, kv_lens, o, lse,
):
    """Identity on (o, lse) whose VJP is the real flash backward — the
    selective-remat composition hook (``save_names=`` in the public
    API). Its residuals are its own INPUTS, so under
    ``jax.checkpoint(policy=save_only_these_names(...))`` the saved
    (named) o/lse substitute directly and DCE drops the flash *forward*
    from the backward recompute. Naming the outputs of ``_flash`` alone
    cannot achieve that: a custom-vjp's residuals are the pre-name
    values, so the kernel still reruns (measured — recompute FLOPs
    unchanged). The gradient path is exclusively through this function
    (the primal ``_flash`` call is gradient-stopped), so nothing double
    counts; o/lse arrive via stop_gradient and get zero cotangents."""
    return o, lse


def _flash_rebuild_fwd(
    causal, window, offset, bq, bk, interpret, vma, hq, hkv, fused,
    q, k, v, kv_lens, o, lse,
):
    return (o, lse), (q, k, v, o, lse, kv_lens)


def _flash_rebuild_bwd(
    causal, window, offset, bq, bk, interpret, vma, hq, hkv, fused, res, g
):
    dq, dk, dv = _flash_bwd_impl(
        causal, window, offset, bq, bk, interpret, vma, hq, hkv, fused,
        res, g,
    )
    _, _, _, o, lse, _ = res
    return dq, dk, dv, None, jnp.zeros_like(o), jnp.zeros_like(lse)


_flash_rebuild.defvjp(_flash_rebuild_fwd, _flash_rebuild_bwd)


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = False,
    window: int | None = None,
    kv_lens: jax.Array | None = None,
    offset: int = 0,
    block_q: int | None = None,
    block_k: int | None = None,
    interpret: bool | None = None,
    vma: tuple[str, ...] | None = None,
    fused: bool | None = None,
    save_names: tuple[str, str] | None = None,
) -> jax.Array:
    """Exact attention on [B, L, H, D] without materializing [L, L] scores.

    ``window=W`` (requires ``causal``) is sliding-window attention: each
    query sees only its last W keys (self included), and block pairs wholly
    outside the band are skipped — compute scales O(L·W) instead of O(L²).

    ``v`` may have a head size of its own (latent attention trains with
    q and k at 192 and v at 128): o, and dv in the backward, take it; the
    scale is q's and k's. A head size that is no multiple of the 128 lanes
    is never padded in HBM: a block spans the array's whole last
    dimension and the compiler pads its tile in VMEM.

    Grouped-query attention: k/v may carry fewer heads than q (``Hq`` a
    multiple of ``Hkv``); each group of ``Hq/Hkv`` query heads reads one KV
    head via the grid index maps (no materialized repeat), and dk/dv
    accumulate over the whole group in-kernel.

    ``kv_lens`` [B] int32 is the key-padding mask in right-padded form
    (lengths ≥ 1): keys at positions ≥ kv_lens[b] are masked for every
    query, forward and backward — identical semantics to
    ``dense_attention(kv_lens=...)``. Padded *query* rows still produce
    (well-defined) outputs; mask them downstream (``GPTLM.loss(lengths=)``).

    Drop-in for :func:`ops.ring_attention.dense_attention` (same signature,
    same math, differentiable via fused Pallas backward kernels); use it as
    the within-device attention whenever L is long enough that the score
    matrix dominates memory (the crossover on v5e is roughly L ≥ 512).

    Auto-picked blocks follow the measured per-length policy in
    ``_pick_block`` (512 below L=4096, 1024 from there up — the round-3 ≤128
    cap was 4x slower at L=2048); pass ``block_q``/``block_k`` to
    override for odd shapes.

    ``fused`` picks the backward: the default (None) runs the one-pass
    fused dq+dk+dv kernel whenever its dq-partial buffer fits
    ``_FUSED_DQ_CAP_BYTES`` (see :func:`_resolve_fused`), falling back
    to the two-kernel split past the cap; an explicit True/False always
    wins. Gradients are identical either way within accumulation-order
    tolerance — pinned in tests/test_pallas_attention.py and
    tools/attention_parity.py. ``save_names`` — see
    :func:`flash_attention_with_lse`.
    """
    out, _ = flash_attention_with_lse(
        q, k, v,
        causal=causal, window=window, kv_lens=kv_lens, offset=offset,
        block_q=block_q, block_k=block_k,
        interpret=interpret, vma=vma, fused=fused, save_names=save_names,
    )
    return out


def flash_attention_with_lse(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = False,
    window: int | None = None,
    kv_lens: jax.Array | None = None,
    offset: int = 0,
    block_q: int | None = None,
    block_k: int | None = None,
    interpret: bool | None = None,
    vma: tuple[str, ...] | None = None,
    fused: bool | None = None,
    save_names: tuple[str, str] | None = None,
) -> tuple[jax.Array, jax.Array]:
    """:func:`flash_attention` that also returns the per-row softmax
    log-sum-exp, shape [B, L, H] f32 — the statistic needed to *combine*
    partial attention over disjoint KV chunks exactly (ring attention's
    per-hop accumulation). Both outputs are differentiable. Pass
    ``vma=(axis,...)`` when calling inside a ``shard_map`` that checks
    varying-mesh-axes types (Pallas outputs carry no vma by default).

    ``offset=F`` (static, requires ``causal``) shifts every query's global
    position F ahead of the keys': the mask keeps ``0 <= q+F-k`` (and
    ``< window``). This is the blockwise-composition hook — a ring hop
    holding a KV block that originated F positions behind the local queries
    is exactly causal+window attention at offset F (all-past blocks without
    a window are the degenerate ``F >= L`` case, where it equals
    ``causal=False``).

    ``save_names=(out_name, lse_name)`` arms the selective-remat
    composition (pass :data:`REMAT_SAVE_NAMES` unless you need distinct
    labels): the forward is computed gradient-stopped, both outputs are
    tagged with ``jax.ad_checkpoint.checkpoint_name``, and gradients
    route through :func:`_flash_rebuild` whose residuals ARE the named
    values — so an enclosing ``jax.checkpoint`` with
    ``save_only_these_names(*save_names)`` stores only out+lse
    (O(B·L·d), cheap) and the backward recompute skips the O(L²)-work
    forward kernel entirely. Without an enclosing policy the naming is
    inert and the math/gradients are unchanged (pinned in
    tests/test_gpt.py selective-remat grad-identity tests)."""
    if k.shape[:3] != v.shape[:3]:
        raise ValueError(
            f"k/v must match in batch, length and heads: {k.shape} {v.shape}")
    if (
        q.shape[0] != k.shape[0]
        or q.shape[1] != k.shape[1]
        or q.shape[3] != k.shape[3]
        or k.shape[2] < 1
        or q.shape[2] % k.shape[2]
    ):
        raise ValueError(
            f"q {q.shape} incompatible with k/v {k.shape}: batch/len/head_dim"
            f" must match and query heads must be a multiple of KV heads"
        )
    if window is not None:
        if not causal:
            raise ValueError("window requires causal=True")
        if window < 1:
            raise ValueError(f"window must be >= 1, got {window}")
    if offset:
        if not causal:
            raise ValueError("offset requires causal=True")
        if offset < 0:
            raise ValueError(f"offset must be >= 0, got {offset}")
    interpret = resolve_interpret(interpret)
    b, l, h, d = q.shape
    hkv = k.shape[2]
    if kv_lens is not None and kv_lens.shape != (b,):
        raise ValueError(
            f"kv_lens must be [batch]=({b},), got {kv_lens.shape}"
        )
    bq = _pick_block(l, block_q)
    bk = _pick_block(l, block_k)
    statics = (
        causal, window, offset, bq, bk, interpret,
        frozenset(vma) if vma else None,  # ShapeDtypeStruct wants a set
        h, hkv, _resolve_fused(fused, b * h, l, d, bk, window),
    )
    qb, kb, vb = _to_bh(q), _to_bh(k), _to_bh(v)
    if save_names is None:
        out, lse = _flash(*statics, qb, kb, vb, kv_lens)
    else:
        if len(save_names) != 2:
            raise ValueError(
                f"save_names must be (out_name, lse_name), got {save_names}"
            )
        from jax.ad_checkpoint import checkpoint_name

        # Gradient-stopped primal + named outputs + rebuild: the ONLY
        # grad path is _flash_rebuild's vjp (no double counting), and
        # its residuals are the named values a selective policy saves.
        o, lse0 = _flash(
            *statics,
            lax.stop_gradient(qb), lax.stop_gradient(kb),
            lax.stop_gradient(vb), kv_lens,
        )
        # The output is named in a shape whose rows fill the chip's 128
        # lanes: held as the kernel writes it, a head_dim of 64 is padded
        # to twice its bytes in every layer's kept copy.
        dv = o.shape[-1]
        fold = _LANES // dv if _LANES % dv == 0 else 1
        if l % fold:
            fold = 1
        o = checkpoint_name(
            o.reshape(-1, dv * fold), save_names[0]
        ).reshape(o.shape)
        lse0 = checkpoint_name(lse0, save_names[1])
        out, lse = _flash_rebuild(*statics, qb, kb, vb, kv_lens, o, lse0)
    return _from_bh(out, b, h), jnp.transpose(lse.reshape(b, h, l), (0, 2, 1))
