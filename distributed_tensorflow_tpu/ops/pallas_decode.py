"""Pallas TPU kernel: the fused decode step of a GPT block stack.

The reference has no generative path at all (its one "inference" is the
in-loop accuracy fetch, reference tfsingle.py:94); serving decode is this
framework's hottest un-kerneled path. At L=1 each transformer block of
``models/gpt.py`` lowers to ~20 small XLA ops (the ``decode_step``
docstring), so per-token time is dominated by per-op dispatch overhead
and KV-cache HBM traffic, not FLOPs. This module collapses a block's
whole few-token step

    layernorm₁ → QKV projection → RoPE → quantize-on-write of the fresh
    K/V rows → online-softmax attention over the resident cache →
    output projection → residual → layernorm₂ → dense FFN → residual

into ONE kernel body (:func:`_decode_kernel`) behind five entry points:

- :func:`decode_block_slab` / :func:`decode_block_paged` —
  ``decode_engine="pallas-layer"``: one launch per LAYER; the fresh K/V
  rows come back as outputs and ``models/gpt.py`` commits them with the
  XLA engine's own scatter arithmetic (``_commit_slot_rows`` /
  ``_commit_paged_rows``), so the two engines' caches agree by
  construction.
- :func:`decode_token_slab` / :func:`decode_token_paged` —
  ``decode_engine="pallas"``, the megakernel: one launch per TOKEN. The
  layer loop is the outermost grid dimension, per-layer weights are
  STREAMED through layer-indexed block maps (only the current layer —
  and the next one being prefetched — is VMEM-resident), the residual
  rows live in a VMEM scratch across the whole sequential grid, and the
  fresh rows are committed IN the kernel.
- :func:`verify_tokens_paged` — the small-L speculation verify
  (``spec_draft > 0`` under ``"pallas"``): the same body at L rows per
  slot, the suffix's causal block folded into the online-softmax init.

**Grid and blocks.** ``(n_layers, S, nc + 1)``: per layer and serving
slot, one step per cache block plus one finalize step. TPU grids run
sequentially with the minor dimension fastest, so VMEM scratch carries
the per-head online-softmax state (m / l / acc, one ``[L, ·]`` tile per
query head) across a slot's steps. Every block's last two dimensions
equal the array's — the (8, 128) tiling rule the chip's compiler
enforces and the interpreter does not: activations ride as ``[S, L, d]``
with ``(1, L, d)`` blocks, a cache block is ``(bc, Hkv, Dh)`` — ALL KV
heads of ``bc`` positions — and heads are picked inside the kernel by
static index (a strided sublane load), never by a per-head block map.
Weights use whole-layer blocks, so the projections are one
``[L, d]·[d, H·Dh]`` matmul each and heads are static lane slices of
the result.

**Fresh rows.** The fresh K/V rows are folded into the attention
ONLINE-SOFTMAX INIT after a round-trip through the cache's storage
dtype, so the kernel attends precisely the values the cache will hold —
the round-15 uniform rule ("a quantized cache attends stored values
EVERYWHERE") that keeps the fused engines token-compatible with the XLA
engine. The cache blocks themselves are attended with the fresh
positions masked OUT (``idx != slot`` / ``idx < prefix_len``): the
kernel reads the PRE-write cache, so the write's rows must come from
registers, not memory. Quantized caches (round 15) dequantize int8/fp8
payload blocks inside the kernel, to the COMPUTE dtype (never f32
storage; the f32 view is only the transient dot operand).

**In-kernel commit** (token and verify entry points). The cache arrays
ride the launch TWICE — once as BlockSpec-pipelined read operands and
once as ``memory_space=ANY`` operands aliased input→output
(``input_output_aliases``), written by small manual DMAs at each
layer's finalize step: one ``[Hkv, Dh]`` row group per committed
position. That sidesteps the output-revisit rule (the commit is a DMA,
not a pipelined output block) without copying the cache. Rows that must
not commit (inactive slots, ``li >= suffix_len``) SKIP the DMA — exactly
the XLA scatter's drop-at-sentinel / write-old-value-back no-op, so the
committed bytes match the XLA index math bit-for-bit on the storage
dtype (scale side tensors included). Writes are disjoint from every
read by construction: the kernel attends the PRE-write cache and active
slots never share writable blocks (the serve_pool allocator invariant —
COW prefixes are read-only).

``interpret=None`` resolves through ``ops/pallas_mode`` (interpreter
off-TPU, Mosaic on TPU); parity vs the XLA engine is pinned in
tests/test_pallas_decode.py (interpreter), the compile for the chip in
tests/test_chip_compile.py, and the on-chip token match by
``chip_smoke.py``.
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

_NEG_INF = -1e30
_EPS = 1e-12
# qmax per quantized KV dtype — MUST match ops/quantized._QMAX (the
# kernel re-derives the same symmetric per-row scales the XLA engine
# commits, so both engines attend identical stored values).
_QMAX = {"int8": 127.0, "fp8": 448.0}

_WEIGHT_NAMES = (
    "wq", "wk", "wv", "wo", "ln1_scale", "ln1_bias", "ln2_scale",
    "ln2_bias", "w_up", "b_up", "w_down", "b_down",
)
_MATRICES = ("wq", "wk", "wv", "wo", "w_up", "w_down")


def _pick_cache_block(c: int, requested: int | None) -> int:
    """Largest power-of-two divisor of the cache length ≤ 512 (one score
    tile is [L, bc] — tiny; the cap bounds the resident KV block at
    bc·Hkv·Dh elements), or ``c`` itself for short/odd caches (a single
    whole-cache block)."""
    if requested is not None:
        if c % requested:
            raise ValueError(f"block {requested} must divide cache {c}")
        return requested
    for cand in (512, 256, 128, 64, 32, 16, 8):
        if c % cand == 0 and cand <= c:
            return cand
    return c


def _ln_rows(x, scale, bias):
    """f32 layernorm on [L, d] rows — the models/base.layernorm
    arithmetic verbatim (eps included), so the fused block cannot drift
    numerically from the XLA block."""
    x32 = x.astype(jnp.float32)
    mu = x32.mean(-1, keepdims=True)
    var = ((x32 - mu) ** 2).mean(-1, keepdims=True)
    return ((x32 - mu) * lax.rsqrt(var + 1e-5)) * scale + bias


def _rope_rows(x, pos_f, dh: int, base: float):
    """Rotary embedding on [L, Dh] — the models/gpt._rope pair rotation
    in f32. ``pos_f`` is a [L, 1] f32 column of per-row positions,
    broadcast against the [1, half] frequency row."""
    half = dh // 2
    io = lax.broadcasted_iota(jnp.int32, (1, half), 1).astype(jnp.float32)
    # base ** (-i/half) in the models/gpt._rope evaluation order (the
    # exp(-ln·i/half) refactoring differs in the last ulp, which the
    # parity tests would otherwise have to budget for).
    freqs = jnp.power(base, -io / half)
    ang = pos_f * freqs
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[:, :half], x[:, half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _quant_rows(x, kv_q: str):
    """Symmetric per-row quantization of [L, Dh] — the
    ops/quantized.quantize_kv recipe (amax over the lane dim, eps floor,
    int8 round-and-clip / fp8 cast) re-derived in-kernel so the fused
    engines commit bit-identical rows to the XLA engine."""
    qmax = _QMAX[kv_q]
    amax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
    scale = jnp.maximum(amax, _EPS) / qmax
    xs = x / scale
    if kv_q == "int8":
        q = jnp.clip(jnp.round(xs), -qmax, qmax).astype(jnp.int8)
    else:
        q = xs.astype(jnp.float8_e4m3fn)
    return q, scale


def _dot_nt(a, b):
    """[M, K] · [N, K]ᵀ → [M, N] f32 (contract the minor dims; no
    materialized transpose)."""
    return lax.dot_general(
        a, b, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )


def _dma(src, dst, sem):
    """One synchronous manual copy (start + wait) — the in-kernel cache
    commit's write primitive. Serialized on one DMA semaphore: commits
    are a few rows per layer, latency-insignificant next to the cache
    read stream."""
    cp = pltpu.make_async_copy(src, dst, sem)
    cp.start()
    cp.wait()


def _decode_kernel(
    *refs,
    n_layers: int, nc: int, hq_n: int, hkv_n: int, dh: int, L: int,
    bc: int, cache_len: int, window: int | None, rolling: bool,
    paged: bool, commit: bool, kv_q: str | None, cd, rope: bool,
    rope_base: float,
):
    """The one kernel body; see the module docstring. Ref order: scalar
    prefetch (prefix lens, suffix lens, active[, tables]); h; the twelve
    weights; cache K, V[, K/V scales]; [the ANY alias sources]; outputs;
    scratch."""
    plen_ref, slen_ref, act_ref = refs[:3]
    tab_ref = refs[3] if paged else None
    i = 4 if paged else 3
    (h_ref, wq_ref, wk_ref, wv_ref, wo_ref, ln1s_ref, ln1b_ref,
     ln2s_ref, ln2b_ref, wup_ref, bup_ref, wdn_ref, bdn_ref,
     ck_ref, cv_ref) = refs[i:i + 15]
    i += 15
    ks_ref = vs_ref = None
    if kv_q is not None:
        ks_ref, vs_ref = refs[i:i + 2]
        i += 2
    if commit:
        i += 2  # ANY alias sources: donated, never read
        ho_any, cko, cvo = refs[i:i + 3]
    else:
        ho_ref, kf_dst, vf_dst = refs[i:i + 3]
    i += 3
    if kv_q is not None:
        ksc_out, vsc_out = refs[i:i + 2]
        i += 2
    h_scr, q_scr, m_scr, l_scr, acc_scr = refs[i:i + 5]
    i += 5
    if commit:
        kf_dst, vf_dst, out_scr, sem = refs[i:i + 4]

    l_i = pl.program_id(0)
    s_i = pl.program_id(1)
    j = pl.program_id(2)
    ic = jnp.minimum(j, nc - 1)
    g = hq_n // hkv_n
    plen = plen_ref[s_i]
    slen = slen_ref[s_i]
    scale = 1.0 / math.sqrt(dh)
    li_col = lax.broadcasted_iota(jnp.int32, (L, 1), 0)

    @pl.when((l_i == 0) & (j == 0))
    def _seed_residual():
        h_scr[s_i] = h_ref[0]

    h_rows = h_scr[s_i]  # [L, d] f32

    @pl.when(j == 0)
    def _start():
        # All heads' projections as three matmuls in the compute dtype
        # with f32 accumulation (GPTLM._dot); heads are static lane
        # slices of the results.
        hn = _ln_rows(h_rows, ln1s_ref[0], ln1b_ref[0]).astype(cd)
        q_all = jnp.dot(hn, wq_ref[0], preferred_element_type=jnp.float32)
        k_all = jnp.dot(hn, wk_ref[0], preferred_element_type=jnp.float32)
        v_all = jnp.dot(hn, wv_ref[0], preferred_element_type=jnp.float32)
        pos_f = (plen + li_col).astype(jnp.float32)
        lj = lax.broadcasted_iota(jnp.int32, (L, L), 1)
        valid = (lj <= li_col) & (lj < slen)
        if window is not None:
            valid &= lj > li_col - window
        hcol = lax.broadcasted_iota(jnp.int32, (1, hkv_n), 1)
        k_scales = jnp.zeros((L, hkv_n), jnp.float32)
        v_scales = jnp.zeros((L, hkv_n), jnp.float32)
        for hk in range(hkv_n):
            kf = k_all[:, hk * dh:(hk + 1) * dh]
            vf = v_all[:, hk * dh:(hk + 1) * dh]
            if rope:
                kf = _rope_rows(kf, pos_f, dh, rope_base)
            # Quantize-on-write, then attend the ROUND-TRIPPED values —
            # the round-15 uniform rule: a fresh position must score
            # exactly as a later decode re-reading it from the cache.
            if kv_q is None:
                kq_rows = kf.astype(kf_dst.dtype)
                vq_rows = vf.astype(vf_dst.dtype)
                kf_att = kq_rows.astype(jnp.float32)
                vf_att = vq_rows.astype(jnp.float32)
            else:
                kq_rows, k_sc = _quant_rows(kf, kv_q)  # [L, Dh], [L, 1]
                vq_rows, v_sc = _quant_rows(vf, kv_q)
                kf_att = (kq_rows.astype(jnp.float32) * k_sc).astype(
                    cd
                ).astype(jnp.float32)
                vf_att = (vq_rows.astype(jnp.float32) * v_sc).astype(
                    cd
                ).astype(jnp.float32)
                k_scales = jnp.where(hcol == hk, k_sc, k_scales)
                v_scales = jnp.where(hcol == hk, v_sc, v_scales)
            # Output blocks carry a leading slot axis the scratch lacks.
            where = (slice(None), hk) if commit else (0, slice(None), hk)
            kf_dst[where] = kq_rows
            vf_dst[where] = vq_rows
            for gi in range(g):
                hq = hk * g + gi
                qh = q_all[:, hq * dh:(hq + 1) * dh]
                if rope:
                    qh = _rope_rows(qh, pos_f, dh, rope_base)
                q_scr[hq] = qh
                # Softmax INIT from the fresh causal block: query row li
                # attends suffix keys lj ≤ li (within the real suffix;
                # windowed models also bound the band). Dead rows (no
                # valid key) are guarded — m is _NEG_INF and l stays 0.
                sf = jnp.where(valid, _dot_nt(qh, kf_att) * scale, _NEG_INF)
                m0 = jnp.max(sf, axis=-1, keepdims=True)
                m_safe = jnp.where(m0 > _NEG_INF * 0.5, m0, 0.0)
                p = jnp.where(valid, jnp.exp(sf - m_safe), 0.0)
                m_scr[hq] = m0
                l_scr[hq] = jnp.sum(p, axis=-1, keepdims=True)
                acc_scr[hq] = jnp.dot(
                    p, vf_att, preferred_element_type=jnp.float32
                )
        if kv_q is not None:
            ksc_out[0, 0] = k_scales
            vsc_out[0, 0] = v_scales

    def _attend():
        idx = ic * bc + lax.broadcasted_iota(jnp.int32, (L, bc), 1)
        if rolling:
            # Rolling slab (windowed models, one row per slot): slot i
            # holds absolute position plen − ((slot − i) mod C) — the
            # models/gpt._decode_block identity — minus the write slot
            # itself (handled exactly at init; the block read here
            # predates the write).
            slot = plen % cache_len
            slot_pos = plen - jnp.mod(slot - idx, cache_len)
            valid = (slot_pos >= 0) & (idx != slot)
        else:
            valid = idx < plen  # STRICT: the kernel reads the PRE-write cache
            if window is not None:
                valid &= idx > plen + li_col - window
        for hk in range(hkv_n):
            kblk = ck_ref[0, 0, :, hk, :]  # [bc, Dh]
            vblk = cv_ref[0, 0, :, hk, :]
            if kv_q is None:
                kb = kblk.astype(jnp.float32)
                vb = vblk.astype(jnp.float32)
            else:
                # Dequantize to the COMPUTE dtype (round-15 rule); the
                # f32 upcast after is the transient dot operand, matching
                # the XLA engine's f32-promoted score einsum.
                ksc = ks_ref[0, 0][:, hk:hk + 1]  # [bc, 1]
                vsc = vs_ref[0, 0][:, hk:hk + 1]
                kb = (kblk.astype(jnp.float32) * ksc).astype(cd).astype(
                    jnp.float32
                )
                vb = (vblk.astype(jnp.float32) * vsc).astype(cd).astype(
                    jnp.float32
                )
            for gi in range(g):
                hq = hk * g + gi
                sblk = jnp.where(
                    valid, _dot_nt(q_scr[hq], kb) * scale, _NEG_INF
                )  # [L, bc]
                m_prev = m_scr[hq]
                m_new = jnp.maximum(
                    m_prev, jnp.max(sblk, axis=-1, keepdims=True)
                )
                corr = jnp.exp(m_prev - m_new)
                p = jnp.where(valid, jnp.exp(sblk - m_new), 0.0)
                l_scr[hq] = l_scr[hq] * corr + jnp.sum(
                    p, axis=-1, keepdims=True
                )
                acc_scr[hq] = acc_scr[hq] * corr + jnp.dot(
                    p, vb, preferred_element_type=jnp.float32
                )
                m_scr[hq] = m_new

    # Skip cache blocks that cannot hold a valid position (absolute
    # layouts: written positions are 0..plen-1, windowed also above the
    # lowest row's band edge plen − W). Rolling slabs interleave
    # positions across blocks, so every block is live there.
    live = j < nc
    if not rolling:
        live &= ic * bc < plen
        if window is not None:
            live &= (ic + 1) * bc - 1 > plen - window
    pl.when(live)(_attend)

    @pl.when(j == nc)
    def _final():
        wo = wo_ref[0]
        out = jnp.zeros((L, wo.shape[1]), jnp.float32)
        # attn·wo as a static per-head sum of [L, Dh]·[Dh, d] dots — the
        # [Hq, L, Dh] → [L, Hq·Dh] flatten it avoids is a cross-tile
        # relayout.
        for hq in range(hq_n):
            l_h = l_scr[hq]
            out_h = jnp.where(l_h > 0, acc_scr[hq] / l_h, 0.0)
            out = out + jnp.dot(
                out_h.astype(cd), wo[hq * dh:(hq + 1) * dh, :],
                preferred_element_type=jnp.float32,
            )
        h1 = h_rows + out
        hn2 = _ln_rows(h1, ln2s_ref[0], ln2b_ref[0])
        up = jnp.dot(
            hn2.astype(cd), wup_ref[0], preferred_element_type=jnp.float32
        ) + bup_ref[0]
        dn = jnp.dot(
            jax.nn.gelu(up).astype(cd), wdn_ref[0],
            preferred_element_type=jnp.float32,
        ) + bdn_ref[0]
        h_new = h1 + dn
        h_scr[s_i] = h_new
        if not commit:
            ho_ref[0] = h_new
            return

        # In-kernel commit: the XLA engines' exact scatter index math
        # (slot = pos % C rolling / pos absolute; paged through the
        # block table), one [Hkv, Dh] row group per position, as manual
        # DMAs into the aliased cache outputs. Invalid positions issue
        # NO DMA — the scatter's drop / write-old-back no-op.
        is_act = act_ref[s_i] != 0
        for li in range(L):
            @pl.when(is_act & (li < slen))
            def _commit(li=li):
                pos = plen + li
                if paged:
                    row, off = tab_ref[s_i, pos // bc], pos % bc
                else:
                    row = s_i
                    off = pos % cache_len if rolling else pos
                _dma(kf_dst.at[li], cko.at[l_i, row, off], sem)
                _dma(vf_dst.at[li], cvo.at[l_i, row, off], sem)

        @pl.when(l_i == n_layers - 1)
        def _emit():
            out_scr[...] = h_new
            _dma(out_scr, ho_any.at[s_i], sem)


def _weight_inputs(w: dict, cd):
    """Order + cast the layer-stacked block weights for the launch:
    projections and FFN matrices to the compute dtype (GPTLM._dot's
    operand cast), layernorm params and biases f32 as [n_layers, 1, n]
    rows."""
    n = w["wq"].shape[0]
    return [
        w[nm].astype(cd) if nm in _MATRICES
        else w[nm].astype(jnp.float32).reshape(n, 1, -1)
        for nm in _WEIGHT_NAMES
    ]


def _vmem_limit(blocks, scratch_bytes: int) -> int:
    """Fast-memory budget for the launch: every blocked operand is
    double-buffered by the pipeline (the next layer's weights stream in
    while the current one computes), plus the scratch, doubled again as
    headroom for tile padding and the compiler's own temporaries.
    Clamped to a v5e core's range (the compiler's default scoped limit
    is 16 MiB of 128 MiB physical)."""
    blocked = sum(math.prod(shape) * itemsize for shape, itemsize in blocks)
    need = 2 * blocked + scratch_bytes
    return int(min(max(2 * need, 32 << 20), 100 << 20))


def _call(
    h, w, ck, cv, k_scale, v_scale, prefix_lens, suffix_lens, active,
    tables, *, num_heads, window, rolling, commit, kv_dtype,
    compute_dtype, rope, rope_base, block_c, interpret, name,
):
    """The one launch builder. ``h`` [S, L, d]; ``w`` the layer-STACKED
    weight dict; ``ck``/``cv`` [n_layers, S, C, Hkv, Dh] (slab) or
    [n_layers, NB, bs, Hkv, Dh] with ``tables`` [S, max_blocks] (paged;
    the tables ride as scalar prefetch and the pool gather is index-map
    arithmetic). ``commit`` selects in-kernel commit through aliased
    ANY-space cache operands (returns the committed caches) against
    fresh rows returned as outputs. ``name`` is the kernel's name in a
    trace (``observability/names.py``)."""
    interpret = resolve_interpret(interpret)
    rows = h.shape[1]
    if rows > 1 and rows % 8:
        # The chip's compiler slices [L, d] row groups out of tiled
        # scratch only at the f32 sublane tile (8) or a single row; pad
        # the verify rows up to it. Padded rows sit past every
        # ``suffix_len``: no real row attends them and they never commit.
        h = jnp.pad(h, ((0, 0), (0, -rows % 8), (0, 0)))
    s, L, d = h.shape
    n_layers = ck.shape[0]
    hkv_n, dh = ck.shape[-2], ck.shape[-1]
    kv_q = None if kv_dtype == "bf16" else kv_dtype
    paged = tables is not None
    if paged:
        bc = ck.shape[2]  # pool block size
        nc = tables.shape[1]
    else:
        bc = _pick_cache_block(ck.shape[2], block_c)
        nc = ck.shape[2] // bc

    def _ic(j):
        return jnp.minimum(j, nc - 1)

    if paged:
        def cmap(l_i, s_i, j, plens, slens, act, tab):
            return (l_i, tab[s_i, _ic(j)], 0, 0, 0)

        def smap(l_i, s_i, j, plens, slens, act, tab):
            return (l_i, tab[s_i, _ic(j)], 0, 0)
    else:
        def cmap(l_i, s_i, j, *pref):
            return (l_i, s_i, _ic(j), 0, 0)

        def smap(l_i, s_i, j, *pref):
            return (l_i, s_i, _ic(j), 0)

    def hmap(l_i, s_i, j, *pref):
        return (s_i, 0, 0)

    def lmap(l_i, s_i, j, *pref):
        return (l_i, 0, 0)

    weights = _weight_inputs(w, compute_dtype)
    inputs = [h.astype(jnp.float32)] + weights + [ck, cv]
    in_specs = [pl.BlockSpec((1, L, d), hmap)]
    in_specs += [pl.BlockSpec((1,) + a.shape[1:], lmap) for a in weights]
    in_specs += [pl.BlockSpec((1, 1, bc, hkv_n, dh), cmap)] * 2
    if kv_q is not None:
        inputs += [k_scale, v_scale]
        in_specs += [pl.BlockSpec((1, 1, bc, hkv_n), smap)] * 2
    blocks = [
        (spec.block_shape, a.dtype.itemsize)
        for a, spec in zip(inputs, in_specs, strict=True)
    ]
    n_prefetch = 4 if paged else 3

    storage = ck.dtype
    f32 = jnp.float32
    scratch = [
        pltpu.VMEM((s, L, d), f32),           # residual rows
        pltpu.VMEM((num_heads, L, dh), f32),  # q, per head
        pltpu.VMEM((num_heads, L, 1), f32),   # m
        pltpu.VMEM((num_heads, L, 1), f32),   # l
        pltpu.VMEM((num_heads, L, dh), f32),  # acc
    ]
    scratch_bytes = 4 * (s * L * d + 2 * num_heads * L * (dh + 1))
    if commit:
        # The alias sources: K and V again, whole-buffer ANY operands
        # donated into the outputs (alias indices count the
        # scalar-prefetch operands).
        any_spec = pl.BlockSpec(memory_space=pl.ANY)
        aliases = {n_prefetch + len(inputs) + i: 1 + i for i in range(2)}
        in_specs += [any_spec] * 2
        inputs += [ck, cv]
        out_specs = [any_spec] * 3
        out_shape = [
            jax.ShapeDtypeStruct((s, L, d), f32),
            jax.ShapeDtypeStruct(ck.shape, storage),
            jax.ShapeDtypeStruct(cv.shape, storage),
        ]
        scratch += [
            pltpu.VMEM((L, hkv_n, dh), storage),  # fresh K rows (commit src)
            pltpu.VMEM((L, hkv_n, dh), storage),  # fresh V rows
            pltpu.VMEM((L, d), f32),              # h_out DMA staging
            pltpu.SemaphoreType.DMA,
        ]
        scratch_bytes += 4 * L * d + 2 * L * hkv_n * dh * storage.itemsize
    else:
        aliases = {}

        def omap(l_i, s_i, j, *pref):
            return (s_i, 0, 0, 0)

        out_specs = [
            pl.BlockSpec((1, L, d), hmap),
            pl.BlockSpec((1, L, hkv_n, dh), omap),
            pl.BlockSpec((1, L, hkv_n, dh), omap),
        ]
        out_shape = [
            jax.ShapeDtypeStruct((s, L, d), f32),
            jax.ShapeDtypeStruct((s, L, hkv_n, dh), storage),
            jax.ShapeDtypeStruct((s, L, hkv_n, dh), storage),
        ]
    if kv_q is not None:
        # Fresh per-row scales, [n_layers, S, L, Hkv]: a [·, Hkv] row is
        # narrower than a lane tile, which no manual DMA may slice, so
        # the scale side tensors are committed after the launch
        # (_commit_index).
        def scmap(l_i, s_i, j, *pref):
            return (l_i, s_i, 0, 0)

        out_specs += [pl.BlockSpec((1, 1, L, hkv_n), scmap)] * 2
        out_shape += [jax.ShapeDtypeStruct((n_layers, s, L, hkv_n), f32)] * 2

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=n_prefetch,
        grid=(n_layers, s, nc + 1),
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=scratch,
    )
    kern = partial(
        _decode_kernel,
        n_layers=n_layers, nc=nc, hq_n=num_heads, hkv_n=hkv_n, dh=dh, L=L,
        bc=bc, cache_len=ck.shape[2], window=window, rolling=rolling,
        paged=paged, commit=commit, kv_q=kv_q, cd=compute_dtype,
        rope=rope, rope_base=rope_base,
    )
    prefetch = [
        p.astype(jnp.int32) for p in (prefix_lens, suffix_lens, active)
    ]
    if paged:
        prefetch.append(tables.astype(jnp.int32))
    outs = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=tuple(out_shape),
        input_output_aliases=aliases,
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_vmem_limit(blocks, scratch_bytes),
        ),
        interpret=interpret,
        name=name,
    )(*prefetch, *inputs)
    ho, nk, nv = outs[0][:, :rows], outs[1], outs[2]
    if kv_q is None:
        return ho, nk, nv, None, None
    if not commit:
        return ho, nk, nv, outs[3][0], outs[4][0]
    where = _commit_index(
        prefix_lens, suffix_lens, active, tables, L, k_scale.shape[1],
        k_scale.shape[2], rolling,
    )
    return (
        ho, nk, nv,
        k_scale.at[where].set(outs[3], mode="drop"),
        v_scale.at[where].set(outs[4], mode="drop"),
    )


def _commit_index(
    prefix_lens, suffix_lens, active, tables, L, n_rows, c, rolling
):
    """Index of the committed positions in a [n_layers, rows, C, ·]
    cache side tensor — the kernel's own commit rule (row li of slot s
    lands at position ``prefix + li``; slab slot ``pos % C`` when
    rolling, else ``pos``; paged through the block table) with every
    position that must NOT commit (inactive slot, ``li >= suffix_len``)
    sent past the row axis, where a ``mode="drop"`` scatter discards
    it."""
    li = jnp.arange(L)[None]
    pos = prefix_lens[:, None] + li  # [S, L]
    valid = (active[:, None] != 0) & (li < suffix_lens[:, None])
    if tables is None:
        row = jnp.broadcast_to(jnp.arange(pos.shape[0])[:, None], pos.shape)
        off = pos % c if rolling else pos
    else:
        blk = jnp.minimum(pos // c, tables.shape[1] - 1)
        row = jnp.take_along_axis(tables, blk, axis=1)
        off = pos % c
    return (slice(None), jnp.where(valid, row, n_rows), off)


def _block_call(h, weights, ck, cv, k_scale, v_scale, lengths, tables, **kw):
    """One LAYER, fresh rows returned: the token call's shapes with a
    leading layer axis of one (free reshapes) and L = 1."""
    lead = lambda a: None if a is None else a[None]  # noqa: E731
    ones = jnp.ones_like(lengths)
    ho, kq, vq, ksc, vsc = _call(
        h[:, None], jax.tree.map(lead, weights), ck[None], cv[None],
        lead(k_scale), lead(v_scale), lengths, ones, ones, tables,
        commit=False, name=names.KERNEL_DECODE_LAYER, **kw,
    )
    squeeze = lambda a: None if a is None else a[:, 0]  # noqa: E731
    return ho[:, 0], kq[:, 0], vq[:, 0], squeeze(ksc), squeeze(vsc)


def decode_block_slab(
    h: jax.Array,
    weights: dict,
    ck: jax.Array,
    cv: jax.Array,
    k_scale: jax.Array | None,
    v_scale: jax.Array | None,
    lengths: jax.Array,
    *,
    num_heads: int,
    window: int | None = None,
    kv_dtype: str = "bf16",
    compute_dtype=jnp.bfloat16,
    rope: bool = False,
    rope_base: float = 10000.0,
    block_c: int | None = None,
    interpret: bool | None = None,
):
    """One GPT block's fused single-token step over a SLAB cache layer.

    ``h`` [S, d] f32 residual rows (one token per slot), ``weights`` the
    block's parameter dict (raw f32 leaves — cast happens inside),
    ``ck``/``cv`` [S, C, Hkv, Dh] (this layer's cache, PRE-write),
    ``k_scale``/``v_scale`` [S, C, Hkv] f32 or None (bf16), ``lengths``
    [S] int32 write positions. Windowed models pass their rolling-buffer
    cache (C = min(window, max_len)); the in-kernel validity reproduces
    the ``models/gpt._decode_block`` rolling identity.

    Returns ``(h_out [S, d] f32, k_fresh [S, Hkv, Dh] storage-dtype,
    v_fresh, k_fresh_scale [S, Hkv] f32 | None, v_fresh_scale)`` — the
    caller commits the fresh row with the SAME scatter index math as the
    XLA engine (``models/gpt.py``), which is what keeps the two engines
    attending identical caches."""
    return _block_call(
        h, weights, ck, cv, k_scale, v_scale, lengths, None,
        num_heads=num_heads, window=window, rolling=window is not None,
        kv_dtype=kv_dtype, compute_dtype=compute_dtype, rope=rope,
        rope_base=rope_base, block_c=block_c, interpret=interpret,
    )


def decode_block_paged(
    h: jax.Array,
    weights: dict,
    pool_k: jax.Array,
    pool_v: jax.Array,
    k_scale: jax.Array | None,
    v_scale: jax.Array | None,
    tables: jax.Array,
    lengths: jax.Array,
    *,
    num_heads: int,
    window: int | None = None,
    kv_dtype: str = "bf16",
    compute_dtype=jnp.bfloat16,
    rope: bool = False,
    rope_base: float = 10000.0,
    interpret: bool | None = None,
):
    """One GPT block's fused single-token step against the PAGED pool:
    ``pool_k``/``pool_v`` [NB, bs, Hkv, Dh] (this layer's pool),
    ``k_scale``/``v_scale`` [NB, bs, Hkv] f32 or None, ``tables``
    [S, max_blocks] int32. The block tables ride as scalar-prefetch
    arguments and the pool gather happens in the grid index maps — the
    kernel DMAs the slot's table block by block, no contiguous view is
    ever materialized (nor does the XLA engine build one: it walks the
    resident blocks, ``ops/paged_attention.paged_decode_attention``).
    Validity is that step's absolute-position rule
    (``idx < length``, windowed ``idx > length − W``); unused table
    entries gather garbage blocks the mask keeps out of the softmax.
    Return contract matches :func:`decode_block_slab` (the caller
    commits via ``ops/paged_attention.scatter_token_kv``)."""
    return _block_call(
        h, weights, pool_k, pool_v, k_scale, v_scale, lengths, tables,
        num_heads=num_heads, window=window, rolling=False,
        kv_dtype=kv_dtype, compute_dtype=compute_dtype, rope=rope,
        rope_base=rope_base, block_c=None, interpret=interpret,
    )


def decode_token_slab(
    h: jax.Array,
    weights: dict,
    ck: jax.Array,
    cv: jax.Array,
    k_scale: jax.Array | None,
    v_scale: jax.Array | None,
    lengths: jax.Array,
    active: jax.Array,
    *,
    num_heads: int,
    window: int | None = None,
    kv_dtype: str = "bf16",
    compute_dtype=jnp.bfloat16,
    rope: bool = False,
    rope_base: float = 10000.0,
    block_c: int | None = None,
    interpret: bool | None = None,
):
    """The WHOLE model's fused single-token step over a SLAB cache —
    one launch per token (``decode_engine="pallas"``).

    ``h`` [S, d] f32 embedded token rows, ``weights`` the layer-STACKED
    parameter dict (every leaf leading [n_layers] — the streamed axis),
    ``ck``/``cv`` [n_layers, S, C, Hkv, Dh], scales
    [n_layers, S, C, Hkv] f32 or None, ``lengths`` [S] int32 write
    positions, ``active`` [S] bool (inactive rows compute but never
    commit — the scatter no-op, in-kernel). Returns
    ``(h_out [S, d] f32, ck', cv', k_scale', v_scale')`` with the fresh
    rows ALREADY committed at the XLA engine's exact indices."""
    ho, *caches = _call(
        h[:, None], weights, ck, cv, k_scale, v_scale, lengths,
        jnp.ones_like(lengths), active, None,
        num_heads=num_heads, window=window, rolling=window is not None,
        commit=True, kv_dtype=kv_dtype, compute_dtype=compute_dtype,
        rope=rope, rope_base=rope_base, block_c=block_c,
        interpret=interpret, name=names.KERNEL_DECODE_TOKEN,
    )
    return (ho[:, 0], *caches)


def decode_token_paged(
    h: jax.Array,
    weights: dict,
    pool_k: jax.Array,
    pool_v: jax.Array,
    k_scale: jax.Array | None,
    v_scale: jax.Array | None,
    tables: jax.Array,
    lengths: jax.Array,
    active: jax.Array,
    *,
    num_heads: int,
    window: int | None = None,
    kv_dtype: str = "bf16",
    compute_dtype=jnp.bfloat16,
    rope: bool = False,
    rope_base: float = 10000.0,
    interpret: bool | None = None,
):
    """Paged counterpart of :func:`decode_token_slab`:
    ``pool_k``/``pool_v`` [n_layers, NB, bs, Hkv, Dh] (scales one axis
    fewer), ``tables`` [S, max_blocks] int32 riding as scalar prefetch.
    The commit lands at ``(table[s, len // bs], len % bs)`` — inactive
    rows skip, the ``scatter_token_kv`` sentinel-drop semantics (the
    sentinel itself never materializes: no DMA is issued at all).
    Active slots never share writable blocks (the serve_pool allocator
    invariant), so in-kernel writes stay disjoint from every read."""
    ho, *caches = _call(
        h[:, None], weights, pool_k, pool_v, k_scale, v_scale, lengths,
        jnp.ones_like(lengths), active, tables,
        num_heads=num_heads, window=window, rolling=False, commit=True,
        kv_dtype=kv_dtype, compute_dtype=compute_dtype, rope=rope,
        rope_base=rope_base, block_c=None, interpret=interpret,
        name=names.KERNEL_DECODE_TOKEN,
    )
    return (ho[:, 0], *caches)


def verify_tokens_paged(
    h: jax.Array,
    weights: dict,
    pool_k: jax.Array,
    pool_v: jax.Array,
    k_scale: jax.Array | None,
    v_scale: jax.Array | None,
    tables: jax.Array,
    prefix_lens: jax.Array,
    suffix_lens: jax.Array,
    active: jax.Array,
    *,
    num_heads: int,
    window: int | None = None,
    kv_dtype: str = "bf16",
    compute_dtype=jnp.bfloat16,
    rope: bool = False,
    rope_base: float = 10000.0,
    interpret: bool | None = None,
):
    """Fused small-L speculation-verify over the paged pool — the whole
    model's ``extend_paged`` math in ONE launch (``decode_engine=
    "pallas"`` with ``spec_draft > 0``).

    ``h`` [S, L, d] f32 embedded draft rows (L ≤ spec_draft + 1),
    ``prefix_lens`` [S] committed lengths (positions for rows li are
    ``prefix + li``), ``suffix_lens`` [S] real suffix sizes (rows past
    them neither attend as keys nor commit), ``active`` [S] bool.
    Attention is causal WITHIN the suffix (folded into the softmax init)
    and STRICT ``idx < prefix_len`` over the pool; fresh K/V round-trips
    through the storage dtype before both attention and commit (the
    round-15 uniform rule — greedy-exact acceptance needs the verify
    pass to attend exactly what the decode pass will). Returns
    ``(h_out [S, L, d] f32, pool_k', pool_v', k_scale', v_scale')`` with
    valid rows committed at extend_paged's exact indices; lengths and
    tables stay caller-owned (the round-11 commit contract)."""
    return _call(
        h, weights, pool_k, pool_v, k_scale, v_scale, prefix_lens,
        suffix_lens, active, tables,
        num_heads=num_heads, window=window, rolling=False, commit=True,
        kv_dtype=kv_dtype, compute_dtype=compute_dtype, rope=rope,
        rope_base=rope_base, block_c=None, interpret=interpret,
        name=names.KERNEL_VERIFY_TOKENS,
    )
