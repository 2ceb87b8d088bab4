"""Low-precision (int8 / fp8) matmuls and KV-cache quantization.

The reference squeezes throughput out of fixed hardware by restructuring
the training step — its whole experiment table is async-vs-sync modes ×
worker counts at fixed wall-clock (reference README.md:166-254, the
multi-ps × multi-worker benchmark grid; no reference analog exists at
the arithmetic level, TF1 ran f32 throughout). This module is the same
theme one layer down:
the v5e MXU's native low-precision regime is int8 (double the bf16
TOPS), and fp8 (e4m3) rides the same hardware path. ``quantized_dot``
computes the forward contraction in the reduced dtype with
full-precision accumulation and SYMMETRIC dynamic scales — per
activation ROW and per weight COLUMN, the standard dynamic-quantization
recipe, so one outlier row/column cannot crush everyone else's
resolution — while the backward is the exact full-precision matmul
transpose via a straight-through estimator: quantization noise perturbs
the forward only, and gradients flow as if the matmul were exact (the
standard quantized-training recipe; W8A8 dynamic, LLM.int8()/SmoothQuant
lineage). The consumer contract is ``GPTLM(matmul_dtype=)`` — opt-in,
guarded by the synthetic-corpus loss-parity test in
tests/test_quantized.py.

Scope note: this is a *dot wrapper*, not a Pallas kernel — XLA lowers an
int8×int8→int32 ``dot_general`` straight onto the MXU's int8 path on
TPU, so there is nothing for a custom kernel to add at these shapes; on
CPU (tests) the same graph runs through XLA's emulation bit-exactly.

Round 15 adds the INFERENCE-side primitives (ISSUE 11 — decode is
HBM-traffic-bound, so serving bytes ≈ latency AND capacity):

- :func:`quantize_kv` / :func:`dequantize_kv` — symmetric per-ROW scales
  (one f32 scale per written cache position per KV head, amax over the
  head_dim lane; the write-local granularity, so a decode step's single
  token row never re-scales — and therefore never perturbs — previously
  written positions). Scales are SMALL SIDE TENSORS riding beside the
  cache (``head_dim × elem_bytes / 4`` smaller than the payload), never
  packed into the block — the paged pool's gather/scatter index math
  applies to them unchanged, and COW prefix sharing shares them with the
  block (``models/gpt.py`` cache structs, ``serve.py kv_dtype=``).
- :class:`QuantizedLinear` + :func:`quantize_linear_columns` +
  :func:`wo_dot` — weight-only quantization for the decode projections
  (AWQ/vLLM inference lineage): weights pre-quantized ONCE at restore
  with per-output-column symmetric scales, activations stay full
  precision, no STE — forward-only by construction
  (``GPTLM.decode_weights``). The claim is bandwidth, not FLOPs: decode
  reads every weight per token, so int8 weights halve the other half of
  decode's HBM traffic (not measured on the chip, like
  ``matmul_dtype``).
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp

_EPS = 1e-12
# Largest representable magnitudes the scales map amax onto: int8's 127,
# float8_e4m3fn's largest normal 448.
_QMAX = {"int8": 127.0, "fp8": 448.0}

MATMUL_DTYPES = tuple(_QMAX)


def _amax_scale(x, axis, qmax):
    """Symmetric dynamic scale mapping max|x| over ``axis`` onto qmax
    (floored at eps so all-zero rows/columns quantize to zeros instead
    of NaNs)."""
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=axis, keepdims=True)
    return jnp.maximum(amax, _EPS) / qmax


def _quantize(xs, dtype: str, qmax: float):
    """The ONE symmetric quantize step (pre-scaled ``xs = x/scale`` →
    stored values): int8 rounds-and-clips, fp8 casts (the cast carries
    rounding; e4m3 covers |x| ≤ 448 post-scale). Shared by the training
    dot, the KV cache, and the weight-only path so their rounding
    semantics cannot drift apart."""
    if dtype == "int8":
        return jnp.clip(jnp.round(xs), -qmax, qmax).astype(jnp.int8)
    return xs.astype(jnp.float8_e4m3fn)


def _qdot_impl(dtype: str, x, w):
    if dtype not in _QMAX:
        raise ValueError(
            f"unknown matmul dtype {dtype!r}; one of {MATMUL_DTYPES}"
        )
    qmax = _QMAX[dtype]
    sx = _amax_scale(x, -1, qmax)  # [..., 1]   per activation row
    sw = _amax_scale(w, 0, qmax)  # [1, N]     per weight column
    xs = x.astype(jnp.float32) / sx
    ws = w.astype(jnp.float32) / sw
    xq = _quantize(xs, dtype, qmax)
    wq = _quantize(ws, dtype, qmax)
    if dtype == "int8":
        # int8×int8 → int32 accumulation: the MXU-native pass.
        acc = jnp.dot(
            xq, wq, preferred_element_type=jnp.int32
        ).astype(jnp.float32)
    else:
        acc = jnp.dot(xq, wq, preferred_element_type=jnp.float32)
    return acc * sx * sw  # dequantize: [..., 1] × [1, N] broadcast


@partial(jax.custom_vjp, nondiff_argnums=(0,))
def quantized_dot(dtype: str, x, w):
    """``x [..., K] @ w [K, N]`` with the contraction in ``dtype``
    (``"int8"`` or ``"fp8"``), f32 result — dynamic symmetric scales per
    activation row and weight column. Differentiable via the
    straight-through estimator: both gradients are the exact f32 matmul
    transposes of the UNquantized operands (residuals x, w), so
    quantization error never enters the backward. Under GSPMD the scale
    reductions partition like the dot itself (a row-sharded weight's
    per-column amax becomes one all-reduce-max)."""
    return _qdot_impl(dtype, x, w)


def _qdot_fwd(dtype, x, w):
    return _qdot_impl(dtype, x, w), (x, w)


def _qdot_bwd(dtype, res, g):
    x, w = res
    gf = g.astype(jnp.float32)
    dx = jnp.dot(gf, w.astype(jnp.float32).T).astype(x.dtype)
    g2 = gf.reshape(-1, gf.shape[-1])
    x2 = x.astype(jnp.float32).reshape(-1, x.shape[-1])
    dw = jnp.dot(x2.T, g2).astype(w.dtype)
    return dx, dw


quantized_dot.defvjp(_qdot_fwd, _qdot_bwd)


# -- per-tensor delta compression (round 17) -------------------------------
# The coarsest scale granularity in the family: ONE symmetric f32 scale
# per tensor. Too coarse for weights/activations (a single outlier row
# crushes resolution — hence the per-row/per-column training scales
# above), but exactly right for the DiLoCo outer pseudo-gradient
# (train/local_sgd.py delta_dtype=): the payload crossing the gang's
# wire is a whole parameter tree whose per-tensor dynamic range is
# narrow, the scale overhead must stay negligible (4 bytes per TENSOR,
# not per row), and the error-feedback residual re-injects whatever the
# coarse scale loses.


def quantize_tensor(x, dtype: str):
    """Quantize a whole tensor symmetrically: ``x`` → ``(q, scale)`` with
    ONE f32 scale (amax over every element, floored at eps so an all-zero
    tensor quantizes to zeros). ``dtype`` is ``"int8"`` or ``"fp8"``;
    rounding semantics are the shared :func:`_quantize` step, so this
    cannot drift from the training dot or the KV cache."""
    if dtype not in _QMAX:
        raise ValueError(
            f"unknown tensor dtype {dtype!r}; one of {MATMUL_DTYPES}"
        )
    qmax = _QMAX[dtype]
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)))
    scale = jnp.maximum(amax, _EPS) / qmax
    return _quantize(x.astype(jnp.float32) / scale, dtype, qmax), scale


def dequantize_tensor(q, scale, out_dtype=jnp.float32):
    """Inverse of :func:`quantize_tensor`: ``q × scale`` in
    ``out_dtype``."""
    return (q.astype(jnp.float32) * scale).astype(out_dtype)


# -- inference-side KV-cache quantization (round 15) -----------------------

# Serving cache dtypes: "bf16" is the identity layout (the cache stores
# the model's compute_dtype, scales absent — the round-11 bitwise path);
# int8/fp8 store 1-byte elements plus the per-row scale side tensor.
KV_DTYPES = ("bf16",) + MATMUL_DTYPES


def kv_storage_dtype(kv_dtype: str, compute_dtype):
    """The jnp dtype a ``kv_dtype`` cache stores its K/V payload in."""
    if kv_dtype == "bf16":
        return compute_dtype
    if kv_dtype == "int8":
        return jnp.int8
    if kv_dtype == "fp8":
        return jnp.float8_e4m3fn
    raise ValueError(f"unknown kv dtype {kv_dtype!r}; one of {KV_DTYPES}")


def kv_elem_bytes(kv_dtype: str, compute_dtype) -> int:
    """Bytes per stored K/V element (the serve_pool HBM accounting)."""
    return jnp.dtype(kv_storage_dtype(kv_dtype, compute_dtype)).itemsize


def quantize_kv(x, kv_dtype: str):
    """Quantize K or V rows ``[..., Dh]`` → ``(q [..., Dh], scale [...])``
    with one symmetric f32 scale per row (amax over the last axis — per
    cache position per KV head). Row granularity is what makes the
    serving cache write-local: a decode step quantizes exactly the rows
    it writes; nothing already resident is ever re-scaled. A row whose
    amax is a power of two holds an EXACTLY representable scale, so
    integer-valued ``x/scale`` round-trips bit-exactly (the equality
    oracle in tests/test_serve_quantized.py)."""
    qmax = _QMAX.get(kv_dtype)
    if qmax is None:
        raise ValueError(
            f"quantize_kv needs a quantized dtype, one of {MATMUL_DTYPES}; "
            f"got {kv_dtype!r}"
        )
    scale = _amax_scale(x, -1, qmax)  # [..., 1]
    q = _quantize(x.astype(jnp.float32) / scale, kv_dtype, qmax)
    return q, scale[..., 0]


def dequantize_kv(q, scale, out_dtype=jnp.float32):
    """Inverse of :func:`quantize_kv`: ``q [..., Dh]`` × ``scale [...]``
    → ``[..., Dh] out_dtype``. Works on any gathered view of the cache —
    the scale tensor is indexed by exactly the same (block, position,
    head) coordinates as the payload, minus the lane axis."""
    return (q.astype(jnp.float32) * scale[..., None]).astype(out_dtype)


# -- weight-only decode matmuls (round 15) ---------------------------------


class QuantizedLinear(NamedTuple):
    """A pre-quantized projection weight: ``qw [..., K, N]`` int8/fp8 with
    per-output-column f32 ``scale [..., N]`` (symmetric — dequantization
    is ``qw · scale``, no zero point). Produced once at restore by
    :func:`quantize_linear_columns` / ``GPTLM.decode_weights``; consumed
    by :func:`wo_dot` wherever ``GPTLM._dot`` meets one. Leading axes
    (the scanned ``num_layers`` stack) ride through untouched."""

    qw: jax.Array
    scale: jax.Array


def quantize_linear_columns(w, dtype: str) -> QuantizedLinear:
    """Quantize a weight ``[..., K, N]`` with one symmetric scale per
    output column (amax over the contraction axis — the round-13
    ``quantized_dot`` weight-side granularity, so one outlier column
    cannot crush the rest)."""
    if dtype not in _QMAX:
        raise ValueError(
            f"unknown weight dtype {dtype!r}; one of {MATMUL_DTYPES}"
        )
    qmax = _QMAX[dtype]
    scale = _amax_scale(w, -2, qmax)  # [..., 1, N]
    q = _quantize(w.astype(jnp.float32) / scale, dtype, qmax)
    return QuantizedLinear(qw=q, scale=scale[..., 0, :])


def wo_dot(x, qw, scale, compute_dtype=jnp.bfloat16):
    """Weight-only quantized matmul: ``x [..., K]`` (full precision) @
    pre-quantized ``qw [K, N]`` with per-column ``scale [N]`` → f32.
    The contraction runs in ``compute_dtype`` (int8/fp8 upcast exactly —
    |q| ≤ 448 — so the only approximation is the one already committed
    at quantization time) and the column scales fold in AFTER the f32
    accumulation. Forward-only by design: this is an inference
    primitive; training keeps :func:`quantized_dot`'s STE."""
    acc = jnp.dot(
        x.astype(compute_dtype),
        qw.astype(compute_dtype),
        preferred_element_type=jnp.float32,
    )
    return acc * scale
