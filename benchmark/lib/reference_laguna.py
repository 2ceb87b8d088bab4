"""The plain reference of a ``laguna`` stack: one forward pass over a whole
sequence in straightforward ``jax.numpy``, float32, ``highest`` matmul
precision, nothing imported from the program.

No cache, no kernels, no paging. Attention is the dense softmax of each
query over the keys it may see: a full layer's every earlier key, in
blocks of queries so that 17k positions fit; a window layer's last
``window`` keys. Rotary: rotate-half pairs over a head's leading
``dims``, YaRN's frequencies for the full layers written out here from
the paper's ramp. Each head's output times the sigmoid of its gate before
``wo``. The experts run one at a time on the rows that chose them (the
router's choice is read back to size that, so nothing is dropped), float32
sigmoid router, top ``k``, weights renormalised and scaled. Weights come in
bfloat16 (``lib/weights_laguna.py``: a kind's leaf a list of its layers'
arrays, or one stacked array) and are widened to float32 here.

``precision``: ``"float32"`` is the reference proper; ``"int8"`` is the
control: both operands of every projection, expert and head product
rounded to int8 (weights per output channel, activations per row).
``fault`` plants one of :data:`FAULTS` in the reference, to show that the
comparison sees it:

- ``window_sees_all``: the window layers attend every earlier key;
- ``no_yarn``: the full layers turn by the plain rotation at their base,
  without YaRN's stretch or its attention factor;
- ``no_gate``: the heads' gates left out (every head times 1);
- ``no_routed``: the routed experts' part left out;
- ``ring_stale``: a window layer reads each key of its query's own block
  from a ring one turn stale (the row ``ring_rows`` positions earlier),
  as a ring addressed one turn off would.
"""

from __future__ import annotations

import functools
import math

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

HI = lax.Precision.HIGHEST
F32 = jnp.float32
FAULTS = ("window_sees_all", "no_yarn", "no_gate", "no_routed", "ring_stale")
QUERY_BLOCK = 512  # queries of a full layer's attention at a time
HEAD_BLOCK = 512  # positions of the head's logits at a time
_NEG = -1e30


def _int8(x, axis):
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    scale = jnp.where(scale == 0, 1.0, scale)
    return jnp.round(x / scale) * scale


def _mm(x, w, precision):
    x, w = x.astype(F32), w.astype(F32)
    if precision == "int8":
        x, w = _int8(x, -1), _int8(w, 0)
    return jnp.matmul(x, w, precision=HI)


def _norm(x, w, eps):
    return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w.astype(F32)


def inv_freq(rope: dict, fault=None) -> np.ndarray:
    """Angle per position of each rotated pair (float64 arithmetic)."""
    dims, theta = rope["dims"], rope["theta"]
    base = theta ** (-np.arange(0, dims, 2) / dims)
    if rope.get("factor", 1.0) <= 1.0 or fault == "no_yarn":
        return base

    def dim_at(turns):
        return dims * math.log(rope["original_len"] / (2 * math.pi * turns)) / (
            2 * math.log(theta))

    lo = max(math.floor(dim_at(rope["beta_fast"])), 0)
    hi = min(math.ceil(dim_at(rope["beta_slow"])), dims - 1)
    ramp = np.clip((np.arange(dims // 2) - lo) / max(hi - lo, 1e-3), 0, 1)
    return base * (1 - ramp) + base / rope["factor"] * ramp


def _rotate(x, rope: dict, fault=None):
    """x [L, H, Dh] at positions 0..L-1."""
    dims = rope["dims"]
    scale = 1.0 if fault == "no_yarn" else rope.get("attention_factor", 1.0)
    ang = jnp.arange(x.shape[0], dtype=F32)[:, None] * jnp.asarray(
        inv_freq(rope, fault), F32)
    cos, sin = (scale * jnp.cos(ang))[:, None], (scale * jnp.sin(ang))[:, None]
    a, b = x[..., : dims // 2], x[..., dims // 2: dims]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin, x[..., dims:]], -1)


def _full_attention(q, k, v):
    """Causal softmax, q [L, Hq, Dh], k/v [L, Hkv, Dh], queries in blocks."""
    l, hq, dh = q.shape
    g = hq // k.shape[1]
    kk, vv = jnp.repeat(k, g, axis=1), jnp.repeat(v, g, axis=1)
    qbl = _block(l, QUERY_BLOCK)

    def block(i):
        qb = lax.dynamic_slice_in_dim(q, i * qbl, qbl)
        s = jnp.einsum("qhd,khd->hqk", qb, kk, precision=HI) / math.sqrt(dh)
        qpos = i * qbl + jnp.arange(qbl)
        s = jnp.where(jnp.arange(l)[None, None] <= qpos[None, :, None], s, _NEG)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1), vv, precision=HI)

    return lax.map(block, jnp.arange(l // qbl)).reshape(l, hq, dh)


def _block(length: int, most: int) -> int:
    b = min(most, length)
    if length % b:
        raise ValueError(f"length {length} is no multiple of a block of {b}")
    return b


def _window_attention(q, k, v, window, stale_rows=None):
    """Each query over its last ``window`` keys. ``stale_rows``: the keys
    of the query's own block (of ``block`` positions) read ``ring_rows``
    positions back instead (the fault ``ring_stale``), given as
    (ring_rows, block)."""
    l, hq, dh = q.shape
    g = hq // k.shape[1]
    kk, vv = jnp.repeat(k, g, axis=1), jnp.repeat(v, g, axis=1)
    qbl = _block(l, QUERY_BLOCK)
    span = qbl + window
    pad = jnp.zeros((window,) + kk.shape[1:], F32)
    kp, vp = jnp.concatenate([pad, kk]), jnp.concatenate([pad, vv])
    if stale_rows is not None:
        back, blk = stale_rows
        zeros = jnp.zeros((back,) + kk.shape[1:], F32)
        ks = jnp.concatenate([pad, zeros, kk])[: l + window]
        vs = jnp.concatenate([pad, zeros, vv])[: l + window]

    def block(i):
        at = i * qbl
        qb = lax.dynamic_slice_in_dim(q, at, qbl)
        kb = lax.dynamic_slice_in_dim(kp, at, span)
        vb = lax.dynamic_slice_in_dim(vp, at, span)
        qpos = at + jnp.arange(qbl)[:, None]
        kpos = at - window + jnp.arange(span)[None, :]
        ok = (kpos >= 0) & (kpos <= qpos) & (kpos > qpos - window)
        s = jnp.einsum("qhd,khd->hqk", qb, kb, precision=HI) / math.sqrt(dh)
        if stale_rows is None:
            p = jax.nn.softmax(jnp.where(ok[None], s, _NEG), -1)
            return jnp.einsum("hqk,khd->qhd", p, vb, precision=HI)
        kst = lax.dynamic_slice_in_dim(ks, at, span)
        vst = lax.dynamic_slice_in_dim(vs, at, span)
        stale = (kpos // blk == qpos // blk) & (kpos < qpos)
        ok &= ~stale | (kpos - back >= 0)
        s2 = jnp.einsum("qhd,khd->hqk", qb, kst, precision=HI) / math.sqrt(dh)
        p = jax.nn.softmax(jnp.where(ok[None], jnp.where(stale[None], s2, s), _NEG), -1)
        return (jnp.einsum("hqk,khd->qhd", p * ~stale[None], vb, precision=HI)
                + jnp.einsum("hqk,khd->qhd", p * stale[None], vst, precision=HI))

    return lax.map(block, jnp.arange(l // qbl)).reshape(l, hq, dh)


@functools.partial(jax.jit, static_argnums=(2, 3, 4, 5, 6))
def _attention_layer(pick, h, kind, z_items, precision, fault, stale):
    z = dict(z_items)
    hd, kv = z["head_dim"], z["kv_heads"]
    heads = z["full_heads"] if kind == "G" else z["window_heads"]
    rope = dict(z["full_rope"] if kind == "G" else z["window_rope"])
    l = h.shape[0]
    u = _norm(h, pick["norm"], z["eps"])
    q = _mm(u, pick["wq"], precision).reshape(l, heads, hd)
    k = _mm(u, pick["wk"], precision).reshape(l, kv, hd)
    v = _mm(u, pick["wv"], precision).reshape(l, kv, hd)
    rot_fault = fault if kind == "G" else None
    q, k = _rotate(q, rope, rot_fault), _rotate(k, rope, rot_fault)
    if kind == "G" or fault == "window_sees_all":
        a = _full_attention(q, k, v)
    else:
        a = _window_attention(q, k, v, z["window"],
                              stale if fault == "ring_stale" else None)
    gate = jax.nn.sigmoid(_mm(u, pick["w_gate"], precision))
    if fault == "no_gate":
        gate = jnp.ones_like(gate)
    a = (a * gate[..., None]).reshape(l, heads * hd)
    return h + _mm(a, pick["wo"], precision)


def _gated_ffn(x, wg, wu, wd, precision):
    return _mm(jax.nn.silu(_mm(x, wg, precision)) * _mm(x, wu, precision),
               wd, precision)


@functools.partial(jax.jit, static_argnums=(2, 3))
def _dense_layer(p, h, z_items, precision):
    z = dict(z_items)
    u = _norm(h, p["norm"], z["eps"])
    return h + _gated_ffn(u, p["w_gate"], p["w_up"], p["w_down"], precision)


@functools.partial(jax.jit, static_argnums=(3,))
def _route(p, h, n, z_items):
    """Normed input, the chosen experts [L, k] (``E``, one past the last,
    for positions at or past ``n``) and their weights."""
    z = dict(z_items)
    u = _norm(h, p["norm"], z["eps"])
    s = jax.nn.sigmoid(jnp.matmul(u, p["router"].astype(F32), precision=HI))
    top, idx = lax.top_k(s, z["top_k"])
    w = z["routed_scale"] * top / jnp.sum(top, -1, keepdims=True)
    real = jnp.arange(h.shape[0])[:, None] < n
    return u, jnp.where(real, idx, z["experts"]), w


@functools.partial(jax.jit, static_argnums=(5, 6, 7, 8))
def _experts(p, h, u, idx, w, cap, z_items, precision, fault):
    """h + the shared expert + each routed expert on the rows that chose
    it, ``cap`` rows an expert at most (the caller read the largest)."""
    z = dict(z_items)
    l, k = idx.shape
    e = z["experts"]
    out = h + _gated_ffn(u, p["shared_gate"], p["shared_up"],
                         p["shared_down"], precision)
    if fault == "no_routed":
        return out
    flat = idx.reshape(-1)
    order = jnp.argsort(flat, stable=True)
    first = jnp.searchsorted(flat[order], jnp.arange(e))
    count = jnp.sum(flat[:, None] == jnp.arange(e)[None, :], 0)

    def one(x, acc):
        j = first[x] + jnp.arange(cap)
        ok = jnp.arange(cap) < count[x]
        pair = order[jnp.clip(j, 0, l * k - 1)]
        tok = pair // k
        rows = jnp.where(ok[:, None], u[tok], 0.0)
        y = _gated_ffn(rows, p["w_gate"][x], p["w_up"][x],
                       p["w_down"][x], precision)
        y = y * jnp.where(ok, w.reshape(-1)[pair], 0.0)[:, None]
        return acc.at[tok].add(y)

    return lax.fori_loop(0, e, one, out)


def final_hidden(weights, tokens, n, z, precision="float32", fault=None,
                 ring_rows=None, block_size=None):
    """tokens [L] (L a multiple of the blocks; real ones the first ``n``)
    -> the final normed hidden states [L, d] float32. ``ring_rows`` and
    ``block_size`` are the server's ring (rows a slot) and block, read by
    the fault ``ring_stale`` alone."""
    if fault is not None and fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}; one of {FAULTS}")
    items = tuple(sorted(
        (k, tuple(sorted(v.items())) if isinstance(v, dict) else v)
        for k, v in z.items()))
    h = weights["embed"][jnp.asarray(tokens)].astype(F32)
    stale = (ring_rows, block_size) if fault == "ring_stale" else None
    at = {"G": 0, "W": 0, "D": 0, "E": 0}
    group = {"G": "full", "W": "window", "D": "dense", "E": "moe"}
    n = jnp.int32(n)
    for kind in z["pattern"]:
        # one layer's leaves (a stacked leaf's slice, or a list's element)
        p = {k: v[at[kind]] for k, v in weights[group[kind]].items()}
        at[kind] += 1
        if kind in "GW":
            h = _attention_layer(p, h, kind, items, precision, fault, stale)
        elif kind == "D":
            h = _dense_layer(p, h, items, precision)
        else:
            u, idx, w = _route(p, h, n, items)
            most = int(np.bincount(np.asarray(idx).reshape(-1),
                                   minlength=z["experts"] + 1)[:-1].max())
            cap = 256  # a power of two at least the fullest expert's rows:
            while cap < most:  # few sizes, so few compiles
                cap *= 2
            h = _experts(p, h, u, idx, w, cap, items, precision, fault)
    return _norm(h, weights["norm_f"].astype(F32), z["eps"])


@functools.partial(jax.jit, static_argnums=(3,))
def head_scan(h, head, targets, precision="float32"):
    """Per position: (best logit, logit of ``targets``, argmax) of the
    head over the final hidden states ``h`` [L, d], in blocks."""
    hbl = _block(h.shape[0], HEAD_BLOCK)

    def block(b):
        hb = lax.dynamic_slice_in_dim(h, b * hbl, hbl)
        tb = lax.dynamic_slice_in_dim(targets, b * hbl, hbl)
        lg = _mm(hb, head, precision)
        return (lg.max(-1), jnp.take_along_axis(lg, tb[:, None], -1)[:, 0],
                jnp.argmax(lg, -1).astype(jnp.int32))

    best, picked, top = lax.map(block, jnp.arange(h.shape[0] // hbl))
    return best.reshape(-1), picked.reshape(-1), top.reshape(-1)
