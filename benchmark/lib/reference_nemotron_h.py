"""The plain reference of a ``nemotron_h`` stack: forward pass, loss,
gradients, in straightforward ``jax.numpy``, float32, ``highest`` matmul
precision. AdamW is ``lib/reference.adamw``.

No kernels, nothing imported from the program, and none of its
algorithms: the state-space recurrence runs TOKEN BY TOKEN (a scan over
the chunks of a scan over a chunk's tokens, each chunk under
``jax.checkpoint``; the chunked "duality" form is the program's), every
held expert is applied to ALL rows and weighted by a dense [tokens,
experts] matrix that is zero where the token did not choose it (no sort,
no ragged product), attention is a plain masked softmax one head at a
time. It follows the published block (``model_type`` ``nemotron_h``):

- every layer is one mixer behind one RMSNorm, ``h <- h + mixer(norm(h))``;
- ``M``: ``[z | xBC | dt] = in_proj(u)``; causal depthwise convolution with
  bias, silu; ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t (outer) B_t``,
  ``y_t = S_t C_t + D x_t`` with ``dt = softplus(dt + dt_bias)``,
  ``A = -exp(A_log)``; RMSNorm over each group of ``y * silu(z)``;
  ``out_proj``;
- ``E``: sigmoid scores over the whole router, the ``k`` largest
  ``score + bias`` chosen, weights ``scale * score / (sum + 1e-20)``; with
  ``balance`` (a number of rounds) the buffer is not read: the ``k``
  largest ``logit + b`` are chosen, ``b`` found for each sequence by
  itself: minus each expert's mark (the logit that exactly an even share
  of the sequence's tokens, ``L * k / experts``, reach), then that many
  rounds in which every token draws its line halfway between its ``k``-th
  and ``k+1``-th largest ``logit + b`` and every expert's ``b`` becomes
  minus its mark over ``logit - line`` (order statistics by sorting);
  ``down relu(up u)^2`` for the experts HELD (``dims["held"]``: the share
  of the deployment) and for the shared expert; what the experts not held
  would add is left out, as in the program;
- ``*``: grouped-query causal softmax attention, no bias, no rotary;
- final RMSNorm, untied head, mean next-token cross-entropy.

``precision``: ``"float32"`` is the reference proper; ``"int8"`` is the
control (every projection's and expert's product with both operands
rounded to int8, forward and backward: ``lib/reference._mm``).
``fault`` plants one fault in the reference put in the program's place:
``"no_routed"`` (the routed experts' contribution left out) or
``"no_carry"`` (the state not carried across chunk boundaries); the
driver plants ``"half_batch"`` itself.
"""

from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp
from jax import lax

from benchmark.lib.reference import HI, _mm
from benchmark.lib.weights_nemotron_h import dims


def _rms(x, w, eps):
    return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _relu2(x):
    return jnp.square(jnp.maximum(x, 0.0))


def _recurrence(x, dt, a, bm, cm, chunk, carry: bool):
    """x [b, l, H, P], dt [b, l, H], a [H], bm / cm [b, l, G, N] (head i
    reads group i // (H / G)) -> y [b, l, H, P], one token at a time, one
    row after the other."""
    _, l, h, p = x.shape
    g, n = bm.shape[-2:]
    q = chunk if l % chunk == 0 else l  # a length that has no whole chunks

    @jax.checkpoint
    def token(s, t):
        xt, dtt, bt, ct = t  # [H, P], [H], [G, N], [G, N]
        bt, ct = (jnp.repeat(v, h // g, axis=0) for v in (bt, ct))
        s = (jnp.exp(dtt * a)[:, None, None] * s
             + (dtt[:, None] * xt)[:, :, None] * bt[:, None, :])
        return s, jnp.einsum("hpn,hn->hp", s, ct, precision=HI)

    @jax.checkpoint
    def one_chunk(s, ts):
        if not carry:
            s = jnp.zeros_like(s)
        return lax.scan(token, s, ts)

    def row(ts):
        by_chunk = [t.reshape(l // q, q, *t.shape[1:]) for t in ts]
        _, y = lax.scan(one_chunk, jnp.zeros((h, p, n), jnp.float32), by_chunk)
        return y.reshape(l, h, p)

    return lax.map(row, (x, dt, bm, cm))


def _mamba(h, p, z, precision, fault):
    b, l, _ = h.shape
    nh, hd, g, n = z["ssm_heads"], z["ssm_head_dim"], z["ssm_groups"], z["ssm_state"]
    inner, cdim, k = z["inner"], z["conv_dim"], z["conv_kernel"]
    proj = _mm(_rms(h, p["norm"], z["eps"]), p["in_proj"], precision)
    gate, xbc, dt = jnp.split(proj, [inner, inner + cdim], axis=-1)
    padded = jnp.pad(xbc, ((0, 0), (k - 1, 0), (0, 0)))
    xbc = jax.nn.silu(p["conv_b"] + sum(  # tap j sees the token k-1-j back
        padded[:, j:j + l] * p["conv_w"][j] for j in range(k)))
    x, bm, cm = jnp.split(xbc, [inner, inner + g * n], axis=-1)
    x = x.reshape(b, l, nh, hd)
    y = _recurrence(
        x, jax.nn.softplus(dt + p["dt_bias"]), -jnp.exp(p["a_log"]),
        bm.reshape(b, l, g, n), cm.reshape(b, l, g, n), z["chunk"],
        carry=fault != "no_carry")
    y = (y + p["d_skip"][:, None] * x).reshape(b, l, inner) * jax.nn.silu(gate)
    y = _rms(y.reshape(b, l, g, inner // g), 1.0, z["eps"]).reshape(b, l, inner)
    return _mm(y * p["gate_norm"], p["out_proj"], precision)


def _experts(h, p, z, precision, fault, balance):
    b, l, d = h.shape
    u = _rms(h, p["norm"], z["eps"]).reshape(b * l, d)
    logit = jnp.matmul(u, p["router"], precision=HI)
    s = jax.nn.sigmoid(logit)
    by = s + p["router_bias"]
    if balance is not None:
        k, share = z["top_k"], max(1, l * z["top_k"] // z["experts"])
        seqs = lax.stop_gradient(logit).reshape(b, l, -1)
        mark = lambda v: -jnp.sort(-v, axis=1)[:, share - 1]  # noqa: E731  [b, experts]
        corr = -mark(seqs)
        for _ in range(balance):
            ranked = -jnp.sort(-(seqs + corr[:, None]), axis=2)
            line = 0.5 * (ranked[..., k - 1] + ranked[..., k])
            corr = -mark(seqs - line[..., None])
        by = logit + jnp.repeat(corr, l, axis=0)
    _, idx = lax.top_k(lax.stop_gradient(by), z["top_k"])
    chosen = jnp.take_along_axis(s, idx, axis=-1)
    w = z["routed_scale"] * chosen / (chosen.sum(-1, keepdims=True) + 1e-20)
    # [tokens, experts]: a token's weight for an expert, 0 where not chosen.
    dense_w = jnp.einsum(
        "tke,tk->te", jax.nn.one_hot(idx, z["experts"], dtype=jnp.float32), w)
    out = _mm(_relu2(_mm(u, p["shared_up"], precision)), p["shared_down"],
              precision)
    if fault != "no_routed":
        first, count = z["held"]

        @jax.checkpoint
        def one(acc, e):
            up, down, we = e
            y = _mm(_relu2(_mm(u, up, precision)), down, precision)
            return acc + we[:, None] * y, None

        out, _ = lax.scan(one, out, (
            p["w_up"], p["w_down"], dense_w[:, first:first + count].T))
    load = jax.nn.one_hot(idx, z["experts"], dtype=jnp.float32).sum((0, 1))
    return out.reshape(b, l, d), load


def _attention(h, p, z, precision):
    b, l, _ = h.shape
    nh, nkv, hd = z["heads"], z["kv_heads"], z["head_dim"]
    u = _rms(h, p["norm"], z["eps"])
    q = _mm(u, p["wq"], precision).reshape(b, l, nh, hd)
    k = _mm(u, p["wk"], precision).reshape(b, l, nkv, hd)
    v = _mm(u, p["wv"], precision).reshape(b, l, nkv, hd)
    k, v = (jnp.repeat(t, nh // nkv, axis=2) for t in (k, v))
    causal = jnp.tril(jnp.ones((l, l), bool))

    @jax.checkpoint
    def head(qkv):
        qh, kh, vh = qkv  # [b, l, hd]
        s = jnp.einsum("bqd,bkd->bqk", qh, kh, precision=HI) / jnp.sqrt(
            jnp.float32(hd))
        pr = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        return jnp.einsum("bqk,bkd->bqd", pr, vh, precision=HI)

    a = lax.map(head, tuple(jnp.moveaxis(t, 2, 0) for t in (q, k, v)))
    return _mm(jnp.moveaxis(a, 0, 2).reshape(b, l, nh * hd), p["wo"], precision)


GROUP = {"M": "mamba", "E": "moe", "*": "attn"}


def per_layer(params: dict, z: dict) -> dict:
    """The per-kind stacks taken apart: ``layers`` holds each layer's own
    leaves in pattern order (a gradient taken with respect to these is
    made layer by layer, not as sums of whole zero-padded stacks)."""
    at = {"M": 0, "E": 0, "*": 0}
    layers = []
    for kind in z["pattern"]:
        layers.append({k: v[at[kind]] for k, v in params[GROUP[kind]].items()})
        at[kind] += 1
    return {"embed": params["embed"], "layers": layers,
            "norm_f": params["norm_f"], "head": params["head"]}


def stacked(tree: dict, like: dict, z: dict) -> dict:
    """The inverse of :func:`per_layer` (``like`` gives the leaves of a
    kind that has no layer)."""
    out = {k: tree[k] for k in ("embed", "norm_f", "head")}
    for kind, group in GROUP.items():
        mine = [p for p, c in zip(tree["layers"], z["pattern"]) if c == kind]
        out[group] = {
            k: jnp.stack([p[k] for p in mine]) if mine else jnp.zeros_like(v)
            for k, v in like[group].items()}
    return out


def forward(params, tokens, z, precision="float32", fault=None, balance=None):
    """tokens [B, L] -> (logits [B, L, vocab], load [E layers, experts]:
    the (token, choice) pairs that chose each expert); ``params`` stacked
    per kind or already :func:`per_layer`."""
    if "layers" not in params:
        params = per_layer(params, z)
    h = params["embed"][tokens]
    loads = []
    for kind, p in zip(z["pattern"], params["layers"]):
        if kind == "M":
            mixer = functools.partial(_mamba, z=z, precision=precision, fault=fault)
        elif kind == "E":
            mixer = functools.partial(_experts, z=z, precision=precision,
                                      fault=fault, balance=balance)
        else:
            mixer = functools.partial(_attention, z=z, precision=precision)
        out = jax.checkpoint(mixer)(h, p)
        if kind == "E":
            out, load = out
            loads.append(load)
        h = h + out
    lg = jnp.matmul(_rms(h, params["norm_f"], z["eps"]), params["head"],
                    precision=HI)
    return lg, (jnp.stack(loads) if loads else jnp.zeros((0, z["experts"])))


def logits(params, tokens, z, precision="float32", fault=None, balance=None):
    return forward(params, tokens, z, precision, fault, balance)[0]


def loss_and_load(params, tokens, z, precision="float32", fault=None,
                  balance=None):
    """(mean next-token cross-entropy over positions 0..L-2, the load)."""
    lg, load = forward(params, tokens, z, precision, fault, balance)
    lg = lg[:, :-1]
    lse = jax.scipy.special.logsumexp(lg, axis=-1)
    picked = jnp.take_along_axis(lg, tokens[:, 1:, None], axis=-1)[..., 0]
    return jnp.mean(lse - picked), lax.stop_gradient(load)


def loss(params, tokens, z, precision="float32", fault=None, balance=None):
    return loss_and_load(params, tokens, z, precision, fault, balance)[0]


@functools.lru_cache(maxsize=8)
def _loss_and_grad(cfg_json: str, rows: int, precision: str, fault, balance):
    z = dims(json.loads(cfg_json))

    @jax.jit
    def run(params, tokens):
        blocks = tokens.reshape(-1, rows, tokens.shape[-1])
        apart = per_layer(params, z)
        grad = lambda blk: jax.value_and_grad(loss_and_load, has_aux=True)(  # noqa: E731
            apart, blk, z, precision, fault, balance)
        if blocks.shape[0] == 1:  # no sum to hold beside the gradient
            (l, load), g = grad(blocks[0])
            return l, stacked(g, params, z), load

        def body(acc, blk):
            (l, load), g = grad(blk)
            return (acc[0] + l, jax.tree.map(jnp.add, acc[1], g), acc[2] + load), None

        n = blocks.shape[0]
        (l, g, load), _ = lax.scan(
            body, (jnp.float32(0), jax.tree.map(jnp.zeros_like, apart),
                   jnp.zeros((z["n_e"], z["experts"]))), blocks)
        return l / n, stacked(jax.tree.map(lambda x: x / n, g), params, z), load

    return run


def loss_and_grad(params, tokens, cfg: dict, rows: int,
                  precision="float32", fault=None, balance=None):
    """Loss and gradient of the mean over all rows of ``tokens``, taken
    ``rows`` rows at a time so that the activations fit, and the load
    (pairs per expert, per E layer) over all of them."""
    return _loss_and_grad(
        json.dumps(cfg, sort_keys=True), rows, precision, fault, balance)(
            params, tokens)
