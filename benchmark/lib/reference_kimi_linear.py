"""The plain reference of a ``kimi_linear`` stack: forward pass, loss,
gradients, in straightforward ``jax.numpy``, float32, ``highest`` matmul
precision. AdamW is ``lib/reference.adamw``.

No kernels, nothing imported from the program, and none of its
algorithms: the delta rule runs TOKEN BY TOKEN (a scan over blocks of 64
tokens, each block under ``jax.checkpoint``, of a scan over the block's
tokens: the backward pass would otherwise keep a 128 x 128 state per head
and token; the chunked WY form is the program's), every held expert is
applied to ALL rows and weighted by a dense [tokens, experts] matrix that
is zero where the token did not choose it (no sort, no ragged product),
attention is a plain masked softmax one head at a time. It follows the
published block (``model_type`` ``kimi_linear``); a published layer is two
residual sublayers, ``h += mixer(norm(h)); h += ffn(norm(h))``, each
RMSNorm with eps ``rms_norm_eps``:

- KDA mixer (layers in ``kda_layers``): ``q, k, v = silu(conv4(W u))``,
  causal depthwise, no bias; q and k L2-normalised per head, q times
  ``128^-1/2``; log-decay per head and KEY CHANNEL ``g = -exp(A_log) *
  softplus(W_fb (W_fa u) + dt_bias)``; ``beta = sigmoid(W_b u)`` per head;
  state ``S`` [128, 128] per head from zero: ``S' = Diag(exp g_t) S``,
  ``S = S' + beta_t k_t (v_t - S'^T k_t)^T``, ``o_t = S^T q_t``; output
  ``W_o(RMSNorm_head(o) * sigmoid(W_gb (W_ga u)))``;
- MLA mixer (layers in ``full_attn_layers``): ``q = W_q u`` per head (128
  + 64); ``[c | k_s] = W_dkv u`` (512 + 64), ``c <- RMSNorm(c)``, ``[k_n |
  v] = W_ukv c`` per head (128 + 128); ``k = [k_n | k_s]``, the 64-wide
  part shared by the heads and, ``mla_use_nope`` being true, NOT rotated;
  causal softmax of ``q.k / sqrt(192)``; ``W_o``;
- feed-forward ``W_down(silu(W_gate u) * W_up u)``: 9216 wide in the
  first ``first_k_dense_replace`` layers, after them the expert layer:
  float32 sigmoid scores over the whole router, the ``k`` largest ``score
  + bias`` chosen (one group), weights ``scale * score / (sum + 1e-20)``;
  with ``balance`` (a number of rounds) the choice is by each sequence's
  own balancing bias as ``lib/reference_nemotron_h`` finds it (by sorting);
  the experts HELD (``dims["held"]``) and the one shared expert are 1024
  wide; what the experts not held would add is left out, as in the program;
- final RMSNorm, untied head, mean next-token cross-entropy.

Departures from the published description (each also under ``assumed`` in
the configuration's file): the published q, k, v, ``W_fa``, ``W_ga`` and
``W_b`` are ONE matrix here, their columns side by side in that order
(``in_proj``: the same products), and the three convolutions one over the
concatenated channels; the rank of the two low-rank gates is 128; the L2
norm adds 1e-6 under the root.

``precision``: ``"float32"`` is the reference proper; ``"int8"`` is the
control (every projection's and expert's product with both operands
rounded to int8, forward and backward: ``lib/reference._mm``).
``fault`` plants one fault in the reference put in the program's place:
``"no_routed"`` (the routed experts' contribution left out),
``"no_carry"`` (the state not carried across the boundaries of 64-token
blocks), ``"no_delta"`` (``S = S' + beta k v^T``: a gated linear attention
under the model's name) or ``"no_shared_key"`` (the 64-wide shared part
left out of MLA's keys); the driver plants ``"half_batch"`` itself.
"""

from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp
from jax import lax

from benchmark.lib.reference import HI, _mm
from benchmark.lib.weights_kimi_linear import dims

BLOCK = 64  # tokens of the recurrence kept at a time in the backward pass
FAULTS = ("no_routed", "no_carry", "no_delta", "no_shared_key")


def _rms(x, w, eps):
    return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _delta_rule(q, k, v, g, beta, carry: bool, delta: bool):
    """q, k, g [b, l, H, K], v [b, l, H, V], beta [b, l, H] -> o [b, l, H,
    V], one token at a time."""
    b, l, h, width = q.shape
    blk = BLOCK if l % BLOCK == 0 else l  # a length that has no whole blocks

    def token(s, t):
        qt, kt, vt, gt, bt = t  # [b, H, K] x2, [b, H, V], [b, H, K], [b, H]
        s = jnp.exp(gt)[..., None] * s
        write = vt
        if delta:
            write = vt - jnp.einsum("bhkv,bhk->bhv", s, kt, precision=HI)
        s = s + (bt[..., None] * kt)[..., None] * write[:, :, None, :]
        return s, jnp.einsum("bhkv,bhk->bhv", s, qt, precision=HI)

    @jax.checkpoint
    def block(s, ts):
        if not carry:
            s = jnp.zeros_like(s)
        return lax.scan(token, s, ts)

    by_block = [jnp.moveaxis(t, 1, 0).reshape(l // blk, blk, *t.shape[:1],
                                              *t.shape[2:])
                for t in (q, k, v, g, beta)]
    _, o = lax.scan(
        block, jnp.zeros((b, h, width, v.shape[-1]), jnp.float32), by_block)
    return jnp.moveaxis(o.reshape(l, b, h, v.shape[-1]), 0, 1)


def _kda(h, p, z, precision, fault):
    b, l, _ = h.shape
    nh, hd, rank, inner = (z["kda_heads"], z["kda_head_dim"], z["gate_rank"],
                           z["kda_inner"])
    kern = z["conv_kernel"]
    heads = (b, l, nh, hd)
    proj = _mm(_rms(h, p["norm"], z["eps"]), p["in_proj"], precision)
    qkv, f, gate, beta = jnp.split(
        proj, [3 * inner, 3 * inner + rank, 3 * inner + 2 * rank], axis=-1)
    padded = jnp.pad(qkv, ((0, 0), (kern - 1, 0), (0, 0)))
    qkv = jax.nn.silu(sum(  # tap j sees the token kern-1-j back
        padded[:, j:j + l] * p["conv_w"][j] for j in range(kern)))
    q, k, v = (t.reshape(heads) for t in jnp.split(qkv, 3, axis=-1))
    q = q / jnp.sqrt(jnp.sum(q * q, -1, keepdims=True) + 1e-6) / jnp.sqrt(
        jnp.float32(hd))
    k = k / jnp.sqrt(jnp.sum(k * k, -1, keepdims=True) + 1e-6)
    g = -jnp.exp(p["a_log"])[:, None] * jax.nn.softplus(
        _mm(f, p["decay_up"], precision).reshape(heads)
        + p["dt_bias"].reshape(nh, hd))
    o = _delta_rule(q, k, v, g, jax.nn.sigmoid(beta),
                    carry=fault != "no_carry", delta=fault != "no_delta")
    o = _rms(o, p["out_norm"], z["eps"]) * jax.nn.sigmoid(
        _mm(gate, p["gate_up"], precision).reshape(heads))
    return _mm(o.reshape(b, l, inner), p["out_proj"], precision)


def _mla(h, p, z, precision, fault):
    b, l, _ = h.shape
    nh, nope, shared, vd = z["heads"], z["nope"], z["shared_k"], z["v_dim"]
    u = _rms(h, p["norm"], z["eps"])
    q = _mm(u, p["wq"], precision).reshape(b, l, nh, nope + shared)
    c, k_s = jnp.split(_mm(u, p["w_dkv"], precision), [z["kv_rank"]], axis=-1)
    kv = _mm(_rms(c, p["kv_norm"], z["eps"]), p["w_ukv"], precision)
    k_n, v = jnp.split(kv.reshape(b, l, nh, nope + vd), [nope], axis=-1)
    if fault == "no_shared_key":
        k_s = jnp.zeros_like(k_s)
    causal = jnp.tril(jnp.ones((l, l), bool))

    @jax.checkpoint
    def head(qkv):
        qh, kh, vh = qkv  # [b, l, 192], [b, l, 128], [b, l, 128]
        kh = jnp.concatenate([kh, k_s], axis=-1)
        s = jnp.einsum("bqd,bkd->bqk", qh, kh, precision=HI) / jnp.sqrt(
            jnp.float32(nope + shared))
        pr = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        return jnp.einsum("bqk,bkd->bqd", pr, vh, precision=HI)

    a = lax.map(head, tuple(jnp.moveaxis(t, 2, 0) for t in (q, k_n, v)))
    return _mm(jnp.moveaxis(a, 0, 2).reshape(b, l, nh * vd), p["wo"], precision)


def _ffn(u, gate, up, down, precision):
    return _mm(jax.nn.silu(_mm(u, gate, precision)) * _mm(u, up, precision),
               down, precision)


def _dense(h, p, z, precision):
    return _ffn(_rms(h, p["norm"], z["eps"]), p["w_gate"], p["w_up"],
                p["w_down"], precision)


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
    one_hot = jax.nn.one_hot(idx, z["experts"], dtype=jnp.float32)
    dense_w = jnp.einsum("tke,tk->te", one_hot, w)
    out = _ffn(u, p["shared_gate"], p["shared_up"], p["shared_down"], precision)
    if fault != "no_routed":
        first, count = z["held"]

        @jax.checkpoint
        def one(acc, e):
            gate, up, down, we = e
            return acc + we[:, None] * _ffn(u, gate, up, down, precision), None

        out, _ = lax.scan(one, out, (
            p["w_gate"], p["w_up"], p["w_down"],
            dense_w[:, first:first + count].T))
    return out.reshape(b, l, d), one_hot.sum((0, 1))


GROUP = {"K": "kda", "L": "mla", "D": "dense", "E": "moe"}


def per_layer(params: dict, z: dict) -> dict:
    """The per-kind stacks taken apart: ``layers`` holds each sublayer's
    own leaves in pattern order (a gradient taken with respect to these is
    made layer by layer, not as sums of whole zero-padded stacks)."""
    at = dict.fromkeys(GROUP, 0)
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
        if kind == "K":
            mixer = functools.partial(_kda, z=z, precision=precision, fault=fault)
        elif kind == "L":
            mixer = functools.partial(_mla, z=z, precision=precision, fault=fault)
        elif kind == "D":
            mixer = functools.partial(_dense, z=z, precision=precision)
        else:
            mixer = functools.partial(_experts, z=z, precision=precision,
                                      fault=fault, balance=balance)
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
