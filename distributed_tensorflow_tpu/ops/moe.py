"""Expert parallelism: a Switch-style top-k MoE FFN with all-to-all dispatch.

Absent from the reference (SURVEY.md §2b: no experts anywhere in the 6
files) but provided as first-class parallelism machinery, like tensor and
sequence parallelism: the ``expert`` mesh axis hosts one expert's weights
per device, tokens are routed by a learned gate and exchanged with a single
``lax.all_to_all`` each way — the EP pattern whose transport the reference
would have had to build from PS RPCs.

Semantics (chosen to be exactly reproducible by a dense reference, which is
how the tests validate the distributed path):

- top-k routing (``k=1`` default = Switch): each token goes to its ``k``
  highest gate logits. Combine weights are the router probabilities —
  raw for k=1 (Switch: out = p·expert(x), the gradient path into the
  gate), renormalized over the chosen experts for k≥2 (the standard
  top-2/Mixtral convention: Σ over chosen = 1);
- per-source-device capacity C: each device sends at most C of its local
  (token, choice) dispatches to each expert, keeping shapes static (XLA
  requirement). Slots fill in CHOICE-MAJOR order (every token's first
  choice before any second choice — GShard priority: a later token's
  second choice never evicts an earlier token's first choice); dispatches
  over capacity contribute zero (standard Switch overflow behavior);
- combined output = Σ_choices weight·expert_out, residual-friendly.

Call :func:`moe_ffn` inside ``jax.shard_map`` over the ``expert`` axis with
tokens sharded on the leading dim and expert weights stacked [E, ...]
sharded on dim 0. :func:`moe_ffn_dense` is the single-device reference.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from distributed_tensorflow_tpu.observability import names


class MoEParams(NamedTuple):
    wg: jax.Array  # [D, E] gate
    w_up: jax.Array  # [E, D, H] expert FFN up
    b_up: jax.Array  # [E, H]
    w_down: jax.Array  # [E, H, D] expert FFN down
    b_down: jax.Array  # [E, D]


class MoEAux(NamedTuple):
    """Router observability/trainability statistics, all scalar f32, computed
    over the tokens one ``moe_ffn*`` call routes:

    - ``balance_loss``: the Switch load-balancing auxiliary loss
      ``E · Σ_e f_e · P_e`` (f_e = fraction of tokens argmax-routed to
      expert e, P_e = mean router probability of e) — differentiable through
      P, minimized at 1.0 by uniform routing; without it nothing stops
      top-1 routing from collapsing onto one expert.
    - ``z_loss``: the ST-MoE router z-loss ``mean(logsumexp(logits)²)``,
      keeping gate logits small so bf16 routing stays stable.
    - ``drop_fraction``: fraction of tokens beyond expert capacity (passed
      through with zero expert contribution). NOT differentiable — a pure
      metric, and the observable guard on every "equal in the no-drop
      regime" claim (models/gpt.py ep==dense, dp==single-device).
    - ``expert_fraction``: the dispatch distribution f itself, [E] — the
      direct utilization readout (collapse shows as one entry → 1).
    """

    balance_loss: jax.Array
    z_loss: jax.Array
    drop_fraction: jax.Array
    expert_fraction: jax.Array

    @staticmethod
    def zero() -> "MoEAux":
        z = jnp.zeros((), jnp.float32)
        return MoEAux(z, z, z, z)


def init_moe(key, d: int, hidden: int, num_experts: int) -> MoEParams:
    k1, k2, k3 = jax.random.split(key, 3)
    return MoEParams(
        wg=jax.random.normal(k1, (d, num_experts), jnp.float32) / jnp.sqrt(d),
        w_up=jax.random.normal(k2, (num_experts, d, hidden), jnp.float32)
        / jnp.sqrt(d),
        b_up=jnp.zeros((num_experts, hidden), jnp.float32),
        w_down=jax.random.normal(k3, (num_experts, hidden, d), jnp.float32)
        / jnp.sqrt(hidden),
        b_down=jnp.zeros((num_experts, d), jnp.float32),
    )


def _expert_ffn(x, w_up, b_up, w_down, b_down):
    h = jax.nn.gelu(
        jnp.dot(x, w_up, preferred_element_type=jnp.float32) + b_up
    )
    return jnp.dot(h, w_down, preferred_element_type=jnp.float32) + b_down


def _route(x, wg, num_experts: int, capacity: int, token_mask=None, k: int = 1):
    """Shared top-k routing: returns (expert_idx [T, k], gate_w [T, k],
    slot [T, k], keep [T, k], aux :class:`MoEAux`) where slot is the
    (token, choice) dispatch's position in its (expert, source) capacity
    buffer and keep = slot < capacity.

    Combine weights ``gate_w``: the raw router probability for k=1 (Switch
    — out = p·expert(x) is the gradient path into the gate), probabilities
    renormalized over the k chosen experts for k≥2 (top-2/Mixtral
    convention). Capacity slots fill CHOICE-MAJOR (all first choices in
    token order, then all second choices, ...) — GShard priority: a later
    token's second choice never evicts an earlier token's first choice.

    ``token_mask`` [T] bool marks real tokens in a right-padded ragged
    batch: pad tokens are never dispatched (keep=False), never consume a
    capacity slot, and are excluded from every aux statistic — so ragged
    MoE batches are exactly pad-content-independent (without the mask, a
    pad token could displace a real one from its expert's queue and the
    balance/z losses would average over garbage)."""
    if not 1 <= k <= num_experts:
        raise ValueError(f"top-k k={k} must be in [1, num_experts={num_experts}]")
    t = x.shape[0]
    logits = jnp.dot(x, wg, preferred_element_type=jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    if k == 1:
        expert_idx = jnp.argmax(logits, axis=-1)[:, None]  # [T, 1]
    else:
        _, expert_idx = lax.top_k(logits, k)  # [T, k], rank order
    gate_w = jnp.take_along_axis(probs, expert_idx, axis=-1)  # [T, k]
    if k > 1:
        gate_w = gate_w / jnp.sum(gate_w, axis=-1, keepdims=True)
    onehot = jax.nn.one_hot(expert_idx, num_experts, dtype=jnp.int32)  # [T, k, E]
    if token_mask is not None:
        onehot = onehot * token_mask[:, None, None].astype(jnp.int32)
    # Queue position per (token, choice) dispatch: cumsum over the
    # choice-major flattening [k·T, E] (choice c of token t at row c·T+t).
    flat = onehot.swapaxes(0, 1).reshape(k * t, num_experts)
    slot_flat = (jnp.cumsum(flat, axis=0) - 1).reshape(k, t, num_experts)
    slot = jnp.take_along_axis(
        slot_flat.swapaxes(0, 1), expert_idx[:, :, None], axis=-1
    )[:, :, 0]  # [T, k]
    keep = slot < capacity
    if token_mask is not None:
        keep &= token_mask[:, None]
    # Aux statistics over this call's REAL dispatches. f rides
    # stop_gradient-free one_hot (int → no gradient anyway); the
    # differentiable path into the gate weights is P — the Switch
    # formulation, with f normalized over T·k dispatches for top-k (so
    # uniform routing still minimizes balance_loss at 1.0 for every k).
    lse2 = jax.scipy.special.logsumexp(logits, axis=-1) ** 2
    dispatch = jnp.sum(onehot, axis=1)  # [T, E] — how many choices hit e
    if token_mask is None:
        f = jnp.mean(dispatch.astype(jnp.float32), axis=0) / k  # [E]
        p_mean = jnp.mean(probs, axis=0)  # [E] mean router prob
        z = jnp.mean(lse2)
        kept = jnp.mean(keep.astype(jnp.float32))
    else:
        w = token_mask.astype(jnp.float32)
        denom = jnp.maximum(jnp.sum(w), 1.0)
        f = jnp.sum(dispatch.astype(jnp.float32), axis=0) / (denom * k)
        p_mean = jnp.sum(probs * w[:, None], axis=0) / denom
        z = jnp.sum(lse2 * w) / denom
        kept = jnp.sum(
            keep.astype(jnp.float32) * w[:, None]
        ) / (denom * k)
    aux = MoEAux(
        balance_loss=num_experts * jnp.sum(f * p_mean),
        z_loss=z,
        drop_fraction=1.0 - kept,
        expert_fraction=f,
    )
    return expert_idx, gate_w, slot, keep, aux


def _combine(gate_w, keep, gathered):
    """Weighted combine over the k choices: Σ_c keep_c·w_c·out_c.
    gate_w/keep: [T, k]; gathered: [T, k, D] → [T, D]."""
    w = jnp.where(keep, gate_w, 0.0)
    return jnp.einsum("tk,tkd->td", w, gathered)


def moe_ffn_dense(
    params: MoEParams,
    x: jax.Array,
    capacity: int,
    *,
    with_aux: bool = False,
    token_mask: jax.Array | None = None,
    k: int = 1,
):
    """Single-device reference with identical routing/drop semantics: every
    expert computed locally, per-expert capacity applied in dispatch order.
    ``with_aux=True`` also returns the router's :class:`MoEAux`;
    ``token_mask`` [T] bool excludes pad tokens from routing and ``k`` is
    the top-k routing width (see :func:`_route`)."""
    e = params.wg.shape[1]
    expert_idx, gate_w, _, keep, aux = _route(
        x, params.wg, e, capacity, token_mask, k=k
    )
    outs = jax.vmap(_expert_ffn, in_axes=(None, 0, 0, 0, 0))(
        x, params.w_up, params.b_up, params.w_down, params.b_down
    )  # [E, T, D]
    picked = outs[expert_idx, jnp.arange(x.shape[0])[:, None]]  # [T, k, D]
    out = _combine(gate_w, keep, picked)
    return (out, aux) if with_aux else out


def moe_ffn_local(
    params: MoEParams,
    x: jax.Array,
    capacity: int,
    *,
    with_aux: bool = False,
    token_mask: jax.Array | None = None,
    k: int = 1,
):
    """Single-device switch FFN at sparse cost: route, gather each expert's
    ≤``capacity`` dispatches into its buffer, run every expert ONCE on its
    buffer, scatter back. Identical semantics to :func:`moe_ffn_dense`
    (same ``_route``, same per-expert choice-major capacity — a single
    source makes per-source and global capacity the same thing) at
    ``E·capacity`` token-FFNs instead of dense's ``E·T`` — the sparse
    compute MoE exists for, without the cross-device exchange.
    ``with_aux=True`` also returns the router's :class:`MoEAux`;
    ``token_mask`` [T] bool excludes pad tokens from routing and ``k`` is
    the top-k routing width (see :func:`_route`)."""
    e = params.wg.shape[1]
    t, d = x.shape
    expert_idx, gate_w, slot, keep, aux = _route(
        x, params.wg, e, capacity, token_mask, k=k
    )

    send = jnp.zeros((e, capacity, d), x.dtype)
    rows = jnp.where(keep, expert_idx, 0)  # [T, k]
    cols = jnp.where(keep, slot, 0)
    contrib = jnp.where(keep[:, :, None], x[:, None, :], 0.0)  # [T, k, D]
    send = send.at[rows, cols].add(contrib)  # kept slots unique → add==set

    out = jax.vmap(_expert_ffn)(
        send, params.w_up, params.b_up, params.w_down, params.b_down
    )  # [E, C, D]
    gathered = out[rows, cols]  # [T, k, D]
    result = _combine(gate_w, keep, gathered)
    return (result, aux) if with_aux else result


def moe_ffn(
    params: MoEParams,
    x: jax.Array,
    axis_name: str,
    capacity: int,
    *,
    with_aux: bool = False,
    token_mask: jax.Array | None = None,
    k: int = 1,
):
    """Expert-parallel forward body (inside shard_map over ``axis_name``).

    ``x``: this device's local tokens [T_loc, D]. ``params.w_up`` etc. carry
    a leading [1, ...] slice — this device's expert. Returns [T_loc, D].
    ``with_aux=True`` also returns this device's router :class:`MoEAux`
    (local-token statistics; pmean over the axis for the global view);
    ``k`` is the top-k routing width (see :func:`_route` — k≥2 sends each
    token to up to k experts through the same two all-to-alls).
    """
    n = lax.axis_size(axis_name)
    t_loc, d = x.shape
    expert_idx, gate_w, slot, keep, aux = _route(
        x, params.wg, n, capacity, token_mask, k=k
    )

    # Build the outgoing buffers: for each destination expert e, a [C, D]
    # block of this device's dispatches routed to e (zeros elsewhere).
    send = jnp.zeros((n, capacity, d), x.dtype)
    rows = jnp.where(keep, expert_idx, 0)  # [T_loc, k]
    cols = jnp.where(keep, slot, 0)
    contrib = jnp.where(keep[:, :, None], x[:, None, :], 0.0)  # [T, k, D]
    send = send.at[rows, cols].add(contrib)  # kept slots unique → add==set

    # Exchange: device g's block e goes to device e (and we receive one
    # [C, D] block from every source) → [n, C, D] of tokens for OUR expert.
    recv = lax.all_to_all(send, axis_name, split_axis=0, concat_axis=0, tiled=True)

    # Run our expert on all received tokens.
    out = _expert_ffn(
        recv.reshape(n * capacity, d),
        params.w_up[0],
        params.b_up[0],
        params.w_down[0],
        params.b_down[0],
    ).reshape(n, capacity, d)

    # Return to senders and un-permute into token order.
    back = lax.all_to_all(out, axis_name, split_axis=0, concat_axis=0, tiled=True)
    gathered = back[rows, cols]  # [T_loc, k, D]
    result = _combine(gate_w, keep, gathered)
    return (result, aux) if with_aux else result


# -- the dropless layer, told which experts it holds ---------------------------
#
# The capacity layer above gives every expert a fixed buffer and drops what
# does not fit; the ``ep`` mode keeps it. The layer below has no capacity:
# every (token, choice) pair that lands on an expert held here is computed,
# whatever the routing. It is what one chip of an expert-parallel layer
# runs: it routes over ALL experts, is told ``first`` (and holds
# ``w_up.shape[0]`` experts from there), computes only the choices that
# landed on those, and adds nothing for the others. On one chip it runs
# without its exchange; nothing here stands in for the other chips.


def kth_largest(v, j: int):
    """The ``j``-th largest of ``v`` [r, n, e] over its axis 1 -> [r, e],
    by bisection on the value (32 halvings of [min, max], each a compare
    and a count over ``v``: no sort), then the smallest value that reaches
    the bound found — the order statistic itself, not a value near it."""
    lo, hi = jnp.min(v, axis=1), jnp.max(v, axis=1)

    def halve(_, bounds):
        lo, hi = bounds
        mid = 0.5 * (lo + hi)
        reached = jnp.sum(v >= mid[:, None], axis=1) >= j
        return jnp.where(reached, mid, lo), jnp.where(reached, hi, mid)

    lo, _ = lax.fori_loop(0, 32, halve, (lo, hi))
    return jnp.min(jnp.where(v >= lo[:, None], v, jnp.inf), axis=1)


def balancing_bias(logits, k: int, balance_tokens: int, rounds: int):
    """The bias that deals each run of ``balance_tokens`` tokens (a
    sequence) evenly over the experts when a token chooses its ``k``
    largest ``logit + bias``: a few rounds of an auction over the run's
    own logits. To begin, each expert's bias is minus its MARK, the logit
    that exactly an even share of the run's tokens (``balance_tokens * k
    / E``) reach. Then ``rounds`` times: every token draws its cut line
    between its ``k``-th and ``k+1``-th largest ``logit + bias``, and
    every expert marks itself anew against those lines, so that an even
    share of tokens would put it above their line. Logits that spread
    alike and apart from each other are dealt to 6% by the marks alone
    and to 2% after two rounds; the rounds matter where training has made
    the experts' logits move together over the tokens: with half of their
    spread in one direction the marks leave experts at 2 to 6 times their
    share, and a round takes about a third off the excess (PERF.md
    section 6, PR 28). Taken on the logits, not the scores: an expert
    pushed toward 0 or 1 keeps its spread there. A rule without memory:
    it follows a router and a
    residual stream that the optimizer moves by tenths a step, and a
    sequence needs no other sequence's logits, on this chip or another.
    No gradient passes. logits [T, E] -> bias [T, E] (a run's bias
    repeated over it)."""
    t, e = logits.shape
    runs = lax.stop_gradient(logits).reshape(
        t // balance_tokens, balance_tokens, e)
    even = max(1, balance_tokens * k // e)
    bias = -kth_largest(runs, even)
    for _ in range(rounds):
        top, _ = lax.top_k(runs + bias[:, None], k + 1)
        line = 0.5 * (top[..., k - 1] + top[..., k])
        bias = -kth_largest(runs - line[..., None], even)
    return jnp.broadcast_to(bias[:, None], runs.shape).reshape(t, e)


def route_sigmoid_topk(x, router, bias, k: int, scale: float,
                       balance: tuple[int, int] | None = None):
    """Sigmoid scores over all experts (float32); the ``k`` experts with
    the largest ``score + bias`` are chosen (``bias`` is a buffer: no
    gradient reaches it), and weighed by their *scores*, normalised over
    the chosen and times ``scale``. With ``balance`` = (tokens a run,
    rounds) the buffer is not read: the ``k`` largest ``logit +``
    :func:`balancing_bias` are chosen (``T`` is a multiple of the run),
    weighed by their scores as before. x [T, D], router [D, E], bias [E]
    -> (expert index [T, k] int32, weight [T, k] float32)."""
    f32 = jnp.float32
    logits = jnp.dot(
        x.astype(f32), router.astype(f32), precision=lax.Precision.HIGHEST)
    s = jax.nn.sigmoid(logits)
    by = (s + bias.astype(f32) if balance is None
          else logits + balancing_bias(logits, k, *balance))
    _, idx = lax.top_k(lax.stop_gradient(by), k)
    chosen = jnp.take_along_axis(s, idx, axis=-1)
    w = scale * chosen / (jnp.sum(chosen, axis=-1, keepdims=True) + 1e-20)
    return idx.astype(jnp.int32), w


def route_softmax_topk(x, router, k: int):
    """Softmax probabilities over all experts (float32); the ``k`` most
    probable chosen and weighed by their probabilities renormalised to
    sum 1 over the chosen (no bias, no scale). x [T, D], router [D, E] ->
    (expert index [T, k] int32, weight [T, k] float32)."""
    f32 = jnp.float32
    logits = jnp.dot(
        x.astype(f32), router.astype(f32), precision=lax.Precision.HIGHEST)
    p = jax.nn.softmax(logits, axis=-1)
    top, idx = lax.top_k(p, k)
    return idx.astype(jnp.int32), top / jnp.sum(top, axis=-1, keepdims=True)


def dispatch_held(idx, first: int, count: int, experts: int):
    """Which (token, choice) pairs landed on the ``count`` experts from
    ``first``, in expert order. idx [T, k] -> (``order`` [T*k]: the pair
    ids (token * k + choice) sorted by held expert, pairs that landed
    elsewhere last; ``load`` [experts] int32: pairs per expert, over ALL
    experts)."""
    flat = idx.reshape(-1)
    local = flat - first
    key = jnp.where((local >= 0) & (local < count), local, count)
    order = jnp.argsort(key, stable=True).astype(jnp.int32)
    load = jnp.sum(
        flat[:, None] == jnp.arange(experts, dtype=flat.dtype)[None, :],
        axis=0, dtype=jnp.int32)
    return order, load


def relu2_experts(rows, w_up, w_down, group_sizes, compute_dtype):
    """Each expert's rows through its own ``w_down relu(w_up x)^2`` (not
    gated): ``rows`` [M, D] sorted by expert, ``group_sizes`` [E_held]
    rows each; rows past their sum are not computed (read them as
    undefined). Operands in ``compute_dtype``, float32 accumulation."""
    cd = compute_dtype
    up = lax.ragged_dot(rows.astype(cd), w_up.astype(cd), group_sizes,
                        preferred_element_type=jnp.float32)
    act = jnp.square(jax.nn.relu(up))
    return lax.ragged_dot(act.astype(cd), w_down.astype(cd), group_sizes,
                          preferred_element_type=jnp.float32)


def silu_gated_experts(rows, w_gate, w_up, w_down, group_sizes, compute_dtype):
    """Each expert's rows through its own ``w_down (silu(w_gate x) *
    w_up x)`` (the gated form, three matrices): arguments and undefined
    rows as :func:`relu2_experts`."""
    cd = compute_dtype
    rows = rows.astype(cd)
    gate, up = (
        lax.ragged_dot(rows, w.astype(cd), group_sizes,
                       preferred_element_type=jnp.float32)
        for w in (w_gate, w_up))
    act = jax.nn.silu(gate) * up
    return lax.ragged_dot(act.astype(cd), w_down.astype(cd), group_sizes,
                          preferred_element_type=jnp.float32)


def held_block_rows(pairs: int, held: int, experts: int) -> int:
    """Rows of one block of the held experts' row buffer: twice what an
    even routing sends to ``held`` of ``experts``, in whole tiles of 512,
    at most every pair. A block size, not a capacity: rows beyond the
    first block are computed in further blocks (:func:`moe_ffn_held`)."""
    even = -(-2 * pairs * held // experts)
    return min(pairs, -(-even // 512) * 512)


def moe_ffn_held(x, idx, w, w_up, w_down, *, experts: int, first: int,
                 w_gate=None, compute_dtype=jnp.bfloat16,
                 block_rows: int | None = None, token_mask=None):
    """The routed part of a top-``k`` expert layer that this holder of
    experts ``first .. first + w_up.shape[0] - 1`` computes, given the
    routing (:func:`route_sigmoid_topk` or :func:`route_softmax_topk`).
    x [T, D], idx [T, k] int32 the chosen of all ``experts`` experts, w
    [T, k] their weights, w_up [E_held, D, F], w_down [E_held, F, D] ->
    (out [T, D] float32: the sum, over the choices that landed here, of
    weight * expert(x); load [experts] int32: the (token, choice) pairs
    that chose each expert, held or not — ``load[first:first + E_held]``
    landed here). The expert's form: ``w_down relu(w_up x)^2``
    (:func:`relu2_experts`), or with ``w_gate`` [E_held, D, F] the gated
    ``w_down (silu(w_gate x) * w_up x)`` (:func:`silu_gated_experts`).

    Dropless, with no capacity: the pairs that landed here, sorted by
    expert, are computed ``block_rows`` at a time (default
    :func:`held_block_rows`; tests set it to reach the loop at a size
    they can hold). Where they fit one block — the usual case — that
    block is the whole computation; where a routing sends more, a loop
    over as many blocks as hold every pair computes them all, each block
    recomputed in the backward pass so that memory stays one block's (as
    the only path the loop would recompute the usual case's products a
    third time under a layer's remat, or keep every block's residuals).
    Shapes are static either way, and only rows that landed here are
    multiplied: a step's time follows the load on the held experts.
    ``token_mask`` [T] bool (None: every token) leaves the tokens it is
    False for out of the experts and of ``load``: they are routed, land
    nowhere and get 0 (a serving step's vacant slots and pad positions)."""
    t, d = x.shape
    k = idx.shape[1]
    pairs = t * k
    count = w_up.shape[0]
    r = block_rows or held_block_rows(pairs, count, experts)
    nb = -(-pairs // r)
    with jax.named_scope(names.MOE_DISPATCH):
        order, load = dispatch_held(
            idx if token_mask is None
            else jnp.where(token_mask[:, None], idx, -1),
            first, count, experts)
        rows = load[first:first + count]
        order = jnp.pad(order, (0, nb * r - pairs))
        ends = jnp.cumsum(rows)
        landed = ends[-1]
        xc = x.astype(compute_dtype)
        w_flat = w.reshape(-1)

    def block(start):
        """[T, D]: what the pairs at sorted positions start .. start+r-1
        add."""
        with jax.named_scope(names.MOE_DISPATCH):
            ids = lax.dynamic_slice(order, (start,), (r,))
            here = start + jnp.arange(r) < landed
            tok = ids // k
            # The products leave rows past the landed ones undefined, in
            # the backward pass too: select, never multiply, them away.
            taken = jnp.where(here[:, None], xc[tok], 0)
            sizes = (jnp.clip(ends - start, 0, r)
                     - jnp.clip(ends - rows - start, 0, r))
        with jax.named_scope(names.MOE_EXPERTS):
            y = (relu2_experts(taken, w_up, w_down, sizes, compute_dtype)
                 if w_gate is None else silu_gated_experts(
                     taken, w_gate, w_up, w_down, sizes, compute_dtype))
        with jax.named_scope(names.MOE_DISPATCH):
            y = jnp.where(here[:, None], y, 0.0) * w_flat[ids][:, None]
            return jnp.zeros((t, d), jnp.float32).at[tok].add(y)

    def every_block():
        body = jax.checkpoint(block)
        return lax.fori_loop(
            0, nb, lambda b, acc: acc + body(b * r),
            jnp.zeros((t, d), jnp.float32))

    return lax.cond(landed <= r, lambda: block(0), every_block), load
