"""Local-SGD / DiLoCo outer loop for the LM family — the paper's async
thesis at LM scale.

The reference's signature result is that ASYNC parameter-server training
beats sync at fixed wall-clock because workers apply updates the moment
they have them instead of waiting for the slowest peer (reference
tfdist_between.py:64-66, README.md:66-74; reproduced by our oracles:
async 0.8156 vs sync 0.618 @ 2 workers/100 epochs,
tools/parity_converged.py). ``make_lm_async_parts`` carries that claim to
the GPT family as per-chip copies exchanging at the mean. This module is
the *communication-reducing* modern form of the same thesis — local-SGD
with a DiLoCo-style outer optimizer (Douillard et al. 2023):

- each worker runs ``sync_every`` = H **inner** steps with the ordinary
  inner optimizer on its own data shard (zero cross-worker traffic);
- the gang then applies ONE **outer** update from the pseudo-gradient

      Δ = θ_start − mean_w(θ_w)

  through Nesterov momentum:  m ← μ·m + Δ;  θ ← θ_start − η_out·(Δ + μ·m)
  (``nesterov=False`` uses the heavy-ball form θ ← θ_start − η_out·m);
  every worker copy then jumps to the new θ, which becomes the next
  round's θ_start.

That is H× fewer all-reduce rounds per token than sync dp — and, since
every dispatch carries a fixed host cost, the outer round is also the
natural dispatch unit, so comm reduction and dispatch amortization
compound (the whole H-step round rides the scanned-epoch ``lax.scan``
machinery as part of one dispatch).

``outer_lr`` defaults to **N (the worker count)** — the same convention
as ``AsyncDataParallel``/``make_lm_async_parts``'s ``update_scale=N``
(parallel/strategy.py:451-470): the reference PS applied all N workers'
updates *sequentially* to one parameter set, moving it N× the mean
worker movement per exchange; Δ is exactly the mean worker movement, so
``outer_lr=N`` with the default ``outer_momentum=0`` reproduces the
sequential-apply semantics, while ``outer_lr=1`` is pure local-SGD
averaging. DiLoCo-paper settings are the explicit opt-in —
``outer_lr≈0.7-1.0, outer_momentum=0.9`` — used by the convergence
record (an N× step COMPOUNDED by momentum is sanctioned by neither
regime and measurably overshoots, hence the momentum-free default).

Degenerate anchor: at ``sync_every=1, outer_lr=1, outer_momentum=0`` the
outer update IS the per-step parameter mean — the computation is
implemented to reduce to exactly ``pmean(θ_w)`` in that corner (see
:func:`outer_update`), which makes it bitwise-identical to the async
exchange (``make_lm_async_parts`` with ``avg_every=1, update_scale=1``)
and — for SGD, which is linear in the gradient — equal to the sync
data-parallel step up to float reassociation (both pinned in
tests/test_local_sgd.py).

Two engines, one math:

- :func:`make_lm_diloco_parts` — the gang on a live mesh: ``shard_map``
  over the data axis, per-worker copies as [n, ...] stacked leaves (the
  ``make_lm_async_parts`` layout), outer state replicated.
- :func:`make_lm_diloco_vmapped` — the same gang as ONE single-device
  program (``jax.vmap`` over the worker axis). Mathematically the same
  update; runs on any jax, including degraded containers without the
  mesh APIs — the engine ``tools/diloco_bench.py`` uses for the CPU
  perplexity record, and the LMTrainer's ``dp_mode="diloco"`` fallback
  when no mesh is given (``TrainConfig.diloco_workers``).

Round 17 — streaming/compressed DiLoCo (all levers default-off; the
round-14 path above stays bitwise):

- **Compressed deltas** (``delta_dtype="int8"|"fp8"``): the outer
  pseudo-gradient is quantized per-TENSOR
  (``ops/quantized.quantize_tensor``) before it crosses the wire, with
  an error-feedback residual carried in :class:`DiLoCoState` — each
  round compresses ``Δ + residual`` and keeps ``(Δ + residual) − Δ̂``
  for the next one, so compression error is deferred, never lost
  (1-bit-SGD/EF-SGD lineage). One byte per element + one f32 scale per
  tensor ≈ another 4× comm reduction on top of H×
  (:func:`delta_payload_nbytes` is the accounting).
- **Overlapped exchange** (``overlap=True``): the delta computed at a
  boundary goes IN FLIGHT and the completed outer update applies one
  round late — workers never wait on the all-reduce, because the value
  being applied finished exchanging during the round that just ran. In
  a real gang the payload streams as layer-wise partitions spread over
  the H inner steps (:func:`streaming_schedule` is that comm plan); the
  engines realize the algorithm's math (the stale apply), which is
  identical whether the partitions land mid-round or all at the next
  boundary. The in-flight state rides :class:`DiLoCoState` — dense,
  world-invariant, resize-safe like θ_start/momentum. Semantics that
  made it CONVERGE (measured; :func:`outer_round_step` docstring): the
  pseudo-gradient is the mean round MOVEMENT (landing-mean based, not
  anchor based) and workers MERGE toward the stale-applied anchor
  (:data:`OVERLAP_MERGE`) instead of resetting; halve the outer
  momentum under overlap (the one-round delay compounds it — μ=0.9
  diverges, μ≈0.4-0.5 matches the non-overlapped row).
- **Stale-tolerant gang** (:class:`DeltaExchange` +
  ``TrainConfig.stale_limit``): the synchronous engines above exchange
  in-graph (every worker at the same boundary); the mailbox exchange
  moves the outer round to the HOST — each member posts its
  (compressed) delta to a shared directory at its own boundary and
  applies the outer update from whatever peers have posted, weighting a
  delta that is ``age`` rounds old by ``1/(1+age)``
  (:func:`staleness_weight`) and dropping anything older than
  ``stale_limit``. A throttled member therefore contributes stale
  deltas instead of stalling the gang — the PS async thesis, third
  incarnation (PS → DiLoCo → stale-tolerant DiLoCo). Member anchors may
  transiently differ (each applies its own arrival view — exactly the
  reference PS's async parameter drift); checkpoints/eval are
  per-member as in any async mode.
"""

from __future__ import annotations

from functools import partial
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from distributed_tensorflow_tpu.train import failpoints, resilience


class DiLoCoState(NamedTuple):
    """The ``opt_state`` slot of a diloco-mode ``TrainState``.

    ``inner`` are the per-worker inner optimizer states ([n, ...] stacked
    leaves, sharded/vmapped over the worker axis — they persist ACROSS
    outer rounds, the DiLoCo recipe); ``theta`` is the outer anchor
    θ_start (dense parameter shapes, replicated) and ``momentum`` the
    outer Nesterov buffer (same shapes). ``theta``/``momentum`` are
    world-size-invariant, which is what lets an elastic resize carry the
    outer state across a world change (train/lm_trainer.py).

    Round 17: ``residual`` is the error-feedback residual of the
    compressed-delta lever (dense parameter shapes; ``None`` when
    ``delta_dtype`` is off) and ``inflight`` the overlapped exchange's
    in-flight state — a dict ``{"delta": Δ̂, "landing": L}`` of the
    pending outer pseudo-gradient and the mean point the worker copies
    landed on at the last boundary (both dense; ``None`` when
    ``overlap`` is off). All are world-size-invariant like
    θ_start/momentum, so a diloco→diloco elastic resize carries them
    VERBATIM; ``None`` fields are empty pytree nodes — with the levers
    off, the state's leaves (and therefore its checkpoints) are
    byte-identical to round 14."""

    inner: Any
    theta: Any
    momentum: Any
    residual: Any = None
    inflight: Any = None


def outer_update(
    theta,
    mean_params,
    momentum,
    *,
    outer_lr: float,
    outer_momentum: float,
    nesterov: bool = True,
):
    """One outer apply: ``(θ_start, mean_w(θ_w), m) → (θ', m')``.

    Pseudo-gradient Δ = θ_start − mean_params; m' = μ·m + Δ; the applied
    step is Δ + μ·m' (Nesterov) or m' (heavy-ball); θ' = θ_start −
    η_out·step. ``outer_lr``/``outer_momentum`` are trace-time Python
    floats: the ``outer_lr==1 and outer_momentum==0`` corner is
    specialized to ``θ' = mean_params`` — algebraically identical
    (θ − 1·(θ − mean) = mean) and, as floats, EXACTLY the parameter mean,
    which is what makes ``sync_every=1`` degenerate bitwise to the async
    per-step exchange (module docstring)."""
    mu = float(outer_momentum)
    eta = float(outer_lr)
    delta = jax.tree.map(lax.sub, theta, mean_params)
    if eta == 1.0 and mu == 0.0:
        return mean_params, delta
    return outer_apply(
        theta,
        delta,
        momentum,
        outer_lr=eta,
        outer_momentum=mu,
        nesterov=nesterov,
    )


def outer_apply(
    theta,
    delta,
    momentum,
    *,
    outer_lr: float,
    outer_momentum: float,
    nesterov: bool = True,
):
    """The outer optimizer on an explicit pseudo-gradient:
    ``(θ_start, Δ, m) → (θ', m')`` — the half of :func:`outer_update`
    below the Δ computation, factored out for the round-17 levers (a
    compressed Δ̂ or a one-round-stale in-flight Δ is applied through
    exactly the same Nesterov recurrence)."""
    mu = float(outer_momentum)
    eta = float(outer_lr)
    new_m = (
        jax.tree.map(lambda m, d: mu * m + d, momentum, delta)
        if mu != 0.0
        else delta
    )
    if nesterov:
        step = (
            jax.tree.map(lambda d, m: d + mu * m, delta, new_m)
            if mu != 0.0
            else delta
        )
    else:
        step = new_m
    new_theta = jax.tree.map(lambda t, s: t - eta * s, theta, step)
    return new_theta, new_m


def compress_delta(delta, residual, delta_dtype: str):
    """Error-feedback compression of the outer pseudo-gradient: quantize
    ``Δ + residual`` per-tensor (``ops/quantized.quantize_tensor`` —
    one symmetric f32 scale per tensor, the wire format) and carry the
    quantization error forward: ``residual' = (Δ + residual) − Δ̂``.
    Returns ``(Δ̂, residual')`` — what the gang applies, and what the
    next round re-injects. Elementwise and replicated-in/replicated-out,
    so it composes under both engines unchanged."""
    from distributed_tensorflow_tpu.ops.quantized import (
        dequantize_tensor,
        quantize_tensor,
    )

    corr = jax.tree.map(lax.add, delta, residual)

    def roundtrip(x):
        q, s = quantize_tensor(x, delta_dtype)
        return dequantize_tensor(q, s, x.dtype)

    dhat = jax.tree.map(roundtrip, corr)
    new_residual = jax.tree.map(lax.sub, corr, dhat)
    return dhat, new_residual


# Streaming-merge mixing factor: how far an overlapped boundary pulls
# each worker copy toward the stale-applied global anchor (0 = keep
# local, 1 = full reset — the streaming-DiLoCo merge knob). Measured at
# toy scale (8-epoch copy-corpus grid, docs/benchmarks/diloco.md):
# α=0.25 with outer momentum ≈0.4-0.5 matches or beats the
# non-overlapped row (ppl 7.07-7.20 vs 7.25), α=0.75 and full reset
# degrade sharply (9.6 / 17.7-213). Both engines read THIS constant so
# they cannot drift.
OVERLAP_MERGE = 0.25


def outer_round_step(
    theta,
    mean_params,
    momentum,
    residual,
    inflight,
    *,
    outer_lr: float,
    outer_momentum: float,
    nesterov: bool = True,
    delta_dtype: str | None = None,
    overlap: bool = False,
):
    """ONE outer round under the round-17 levers, shared verbatim by both
    engines (a divergence here would split their proven equality):
    ``(θ_start, mean_w(θ_w), m, r, f) → (θ', m', r', f')``.

    With both levers off this IS :func:`outer_update` (trace-time Python
    branch — the round-14 path stays bitwise, including the
    ``outer_lr=1, μ=0`` mean specialization). ``delta_dtype`` routes the
    pseudo-gradient through :func:`compress_delta` (EF residual);
    ``overlap`` applies the IN-FLIGHT delta from the previous boundary
    and stashes this round's (compressed) delta in its place — the first
    boundary applies a zero delta, so the outer trajectory trails one
    round behind, which is exactly the slack a real gang's all-reduce
    hides behind the next H inner steps.

    Overlap semantics (both measured into shape at toy scale —
    docs/benchmarks/diloco.md):

    - the pseudo-gradient is the gang's mean ROUND MOVEMENT,
      ``Δ_r = L_{r-1} − mean_w(θ_w)`` with ``L`` the mean point the
      copies LANDED on at the previous boundary (carried in
      ``inflight["landing"]``) — measuring against the outer anchor θ
      instead (the non-overlapped definition) injects an
      anchor-mismatch term once workers stop starting rounds AT θ;
    - the engines MERGE instead of reset: ``θ_w ← (1−α)·θ_w + α·θ'``
      with ``α`` = :data:`OVERLAP_MERGE` (the streaming-DiLoCo merge) —
      a full reset to the one-round-stale θ' discards every round's
      fresh progress until its delta lands and measurably oscillates
      (ppl 17.7–213 vs 7.2 across outer settings when probed); the
      merge keeps the local half and pulls the copies geometrically
      toward the common anchor (dispersion × (1−α) per round).
    ``L`` updates to the mean of the merged landing points,
    ``(1−α)·mean + α·θ'`` — at ``α=1`` the whole scheme degenerates to
    the anchor-based reset form."""
    if delta_dtype is None and not overlap:
        theta2, m2 = outer_update(
            theta,
            mean_params,
            momentum,
            outer_lr=outer_lr,
            outer_momentum=outer_momentum,
            nesterov=nesterov,
        )
        return theta2, m2, residual, inflight
    if overlap:
        delta = jax.tree.map(
            lax.sub, inflight["landing"], mean_params
        )
    else:
        delta = jax.tree.map(lax.sub, theta, mean_params)
    if delta_dtype is not None:
        delta, residual = compress_delta(delta, residual, delta_dtype)
    if overlap:
        theta2, m2 = outer_apply(
            theta,
            inflight["delta"],
            momentum,
            outer_lr=outer_lr,
            outer_momentum=outer_momentum,
            nesterov=nesterov,
        )
        a = OVERLAP_MERGE
        landing = jax.tree.map(
            lambda mp, t2: (1.0 - a) * mp + a * t2, mean_params, theta2
        )
        inflight = {"delta": delta, "landing": landing}
    else:
        theta2, m2 = outer_apply(
            theta,
            delta,
            momentum,
            outer_lr=outer_lr,
            outer_momentum=outer_momentum,
            nesterov=nesterov,
        )
    return theta2, m2, residual, inflight


def resolve_outer_lr(outer_lr: float | None, num_workers: int) -> float:
    """The ONE place the ``None → N`` default lives (the
    ``update_scale=N`` convention both async APIs share — module
    docstring); both engines and the trainer's comm accounting route
    through it so they cannot drift."""
    return float(num_workers) if outer_lr is None else float(outer_lr)


def sync_rounds_between(count0: int, count1: int, sync_every: int) -> int:
    """Outer rounds fired by steps ``count0 .. count1-1`` (global step
    counter semantics: step ``t`` fires the exchange iff
    ``(t+1) % sync_every == 0`` — the ``make_lm_async_parts`` cadence).
    Host-side mirror of the traced predicate, used by the trainer's
    per-epoch comm accounting (``comm_stats`` journal events)."""
    if sync_every < 1:
        raise ValueError(f"sync_every must be >= 1, got {sync_every}")
    return count1 // sync_every - count0 // sync_every


def params_nbytes(params) -> int:
    """Bytes of ONE dense parameter set — the payload of one outer
    all-reduce round (sync dp moves the same bytes per STEP as gradient
    traffic; the ratio is the H× headline). Works on concrete arrays and
    ShapeDtypeStructs alike."""
    return int(
        sum(
            x.size * jnp.dtype(x.dtype).itemsize
            for x in jax.tree.leaves(params)
        )
    )


def delta_payload_nbytes(params, delta_dtype: str | None) -> int:
    """Bytes ONE outer delta actually puts on the wire: the dense payload
    (:func:`params_nbytes`) at ``delta_dtype=None``, else one byte per
    element plus one f32 scale per tensor (the per-tensor symmetric wire
    format of :func:`compress_delta`). Works on concrete arrays and
    ShapeDtypeStructs alike — the trainer's ``comm_stats`` accounting
    and the :class:`DeltaExchange` file payloads both measure THIS."""
    if delta_dtype is None:
        return params_nbytes(params)
    if delta_dtype not in ("int8", "fp8"):
        raise ValueError(
            f"delta_dtype must be None, 'int8', or 'fp8'; got "
            f"{delta_dtype!r}"
        )
    leaves = jax.tree.leaves(params)
    return int(sum(x.size for x in leaves) + 4 * len(leaves))


def staleness_weight(age: int, stale_limit: int) -> float:
    """Weight of a delta that is ``age`` outer rounds old: ``1/(1+age)``
    inside the tolerance window, 0.0 beyond it (and for negative ages —
    a peer cannot be fresher than the boundary consuming it; the
    exchange clamps ahead-of-round posts to age 0 before calling).
    ``stale_limit=0`` admits same-round deltas only."""
    if age < 0 or age > stale_limit:
        return 0.0
    return 1.0 / (1.0 + age)


def streaming_schedule(
    params, sync_every: int, partitions: int | None = None
) -> list[dict]:
    """The overlapped exchange's comm plan: the outer delta partitioned
    LAYER-WISE (leaf order, greedy byte-balanced into ``partitions``
    groups — default one per leaf, capped at H) with each partition's
    all-reduce issued at an inner-step offset spread across the next
    round. Returns ``[{"partition", "leaves", "nbytes", "issue_step"},
    ...]`` with ``issue_step`` in ``[0, sync_every)``.

    This is the SCHEDULE a multi-host deployment issues so the payload
    streams while compute runs; the engines' math is independent of it —
    every partition completes within the round, so applying the
    assembled delta at the next boundary (what :func:`outer_round_step`
    does) is value-identical to consuming partitions as they land."""
    if sync_every < 1:
        raise ValueError(f"sync_every must be >= 1, got {sync_every}")
    leaves = jax.tree.leaves(params)
    if not leaves:
        return []
    if partitions is None:
        partitions = min(len(leaves), sync_every)
    partitions = max(1, min(int(partitions), len(leaves)))
    sizes = [
        int(x.size * jnp.dtype(x.dtype).itemsize) for x in leaves
    ]
    # Greedy balance in leaf order: start a new partition when the
    # current one holds its fair share (layer-wise contiguity preserved —
    # a partition is a run of adjacent leaves, i.e. adjacent layers).
    total = sum(sizes)
    target = total / partitions
    groups: list[list[int]] = [[]]
    acc = 0
    for i, nb in enumerate(sizes):
        remaining_groups = partitions - (len(groups) - 1)
        remaining_leaves = len(sizes) - i
        if (
            groups[-1]
            and acc + nb / 2 >= target
            and remaining_groups > 1
            and remaining_leaves >= remaining_groups
        ):
            groups.append([])
            acc = 0
        groups[-1].append(i)
        acc += nb
    plan = []
    for k, idxs in enumerate(groups):
        plan.append(
            {
                "partition": k,
                "leaves": len(idxs),
                "nbytes": int(sum(sizes[i] for i in idxs)),
                # Spread issue points over the round: partition k fires
                # after inner step floor(k·H/P) of the next round.
                "issue_step": (k * sync_every) // len(groups),
            }
        )
    return plan


def _local_inner_step(model, optimizer, ragged: bool):
    """One worker's inner step — shared verbatim by both engines (a
    divergence here would silently split their proven equality)."""
    import optax

    def step(p, o, tokens, lens):
        loss_fn = (
            (lambda q: model.loss(q, tokens, lens))
            if ragged
            else (lambda q: model.loss(q, tokens))
        )
        loss, grads = jax.value_and_grad(loss_fn)(p)
        updates, o = optimizer.update(grads, o, p)
        p = optax.apply_updates(p, updates)
        return p, o, loss

    return step


def make_lm_diloco_parts(
    model,
    optimizer,
    mesh,
    *,
    axis: str = "data",
    sync_every: int,
    outer_lr: float | None = None,
    outer_momentum: float = 0.0,
    nesterov: bool = True,
    ragged: bool = False,
    delta_dtype: str | None = None,
    overlap: bool = False,
):
    """DiLoCo building blocks on a live mesh (the LMTrainer's
    ``dp_mode="diloco"`` engine) — same contract as
    :func:`~models.gpt.make_lm_async_parts`: returns ``(init_state,
    mapped)`` where

    - ``init_state(params, opt_state) -> (stacked_params, DiLoCoState,
      count)`` — per-worker copies ([n, ...] leaves sharded over
      ``axis``), outer anchor θ_start = params and zero momentum
      (replicated), plus the step counter the exchange keys on;
    - ``mapped(stacked_params, dstate, tokens, lens, count) ->
      (stacked_params, dstate, loss)`` — NOT jitted (call it inside your
      own jit/scan); tokens [n·B, L] sharded on the batch dim; loss is
      the cross-worker mean of the local losses.

    The exchange is a ``lax.cond`` keyed on the replicated ``count`` (the
    all-reduce fires only on round boundaries — a ``where`` would void
    the traffic bound, same trap as the async exchange).
    ``delta_dtype``/``overlap`` are the round-17 levers (module
    docstring), realized in the shared :func:`outer_round_step`; their
    state (EF residual, in-flight delta) rides the replicated half of
    ``DiLoCoState`` and is absent (None) when the levers are off."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from distributed_tensorflow_tpu.models.gpt import _default_lens
    from distributed_tensorflow_tpu.ops.collectives import to_varying

    if sync_every < 1:
        raise ValueError(f"sync_every must be >= 1, got {sync_every}")
    n = mesh.shape[axis]
    eta = resolve_outer_lr(outer_lr, n)
    step_fn = _local_inner_step(model, optimizer, ragged)

    def init_state(params, opt_state):
        stacked = jax.tree.map(
            lambda x: jnp.broadcast_to(x[None], (n,) + x.shape),
            (params, opt_state),
        )
        stacked = jax.device_put(stacked, NamedSharding(mesh, P(axis)))
        repl = NamedSharding(mesh, P())
        theta = jax.device_put(params, repl)
        m = jax.device_put(jax.tree.map(jnp.zeros_like, params), repl)
        zeros = lambda: jax.device_put(  # noqa: E731
            jax.tree.map(jnp.zeros_like, params), repl
        )
        return (
            stacked[0],
            DiLoCoState(
                stacked[1],
                theta,
                m,
                zeros() if delta_dtype is not None else None,
                # Round 0: nothing in flight, every copy lands on θ_0
                # (a COPY — an alias of theta would donate the same
                # buffer twice under the scanned path's donate_argnums).
                {"delta": zeros(), "landing": jax.tree.map(jnp.copy, theta)}
                if overlap
                else None,
            ),
            jnp.zeros((), jnp.int32),
        )

    def local(params, inner, theta, m, residual, inflight, tokens, lens,
              count):
        p = jax.tree.map(lambda x: x[0], params)
        o = jax.tree.map(lambda x: x[0], inner)
        p, o, loss = step_fn(p, o, tokens, lens if ragged else None)
        pvary = partial(to_varying, axis_name=(axis,))

        def exchange(args):
            p, theta, m, residual, inflight = args
            # pmean outputs are typed invariant — exactly right for the
            # outer state (replicated, like residual/inflight, which
            # stay invariant through the elementwise round step); the
            # worker copy is re-cast to varying so both cond branches
            # agree under check_vma (the make_lm_async_parts pattern).
            pbar = jax.tree.map(lambda x: lax.pmean(x, axis), p)
            theta2, m2, r2, f2 = outer_round_step(
                theta,
                pbar,
                m,
                residual,
                inflight,
                outer_lr=eta,
                outer_momentum=outer_momentum,
                nesterov=nesterov,
                delta_dtype=delta_dtype,
                overlap=overlap,
            )
            if overlap:
                # Streaming merge (module constant OVERLAP_MERGE): keep
                # the local half — a full reset to the one-round-stale
                # anchor discards this round's progress until its delta
                # lands (it measurably oscillates; outer_round_step
                # docstring).
                target = jax.tree.map(
                    lambda local, t2: (1.0 - OVERLAP_MERGE) * local
                    + OVERLAP_MERGE * pvary(t2),
                    p,
                    theta2,
                )
            else:
                target = jax.tree.map(pvary, theta2)
            return target, theta2, m2, r2, f2

        p, theta, m, residual, inflight = lax.cond(
            (count + 1) % sync_every == 0,
            exchange,
            lambda args: args,
            (p, theta, m, residual, inflight),
        )
        return (
            jax.tree.map(lambda x: x[None], p),
            jax.tree.map(lambda x: x[None], o),
            theta,
            m,
            residual,
            inflight,
            lax.pmean(loss, axis),
        )

    lens_spec = (P(axis),) if ragged else (P(),)
    inner_fn = jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(P(axis), P(axis), P(), P(), P(), P(), P(axis))
        + lens_spec
        + (P(),),
        out_specs=(P(axis), P(axis), P(), P(), P(), P(), P()),
    )

    def mapped(params, dstate, tokens, lens, count):
        if lens is None:
            lens = _default_lens(tokens, ragged)
        p, inner, theta, m, residual, inflight, loss = inner_fn(
            params, dstate.inner, dstate.theta, dstate.momentum,
            dstate.residual, dstate.inflight, tokens, lens, count,
        )
        return p, DiLoCoState(inner, theta, m, residual, inflight), loss

    return init_state, mapped


def make_lm_diloco_vmapped(
    model,
    optimizer,
    num_workers: int,
    *,
    sync_every: int,
    outer_lr: float | None = None,
    outer_momentum: float = 0.0,
    nesterov: bool = True,
    ragged: bool = False,
    delta_dtype: str | None = None,
    overlap: bool = False,
):
    """The same DiLoCo gang as ONE single-device program: worker copies
    are [n, ...] stacked leaves advanced by ``jax.vmap`` over the worker
    axis, the exchange is a mean over axis 0 — mathematically the mesh
    engine with the parallelism replaced by vectorization (reduction
    order may differ at float precision; the per-worker inner step is
    the SAME function, and the round-17 levers route through the SAME
    :func:`outer_round_step`). Contract identical to
    :func:`make_lm_diloco_parts` (tokens [n·B, L]; the first batch
    dimension is split n ways in worker order, matching the mesh
    engine's ``P(axis)`` batch sharding)."""
    if sync_every < 1:
        raise ValueError(f"sync_every must be >= 1, got {sync_every}")
    if num_workers < 1:
        raise ValueError(f"num_workers must be >= 1, got {num_workers}")
    n = num_workers
    eta = resolve_outer_lr(outer_lr, n)
    step_fn = _local_inner_step(model, optimizer, ragged)
    vstep = jax.vmap(step_fn)

    def init_state(params, opt_state):
        stacked = jax.tree.map(
            lambda x: jnp.broadcast_to(x[None], (n,) + x.shape),
            (params, opt_state),
        )
        zeros = lambda: jax.tree.map(jnp.zeros_like, params)  # noqa: E731
        return (
            stacked[0],
            DiLoCoState(
                stacked[1],
                params,
                zeros(),
                zeros() if delta_dtype is not None else None,
                # Round 0: nothing in flight, every copy lands on θ_0
                # (a COPY — see the mesh engine's donation note).
                {"delta": zeros(), "landing": jax.tree.map(jnp.copy, params)}
                if overlap
                else None,
            ),
            jnp.zeros((), jnp.int32),
        )

    def mapped(params, dstate, tokens, lens, count):
        b, L = tokens.shape
        if b % n:
            raise ValueError(
                f"batch {b} must divide over {n} emulated workers"
            )
        toks = tokens.reshape(n, b // n, L)
        wl = (
            lens.reshape(n, b // n)
            if ragged
            else jnp.zeros((n, b // n), jnp.int32)
        )
        p, inner, losses = vstep(params, dstate.inner, toks, wl)
        theta, m = dstate.theta, dstate.momentum
        residual, inflight = dstate.residual, dstate.inflight

        def exchange(args):
            p, theta, m, residual, inflight = args
            pbar = jax.tree.map(lambda x: jnp.mean(x, axis=0), p)
            theta2, m2, r2, f2 = outer_round_step(
                theta,
                pbar,
                m,
                residual,
                inflight,
                outer_lr=eta,
                outer_momentum=outer_momentum,
                nesterov=nesterov,
                delta_dtype=delta_dtype,
                overlap=overlap,
            )
            if overlap:
                # Streaming merge — same arithmetic as the mesh engine
                # (trailing-dim broadcast against the [n, ...] stack).
                p2 = jax.tree.map(
                    lambda local, t2: (1.0 - OVERLAP_MERGE) * local
                    + OVERLAP_MERGE * t2,
                    p,
                    theta2,
                )
            else:
                p2 = jax.tree.map(
                    lambda x: jnp.broadcast_to(x[None], (n,) + x.shape),
                    theta2,
                )
            return p2, theta2, m2, r2, f2

        p, theta, m, residual, inflight = lax.cond(
            (count + 1) % sync_every == 0,
            exchange,
            lambda args: args,
            (p, theta, m, residual, inflight),
        )
        return (
            p,
            DiLoCoState(inner, theta, m, residual, inflight),
            jnp.mean(losses),
        )

    return init_state, mapped


# ---------------------------------------------------------------------------
# Stale-tolerant gang: the host-mailbox outer exchange (round 17).
#
# The in-graph engines above are SYNCHRONOUS gangs — every worker reaches
# the boundary together (a shard_map pmean, or one vmapped program). The
# mailbox moves the outer round to the host: each member posts its
# (compressed) delta to a shared directory at its own boundary and applies
# the outer update from whatever peers have posted, weighted by staleness
# (module docstring). Files commit atomically (tmp + os.replace — the
# serve_fleet mailbox discipline), so a reader never sees a torn payload
# and a member crash leaves nothing half-written. numpy-only numerics: the
# encode/decode pair mirrors ops/quantized's per-tensor semantics exactly
# (pinned in tests/test_local_sgd.py) so the wire format cannot drift from
# the in-graph compressed path.
# ---------------------------------------------------------------------------


def _np_encode_delta(leaves, delta_dtype):
    """Encode leaves for the wire via :func:`ops.quantized.quantize_tensor`
    (the SAME quantizer as the in-graph compressed path — bit-equal by
    construction, not by a parallel numpy implementation; XLA's fp8 cast
    double-rounds midpoints differently than a naive ml_dtypes cast, so
    a mirror would drift): → ``(stored_leaves, scales,
    dequantized_leaves)``. ``stored`` is what hits the disk (int8, or
    the fp8 payload viewed uint8 — npz-safe); ``dequantized`` is what
    every reader reconstructs, returned so the poster's EF residual sees
    the wire values."""
    import numpy as np

    if delta_dtype is None:
        leaves = [np.asarray(x, np.float32) for x in leaves]
        return leaves, None, leaves
    if delta_dtype not in ("int8", "fp8"):
        raise ValueError(
            f"delta_dtype must be None, 'int8', or 'fp8'; got "
            f"{delta_dtype!r}"
        )
    from distributed_tensorflow_tpu.ops.quantized import quantize_tensor

    stored, scales, deq = [], [], []
    for x in leaves:
        q, scale = quantize_tensor(jnp.asarray(x, jnp.float32), delta_dtype)
        q = np.asarray(jax.device_get(q))
        scale = float(scale)
        if delta_dtype == "fp8":
            stored.append(q.view(np.uint8))
        else:
            stored.append(q)
        deq.append(q.astype(np.float32) * scale)
        scales.append(scale)
    return stored, np.asarray(scales, np.float32), deq


def _np_decode_delta(stored, scales, delta_dtype):
    """Inverse of :func:`_np_encode_delta` on the read side."""
    import numpy as np

    if delta_dtype is None:
        return [np.asarray(x, np.float32) for x in stored]
    out = []
    for x, s in zip(stored, scales):
        if delta_dtype == "fp8":
            import ml_dtypes

            x = x.view(ml_dtypes.float8_e4m3fn)
        out.append(x.astype(np.float32) * float(s))
    return out


class DeltaExchange:
    """Filesystem outer-delta mailbox for a stale-tolerant DiLoCo gang.

    One instance per gang member (``rank`` of ``world``), all pointing at
    the same ``dirpath`` (any shared filesystem). Protocol per outer
    round boundary (LMTrainer drives it when constructed with
    ``delta_exchange=``):

    1. :meth:`post` — EF-compress (``delta_dtype``) and atomically
       publish this member's pseudo-gradient for round ``r`` as
       ``w<rank>_r<round>.npz``; returns the dequantized wire values
       (what peers will read — the caller's residual must see these).
    2. :meth:`weighted_delta` — assemble the round's outer
       pseudo-gradient: own delta at weight 1 plus every peer post NOT
       YET CONSUMED by this member and no more than ``stale_limit``
       rounds old, each weighted ``1/(1+age)`` (:func:`staleness_weight`;
       posts from rounds ahead of ours clamp to age 0). Each posted
       delta is applied AT MOST ONCE (per-peer consumed-round
       watermark): a delta is one round of MOVEMENT, and re-applying a
       stalled peer's last post at every subsequent boundary would
       over-apply it by its cumulative discounted weight (the async-PS
       contract is each update applied exactly once). Peers with
       nothing new in the window simply do not contribute — the round
       NEVER waits.

    Old own files past the staleness window are garbage-collected at
    each post (every member cleans only its own). Member anchors may
    transiently differ across the gang (each applies its own arrival
    view) — the async-PS drift semantics, see the module docstring. The
    consumed watermark is in-memory: a member restarted from a
    checkpoint may re-consume posts still inside the window (bounded by
    ``stale_limit`` rounds of peer movement — the same replay bound any
    restore has).

    Integrity (round 19): every post carries a CRC32C envelope (the
    round-6 checkpoint-manifest kernel) over the stored array bytes,
    verified on read. A committed-but-corrupt post (CRC mismatch, bad
    zip — the storage layer corrupting committed bytes; atomic replace
    already keeps torn *tmp* files invisible) is SKIPPED, never
    consumed into the mean: the watermark advances past it (a
    permanently bad file must not block that peer's later posts
    forever), a structured ``mailbox_corrupt`` journal event fires, and
    ``corrupt_posts`` counts it. Transient unreadability (OSError — a
    shared-fs hiccup, a racing GC) keeps the old contract: break
    without advancing, retry next boundary. Pre-round-19 posts without
    a ``crc`` entry verify as legacy (accepted unchecked). Stale
    ``.tmp`` orphans from writers killed mid-post are age-guard swept
    on construction and at each post's GC pass
    (:func:`resilience.sweep_tmp_orphans`)."""

    _CORRUPT = object()  # _load sentinel: committed-but-bad, skip + advance

    def __init__(
        self,
        dirpath: str,
        rank: int,
        world: int,
        *,
        stale_limit: int = 0,
        delta_dtype: str | None = None,
        journal=None,
        metrics=None,
        orphan_age_s: float = 60.0,
    ):
        import os

        if world < 1:
            raise ValueError(f"world must be >= 1, got {world}")
        if not 0 <= rank < world:
            raise ValueError(f"rank must be in [0, {world}), got {rank}")
        if stale_limit < 0:
            raise ValueError(
                f"stale_limit must be >= 0, got {stale_limit}"
            )
        if delta_dtype not in (None, "int8", "fp8"):
            raise ValueError(
                f"delta_dtype must be None, 'int8', or 'fp8'; got "
                f"{delta_dtype!r}"
            )
        self.dirpath = str(dirpath)
        self.rank = int(rank)
        self.world = int(world)
        self.stale_limit = int(stale_limit)
        self.delta_dtype = delta_dtype
        self.journal = journal  # LMTrainer wires its own; None → process
        self.metrics = metrics  # round 21: counters beside the journal
        self.orphan_age_s = float(orphan_age_s)
        self.corrupt_posts = 0  # committed-but-corrupt peer posts skipped
        # Per-peer consumed-round watermark: each posted delta is
        # applied at most once (class docstring).
        self._consumed: dict[int, int] = {}
        os.makedirs(self.dirpath, exist_ok=True)
        resilience.sweep_tmp_orphans(self.dirpath, age_s=self.orphan_age_s)

    def _emit_corrupt(self, *, file: str, reason: str, peer: int, round_idx: int):
        self.corrupt_posts += 1
        if self.metrics is not None:
            self.metrics.counter("mailbox_corrupt_posts_total").inc()
        j = self.journal
        if j is None:
            from distributed_tensorflow_tpu.observability import (
                journal as obs_journal,
            )

            j = obs_journal.get_journal()
        j.emit(
            "mailbox_corrupt",
            mailbox="delta",
            file=file,
            reason=reason,
            action="skipped",
            peer=int(peer),
            round=int(round_idx),
        )

    def _fname(self, rank: int, round_idx: int) -> str:
        return f"w{rank:04d}_r{round_idx:010d}.npz"

    def _scan(self) -> dict[int, list[int]]:
        """ONE directory scan → ``{rank: sorted rounds}``. gather() and
        the GC both read from this so a boundary costs O(1) listdir
        calls, not O(world) — on a shared filesystem each listdir is a
        metadata RPC and the boundary's wall_ms is journaled as the
        round's entire non-overlapped cost."""
        import os

        out: dict[int, list[int]] = {}
        for name in os.listdir(self.dirpath):
            if not (name.startswith("w") and name.endswith(".npz")):
                continue
            try:
                rank = int(name[1:5])
                r = int(name[7:-4])
            except ValueError:
                continue
            out.setdefault(rank, []).append(r)
        for rounds in out.values():
            rounds.sort()
        return out

    def _rounds_of(self, rank: int) -> list[int]:
        return self._scan().get(rank, [])

    def payload_nbytes(self, round_idx: int) -> int | None:
        """On-disk size of this member's posted payload for ``round_idx``
        (None before it posts) — the honest wire-bytes measurement the
        trainer's ``comm_stats`` accounting reports for the mailbox
        gang."""
        import os

        path = os.path.join(
            self.dirpath, self._fname(self.rank, round_idx)
        )
        try:
            return os.path.getsize(path)
        except OSError:
            return None

    @staticmethod
    def _payload_crc(stored, scales) -> int:
        """CRC32C envelope over the wire bytes: every stored array's
        buffer in index order, then the scales. Round-6 kernel
        (native fast path, table fallback — bit-identical)."""
        import numpy as np

        blob = b"".join(
            np.ascontiguousarray(x).tobytes() for x in stored
        )
        if scales is not None:
            blob += np.ascontiguousarray(scales).tobytes()
        return resilience._crc32c_bytes(blob)

    def post(self, round_idx: int, leaves) -> list:
        """Publish round ``round_idx``'s delta (numpy leaves, dense
        parameter order); returns the dequantized leaves exactly as
        peers will read them. Failpoints: ``delta.post`` at entry (+
        tear of the committed npz), ``delta.post.commit`` between the
        tmp write and the atomic replace."""
        import os

        import numpy as np

        failpoints.fire("delta.post")
        stored, scales, deq = _np_encode_delta(leaves, self.delta_dtype)
        payload = {f"a{i}": x for i, x in enumerate(stored)}
        payload["n"] = np.asarray(len(stored), np.int64)
        if scales is not None:
            payload["scales"] = scales
        payload["crc"] = np.asarray(
            self._payload_crc(stored, scales), np.int64
        )
        path = os.path.join(
            self.dirpath, self._fname(self.rank, round_idx)
        )
        tmp = path + f".tmp{os.getpid()}"
        with open(tmp, "wb") as f:
            np.savez(f, **payload)
        failpoints.fire("delta.post.commit")
        os.replace(tmp, path)  # commit is atomic: readers see all or nothing
        failpoints.tear("delta.post", path)
        # GC own history past the window (+1 so a peer mid-read of the
        # oldest admissible round never races its unlink).
        floor = round_idx - self.stale_limit - 1
        for r in self._rounds_of(self.rank):
            if r < floor:
                try:
                    os.remove(
                        os.path.join(self.dirpath, self._fname(self.rank, r))
                    )
                except OSError:
                    pass
        resilience.sweep_tmp_orphans(self.dirpath, age_s=self.orphan_age_s)
        return deq

    def _load(self, rank: int, round_idx: int):
        """Read + verify a peer post. Returns the decoded leaves, None
        for TRANSIENT unreadability (vanished to owner GC, an fs
        hiccup — retried next boundary, watermark unmoved), or
        ``_CORRUPT`` for a committed-but-bad file (CRC mismatch, torn
        zip structure, missing keys — skipped forever, watermark
        advances; class docstring)."""
        import os
        import zipfile

        import numpy as np

        path = os.path.join(self.dirpath, self._fname(rank, round_idx))
        try:
            failpoints.fire("delta.load")
            with np.load(path) as z:
                n = int(z["n"])
                stored = [z[f"a{i}"] for i in range(n)]
                scales = z["scales"] if "scales" in z.files else None
                crc = int(z["crc"]) if "crc" in z.files else None
        except OSError:
            return None  # vanished (owner GC) or transient fs hiccup
        except (KeyError, ValueError, zipfile.BadZipFile, EOFError):
            return self._CORRUPT  # committed file, broken structure
        if crc is not None and crc != self._payload_crc(stored, scales):
            return self._CORRUPT  # committed bytes flipped under the CRC
        return _np_decode_delta(stored, scales, self.delta_dtype)

    def gather(self, round_idx: int) -> list[tuple[int, int, float, list]]:
        """Peers' contributions for the boundary at ``round_idx``:
        ``[(rank, age, weight, leaves), ...]`` — every post this member
        has NOT yet consumed and still inside the staleness window, each
        weighted once (a peer that fell behind and catches up
        contributes each missed round's movement exactly once; posts
        ahead of our round clamp to age 0). Advances the per-peer
        consumed watermark — posts beyond the window are dropped forever
        (their movement is lost, the documented staleness cost), never
        retried. Own rank excluded (the caller holds its own fresh
        delta)."""
        posts = self._scan()
        out = []
        for rank in range(self.world):
            if rank == self.rank:
                continue
            floor = self._consumed.get(rank, -1)
            consumed = floor
            for r in posts.get(rank, []):
                if r <= floor:
                    continue
                if round_idx - r > self.stale_limit:
                    consumed = max(consumed, r)  # too old: dropped forever
                    continue
                leaves = self._load(rank, r)
                if leaves is None:
                    # Transiently unreadable (shared-fs hiccup) or
                    # vanished to owner GC: stop consuming THIS peer for
                    # the boundary without advancing the watermark — a
                    # hiccup retries next boundary (age+1, still
                    # weighted; consuming a newer post now would jump
                    # the watermark past the unread round forever), a
                    # GC'd file simply stops appearing in _scan.
                    break
                if leaves is self._CORRUPT:
                    # Committed-but-corrupt: skipped, NEVER consumed
                    # into the mean — but the watermark must advance
                    # past it, or a permanently bad file would block
                    # this peer's later posts forever.
                    consumed = max(consumed, r)
                    self._emit_corrupt(
                        file=self._fname(rank, r),
                        reason="crc",
                        peer=rank,
                        round_idx=r,
                    )
                    continue
                consumed = max(consumed, r)
                age = max(0, round_idx - r)  # ahead-of-round → fresh
                out.append(
                    (rank, age, staleness_weight(age, self.stale_limit),
                     leaves)
                )
            if consumed > floor:
                self._consumed[rank] = consumed
        return out

    def weighted_delta(self, round_idx: int, own_leaves):
        """The round's outer pseudo-gradient: staleness-weighted mean of
        own (weight 1) + every not-yet-consumed admissible peer post (a
        catching-up peer may contribute several entries, one per missed
        round). Returns ``(leaves, total_weight, contributors)`` with
        contributors ``[(rank, age, weight), ...]`` own-first — the
        trainer journals them, and ``total_weight`` (= 1 + Σ weights) is
        what the ``outer_lr=None`` default must scale by: the in-graph
        ``η=N`` convention compensates an exact 1/N mean over N
        contributing workers, so the mailbox's variable-contributor mean
        must scale by the ACTUAL total weight — scaling by the fixed
        world size would over-apply by up to N× whenever peers are
        missing or stale-dropped."""
        import numpy as np

        own = [np.asarray(x, np.float32) for x in own_leaves]
        peers = self.gather(round_idx)
        total = 1.0 + sum(w for _, _, w, _ in peers)
        acc = [x.copy() for x in own]
        for _, _, w, leaves in peers:
            for a, b in zip(acc, leaves):
                a += w * b
        mean = [a / total for a in acc]
        contributors = [(self.rank, 0, 1.0)] + [
            (r, age, w) for r, age, w, _ in peers
        ]
        return mean, total, contributors
