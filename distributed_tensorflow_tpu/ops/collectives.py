"""Ring collectives over the device mesh.

The reference's cross-device communication was a gRPC parameter-server star
(SURVEY.md §5 "Distributed communication backend") — every gradient hop
traversed host NICs. Here the framework-level collectives are XLA's
(``psum``/``pmean`` over ICI, used by the sync strategies), and this module
additionally provides *explicit* ring algorithms built from
``lax.ppermute`` — the neighbor-exchange pattern ICI topologies are built
for. They serve two purposes:

1. load-bearing: the async strategy's periodic parameter exchange can run as
   a ring all-reduce (``AsyncDataParallel.make_exchange_fn(collective="ring")``);
2. infrastructure: the same ppermute ring is the building block for
   sequence-parallel/ring-attention workloads on a future ``seq`` mesh axis
   (SURVEY.md §5 "Long-context": absent in the reference workload; the
   machinery is first-class here).

All functions are collective-inside-``shard_map`` primitives: call them from
a function mapped over the named axis.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax


def _ring_perm(n: int) -> list[tuple[int, int]]:
    return [(j, (j + 1) % n) for j in range(n)]


def to_varying(a, axis_name):
    """Cast a value to varying over ``axis_name`` (vma typing under
    ``shard_map``; accepts one axis or a tuple). Idempotent: axes the
    value ALREADY varies over are skipped — ``pcast(to='varying')``
    rejects them, and callers like the ring-attention carry inits derive
    their zeros from inputs whose vma depends on the enclosing mesh (1-D
    sp vs 2-D dp×sp)."""
    axes = (axis_name,) if isinstance(axis_name, str) else tuple(axis_name)
    have = jax.typeof(a).vma
    axes = tuple(ax for ax in axes if ax not in have)
    if not axes:
        return a
    return lax.pcast(a, axes, to="varying")


def ring_all_reduce(x: jax.Array, axis_name: str) -> jax.Array:
    """Sum ``x`` across the named axis with N-1 neighbor exchanges (each
    step moves one chunk over one ICI hop), no tree/star topology."""
    n = lax.axis_size(axis_name)
    perm = _ring_perm(n)

    def body(_, carry):
        acc, cur = carry
        cur = lax.ppermute(cur, axis_name, perm)
        return acc + cur, cur

    acc, _ = lax.fori_loop(0, n - 1, body, (x, x))
    return acc


def ring_all_mean(x: jax.Array, axis_name: str) -> jax.Array:
    n = lax.axis_size(axis_name)
    return ring_all_reduce(x, axis_name) / n


def ring_all_gather(x: jax.Array, axis_name: str) -> jax.Array:
    """Gather every device's ``x`` into a new leading axis (shape [N, ...]),
    rotating chunks around the ring. After k hops a device holds the chunk
    that originated k positions behind it."""
    n = lax.axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    perm = _ring_perm(n)
    out = jnp.zeros((n,) + x.shape, x.dtype)
    out = lax.dynamic_update_slice(out, x[None], (idx,) + (0,) * x.ndim)

    def body(k, carry):
        out, cur = carry
        cur = lax.ppermute(cur, axis_name, perm)
        src = (idx - k - 1) % n
        out = lax.dynamic_update_slice(out, cur[None], (src,) + (0,) * x.ndim)
        return out, cur

    out, _ = lax.fori_loop(0, n - 1, body, (out, x))
    return out
