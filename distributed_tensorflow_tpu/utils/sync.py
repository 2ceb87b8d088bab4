"""Ending a timed region: one shared implementation.

JAX dispatch is asynchronous — a call returns once the work is enqueued,
so a clock read straight after it times the enqueue. A timed region must
END in something that waits for the device: ``jax.block_until_ready`` on
the result, or a device-to-host **value fetch** of a buffer that
transitively depends on the work being timed. Either is a barrier on a
directly attached chip (``chip_smoke.py`` times one long dispatch both
ways on every run and prints whether they agree). This module uses the
fetch: the callers here want a value on the host anyway (a cost to
validate, a token to return), and one scalar costs nothing extra.

The reference's timing (AvgTime/Total Time around blocking ``sess.run``
calls, reference tfdist_between.py:92-110) never had the problem because
``sess.run`` fetches values; in JAX's async-dispatch model the wait must
be explicit.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np


def timed_fetch(fn, *args):
    """Run ``fn(*args)`` and return ``(seconds, result)`` with the clock
    read AFTER a one-scalar D2H fetch of the result — the ONE audited
    dispatch-timing wrapper (three hand copies of this four-liner existed
    and one of them read the clock before the fetch, timing enqueue).
    The barrier fetches a single element of the first array leaf (4
    bytes — never the whole buffer): any output element becomes
    available only when the whole dispatch has executed."""
    t0 = time.perf_counter()
    out = fn(*args)
    leaf = jax.tree_util.tree_leaves(out)[0]
    float(jnp.reshape(leaf, (-1,))[0].astype(jnp.float32))
    return time.perf_counter() - t0, out


def two_point_seconds(time_short, time_long, span: int, reps: int = 5) -> float:
    """Per-unit seconds by the TWO-POINT method — the ONE audited
    implementation (three hand copies had already drifted to reps 7/3/5
    and one sized its span below the jitter floor).

    One dispatch-and-sync has a fixed cost (launch, the fetch's round
    trip) — NOT MEASURED on a directly attached chip yet (ROADMAP S2) —
    and dividing one chain's wall time by its length folds that cost
    into every unit. Instead call ``time_short()`` and ``time_long()``
    (each a full timed dispatch whose clock reads AFTER the region's
    sync) and divide the difference by ``span`` (the extra units the
    long chain runs): the fixed cost cancels, whatever it is. Median
    over ``reps`` resists jitter; the caller must size ``span`` so the
    differenced wall time dwarfs the run-to-run jitter — negative medians
    (span below the noise floor) are clamped to 1e-12, so a 0.0-looking
    result means "span too small", not "free".
    """
    deltas = []
    for _ in range(reps):
        t_short = time_short()
        t_long = time_long()
        deltas.append((t_long - t_short) / span)
    deltas.sort()
    return max(deltas[len(deltas) // 2], 1e-12)


def d2h_barrier(tree) -> None:
    """Block until every computation ``tree`` depends on has executed, by
    copying one array leaf to host. Prefer fetching a value you already
    need (as ``bench.py`` does with the final cost); use this when the
    timed code produces nothing the caller wants on host.
    """
    for leaf in jax.tree_util.tree_leaves(tree):
        # Every device leaf, not just the first: leaves may come from
        # independent dispatches, and a host-numpy first leaf would make a
        # single-leaf fetch a silent no-op.
        if isinstance(leaf, jax.Array):
            np.asarray(leaf)
