"""Scanned multi-step training: many SGD steps per device dispatch.

The reference pays one Python→runtime round trip per 100-example batch
(``sess.run`` per batch, reference tfsingle.py:78-80) — on its hardware that
cost 1.3 s/epoch; on a dispatch-latency-bound link it is catastrophic. The
TPU-first design instead compiles K steps into one XLA program with
``lax.scan``: the full epoch's batches are staged in HBM once (MNIST is
~86 MB in bf16 — trivially resident), the scan walks batch slices on-device,
and the host syncs once per dispatch. Per-step overhead drops to zero and
XLA can overlap the data slicing with MXU work.

This is the path ``bench.py`` measures and the path to use whenever the
per-step host round trip (logging every batch) is not needed. The semantics
are bit-identical to the eager loop: same batches, same order, same updates.
"""

from __future__ import annotations

from functools import partial
from typing import Callable

import jax
import jax.numpy as jnp
import optax

from distributed_tensorflow_tpu.parallel.strategy import TrainState, _loss_from_model


def make_scanned_train_fn(
    model,
    loss_fn: Callable,
    optimizer: optax.GradientTransformation,
    *,
    batch_sharding=None,
    donate: bool = True,
) -> Callable:
    """Build ``fn(state, xs, ys) -> (state, costs)`` where ``xs`` has shape
    [num_steps, batch, features]: one compiled dispatch running every step.

    With ``batch_sharding`` (a NamedSharding over the ``data`` axis on dim 1
    of each scan slice), the same program is sync data-parallel: each scan
    iteration's batch is sharded across chips and GSPMD inserts the gradient
    all-reduce — ``SyncReplicasOptimizer`` at zero dispatch cost.
    """

    def step(state: TrainState, batch):
        x, y = batch
        if batch_sharding is not None:
            x = jax.lax.with_sharding_constraint(x, batch_sharding)
            y = jax.lax.with_sharding_constraint(y, batch_sharding)
        cost, grads = jax.value_and_grad(partial(_loss_from_model, model, loss_fn))(
            state.params, x, y
        )
        updates, opt_state = optimizer.update(grads, state.opt_state, state.params)
        params = optax.apply_updates(state.params, updates)
        return TrainState(params, opt_state, state.step + 1), cost

    @partial(jax.jit, donate_argnums=0 if donate else ())
    def run(state: TrainState, xs: jax.Array, ys: jax.Array):
        return jax.lax.scan(step, state, (xs, ys))

    return run


def make_indexed_scanned_train_fn(
    model,
    loss_fn: Callable,
    optimizer: optax.GradientTransformation,
    *,
    batch_sharding=None,
    donate: bool = True,
) -> Callable:
    """Build ``fn(state, train_x, train_y, idxs) -> (state, costs)`` where
    ``train_x``/``train_y`` are the FULL flat training arrays (device-resident,
    staged once for the whole run) and ``idxs`` is ``[steps, batch]`` int32 row
    indices — the only per-epoch upload. Each scan iteration gathers its batch
    on-device, so re-shuffling an epoch costs a ~0.2 MB index transfer instead
    of re-staging ~170 MB of batches from the host (the round-1
    Trainer-on-TPU gap: per-epoch restaging cost more than the epoch's
    compute). Same update semantics as ``make_scanned_train_fn`` over
    ``stage_epoch`` output for the same permutation."""

    def step_fn(train_x, train_y):
        def step(state: TrainState, idx):
            x = jnp.take(train_x, idx, axis=0)
            y = jnp.take(train_y, idx, axis=0)
            if batch_sharding is not None:
                x = jax.lax.with_sharding_constraint(x, batch_sharding)
                y = jax.lax.with_sharding_constraint(y, batch_sharding)
            cost, grads = jax.value_and_grad(
                partial(_loss_from_model, model, loss_fn)
            )(state.params, x, y)
            updates, opt_state = optimizer.update(grads, state.opt_state, state.params)
            params = optax.apply_updates(state.params, updates)
            return TrainState(params, opt_state, state.step + 1), cost

        return step

    @partial(jax.jit, donate_argnums=0 if donate else ())
    def run(state: TrainState, train_x, train_y, idxs):
        return jax.lax.scan(step_fn(train_x, train_y), state, idxs)

    return run


def stage_epoch(
    images, labels, batch_size: int, *, rng=None, dtype=jnp.float32
):
    """Shape one epoch of host data into [steps, batch, ...] scan slices
    (shuffled like ``DataSet.next_batch``), ready for a single device_put."""
    import numpy as np

    n = (images.shape[0] // batch_size) * batch_size
    perm = (
        rng.permutation(images.shape[0])[:n]
        if rng is not None
        else np.arange(n)
    )
    xs = images[perm].reshape(-1, batch_size, images.shape[1]).astype(dtype)
    ys = labels[perm].reshape(-1, batch_size, labels.shape[1]).astype(dtype)
    return xs, ys
