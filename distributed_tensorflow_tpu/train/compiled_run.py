"""Whole-run compilation: every epoch, shuffle, and eval in ONE dispatch.

The reference's entire experiment is a fixed program — 100 epochs × 550
batches of SGD with a per-epoch test-set eval (reference tfsingle.py:72-95,
tfdist_between.py:86-111) — executed as ~55,000 Python→runtime round trips.
train/scan.py collapses one epoch into one dispatch; this module collapses
the *run*: a nested ``lax.scan`` (epochs over steps) with the epoch shuffle
performed on-device (``jax.random.permutation`` + gather) and the per-epoch
test accuracy computed in-graph, so the host dispatches once and receives
the full training history — per-step costs ``[epochs, steps]`` and
per-epoch accuracies ``[epochs]`` — in a single D2H transfer.

Why this is the TPU-shaped design (and not just a bigger batch of the same):

- The train set is staged in HBM **once** (~172 MB f32 for MNIST) instead
  of per-epoch; each epoch re-reads it through a fresh permutation gather.
- Zero host round trips between epochs — each costs a fixed
  dispatch-and-sync, which at MNIST sizes is comparable to the whole
  on-device epoch.
- Eval rides the same program: the ``[10000, 784]`` test matmul is a large
  MXU-friendly shape, cheaper than shipping params to the host would be.

Semantics vs the eager loop: identical update rule, batch size, and update
count (``state.step`` advances ``epochs × steps``). The shuffle uses JAX's
on-device PRNG instead of the host numpy generator, so batch *composition*
differs from the host-shuffled paths run-to-run the same way two host seeds
differ from each other — distributionally equivalent, bit-different
(SURVEY.md §7 hard-part b treats init seeds the same way). With
``shuffle=False`` batches are taken in dataset order every epoch — the same
update sequence as ``train/scan.py`` over unshuffled staging, equal to
ulp-level (the gather-built batch may reassociate float ops vs the sliced
batch); tests/test_compiled_run.py asserts that parity.
"""

from __future__ import annotations

from functools import partial
from typing import Callable

import jax
import jax.numpy as jnp
import optax

from distributed_tensorflow_tpu.ops import losses as losses_lib
from distributed_tensorflow_tpu.parallel.strategy import TrainState, _loss_from_model


def wrapped_epoch_perm(sub, *, domain: int, need: int, k: int, shuffle: bool):
    """One epoch's index stream over a device-resident dataset of ``domain``
    rows: ``need`` indices drawn from ``k`` fresh full permutations
    concatenated (the on-device analog of ``DataSet.next_batch``'s
    tail-carry reshuffle; ``k == 1`` is the plain single-permutation epoch),
    or dataset order tiled when not shuffling. Shared by the generic and
    async compiled-run builders so the wrap convention cannot diverge."""
    if not shuffle:
        return jnp.tile(jnp.arange(domain), k)[:need]
    if k == 1:
        return jax.random.permutation(sub, domain)[:need]
    subs = jax.random.split(sub, k)
    return jnp.concatenate(
        [jax.random.permutation(s, domain) for s in subs]
    )[:need]


def make_compiled_run_fn(
    model,
    loss_fn: Callable,
    optimizer: optax.GradientTransformation,
    *,
    batch_size: int,
    epochs: int,
    shuffle: bool = True,
    batch_sharding=None,
    donate: bool = True,
    steps_per_epoch: int | None = None,
) -> Callable:
    """Build ``fn(state, train_x, train_y, test_x, test_y, key) ->
    (state, {"costs": [epochs, steps], "accuracy": [epochs]})`` — the whole
    training run as one jitted program.

    ``train_x``/``train_y`` are the full (un-batched) arrays; the step count
    is ``len(train_x) // batch_size`` (tail dropped, matching the reference's
    ``int(num_examples/batch_size)``, reference tfdist_between.py:87).
    ``key`` is a ``jax.random`` key driving the per-epoch shuffles. With
    ``batch_sharding`` (a NamedSharding over the ``data`` axis) each gathered
    batch is sharded across chips → sync data-parallel, GSPMD inserting the
    gradient all-reduce.

    ``steps_per_epoch`` overrides the step count (the reference's
    ``per_worker_epoch`` convention: N workers × num_examples/100 steps,
    reference tfdist_between.py:87); the per-epoch index stream then wraps
    across as many fresh full-dataset permutations as needed — the on-device
    analog of ``DataSet.next_batch``'s tail-carry reshuffle.
    """

    @partial(jax.jit, donate_argnums=0 if donate else ())
    def run(state: TrainState, train_x, train_y, test_x, test_y, key):
        steps = (
            train_x.shape[0] // batch_size
            if steps_per_epoch is None
            else steps_per_epoch
        )
        need = steps * batch_size
        # Permutation domain: the trimmed dataset for the plain convention
        # (old behavior bit-preserved), the full dataset when wrapping.
        domain = need if steps_per_epoch is None else train_x.shape[0]
        k = (need + domain - 1) // domain if need else 1

        def epoch_perm(sub):
            return wrapped_epoch_perm(
                sub, domain=domain, need=need, k=k, shuffle=shuffle
            )

        def train_step(state: TrainState, idx):
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

        def epoch_body(carry, _):
            state, key = carry
            key, sub = jax.random.split(key)
            perm = epoch_perm(sub)
            state, costs = jax.lax.scan(
                train_step, state, perm.reshape(steps, batch_size)
            )
            acc = losses_lib.accuracy(model.apply(state.params, test_x), test_y)
            return (state, key), (costs, acc)

        (state, _), (costs, accs) = jax.lax.scan(
            epoch_body, (state, key), None, length=epochs
        )
        return state, {"costs": costs, "accuracy": accs}

    return run
