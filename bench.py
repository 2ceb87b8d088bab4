"""Benchmark harness: MNIST MLP training throughput on one chip.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", "impl",
"stream_dtype", "platform", "device_kind", "device_count"}. Refuses to
time anything unless JAX's first device is a TPU: a CPU run is not a
measurement of this program (exit 2, no JSON).

Baseline: the reference's best single-device number — 550 batches × 100
examples in ~1.3 s/epoch on a GTX 1080 (reference README.md:13-15) ≈ 42k
examples/sec (BASELINE.md). North star: ≥50k examples/sec/chip.

Method: the scanned train path (train/scan.py) — whole epochs staged in
device memory and walked by one `lax.scan`, identical update semantics to
the reference loop (SGD lr=0.001, batch 100). Each dispatch covers
`BENCH_EPOCHS_PER_DISPATCH` epochs (default 5, each with its own shuffle)
so the fixed cost of a dispatch is amortised the way any real multi-epoch
run would amortise it. Timing: warmups first (compile + donation
settling), then three TWO-POINT region pairs — each pair times a
5-dispatch and a 20-dispatch region, each ended by *fetching* the final
cost (a value fetch that depends on every enqueued step waits for the
device exactly as `block_until_ready` does, and yields the number the
validity gate below needs), and per-epoch time is the pair's DIFFERENCE
over the extra epochs, so whatever one sync costs cancels. That fixed
cost is not measured on this machine yet (ROADMAP S2). Median pair is
reported.

`BENCH_IMPL=pallas-epoch` (default) runs the whole dispatch as ONE Pallas
kernel launch (ops/pallas_mlp.py `make_fused_epoch_fn`: grid over every
staged step, params VMEM-resident throughout). `pallas` scans the
per-step fused kernel; `xla` is the pure-XLA scan. The impl asked for
runs, or the script exits non-zero: nothing falls back to another impl.
Diagnostics go to stderr; stdout carries exactly the one JSON line.
"""

from __future__ import annotations

import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, ".")

from distributed_tensorflow_tpu.data import read_data_sets
from distributed_tensorflow_tpu.models import MLP
from distributed_tensorflow_tpu.ops import cross_entropy, sgd
from distributed_tensorflow_tpu.parallel.strategy import SingleDevice
from distributed_tensorflow_tpu.train.scan import make_scanned_train_fn
from distributed_tensorflow_tpu.utils.compile_cache import (
    configure_compile_cache,
)

BASELINE_EXAMPLES_PER_SEC = 42_000.0
BATCH_SIZE = 100
LEARNING_RATE = 0.001
TIMED_DISPATCHES = 5


def log(*a):
    print(*a, file=sys.stderr, flush=True)


def main(impl: str) -> None:
    if impl not in ("pallas-epoch", "pallas", "xla"):
        raise SystemExit(
            f"unknown BENCH_IMPL {impl!r} (expected pallas-epoch|pallas|xla)"
        )
    dev = jax.devices()[0]
    log(f"device: {dev}  impl: {impl}")
    if dev.platform != "tpu":
        log(
            f"FATAL: platform {dev.platform!r} is not a TPU; bench.py times "
            "the chip only (tests and counts run on the CPU, rates do not)"
        )
        raise SystemExit(2)
    log(f"compile cache: {configure_compile_cache()}")
    ds = read_data_sets("MNIST_data", one_hot=True)

    model = MLP()  # bf16 matmuls, f32 accumulation/softmax (xla impl)

    # Stage E epochs, each with its own shuffle, as one flattened scan:
    # [E*steps, batch, ...]. The scan body is unchanged, so update semantics
    # are bit-identical to E successive single-epoch dispatches over the
    # same permutations — only the host syncs are fewer.
    epochs_per_dispatch = int(os.environ.get("BENCH_EPOCHS_PER_DISPATCH", "5"))
    # pallas-epoch streams batches half-width from HBM; stage them in that
    # dtype ONCE here (a per-dispatch astype inside the timed region would
    # re-read the full staging each call). BENCH_STREAM_DTYPE=float32 opts
    # back into full-width staging.
    stream = (
        os.environ.get("BENCH_STREAM_DTYPE", "bfloat16")
        if impl == "pallas-epoch"
        else "float32"
    )
    # Stage ON DEVICE: upload the flat dataset once (~86 MB bf16) plus the
    # shuffle indices (~1 MB), then gather/reshape into the [E*steps, B, ...]
    # scan layout in a jitted program — a fifth of the bytes of shipping
    # the pre-gathered staging (431 MB bf16) from the host.
    rng = np.random.default_rng(0)
    n_ex = ds.train.images.shape[0]
    steps = n_ex // BATCH_SIZE
    batch = BATCH_SIZE
    n_used = steps * BATCH_SIZE
    flat_x = jax.device_put(
        jnp.asarray(ds.train.images, dtype=jnp.dtype(stream)), dev
    )
    flat_y = jax.device_put(
        jnp.asarray(ds.train.labels, dtype=jnp.dtype(stream)), dev
    )
    perms = np.concatenate(
        [rng.permutation(n_ex)[:n_used] for _ in range(epochs_per_dispatch)]
    ).astype(np.int32)

    @jax.jit
    def _stage(fx, fy, perm):
        return (
            fx[perm].reshape(-1, BATCH_SIZE, fx.shape[1]),
            fy[perm].reshape(-1, BATCH_SIZE, fy.shape[1]),
        )

    xs, ys = _stage(flat_x, flat_y, jax.device_put(jnp.asarray(perms), dev))
    uploaded_mb = (flat_x.nbytes + flat_y.nbytes + perms.nbytes) / 1e6
    del flat_x, flat_y
    log(
        f"staged {epochs_per_dispatch} epochs x {steps} steps x {batch} "
        f"examples per dispatch ({xs.nbytes / 1e6:.0f} MB {stream} in HBM, "
        f"{uploaded_mb:.0f} MB uploaded)"
    )

    if impl in ("pallas", "pallas-epoch"):
        # NOTE: the fused kernels compute their matmuls in f32 (not bf16),
        # so an xla-vs-pallas delta includes that dtype difference.
        from distributed_tensorflow_tpu.ops.pallas_mlp import (
            make_fused_epoch_fn,
            make_fused_scanned_fn,
            to_fused,
        )

        log("pallas impls run f32 update math (xla impl runs bf16 matmuls)")
        state = to_fused(model.init(seed=1))
        if impl == "pallas-epoch":
            # The whole dispatch (E epochs) is ONE kernel launch: grid over
            # all staged steps, params VMEM-resident throughout. Batches
            # were staged in `stream` dtype above (the astype in run() is
            # then an identity).
            run_epoch = make_fused_epoch_fn(
                steps=steps * epochs_per_dispatch,
                batch_size=BATCH_SIZE,
                learning_rate=LEARNING_RATE,
                stream_dtype=jnp.dtype(stream),
            )
        else:
            run_epoch = make_fused_scanned_fn(
                batch_size=BATCH_SIZE, learning_rate=LEARNING_RATE
            )
    else:
        opt = sgd(LEARNING_RATE)
        state = SingleDevice().init_state(model, opt, seed=1)
        run_epoch = make_scanned_train_fn(model, cross_entropy, opt)

    # Commit the initial state to the device BEFORE the first dispatch:
    # eagerly-built arrays are uncommitted (sharding "unspecified"), while
    # dispatch outputs are committed — without this the second call would
    # miss the jit cache and recompile (the round-1 "warmup 2" recompile;
    # docs/performance.md).
    state = jax.device_put(state, dev)

    # Warmup: dispatch 1 compiles + absorbs the staging upload; dispatch 2
    # must then match dispatch 1's executable (no recompile) and run at
    # steady-state speed.
    for i in range(2):
        t0 = time.perf_counter()
        state, costs = run_epoch(state, xs, ys)
        _ = float(costs[-1])  # value fetch = waits for the dispatch
        log(f"warmup {i + 1}: {time.perf_counter() - t0:.2f}s")

    # Sustained measurement, TWO-POINT: each region enqueues its dispatches
    # back-to-back and syncs once by *fetching* the final cost (a value
    # read that depends on every enqueued step; the gate below needs the
    # number anyway). One sync has a fixed cost that is not measured on
    # this machine yet (ROADMAP S2), so per-epoch time is the DIFFERENCE
    # between a 4k-dispatch and a k-dispatch region over the extra epochs,
    # in which that cost cancels whatever it is; median of 3 pairs.
    from distributed_tensorflow_tpu.utils.sync import two_point_seconds

    region_costs = []
    region_count = [0]

    def region(dispatches):
        nonlocal state
        region_count[0] += 1
        t0 = time.perf_counter()
        for _ in range(dispatches):
            state, costs = run_epoch(state, xs, ys)
        final_cost = float(costs[-1])  # value fetch = waits for the region
        total = time.perf_counter() - t0
        epochs = dispatches * epochs_per_dispatch
        region_costs.append(final_cost)
        log(
            f"region {region_count[0]}: {epochs} epochs in "
            f"{total * 1000:.1f}ms ({total / epochs * 1000:.2f}ms/epoch "
            f"raw)  cost={final_cost:.4f}"
        )
        return total

    sec_per_epoch = two_point_seconds(
        lambda: region(TIMED_DISPATCHES),
        lambda: region(4 * TIMED_DISPATCHES),
        3 * TIMED_DISPATCHES * epochs_per_dispatch,
        reps=3,
    )
    log(f"two-point: {sec_per_epoch * 1000:.3f}ms/epoch (median of 3 pairs)")

    # Validity: every region trains MORE epochs (pairs alternate 25- and
    # 100-epoch regions), so the fetched costs must be finite, descend
    # overall by MORE than tol (a flat trajectory means updates were
    # no-ops — e.g. a donation bug returning stale params — and must be
    # refused, not published), and never *increase* between adjacent
    # regions (tolerance: near convergence adjacent regions may plateau to
    # within ulps; the unequal epoch spacing only makes descent easier to
    # observe). Anything else means the barrier did not actually observe
    # execution (or training diverged/stalled) — refuse to publish a
    # number rather than emit a silently-corrupt measurement.
    tol = 1e-3
    if (
        not all(np.isfinite(c) for c in region_costs)
        or region_costs[-1] >= region_costs[0] - tol
        or any(b > a + tol for a, b in zip(region_costs, region_costs[1:]))
    ):
        log(f"FATAL: region costs not finite+descending: {region_costs}")
        raise SystemExit(1)

    examples_per_sec = steps * batch / sec_per_epoch
    print(
        json.dumps(
            {
                "metric": "mnist_mlp_train_examples_per_sec_per_chip",
                "value": round(examples_per_sec, 1),
                "unit": "examples/sec/chip",
                "vs_baseline": round(examples_per_sec / BASELINE_EXAMPLES_PER_SEC, 3),
                "impl": impl,
                "stream_dtype": stream,
                "platform": dev.platform,
                "device_kind": dev.device_kind,
                "device_count": len(jax.devices()),
            }
        )
    )


if __name__ == "__main__":
    main(os.environ.get("BENCH_IMPL", "pallas-epoch"))
