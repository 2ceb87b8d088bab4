"""Pallas TPU kernel: the MLP train step fused into one kernel launch.

The reference's hot loop is fwd → loss → bwd → SGD apply per batch, executed
as a TF graph of many small CUDA kernels (reference tfsingle.py:78-80). XLA
already fuses most of that; this module goes the rest of the way with a
single Pallas kernel computing forward, naive-CE loss, analytic backward,
and the in-place SGD update in one VMEM-resident program:

    z1 = x·W1+b1; h = σ(z1); p = softmax(h·W2+b2)
    dlogits = (p - y)/B                        (softmax+CE analytic grad)
    dW2 = hᵀ·dlogits   dh = dlogits·W2ᵀ
    dz1 = dh·h·(1-h)   dW1 = xᵀ·dz1
    W ← W - lr·dW      b ← b - lr·db

Every tensor (batch 100×784 plus both weight matrices, ~700 KB f32) fits in
VMEM simultaneously, so HBM traffic per step is exactly one read of
x/y/params and one write of params — the bandwidth floor. The four matmuls
hit the MXU with f32 accumulation.

Biases are carried as (1, H) 2-D rows: TPU tiling is (sublane, lane)-
oriented and 1-D vectors would be padded awkwardly.
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from distributed_tensorflow_tpu.models.mlp import MLPParams
from distributed_tensorflow_tpu.observability import names
from distributed_tensorflow_tpu.ops.pallas_mode import resolve_interpret

_LOG_EPS = 1e-30


def _mlp_sgd_math(x, y, w1, b1, w2, b2, lr: float):
    """The fwd/loss/bwd/SGD math shared by both kernels (one source of
    truth — the scan-vs-epoch equivalence test depends on it). Shapes stay
    2-D throughout: Mosaic's vector layouts are (sublane, lane)-tiled and
    1-D intermediates trip relayout bugs. Returns (nw1, nb1, nw2, nb2,
    cost_scalar)."""
    # Forward (MXU matmuls, f32 accumulation).
    z1 = jnp.dot(x, w1, preferred_element_type=jnp.float32) + b1
    h = jax.nn.sigmoid(z1)
    logits = jnp.dot(h, w2, preferred_element_type=jnp.float32) + b2
    p = jax.nn.softmax(logits, axis=-1)

    # The reference's naive CE (NaN-guarded), reference tfsingle.py:44-45.
    inv_b = 1.0 / x.shape[0]
    per_example = -jnp.sum(
        y * jnp.log(jnp.maximum(p, _LOG_EPS)), axis=-1, keepdims=True
    )
    cost = jnp.sum(per_example) * inv_b
    dlogits = (p - y) * inv_b
    dw2 = jnp.dot(h.T, dlogits, preferred_element_type=jnp.float32)
    db2 = jnp.sum(dlogits, axis=0, keepdims=True)
    dh = jnp.dot(dlogits, w2.T, preferred_element_type=jnp.float32)
    dz1 = dh * h * (1.0 - h)
    dw1 = jnp.dot(x.T, dz1, preferred_element_type=jnp.float32)
    db1 = jnp.sum(dz1, axis=0, keepdims=True)

    # Fused SGD apply (C10 semantics: plain SGD, reference tfdist_between.py:64-66).
    return w1 - lr * dw1, b1 - lr * db1, w2 - lr * dw2, b2 - lr * db2, cost


def _fused_train_kernel(
    x_ref, y_ref, w1_ref, b1_ref, w2_ref, b2_ref,
    nw1_ref, nb1_ref, nw2_ref, nb2_ref, cost_ref,
    *, lr: float,
):
    nw1, nb1, nw2, nb2, cost = _mlp_sgd_math(
        x_ref[:], y_ref[:], w1_ref[:], b1_ref[:], w2_ref[:], b2_ref[:], lr
    )
    cost_ref[0, 0] = cost
    nw1_ref[:] = nw1
    nb1_ref[:] = nb1
    nw2_ref[:] = nw2
    nb2_ref[:] = nb2


class FusedState(NamedTuple):
    """Params with 2-D biases, the kernel's native layout."""

    w1: jax.Array
    b1: jax.Array  # [1, hidden]
    w2: jax.Array
    b2: jax.Array  # [1, out]


def to_fused(params: MLPParams) -> FusedState:
    # copy=True: the caller's buffers may be donated elsewhere (the fused
    # step itself donates via input_output_aliases), so never alias them.
    return FusedState(
        jnp.array(params.w1, jnp.float32, copy=True),
        jnp.array(params.b1.reshape(1, -1), jnp.float32, copy=True),
        jnp.array(params.w2, jnp.float32, copy=True),
        jnp.array(params.b2.reshape(1, -1), jnp.float32, copy=True),
    )


def from_fused(state: FusedState) -> MLPParams:
    return MLPParams(state.w1, state.b1[0], state.w2, state.b2[0])


def make_fused_train_step(
    *,
    batch_size: int,
    in_dim: int = 784,
    hidden_dim: int = 100,
    out_dim: int = 10,
    learning_rate: float = 0.001,
    interpret: bool | None = None,
):
    """Build ``step(fused_state, x, y) -> (fused_state, cost)``, one kernel
    launch per call. ``interpret=None`` auto-selects the Pallas interpreter
    off-TPU (CI / CPU-mesh tests) and the Mosaic compiler on TPU."""
    interpret = resolve_interpret(interpret)

    f32 = jnp.float32
    call = pl.pallas_call(
        partial(_fused_train_kernel, lr=learning_rate),
        out_shape=(
            jax.ShapeDtypeStruct((in_dim, hidden_dim), f32),
            jax.ShapeDtypeStruct((1, hidden_dim), f32),
            jax.ShapeDtypeStruct((hidden_dim, out_dim), f32),
            jax.ShapeDtypeStruct((1, out_dim), f32),
            jax.ShapeDtypeStruct((1, 1), f32),
        ),
        in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM)] * 6,
        out_specs=(
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ),
        # Params update in place: new W/b alias the incoming buffers.
        input_output_aliases={2: 0, 3: 1, 4: 2, 5: 3},
        interpret=interpret,
        name=names.KERNEL_MLP_TRAIN_STEP,
    )

    @jax.jit
    def step(state: FusedState, x: jax.Array, y: jax.Array):
        nw1, nb1, nw2, nb2, cost = call(
            x.astype(f32), y.astype(f32), state.w1, state.b1, state.w2, state.b2
        )
        return FusedState(nw1, nb1, nw2, nb2), cost[0, 0]

    return step


def make_fused_scanned_fn(
    *,
    batch_size: int,
    learning_rate: float = 0.001,
    interpret: bool | None = None,
    **dims,
):
    """Scan the fused kernel over a staged epoch: [steps, B, ...] → one
    dispatch per epoch AND one kernel per step inside it."""
    step = make_fused_train_step(
        batch_size=batch_size, learning_rate=learning_rate, interpret=interpret, **dims
    )

    @partial(jax.jit, donate_argnums=0)
    def run(state: FusedState, xs: jax.Array, ys: jax.Array):
        def body(state, batch):
            x, y = batch
            state, cost = step(state, x, y)
            return state, cost

        return jax.lax.scan(body, state, (xs, ys))

    return run


def _epoch_kernel(
    x_ref, y_ref, w1_ref, b1_ref, w2_ref, b2_ref,
    nw1_ref, nb1_ref, nw2_ref, nb2_ref, cost_ref,
    *, lr: float,
):
    """Grid step i = SGD step i of the epoch. Params live in the *output*
    VMEM blocks (constant index map → resident across the whole grid, never
    round-tripping HBM between steps); each step streams only its batch
    block in. First iteration seeds the output blocks from the inputs."""
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _seed():
        nw1_ref[:] = w1_ref[:]
        nb1_ref[:] = b1_ref[:]
        nw2_ref[:] = w2_ref[:]
        nb2_ref[:] = b2_ref[:]

    # Batches may be staged bf16 (halves the per-step HBM stream — the
    # only HBM traffic this kernel has); math runs f32 as always.
    nw1, nb1, nw2, nb2, cost = _mlp_sgd_math(
        x_ref[0].astype(jnp.float32),
        y_ref[0].astype(jnp.float32),
        nw1_ref[:], nb1_ref[:], nw2_ref[:], nb2_ref[:], lr,
    )
    # Costs are written into (8, 128) VMEM blocks — the smallest f32 tile
    # TPU block specs allow — grouped 8 steps per block (index map i // 8):
    # the block stays resident across its 8 revisits, each step storing its
    # lane-broadcast scalar into sublane i % 8. The host reads [:, 0].
    cost_ref[pl.ds(i % 8, 1), :] = jnp.broadcast_to(
        cost, (1, cost_ref.shape[1])
    )
    nw1_ref[:] = nw1
    nb1_ref[:] = nb1
    nw2_ref[:] = nw2
    nb2_ref[:] = nb2


def _epoch_call(
    *,
    steps: int,
    batch_size: int,
    in_dim: int,
    hidden_dim: int,
    out_dim: int,
    learning_rate: float,
    interpret: bool,
):
    """The raw whole-epoch ``pallas_call`` (grid over ``steps``), shared by
    the single-chip jitted wrapper (``make_fused_epoch_fn``) and the
    data-parallel composition (``make_fused_async_epoch_fn``), which embeds
    it under ``shard_map``."""
    f32 = jnp.float32
    full = lambda *shape: pl.BlockSpec(shape, lambda i: (0,) * len(shape))
    return pl.pallas_call(
        partial(_epoch_kernel, lr=learning_rate),
        grid=(steps,),
        in_specs=[
            pl.BlockSpec((1, batch_size, in_dim), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, batch_size, out_dim), lambda i: (i, 0, 0)),
            full(in_dim, hidden_dim),
            full(1, hidden_dim),
            full(hidden_dim, out_dim),
            full(1, out_dim),
        ],
        out_specs=(
            full(in_dim, hidden_dim),
            full(1, hidden_dim),
            full(hidden_dim, out_dim),
            full(1, out_dim),
            pl.BlockSpec((8, 128), lambda i: (i // 8, 0)),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((in_dim, hidden_dim), f32),
            jax.ShapeDtypeStruct((1, hidden_dim), f32),
            jax.ShapeDtypeStruct((hidden_dim, out_dim), f32),
            jax.ShapeDtypeStruct((1, out_dim), f32),
            jax.ShapeDtypeStruct((-(-steps // 8) * 8, 128), f32),
        ),
        interpret=interpret,
        name=names.KERNEL_MLP_TRAIN_EPOCH,
    )


def make_fused_epoch_fn(
    *,
    steps: int,
    batch_size: int,
    in_dim: int = 784,
    hidden_dim: int = 100,
    out_dim: int = 10,
    learning_rate: float = 0.001,
    stream_dtype: jnp.dtype = jnp.float32,
    interpret: bool | None = None,
):
    """Build ``run(state, xs, ys) -> (state, costs)`` where the WHOLE epoch
    (or several concatenated epochs) is ONE kernel launch: ``grid=(steps,)``
    walks the staged batches, parameters stay VMEM-resident across every
    step (constant-index-map output blocks), and per-step HBM traffic is
    exactly the batch read plus one scalar cost write — strictly less than
    the scan-of-kernels path, which re-reads and re-writes the params each
    step. ``xs``/``ys`` are ``[steps, batch, ...]`` in ``stream_dtype``.

    ``stream_dtype=bf16`` stages the batches half-width — the batch read is
    the kernel's only per-step HBM traffic — and upcasts in VMEM; the
    update math stays f32 (costs differ from f32 staging only by input
    rounding).

    Tried and rejected: unrolling U steps per grid iteration (measured
    *slower* on v5e, ~6.2 vs ~5.1 ms per 550-step epoch at U=8 — the
    per-grid-step overhead is already hidden behind the batch-block DMA,
    and bigger blocks pipeline worse; see docs/performance.md).
    """
    interpret = resolve_interpret(interpret)
    call = _epoch_call(
        steps=steps,
        batch_size=batch_size,
        in_dim=in_dim,
        hidden_dim=hidden_dim,
        out_dim=out_dim,
        learning_rate=learning_rate,
        interpret=interpret,
    )

    @partial(jax.jit, donate_argnums=0)
    def run(state: FusedState, xs: jax.Array, ys: jax.Array):
        nw1, nb1, nw2, nb2, costs = call(
            xs.astype(stream_dtype),
            ys.astype(stream_dtype),
            state.w1, state.b1, state.w2, state.b2,
        )
        return FusedState(nw1, nb1, nw2, nb2), costs[:steps, 0]

    return run


def make_fused_async_epoch_fn(
    mesh,
    *,
    steps: int,
    batch_size: int,
    in_dim: int = 784,
    hidden_dim: int = 100,
    out_dim: int = 10,
    learning_rate: float = 0.001,
    avg_every: int = 0,
    stream_dtype: jnp.dtype = jnp.float32,
    interpret: bool | None = None,
):
    """The whole-epoch grid kernel composed with data parallelism — the
    framework's fastest engine distributed over the ``data`` mesh axis
    (round-1 gap: the bench-default kernel was single-device only; the
    reference's whole point was distributing this workload, reference
    tfdist_between.py:86-95).

    Async local-SGD is the natural first composition because an exchange
    round needs ZERO cross-chip traffic inside it: each chip runs the grid
    kernel over its own ``avg_every``-step batch slice with params
    VMEM-resident (one Mosaic launch per round), then all copies jump to the
    ``pmean`` over ICI — the same semantics as
    ``AsyncDataParallel.make_scanned_train_fn`` with the per-step XLA scan
    replaced by the Pallas grid. (A per-step sync composition would need a
    collective between grid steps, destroying the VMEM residency that makes
    the kernel fast.)

    Returns ``run(state, xs, ys) -> (state, costs)`` with ``state`` a
    ``FusedState`` of stacked per-chip copies (leading axis ``n`` sharded
    over ``data``), ``xs``/``ys`` ``[steps, n*batch, ...]`` with dim 1
    sharded over ``data``, and ``costs`` ``[steps]`` the per-step mean over
    chips. ``update_scale`` is not modeled here: per-chip lr stays the
    constructor's ``learning_rate`` (pass a pre-scaled value if emulating
    the async update-count effect).
    """
    from jax.sharding import PartitionSpec as P

    interpret = resolve_interpret(interpret)
    # Exchange cadence must match _scan_with_exchange exactly: rounds only
    # when a full avg_every round fits (an epoch shorter than avg_every
    # runs plain, with NO exchange — strategy.py:82's `steps >= avg_every`).
    use_rounds = bool(avg_every) and steps >= avg_every
    seg = avg_every if use_rounds else steps
    rounds = steps // seg
    head = rounds * seg
    kw = dict(
        batch_size=batch_size,
        in_dim=in_dim,
        hidden_dim=hidden_dim,
        out_dim=out_dim,
        learning_rate=learning_rate,
        interpret=interpret,
    )
    call = _epoch_call(steps=seg, **kw)
    tail_call = _epoch_call(steps=steps - head, **kw) if steps % seg else None

    def _exchange(params):
        # Every copy jumps to the mean (AsyncDataParallel.make_exchange_fn
        # semantics), cast back to varying for the scan carry.
        from distributed_tensorflow_tpu.ops.collectives import to_varying

        return tuple(
            to_varying(jax.lax.pmean(p, "data"), "data") for p in params
        )

    def local_epoch(state: FusedState, xs, ys):
        # Local view: state leaves [1, ...] (this chip's copy), xs/ys
        # [steps, batch, ...] (this chip's slice of each global batch).
        params = tuple(a[0] for a in state)
        xs = xs.astype(stream_dtype)
        ys = ys.astype(stream_dtype)

        def round_body(params, xy):
            # Exchange after every round (incl. an epoch-final one when the
            # count divides) — _scan_with_exchange's cadence exactly; the
            # remainder steps run after the last exchange, below.
            xr, yr = xy
            nw1, nb1, nw2, nb2, costs = call(xr, yr, *params)
            nw1, nb1, nw2, nb2 = _exchange((nw1, nb1, nw2, nb2))
            return (nw1, nb1, nw2, nb2), costs[:seg, 0]

        if use_rounds:
            params, costs = jax.lax.scan(
                round_body,
                params,
                (
                    xs[:head].reshape(rounds, seg, *xs.shape[1:]),
                    ys[:head].reshape(rounds, seg, *ys.shape[1:]),
                ),
            )
            costs = costs.reshape(head)
            if tail_call is not None:
                nw1, nb1, nw2, nb2, tail_costs = tail_call(
                    xs[head:], ys[head:], *params
                )
                params = (nw1, nb1, nw2, nb2)
                costs = jnp.concatenate([costs, tail_costs[: steps - head, 0]])
        else:
            nw1, nb1, nw2, nb2, costs = call(xs, ys, *params)
            params = (nw1, nb1, nw2, nb2)
            costs = costs[:steps, 0]

        new = FusedState(*(p[None] for p in params))
        return new, costs[:, None]  # [steps, 1] → global [steps, n]

    mapped = jax.shard_map(
        local_epoch,
        mesh=mesh,
        in_specs=(
            FusedState(P("data"), P("data"), P("data"), P("data")),
            P(None, "data"),
            P(None, "data"),
        ),
        out_specs=(
            FusedState(P("data"), P("data"), P("data"), P("data")),
            P(None, "data"),
        ),
        # pallas_call outputs carry no varying-mesh-axes metadata; the specs
        # above are the full contract.
        check_vma=False,
    )

    @partial(jax.jit, donate_argnums=0)
    def run(state: FusedState, xs: jax.Array, ys: jax.Array):
        state, costs = mapped(state, xs, ys)
        return state, jnp.mean(costs, axis=1)

    return run


def make_fused_compiled_run_fn(
    *,
    batch_size: int,
    epochs: int,
    in_dim: int = 784,
    hidden_dim: int = 100,
    out_dim: int = 10,
    learning_rate: float = 0.001,
    shuffle: bool = True,
    steps_per_epoch: int | None = None,
    stream_dtype: jnp.dtype = jnp.bfloat16,
    interpret: bool | None = None,
):
    """The whole-run compiled path (train/compiled_run.py's contract) with
    the inner per-epoch step scan replaced by the whole-epoch Pallas grid
    kernel: ``lax.scan`` over epochs, each iteration building its shuffled
    [steps, B, ...] staging by on-device gather and running it as ONE kernel
    launch with params VMEM-resident. Same observable surface —
    ``fn(state, train_x, train_y, test_x, test_y, key) -> (state, {"costs":
    [epochs, steps], "accuracy": [epochs]})`` with ``state`` a
    ``FusedState`` — at the grid kernel's per-step cost instead of the XLA
    scan's. This is how the Trainer API reaches bench.py's engine
    (round-1 gap: the fastest kernel existed only inside bench.py).

    ``train_x``/``train_y`` are full flat arrays, any float dtype; batches
    are gathered and streamed in ``stream_dtype`` (bf16 default: the batch
    read is the kernel's only per-step HBM traffic; update math stays f32).
    Eval runs in f32 jnp ops on the current params (same math as
    ``MLP(compute_dtype=f32).apply``).
    """
    interpret = resolve_interpret(interpret)

    from distributed_tensorflow_tpu.train.compiled_run import wrapped_epoch_perm

    @partial(jax.jit, donate_argnums=0)
    def run(state: FusedState, train_x, train_y, test_x, test_y, key):
        steps = (
            train_x.shape[0] // batch_size
            if steps_per_epoch is None
            else steps_per_epoch
        )
        need = steps * batch_size
        domain = need if steps_per_epoch is None else train_x.shape[0]
        k = (need + domain - 1) // domain if need else 1
        call = _epoch_call(
            steps=steps,
            batch_size=batch_size,
            in_dim=in_dim,
            hidden_dim=hidden_dim,
            out_dim=out_dim,
            learning_rate=learning_rate,
            interpret=interpret,
        )
        fx = train_x.astype(stream_dtype)
        fy = train_y.astype(stream_dtype)
        tx = test_x.astype(jnp.float32)
        ty = test_y.astype(jnp.float32)

        def epoch_body(carry, _):
            (w1, b1, w2, b2), key = carry
            key, sub = jax.random.split(key)
            perm = wrapped_epoch_perm(
                sub, domain=domain, need=need, k=k, shuffle=shuffle
            )
            xs = jnp.take(fx, perm, axis=0).reshape(steps, batch_size, in_dim)
            ys = jnp.take(fy, perm, axis=0).reshape(steps, batch_size, out_dim)
            nw1, nb1, nw2, nb2, costs = call(xs, ys, w1, b1, w2, b2)
            # In-graph eval, f32 (the per-epoch Test-Accuracy line).
            h = jax.nn.sigmoid(
                jnp.dot(tx, nw1, preferred_element_type=jnp.float32) + nb1
            )
            logits = jnp.dot(h, nw2, preferred_element_type=jnp.float32) + nb2
            acc = jnp.mean(
                (jnp.argmax(logits, -1) == jnp.argmax(ty, -1)).astype(jnp.float32)
            )
            return ((nw1, nb1, nw2, nb2), key), (costs[:steps, 0], acc)

        (params, _), (costs, accs) = jax.lax.scan(
            epoch_body, (tuple(state), key), None, length=epochs
        )
        return FusedState(*params), {"costs": costs, "accuracy": accs}

    return run


def to_fused_stacked(params: MLPParams, n: int, sharding=None) -> FusedState:
    """Stack ``n`` identical per-chip copies of ``params`` (every reference
    worker starts from the same seed-1 graph) for the async-DP composition;
    ``sharding`` (e.g. ``NamedSharding(mesh, P("data"))``) places copy i on
    chip i."""
    base = to_fused(params)
    stacked = FusedState(
        *(jnp.broadcast_to(a[None], (n,) + a.shape) for a in base)
    )
    if sharding is not None:
        stacked = jax.device_put(stacked, sharding)
    return stacked
