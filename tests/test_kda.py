"""The gated delta rule with a per-channel decay (ops/kda.py): the chunked
form against the recurrence token by token, values and the gradients to
all five inputs, at float32 ``highest``; the kernel pair that computes a
chunk's decayed scores (interpreted here) against the ``jax.numpy`` form
it stands in for."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_tensorflow_tpu.observability import names
from distributed_tensorflow_tpu.ops import kda
from distributed_tensorflow_tpu.ops.kda import kda_chunked, kda_sequential

HI = jax.lax.Precision.HIGHEST
NAMES = ("q", "k", "v", "g", "beta")


def inputs(length, strength, seed=0, b=2, h=3, width=16, v_width=8):
    """q, k as a layer makes them (unit length, q times width^-1/2); the
    log-decay ``-strength * softplus(n)``, n ~ N(0, 2): ``strength`` 16
    with a softplus past 1.4 passes float32's -88 inside four tokens."""
    ks = jax.random.split(jax.random.key(seed), 5)
    unit = lambda t: t / jnp.linalg.norm(t, axis=-1, keepdims=True)  # noqa: E731
    q = unit(jax.random.normal(ks[0], (b, length, h, width))) * width ** -0.5
    k = unit(jax.random.normal(ks[1], (b, length, h, width)))
    v = jax.random.normal(ks[2], (b, length, h, v_width))
    g = -strength * jax.nn.softplus(
        2.0 * jax.random.normal(ks[3], (b, length, h, width)))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (b, length, h)))
    return q, k, v, g, beta


def close(a, b, tol):
    a, b = np.asarray(a), np.asarray(b)
    assert np.all(np.isfinite(a))
    scale = max(float(np.abs(b).max()), 1e-30)
    assert float(np.abs(a - b).max()) <= tol * scale, (
        float(np.abs(a - b).max()), scale)


@pytest.fixture(autouse=True)
def small_blocks(monkeypatch):
    """Sub-blocks of 8 in chunks of 32, two chunks a step: every boundary
    the module has falls inside a row a test can hold."""
    monkeypatch.setattr(kda, "SUB", 8)
    monkeypatch.setattr(kda, "CHUNKS_PER_STEP", 2)


def chunked(*args):
    return kda_chunked(*args, chunk=32, precision=HI)


# mild: a state that lives for hundreds of tokens; the strongest decay the
# configuration's start admits: A = 16 and a gate well past 1.4
@pytest.mark.parametrize("strength", [0.05, 16.0], ids=["mild", "strongest"])
@pytest.mark.parametrize("length", [128, 100], ids=["whole", "ragged"])
def test_chunked_equals_the_token_by_token_recurrence(length, strength):
    args = inputs(length, strength)
    if strength == 16.0:  # the trap is there: one chunk's decay overflows exp
        assert float(jnp.cumsum(args[3][:, :32], axis=1).min()) < -88.0
    want = kda_sequential(*args, precision=HI)
    close(chunked(*args), want, 2e-5)
    weight = jax.random.normal(jax.random.key(9), want.shape)
    grad = lambda f: jax.grad(  # noqa: E731
        lambda *a: jnp.sum(f(*a) * weight), argnums=(0, 1, 2, 3, 4))(*args)
    for name, got, ref in zip(NAMES, grad(chunked), grad(
            lambda *a: kda_sequential(*a, precision=HI))):
        try:
            close(got, ref, 1e-4)
        except AssertionError as e:
            raise AssertionError(f"gradient to {name}: {e}") from None


@pytest.mark.parametrize("chunks_per_step", [1, 2, 8])
def test_the_grouping_of_chunks_changes_nothing(chunks_per_step, monkeypatch):
    monkeypatch.setattr(kda, "CHUNKS_PER_STEP", chunks_per_step)
    args = inputs(128, 1.0)
    close(chunked(*args), kda_sequential(*args, precision=HI), 2e-5)


def test_a_state_dropped_at_a_chunk_boundary_is_seen():
    """Every chunk scanned as a sequence of its own (what a carry that is
    not passed on computes) equals the whole row's result on the first
    chunk and nowhere after."""
    args = inputs(64, 0.05)
    whole = chunked(*args)
    apart = lambda t: t.reshape(4, 32, *t.shape[2:])  # noqa: E731
    dropped = chunked(*(apart(t) for t in args)).reshape(whole.shape)
    close(dropped[:, :32], whole[:, :32], 2e-5)
    assert float(jnp.abs(dropped[:, 32:] - whole[:, 32:]).max()) > 0.05


def test_the_delta_correction_is_there():
    """Without it (``S = S' + beta k v^T``: a gated linear attention) the
    outputs differ: the sequential form says by how much."""
    q, k, v, g, beta = inputs(64, 0.05)
    f32 = jnp.float32

    def linear_attention(q, k, v, g, beta):
        def step(s, t):
            qt, kt, vt, gt, bt = t
            s = jnp.exp(gt)[..., None] * s + (
                (bt[..., None] * kt)[..., None] * vt[:, :, None, :])
            return s, jnp.einsum("zhkv,zhk->zhv", s, qt, precision=HI)

        s0 = jnp.zeros((2, 3, 16, 8), f32)
        _, o = jax.lax.scan(step, s0, tuple(
            jnp.moveaxis(t, 1, 0) for t in (q, k, v, g, beta)))
        return jnp.moveaxis(o, 0, 1)

    got = chunked(q, k, v, g, beta)
    assert float(jnp.abs(got - linear_attention(q, k, v, g, beta)).max()) > 0.05


def test_no_exponent_that_is_formed_is_positive(monkeypatch):
    """Every argument of ``exp`` inside the chunked form is <= 0 at the
    strongest decay (so no gate need be clipped and nothing overflows)."""
    seen = []
    real = jnp.exp

    def watched(x):
        seen.append(float(jnp.max(x)))
        return real(x)

    monkeypatch.setattr(kda.jnp, "exp", watched)
    with jax.disable_jit():
        kda._within_chunks(*(t[:, :32].transpose(0, 2, 1, 3) for t in
                             inputs(32, 16.0)[:4]),
                           inputs(32, 16.0)[4][:, :32].transpose(0, 2, 1),
                           8, HI)
    assert seen and max(seen) <= 0.0


def test_the_unit_lower_inverse_is_the_inverse():
    n = jnp.tril(jax.random.normal(jax.random.key(0), (3, 64, 64)), -1) * 0.3
    inv = kda._unit_lower_inverse(n, HI)
    close(jnp.matmul(jnp.eye(64) + n, inv, precision=HI),
          jnp.broadcast_to(jnp.eye(64), n.shape), 1e-4)


def test_a_sub_block_must_divide_the_chunk():
    with pytest.raises(ValueError, match="sub-block"):
        kda_chunked(*inputs(24, 1.0), chunk=20)  # SUB is 8 here


# -- the decayed scores as a kernel pair ----------------------------------------
# The cases above stay on the jax.numpy path (16-wide heads); these run the
# kernels, interpreted, at the widths they tile and the module's own
# constants.

CHUNK, WIDTH = 64, 128


@pytest.fixture
def real_blocks(monkeypatch):
    monkeypatch.setattr(kda, "SUB", 16)
    monkeypatch.setattr(kda, "CHUNKS_PER_STEP", 8)


def score_inputs(strength, per=2, b=1, h=3):
    """x = (k, q), k and the cumulative log-decay as ``_within_chunks``
    hands them over: [2, per, B, H, 64, 128] beside two [per, B, H, 64,
    128]."""
    q, k, _, g, _ = inputs(CHUNK, strength, b=per * b, h=h, width=WIDTH)
    by_unit = lambda t: t.transpose(0, 2, 1, 3).reshape(  # noqa: E731
        per, b, h, CHUNK, WIDTH)
    q, k, g = by_unit(q), by_unit(k), by_unit(g)
    return jnp.stack([k, q]), k, jnp.cumsum(g, axis=-2)


@pytest.mark.parametrize("strength", [0.05, 16.0], ids=["mild", "strongest"])
def test_the_kernel_pair_equals_the_jax_numpy_scores(strength, real_blocks):
    x, k, cum = score_inputs(strength)
    if strength == 16.0:
        assert float(cum.min()) < -88.0
    oracle = lambda *a: kda._decayed_scores(*a, kda.SUB, HI)  # noqa: E731
    kernels = lambda *a: kda._decayed_scores_kernels(*a, kda.SUB)  # noqa: E731
    want = oracle(x, k, cum)
    close(jax.jit(kernels)(x, k, cum), want, 1e-6)
    weight = jax.random.normal(jax.random.key(9), want.shape)
    grad = lambda f: jax.jit(jax.grad(  # noqa: E731
        lambda *a: jnp.sum(f(*a) * weight), argnums=(0, 1, 2)))(x, k, cum)
    for name, got, ref in zip(("x", "k", "cum"), grad(kernels), grad(oracle)):
        try:
            close(got, ref, 2e-6)
        except AssertionError as e:
            raise AssertionError(f"gradient to {name}: {e}") from None


def kernel_names(fn, *args):
    """The Pallas kernels a function's jaxpr calls, by name."""
    text = str(jax.make_jaxpr(fn)(*args))
    return {n for n in (names.KERNEL_KDA_SCORES_FWD, names.KERNEL_KDA_SCORES_BWD)
            if f"name={n}" in text}


def test_chunked_with_the_kernels_equals_the_recurrence(real_blocks):
    """128-wide heads, the module's own chunk, sub-block and step: the
    kernels are in the program, forward and backward, and values and all
    five gradients are the token-by-token recurrence's."""
    args = inputs(200, 16.0, b=1, h=2, width=WIDTH, v_width=WIDTH)
    assert float(jnp.cumsum(args[3][:, :CHUNK], axis=1).min()) < -88.0
    run = lambda *a: kda_chunked(*a, precision=HI)  # noqa: E731
    want = kda_sequential(*args, precision=HI)
    weight = jax.random.normal(jax.random.key(9), want.shape)
    loss = lambda f: (lambda *a: jnp.sum(f(*a) * weight))  # noqa: E731
    grad = lambda f: jax.grad(loss(f), argnums=(0, 1, 2, 3, 4))  # noqa: E731
    assert kernel_names(run, *args) == {names.KERNEL_KDA_SCORES_FWD}
    assert kernel_names(grad(run), *args) == {
        names.KERNEL_KDA_SCORES_FWD, names.KERNEL_KDA_SCORES_BWD}
    close(jax.jit(run)(*args), want, 2e-5)
    for name, got, ref in zip(NAMES, jax.jit(grad(run))(*args), grad(
            lambda *a: kda_sequential(*a, precision=HI))(*args)):
        try:
            close(got, ref, 1e-4)
        except AssertionError as e:
            raise AssertionError(f"gradient to {name}: {e}") from None


@pytest.mark.parametrize("width,dtype", [(64, "float32"), (128, "bfloat16")],
                         ids=["narrow", "not-float32"])
def test_what_the_kernels_do_not_tile_takes_the_jax_numpy_path(width, dtype):
    x, k, cum = (jnp.ones(shape, dtype) for shape in (
        (2, 1, 1, 2, 32, width), (1, 1, 2, 32, width), (1, 1, 2, 32, width)))
    assert not kernel_names(
        lambda *a: kda._scores(*a, kda.SUB, HI), x, k, cum)
    assert kernel_names(lambda *a: kda._scores(*a, kda.SUB, HI), *(
        jnp.ones((*t.shape[:-1], WIDTH), "float32") for t in (x, k, cum)))
