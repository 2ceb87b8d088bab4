"""Ask the chip's compiler, without the chip.

Every Pallas kernel on the main path is compiled here for a DESCRIBED
TPU v5e (``topologies.get_topology_desc("tpu", "v5e:2x2")``) at the
widths ``chip_smoke.py`` runs: the installed TPU compiler refuses what
the interpreter tolerates — a block whose last two dimensions break the
(8, 128) tiling, a slice narrower than a lane tile, more fast memory than
a kernel may use. Nothing runs, so these cases say nothing about results
or times; a compile that passes is not a chip run. They guard every
later change at no chip time.

The file skips itself where the topology cannot be described (no TPU
compiler installed). The persistent compile cache is off around the
cases: an executable compiled for a described device is written to it
but cannot be read back without a chip.
"""

import os

import pytest

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else libtpu logs to /tmp

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.experimental import topologies  # noqa: E402
from jax.experimental.compilation_cache import compilation_cache  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

from distributed_tensorflow_tpu.ops import pallas_mlp  # noqa: E402
from distributed_tensorflow_tpu.ops.pallas_attention import flash_attention  # noqa: E402
from distributed_tensorflow_tpu.ops.pallas_mode import has_compiled_kernel  # noqa: E402


@pytest.fixture(scope="module")
def chip():
    """One described v5e chip, as a sharding for abstract arguments."""
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as exc:  # noqa: BLE001 — any failure means "no compiler"
        pytest.skip(f"cannot describe a v5e topology here: {exc}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def no_persistent_cache():
    old = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", old)
    compilation_cache.reset_cache()


def _compile(fn, *args) -> str:
    """Compile for the described chip; returns the program text."""
    return jax.jit(fn).lower(*args).compile().as_text()


# -- training side: flash attention at the full-width shapes, MLP kernels ----

B, L, H, DH = 8, 2048, 16, 128  # gpt-xl-L2048-flash-remat's attention


@pytest.mark.parametrize("which", ["fwd", "bwd-split", "bwd-fused"])
def test_flash_attention_compiles_at_full_width(chip, which):
    q = jax.ShapeDtypeStruct((B, L, H, DH), jnp.bfloat16, sharding=chip)

    def fwd(q, k, v, fused=None):
        return flash_attention(
            q, k, v, causal=True, interpret=False, fused=fused
        )

    if which == "fwd":
        fn = fwd
    else:
        fused = which == "bwd-fused"
        fn = jax.grad(
            lambda q, k, v: fwd(q, k, v, fused).astype(jnp.float32).sum(),
            argnums=(0, 1, 2),
        )
    assert has_compiled_kernel(_compile(fn, q, q, q))


def test_flash_attention_compiles_at_gpt2_medium_call(chip):
    """``train-gpt2m``'s call as ``GPTLM`` makes it: bfloat16 operands,
    8 rows x 16 heads of 1,024 tokens at head_dim 64, causal, blocks of
    512, forward and fused backward in one program. The full-width cases
    above prove head_dim 128 only; a 64-wide bfloat16 block is tiled
    (16, 128) and half-fills its lanes."""
    q = jax.ShapeDtypeStruct((8, 1024, 16, 64), jnp.bfloat16, sharding=chip)
    text = _compile(
        jax.value_and_grad(
            lambda q, k, v: flash_attention(
                q, k, v, causal=True, interpret=False
            ).astype(jnp.float32).sum(),
            argnums=(0, 1, 2),
        ),
        q, q, q,
    )
    calls = [ln for ln in text.splitlines() if "tpu_custom_call" in ln]
    assert len(calls) == 2, calls
    fwd, bwd = (ln.split(" custom-call(")[0] for ln in calls)
    assert "flash_fwd" in fwd and "flash_bwd_fused" in bwd
    # o in bfloat16 beside a float32 log-sum-exp; float32 dq partials (two
    # k blocks) beside bfloat16 dk, dv.
    assert fwd.count("bf16[128,1024,64]") == 1 and "f32[128,1024,1]" in fwd
    assert bwd.count("bf16[128,1024,64]") == 2 and "f32[2,128,1024,64]" in bwd


def test_flash_attention_compiles_at_two_head_sizes(chip):
    """Latent attention's call as ``HybridLM`` makes it in the 8k cell:
    bfloat16, 2 rows x 32 heads of 8,192 tokens, q and k 192 wide (not a
    multiple of the 128 lanes: a block spans the whole last dimension and
    is padded in VMEM only), v 128 wide, blocks of 1,024. The dq partials
    of a fused backward would be 3.2 GB here, so the backward is the
    two-kernel split; o and dv are 128 wide, dq and dk 192."""
    q = jax.ShapeDtypeStruct((2, 8192, 32, 192), jnp.bfloat16, sharding=chip)
    v = jax.ShapeDtypeStruct((2, 8192, 32, 128), jnp.bfloat16, sharding=chip)
    text = _compile(
        jax.value_and_grad(
            lambda q, k, v: flash_attention(
                q, k, v, causal=True, interpret=False
            ).astype(jnp.float32).sum(),
            argnums=(0, 1, 2),
        ),
        q, q, v,
    )
    lines = [ln.split(" custom-call(")[0] for ln in text.splitlines()
             if "tpu_custom_call" in ln]
    assert len(lines) == 3, lines
    calls = {name: ln for name in ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv")
             for ln in lines if name in ln.split(" = ")[0]}
    assert len(calls) == 3, lines
    assert "bf16[64,8192,128]" in calls["flash_fwd"]
    assert "bf16[64,8192,192]" in calls["flash_bwd_dq"]
    assert ("bf16[64,8192,192]" in calls["flash_bwd_dkv"]
            and "bf16[64,8192,128]" in calls["flash_bwd_dkv"])


def test_delta_rule_scores_compile_as_the_kernel_pair(chip, monkeypatch):
    """``train-kimilinear-share32``'s call of the chunked delta rule as
    ``HybridLM._kda`` makes it (2 rows x 8,192 tokens x 32 heads x 128
    channels, float32, products at the highest precision), value and
    gradient: the decayed scores of a scan step's 512 (chunk, row, head)
    units are the two Mosaic kernels, and the sub-block decay tensor that
    autodiff used to write out and read back is nowhere in the program.
    ``kda_chunked`` takes no ``interpret``; the kernels follow the backend,
    which is the CPU here, so the test says what the chip would."""
    from distributed_tensorflow_tpu.observability import names
    from distributed_tensorflow_tpu.ops import pallas_mode
    from distributed_tensorflow_tpu.ops.kda import kda_chunked

    monkeypatch.setattr(pallas_mode, "default_interpret", lambda: False)
    t = jax.ShapeDtypeStruct((2, 8192, 32, 128), jnp.float32, sharding=chip)
    beta = jax.ShapeDtypeStruct((2, 8192, 32), jnp.float32, sharding=chip)
    text = _compile(
        jax.value_and_grad(
            lambda *a: kda_chunked(
                *a, precision=jax.lax.Precision.HIGHEST).sum(),
            argnums=(0, 1, 2, 3, 4),
        ),
        t, t, t, t, beta,
    )
    assert has_compiled_kernel(text)
    calls = [ln.split(" custom-call(")[0].split(" = ")[0]
             for ln in text.splitlines() if "tpu_custom_call" in ln]
    # the forward scan's, the replay's inside the backward scan, the backward
    assert sum(names.KERNEL_KDA_SCORES_FWD in c for c in calls) == 2, calls
    assert sum(names.KERNEL_KDA_SCORES_BWD in c for c in calls) == 1, calls
    assert "f32[8,2,32,4,16,16,128]" not in text


@pytest.mark.parametrize("which", ["epoch", "per-step"])
def test_mlp_kernels_compile(chip, which):
    f32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32, sharding=chip)  # noqa: E731
    state = pallas_mlp.FusedState(
        f32(784, 100), f32(1, 100), f32(100, 10), f32(1, 10)
    )
    if which == "epoch":  # bench.py: five 550-step epochs, bf16 stream
        steps, stream = 2750, jnp.bfloat16
        fn = pallas_mlp.make_fused_epoch_fn(
            steps=steps, batch_size=100, stream_dtype=stream, interpret=False
        )
    else:
        steps, stream = 550, jnp.float32
        fn = pallas_mlp.make_fused_scanned_fn(batch_size=100, interpret=False)
    xs = jax.ShapeDtypeStruct((steps, 100, 784), stream, sharding=chip)
    ys = jax.ShapeDtypeStruct((steps, 100, 10), stream, sharding=chip)
    assert has_compiled_kernel(_compile(fn, state, xs, ys))


# -- serving side: the paged chunk program ------------------------------------


@pytest.mark.parametrize("kv", ["bf16", "int8"])
def test_paged_chunk_loop_leaves_the_pool_in_place(chip, kv):
    """gpt2-large's head geometry (20 × 64, the benchmark's chat cell) at
    three layers: inside the chunk's loop the chip's compiler makes no
    copy of the pool and no second pool, only the two scatters that
    update it. XLA:TPU lays an array out by its two minor axes, and a
    commit whose window spans the layer axis, or a carry that ends in
    [20, 64], each brought two whole-pool copies a step back (PR 27).
    Nor does it build any array the size of every slot's whole table
    (PR 29: a layer reads the pool a tile of the live-block list at a
    time, in a loop whose trip count the device computes), and the chunk
    is still one program whatever the residency."""
    import re

    import numpy as np

    from distributed_tensorflow_tpu.models.gpt import GPTLM
    from distributed_tensorflow_tpu.serve import TextServer

    model = GPTLM(
        vocab_size=512, max_len=256, model_dim=1280, num_heads=20, num_layers=3
    )
    srv = TextServer(
        model, None, slots=4, chunk=4, paged=True, block_size=16,
        kv_blocks=48, kv_dtype=kv,
    )
    on_chip = lambda x: jax.ShapeDtypeStruct(  # noqa: E731
        x.shape, x.dtype, sharding=chip)
    text = srv._chunk_jit.lower(
        jax.tree.map(on_chip, jax.eval_shape(model.init)),
        jax.tree.map(on_chip, srv._state),
    ).compile().as_text()
    assert "input_output_alias" in text
    assert text.count("HloModule ") == 1 and text.count("\nENTRY ") == 1
    assert text.startswith("HloModule jit__chunk_graph")
    loop = text[: text.index("\nENTRY ")]  # every computation but the entry
    made = [
        (op, name, np.prod([int(d) for d in dims.split(",") if d]))
        for name, dims, op in re.findall(
            r"%?([\w.\-]+) = \w+\[([\d,]*)\]\S* ([\w\-]+)\(", loop)
        if op not in ("parameter", "get-tuple-element", "bitcast", "tuple")
    ]
    size = srv._state.k.size  # no other array of the program has it
    big = [(op, name) for op, name, n in made if n == size]
    moved = [b for b in big if b[0] in (
        "copy", "concatenate", "slice", "dynamic-slice", "dynamic-update-slice")]
    assert not moved, moved
    assert sum(op == "scatter" for op, _ in big) == 2, big
    # every slot's whole table as one view: slots × max_blocks × block_size
    # rows of Hkv·Dh (a tile of the list is half of it here)
    view = srv.slots * model.max_len * model.model_dim
    assert view != size and not [m for m in made if m[2] == view]
    # one loop per layer, inside the chunk's own
    assert len(re.findall(r" while\(", loop)) == model.num_layers


# -- the hybrid stack's routed experts: the kernel a trace names ---------------


def test_ragged_products_compile_to_the_kernel_the_benchmark_reads(chip):
    """``lax.ragged_dot`` becomes a Mosaic kernel the chip's compiler names
    ``ragged-dot-*``, outside every scope of the program: the benchmark's
    ``moe_experts_share_pct.nemo`` finds the routed experts' products by
    that prefix (``benchmark/lib/scope_shares.py``). A compiler that names
    it otherwise fails here before a traced run reads the shared expert
    alone."""
    from distributed_tensorflow_tpu.ops.moe import relu2_experts

    held, d, f, rows = 8, 256, 384, 1024
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=chip)  # noqa: E731
    text = _compile(
        lambda x, up, down, sizes: relu2_experts(x, up, down, sizes, jnp.bfloat16),
        sds((rows, d), jnp.float32), sds((held, d, f), jnp.float32),
        sds((held, f, d), jnp.float32), sds((held,), jnp.int32))
    assert text.count('op_name="ragged-dot-') >= 2, "no ragged-dot kernel by name"
