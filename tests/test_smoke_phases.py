"""chip_smoke.py's control flow, rehearsed on the CPU.

The script proves on the chip that the main path starts; nothing here is
a chip run. These cases call each phase function at a tiny width
(interpreted kernels, the suite's virtual devices for the parallel
phase) so that a wrong path, argument or comparison is found before chip
time is spent, and pin the two rules the script's contract rests on: it
cannot pass without a TPU, and the compile cache goes where the
environment says.
"""

import importlib.util
import json
import os
import subprocess
import sys

import pytest

import jax
import jax.numpy as jnp

from distributed_tensorflow_tpu.utils import compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(REPO, "chip_smoke.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture
def meter(smoke):
    m = smoke.Meter()
    yield m
    m.close()


def _lines(capsys):
    return [json.loads(ln) for ln in capsys.readouterr().out.splitlines()]


TINY_LM = dict(
    vocab_size=64, max_len=32, model_dim=32, num_heads=4, num_layers=2,
    attention_impl="flash", flash_min_len=0, remat=True,
)


def test_device_phase_refuses_cpu(smoke, meter, capsys):
    with pytest.raises(smoke.SmokeFailure, match="no accelerator"):
        smoke.phase_device(meter, 1)
    assert capsys.readouterr().out == ""  # no phase line, no result


def test_main_exits_nonzero_without_a_chip():
    """The script as the driver runs it, under JAX_PLATFORMS=cpu: non-zero
    at the device phase, a clear message, and no result on stdout."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    done = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        env=env, cwd=REPO, capture_output=True, text=True, timeout=120,
    )
    assert done.returncode != 0
    assert "no accelerator" in done.stderr
    assert done.stdout == ""


def test_block_until_ready_probe_shape(smoke):
    probe = smoke.probe_block_until_ready(n=64, iters=4)
    assert set(probe) == {
        "enqueue_s", "block_until_ready_s", "value_fetch_s", "waits"
    }


def test_mlp_phase(smoke, meter, small_datasets, capsys):
    from distributed_tensorflow_tpu.data.mnist import DataSet, Datasets

    # The session's arrays under a shuffle state of their own: the shared
    # fixture's position depends on which tests ran before on this worker,
    # and "the cost fell" over two short epochs read 9.03 -> 9.27 once in a
    # whole run of the suite (PR 26) where it passes alone.
    fresh = Datasets(
        train=DataSet(
            small_datasets.train.images, small_datasets.train.labels, seed=1
        ),
        validation=small_datasets.validation,
        test=DataSet(
            small_datasets.test.images, small_datasets.test.labels, seed=2
        ),
    )
    smoke.phase_mlp(
        meter, compiled_kernels=False, epochs=2, fused_steps=4,
        datasets=fresh,
    )
    (line,) = _lines(capsys)
    assert line["phase"] == "mlp" and line["passed"]
    assert line["trainer"]["last_cost"] < line["trainer"]["first_cost"]
    # True f32 here (the interpreter): tests/test_pallas_mlp.py's bound,
    # not the chip's.
    assert line["fused_epoch"]["max_rel_deviation_vs_xla"] <= 1e-5
    assert set(line["seconds"]) == {"wall", "compile", "run"}


def test_lm_train_then_serve_phases(smoke, meter, tmp_path, capsys):
    model, optimizer, params = smoke.phase_lm_train(
        meter, str(tmp_path), compiled_kernels=False, model_kw=TINY_LM,
        batch=4, steps=3,
    )
    smoke.phase_serve(
        meter, model, optimizer, params, str(tmp_path), slots=2, chunk=4,
        block_size=4, buckets=(8, 16), greedy_lens=(3, 3, 6),
        sampled_lens=(4, 7), max_new=5,
    )
    train, serve = _lines(capsys)
    assert train["phase"] == "lm_train" and train["passed"]
    assert train["steps"] == {"scanned": 6, "per_step": 3}
    assert train["checkpoint_step"] == 9
    assert train["last_loss"] < train["first_loss"]
    assert serve["phase"] == "serve" and serve["passed"]
    assert serve["checkpoint_step"] == 9
    assert serve["requests"] == 5 > serve["slots"]
    # f32-exact on the CPU: no near-tie is ever needed here.
    assert serve["greedy_equal_to_greedy_decode"] == 3
    assert serve["greedy_near_ties"] == []
    assert serve["sampled_reproduced"] == 2


def test_hybrid_train_phase(smoke, meter, capsys):
    smoke.phase_hybrid_train(
        meter, compiled_kernels=False,
        model_kw=dict(
            vocab_size=64, model_dim=32, pattern="EM*", ssm_heads=4,
            ssm_head_dim=8, ssm_state=16, ssm_groups=2, chunk_size=8,
            num_experts=8, experts_per_token=2, expert_dim=16, shared_dim=32,
            routed_scale=2.5, experts_held=(2, 4), num_heads=4,
            num_kv_heads=2, head_dim=8, attention_impl="flash",
            flash_min_len=0, remat=True, compute_dtype=jnp.float32,
        ),
        seq_len=32, batch=4, steps=3, loss_rtol=1e-5, grad_rtol=1e-3,
    )
    (line,) = _lines(capsys)
    assert line["phase"] == "hybrid_train" and line["passed"]
    assert line["last_loss"] < line["first_loss"]
    assert line["loss_gap"] <= 1e-5 and line["gradient_rel_err"] <= 1e-3
    assert line["steps"] == 6
    assert 0 < line["gauges"]["moe_rows_per_step"] <= 4 * 32 * 2


def test_delta_train_phase(smoke, meter, capsys):
    smoke.phase_delta_train(
        meter, compiled_kernels=False,
        model_kw=dict(
            vocab_size=64, model_dim=32, pattern="KDLE", kda_heads=4,
            kda_head_dim=8, kda_gate_rank=4, num_heads=4,
            kv_lora_rank=12, qk_nope_dim=8, qk_shared_dim=4, v_head_dim=8,
            dense_dim=48, expert_form="silu_gated", num_experts=8,
            experts_per_token=2, expert_dim=16, shared_dim=16,
            routed_scale=2.446, experts_held=(2, 4), attention_impl="flash",
            flash_min_len=0, remat=True, compute_dtype=jnp.float32,
        ),
        seq_len=32, batch=4, steps=3, loss_rtol=1e-5, grad_rtol=1e-3,
    )
    (line,) = _lines(capsys)
    assert line["phase"] == "delta_train" and line["passed"]
    assert line["last_loss"] < line["first_loss"]
    assert line["loss_gap"] <= 1e-5 and line["gradient_rel_err"] <= 1e-3
    assert line["model"]["pattern"] == "KDLE"
    assert 0 < line["gauges"]["moe_rows_per_step"] <= 4 * 32 * 2


def test_compare_streams_names_the_first_divergence(smoke):
    """A stream that differs from its reference beyond a near-tie fails
    with the position and both tokens; equal streams pass silently."""
    from distributed_tensorflow_tpu.models.gpt import GPTLM

    model = GPTLM(
        vocab_size=64, max_len=32, model_dim=32, num_heads=4, num_layers=2,
        compute_dtype=jnp.float32,
    )
    params = model.init(seed=1)
    prompt = jnp.arange(5, dtype=jnp.int32)
    want = model.greedy_decode(params, prompt[None], 4)[0, 5:]
    assert smoke.compare_streams(model, params, prompt, want, want) is None
    got = want.at[2].set((want[2] + 1) % 64)
    with pytest.raises(smoke.SmokeFailure, match="'position': 2"):
        smoke.compare_streams(model, params, prompt, got, want)


def test_parallel_phase_on_virtual_devices(smoke, meter, small_datasets, capsys):
    n = len(jax.devices())
    smoke.phase_parallel_mlp(meter, datasets=small_datasets)
    smoke.phase_parallel_lm(
        meter, model_kw=dict(TINY_LM, compute_dtype=jnp.float32), batch=8,
        steps=2,
    )
    mlp, lm = _lines(capsys)
    assert mlp["phase"] == "parallel_mlp" and mlp["devices"] == n
    assert mlp["all_reduce_in_program"]
    assert lm["phase"] == "parallel_lm" and lm["mesh"] == {
        "data": n // 2, "model": 2
    }
    assert lm["max_rel_deviation"] <= smoke.TP_RTOL
    full = lm["params"]["full_bytes"]
    assert all(b < full for b in lm["params"]["bytes_per_device"].values())


# -- the compile-cache rule ------------------------------------------------


@pytest.fixture
def no_configured_cache():
    old = jax.config.jax_compilation_cache_dir
    jax.config.update("jax_compilation_cache_dir", None)
    yield
    jax.config.update("jax_compilation_cache_dir", old)


def test_cache_dir_from_environment_is_left_to_jax(
    monkeypatch, no_configured_cache
):
    monkeypatch.setenv(compile_cache.ENV_VAR, "/somewhere/else")
    assert compile_cache.configure_compile_cache() == "/somewhere/else"
    assert jax.config.jax_compilation_cache_dir is None  # nothing set in code


def test_cache_dir_default_is_fixed_inside_the_checkout(
    monkeypatch, no_configured_cache
):
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    want = os.path.join(REPO, ".jax_cache")
    assert compile_cache.configure_compile_cache() == want
    assert jax.config.jax_compilation_cache_dir == want
    assert compile_cache.configure_compile_cache() == want  # and stays


def test_bench_refuses_to_time_without_a_chip():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    done = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py")],
        env=env, cwd=REPO, capture_output=True, text=True, timeout=120,
    )
    assert done.returncode == 2
    assert "not a TPU" in done.stderr
    assert done.stdout == ""
