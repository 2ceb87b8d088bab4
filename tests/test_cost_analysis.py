"""Cost-analysis/roofline tool: analytical FLOPs/bytes for a compiled step.

Replaces the reference's wall-clock-only performance reasoning (AvgTime
lines, reference tfdist_between.py:98-110) with compiler-analytical
observability; numbers must be present, positive, and scale with batch.
"""

import json

import jax.numpy as jnp

from distributed_tensorflow_tpu.models import MLP
from distributed_tensorflow_tpu.tools import cost_analysis


def small_mlp():
    return MLP(hidden_dim=16, compute_dtype=jnp.float32)


def test_report_shape_and_positivity():
    r = cost_analysis.analyze(small_mlp(), batch_size=32)
    assert r["param_count"] == 784 * 16 + 16 + 16 * 10 + 10
    assert r["flops_per_step"] > 0
    assert r["bytes_per_step"] > 0
    # The CPU the tests run on has no row in CHIP_PEAKS: the analytical
    # half is reported, the classification is refused.
    assert r["bound"] == "unknown"
    assert r["roofline_floor_us"] is None
    assert r["examples_per_sec_roofline"] is None


def test_known_chip_classifies():
    class V5e:
        device_kind = "TPU v5 lite"

    r = cost_analysis.analyze(small_mlp(), batch_size=32, device=V5e())
    assert r["bound"] in ("compute", "memory")
    assert r["roofline_floor_us"] > 0
    assert r["examples_per_sec_roofline"] > 0


def test_flops_scale_with_batch():
    small = cost_analysis.analyze(small_mlp(), batch_size=32)
    big = cost_analysis.analyze(small_mlp(), batch_size=128)
    # 4x the batch ≈ 4x the matmul FLOPs (within overhead slack).
    ratio = big["flops_per_step"] / small["flops_per_step"]
    assert 3.0 < ratio < 5.0


def test_flops_match_analytic_estimate():
    # fwd matmuls: B*(in*h + h*out)*2 FLOPs; fwd+bwd ≈ 3x (two extra
    # matmul-shaped products per layer in the backward pass).
    B, i, h, o = 64, 784, 16, 10
    r = cost_analysis.analyze(small_mlp(), batch_size=B)
    matmul_fwd = 2 * B * (i * h + h * o)
    assert matmul_fwd < r["flops_per_step"] < 5 * matmul_fwd


def test_cli_json(capsys):
    rc = cost_analysis.main(["--model", "mlp", "--batch", "16", "--json"])
    assert rc == 0
    out = capsys.readouterr().out.strip()
    r = json.loads(out)
    assert r["model"] == "MLP" and r["batch_size"] == 16


def test_cli_text(capsys):
    rc = cost_analysis.main(["--model", "lstm", "--batch", "8"])
    assert rc == 0
    out = capsys.readouterr().out
    assert "bound:" in out and "unknown" in out


def test_unknown_chip_refuses_to_classify():
    class FakeDev:
        device_kind = "tpu v99 mega"

    r = cost_analysis.analyze(small_mlp(), batch_size=8, device=FakeDev())
    assert r["bound"] == "unknown"
    assert r["roofline_floor_us"] is None
    assert r["flops_per_step"] > 0  # analytical part still reported
    assert "unknown" in cost_analysis.format_report(r)


def test_analyze_lm_reports_roofline():
    from distributed_tensorflow_tpu.models.gpt import GPTLM
    from distributed_tensorflow_tpu.tools.cost_analysis import analyze_lm

    report = analyze_lm(
        GPTLM(
            vocab_size=64, max_len=32, model_dim=32, num_heads=4,
            num_layers=2, compute_dtype="float32",
        ),
        batch_size=4,
    )
    assert report["model"] == "GPTLM"
    assert report["tokens_per_step"] == 4 * 32
    assert report["param_count"] > 0
    assert report["flops_per_step"] > 0
    assert report["bound"] in ("compute", "memory", "unknown")
