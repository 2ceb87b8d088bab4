"""The ``laguna_serve`` kind at a tiny size on the CPU, the look for a chip
skipped: the cell end to end through ``TextServer``'s paged engine
(window layers in rings that wrap, full layers in the pool), the int8
control and each planted fault seen as ``correct`` false, the
configuration's file against the published row, the parameter count,
the new readers on made-up runs. Nothing here is a measurement."""

import copy

import numpy as np
import pytest

from benchmark.lib import (
    flops_laguna, harness, laguna_serve_cell as cell, program_scopes,
    reference_laguna, weights_laguna as weights,
)
from benchmark.tests import helpers

CELL = "serve-laguna-mixed"

TINY_CFG = {
    "name": "tiny-laguna", "hidden_size": 64, "vocab_size": 256,
    "intermediate_size": 96, "num_hidden_layers": 5,
    "num_attention_heads": 6, "num_key_value_heads": 2, "head_dim": 16,
    "rms_norm_eps": 1e-6, "num_experts": 16, "num_experts_per_tok": 4,
    "moe_intermediate_size": 16, "shared_expert_intermediate_size": 16,
    "sliding_window": 8, "moe_routed_scaling_factor": 2.5,
    "rope_parameters": {
        "full_attention": {
            "rope_theta": 500000, "rope_type": "yarn", "factor": 64,
            "original_max_position_embeddings": 16, "beta_slow": 1,
            "beta_fast": 64, "attention_factor": 1.4158883083359672,
            "partial_rotary_factor": 0.5},
        "sliding_attention": {"rope_type": "default", "rope_theta": 10000,
                              "partial_rotary_factor": 1}},
    "layer_types": ["full_attention"] + ["sliding_attention"] * 3
    + ["full_attention"],
    "mlp_layer_types": ["dense"] + ["sparse"] * 4,
    "num_attention_heads_per_layer": [6, 8, 8, 8, 6],
    "published": {"num_hidden_layers": 1},
    "deployment": {"pattern": "GDWEWEWEGE"},
}
# Set as the real one is, from readings at this size on the CPU (seed 5,
# printed by `pytest -s`): above a sound run's mean gap (0.17) and below
# what the control (0.59) and each fault (1.5 to 4.3) read. The widest gap
# is printed, not compared, as in the cell's limits file.
TINY_LIMITS = {"mean_logit_gap": 0.5}


def tiny_traffic() -> dict:
    t = copy.deepcopy(harness.traffic("mixed-long-short"))
    t["server"].update(slots=4, chunk=4, block_size=4, kv_blocks=96,
                       buckets=[16, 32, 64])
    t.update(max_len=96, attention_impl="xla", trace_seconds=0.5,
             check_requests=3)
    t["arrivals"].update(rate_per_s=4.0, warmup_s=1.0)
    # contexts of 5 to 9 windows: the rings wrap
    t["prompt_short"].update(min=4, max=16, median=10)
    t["prompt_long"].update(min=40, max=64, median=52)
    t["answer"].update(min=6, max=24, median=12)
    return t


@pytest.fixture
def limits(monkeypatch):
    monkeypatch.setattr(harness, "limits", lambda name: TINY_LIMITS)
    # At 64 wide a start of N(0, 0.02) leaves every layer's output far
    # under the embedding's: no fault would reach a logit. A larger start
    # (and a published depth of 1) lets them.
    monkeypatch.setattr(weights, "STD", 0.3)


def _run(monkeypatch, gaps_kw=None):
    """The cell end to end; ``gaps_kw`` puts a faulty (or int8)
    reference in the reference's place, as the calibration does."""
    if gaps_kw:
        real = cell.sample_gaps
        monkeypatch.setattr(
            cell, "sample_gaps", lambda *a, **kw: real(*a, **{**kw, **gaps_kw}))
    ctx = helpers.context(CELL, TINY_CFG, tiny_traffic(), seconds=2.0)
    return cell.run(ctx)


def test_laguna_cell_is_correct(monkeypatch, limits):
    run = _run(monkeypatch)
    print({k: v for k, (v, _) in run.checks.items()})
    assert harness.judge(run.checks), run.checks
    assert run.failed == 0 and run.attempted >= 3
    assert run.compiles_in_window == 0
    assert run.end_to_end["tpot_p50_ms"] > 0
    c = run.counters
    assert c["ring_blocks"] == 3 and c["checked_tokens"] > 0
    assert c["decode_key_rows_full"] > c["decode_key_rows_window"] > 0
    touched = harness.metric_reader("experts_touched_mean.laguna")(run)
    # 4 slots x 4 choices over 16 experts: between 4 and 16 a step
    assert 1 <= touched <= 16
    for name in ("decode_step_ms.laguna", "decode_roofline_pct.laguna",
                 "moe_decode_share_pct.laguna", "attn_window_share_pct.laguna",
                 "flash_fwd_roofline_pct.laguna"):
        assert harness.metric_reader(name)(run) is None  # no trace


@pytest.mark.parametrize("gaps_kw", [
    {"precision": "int8"}, *({"fault": f} for f in reference_laguna.FAULTS),
], ids=lambda kw: next(iter(kw.values())))
def test_the_control_and_each_fault_is_not_correct(monkeypatch, limits, gaps_kw):
    run = _run(monkeypatch, gaps_kw)
    print({k: v for k, (v, _) in run.checks.items()})
    assert not harness.judge(run.checks), run.checks


def test_the_configuration_is_the_published_row_cut_to_five_layers():
    cfg = harness.config("laguna-xs.2")
    assert cfg["reduced"] == ["num_hidden_layers", "layer_types",
                              "mlp_layer_types", "num_attention_heads_per_layer"]
    for key in cfg["reduced"][1:]:
        assert cfg[key] == cfg["published"][key][:5]
    assert cfg["published"]["num_hidden_layers"] == 40
    z = weights.dims(cfg)
    assert z["pattern"] == "GDWEWEWEGE"
    assert (z["full_heads"], z["window_heads"], z["kv_heads"]) == (48, 64, 8)
    assert weights.count(cfg) == weights.EXPECTED == 3_869_857_792
    assert z["full_rope"]["dims"] == 64 and z["window_rope"]["dims"] == 128


def test_the_schedule_is_one_fixed_order_of_both_classes():
    t = harness.traffic("mixed-long-short")
    a = cell.mixed_schedule(t, 100352, 1, 45.0)
    b = cell.mixed_schedule(t, 100352, 2 ** 31 + 7, 45.0)
    window = [r for r in a if r.due_s >= 0]
    assert sum(r.tokens.size >= 8192 for r in window) == round(len(window) / 4)
    # every seed runs the same lengths, answers and due times in the same
    # order (which requests overlap which sets the time per token) ...
    for part in (lambda r: r.tokens.size, lambda r: r.max_new,
                 lambda r: r.due_s):
        assert [part(r) for r in a] == [part(r) for r in b]
    # ... over prompts of its own
    assert all(not np.array_equal(x.tokens, y.tokens) for x, y in zip(a, b))


def test_flash_and_decode_readers_on_made_up_runs():
    import types

    cfg = harness.config("laguna-xs.2")
    z = weights.dims(cfg)
    # a decode step with nothing live reads every weight but the routed
    # experts and the embedding table, and the experts it touched
    base = flops_laguna.decode_step_min_bytes(cfg, 0, 0, 0)
    one = flops_laguna.decode_step_min_bytes(cfg, 1, 0, 0)
    assert one - base == 2 * z["n_e"] * 3 * 2048 * 512
    assert flops_laguna.band_pairs(10, 4) == 1 + 2 + 3 + 4 * 7
    assert flops_laguna.band_pairs(600, 512) == 512 * 513 // 2 + 88 * 512
    run = types.SimpleNamespace(
        spans=[{"name": "decode_chunk", "args": {"experts_touched": [320, 320]}}],
        counters={"chunk": 2})
    assert flops_laguna.experts_touched_mean(run) == 160
    reduced = {"seconds": 2.0, "runs": 1, "by_scope": {"moe_experts": 0.5},
               "unscoped": {"ragged-dot-none": 0.3, "fusion": 0.1}}
    assert program_scopes.share_pct(
        reduced, ("moe_experts",), ("ragged-dot",)) == pytest.approx(40.0)
    assert program_scopes.share_pct(reduced, ("attn_window",)) is None
