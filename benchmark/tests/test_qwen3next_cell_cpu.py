"""The ``qwen3next_serve`` kind at a tiny size on the CPU, the look for a
chip skipped: the cell end to end through ``TextServer``'s paged engine
(each slot's delta-rule state beside the full layers' pool), the int8
control and each planted fault seen as ``correct`` false, the
configuration's file against the published row, the parameter count,
the new readers on made-up runs. Nothing here is a measurement."""

import copy
import types

import pytest

from benchmark.lib import (
    flops_qwen3next, harness, program_scopes, qwen3next_serve_cell as cell,
    reference_qwen3next, weights_qwen3next as weights,
)
from benchmark.tests import helpers

CELL = "serve-qwen3next-reason"

TINY_CFG = {
    "name": "tiny-qwen3next", "hidden_size": 64, "vocab_size": 256,
    "num_hidden_layers": 4, "full_attention_interval": 4,
    "decoder_sparse_step": 1, "mlp_only_layers": [],
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 32,
    "partial_rotary_factor": 0.25, "rope_theta": 10000000,
    "linear_num_key_heads": 2, "linear_key_head_dim": 16,
    "linear_num_value_heads": 4, "linear_value_head_dim": 16,
    "linear_conv_kernel_dim": 4, "num_experts": 8, "num_experts_per_tok": 4,
    "moe_intermediate_size": 16, "shared_expert_intermediate_size": 16,
    "rms_norm_eps": 1e-6,
    "published": {"num_hidden_layers": 1, "num_experts": 32},
    "deployment": {"pattern": "NENENEGE"},
}
# Set as the real one is, from readings at this size on the CPU (seed 5,
# printed by `pytest -s`, mean / first): above a sound run's (0.0038 /
# 0.0017) and below what the control (0.081 / 0.065) and each fault (0.31
# to 4.3) read.
TINY_LIMITS = {"mean_logit_gap": 0.02, "first_logit_gap": 0.02}


def tiny_traffic() -> dict:
    t = copy.deepcopy(harness.traffic("reason-long-answers"))
    t["server"].update(slots=4, chunk=4, block_size=4, kv_blocks=160,
                       buckets=[32, 64])
    t.update(max_len=160, attention_impl="xla", trace_seconds=0.5,
             check_requests=3)
    t["arrivals"].update(rate_per_s=3.0, warmup_s=1.0)
    t["prompt"].update(min=8, max=64, median=30)
    t["answer"].update(min=16, max=40, median=24)
    return t


@pytest.fixture
def limits(monkeypatch):
    monkeypatch.setattr(harness, "limits", lambda name: TINY_LIMITS)
    # At 64 wide a start of N(0, 0.02) leaves every layer's output far
    # under the embedding's, and decays drawn from U(0, 16) forget a state
    # within a token or two: no fault would reach a logit. A larger start
    # (and a published depth of 1) and slow decays let them.
    monkeypatch.setattr(weights, "STD", 0.3)
    monkeypatch.setattr(weights, "A_RANGE", (0.01, 0.1))


def _run(monkeypatch, gaps_kw=None):
    if gaps_kw:
        real = cell.sample_gaps
        monkeypatch.setattr(
            cell, "sample_gaps", lambda *a, **kw: real(*a, **{**kw, **gaps_kw}))
    ctx = helpers.context(CELL, TINY_CFG, tiny_traffic(), seconds=3.0)
    return cell.run(ctx)


def test_qwen3next_cell_is_correct(monkeypatch, limits):
    run = _run(monkeypatch)
    print({k: v for k, (v, _) in run.checks.items()})
    assert harness.judge(run.checks), run.checks
    assert run.failed == 0 and run.attempted >= 3
    assert run.compiles_in_window == 0
    assert run.end_to_end["tpot_p50_ms"] > 0
    c = run.counters
    assert c["checked_tokens"] > 0 and c["decode_key_rows_full"] > 0
    assert c["slot_state_bytes"] == 4 * 4 * 3 * (
        4 * 16 * 16 + 3 * (2 * 32 + 64))  # slots x f32 x N layers
    touched = harness.metric_reader("experts_touched_mean.laguna")(run)
    assert 1 <= touched <= 8
    assert 0 < flops_qwen3next.active_mean(run) <= 4
    for name in ("decode_roofline_pct.qnext", "gdn_state_share_pct.qnext",
                 "gdn_state_roofline_pct.qnext", "gdn_prefill_share_pct.qnext"):
        assert harness.metric_reader(name)(run) is None  # no trace


@pytest.mark.parametrize("gaps_kw", [
    {"precision": "int8"}, *({"fault": f} for f in reference_qwen3next.FAULTS),
], ids=lambda kw: next(iter(kw.values())))
def test_the_control_and_each_fault_is_not_correct(monkeypatch, limits, gaps_kw):
    run = _run(monkeypatch, gaps_kw)
    print({k: v for k, (v, _) in run.checks.items()})
    assert not harness.judge(run.checks), run.checks


def test_the_configuration_is_the_published_row_cut_to_two_periods():
    cfg = harness.config("qwen3-next-80b-a3b")
    assert cfg["reduced"] == ["num_hidden_layers", "num_experts"]
    assert cfg["published"] == {"num_hidden_layers": 48, "num_experts": 512}
    z = weights.dims(cfg)
    assert z["pattern"] == "NENENEGE" * 2
    assert (z["held"], z["experts"], z["top_k"]) == (128, 512, 10)
    assert (z["heads"], z["kv_heads"], z["head_dim"]) == (16, 2, 256)
    assert z["rope"] == {"theta": 1e7, "dims": 64}
    assert weights.count(cfg) == weights.EXPECTED == 4_133_998_720


def test_the_schedule_is_one_fixed_order():
    t = harness.traffic("reason-long-answers")
    a = cell.reason_schedule(t, 151936, 1, 45.0)
    b = cell.reason_schedule(t, 151936, 2 ** 31 + 7, 45.0)
    for part in (lambda r: r.tokens.size, lambda r: r.max_new,
                 lambda r: r.due_s):
        assert [part(r) for r in a] == [part(r) for r in b]
    assert all(r.tokens.size + r.max_new <= t["max_len"] for r in a)


def test_state_and_decode_readers_on_made_up_runs():
    cfg = harness.config("qwen3-next-80b-a3b")
    ops, byts = flops_qwen3next.state_update_cost(cfg, 1)
    # a slot: 6 layers x (32 x 128 x 128 state + 3 x 8192 conv rows) x 4
    # bytes, read and written
    assert byts == 6 * 2 * 4 * (32 * 128 * 128 + 3 * 8192)
    base = flops_qwen3next.decode_step_min_bytes(cfg, 0, 0, 0)
    one = flops_qwen3next.decode_step_min_bytes(cfg, 1, 0, 0)
    assert one - base == 2 * 8 * 3 * 2048 * 512
    run = types.SimpleNamespace(
        spans=[{"name": "decode_chunk",
                "args": {"slot_steps": 60, "experts_touched": [1] * 8}}],
        counters={"chunk": 2, "by_program": {"decode": {
            "seconds": 2.0, "runs": 1,
            "by_scope": {"gdn_state": 0.5, "gdn_conv": 0.1},
            "unscoped": {}}}},
        cfg=cfg, peaks={"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12})
    assert flops_qwen3next.active_mean(run) == 30
    got = harness.metric_reader("gdn_state_roofline_pct.qnext")(run)
    assert got == pytest.approx(100 * 30 * byts / 819e9 / 0.3)
    assert program_scopes.share_pct(
        run.counters["by_program"]["decode"],
        ("gdn_state", "gdn_conv")) == pytest.approx(30.0)
