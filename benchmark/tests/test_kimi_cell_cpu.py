"""The ``kimi_linear_train`` kind at a tiny size on the CPU, the look for
a chip skipped: its driver end to end, the control and each planted fault
seen as ``correct`` false, the configuration's file against the published
row, the operation count against a hand count, the new readers on a
made-up reduction. Nothing here is a measurement."""

import copy
import importlib
import os
import types

import pytest

from benchmark.lib import (
    flops_kimi_linear, harness, kernels_two_sizes, kimi_linear_train_cell as cell,
    peaks, reference_kimi_linear, weights_kimi_linear as weights,
)
from benchmark.tests import helpers

CELL = "train-kimilinear-share32"

TINY_CFG = {
    "name": "tiny-kimi", "hidden_size": 64, "vocab_size": 256,
    "num_hidden_layers": 3, "first_k_dense_replace": 1, "intermediate_size": 96,
    "linear_attn_config": {
        "kda_layers": [1, 3], "full_attn_layers": [2], "head_dim": 16,
        "num_heads": 4, "short_conv_kernel_size": 4},
    "num_attention_heads": 4, "kv_lora_rank": 24, "qk_nope_head_dim": 16,
    "qk_rope_head_dim": 8, "v_head_dim": 16, "num_experts": 4,
    "num_experts_per_token": 3, "moe_intermediate_size": 48,
    "num_shared_experts": 1, "routed_scaling_factor": 2.446,
    "rms_norm_eps": 1e-5, "published": {"num_hidden_layers": 27},
    "deployment": {"router_width": 16, "experts_held": [4, 4],
                   "pattern": "KDLEKE"},
}
# Set as the real ones are, from readings at this size on the CPU (seed 5,
# printed by `pytest -s`): above a sound run's and below what the control
# and each fault read on the number that sees it. The numbers the cell's
# own limits file compares, and no other.
TINY_LIMITS = {
    "loss_gap_step1": 3e-4, "moment_norm_gap": 0.05, "change_norm_gap": 0.05,
    "moment_rel_err_all": 0.02, "moment_rel_err_scan": 0.1,
}


def tiny_traffic() -> dict:
    t = copy.deepcopy(harness.traffic("pretrain-8k"))
    # 128 tokens: two of the reference's 64-token blocks, so that a state
    # not carried across their boundary differs
    t.update(seq_len=128, batch_per_chip={"1": 2}, warm_dispatches=1,
             attention_impl="xla", remat=True, trace_seconds=0.5,
             reference_rows_per_block=2)
    return t


@pytest.fixture
def limits(monkeypatch):
    monkeypatch.setattr(harness, "limits", lambda name: TINY_LIMITS)


@pytest.fixture(autouse=True)
def tiny_rank(monkeypatch):
    monkeypatch.setattr(weights, "GATE_RANK", 8)


def _run(monkeypatch, reference_kw=None):
    """The driver end to end; ``reference_kw`` puts a faulty (or int8)
    reference in the reference's place, as the calibration does on the
    chip."""
    if reference_kw:
        real = cell.reference_readings
        monkeypatch.setattr(
            cell, "reference_readings",
            lambda *a, **kw: real(*a, **{**kw, **reference_kw}))
    ctx = helpers.context(CELL, TINY_CFG, tiny_traffic(), seconds=1.0)
    return cell.run(ctx)


def test_kimi_cell_is_correct(monkeypatch, limits):
    run = _run(monkeypatch)
    print({k: v for k, (v, _) in run.checks.items()})
    assert harness.judge(run.checks), run.checks
    assert set(run.checks) == set(TINY_LIMITS)
    assert run.failed == 0 and run.attempted >= 1
    assert run.compiles_in_window == 0
    assert run.end_to_end["train_tokens_per_s"] > 0
    c = run.counters
    # 3 choices x 2 expert layers, the part that landed on 4 of 16
    assert 0 < c["moe_rows_per_token"] <= 6
    assert c["moe_expert_rows_max"] >= c["moe_expert_rows_mean"] > 0
    assert c["flops_per_token"] == flops_kimi_linear.train_flops_per_token(
        TINY_CFG, 128, c["moe_rows_per_token"])
    reader = harness.metric_reader("moe_expert_load_max_over_mean.kimi")
    assert reader(run) == c["moe_expert_rows_max"] / c["moe_expert_rows_mean"]
    for name in ("kda_scan_share_pct.kimi", "flash_fwd_roofline_pct.kimi",
                 "flash_bwd_roofline_pct.kimi"):
        assert harness.metric_reader(name)(run) is None  # no trace


@pytest.mark.parametrize("reference_kw", [
    {"precision": "int8"}, {"fault": "half_batch"}, {"fault": "no_routed"},
    {"fault": "no_carry"}, {"fault": "no_delta"}, {"fault": "no_shared_key"},
], ids=lambda kw: next(iter(kw.values())))
def test_the_control_and_each_fault_is_not_correct(monkeypatch, limits, reference_kw):
    run = _run(monkeypatch, reference_kw)
    print({k: v for k, (v, _) in run.checks.items()})
    assert not harness.judge(run.checks), run.checks


def test_a_state_left_unchanged_is_not_correct(monkeypatch, limits):
    import jax
    import jax.numpy as jnp

    build = cell.build_trainer

    def broken(*a, **kw):
        trainer = build(*a, **kw)
        real = trainer._build_scanned_fn()

        def fake(state, toks, lens, idxs):
            _, out = real(jax.tree.map(jnp.copy, state), toks, lens, idxs)
            return state, out

        trainer._scanned_fn = fake
        return trainer

    monkeypatch.setattr(cell, "build_trainer", broken)
    run = _run(monkeypatch)
    assert run.checks["change_norm_gap"][0] == pytest.approx(1.0, abs=1e-3)
    assert not harness.judge(run.checks)


# -- the configuration's file ----------------------------------------------------

PUBLISHED = {  # the catalog row's `config`, typed here
    "first_k_dense_replace": 1, "head_dim": 72, "hidden_act": "silu",
    "hidden_size": 2304, "intermediate_size": 9216, "kv_lora_rank": 512,
    "mla_use_nope": True, "model_max_length": 1048576,
    "model_type": "kimi_linear", "moe_intermediate_size": 1024,
    "moe_layer_freq": 1, "moe_renormalize": True,
    "moe_router_activation_func": "sigmoid", "num_attention_heads": 32,
    "num_expert_group": 1, "num_experts": 256, "num_experts_per_token": 8,
    "num_hidden_layers": 27, "num_key_value_heads": 32,
    "num_nextn_predict_layers": 0, "num_shared_experts": 1,
    "q_lora_rank": None, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
    "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000,
    "routed_scaling_factor": 2.446, "tie_word_embeddings": False,
    "topk_group": 1, "use_grouped_topk": True, "v_head_dim": 128,
    "vocab_size": 163840,
}
PUBLISHED_LINEAR = {"head_dim": 128, "num_heads": 32, "short_conv_kernel_size": 4}
CUT = {"num_hidden_layers": 5, "num_experts": 8, "vocab_size": 20480}


def test_the_configuration_keeps_every_published_width():
    cfg = harness.config("kimi-linear-48b-a3b")
    entry = next(c for c in harness.manifest()["configs"]
                 if c["name"] == "kimi-linear-48b-a3b")
    assert set(cfg["reduced"]) == set(entry["reduced"]) == {
        "num_hidden_layers", "linear_attn_config", "num_experts", "vocab_size"}
    for key, value in PUBLISHED.items():
        assert cfg[key] == CUT.get(key, value), key
    lin = cfg["linear_attn_config"]
    assert {k: lin[k] for k in PUBLISHED_LINEAR} == PUBLISHED_LINEAR
    assert (lin["kda_layers"], lin["full_attn_layers"]) == ([1, 2, 3, 5], [4])
    pub = cfg["published"]
    assert {k: pub[k] for k in CUT} == {k: PUBLISHED[k] for k in CUT}
    assert pub["linear_attn_config"]["full_attn_layers"] == [4, 8, 12, 16, 20, 24, 27]
    assert len(pub["linear_attn_config"]["kda_layers"]) == 20
    dep = cfg["deployment"]
    assert (dep["chips_per_layer"], dep["router_width"], dep["experts_held"],
            dep["experts_per_token"]) == (32, 256, [0, 8], 8)
    assert weights.pattern_of(cfg) == dep["pattern"] == "KDKEKELEKE"
    assert {"kda_gate_rank", "kda_init", "conv_init", "init", "residual",
            "e_score_correction_bias"} <= set(cfg["assumed"])


def test_the_weights_count_what_the_issue_reckons(monkeypatch):
    monkeypatch.setattr(weights, "GATE_RANK", 128)
    assert weights.count(harness.config("kimi-linear-48b-a3b")) == 602_434_432


def test_the_files_the_harness_finds_for_the_cell():
    w = harness.workload(CELL)
    assert (w["config"], w["traffic"], w["chips"]) == (
        "kimi-linear-48b-a3b", "pretrain-8k", 1)
    t = harness.traffic(w["traffic"])
    assert (t["kind"], t["seq_len"], t["batch_per_chip"]) == (
        "kimi_linear_train", 8192, {"1": 2})
    assert importlib.import_module(f"benchmark.lib.{t['kind']}_cell") is cell
    limits = harness.limits(CELL)
    assert set(limits) == set(TINY_LIMITS) and all(
        v > 0 for v in limits.values())
    names = {m["name"] for m in harness.metrics_for(CELL, "per_layer")}
    assert {n for n in names if n.endswith(".kimi")} == {
        "kda_scan_share_pct.kimi", "kda_elementwise_share_pct.kimi",
        "mla_core_share_pct.kimi", "moe_experts_share_pct.kimi",
        "moe_overhead_share_pct.kimi", "moe_expert_load_max_over_mean.kimi",
        "flash_fwd_roofline_pct.kimi", "flash_bwd_roofline_pct.kimi"}
    assert {"train_mfu_pct", "train_step_device_ms", "hbm_peak_gb.train",
            "compiles_in_window.train"} <= names
    # the accepted flash readers would count q.k at 128 wide; one dispatch
    # fills the 6 traced seconds, so no gap between two is there to read
    assert not {"flash_fwd_roofline_pct", "flash_bwd_roofline_pct",
                "train_dispatch_gap_ms"} & names
    for name in names:
        assert os.path.exists(os.path.join(
            harness.BENCH, "metrics", f"{name}.py")), name
    assert {m["name"] for m in harness.metrics_for(CELL, "end_to_end")} == {
        "train_tokens_per_s", "setup_s"}


# -- the operation count -----------------------------------------------------------


def test_the_operation_count_against_a_hand_count(monkeypatch):
    monkeypatch.setattr(weights, "GATE_RANK", 128)
    cfg = harness.config("kimi-linear-48b-a3b")
    d, L = 2304, 8192
    kda = (2 * d * (3 * 4096 + 2 * 128 + 32)  # q, k, v, two gates' halves, beta
           + 2 * 2 * 128 * 4096  # the gates' second halves
           + 2 * 4096 * d  # o
           + 2 * 4 * 3 * 4096  # three convolutions of 4 taps
           + 3 * 2 * 32 * 128 * 128)  # S'^T k, the rank-one update, S^T q
    mla = (2 * d * 32 * 192 + 2 * d * 576 + 2 * 512 * 32 * 256 + 2 * 4096 * d
           + 2 * 32 * (192 + 128) * (L * (L + 1) // 2) / L)
    dense = 6 * d * 9216
    fixed = 2 * d * 256 + 6 * d * 1024
    even = 4 * 8 * 8 / 256  # pairs a token that land on 8 of 256, 4 layers
    forward = (4 * kda + mla + dense + 4 * fixed + even * 6 * d * 1024
               + 2 * d * 20480)
    assert flops_kimi_linear.train_flops_per_token(cfg, L, even) == pytest.approx(
        3 * forward, rel=1e-12)
    # all but attention's pairs: 684 M a token (ISSUE 35 reckoned 667 M for
    # the projections' products alone); the pairs add L * 32 * 320
    products = forward - 2 * 32 * 320 * (L * (L + 1) // 2) / L
    assert 680e6 < products < 690e6
    ops, byts = flops_kimi_linear.flash_fwd_cost(64, L, 192, 128)
    assert ops == 2 * 64 * (L * (L + 1) // 2) * 320
    assert byts == 64 * L * (192 + 192 + 128 + 128) * 2
    ops, byts = flops_kimi_linear.flash_bwd_cost(64, L, 192, 128)
    assert ops == 2 * 64 * (L * (L + 1) // 2) * (3 * 192 + 2 * 128)
    assert byts == 64 * L * (4 * 192 + 4 * 128) * 2


# -- the new readers on a made-up reduction ------------------------------------------


def _run_with(by_scope=None, trace=None):
    run = harness.Run(CELL, harness.config("kimi-linear-48b-a3b"), {}, 1,
                      peaks.PEAKS["TPU v5 lite"])
    if by_scope is not None:
        run.counters["by_scope"] = by_scope
    run.trace = trace
    return run


def test_the_share_readers_on_a_made_up_reduction():
    by_scope = {
        "scope_total_pct": {"kda_scan": 30.0, "kda_conv": 4.0, "kda_gate": 6.0,
                            "attn_core": 9.0, "moe_experts": 2.0,
                            "moe_shared": 5.0, "moe_route": 1.5,
                            "moe_dispatch": 2.5},
        "unscoped_by_op_name": [{"op_name": "ragged-dot-none", "share_pct": 7.0},
                                {"op_name": "copy", "share_pct": 3.0}]}
    run = _run_with(by_scope)
    read = lambda name: harness.metric_reader(name)(run)  # noqa: E731
    assert read("kda_scan_share_pct.kimi") == 30.0
    assert read("kda_elementwise_share_pct.kimi") == 10.0
    assert read("mla_core_share_pct.kimi") == 9.0
    assert read("moe_experts_share_pct.kimi") == 14.0
    assert read("moe_overhead_share_pct.kimi") == 4.0
    # a program without the scopes (the parent's): nothing to read, no raise
    bare = _run_with({"scope_total_pct": {"attn_qkv": 50.0},
                      "unscoped_by_op_name": []})
    for name in ("kda_scan_share_pct.kimi", "kda_elementwise_share_pct.kimi",
                 "mla_core_share_pct.kimi", "moe_experts_share_pct.kimi",
                 "moe_overhead_share_pct.kimi",
                 "moe_expert_load_max_over_mean.kimi"):
        assert harness.metric_reader(name)(bare) is None
        assert harness.metric_reader(name)(_run_with()) is None


def _trace(ops: dict):
    """A made-up summary: {short name: (HLO result type, [seconds])}."""
    return types.SimpleNamespace(
        hlo_of={k: f"%{k} = {v[0]} custom-call(...)" for k, v in ops.items()},
        opcode_of=dict.fromkeys(ops, "custom-call"),
        op_calls={k: v[1] for k, v in ops.items()})


def test_the_roofline_readers_on_a_made_up_trace():
    peak = peaks.PEAKS["TPU v5 lite"]
    fwd = "(bf16[64,8192,128]{2,1,0}, f32[64,8192,1]{2,1,0})"
    split = {
        "flash_fwd.1": (fwd, [0.004, 0.006]),
        "flash_bwd_dq.2": ("bf16[64,8192,192]{2,1,0}", [0.006]),
        "flash_bwd_dkv.3": ("(bf16[64,8192,192]{2,1,0}, bf16[64,8192,128]{2,1,0})",
                            [0.009]),
        "fusion.9": ("f32[8]{0}", [1.0]),
    }
    run = _run_with(trace=_trace(split))
    ops, _ = flops_kimi_linear.flash_fwd_cost(64, 8192, 192, 128)
    assert kernels_two_sizes.flash_roofline_pct(run, False) == pytest.approx(
        100 * ops / peak["bf16_flops_per_s"] / 0.005)
    ops, _ = flops_kimi_linear.flash_bwd_cost(64, 8192, 192, 128)
    least = ops / peak["bf16_flops_per_s"]
    assert kernels_two_sizes.flash_roofline_pct(run, True) == pytest.approx(
        100 * least / 0.015)
    fused = {"flash_bwd_fused.4": (
        "(f32[8,64,8192,192]{3,2,1,0}, bf16[64,8192,192]{2,1,0}, "
        "bf16[64,8192,128]{2,1,0})", [0.012])}
    assert kernels_two_sizes.flash_roofline_pct(
        _run_with(trace=_trace(fused)), True) == pytest.approx(100 * least / 0.012)
    # no such kernel in the trace, or no trace: nothing to read
    none = _run_with(trace=_trace({"fusion.9": ("f32[8]{0}", [1.0])}))
    for backward in (False, True):
        assert kernels_two_sizes.flash_roofline_pct(none, backward) is None
        assert kernels_two_sizes.flash_roofline_pct(_run_with(), backward) is None


def test_the_reference_knows_its_faults():
    assert set(reference_kimi_linear.FAULTS) == {
        "no_routed", "no_carry", "no_delta", "no_shared_key"}
