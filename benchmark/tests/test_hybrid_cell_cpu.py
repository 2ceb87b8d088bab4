"""The ``hybrid_train`` kind at a tiny size on the CPU, the look for a
chip skipped: its driver end to end, each planted fault seen as
``correct`` false, the files the harness finds for it, the operation
count against a hand count. Nothing here is a measurement."""

import copy
import importlib
import os

import pytest

from benchmark.lib import (
    flops_nemotron_h, harness, hybrid_train_cell as cell, scope_shares,
    weights_nemotron_h as weights,
)
from benchmark.tests import helpers

CELL = "train-nemotron3nano-share16"

TINY_CFG = {
    "name": "tiny-hybrid", "hidden_size": 64, "vocab_size": 256,
    "num_hidden_layers": 5, "hybrid_override_pattern": "EMEM*",
    "mamba_num_heads": 4, "mamba_head_dim": 16, "n_groups": 2,
    "ssm_state_size": 16, "conv_kernel": 4, "chunk_size": 8,
    "n_routed_experts": 4, "num_experts_per_tok": 3,
    "moe_intermediate_size": 48, "moe_shared_expert_intermediate_size": 96,
    "routed_scaling_factor": 2.5, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "norm_eps": 1e-5,
    "time_step_min": 0.001, "time_step_max": 0.1, "time_step_floor": 1e-4,
    "published": {"num_hidden_layers": 52},
    "deployment": {"router_width": 16, "experts_held": [4, 4]},
}
# Above what a sound run reads at this size on the CPU (loss 5e-5, moment
# norm 0.005, change 0.003, moment error 0.012 over the tree and 0.018
# over the recurrence's leaves) and below what the control and each fault
# read: the reference in int8 0.026-0.030 over the tree; half of the batch
# 1.0; the routed experts left out 0.06 (and 1.0 on the norms); the state
# not carried 0.5-0.8 over the recurrence's leaves (0.001 over the tree:
# why that number exists); a state left unchanged 1.0 on the change.
TINY_LIMITS = {
    "loss_gap_step1": 3e-4, "loss_gap_step2": 3e-4, "loss_gap_step3": 3e-4,
    "moment_norm_gap": 0.05, "change_norm_gap": 0.05, "moment_rel_err_all": 0.02,
    "moment_rel_err_scan": 0.1,
}


def tiny_traffic() -> dict:
    t = copy.deepcopy(harness.traffic("pretrain-4k"))
    t.update(seq_len=32, batch_per_chip={"1": 4}, warm_dispatches=1,
             attention_impl="xla", remat=True, trace_seconds=0.5,
             reference_rows_per_block=2)
    return t


@pytest.fixture
def limits(monkeypatch):
    monkeypatch.setattr(harness, "limits", lambda name: TINY_LIMITS)


def _run(monkeypatch, break_trainer=None):
    if break_trainer is not None:
        build = cell.build_trainer

        def broken(*a, **kw):
            trainer = build(*a, **kw)
            break_trainer(trainer, monkeypatch)
            return trainer

        monkeypatch.setattr(cell, "build_trainer", broken)
    ctx = helpers.context(CELL, TINY_CFG, tiny_traffic(), seconds=1.0)
    return cell.run(ctx)


def state_unchanged(trainer, monkeypatch):
    import jax
    import jax.numpy as jnp

    real = trainer._build_scanned_fn()

    def fake(state, toks, lens, idxs):
        _, out = real(jax.tree.map(jnp.copy, state), toks, lens, idxs)
        return state, out

    trainer._scanned_fn = fake


def half_batch(trainer, monkeypatch):
    counted = trainer.model.loss_and_counters
    trainer.model.loss_and_counters = (
        lambda p, t, l=None: counted(p, t[: t.shape[0] // 2], l))


def no_routed(trainer, monkeypatch):
    """The routed experts' contribution left out: only the shared expert."""
    import jax.numpy as jnp

    from distributed_tensorflow_tpu.models import hybrid

    real = hybrid.moe_ffn_held

    def nothing(*a, **kw):
        out, rows = real(*a, **kw)
        return jnp.zeros_like(out), rows

    monkeypatch.setattr(hybrid, "moe_ffn_held", nothing)


def no_carry(trainer, monkeypatch):
    """The state not carried across chunk boundaries: every chunk scanned
    as a sequence of its own."""
    from distributed_tensorflow_tpu.models import hybrid

    real = hybrid.ssd_chunked

    def chunks_apart(x, dt, a, b, c, *, chunk, **kw):
        bsz, l = x.shape[:2]
        apart = lambda t: t.reshape(bsz * (l // chunk), chunk, *t.shape[2:])  # noqa: E731
        y = real(apart(x), apart(dt), a, apart(b), apart(c), chunk=chunk, **kw)
        return y.reshape(x.shape)

    monkeypatch.setattr(hybrid, "ssd_chunked", chunks_apart)


def test_hybrid_cell_is_correct(monkeypatch, limits):
    run = _run(monkeypatch)
    assert harness.judge(run.checks), run.checks
    assert set(run.checks) == set(TINY_LIMITS)
    assert run.failed == 0 and run.attempted >= 1
    assert run.compiles_in_window == 0
    assert run.end_to_end["train_tokens_per_s"] > 0
    c = run.counters
    # 4 rows x 32 tokens x 3 choices x 2 layers, the part that landed here
    assert 0 < c["moe_rows_per_token"] <= 6
    assert c["moe_expert_rows_max"] >= c["moe_expert_rows_mean"] > 0
    assert c["flops_per_token"] == flops_nemotron_h.train_flops_per_token(
        TINY_CFG, 32, c["moe_rows_per_token"])
    reader = harness.metric_reader("moe_expert_load_max_over_mean.nemo")
    assert reader(run) == c["moe_expert_rows_max"] / c["moe_expert_rows_mean"]
    assert harness.metric_reader("ssm_scan_share_pct.nemo")(run) is None  # no trace


def no_balance(trainer, monkeypatch):
    """The balancing left out: the choice by score + buffer."""
    trainer.model.balance_rounds = None


def no_warmup(trainer, monkeypatch):
    """The full rate from the first step."""
    import optax

    trainer.optimizer = optax.adamw(3e-4)


@pytest.mark.parametrize("fault,number", [
    (state_unchanged, "change_norm_gap"),
    (half_batch, "moment_rel_err_all"),
    (no_routed, "moment_rel_err_all"),
    (no_carry, "moment_rel_err_scan"),
    (no_balance, "moment_rel_err_all"),
    (no_warmup, "change_norm_gap"),
])
def test_hybrid_fault_is_not_correct(fault, number, monkeypatch, limits):
    run = _run(monkeypatch, break_trainer=fault)
    assert not harness.judge(run.checks), run.checks
    value, limit = run.checks[number]
    assert value > limit
    if fault is state_unchanged:
        assert value == pytest.approx(1.0, abs=1e-3)


@pytest.mark.parametrize("seed", [3, 4])
def test_hybrid_control_is_not_correct(seed):
    """The reference with int8 products, put in the program's place."""
    from benchmark.lib import traffic as traffic_lib

    mix = tiny_traffic()
    rows = traffic_lib.train_rows(mix, TINY_CFG["vocab_size"], seed, 12)
    low = cell.reference_readings(
        TINY_CFG, mix, seed, rows, 3, precision="int8", keep_moment=True)
    ref = cell.reference_readings(
        TINY_CFG, mix, seed, rows, 3, against={"program": low["moment_tree"]})
    numbers = cell.compare(low, ref)
    assert numbers["moment_rel_err_all"] > TINY_LIMITS["moment_rel_err_all"], numbers


def test_the_harness_finds_the_kinds_files():
    m = harness.manifest()
    w = harness.workload(CELL)
    mix = harness.traffic(w["traffic"])
    driver = importlib.import_module(f"benchmark.lib.{mix['kind']}_cell")
    assert driver is cell and callable(driver.run)
    cfg = harness.config(w["config"])
    entry = next(c for c in m["configs"] if c["name"] == w["config"])
    assert cfg["source"] == entry["source"] and cfg["reduced"] == entry["reduced"]
    assert os.path.isfile(os.path.join(harness.ROOT, entry["file"]))
    assert set(harness.limits(CELL)) <= set(TINY_LIMITS)
    assert w["chips"] == 1 and len(w["why"]) <= 200
    per_layer = [p["name"] for p in harness.metrics_for(CELL, "per_layer")]
    assert per_layer == [
        "train_mfu_pct", "flash_fwd_roofline_pct", "train_step_device_ms",
        "train_dispatch_gap_ms",
        "hbm_peak_gb.train", "compiles_in_window.train",
        "ssm_scan_share_pct.nemo", "moe_overhead_share_pct.nemo",
        "moe_experts_share_pct.nemo", "moe_expert_load_max_over_mean.nemo"]
    for name in per_layer:
        assert callable(harness.metric_reader(name))
    assert [e["name"] for e in harness.metrics_for(CELL, "end_to_end")] == [
        "train_tokens_per_s", "setup_s"]
    # the cells that were there report what they reported
    assert not any(n.endswith(".nemo") for n in (
        p["name"] for p in harness.metrics_for("train-gpt2m", "per_layer")))


def test_the_configuration_keeps_every_published_width():
    import json

    cfg = harness.config("nemotron3-nano-30b-a3b")
    row = next(json.loads(line) for line in open(
        "/opt/skills/guides/model-configs/architectures.jsonl")
        if '"NVIDIA-Nemotron-3-Nano-30B-A3B-BF16"' in line) if os.path.exists(
        "/opt/skills/guides/model-configs/architectures.jsonl") else None
    if row is None:
        pytest.skip("no catalog on this machine")
    for key, value in row["config"].items():
        if key in cfg["reduced"]:
            assert cfg["published"][key] == value
        else:
            assert cfg[key] == value, key
    assert cfg["reduced"] == ["num_hidden_layers", "hybrid_override_pattern",
                              "n_routed_experts", "vocab_size"]
    assert row["config"]["hybrid_override_pattern"][34:43] == cfg[
        "hybrid_override_pattern"]
    assert weights.count(cfg) == 666_963_456


def test_operation_count_against_a_hand_count():
    z = weights.dims(harness.config("nemotron3-nano-30b-a3b"))
    # M: 2688 -> 10304 and 4096 -> 2688, 4 taps on 6144 channels, the scan
    m = 2 * 2688 * 10304 + 2 * 4096 * 2688 + 2 * 4 * 6144 + 2 * 64 * (
        128 * 128 + 128 * 64 + 2 * 128 * 64)
    assert flops_nemotron_h.mamba_layer_flops(z) == m == 82_706_432
    # E: router over 128, shared 2688 -> 3712 -> 2688; a routed pair
    assert flops_nemotron_h.expert_layer_fixed_flops(z) == (
        2 * 2688 * 128 + 4 * 2688 * 3712)
    assert flops_nemotron_h.routed_pair_flops(z) == 4 * 2688 * 1856
    # *: q and o 2688 <-> 4096, k and v 2688 -> 256, 32 heads x 128 over
    # (L + 1) / 2 keys a query
    a = 2 * 2688 * (2 * 4096 + 2 * 256) + 4 * 32 * 128 * 4097 / 2
    assert flops_nemotron_h.attention_layer_flops(z, 4096) == a
    even = 4 * 6 * 8 / 128  # pairs a token lands here, four E layers
    fwd = flops_nemotron_h.forward_flops_per_token(
        harness.config("nemotron3-nano-30b-a3b"), 4096, even)
    assert fwd == pytest.approx(691.6e6, rel=1e-4)
    assert flops_nemotron_h.train_flops_per_token(
        harness.config("nemotron3-nano-30b-a3b"), 4096, even) == 3 * fwd


def test_scope_shares_read_the_reduction_and_nothing_else():
    run = harness.Run(CELL, {}, {}, 1, {})
    assert scope_shares.share_pct(run, "ssm_scan") is None
    run.counters["by_scope"] = {"scope_total_pct": {
        "ssm_scan": 20.0, "moe_route": 1.5, "moe_dispatch": 2.5, "mlp": 9.0}}
    read = harness.metric_reader
    assert read("ssm_scan_share_pct.nemo")(run) == 20.0
    assert read("moe_overhead_share_pct.nemo")(run) == 4.0
    assert read("moe_experts_share_pct.nemo")(run) is None
    # the shared expert alone is not the experts' share: no routed
    # kernel found by name, no reading
    run.counters["by_scope"]["scope_total_pct"]["moe_shared"] = 7.8
    run.counters["by_scope"]["unscoped_by_op_name"] = [
        {"op_name": "jit(epoch)/while:", "opcode": "copy", "ops": 52,
         "share_pct": 1.4}]
    assert read("moe_experts_share_pct.nemo")(run) is None
    run.counters["by_scope"]["unscoped_by_op_name"] = [
        {"op_name": "ragged-dot-none:", "opcode": "custom-call", "ops": 32,
         "share_pct": 7.5},
        {"op_name": "jit(epoch)/while:", "opcode": "copy", "ops": 52,
         "share_pct": 1.4}]
    assert read("moe_experts_share_pct.nemo")(run) == 7.5 + 7.8
    assert read("ssm_scan_share_pct.nemo")(run) == 20.0
    assert read("moe_expert_load_max_over_mean.nemo")(run) is None
