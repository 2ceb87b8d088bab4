"""The FLOP and byte functions against counts made by hand."""

import pytest

from benchmark.lib import flops, harness, peaks, weights

# (config, parameters as the program holds them, block matmul parameters)
HAND = {
    # 24 layers x 12 x 1024^2; embed 50257 x 1024
    "gpt2-medium": (354_724_864, 301_989_888, 51_463_168),
    # 36 layers x 12 x 1280^2; embed 50257 x 1280
    "gpt2-large": (773_845_760, 707_788_800, 64_328_960),
}


@pytest.mark.parametrize("name", sorted(HAND))
def test_parameter_counts(name):
    cfg = harness.config(name)
    total, block, head = HAND[name]
    assert flops.block_matmul_params(cfg) == block
    assert flops.head_params(cfg) == head
    d, n = cfg["n_embd"], cfg["n_layer"]
    # embeddings + positions + matmuls + (2 LN x 2 + b_up + b_down) per
    # layer + final LN
    by_hand = head + cfg["n_positions"] * d + block + n * (4 * d + 4 * d + d) + 2 * d
    assert weights.count(cfg) == by_hand == total


@pytest.mark.parametrize("name", sorted(HAND))
def test_train_flops_per_token(name):
    cfg = harness.config(name)
    _, block, head = HAND[name]
    seq = 1024
    pairs_per_token = (seq + 1) / 2  # 1024 * 1025 / 2 pairs over 1024 tokens
    by_hand = 3 * (2 * (block + head) + 4 * cfg["n_embd"] * cfg["n_layer"] * pairs_per_token)
    assert flops.train_flops_per_token(cfg, seq) == pytest.approx(by_hand, rel=1e-12)
    # 2.27 and 4.92 GFLOP a token
    assert flops.train_flops_per_token(harness.config("gpt2-medium"), seq) == pytest.approx(2.2720e9, rel=1e-3)


def test_flash_costs():
    ops, byts = flops.flash_fwd_cost(8, 16, 1024, 64)
    assert ops == 4 * 8 * 16 * (1024 * 1025 // 2) * 64
    assert byts == 4 * 8 * 16 * 1024 * 64 * 2
    ops_b, byts_b = flops.flash_bwd_cost(8, 16, 1024, 64)
    assert ops_b == 2.5 * ops and byts_b == 2 * byts


def test_decode_min_bytes_and_serve_flops():
    cfg = harness.config("gpt2-large")
    _, block, head = HAND["gpt2-large"]
    assert flops.decode_step_min_bytes(cfg, 0) == 2 * (block + head)
    one_row = 2 * cfg["n_layer"] * cfg["n_embd"] * 2
    assert flops.decode_step_min_bytes(cfg, 1000) - flops.decode_step_min_bytes(cfg, 0) == 1000 * one_row
    assert flops.serve_flops(cfg, 10, 100, 3) == 2 * block * 10 + 4 * cfg["n_embd"] * cfg["n_layer"] * 100 + 2 * head * 3


def test_peaks_table():
    assert peaks.peaks_for("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(peaks.UnknownDevice):
        peaks.peaks_for("cpu")
