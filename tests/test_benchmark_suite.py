"""Benchmark grid + device snapshot tools (SURVEY.md §7 item 7, §4 item 3)."""

import json

import jax

from distributed_tensorflow_tpu.tools import benchmark_suite, device_info


def test_row_specs_cover_reference_grid():
    rows = [r[0] for r in benchmark_suite._row_specs(8)]
    ks = [f"single-k{k}" for k in benchmark_suite.K_SWEEP]
    assert rows == [
        "single",
        "single-compiled",
        "single-compiled-pallas",
        *ks,
        "sync-2",
        "async-2",
        "zero-2",
        "sync-8",
        "async-8",
        "zero-8",
        "tp-2",
    ]
    assert "single-k10" in ks  # the round-5 row is a sweep point
    # One chip: only the single-device rows survive.
    assert [r[0] for r in benchmark_suite._row_specs(1)] == [
        "single",
        "single-compiled",
        "single-compiled-pallas",
        *ks,
    ]


def test_k_sweep_fixed_cost_recovers_model():
    """The fit inverts its own model: rows generated from s(k) = t + C/k
    give back (t, C)."""
    t, c = 0.02, 0.5
    rows = [
        {
            "row": f"single-k{k}",
            "devices": 1,
            "mode": f"chunked-{k}",
            "s_per_epoch": t + c / k,
            "examples_per_sec": 100.0,
            "reference": "ref #1",
        }
        for k in benchmark_suite.K_SWEEP
    ]
    fit = benchmark_suite.k_sweep_fixed_cost(rows)
    assert abs(fit["per_epoch_compute_s"] - t) < 1e-3
    assert abs(fit["per_dispatch_fixed_s"] - c) < 1e-2
    assert benchmark_suite.k_sweep_fixed_cost(rows[:1]) is None
    # The fit line rides the generated table.
    table = benchmark_suite.markdown_table(rows)
    assert "k-sweep fit" in table and "per-dispatch fixed cost" in table


def test_suite_runs_grid_on_virtual_mesh(small_datasets):
    results = benchmark_suite.run_suite(
        epochs=1,
        datasets=small_datasets,
        rows=["single", "sync-8", "async-2", "zero-2", "tp-2"],
        print_fn=lambda *a: None,
    )
    # Results follow grid order, not filter order.
    assert [r["row"] for r in results] == [
        "single",
        "async-2",
        "zero-2",
        "sync-8",
        "tp-2",
    ]
    for r in results:
        assert r["s_per_epoch"] > 0
        assert r["examples_per_sec"] > 0
        assert 0.0 <= r["final_accuracy"] <= 1.0
    by_name = {r["row"]: r for r in results}
    assert by_name["sync-8"]["devices"] == 8
    assert by_name["sync-8"]["mode"] == "scan"
    assert by_name["async-2"]["mode"] == "scan"  # async gained a scanned path
    assert by_name["zero-2"]["mode"] == "eager"
    json.dumps(results)  # machine-readable


def test_markdown_table_shape(small_datasets):
    results = benchmark_suite.run_suite(
        epochs=1, datasets=small_datasets, rows=["single"], print_fn=lambda *a: None
    )
    table = benchmark_suite.markdown_table(results)
    lines = table.split("\n")
    assert lines[0].startswith("| Row |")
    assert lines[2].startswith("| single |") and "tfsingle.py" in lines[2]
    # No accuracy column: short-run accuracies next to converged reference
    # numbers implied a false parity failure (round-1 finding); the table
    # instead points at parity_converged.md.
    assert "accuracy" not in lines[0]
    assert "parity_converged.md" in table


def test_device_snapshot_lists_all_devices():
    lines = []
    rows = device_info.snapshot(print_fn=lines.append)
    assert len(rows) == len(jax.local_devices()) == 8
    assert all(r["platform"] == "cpu" for r in rows)
    assert len(lines) == 9  # header + 8 devices
    # Live-array accounting sees something (conftest datasets, jit consts...).
    x = jax.numpy.ones((16, 16))
    rows2 = device_info.snapshot(print_fn=None)
    assert sum(r["live_arrays"] for r in rows2) >= 1
    del x


def test_d2h_barrier_handles_mixed_and_empty_trees():
    import numpy as np

    from distributed_tensorflow_tpu.utils.sync import d2h_barrier

    # Mixed tree: host numpy first (must not short-circuit the fetch),
    # device arrays from two independent dispatches after it.
    a = jax.jit(lambda x: x * 2)(jax.numpy.ones((4, 4)))
    b = jax.jit(lambda x: x + 1)(jax.numpy.ones((2, 2)))
    d2h_barrier({"host": np.zeros(3), "a": a, "b": b})
    assert float(a[0, 0]) == 2.0 and float(b[0, 0]) == 2.0
    # Degenerate trees are no-ops, not errors.
    d2h_barrier({})
    d2h_barrier(None)
    d2h_barrier([np.ones(2)])


def test_single_compiled_row_runs(small_datasets):
    results = benchmark_suite.run_suite(
        epochs=1,
        datasets=small_datasets,
        rows=["single-compiled"],
        print_fn=lambda *a: None,
        compiled_min_epochs=1,
    )
    (row,) = results
    assert row["mode"] == "whole-run"
    assert row["epochs_timed"] == 1
    assert row["examples_per_sec"] > 0


def test_attention_bench_smoke(capsys):
    # Tiny shapes on the CPU interpreter: the tool must produce a table row
    # per length and valid JSON, with the window column present.
    from distributed_tensorflow_tpu.tools import attention_bench

    attention_bench.main(
        [
            "--lengths", "32", "64",
            "--batch", "1", "--heads", "2", "--head-dim", "8",
            "--window", "16", "--iters", "1",
        ]
    )
    out = capsys.readouterr().out
    assert "| 32 |" in out and "| 64 |" in out
    import json as _json

    payload = _json.loads(out.strip().splitlines()[-1])
    assert len(payload["rows"]) == 2
    assert all("flash_ms" in r for r in payload["rows"])


def test_lm_bench_smoke(capsys, monkeypatch):
    # A micro config injected into the grid, 2 steps, on CPU: the tool must
    # produce a table row with throughput + MFU fields and valid JSON.
    # (This test once ran the real gpt-s config on CPU — 21 MINUTES, half
    # the whole suite; the smoke's job is the tool's plumbing, not the
    # model. The real configs are measured on the chip by --write-docs.)
    from distributed_tensorflow_tpu.tools import lm_bench

    monkeypatch.setitem(
        lm_bench.CONFIGS,
        "micro",
        dict(
            batch=4,
            model=dict(model_dim=32, num_layers=1, num_heads=4, max_len=32),
        ),
    )
    monkeypatch.setattr(lm_bench, "_VOCAB", 64)
    monkeypatch.setattr(
        lm_bench,
        "DECODE_CONFIGS",
        {
            "micro-decode": dict(
                batch=2, prompt=8, max_new=8,
                model=dict(
                    model_dim=32, num_layers=1, num_heads=4, max_len=32
                ),
            )
        },
    )
    lm_bench.main(["--configs", "micro", "--steps", "2", "--decode"])
    out = capsys.readouterr().out
    assert "micro" in out
    import json as _json

    payload = _json.loads(out.strip().splitlines()[-1])
    (row,) = payload["rows"]
    assert row["tokens_per_sec"] > 0 and row["flops_per_step"] > 0
    assert row["timing"].startswith("two-point")
    # 6·N·tokens over the NON-embedding parameters (round 6's convention).
    assert row["param_count_nonembed"] < row["param_count"]
    assert row["model_flops_per_step"] == (
        6 * row["param_count_nonembed"] * 4 * 32
    )
    (drow,) = payload["decode_rows"]
    assert drow["gen_tokens_per_sec"] > 0


def test_lm_phase_bench_smoke(capsys, monkeypatch):
    # Same plumbing-only contract for the phase decomposition tool: a
    # micro config (remat on, to exercise the blocks-fwd checkpoint path)
    # must produce nested phase timings that are positive and consistent
    # (step >= fwd+bwd region; per-layer micros present).
    from distributed_tensorflow_tpu.tools import lm_phase_bench

    monkeypatch.setattr(
        lm_phase_bench,
        "CONFIGS",
        {
            "micro": (
                dict(
                    model_dim=32, num_layers=2, num_heads=4, max_len=32,
                    remat=True,
                ),
                4,
            )
        },
    )
    monkeypatch.setattr(lm_phase_bench, "_VOCAB", 64)
    lm_phase_bench.main(["--configs", "micro", "--steps", "2", "--reps", "1"])
    out = capsys.readouterr().out
    import json as _json

    row = _json.loads(out.strip().splitlines()[0])
    # Plumbing contract only: phases present and finite. Positivity (or
    # even sign, for the DIFFERENCE-based phases) is NOT asserted — a CPU
    # micro's two-point deltas sit inside dispatch jitter, so fwd can
    # time below blocks-fwd and a derived phase can come out negative
    # (flaked twice in review). Real magnitudes are the chip run's job.
    import math

    p = row["phase_ms"]
    assert set(p) == {
        "blocks-fwd", "logits+loss", "backward", "bwd-dgrad", "optimizer",
        "step", "backward-selective",  # round 13's selective-remat column
    }
    # The split is derived, keys always present (values are chip-grade
    # only on-chip; remat micro attributes recompute at blocks-fwd).
    assert set(row["backward_split"]) == {"recompute", "dgrad", "wgrad"}
    assert all(math.isfinite(v) for v in p.values())
    assert math.isfinite(row["per_layer_ms"]["attention"])
    assert math.isfinite(row["per_layer_ms"]["ffn"])
    assert row["tokens_per_sec"] > 0
    assert row["model_flops_per_step"] > 0
    assert "| micro (cpu) |" in out  # off-chip rows carry their device
