"""Number-of-record freshness (round 8, VERDICT r5 weak #6): a perf
doc's bench citation is GENERATED from the newest ``BENCH_r*.json`` beside
it, and ``perf_record`` pins docs against the newest artifact — landing a
new driver artifact without running ``perf_record --write-docs`` is caught
instead of shipping a stale number-of-record. The driver's own artifacts
left the repo with PR 22 (their logs named an installation that is gone;
the ledger replaces them), so these cases build their artifacts and docs
under ``tmp_path``. No jax needed (pure file checks)."""

import json
import os

import pytest

from distributed_tensorflow_tpu.tools import perf_record


def _bench(root, n, value, impl="pallas-epoch"):
    payload = {"rc": 0, "parsed": {
        "value": value, "vs_baseline": value / 42000.0, "impl": impl,
    }}
    (root / f"BENCH_r{n:02d}.json").write_text(json.dumps(payload))


@pytest.fixture
def record_root(tmp_path):
    """A repo-shaped root: three driver artifacts and the three docs that
    carry a bench-record span, citing a STALE artifact."""
    for n, value in ((1, 1.0e7), (2, 9.5e6), (10, 5.9e7)):
        _bench(tmp_path, n, value)
    stale = perf_record.citation(
        "BENCH_r01.json", {"value": 1.0e7, "vs_baseline": 238.1, "impl": "x"}
    )
    for rel in perf_record.DOC_FILES:
        path = tmp_path / rel
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(f"# a doc\n\nbefore {stale} after\n")
    return tmp_path


def test_latest_bench_resolves_highest_round(record_root):
    name, parsed = perf_record.latest_bench(str(record_root))
    # Highest-NUMBERED artifact wins (r10 over r02 — numeric, not lexical).
    assert name == "BENCH_r10.json"
    assert parsed["value"] == 5.9e7 and "impl" in parsed
    assert perf_record.latest_bench(str(record_root / "docs")) is None


def test_latest_bench_skips_unparseable(tmp_path):
    (tmp_path / "BENCH_r01.json").write_text(
        json.dumps({"parsed": {"value": 1.0, "vs_baseline": 1.0, "impl": "x"}})
    )
    (tmp_path / "BENCH_r02.json").write_text("not json")
    (tmp_path / "BENCH_r03.json").write_text(json.dumps({"rc": 1}))
    name, parsed = perf_record.latest_bench(str(tmp_path))
    assert name == "BENCH_r01.json"  # r02/r03 carry no parseable metric


def test_committed_docs_cite_newest_artifact(record_root):
    root = str(record_root)
    assert perf_record.check_docs(root) == list(perf_record.DOC_FILES)
    assert perf_record.write_docs(root, print_fn=lambda *a: None) is True
    assert perf_record.check_docs(root) == []
    text = (record_root / "README.md").read_text()
    assert "BENCH_r10.json" in text and "BENCH_r01.json" not in text
    assert text.startswith("# a doc\n\nbefore ") and text.endswith(" after\n")


def test_write_docs_is_idempotent(record_root):
    root = str(record_root)
    perf_record.write_docs(root, print_fn=lambda *a: None)
    assert perf_record.write_docs(root, print_fn=lambda *a: None) is False


def test_lm_phases_docs_match_committed_artifact(tmp_path):
    """docs/benchmarks/lm_phases.md is GENERATED from lm_phases.json
    (lm_phase_bench render + _write_md): re-rendering the committed JSON
    must reproduce the committed md byte for byte, so new JSON columns
    (round 13: the plain-vs-selective backward pair) cannot land without
    regenerating the doc — the serving.md staleness discipline."""
    from distributed_tensorflow_tpu.tools import lm_phase_bench

    root = os.path.abspath(
        os.path.join(
            os.path.dirname(perf_record.__file__), "..", "..", "docs",
            "benchmarks",
        )
    )
    with open(os.path.join(root, "lm_phases.json")) as f:
        payload = json.load(f)
    with open(os.path.join(root, "lm_phases.md")) as f:
        committed = f.read()
    table = lm_phase_bench.render(payload["rows"])
    lm_phase_bench._write_md(
        str(tmp_path), table, lm_phase_bench.recorded_ceiling(payload["rows"])
    )
    with open(tmp_path / "lm_phases.md") as f:
        regenerated = f.read()
    assert regenerated == committed, (
        "docs/benchmarks/lm_phases.md is stale vs lm_phases.json; run "
        "python -m distributed_tensorflow_tpu.tools.lm_phase_bench "
        "--recompute-docs (or --write-docs after a measurement)"
    )
    # The committed artifact carries the round-13 comparison at least
    # once (the CPU point until the chip rerun fills the xl rows).
    assert any(
        (r.get("phase_ms") or {}).get("backward-selective") is not None
        for r in payload["rows"]
    )


def test_diloco_docs_match_committed_artifact():
    """docs/benchmarks/diloco.md is GENERATED from diloco.json
    (diloco_bench.render_from_payload): re-rendering the committed JSON
    must reproduce the committed md byte for byte — the lm_phases.md
    staleness discipline for the round-14 DiLoCo record."""
    from distributed_tensorflow_tpu.tools import diloco_bench

    root = diloco_bench._docs_root()
    with open(os.path.join(root, "diloco.json")) as f:
        payload = json.load(f)
    with open(os.path.join(root, "diloco.md")) as f:
        committed = f.read()
    assert diloco_bench.render_from_payload(payload) == committed, (
        "docs/benchmarks/diloco.md is stale vs diloco.json; run "
        "python -m distributed_tensorflow_tpu.tools.diloco_bench "
        "--write-docs"
    )


def test_serving_load_gen_record():
    """Round 21: the overload-robustness row is part of the committed
    serving record — serving.json carries the ``load_gen`` section with
    the priority_mix scenario (per-class stats) and the two acceptance
    booleans the bench asserts: zero hi-class misses under ~2x offered
    load, and every miss landing on the lowest class as a loud shed. A
    full serve_bench rerun dropping the --load-gen merge key fails
    here."""
    from distributed_tensorflow_tpu.tools import serve_bench

    root = serve_bench._docs_root()
    with open(os.path.join(root, "serving.json")) as f:
        payload = json.load(f)
    lg = payload.get("load_gen")
    assert lg, (
        "serving.json lost its load_gen section; run python -m "
        "distributed_tensorflow_tpu.tools.serve_bench --load-gen "
        "--write-docs"
    )
    mix = lg["scenarios"]["priority_mix"]
    assert mix["hi_class_misses"] == 0
    assert mix["sheds_on_lowest_class_only"] is True
    classes = mix["classes"]
    assert {int(k) for k in classes} == {0, 1, 2}
    for stats in classes.values():
        for key in ("requests", "done", "shed", "shed_rate", "ttft_s"):
            assert key in stats
    # The steady baseline rides alongside: no shedding at sub-capacity.
    steady = lg["scenarios"]["steady"]
    assert all(s["shed"] == 0 for s in steady["classes"].values())
