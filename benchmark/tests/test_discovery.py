"""A configuration, a traffic mix, a per-layer metric and a cell are
added as new files and new manifest entries alone: drop them into a
temporary copy of the benchmark and let the copy's own harness find
them."""

import importlib.util
import json
import os
import shutil
import sys

from benchmark.lib import harness


def _copy_with_additions(tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(harness.BENCH, root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    manifest = harness.manifest()
    cfg = dict(harness.config("gpt2-medium"), name="test-only", n_layer=2)
    (root / "benchmark/configs/test-only.json").write_text(json.dumps(cfg))
    mix = dict(harness.traffic("chat-steady"), what="test-only mix")
    mix["arrivals"] = dict(mix["arrivals"], rate_per_s=1.5)
    (root / "benchmark/traffic/test-mix.json").write_text(json.dumps(mix))
    (root / "benchmark/metrics/answers_per_request.py").write_text(
        '"""Mean answer length of the window\'s requests."""\n\n\n'
        "def read(run):\n"
        "    n = [r['answer'] for r in run.requests]\n"
        "    return sum(n) / len(n) if n else None\n")
    (root / "benchmark/limits/test-cell.json").write_text('{"max_logit_gap": 0.5}')
    manifest["configs"].append(dict(
        name="test-only", source="test", file="benchmark/configs/test-only.json",
        reduced=["n_layer"], why="test"))
    manifest["workloads"].append(dict(
        name="test-cell", config="test-only", traffic="test-mix", chips=1, why="test"))
    manifest["per_layer"].append(dict(
        name="answers_per_request", unit="count", better="higher",
        source="program_counter", layer="load generator", moves="tpot_p50_ms",
        workloads=["test-cell"]))
    next(e for e in manifest["end_to_end"]
         if e["name"] == "tpot_p50_ms")["workloads"].append("test-cell")
    (root / "BENCHMARK.json").write_text(json.dumps(manifest))
    return root


def test_new_files_are_found_without_editing_any(tmp_path):
    root = _copy_with_additions(tmp_path)
    spec = importlib.util.spec_from_file_location(
        "copied_harness", root / "benchmark/lib/harness.py")
    h = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = h  # dataclasses look the module up by name
    try:
        spec.loader.exec_module(h)
    finally:
        del sys.modules[spec.name]
    assert h.ROOT == str(root)
    cell = h.workload("test-cell")
    assert h.config(cell["config"])["n_layer"] == 2
    assert h.traffic(cell["traffic"])["arrivals"]["rate_per_s"] == 1.5
    assert h.limits("test-cell") == {"max_logit_gap": 0.5}
    names = [m["name"] for m in h.metrics_for("test-cell", "per_layer")]
    assert names == ["answers_per_request"]
    run = h.Run("test-cell", {}, {}, 1, {}, requests=[{"answer": 4}, {"answer": 8}])
    assert h.metric_reader("answers_per_request")(run) == 6.0
    assert [m["name"] for m in h.metrics_for("test-cell", "end_to_end")] == [
        "tpot_p50_ms", "setup_s"]
    # the cells that were there report what they reported
    assert "answers_per_request" not in [
        m["name"] for m in h.metrics_for("serve-gpt2l-chat", "per_layer")]


def test_manifest_names_files_that_exist():
    m = harness.manifest()
    for c in m["configs"]:
        assert os.path.isfile(os.path.join(harness.ROOT, c["file"]))
        assert harness.config(c["name"])["source"] == c["source"]
    for w in m["workloads"]:
        assert harness.traffic(w["traffic"])["kind"] in ("train", "serve")
        assert harness.limits(w["name"])
        assert len(w["why"]) <= 200
    e2e = {e["name"] for e in m["end_to_end"]}
    for p in m["per_layer"]:
        assert p["moves"] in e2e
        assert callable(harness.metric_reader(p["name"]))


def test_nothing_imports_the_programs_tools():
    for dirpath, _, files in os.walk(harness.BENCH):
        for f in files:
            if f.endswith(".py") and "tests" not in dirpath:
                text = open(os.path.join(dirpath, f)).read()
                assert "distributed_tensorflow_tpu.tools" not in text, f
