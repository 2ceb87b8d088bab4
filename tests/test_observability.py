"""Telemetry layer (observability/, round 10) — fast tier.

Four contracts under test:

1. **Byte parity** (SURVEY §5 log contract): with the journal attached,
   stdout is byte-identical to the pre-journal StepLogger / lifecycle
   wording — every line is rendered FROM its event, and re-rendering the
   journal through a vendored copy of the PRE-PR formatting reproduces
   the captured lines exactly.
2. **Dual landing**: each lifecycle signal (restart/resize/rollback/
   world_size) reaches BOTH tfevents and the journal through the one
   ``utils/summary.lifecycle_event`` emitter.
3. **Barrier honesty**: a dispatch span refuses to close without a D2H
   value fetch (the CLAUDE.md timing-trap discipline, enforced by API).
4. **Grep-lint**: no structured-line literal (``"Restart:`` …) outside
   ``observability/format.py`` — new lifecycle lines must go through
   ``emit_line`` (same staleness-guard pattern as test_perf_record).

The journal/metrics/spans halves are jax-free; the trainer/server
integration halves use the virtual CPU mesh like the rest of the tier.
"""

from __future__ import annotations

import json
import os
import re
import struct
import subprocess
import sys
import textwrap
import time

import pytest

from distributed_tensorflow_tpu import observability as obs
from distributed_tensorflow_tpu.observability import format as obs_format
from distributed_tensorflow_tpu.utils.logging import StepLogger
from distributed_tensorflow_tpu.utils.summary import lifecycle_event

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PKG = os.path.join(REPO, "distributed_tensorflow_tpu")


# ---------------------------------------------------------------------------
# Journal: JSONL roundtrip, tagging, crash-tail tolerance.
# ---------------------------------------------------------------------------


def test_journal_roundtrip_and_tags(tmp_path):
    j = obs.EventJournal.in_dir(str(tmp_path), rank=1, world=4, run_id="r9")
    j.emit("step", step=7, cost=1.5)
    j.emit("restart", restart=1)
    j.close()
    evs = obs.read_events(str(tmp_path))
    assert [e["kind"] for e in evs] == ["step", "restart"]
    assert evs[0]["rank"] == 1 and evs[0]["world"] == 4 and evs[0]["run"] == "r9"
    assert evs[0]["step"] == 7 and evs[0]["cost"] == 1.5
    assert evs[0]["ts"] <= evs[1]["ts"]
    assert obs.read_events(str(tmp_path), kind="restart") == evs[1:]


def test_journal_tolerates_torn_tail_but_not_mid_corruption(tmp_path):
    path = str(tmp_path / "events.jsonl")
    j = obs.EventJournal(path)
    j.emit("a")
    j.emit("b")
    j.close()
    with open(path, "a") as f:
        f.write('{"kind": "torn-mid-wri')  # killed mid-append, no newline
    assert [e["kind"] for e in obs.read_events(path)] == ["a", "b"]
    with open(path, "a") as f:
        f.write('\n{"kind": "c"}\n')  # the torn line is now MID-file
    with pytest.raises(ValueError, match="corrupt event line"):
        obs.read_events(path)


def test_null_journal_builds_events_without_io(tmp_path):
    n = obs.NullJournal()
    ev = n.emit("step", step=1)
    assert ev["kind"] == "step" and ev["step"] == 1 and "ts" in ev
    assert not os.listdir(tmp_path)  # nothing anywhere near disk


def test_append_event_one_shot(tmp_path):
    path = str(tmp_path / "events.jsonl")
    obs.append_event(path, "bench_point", tool="t", value=1.0)
    obs.append_event(path, "bench_point", tool="t", value=2.0)
    assert [e["value"] for e in obs.read_events(path)] == [1.0, 2.0]


def test_configure_default_journal(tmp_path):
    try:
        obs.configure(str(tmp_path), rank=0)
        ev = obs.emit("step", step=3)
        assert ev["rank"] == 0
        assert obs.read_events(str(tmp_path))[0]["step"] == 3
    finally:
        obs.configure()  # back to the NullJournal
    assert isinstance(obs.get_journal(), obs.NullJournal)


# ---------------------------------------------------------------------------
# Metrics registry.
# ---------------------------------------------------------------------------


def test_metrics_counter_gauge_histogram():
    r = obs.MetricsRegistry()
    c = r.counter("requests_total")
    c.inc()
    c.inc(2)
    assert c.value == 3
    with pytest.raises(ValueError):
        c.inc(-1)
    g = r.gauge("queue_depth")
    g.set(5)
    g.dec()
    assert g.value == 4
    h = r.histogram("lat_s", edges=(0.1, 1.0, 10.0))
    for v in (0.05, 0.5, 0.5, 100.0):
        h.observe(v)
    assert h.counts == [1, 2, 0, 1] and h.count == 4
    assert h.quantile(0.5) == 1.0  # bucket upper bound of the median
    # get-or-create returns the same instrument; type mismatch is loud
    assert r.counter("requests_total") is c
    with pytest.raises(TypeError):
        r.gauge("requests_total")


def test_metrics_prometheus_text_and_snapshot():
    r = obs.MetricsRegistry()
    r.counter("x_total").inc(3)
    r.gauge("world_size", labels={"gang": "g0"}).set(2)
    h = r.histogram("lat_s", edges=(0.1, 1.0))
    h.observe(0.05)
    h.observe(5.0)
    text = r.prometheus_text()
    assert "# TYPE x_total counter\nx_total 3" in text
    assert 'world_size{gang="g0"} 2' in text
    assert 'lat_s_bucket{le="0.1"} 1' in text
    assert 'lat_s_bucket{le="+Inf"} 2' in text
    assert "lat_s_sum 5.05" in text and "lat_s_count 2" in text
    snap = r.snapshot()
    assert snap["x_total"][0]["value"] == 3
    assert snap["lat_s"][0]["counts"] == [1, 0, 1]


def test_metrics_flush_to_journal(tmp_path):
    r = obs.MetricsRegistry()
    r.counter("epochs_total").inc(2)
    j = obs.EventJournal.in_dir(str(tmp_path))
    r.flush_to(j, component="trainer")
    j.close()
    (ev,) = obs.read_events(str(tmp_path), kind="metrics")
    assert ev["component"] == "trainer"
    assert ev["metrics"]["epochs_total"][0]["value"] == 2


# ---------------------------------------------------------------------------
# Spans: chrome trace + the enforced D2H barrier.
# ---------------------------------------------------------------------------


def test_dispatch_span_requires_d2h_fetch():
    rec = obs.SpanRecorder()
    with pytest.raises(RuntimeError, match="without a D2H fetch"):
        with rec.dispatch("train_step"):
            pass  # no fetch: must refuse to close (TIMING TRAP contract)
    import numpy as np

    with rec.dispatch("train_step") as sp:
        out = sp.fetch(np.float32(1.5))  # __array__ → host materialization
    assert float(out) == 1.5
    spans = [s for s in rec.spans if s["args"].get("barrier") == "d2h"]
    assert len(spans) == 1 and spans[0]["name"] == "train_step"
    with pytest.raises(ValueError):
        obs.force_host(None)


def test_dispatch_span_error_is_recorded_not_masked():
    rec = obs.SpanRecorder()
    with pytest.raises(RuntimeError, match="boom"):
        with rec.dispatch("bad"):
            raise RuntimeError("boom")
    assert rec.spans[-1]["args"]["error"] is True


def test_dispatch_span_with_jax_array():
    """The case ``mark()`` + ``dispatch_fetch`` used to cover (PR 26 moved
    their callers to the ``with`` form): a device array comes back as a
    host value, and arguments written between the fetch and the end of
    the block land in the span."""
    import jax.numpy as jnp

    rec = obs.SpanRecorder()
    with rec.dispatch("scan", epoch=0) as sp:
        host = sp.fetch(jnp.arange(4.0))
        sp.args["delivered"] = 4
    assert list(host) == [0.0, 1.0, 2.0, 3.0]
    assert rec.spans[-1]["args"] == {
        "epoch": 0, "delivered": 4, "barrier": "d2h",
    }
    # the span ends AT the fetch, not at the end of the block
    with rec.dispatch("scan") as sp:
        sp.fetch(1.0)
        time.sleep(0.05)
    assert rec.spans[-1]["dur_us"] < 40_000


def test_chrome_trace_export_loads(tmp_path):
    j = obs.EventJournal.in_dir(str(tmp_path))
    rec = obs.SpanRecorder(journal=j)
    with rec.span("compile", cat="xla"):
        pass
    with rec.dispatch("step") as sp:
        sp.fetch(1.0)
    out = str(tmp_path / "trace.json")
    rec.export_chrome_trace(out)
    with open(out) as f:
        trace = json.load(f)
    assert trace["displayTimeUnit"] == "ms"
    evs = trace["traceEvents"]
    assert [e["name"] for e in evs] == ["compile", "step"]
    for e in evs:
        # The chrome trace event format fields Perfetto requires.
        assert e["ph"] == "X"
        assert isinstance(e["ts"], float) and isinstance(e["dur"], float)
        assert isinstance(e["pid"], int) and isinstance(e["tid"], int)
    # Spans also mirrored into the journal → obs_report can rebuild.
    j.close()
    from distributed_tensorflow_tpu.observability.spans import chrome_trace

    from_journal = chrome_trace(obs.read_events(str(tmp_path), kind="span"))
    assert [e["name"] for e in from_journal["traceEvents"]] == [
        "compile",
        "step",
    ]


# ---------------------------------------------------------------------------
# Byte parity: the pre-PR formatting, vendored VERBATIM, re-rendered from
# the journal events must equal the captured stdout.
# ---------------------------------------------------------------------------


def legacy_render(events):
    """The PRE-round-10 StepLogger/lifecycle print calls, copied verbatim
    (print joined multi-args with one space), replayed over journal
    events."""
    out = []
    pr = lambda *a: out.append(" ".join(map(str, a)))  # noqa: E731
    for ev in events:
        k = ev["kind"]
        if k == "step":
            pr(
                "Step: %d," % ev["step"],
                " Epoch: %2d," % ev["epoch"],
                " Batch: %3d of %3d," % (ev["batch"], ev["batch_count"]),
                " Cost: %.4f," % ev["cost"],
                " AvgTime: %3.2fms" % ev["avg_ms"],
            )
        elif k == "epoch":
            if ev["metric"] == "Test-Accuracy":
                pr("Test-Accuracy: %2.2f" % ev["value"])
            else:
                pr("%s: %.4f" % (ev["metric"], ev["value"]))
            pr("Total Time: %3.2fs" % ev["total_time_s"])
        elif k == "final":
            pr("Final Cost: %.4f" % ev["cost"])
            pr("Done")
    return out


def test_step_logger_byte_parity(tmp_path):
    j = obs.EventJournal.in_dir(str(tmp_path))
    lines = []
    logger = StepLogger(
        freq=2, print_fn=lambda *a: lines.append(" ".join(map(str, a))),
        journal=j,
    )
    for i in range(5):
        logger.maybe_log_step(
            step=i + 1, epoch=0, batch=i, batch_count=5, cost=2.0 / (i + 1)
        )
    logger.log_epoch(test_accuracy=0.8156)
    logger.log_epoch_metric("Test-Perplexity", 12.3456)
    logger.log_final(cost=0.0123)
    j.close()
    events = obs.read_events(str(tmp_path))
    assert lines == legacy_render(events)
    # Spot-pin the exact reference bytes too (freq=2 → batches 2, 4, 5).
    assert lines[0].startswith("Step: 2,  Epoch:  1,  Batch:   2 of   5,")
    assert "Test-Accuracy: 0.82" in lines
    assert lines[-1] == "Done"


LEGACY_LIFECYCLE = {
    # kind → (fields, the exact pre-PR f-string output)
    "restart": (
        dict(restart=2, max_restarts=3, cause="worker0=rc=1", backoff_s=1.25),
        "Restart: restart=2/3 cause[worker0=rc=1] backoff_s=1.2",
    ),
    "restart_exhausted": (
        dict(restarts=3, max_restarts=3, cause="worker1=dead"),
        "Restart: budget exhausted restarts=3/3 cause[worker1=dead] — "
        "failing stop (checkpoints intact; newest valid step restores on "
        "the next launch)",
    ),
    "resize": (
        dict(world=1, from_world=2, min_workers=1, direction="shrink",
             dropped=["worker1"], rejoined=[], restart=1, max_restarts=3),
        "Resize: world=1 from=2 min_workers=1 direction=shrink "
        "dropped=[worker1] rejoined=[] restart=1/3",
    ),
    "resize_denied": (
        dict(world=0, min_workers=1, restarts=2, max_restarts=3,
             cause="worker0=dead"),
        "Resize: denied world=0 min_workers=1 restarts=2/3 "
        "cause[worker0=dead] — failing stop (checkpoints intact; newest "
        "valid step restores on the next launch)",
    ),
    "rollback": (
        dict(anomaly="spike", epoch=4, detected_step=400, restored_step=300,
             rollback=1, max_rollbacks=3),
        "Rollback: kind=spike epoch=4 detected_step=400 restored_step=300 "
        "rollback=1/3 data_window=skipped",
    ),
    "rollback_compiled": (
        {},
        "Rollback: kind=nan dispatch=compiled save=skipped "
        "(state not checkpointed; last good step kept)",
    ),
    "preemption": (
        dict(signal=15),
        "Preemption: signal=15 stop_requested=1 — finishing the current "
        "epoch, saving, exiting (signal again to force)",
    ),
    "restore": (
        dict(global_batch=200, from_world=2, world=1, config_batch=100,
             config_global=100, per_replica=200),
        "Restore: global_batch=200 preserved (world=2->1, config batch "
        "100x1=100 overridden, per-replica batch 200)",
    ),
}


def test_lifecycle_lines_byte_identical():
    for kind, (fields, expected) in LEGACY_LIFECYCLE.items():
        ev = obs.NullJournal().emit(kind, **fields)
        assert obs_format.render(kind, ev) == [expected], kind


def _read_tfevent_records(path):
    records = []
    with open(path, "rb") as f:
        while True:
            header = f.read(8)
            if not header:
                return records
            (length,) = struct.unpack("<Q", header)
            f.read(4)
            records.append(f.read(length))
            f.read(4)


def test_lifecycle_lands_in_tfevents_and_journal(tmp_path):
    """Satellite: the shared emitter routes every lifecycle scalar to
    BOTH sinks (plus stdout) in one call."""
    from distributed_tensorflow_tpu.utils.summary import SummaryWriter

    j = obs.EventJournal.in_dir(str(tmp_path))
    writer = SummaryWriter(str(tmp_path))
    lines = []
    cases = [
        ("restart", ("restart", 1.0, 1), LEGACY_LIFECYCLE["restart"][0]),
        ("resize", ("world_size", 1.0, 1), LEGACY_LIFECYCLE["resize"][0]),
        ("rollback", ("rollback", 300.0, 400), LEGACY_LIFECYCLE["rollback"][0]),
    ]
    for kind, scalar, fields in cases:
        lifecycle_event(
            kind, print_fn=lines.append, journal=j, writer=writer,
            scalar=scalar, **fields,
        )
    writer.close()
    j.close()
    events = obs.read_events(str(tmp_path))
    assert [e["kind"] for e in events] == [k for k, _, _ in cases]
    records = b"".join(_read_tfevent_records(writer.path))
    for tag in (b"restart", b"world_size", b"rollback"):
        assert tag in records, tag
    assert lines[0] == LEGACY_LIFECYCLE["restart"][1]
    assert lines[1] == LEGACY_LIFECYCLE["resize"][1]
    assert lines[2] == LEGACY_LIFECYCLE["rollback"][1]


def test_preemption_guard_journals_the_event(tmp_path):
    import signal

    from distributed_tensorflow_tpu.train import resilience as R
    from distributed_tensorflow_tpu.train.supervisor import Supervisor

    j = obs.EventJournal.in_dir(str(tmp_path))
    sup = Supervisor()
    lines = []
    with R.preemption_guard(sup, print_fn=lines.append, journal=j) as handler:
        handler(signal.SIGTERM, None)
    j.close()
    assert sup.should_stop
    (ev,) = obs.read_events(str(tmp_path), kind="preemption")
    assert ev["signal"] == signal.SIGTERM
    assert lines == legacy_lifecycle_line("preemption", signal=signal.SIGTERM)


def legacy_lifecycle_line(kind, **fields):
    return obs_format.render(kind, obs.NullJournal().emit(kind, **fields))


def test_round22_lifecycle_renderers():
    """Watchdog + preemption-variant lines (round 22). The default
    preemption line stays byte-identical (LEGACY_LIFECYCLE above); the
    disarmed and saved_step variants are additive."""
    assert legacy_lifecycle_line(
        "preemption", disarmed="non-main thread"
    ) == ["Preemption: disarmed (non-main thread)"]
    assert legacy_lifecycle_line("preemption", signal=15, saved_step=70) == [
        "Preemption: signal=15 stop_requested=1 — finishing the current "
        "epoch, saving, exiting (signal again to force) saved_step=70"
    ]
    assert legacy_lifecycle_line("heartbeat", rank=2, step=400) == [
        "Heartbeat: rank=2 step=400"
    ]
    assert legacy_lifecycle_line(
        "stall", member="worker1", age_s=42.125, stall_after_s=30.0
    ) == [
        "Stall: member=worker1 heartbeat_age_s=42.1 stall_after_s=30.0 "
        "— killing and recovering through the elastic path"
    ]


# ---------------------------------------------------------------------------
# Grep-lint: structured-line literals only inside observability/format.py.
# ---------------------------------------------------------------------------

_STRUCTURED_LITERAL = re.compile(
    r"""["']f?(Restart|Resize|Rollback|Preemption|Restore|Stall|Heartbeat):|"""
    r"""f["'](Restart|Resize|Rollback|Preemption|Restore|Stall|Heartbeat):"""
)


def test_no_structured_line_literals_outside_format():
    offenders = []
    for dirpath, _, files in os.walk(PKG):
        for fname in files:
            if not fname.endswith(".py"):
                continue
            path = os.path.join(dirpath, fname)
            rel = os.path.relpath(path, PKG)
            if rel == os.path.join("observability", "format.py"):
                continue  # the ONE home of the line wording
            with open(path, encoding="utf-8") as f:
                for lineno, line in enumerate(f, 1):
                    if _STRUCTURED_LITERAL.search(line):
                        offenders.append(f"{rel}:{lineno}: {line.strip()}")
    assert not offenders, (
        "structured lifecycle line literals outside observability/format.py "
        "— route them through observability.format.emit_line / "
        "utils.summary.lifecycle_event so the journal sees them:\n"
        + "\n".join(offenders)
    )


# ---------------------------------------------------------------------------
# Trainer integration: byte parity on a real run + events in the journal.
# ---------------------------------------------------------------------------


def _small_run(small_datasets, tmp_path, journal):
    from distributed_tensorflow_tpu.config import TrainConfig
    from distributed_tensorflow_tpu.data.mnist import DataSet, Datasets
    from distributed_tensorflow_tpu.models import MLP
    from distributed_tensorflow_tpu.train.trainer import Trainer

    ds = Datasets(
        train=DataSet(
            small_datasets.train.images[:2000],
            small_datasets.train.labels[:2000],
            seed=1,
        ),
        validation=small_datasets.validation,
        test=DataSet(
            small_datasets.test.images[:500],
            small_datasets.test.labels[:500],
            seed=2,
        ),
    )
    lines = []
    tr = Trainer(
        MLP(),
        ds,
        TrainConfig(epochs=1, log_frequency=10),
        print_fn=lambda *a: lines.append(" ".join(map(str, a))),
        journal=journal,
    )
    tr.run()
    return lines


def test_trainer_run_byte_parity_and_journal(small_datasets, tmp_path):
    j = obs.EventJournal.in_dir(str(tmp_path), run_id="parity")
    lines = _small_run(small_datasets, tmp_path, j)
    j.close()
    events = obs.read_events(str(tmp_path))
    kinds = {e["kind"] for e in events}
    assert {"step", "epoch", "final", "metrics"} <= kinds
    # Every stdout line is exactly the PRE-PR rendering of its event.
    printable = [
        e for e in events if e["kind"] in ("step", "epoch", "final")
    ]
    assert lines == legacy_render(printable)
    # And with NO journal (the default NullJournal) the bytes are the
    # same modulo wall-clock times: same count, same shapes.
    lines2 = _small_run(small_datasets, tmp_path, None)
    assert len(lines2) == len(lines)
    strip = lambda ls: [  # noqa: E731 — mask the timing fields
        re.sub(r"AvgTime: *[0-9.]+ms|Total Time: *[0-9.]+s", "T", x)
        for x in ls
    ]
    assert strip(lines2) == strip(lines)
    # The metrics snapshot carries the trainer instruments.
    snap = [e for e in events if e["kind"] == "metrics"][-1]["metrics"]
    assert snap["epochs_total"][0]["value"] == 1
    assert snap["step_time_ms"][0]["count"] >= 1


# ---------------------------------------------------------------------------
# Elastic gang integration: Restart events + heartbeat metrics.
# ---------------------------------------------------------------------------


class _Proc:
    def __init__(self, script):
        self.script = list(script)
        self.killed = False

    def poll(self):
        if self.killed:
            return -9
        if len(self.script) > 1:
            return self.script.pop(0)
        return self.script[0]

    def kill(self):
        self.killed = True

    def wait(self, timeout=None):
        return -9


def test_elastic_gang_journals_restart(tmp_path):
    from distributed_tensorflow_tpu.train.elastic import (
        ElasticAgent,
        ElasticGang,
    )

    j = obs.EventJournal.in_dir(str(tmp_path))
    scripts = {0: [[None, 1], [None, 0]], 1: [[None, None, 0], [None, 0]]}
    spawned = {0: 0, 1: 0}

    def spawner(i):
        def _spawn():
            p = _Proc(scripts[i][min(spawned[i], 1)])
            spawned[i] += 1
            return p

        return _spawn

    gang = ElasticGang(
        [ElasticAgent(f"worker{i}", spawner(i)) for i in range(2)],
        max_restarts=2,
        jitter=0.0,
        sleep=lambda s: None,
        print_fn=lambda *a: None,
        journal=j,
    )
    assert gang.run() == 0
    j.close()
    events = obs.read_events(str(tmp_path))
    (restart,) = [e for e in events if e["kind"] == "restart"]
    assert restart["restart"] == 1 and "worker0=rc=1" in restart["cause"]
    (snap,) = [e for e in events if e["kind"] == "metrics"]
    assert snap["component"] == "elastic"
    assert snap["metrics"]["restarts_total"][0]["value"] == 1
    assert snap["metrics"]["world_size"][0]["value"] == 2
    assert gang.metrics.counter("restarts_total").value == 1


# ---------------------------------------------------------------------------
# Supervisor checkpoint telemetry.
# ---------------------------------------------------------------------------


def test_supervisor_save_restore_events(tmp_path):
    pytest.importorskip("orbax.checkpoint")
    import jax.numpy as jnp

    from distributed_tensorflow_tpu.parallel.strategy import TrainState
    from distributed_tensorflow_tpu.train.supervisor import Supervisor

    j = obs.EventJournal.in_dir(str(tmp_path))
    metrics = obs.MetricsRegistry()
    sup = Supervisor(checkpoint_dir=str(tmp_path / "ckpt"))
    sup.attach_observability(j, metrics, obs.SpanRecorder(journal=j))
    state = TrainState(
        {"w": jnp.ones((4, 4))}, {}, jnp.asarray(3, jnp.int32)
    )
    sup.save(state, 3)
    restored, step = sup.prepare_or_restore(state)
    j.close()
    assert step == 3
    (save_ev,) = obs.read_events(str(tmp_path), kind="checkpoint_save")
    assert save_ev["step"] == 3 and save_ev["bytes"] > 0
    assert save_ev["duration_s"] > 0
    (rest_ev,) = obs.read_events(str(tmp_path), kind="checkpoint_restore")
    assert rest_ev["step"] == 3 and rest_ev["fallback"] is False
    spans = obs.read_events(str(tmp_path), kind="span")
    assert any(s["name"] == "checkpoint_save" for s in spans)
    assert metrics.counter("checkpoint_saves_total").value == 1
    assert metrics.counter("checkpoint_bytes_total").value == save_ev["bytes"]
    assert metrics.counter("checkpoint_restores_total").value == 1


# ---------------------------------------------------------------------------
# TextServer instrumentation (admissions/completions/TTFT/spans).
# ---------------------------------------------------------------------------


def test_text_server_telemetry(tmp_path):
    import numpy as np

    from distributed_tensorflow_tpu.models.gpt import GPTLM
    from distributed_tensorflow_tpu.serve import GenerationConfig, TextServer

    model = GPTLM(
        vocab_size=64, max_len=64, model_dim=32, num_heads=2, num_layers=1
    )
    params = model.init(seed=0)
    j = obs.EventJournal.in_dir(str(tmp_path))
    srv = TextServer(
        model, params, slots=2, buckets=(16,), chunk=4, journal=j
    )
    prompts = [np.arange(1, 6, dtype=np.int32)] * 3  # 3 reqs through 2 slots
    outs = srv.generate(prompts, GenerationConfig(max_new=6))
    j.close()
    assert all(len(o) == 6 for o in outs)
    events = obs.read_events(str(tmp_path))
    admissions = [e for e in events if e["kind"] == "admission"]
    completions = [e for e in events if e["kind"] == "completion"]
    assert len(admissions) == 3 and len(completions) == 3
    assert {e["rid"] for e in completions} == {0, 1, 2}
    for e in completions:
        assert e["tokens"] == 6
        assert e["latency_s"] >= e["ttft_s"] > 0
    # Continuous batching visible in the journal: the third request is
    # admitted AFTER some completion freed a slot.
    assert admissions[2]["ts"] >= min(e["ts"] for e in completions)
    assert admissions[2]["queue_wait_s"] > 0
    spans = [e for e in events if e["kind"] == "span"]
    names = {s["name"] for s in spans}
    assert {"prefill", "decode_chunk"} <= names
    assert all(s["args"]["barrier"] == "d2h" for s in spans)
    m = srv.metrics
    assert m.counter("admissions_total").value == 3
    assert m.counter("completions_total").value == 3
    assert m.counter("tokens_generated_total").value == 18
    assert m.histogram("ttft_s").count == 3
    assert m.histogram("request_latency_s").count == 3


def test_paged_server_cache_telemetry_and_report(tmp_path):
    """Round 11 serving-cache instrumentation: kv_blocks gauges, prefix
    hit/miss counters, spec_tokens counters, their journal events
    (admission prefix fields + spec_verify), and obs_report's
    serving-cache section computed from them."""
    import numpy as np

    from distributed_tensorflow_tpu.models.gpt import GPTLM
    from distributed_tensorflow_tpu.serve import GenerationConfig, TextServer
    from distributed_tensorflow_tpu.tools import obs_report

    model = GPTLM(
        vocab_size=64, max_len=64, model_dim=32, num_heads=2, num_layers=1
    )
    params = model.init(seed=0)
    j = obs.EventJournal.in_dir(str(tmp_path))
    srv = TextServer(
        model, params, slots=2, buckets=(16,), chunk=4, journal=j,
        paged=True, block_size=4, spec_draft=3,
    )
    sysp = np.arange(1, 13, dtype=np.int32)  # 12-token shared prefix
    srv.generate([sysp], GenerationConfig(max_new=4))
    prompts = [np.concatenate([sysp, np.asarray([t], np.int32)])
               for t in (20, 21)]
    srv.generate(prompts, GenerationConfig(max_new=6))
    srv.metrics.flush_to(j)
    j.close()

    m = srv.metrics
    assert m.gauge("kv_blocks_total").value == srv.kv_blocks
    assert m.gauge("kv_blocks_used").value == len(srv._prefix._map)
    assert m.counter("prefix_cache_hits").value == 6  # 2 reqs x 3 blocks
    assert m.counter("spec_tokens_proposed").value >= (
        m.counter("spec_tokens_accepted").value
    )

    events = obs.read_events(str(tmp_path))
    admissions = [e for e in events if e["kind"] == "admission"]
    assert all("prefix_hit_blocks" in e for e in admissions)
    assert sum(e["prefix_hit_blocks"] for e in admissions) == 6
    assert any(e["kind"] == "spec_verify" for e in events)
    assert {"prefill", "spec_verify"} <= {
        e["name"] for e in events if e["kind"] == "span"
    }

    summary = obs_report.summarize(events)
    sc = summary["serving_cache"]
    assert sc["prefix"]["hit_blocks"] == 6
    assert 0 < sc["prefix"]["hit_rate"] <= 1
    assert sc["speculation"]["verify_dispatches"] >= 1
    assert sc["speculation"]["tokens_per_dispatch"] >= 1
    assert sc["kv_blocks"]["total"] == srv.kv_blocks
    report = obs_report.render_report(summary)
    assert "serving cache:" in report and "acceptance" in report


# ---------------------------------------------------------------------------
# obs_report: the replay reconstructs the run.
# ---------------------------------------------------------------------------


def _synthetic_journal(tmp_path):
    j = obs.EventJournal.in_dir(str(tmp_path), run_id="synthetic")
    j.emit("step", step=100, epoch=1, batch=100, batch_count=550,
           cost=2.1, avg_ms=1.5)
    j.emit("step", step=550, epoch=1, batch=550, batch_count=550,
           cost=1.7, avg_ms=1.4)
    j.emit("epoch", metric="Test-Accuracy", value=0.62, total_time_s=10.0)
    j.emit("restart", **LEGACY_LIFECYCLE["restart"][0])
    j.emit("resize", **LEGACY_LIFECYCLE["resize"][0])
    j.emit("rollback", **LEGACY_LIFECYCLE["rollback"][0])
    j.emit("checkpoint_save", step=550, bytes=12345, duration_s=0.2)
    j.emit("admission", rid=0, slot=0, bucket=16, prompt_len=5,
           queue_wait_s=0.001)
    j.emit("completion", rid=0, slot=0, tokens=6, latency_s=0.5,
           ttft_s=0.1)
    j.emit("span", name="prefill", cat="dispatch", ts_us=0.0, dur_us=900.0,
           args={"barrier": "d2h"})
    j.emit("final", cost=1.7)
    j.close()
    return str(tmp_path)


def test_obs_report_reconstructs_history(tmp_path, capsys):
    from distributed_tensorflow_tpu.tools import obs_report

    path = _synthetic_journal(tmp_path)
    events = obs.read_events(path)
    summary = obs_report.summarize(events)
    assert summary["training"]["last_step"] == 550
    assert summary["final_cost"] == 1.7
    assert [h["kind"] for h in summary["lifecycle"]] == [
        "restart", "resize", "rollback",
    ]
    # The replayed lines ARE the byte-identical structured lines.
    assert summary["lifecycle"][0]["line"] == LEGACY_LIFECYCLE["restart"][1]
    assert summary["lifecycle"][1]["line"] == LEGACY_LIFECYCLE["resize"][1]
    assert summary["checkpoints"]["bytes_total"] == 12345
    assert summary["serving"]["admissions"] == 1
    assert summary["serving"]["latency_s"]["p50"] == 0.5
    # CLI: report + trace export.
    trace_out = str(tmp_path / "trace.json")
    rc = obs_report.main([path, "--trace", trace_out])
    assert rc == 0
    printed = capsys.readouterr().out
    assert "lifecycle history:" in printed
    assert LEGACY_LIFECYCLE["restart"][1] in printed
    with open(trace_out) as f:
        trace = json.load(f)
    assert trace["traceEvents"][0]["name"] == "prefill"
    rc = obs_report.main([path, "--json"])
    assert rc == 0
    assert json.loads(capsys.readouterr().out.strip())["events"] == 11


def test_perf_record_reads_journal_points(tmp_path, capsys):
    from distributed_tensorflow_tpu.tools import perf_record

    path = str(tmp_path / "events.jsonl")
    obs.append_event(path, "bench_point", tool="serve_bench",
                     name="batched_tokens_per_s", value=100.0, unit="tokens/s")
    obs.append_event(path, "bench_point", tool="serve_bench",
                     name="batched_tokens_per_s", value=120.0, unit="tokens/s")
    obs.append_event(path, "bench_point", tool="lm_bench",
                     name="gpt-s-L512-xla", value=150000.0, unit="tokens/s")
    points = perf_record.journal_points(path)
    assert len(points) == 2  # latest wins per (tool, name)
    by_tool = {p["tool"]: p for p in points}
    assert by_tool["serve_bench"]["value"] == 120.0
    assert perf_record.main(["--journal", path]) == 0
    assert "150000" in capsys.readouterr().out


def test_serve_bench_emitter_shares_the_journal_source(tmp_path):
    from distributed_tensorflow_tpu.tools import perf_record, serve_bench

    payload = {
        "device": "cpu",
        "batched": {"tokens_per_s": 100.0, "slots": 8, "chunk": 32},
        "sequential": {"tokens_per_s": 50.0},
        "batched_speedup": 2.0,
        "chunk_speedup": 6.6,
        "dispatch_fixed_ms": 2.4,
        "marginal_token_ms": 0.34,
    }
    path = str(tmp_path / "events.jsonl")
    evs = serve_bench.emit_bench_events(payload, path)
    assert len(evs) == 6
    points = perf_record.journal_points(path)
    assert {p["name"] for p in points} == {
        "batched_tokens_per_s", "sequential_tokens_per_s",
        "batched_speedup", "chunk_speedup", "dispatch_fixed_ms",
        "marginal_token_ms",
    }


def test_lm_bench_emitter(tmp_path):
    # Import ONLY the emitter's module lazily: lm_bench imports jax/optax
    # at module level (it is a chip tool), fine on this tier.
    from distributed_tensorflow_tpu.tools import lm_bench, perf_record

    rows = [
        {"config": "gpt-s-L512-xla", "tokens_per_sec": 150000.0,
         "step_ms": 10.0, "mfu_model_pct": 5.0, "mfu_star_pct": 2.0},
        {"config": "broken", "error": "boom"},
    ]
    path = str(tmp_path / "events.jsonl")
    evs = lm_bench.emit_bench_events(rows, "cpu", path)
    assert len(evs) == 1  # error rows are skipped
    (point,) = perf_record.journal_points(path)
    assert point["name"] == "gpt-s-L512-xla" and point["value"] == 150000.0


# ---------------------------------------------------------------------------
# Lean import: the whole reader stack works with NO jax at all.
# ---------------------------------------------------------------------------


def test_observability_imports_and_runs_without_jax(tmp_path):
    """Satellite: the package and tools/obs_report work on a container
    whose jax is broken — a poisoned `jax` stub raises on import, and the
    subprocess exercises journal + metrics + spans + render + obs_report
    end to end."""
    stub_dir = tmp_path / "nojax"
    stub_dir.mkdir()
    (stub_dir / "jax.py").write_text(
        'raise ImportError("jax deliberately unavailable in this test")\n'
    )
    script = textwrap.dedent(
        """
        import sys
        sys.modules.pop("jax", None)
        import distributed_tensorflow_tpu.observability as obs
        from distributed_tensorflow_tpu.observability import aggregate, tracing
        from distributed_tensorflow_tpu.observability import format as F
        from distributed_tensorflow_tpu.tools import (
            obs_report, perf_record, regression_gate,
        )
        from distributed_tensorflow_tpu.utils import summary
        from distributed_tensorflow_tpu.utils.logging import StepLogger

        try:
            import jax  # noqa: F401
        except ImportError:
            pass
        else:
            raise SystemExit("stub failed: jax imported")

        j = obs.EventJournal.in_dir(%(d)r)
        lines = []
        logger = StepLogger(freq=1, print_fn=lines.append, journal=j)
        logger.log_step_line(step=1, epoch=0, batch=0, batch_count=2,
                             cost=1.5, avg_ms=2.0)
        summary.lifecycle_event("restart", print_fn=lines.append,
                                journal=j, restart=1, max_restarts=2,
                                cause="x=rc=1", backoff_s=0.5)
        r = obs.MetricsRegistry()
        r.counter("c_total").inc()
        r.flush_to(j)
        rec = obs.SpanRecorder(journal=j)
        with rec.span("host_work"):
            pass
        with rec.dispatch("d") as sp:
            sp.fetch(1.0)
        j.close()
        s = obs_report.summarize(obs.read_events(%(d)r))
        assert s["training"]["last_step"] == 1
        assert s["lifecycle"][0]["line"].startswith("Restart: restart=1/2")
        assert s["kinds"]["span"] == 2
        assert lines[0].startswith("Step: 1,")

        # Round 12: tracing + aggregator + exporter + regression gate are
        # all jax-free too (the fleet layer must run on the driver host).
        with tracing.trace("t-nojax"):
            assert obs.NullJournal().emit("x")["trace"] == "t-nojax"
        rj = obs.EventJournal(obs.rank_journal_path(%(d)r, 0), rank=0)
        rj.emit("worker_start", pid=1)
        rj.close()
        merged = aggregate.merge(%(d)r)
        assert set(merged["ranks"]) == {"driver", "rank0"}
        trace = aggregate.gang_chrome_trace(merged)
        assert any(e["name"] == "process_name" for e in trace["traceEvents"])

        import json as _json
        from urllib.request import urlopen
        reg = obs.MetricsRegistry()
        reg.gauge("world_size").set(1)
        with obs.MetricsExporter(reg, health_fn=lambda: {"ok": 1}) as exp:
            body = urlopen(exp.url + "/metrics").read().decode()
            assert "world_size 1" in body
            hz = _json.loads(urlopen(exp.url + "/healthz").read())
            assert hz["status"] == "ok" and hz["ok"] == 1

        gpath = %(d)r + "/gate.jsonl"
        for v in (100.0, 10.0):
            obs.append_event(gpath, "bench_point", tool="t", name="n",
                             value=v, unit="tokens/s")
        assert regression_gate.main(
            ["--journal", gpath, "--bench-root", %(d)r]
        ) == 1  # the injected drop is caught with no jax anywhere
        print("NOJAX-OK")
        """
        % {"d": str(tmp_path)}
    )
    env = dict(os.environ)
    env["PYTHONPATH"] = f"{stub_dir}{os.pathsep}{REPO}"
    env.pop("JAX_PLATFORMS", None)
    out = subprocess.run(
        [sys.executable, "-c", script],
        capture_output=True,
        text=True,
        env=env,
        timeout=120,
    )
    assert out.returncode == 0, out.stderr
    assert "NOJAX-OK" in out.stdout


# ---------------------------------------------------------------------------
# Round 21: breaker lifecycle renderers + fsync-per-append opt-in.
# ---------------------------------------------------------------------------


def test_breaker_lines_byte_identical():
    cases = {
        "breaker_open": (
            {"replica": "r2", "failures": 3, "reason": "2 route timeout(s)",
             "reset_s": 5.0},
            "Breaker: open replica=r2 failures=3 "
            "reason[2 route timeout(s)] reset_s=5.0",
        ),
        "breaker_half_open": (
            {"replica": "r2"},
            "Breaker: half-open replica=r2 — probing one request",
        ),
        "breaker_close": (
            {"replica": "r2"},
            "Breaker: close replica=r2",
        ),
    }
    for kind, (fields, expected) in cases.items():
        ev = obs.NullJournal().emit(kind, **fields)
        assert obs_format.render(kind, ev) == [expected], kind


def test_journal_fsync_opt_in(tmp_path):
    """DTF_JOURNAL_FSYNC=1 arms fsync-per-append (round 21 — closes the
    kill-inside-append durability window for operators who want it);
    default stays OFF and byte-identical."""
    from distributed_tensorflow_tpu.observability.journal import (
        EventJournal,
        configure_from_env,
        read_events,
    )

    p = tmp_path / "events.jsonl"
    j = EventJournal(str(p), fsync=True)
    j.emit("step", value=1)
    j.emit("step", value=2)
    j.close()
    assert [e["value"] for e in read_events(str(p))] == [1, 2]
    assert EventJournal(str(tmp_path / "x.jsonl")).fsync is False

    try:
        env = {"DTF_EVENTS_PATH": str(tmp_path / "armed.jsonl"),
               "DTF_JOURNAL_FSYNC": "1"}
        j2 = configure_from_env(environ=env, announce=False)
        assert j2.fsync is True
        env2 = {"DTF_EVENTS_PATH": str(tmp_path / "plain.jsonl")}
        j3 = configure_from_env(environ=env2, announce=False)
        assert j3.fsync is False
    finally:
        obs.configure()  # back to the NullJournal
