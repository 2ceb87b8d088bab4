"""Fleet-scope observability (round 12) — fast tier.

Five contracts under test:

1. **Tracing**: one trace id joins every journal event of a logical
   operation — per-request through the TextServer lifecycle (both cache
   engines, mid-flight admissions included), ambient per-run through the
   trainers and the elastic gang — and ``obs_report --requests`` rebuilds
   the per-request queue/prefill/decode/TTFT timeline from the journal
   alone, with stdout untouched (the round-10 byte-parity guard keeps
   running unchanged in test_observability.py).
2. **Aggregation**: N ranks' journals merge into one skew-aligned fleet
   timeline; the gang chrome trace has one track per rank with gang
   lifecycle moments visible on all of them. Proven synthetically (known
   injected skew) AND on a real 2-rank launch_local gang with a restart.
3. **Exporter**: ``/metrics`` scraped over live HTTP returns the
   registry's Prometheus text; ``/healthz`` judges via content.
4. **Journal mechanics**: size-based rotation with a segment-spanning
   reader, and whole-line atomicity under N concurrent subprocess
   appenders — including events larger than the 8 KiB stdio buffer that
   would tear on a buffered writer.
5. **Regression gate**: latest-vs-band per (tool, name), direction-aware
   by unit, nonzero naming the culprit on an out-of-band point, zero on
   the committed artifacts.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time
from urllib.request import urlopen

import numpy as np
import pytest

from distributed_tensorflow_tpu import observability as obs
from distributed_tensorflow_tpu.observability import aggregate, tracing
from distributed_tensorflow_tpu.tools import obs_report, regression_gate

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# Tracing primitives.
# ---------------------------------------------------------------------------


def test_trace_ids_unique_and_context_nests():
    ids = {tracing.new_trace_id() for _ in range(64)}
    assert len(ids) == 64
    assert all(len(i) == 16 for i in ids)
    assert tracing.current_trace() is None
    with tracing.trace() as outer:
        assert tracing.current_trace() == outer
        with tracing.trace("inner-id") as inner:
            assert inner == "inner-id"
            assert tracing.current_trace() == "inner-id"
        assert tracing.current_trace() == outer
        # Reuse idiom: trace(current_trace()) keeps the enclosing id.
        with tracing.trace(tracing.current_trace()) as reused:
            assert reused == outer
    assert tracing.current_trace() is None


def test_journal_auto_tags_ambient_trace(tmp_path):
    j = obs.EventJournal.in_dir(str(tmp_path))
    null = obs.NullJournal()
    plain = j.emit("a")
    assert "trace" not in plain
    with tracing.trace("t-123"):
        tagged = j.emit("b")
        explicit = j.emit("c", trace="t-override")
        assert null.emit("d")["trace"] == "t-123"
    j.close()
    assert tagged["trace"] == "t-123"
    assert explicit["trace"] == "t-override"  # explicit beats ambient
    evs = obs.read_events(str(tmp_path))
    assert [e.get("trace") for e in evs] == [None, "t-123", "t-override"]


# ---------------------------------------------------------------------------
# Journal rotation + multi-process append atomicity.
# ---------------------------------------------------------------------------


def test_journal_rotation_spans_segments(tmp_path):
    path = str(tmp_path / "events.jsonl")
    j = obs.EventJournal(path, rotate_bytes=200)
    for i in range(20):
        j.emit("tick", i=i, pad="x" * 40)
    j.close()
    segs = obs.journal_segments(path)
    assert len(segs) > 2 and segs[-1] == path
    # Segment names are .1 (oldest) .. .N, then the active file.
    assert segs[0].endswith(".1")
    evs = obs.read_events(path)
    assert [e["i"] for e in evs] == list(range(20))  # order preserved
    # Every segment stayed under-ish the cap (one event of slack).
    for seg in segs[:-1]:
        assert os.path.getsize(seg) <= 200 + 100
    # A reopened journal keeps rotating into fresh indices.
    j2 = obs.EventJournal(path, rotate_bytes=200)
    for i in range(20, 30):
        j2.emit("tick", i=i, pad="x" * 40)
    j2.close()
    assert [e["i"] for e in obs.read_events(path)] == list(range(30))
    # kind filter + torn tail still behave across segments.
    with open(path, "a") as f:
        f.write('{"kind": "torn')
    assert len(obs.read_events(path, kind="tick")) == 30


def test_journal_rotation_default_off(tmp_path):
    path = str(tmp_path / "events.jsonl")
    j = obs.EventJournal(path)
    for i in range(50):
        j.emit("tick", i=i, pad="x" * 100)
    j.close()
    assert obs.journal_segments(path) == [path]
    with pytest.raises(ValueError):
        obs.EventJournal(path, rotate_bytes=-1)


_WRITER = """
import sys
from distributed_tensorflow_tpu.observability.journal import EventJournal
path, wid, n = sys.argv[1], int(sys.argv[2]), int(sys.argv[3])
j = EventJournal(path, rank=wid)
big = "y" * 9000  # > the 8 KiB stdio buffer: tears on a buffered writer
for i in range(n):
    j.emit("stress", wid=wid, i=i, **({"pad": big} if i % 5 == 0 else {}))
j.close()
"""


def test_concurrent_multiprocess_appenders_never_tear(tmp_path):
    """Satellite: N subprocess writers × one shared O_APPEND journal =
    whole-line interleaving, no merged/corrupt/lost events — including
    >8 KiB lines, which is exactly what the raw-os.write append path
    exists for (a buffered text stream splits those into multiple
    write(2) calls and interleaves torn halves)."""
    path = str(tmp_path / "events.jsonl")
    n_writers, n_events = 4, 60
    procs = [
        subprocess.Popen(
            [sys.executable, "-c", _WRITER, path, str(w), str(n_events)],
            env={**os.environ, "JAX_PLATFORMS": "cpu"},
        )
        for w in range(n_writers)
    ]
    for p in procs:
        assert p.wait(timeout=120) == 0
    evs = obs.read_events(path)  # raises on any mid-file corruption
    assert len(evs) == n_writers * n_events
    seen = {(e["wid"], e["i"]) for e in evs}
    assert len(seen) == n_writers * n_events  # nothing merged or lost
    # Per-writer order is preserved (O_APPEND never reorders one fd).
    for w in range(n_writers):
        order = [e["i"] for e in evs if e["wid"] == w]
        assert order == sorted(order)
    # The big events survived intact.
    bigs = [e for e in evs if "pad" in e]
    assert bigs and all(e["pad"] == "y" * 9000 for e in bigs)


def test_torn_tail_then_reopened_writer(tmp_path):
    """A writer killed mid-append leaves a torn tail; the reader skips it
    and a NEW single-writer journal appends after it cleanly (the torn
    bytes stay as the crash scar — O_APPEND writes whole lines after)."""
    path = str(tmp_path / "events.jsonl")
    j = obs.EventJournal(path)
    j.emit("a")
    j.close()
    with open(path, "a") as f:
        f.write('{"kind": "torn-mid')
    assert [e["kind"] for e in obs.read_events(path)] == ["a"]


# ---------------------------------------------------------------------------
# Prometheus histogram export consistency (satellite).
# ---------------------------------------------------------------------------


def test_histogram_export_matches_raw_observations():
    r = obs.MetricsRegistry()
    h = r.histogram("lat_s", edges=(0.1, 1.0, 10.0))
    observations = [0.05, 0.1, 0.4, 0.9, 5.0, 5.0, 50.0, 0.01]
    for v in observations:
        h.observe(v)
    text = r.prometheus_text()
    lines = dict(
        line.rsplit(" ", 1)
        for line in text.splitlines()
        if not line.startswith("#")
    )
    from distributed_tensorflow_tpu.observability.metrics import _fmt

    # Cumulative bucket counts == raw counting at each edge (le is
    # INCLUSIVE per Prometheus; observe() buckets via bisect_left, i.e.
    # v == edge lands in that edge's bucket). Edge labels use the
    # Prometheus float rendering (1.0 → "1").
    for edge in (0.1, 1.0, 10.0):
        expect = sum(1 for v in observations if v <= edge)
        assert int(lines[f'lat_s_bucket{{le="{_fmt(edge)}"}}']) == expect, edge
    assert int(lines['lat_s_bucket{le="+Inf"}']) == len(observations)
    assert float(lines["lat_s_sum"]) == pytest.approx(sum(observations))
    assert int(lines["lat_s_count"]) == len(observations)
    # Buckets are monotone non-decreasing in edge order.
    cums = [
        int(lines[f'lat_s_bucket{{le="{_fmt(e)}"}}'])
        for e in (0.1, 1.0, 10.0)
    ] + [int(lines['lat_s_bucket{le="+Inf"}'])]
    assert cums == sorted(cums)
    # And the snapshot's per-bucket counts sum to the count.
    snap = r.snapshot()["lat_s"][0]
    assert sum(snap["counts"]) == snap["count"] == len(observations)


def test_histogram_export_labeled_families():
    r = obs.MetricsRegistry()
    for slot, v in (("a", 0.05), ("a", 5.0), ("b", 0.05)):
        r.histogram(
            "lat_s", edges=(0.1, 1.0), labels={"slot": slot}
        ).observe(v)
    text = r.prometheus_text()
    assert 'lat_s_bucket{le="0.1",slot="a"} 1' in text
    assert 'lat_s_bucket{le="+Inf",slot="a"} 2' in text
    assert 'lat_s_count{slot="b"} 1' in text
    assert text.count("# TYPE lat_s histogram") == 1  # one family header


# ---------------------------------------------------------------------------
# Live exporter.
# ---------------------------------------------------------------------------


def test_exporter_serves_metrics_and_healthz():
    r = obs.MetricsRegistry()
    r.counter("ticks_total").inc(3)
    r.gauge("world_size").set(2)
    health = {"world_size": 2, "restarts": 0}
    with obs.MetricsExporter(r, health_fn=lambda: health) as exp:
        port = exp.port
        assert exp.url == f"http://127.0.0.1:{port}"
        text = urlopen(f"http://127.0.0.1:{port}/metrics").read().decode()
        assert "# TYPE ticks_total counter\nticks_total 3" in text
        assert "world_size 2" in text
        r.counter("ticks_total").inc()  # scrape sees live values
        text2 = urlopen(f"http://127.0.0.1:{port}/metrics").read().decode()
        assert "ticks_total 4" in text2
        hz = json.loads(urlopen(f"http://127.0.0.1:{port}/healthz").read())
        assert hz["status"] == "ok" and hz["world_size"] == 2
        assert hz["uptime_s"] >= 0
        with pytest.raises(Exception):  # noqa: B017 — 404 via HTTPError
            urlopen(f"http://127.0.0.1:{port}/nope")
    # Stopped: the port no longer answers.
    with pytest.raises(Exception):  # noqa: B017 — connection refused
        urlopen(f"http://127.0.0.1:{port}/metrics", timeout=0.5)


def test_exporter_health_fn_error_degrades_not_dies():
    r = obs.MetricsRegistry()

    def bad():
        raise RuntimeError("gauge race")

    with obs.MetricsExporter(r, health_fn=bad) as exp:
        hz = json.loads(urlopen(f"{exp.url}/healthz").read())
        assert "gauge race" in hz["error"]
        assert hz["status"] == "ok"  # the PROCESS is up; content judges


# ---------------------------------------------------------------------------
# Gang aggregation (synthetic: known injected skew).
# ---------------------------------------------------------------------------


def _synthetic_gang(tmp_path, skew1=2.5):
    """Driver + two rank journals; rank1's clock runs `skew1` s ahead.
    The restart is the shared anchor (all three record it)."""
    t0 = 1000.0
    restart = dict(restart=1, max_restarts=2, cause="worker1=rc=1",
                   backoff_s=0.5)
    drv = obs.EventJournal.in_dir(str(tmp_path), run_id="drv")
    drv.emit = drv.emit  # noqa: B010 — readability only
    clockless = [
        ("restart", t0 + 5.0, restart),
        ("metrics", t0 + 9.0, {"metrics": {}}),
    ]
    for kind, ts, fields in clockless:
        drv._clock = lambda ts=ts: ts
        drv.emit(kind, **fields)
    drv.close()
    for rank, skew in ((0, 0.0), (1, skew1)):
        j = obs.EventJournal(
            obs.rank_journal_path(str(tmp_path), rank), rank=rank
        )
        for kind, ts, fields in (
            ("worker_start", t0 + 1.0, {"pid": 100 + rank}),
            ("step", t0 + 3.0, dict(step=1, epoch=1, batch=1,
                                    batch_count=2, cost=1.0, avg_ms=2.0)),
            ("restart", t0 + 5.0, restart),  # the shared gang anchor
            ("worker_start", t0 + 6.0, {"pid": 200 + rank}),
            ("span", t0 + 8.0, dict(name="epoch_scan", cat="dispatch",
                                    ts_us=0.0, dur_us=1500.0)),
        ):
            j._clock = lambda ts=ts, skew=skew: ts + skew
            j.emit(kind, **fields)
        j.close()
    return str(tmp_path)


def test_aggregate_discovers_and_corrects_skew(tmp_path):
    logdir = _synthetic_gang(tmp_path, skew1=2.5)
    paths = aggregate.discover_journals(logdir)
    assert set(paths) == {"driver", "rank0", "rank1"}
    merged = aggregate.merge(logdir)
    assert merged["ranks"] == ["driver", "rank0", "rank1"]
    # rank1's 2.5 s clock skew is estimated from the shared restart
    # anchor and subtracted: its events land back on the fleet clock.
    assert merged["skew_s"]["rank1"] == pytest.approx(2.5)
    assert merged["skew_s"]["rank0"] == 0.0
    r1 = [e for e in merged["events"] if e["_src"] == "rank1"]
    r0 = [e for e in merged["events"] if e["_src"] == "rank0"]
    for a, b in zip(r0, r1):
        assert a["kind"] == b["kind"]
        assert a["ts"] == pytest.approx(b["ts"], abs=1e-6)
    # Merged stream is time-sorted.
    ts = [e["ts"] for e in merged["events"]]
    assert ts == sorted(ts)


def test_gang_chrome_trace_tracks_and_mirrored_restart(tmp_path):
    merged = aggregate.merge(_synthetic_gang(tmp_path))
    trace = aggregate.gang_chrome_trace(merged)
    evs = trace["traceEvents"]
    names = {
        e["args"]["name"] for e in evs if e["name"] == "process_name"
    }
    assert names == {"driver", "rank0", "rank1"}
    # The restart instant is visible on EVERY track (driver recorded it
    # once; ranks recorded their own) — 3 tracks × 3 recordings = 9.
    restarts = [e for e in evs if e["name"] == "restart"]
    assert {e["pid"] for e in restarts} == {0, 1, 2}
    assert all(e["ph"] == "i" for e in restarts)
    # Rank spans are wall-anchored complete events on their own track.
    spans = [e for e in evs if e["ph"] == "X"]
    assert {e["pid"] for e in spans} == {1, 2}
    for s in spans:
        assert s["dur"] == 1500.0 and s["ts"] >= 0
    # worker_start incarnations: two per rank, none on the driver.
    ws = [e for e in evs if e["name"] == "worker_start"]
    assert {e["pid"] for e in ws} == {1, 2} and len(ws) == 4
    summary = aggregate.fleet_summary(merged)
    assert summary["worker_starts"] == {"driver": 0, "rank0": 2, "rank1": 2}
    assert any("Restart: restart=1/2" in h["line"]
               for h in summary["lifecycle"])


# ---------------------------------------------------------------------------
# Real 2-rank launch_local gang: per-rank journals → --gang → chrome trace.
# ---------------------------------------------------------------------------

_GANG_WORKER = """
import os, sys, time
import distributed_tensorflow_tpu.observability as obs
j = obs.configure_from_env()           # DTF_JOURNAL_DIR/DTF_RANK from driver
rank = os.environ["DTF_RANK"]
j.emit("step", step=1, epoch=1, batch=1, batch_count=2, cost=1.0, avg_ms=2.0)
marker = os.path.join(os.environ["DTF_JOURNAL_DIR"], "fail_once")
if rank == "0" and not os.path.exists(marker):
    open(marker, "w").close()
    # Die only once rank 1 has announced itself: the driver kills the gang
    # at this exit, and on a loaded machine an incarnation killed before
    # its first event never reaches its journal.
    other = obs.rank_journal_path(os.environ["DTF_JOURNAL_DIR"], 1)
    until = time.time() + 60
    while time.time() < until and not (
            os.path.exists(other) and os.path.getsize(other)):
        time.sleep(0.01)
    j.close()
    sys.exit(3)                         # first incarnation dies -> restart
j.emit("step", step=2, epoch=1, batch=2, batch_count=2, cost=0.5, avg_ms=2.0)
j.close()
"""


def test_launch_local_gang_journals_merge_with_restart(tmp_path):
    """Acceptance: a real 2-rank elastic launch writes per-rank journals;
    ``obs_report --gang`` merges them and exports a valid chrome trace
    with per-rank tracks showing the restart on both ranks."""
    from distributed_tensorflow_tpu.tools.launch_local import launch

    lines = []
    rc = launch(
        [sys.executable, "-c", _GANG_WORKER],
        num_workers=2,
        logdir=str(tmp_path),
        max_restarts=2,
        backoff=0.05,
        poll_interval=0.05,
        print_fn=lines.append,
    )
    assert rc == 0
    assert any("Restart: restart=1/2" in str(ln) for ln in lines)
    for rank in (0, 1):
        path = obs.rank_journal_path(str(tmp_path), rank)
        assert os.path.exists(path)
        evs = obs.read_events(path)
        # Two incarnations announced themselves; the run id ties them to
        # the driver's journal.
        assert sum(e["kind"] == "worker_start" for e in evs) == 2
        assert all(e["run"].startswith("elastic-") for e in evs)
        assert all(e["rank"] == rank for e in evs)
    merged = aggregate.merge(str(tmp_path))
    assert merged["ranks"] == ["driver", "rank0", "rank1"]
    summary = aggregate.fleet_summary(merged)
    assert summary["worker_starts"]["rank0"] == 2
    assert any(h["kind"] == "restart" for h in summary["lifecycle"])
    # CLI: --gang report + trace export.
    trace_out = str(tmp_path / "gang_trace.json")
    assert obs_report.main([str(tmp_path), "--gang", "--trace", trace_out]) == 0
    with open(trace_out) as f:
        trace = json.load(f)
    evs = trace["traceEvents"]
    names = {e["args"]["name"] for e in evs if e["name"] == "process_name"}
    assert names == {"driver", "rank0", "rank1"}
    rank_pids = {
        e["pid"]
        for e in evs
        if e["name"] == "process_name" and e["args"]["name"] != "driver"
    }
    restart_pids = {e["pid"] for e in evs if e["name"] == "restart"}
    assert rank_pids <= restart_pids  # the restart shows on BOTH ranks
    for e in evs:
        assert isinstance(e["pid"], int) and "ph" in e


def test_gang_heartbeats_summarize_as_last_progress(tmp_path):
    """Round 22 (progress watchdog): per-rank heartbeat events become a
    last_progress {step, age_s} summary (age vs the merged timeline's
    newest event), stay OUT of the lifecycle history and OUT of the skew
    anchors, and render on the --gang report's per-rank lines."""
    t0 = 1000.0
    restart = dict(restart=1, max_restarts=2, cause="worker1=rc=1",
                   backoff_s=0.5)
    drv = obs.EventJournal.in_dir(str(tmp_path), run_id="drv")
    drv._clock = lambda: t0 + 20.0
    drv.emit("restart", **restart)
    drv.close()
    # Both ranks beat at step 5 at DIFFERENT wall times: if heartbeats
    # were skew anchors, the 6 s delta would be misread as clock skew.
    for rank, beats in ((0, ((t0 + 4.0, 3), (t0 + 10.0, 5))),
                        (1, ((t0 + 16.0, 5),))):
        j = obs.EventJournal(
            obs.rank_journal_path(str(tmp_path), rank), rank=rank
        )
        for ts, step in beats:
            j._clock = lambda ts=ts: ts
            j.emit("heartbeat", rank=rank, step=step)
        j._clock = lambda: t0 + 20.0
        j.emit("restart", **restart)  # the real shared anchor
        j.close()
    merged = aggregate.merge(str(tmp_path))
    assert merged["skew_s"]["rank0"] == 0.0
    assert merged["skew_s"]["rank1"] == 0.0
    summary = aggregate.fleet_summary(merged)
    # Newest merged ts is the restart at t0+20.
    assert summary["ranks"]["rank0"]["last_progress"] == {
        "step": 5, "age_s": pytest.approx(10.0)
    }
    assert summary["ranks"]["rank1"]["last_progress"] == {
        "step": 5, "age_s": pytest.approx(4.0)
    }
    assert "last_progress" not in summary["ranks"]["driver"]
    # Beats never flood the lifecycle history.
    assert all(h["kind"] != "heartbeat" for h in summary["lifecycle"])
    rendered = obs_report.render_gang(summary)
    assert "rank0: " in rendered
    assert "last progress step 5 (10.0s ago)" in rendered
    assert "last progress step 5 (4.0s ago)" in rendered


def test_launch_local_metrics_port_scrapes_live_gang(tmp_path):
    """Acceptance: /metrics over HTTP DURING a live gang run returns
    Prometheus text (world_size gauge et al.)."""
    import socket
    import threading

    from distributed_tensorflow_tpu.tools.launch_local import launch

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    # The gang lives until the scrape has succeeded: each worker waits for
    # a file this test writes (60 s at most), and the scrape goes on for
    # as long as the launcher runs. A gang alive for a fixed 4 s scraped
    # by 80 fixed tries raced the machine: under load the driver bound
    # its port after the last try.
    release = tmp_path / "scraped"
    worker = (
        "import os, sys, time\n"
        "end = time.time() + 60\n"
        "while not os.path.exists(sys.argv[1]) and time.time() < end:\n"
        "    time.sleep(0.05)\n"
    )
    result = {}

    def _run():
        result["rc"] = launch(
            [sys.executable, "-c", worker, str(release)],
            num_workers=2,
            logdir=str(tmp_path),
            max_restarts=1,
            poll_interval=0.05,
            metrics_port=port,
            print_fn=lambda *a: None,
        )

    t = threading.Thread(target=_run)
    t.start()
    try:
        text, hz = None, None
        while t.is_alive():
            try:
                text = urlopen(
                    f"http://127.0.0.1:{port}/metrics", timeout=1
                ).read().decode()
                hz = json.loads(
                    urlopen(
                        f"http://127.0.0.1:{port}/healthz", timeout=1
                    ).read()
                )
                break
            except Exception:  # noqa: BLE001 — not bound yet
                time.sleep(0.05)
    finally:
        release.write_text("")
        t.join(timeout=90)
    assert result["rc"] == 0
    assert text is not None, "never scraped the live driver"
    assert "# TYPE world_size gauge" in text and "world_size 2" in text
    assert hz["world_size"] == 2 and hz["restarts"] == 0


# ---------------------------------------------------------------------------
# Per-request tracing through the TextServer (slab + paged engines).
# ---------------------------------------------------------------------------


def _serve_model():
    from distributed_tensorflow_tpu.models.gpt import GPTLM

    model = GPTLM(
        vocab_size=64, max_len=64, model_dim=32, num_heads=2, num_layers=1
    )
    return model, model.init(seed=0)


@pytest.mark.parametrize("paged", [False, True], ids=["slab", "paged"])
def test_text_server_request_traces_reconstruct(tmp_path, paged):
    from distributed_tensorflow_tpu.serve import GenerationConfig, TextServer

    model, params = _serve_model()
    j = obs.EventJournal.in_dir(str(tmp_path))
    kw = dict(paged=True, block_size=4) if paged else {}
    srv = TextServer(
        model, params, slots=2, buckets=(16,), chunk=4, journal=j, **kw
    )
    # 3 requests through 2 slots: the third is a MID-FLIGHT admission
    # (enters after a completion frees a slot).
    prompts = [np.arange(1, 6, dtype=np.int32)] * 3
    outs = srv.generate(prompts, GenerationConfig(max_new=6))
    j.close()
    assert all(len(o) == 6 for o in outs)
    events = obs.read_events(str(tmp_path))

    submits = [e for e in events if e["kind"] == "request_submit"]
    assert [e["rid"] for e in submits] == [0, 1, 2]
    traces = {e["rid"]: e["trace"] for e in submits}
    assert len(set(traces.values())) == 3  # unique per request
    # Admission + completion carry the SAME trace id as the submit.
    for kind in ("admission", "completion"):
        for e in (x for x in events if x["kind"] == kind):
            assert e["trace"] == traces[e["rid"]], (kind, e["rid"])
    # Every dispatch span names its resident requests.
    spans = [e for e in events if e["kind"] == "span"]
    prefills = [s for s in spans if s["name"] == "prefill"]
    assert {rid for s in prefills for rid in s["args"]["rids"]} == {0, 1, 2}
    decodes = [s for s in spans if s["name"] == "decode_chunk"]
    assert decodes and all(s["args"]["rids"] for s in decodes)

    # The reconstruction: full queue→prefill→decode→completion timeline
    # per request, from the journal alone.
    recs = obs_report.reconstruct_requests(events)
    assert [r["rid"] for r in recs] == [0, 1, 2]
    for r in recs:
        assert r["done"] and r["trace"] == traces[r["rid"]]
        assert r["prompt_len"] == 5 and r["max_new"] == 6
        assert r["queue_wait_s"] >= 0 and r["prefill_ms"] > 0
        assert r["decode_chunks"] >= 1 and r["decode_ms"] > 0
        assert r["latency_s"] >= r["ttft_s"] > 0
        assert r["tokens"] == 6
    # The mid-flight admission waited for a slot: its queue wait spans
    # the first generation round.
    assert recs[2]["queue_wait_s"] > recs[0]["queue_wait_s"]
    pct = obs_report.request_percentiles(recs)
    assert pct["requests"] == 3
    assert pct["latency_s"]["p99"] >= pct["latency_s"]["p50"] > 0
    rendered = obs_report.render_requests(recs)
    assert "TTFT p50/p95/p99" in rendered


def test_obs_report_requests_cli(tmp_path, capsys):
    from distributed_tensorflow_tpu.serve import GenerationConfig, TextServer

    model, params = _serve_model()
    j = obs.EventJournal.in_dir(str(tmp_path))
    srv = TextServer(model, params, slots=2, buckets=(16,), chunk=4, journal=j)
    srv.generate(
        [np.arange(1, 6, dtype=np.int32)] * 2, GenerationConfig(max_new=4)
    )
    j.close()
    assert obs_report.main([str(tmp_path), "--requests", "--json"]) == 0
    records = json.loads(capsys.readouterr().out)
    assert len(records) == 2 and all(r["done"] for r in records)


def test_text_server_metrics_port_serves_live_gauges():
    """Acceptance: serving gauges over live HTTP during a run."""
    import socket

    from distributed_tensorflow_tpu.serve import GenerationConfig, TextServer

    model, params = _serve_model()
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    srv = TextServer(
        model, params, slots=2, buckets=(16,), chunk=4, metrics_port=port
    )
    try:
        rid = srv.submit(
            np.arange(1, 6, dtype=np.int32), GenerationConfig(max_new=8)
        )
        srv.step()  # mid-run: the request is resident
        text = urlopen(f"http://127.0.0.1:{port}/metrics").read().decode()
        assert "# TYPE slots_busy gauge" in text
        assert "requests_submitted_total 1" in text
        assert "ttft_s_bucket" in text
        hz = json.loads(urlopen(f"http://127.0.0.1:{port}/healthz").read())
        assert hz["slots"] == 2 and hz["heartbeat_age_s"] < 60
        while srv.step():
            pass
        assert len(srv.result(rid)) == 8
    finally:
        srv.shutdown()
    with pytest.raises(Exception):  # noqa: B017 — exporter stopped
        urlopen(f"http://127.0.0.1:{port}/metrics", timeout=0.5)


def test_prefix_cache_eviction_journals(tmp_path):
    from distributed_tensorflow_tpu.serve_pool import (
        BlockAllocator,
        PrefixCache,
    )

    class _Collect:
        def __init__(self):
            self.events = []

        def emit(self, kind, **fields):
            self.events.append({"kind": kind, **fields})

    sink = _Collect()
    alloc = BlockAllocator(4)
    cache = PrefixCache(alloc, 2, journal=sink)
    bids = alloc.alloc(2)
    cache.insert([1, 2, 3, 4], bids, 2)
    for b in bids:
        alloc.release(b)  # the request completed; cache holds the refs
    assert cache.evict(1) == 1
    (ev,) = sink.events
    assert ev["kind"] == "prefix_evict" and ev["freed_blocks"] == 1
    assert ev["cached_blocks"] == 1  # one block remains registered
    assert cache.evict(0) == 0 and len(sink.events) == 1  # no-op is silent


# ---------------------------------------------------------------------------
# Ambient traces: trainer runs and the elastic gang.
# ---------------------------------------------------------------------------


def test_trainer_run_events_share_one_trace(small_datasets, tmp_path):
    from distributed_tensorflow_tpu.config import TrainConfig
    from distributed_tensorflow_tpu.models import MLP
    from distributed_tensorflow_tpu.train.trainer import Trainer

    j = obs.EventJournal.in_dir(str(tmp_path))
    tr = Trainer(
        MLP(),
        small_datasets,
        TrainConfig(epochs=1, log_frequency=20),
        print_fn=lambda *a: None,
        journal=j,
    )
    tr.run()
    tr.run()  # a second run gets its OWN trace
    j.close()
    events = obs.read_events(str(tmp_path))
    traces = {e.get("trace") for e in events}
    assert None not in traces, [
        e["kind"] for e in events if e.get("trace") is None
    ]
    assert len(traces) == 2  # one id per run, spanning steps+epochs+spans
    first = events[0]["trace"]
    run1 = [e for e in events if e["trace"] == first]
    # (The eager CPU path records no dispatch spans; the scanned path
    # adds "span" kinds to the same trace.)
    assert {"step", "epoch", "final", "metrics"} <= {
        e["kind"] for e in run1
    }


def test_elastic_gang_run_events_share_one_trace(tmp_path):
    from distributed_tensorflow_tpu.train.elastic import (
        ElasticAgent,
        ElasticGang,
    )

    class _Proc:
        def __init__(self, codes):
            self.codes = list(codes)

        def poll(self):
            return self.codes.pop(0) if len(self.codes) > 1 else self.codes[0]

        def kill(self):
            pass

        def wait(self, timeout=None):
            return -9

    j = obs.EventJournal.in_dir(str(tmp_path))
    scripts = iter([[None, 1], [None, 0]])
    gang = ElasticGang(
        [ElasticAgent("worker0", lambda: _Proc(next(scripts)))],
        max_restarts=1,
        jitter=0.0,
        sleep=lambda s: None,
        print_fn=lambda *a: None,
        journal=j,
    )
    assert gang.run() == 0
    j.close()
    events = obs.read_events(str(tmp_path))
    assert {e["kind"] for e in events} == {"restart", "metrics"}
    assert len({e["trace"] for e in events}) == 1


# ---------------------------------------------------------------------------
# Regression gate.
# ---------------------------------------------------------------------------


def test_gate_band_logic_directions():
    mk = lambda vals, unit: [  # noqa: E731
        (i, v, unit) for i, v in enumerate(vals)
    ]
    # Higher-is-better: only a drop below min·(1−tol) fails.
    res = regression_gate.check_series(
        {("t", "up"): mk([100.0, 120.0, 40.0], "tokens/s")}, tolerance=0.5
    )
    assert [f["name"] for f in res["failures"]] == ["up"]
    assert res["failures"][0]["direction"] == "below"
    ok = regression_gate.check_series(
        {("t", "up"): mk([100.0, 120.0, 51.0], "tokens/s")}, tolerance=0.5
    )
    assert not ok["failures"]
    # An improvement above the band never fails.
    assert not regression_gate.check_series(
        {("t", "up"): mk([100.0, 120.0, 500.0], "tokens/s")}, tolerance=0.5
    )["failures"]
    # Lower-is-better (ms): only a rise above max·(1+tol) fails.
    res = regression_gate.check_series(
        {("t", "lat"): mk([2.0, 2.5, 4.0], "ms")}, tolerance=0.5
    )
    assert res["failures"][0]["direction"] == "above"
    assert not regression_gate.check_series(
        {("t", "lat"): mk([2.0, 2.5, 0.1], "ms")}, tolerance=0.5
    )["failures"]
    # Single point: skipped, never failed.
    res = regression_gate.check_series(
        {("t", "solo"): mk([1.0], "x")}, tolerance=0.5
    )
    assert res["checked"] == 0 and res["skipped"][0]["name"] == "solo"


def test_gate_bytes_units_fail_high():
    # Round 17: comm payloads ("bytes", "bytes/token") are
    # lower-is-better like ms/s — traffic creeping back UP past the
    # compressed record is the regression; a further reduction never is.
    mk = lambda vals, unit: [  # noqa: E731
        (i, v, unit) for i, v in enumerate(vals)
    ]
    res = regression_gate.check_series(
        {("diloco_bench", "comm_bytes_per_token"): mk(
            [3.4, 3.4, 13.5], "bytes/token"
        )},
        tolerance=0.5,
    )
    [f] = res["failures"]
    assert f["direction"] == "above" and f["unit"] == "bytes/token"
    assert not regression_gate.check_series(
        {("diloco_bench", "comm_bytes_per_token"): mk(
            [3.4, 3.4, 0.9], "bytes/token"
        )},
        tolerance=0.5,
    )["failures"]
    res = regression_gate.check_series(
        {("t", "payload"): mk([100.0, 100.0, 400.0], "bytes")},
        tolerance=0.5,
    )
    assert res["failures"][0]["direction"] == "above"


def test_gate_microsecond_units_fail_high():
    # Round 18 unit-direction fix: before "us"/"µs" entered
    # LOWER_IS_BETTER_UNITS, a microsecond latency series (serve_bench's
    # decode_us_per_token) gated FAIL-LOW — it would have flagged an
    # improvement and waved a latency regression straight through.
    mk = lambda vals, unit: [  # noqa: E731
        (i, v, unit) for i, v in enumerate(vals)
    ]
    for unit in ("us", "µs", "us/token", "µs/token"):
        assert unit in regression_gate.LOWER_IS_BETTER_UNITS
        # Latency going UP past the band fails...
        res = regression_gate.check_series(
            {("serve_bench", "decode_us_per_token"): mk(
                [300.0, 310.0, 900.0], unit
            )},
            tolerance=0.5,
        )
        [f] = res["failures"]
        assert f["direction"] == "above" and f["unit"] == unit
        # ...and a large improvement (the old silent-fail-LOW case)
        # never does.
        assert not regression_gate.check_series(
            {("serve_bench", "decode_us_per_token"): mk(
                [300.0, 310.0, 40.0], unit
            )},
            tolerance=0.5,
        )["failures"]


def test_gate_dispatch_unit_fails_high():
    # A launch count per token ("dispatches/token"; the gate keeps the
    # unit, no committed series carries it since PR 30) is
    # lower-is-better: MORE launches is the regression and a fusion
    # improvement must never trip the gate.
    mk = lambda vals, unit: [  # noqa: E731
        (i, v, unit) for i, v in enumerate(vals)
    ]
    assert "dispatches/token" in regression_gate.LOWER_IS_BETTER_UNITS
    res = regression_gate.check_series(
        {("serve_bench", "decode_dispatches_per_token_a"): mk(
            [2.0, 2.0, 11.0], "dispatches/token"
        )},
        tolerance=0.5,
    )
    [f] = res["failures"]
    assert f["direction"] == "above" and f["unit"] == "dispatches/token"
    assert not regression_gate.check_series(
        {("serve_bench", "decode_dispatches_per_token_b"): mk(
            [9.0, 9.0, 2.0], "dispatches/token"
        )},
        tolerance=0.5,
    )["failures"]


def test_obs_report_comm_payload_rendering():
    # Round 17: bytes/round + effective compression beside the
    # steps-per-round line; full-precision segments render exactly the
    # round-14 surface (no payload line).
    events = [
        {
            "kind": "comm_stats", "epoch": e, "mode": "diloco",
            "steps": 10, "sync_every": 4, "sync_rounds": r,
            "allreduce_bytes": r * 1000, "payload_bytes": r * 250,
            "delta_dtype": "int8", "overlap": False, "workers": 4,
        }
        for e, r in ((0, 2), (1, 3))
    ] + [
        {
            "kind": "comm_stats", "epoch": 2, "mode": "dp", "steps": 10,
            "sync_every": 1, "sync_rounds": 10,
            "allreduce_bytes": 10_000, "workers": 4,
        }
    ]
    summary = obs_report.summarize(events)
    segs = {s["mode"]: s for s in summary["comm"]}
    assert segs["diloco"]["payload_bytes"] == 1250
    assert segs["diloco"]["bytes_per_round"] == 250.0
    assert segs["diloco"]["compression_x"] == 4.0
    # Pre-round-17 journals: payload defaults to the dense all-reduce.
    assert segs["dp"]["payload_bytes"] == 10_000
    assert segs["dp"]["compression_x"] == 1.0
    report = obs_report.render_report(summary)
    assert (
        "comm payload: int8 deltas — 1250 bytes on the wire "
        "(250.0 bytes/round, 4.0x compressed)" in report
    )
    # The dp segment renders only the round-14 line.
    assert report.count("comm payload:") == 1


def test_gate_fails_on_injected_out_of_band_point(tmp_path, capsys):
    """Acceptance: nonzero exit naming the offending (tool, metric)."""
    path = str(tmp_path / "events.jsonl")
    for v in (1700.0, 1750.0):
        obs.append_event(
            path, "bench_point", tool="serve_bench",
            name="batched_tokens_per_s", value=v, unit="tokens/s",
        )
    empty = str(tmp_path / "bench")  # no BENCH_r*.json here
    os.makedirs(empty)
    assert regression_gate.main(
        ["--journal", path, "--bench-root", empty]
    ) == 0
    # The regression lands: 100 tokens/s against a [1700, 1750] band.
    obs.append_event(
        path, "bench_point", tool="serve_bench",
        name="batched_tokens_per_s", value=100.0, unit="tokens/s",
    )
    capsys.readouterr()
    rc = regression_gate.main(["--journal", path, "--bench-root", empty])
    out = capsys.readouterr().out
    assert rc == 1
    assert "REGRESSION serve_bench/batched_tokens_per_s" in out
    assert "100.0" in out


def test_gate_series_split_by_device(tmp_path):
    """Device is part of a journal series' identity: the first chip
    rerun of a CPU-recorded metric starts a FRESH series (skipped — no
    prior points), it does not collide with the CPU band; a later
    same-device regression is still caught within its own series."""
    path = str(tmp_path / "events.jsonl")
    for v in (2000.0, 2100.0):
        obs.append_event(
            path, "bench_point", tool="serve_bench",
            name="batched_tokens_per_s", value=v, unit="tokens/s",
            device="cpu",
        )
    # ~50x the CPU value — a legitimate chip measurement, not a drop.
    obs.append_event(
        path, "bench_point", tool="serve_bench",
        name="batched_tokens_per_s", value=100000.0, unit="tokens/s",
        device="TPU v5 lite",
    )
    series = regression_gate.journal_series(path)
    assert set(series) == {
        ("serve_bench", "batched_tokens_per_s", "cpu"),
        ("serve_bench", "batched_tokens_per_s", "TPU v5 lite"),
    }
    res = regression_gate.check_series(series, tolerance=0.5)
    assert res["failures"] == []
    assert any(s.get("device") == "TPU v5 lite" for s in res["skipped"])
    # Within the TPU series, a real drop fails and names the device.
    obs.append_event(
        path, "bench_point", tool="serve_bench",
        name="batched_tokens_per_s", value=90000.0, unit="tokens/s",
        device="TPU v5 lite",
    )
    obs.append_event(
        path, "bench_point", tool="serve_bench",
        name="batched_tokens_per_s", value=1000.0, unit="tokens/s",
        device="TPU v5 lite",
    )
    res = regression_gate.check_series(
        regression_gate.journal_series(path), tolerance=0.5
    )
    [f] = res["failures"]
    assert f["device"] == "TPU v5 lite" and f["direction"] == "below"
    # The CPU band is untouched by the chip's history.
    assert not any(
        f2.get("device") == "cpu" for f2 in res["failures"]
    )


def _write_bench(root, n, value):
    with open(os.path.join(root, f"BENCH_r{n:02d}.json"), "w") as f:
        json.dump(
            {"rc": 0, "parsed": {
                "metric": "mnist_mlp_train_examples_per_sec_per_chip",
                "value": value, "unit": "examples/sec/chip",
                "vs_baseline": value / 42000.0, "impl": "pallas-epoch",
            }}, f,
        )


def test_gate_passes_on_committed_artifacts(tmp_path):
    """Satellite (CI wiring): the gate over the repo's committed journal
    plus a BENCH trajectory must exit 0, and an artifact landing outside
    the recorded band fails instead of silently re-anchoring the record.
    The driver's BENCH_r*.json files no longer live in the repo (PR 22:
    their logs named an installation that is gone), so the trajectory
    half is a fixture under tmp_path."""
    root = str(tmp_path)
    for n, value in enumerate((9.0e6, 9.5e6, 1.0e7), start=1):
        _write_bench(root, n, value)
    assert len(regression_gate.bench_series(root)) == 1
    journal = regression_gate.default_journal()
    assert os.path.exists(journal)
    result = regression_gate.gate(journal=journal, bench_root=root)
    assert result["failures"] == [], result["failures"]
    _write_bench(root, 4, 1.0e6)  # a tenth of the band's floor
    [failure] = regression_gate.gate(journal=journal, bench_root=root)[
        "failures"
    ]
    assert failure["tool"] == "driver" and failure["direction"] == "below"


def test_gate_skips_cleanly_with_no_artifacts(tmp_path, capsys):
    empty = str(tmp_path / "nothing")
    os.makedirs(empty)
    rc = regression_gate.main(
        ["--journal", str(tmp_path / "missing.jsonl"), "--bench-root", empty]
    )
    assert rc == 0
    assert "0 series checked" in capsys.readouterr().out


# ---------------------------------------------------------------------------
# serve_bench percentile rows (render + journal emission, offline).
# ---------------------------------------------------------------------------


def test_serve_bench_percentile_rows_render_and_emit(tmp_path):
    from distributed_tensorflow_tpu.tools import perf_record, serve_bench

    payload = {
        "device": "cpu",
        "model": {"vocab": 512, "model_dim": 128, "num_layers": 2,
                  "max_len": 256},
        "workload": {"requests": 24, "max_new": 96, "total_tokens": 2304},
        "batched": {"tokens_per_s": 100.0, "slots": 8, "chunk": 32,
                    "wall_s": 1.0},
        "sequential": {"tokens_per_s": 50.0, "slots": 1, "chunk": 32,
                       "wall_s": 2.0},
        "batched_speedup": 2.0,
        "chunk_sweep": [{"chunk": 1, "wall_s": 1.0, "per_token_ms": 5.0}],
        "chunk_speedup": 6.6,
        "dispatch_fixed_ms": 2.4,
        "marginal_token_ms": 0.34,
        "per_request_ms": 1.0,
        "request_percentiles": {
            "slots": 8, "chunk": 32, "requests": 24,
            "ttft_s": {"p50": 0.1, "p95": 0.4, "p99": 0.6},
            "latency_s": {"p50": 0.5, "p95": 0.9, "p99": 1.2},
        },
    }
    md = serve_bench.render(payload)
    assert "Per-request latency percentiles" in md
    assert "| p95 | 0.4 | 0.9 |" in md
    path = str(tmp_path / "events.jsonl")
    evs = serve_bench.emit_bench_events(payload, path)
    names = {e["name"] for e in evs}
    assert {"ttft_p95_s", "latency_p95_s"} <= names
    points = {p["name"]: p for p in perf_record.journal_points(path)}
    assert points["ttft_p95_s"]["value"] == 0.4
    assert points["latency_p95_s"]["unit"] == "s"


def test_serve_bench_request_percentiles_measures(tmp_path):
    """The measuring half on a tiny model: real journal, real
    reconstruction, sane ordering."""
    from distributed_tensorflow_tpu.tools import serve_bench

    model, params = _serve_model()
    pct = serve_bench.bench_request_percentiles(
        model, params, n_requests=3, max_new=4, slots=2, chunk=4
    )
    assert pct["requests"] == 3
    assert pct["ttft_s"]["p50"] > 0
    assert pct["latency_s"]["p99"] >= pct["latency_s"]["p50"]


def test_lm_phase_bench_events_feed_the_gate(tmp_path):
    # The round-13 phase series (step / backward / backward-selective)
    # must ride the same bench_point → regression-gate path as
    # serve_bench's: two emissions form a band, and a blown-up ms point
    # fails HIGH (lower-is-better unit).
    from distributed_tensorflow_tpu.tools import lm_phase_bench, regression_gate

    row = {
        "config": "x",
        "device": "cpu",
        "phase_ms": {"step": 10.0, "backward": 5.0, "backward-selective": 4.0},
    }
    path = str(tmp_path / "events.jsonl")
    lm_phase_bench.emit_bench_events([row], path)
    row["phase_ms"]["backward-selective"] = 4.1
    lm_phase_bench.emit_bench_events([row], path)
    series = regression_gate.journal_series(path)
    key = ("lm_phase_bench", "x/backward_selective_ms", "cpu")
    assert key in series and len(series[key]) == 2
    res = regression_gate.check_series(series)
    assert not res["failures"]
    row["phase_ms"]["backward-selective"] = 40.0
    lm_phase_bench.emit_bench_events([row], path)
    res = regression_gate.check_series(regression_gate.journal_series(path))
    assert any(
        f["name"] == "x/backward_selective_ms" and f["direction"] == "above"
        for f in res["failures"]
    )


# ---------------------------------------------------------------------------
# Round 21: shed_rate gate direction, load_gen scenarios, per-class rollup.
# ---------------------------------------------------------------------------


def test_gate_shed_rate_unit_fails_high():
    # Round 21: the per-class shed fraction under the fixed overload
    # scenario is lower-is-better — MORE shedding at the same offered
    # load is the regression; a scheduler improvement (less shedding)
    # must never trip the gate.
    mk = lambda vals, unit: [  # noqa: E731
        (i, v, unit) for i, v in enumerate(vals)
    ]
    assert "shed_rate" in regression_gate.LOWER_IS_BETTER_UNITS
    res = regression_gate.check_series(
        {("serve_bench", "shed_rate_p0"): mk(
            [0.5, 0.6, 0.99], "shed_rate"
        )},
        tolerance=0.5,
    )
    [f] = res["failures"]
    assert f["direction"] == "above" and f["unit"] == "shed_rate"
    assert not regression_gate.check_series(
        {("serve_bench", "shed_rate_p0"): mk(
            [0.9, 0.8, 0.1], "shed_rate"
        )},
        tolerance=0.5,
    )["failures"]


def test_load_gen_scenarios_deterministic_and_shaped():
    from distributed_tensorflow_tpu.tools import load_gen

    for name in sorted(load_gen.SCENARIOS):
        a = load_gen.generate(name, seed=7, n=24)
        b = load_gen.generate(name, seed=7, n=24)
        assert [r.to_dict() for r in a] == [r.to_dict() for r in b], name
        c = load_gen.generate(name, seed=8, n=24)
        assert [r.to_dict() for r in a] != [r.to_dict() for r in c], name
        assert all(r.at_s >= 0 and r.tokens for r in a)
        assert [r.at_s for r in a] == sorted(r.at_s for r in a), name
    # Scenario shapes: the properties each one exists to exercise.
    mix = load_gen.generate("priority_mix", seed=7, n=64)
    assert {r.priority for r in mix} == {0, 1, 2}
    assert all(r.deadline_s is not None for r in mix if r.priority > 0)
    assert all(r.deadline_s is None for r in mix if r.priority == 0)
    samp = load_gen.generate("mixed_sampling", seed=7, n=64)
    assert any(not r.greedy for r in samp) and any(r.greedy for r in samp)
    assert len({r.seed for r in samp if not r.greedy}) > 1
    pre = load_gen.generate("long_prefill", seed=7, n=24)
    chat = load_gen.generate("chat", seed=7, n=24)
    assert min(len(r.tokens) for r in pre) > max(len(r.tokens) for r in chat)
    assert min(r.max_new for r in chat) > max(r.max_new for r in pre)


def test_load_gen_summarize_both_event_vocabularies():
    """One summarize over both journal dialects: TextServer
    (admission/completion/request_shed) and router
    (request_route/fleet_result)."""
    from distributed_tensorflow_tpu.tools import load_gen

    server_events = [
        {"kind": "request_submit", "ts": 0.0, "rid": 0, "priority": 2},
        {"kind": "request_submit", "ts": 0.0, "rid": 1},
        {"kind": "admission", "ts": 0.5, "rid": 0},
        {"kind": "completion", "ts": 1.0, "rid": 0},
        {"kind": "request_shed", "ts": 0.2, "rid": 1, "priority": 0,
         "reason": "preempted"},
    ]
    s = load_gen.summarize(server_events)
    assert s["classes"][2]["done"] == 1
    assert s["classes"][2]["ttft_s"]["p50"] == 0.5
    assert s["classes"][2]["latency_s"]["p50"] == 1.0
    assert s["classes"][0]["shed"] == 1
    assert s["classes"][0]["shed_rate"] == 1.0
    assert s["shed_rate"] == 0.5

    router_events = [
        {"kind": "request_submit", "ts": 0.0, "rid": 0, "priority": 1},
        {"kind": "request_route", "ts": 0.25, "rid": 0},
        {"kind": "fleet_result", "ts": 2.0, "rid": 0, "status": "done"},
        {"kind": "request_submit", "ts": 0.0, "rid": 1},
        {"kind": "fleet_result", "ts": 0.1, "rid": 1, "status": "shed"},
    ]
    s = load_gen.summarize(router_events)
    assert s["classes"][1]["done"] == 1
    assert s["classes"][1]["ttft_s"]["p50"] == 0.25
    assert s["classes"][0]["shed"] == 1


def test_serve_bench_load_gen_emits_per_class_series(tmp_path):
    from distributed_tensorflow_tpu.tools import serve_bench

    payload = {
        "load_gen": {
            "device": "cpu", "slots": 2, "chunk": 8, "seed": 21,
            "scenarios": {
                "priority_mix": {
                    "classes": {
                        0: {"shed_rate": 0.7,
                            "ttft_s": {"p50": 0.3, "p95": 0.35}},
                        2: {"shed_rate": 0.0,
                            "ttft_s": {"p50": 0.01, "p95": 0.03}},
                    }
                }
            },
        }
    }
    path = str(tmp_path / "events.jsonl")
    out = serve_bench.emit_load_gen_events(payload, path)
    by_name = {e["name"]: e for e in out}
    assert by_name["shed_rate_p0"]["unit"] == "shed_rate"
    assert by_name["shed_rate_p0"]["value"] == 0.7
    assert by_name["fleet_ttft_p95_p2_s"]["unit"] == "s"
    assert by_name["fleet_ttft_p95_p2_s"]["value"] == 0.03
    # The series feed the gate under the (tool, name, device) key.
    evs = obs.read_events(path)
    assert all(e["tool"] == "serve_bench" for e in evs)


def test_obs_report_per_class_rollup():
    """The --requests view rolls up priority classes and shed outcomes —
    and keeps the round-12 output byte-identical for default journals
    (no priority field anywhere, nothing shed => no class lines)."""
    events = [
        {"kind": "request_submit", "ts": 0.0, "rid": 0, "trace": "t0",
         "priority": 2, "prompt_len": 4, "max_new": 8},
        {"kind": "admission", "ts": 0.1, "rid": 0},
        {"kind": "completion", "ts": 0.4, "rid": 0, "ttft_s": 0.1,
         "latency_s": 0.4, "tokens": 8},
        {"kind": "request_submit", "ts": 0.0, "rid": 1, "trace": "t1",
         "prompt_len": 4, "max_new": 8},
        {"kind": "request_shed", "ts": 0.2, "rid": 1, "priority": 0,
         "reason": "preempted"},
    ]
    records = obs_report.reconstruct_requests(events)
    assert records[0]["priority"] == 2 and records[1]["shed"] is True
    txt = obs_report.render_requests(records)
    assert "class p2: 1 requests, 1 done, 0 shed" in txt
    assert "class p0: 1 requests, 0 done, 1 shed (rate 1.0)" in txt
    assert "(shed)" in txt

    plain = [
        {"kind": "request_submit", "ts": 0.0, "rid": 0, "trace": "t0",
         "prompt_len": 4, "max_new": 8},
        {"kind": "admission", "ts": 0.1, "rid": 0},
        {"kind": "completion", "ts": 0.4, "rid": 0, "ttft_s": 0.1,
         "latency_s": 0.4, "tokens": 8},
    ]
    assert "class p" not in obs_report.render_requests(
        obs_report.reconstruct_requests(plain)
    )


# ---------------------------------------------------------------------------
# Round 23: disaggregated fleet — migration join, role tags, bytes/req gate.
# ---------------------------------------------------------------------------


def test_gate_bytes_per_req_unit_fails_high():
    # Round 23: kv_migration_bytes_per_req is a wire-payload series like
    # round 17's bytes/token — the handoff payload creeping UP past the
    # recorded band is the regression; a smaller payload must never trip.
    mk = lambda vals, unit: [  # noqa: E731
        (i, v, unit) for i, v in enumerate(vals)
    ]
    assert "bytes/req" in regression_gate.LOWER_IS_BETTER_UNITS
    res = regression_gate.check_series(
        {("serve_bench", "kv_migration_bytes_per_req"): mk(
            [4096.0, 4200.0, 9000.0], "bytes/req"
        )},
        tolerance=0.5,
    )
    [f] = res["failures"]
    assert f["direction"] == "above" and f["unit"] == "bytes/req"
    assert not regression_gate.check_series(
        {("serve_bench", "kv_migration_bytes_per_req"): mk(
            [4096.0, 4200.0, 1024.0], "bytes/req"
        )},
        tolerance=0.5,
    )["failures"]


def _merged(events_by_src):
    """A minimal aggregate.merge-shaped dict: events carry _src, router
    journal is 'driver'."""
    events = []
    for src, evs in events_by_src.items():
        for ev in evs:
            events.append({**ev, "_src": src})
    events.sort(key=lambda e: e.get("ts", 0.0))
    return {"ranks": list(events_by_src), "events": events}


def test_obs_report_fleet_migration_two_leg_join():
    """Satellite 3: one trace, two legs — router submit, prefill-leg
    admission on r0, migration, decode-leg admission + completion on r1 —
    joins into ONE record with the migration detail and renders the
    done+migr status plus the kv-migration summary line."""
    merged = _merged({
        "driver": [
            {"kind": "request_submit", "ts": 0.0, "rid": 0, "trace": "tA",
             "prompt_len": 4},
            {"kind": "request_route", "ts": 0.1, "rid": 0, "trace": "tA",
             "replica": "r0", "leg": "prefill"},
            {"kind": "request_migrated", "ts": 0.5, "rid": 0, "trace": "tA",
             "from_replica": "r0", "post": "tA.npz", "blocks": 3,
             "nbytes": 6144},
            {"kind": "request_route", "ts": 0.6, "rid": 0, "trace": "tA",
             "replica": "r1", "leg": "decode"},
        ],
        "r0": [
            {"kind": "admission", "ts": 0.2, "rid": 0, "trace": "tA"},
            {"kind": "kv_migration", "ts": 0.45, "trace": "tA",
             "phase": "post", "blocks": 3, "nbytes": 6144, "wall_ms": 1.5},
        ],
        "r1": [
            {"kind": "admission", "ts": 0.7, "rid": 0, "trace": "tA"},
            {"kind": "kv_migration", "ts": 0.75, "trace": "tA",
             "phase": "import", "slot": 0, "blocks": 3, "wall_ms": 2.0},
            {"kind": "completion", "ts": 1.0, "rid": 0, "trace": "tA",
             "tokens": 8, "latency_s": 0.8, "ttft_s": 0.3},
        ],
    })
    [r] = obs_report.reconstruct_fleet_requests(merged)
    assert r["migrated"] is True
    assert r["replicas"] == ["r0", "r1"]
    assert r["completed_on"] == "r1" and r["done"]
    m = r["migration"]
    assert m["from"] == "r0" and m["to"] == "r1"
    assert m["blocks"] == 3 and m["nbytes"] == 6144
    assert m["post_ms"] == 1.5 and m["import_ms"] == 2.0
    assert m["fallback"] is None
    txt = obs_report.render_fleet_requests([r])
    assert "done+migr" in txt
    assert "1 migrated" in txt
    assert "kv migration:" in txt
    assert "avg blocks 3.0" in txt and "6.0 KiB/req" in txt
    assert "post p50 1.50 ms" in txt and "import p50 2.00 ms" in txt
    assert "0 fallback(s)" in txt


def test_obs_report_fleet_migration_fallback_rendered():
    merged = _merged({
        "driver": [
            {"kind": "request_submit", "ts": 0.0, "rid": 0, "trace": "tB",
             "prompt_len": 4},
            {"kind": "request_migrated", "ts": 0.5, "rid": 0, "trace": "tB",
             "from_replica": "r0", "post": "tB.npz", "blocks": 2,
             "nbytes": 2048},
        ],
        "r1": [
            {"kind": "kv_migration", "ts": 0.7, "trace": "tB",
             "phase": "fallback", "reason": "load_failed"},
            {"kind": "completion", "ts": 1.0, "rid": 0, "trace": "tB",
             "tokens": 8, "latency_s": 0.9, "ttft_s": 0.4},
        ],
    })
    [r] = obs_report.reconstruct_fleet_requests(merged)
    assert r["migration"]["fallback"] == "load_failed"
    txt = obs_report.render_fleet_requests([r])
    assert "1 fallback(s)" in txt


def test_fleet_roles_event_renders_and_tags_summary():
    from distributed_tensorflow_tpu.observability import aggregate, format as fmt

    ev = {"kind": "fleet_roles", "ts": 0.0,
          "roles": {"r0": "prefill", "r1": "decode"},
          "migrate_dir": "/tmp/m"}
    [line] = fmt.render("fleet_roles", ev)
    assert "Fleet: roles" in line
    assert "r0=prefill" in line and "r1=decode" in line
    assert "fleet_roles" in aggregate.GANG_KINDS

    [mig] = fmt.render(
        "request_migrated",
        {"kind": "request_migrated", "trace": "t", "from_replica": "r0",
         "post": "t.npz", "blocks": 2, "nbytes": 4096},
    )
    assert mig.startswith("Migrate:") and "from=r0" in mig
    [kv] = fmt.render(
        "kv_migration",
        {"kind": "kv_migration", "phase": "import", "trace": "t",
         "slot": 1, "wall_ms": 2.5},
    )
    assert kv.startswith("KV-migration:") and "phase=import" in kv


def test_load_gen_summarize_counts_migrations():
    from distributed_tensorflow_tpu.tools import load_gen

    events = [
        {"kind": "request_submit", "ts": 0.0, "rid": 0, "priority": 1},
        {"kind": "request_route", "ts": 0.1, "rid": 0},
        {"kind": "request_migrated", "ts": 0.5, "rid": 0, "nbytes": 4096},
        {"kind": "fleet_result", "ts": 1.0, "rid": 0, "status": "done"},
        {"kind": "request_submit", "ts": 0.0, "rid": 1},
        {"kind": "request_route", "ts": 0.1, "rid": 1},
        {"kind": "request_migrated", "ts": 0.6, "rid": 1, "nbytes": 8192},
        {"kind": "fleet_result", "ts": 1.2, "rid": 1, "status": "done"},
    ]
    s = load_gen.summarize(events)
    assert s["migrated"] == 2
    assert s["kv_migration_bytes_per_req"] == 6144.0
    assert s["classes"][1]["migrated"] == 1
    assert s["classes"][0]["migrated"] == 1
    # No migrations => the keys stay absent (round-21 summaries unchanged).
    plain = load_gen.summarize(events[:2] + [
        {"kind": "fleet_result", "ts": 1.0, "rid": 0, "status": "done"},
    ])
    assert "migrated" not in plain
    assert "kv_migration_bytes_per_req" not in plain
