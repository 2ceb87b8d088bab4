"""Elastic gang-restart state machine (train/elastic.py) — fast tier.

Everything here runs WITHOUT real worker processes or wall time: the gang
is driven over a fake process table with injected ``sleep``/rng, stall vs
dead classification over a fake coordinator, and the bounded
``jax.distributed.initialize`` wrapper over a fake initialize_fn — the
RUN_SLOW end-to-end proof (real subprocesses, real SIGKILL, real UDP
detector) lives in tests/integration/test_fault_injection.py and the
native payload tests in tests/test_runtime_native.py. No jax computation
happens in this module (nothing compiles), so it needs no persistent-cache
opt-out and no slot in conftest's ``_CACHE_OPT_OUT_FIRST``.
"""

from __future__ import annotations

import pytest

elastic = pytest.importorskip(
    "distributed_tensorflow_tpu.train.elastic",
    reason="train package unavailable (jax too old for parallel/mesh)",
)

from distributed_tensorflow_tpu.cluster import (  # noqa: E402
    BootstrapError,
    bounded_initialize,
)
from distributed_tensorflow_tpu.config import ClusterConfig  # noqa: E402
from distributed_tensorflow_tpu.train import resilience  # noqa: E402
from distributed_tensorflow_tpu.train.elastic import (  # noqa: E402
    ElasticAgent,
    ElasticGang,
    HeartbeatHealth,
)


# ---------------------------------------------------------------------------
# resilience.retry — the one backoff state machine everything reuses.
# ---------------------------------------------------------------------------


class _FixedRng:
    def __init__(self, u: float):
        self.u = u

    def random(self) -> float:
        return self.u


def test_retry_backoff_jitter_and_on_retry():
    sleeps, events, calls = [], [], []

    def flaky():
        calls.append(1)
        if len(calls) < 3:
            raise OSError(f"boom {len(calls)}")
        return "done"

    out = resilience.retry(
        flaky,
        attempts=5,
        backoff=1.0,
        jitter=0.2,
        on_retry=lambda exc, attempt, delay: events.append((attempt, delay)),
        sleep=sleeps.append,
        rng=_FixedRng(0.5),
    )
    assert out == "done" and len(calls) == 3
    # exponential 1.0, 2.0 × (1 + 0.2·0.5)
    assert sleeps == [1.1, 2.2]
    assert [a for a, _ in events] == [0, 1]
    assert sleeps == [d for _, d in events]


def test_retry_max_backoff_cap_and_reraise():
    sleeps = []
    with pytest.raises(OSError, match="nope"):
        resilience.retry(
            lambda: (_ for _ in ()).throw(OSError("nope")),
            attempts=6,
            backoff=1.0,
            max_backoff=4.0,
            sleep=sleeps.append,
        )
    assert sleeps == [1.0, 2.0, 4.0, 4.0, 4.0]


def test_retry_io_delegates():
    assert resilience.retry_io(lambda: 42) == 42


# ---------------------------------------------------------------------------
# Fake process table: poll() scripts per incarnation, kill tracking.
# ---------------------------------------------------------------------------


class FakeProc:
    """poll() pops a scripted sequence (last value repeats); kill() pins -9."""

    def __init__(self, script):
        self.script = list(script)
        self.killed = False
        self.reaped = False

    def poll(self):
        if self.killed:
            return -9
        if len(self.script) > 1:
            return self.script.pop(0)
        return self.script[0]

    def kill(self):
        self.killed = True

    def wait(self, timeout=None):
        self.reaped = True
        return -9


class FakeTable:
    """scripts[worker] = [incarnation0 script, incarnation1 script, ...]."""

    def __init__(self, scripts):
        self.scripts = scripts
        self.spawned: list[tuple[int, int]] = []  # (worker, incarnation)
        self.procs: dict[tuple[int, int], FakeProc] = {}

    def spawner(self, i):
        def _spawn():
            inc = sum(1 for w, _ in self.spawned if w == i)
            self.spawned.append((i, inc))
            p = FakeProc(self.scripts[i][min(inc, len(self.scripts[i]) - 1)])
            self.procs[(i, inc)] = p
            return p

        return _spawn

    def gang(self, n, **kw):
        kw.setdefault("sleep", lambda s: None)
        kw.setdefault("jitter", 0.0)
        agents = [
            ElasticAgent(f"worker{i}", self.spawner(i), worker_id=i)
            for i in range(n)
        ]
        return ElasticGang(agents, **kw)


class FakeWriter:
    def __init__(self):
        self.scalars = []
        self.flushed = 0

    def add_scalar(self, tag, value, step):
        self.scalars.append((tag, value, step))

    def flush(self):
        self.flushed += 1


def test_gang_clean_run_no_restart():
    t = FakeTable({0: [[None, 0]], 1: [[None, None, 0]]})
    lines = []
    gang = t.gang(2, max_restarts=3, print_fn=lines.append)
    assert gang.run() == 0
    assert gang.restarts == 0 and lines == []
    assert t.spawned == [(0, 0), (1, 0)]


def test_gang_restart_recovers_and_logs():
    # worker1 dies rc=9 in incarnation 0; incarnation 1 both exit 0.
    t = FakeTable({0: [[None, None], [None, 0]], 1: [[None, 9], [None, 0]]})
    lines, writer = [], FakeWriter()
    gang = t.gang(
        2, max_restarts=2, backoff=0.5, print_fn=lines.append,
        summary_writer=writer,
    )
    assert gang.run() == 0
    assert gang.restarts == 1
    # gang semantics: the survivor was killed and reaped, BOTH relaunched
    assert t.procs[(0, 0)].killed and t.procs[(0, 0)].reaped
    assert t.spawned == [(0, 0), (1, 0), (0, 1), (1, 1)]
    # structured Restart: line + restart tfevents scalar
    (line,) = [l for l in lines if l.startswith("Restart: restart=")]
    assert "restart=1/2" in line and "worker1=rc=9" in line
    assert writer.scalars == [("restart", 1.0, 1)]


def test_gang_budget_exhausted_fails_stop():
    t = FakeTable({0: [[None, 3]]})
    lines = []
    gang = t.gang(1, max_restarts=1, print_fn=lines.append)
    assert gang.run() == 1
    assert gang.restarts == 1
    assert any("budget exhausted restarts=1/1" in l for l in lines)
    assert t.spawned == [(0, 0), (0, 1)]  # budget spent, then stop


def test_gang_max_restarts_zero_preserves_fail_stop():
    """max_restarts=0 = round 6's fail-stop: first failure kills the
    survivors and returns 1 — one incarnation, no Restart: line."""
    t = FakeTable({0: [[None, 5]], 1: [[None, None, None]]})
    lines = []
    gang = t.gang(2, max_restarts=0, print_fn=lines.append)
    assert gang.run() == 1
    assert gang.restarts == 0
    assert t.spawned == [(0, 0), (1, 0)]
    assert t.procs[(1, 0)].killed
    assert not any(l.startswith("Restart: restart=") for l in lines)


def test_gang_straggler_after_drain_timeout():
    """Premature-exit guard: a member wedged in a collective after a peer
    finished beats forever ('ok' to health) — the drain window is the only
    verdict that can fire, and it must (no-hang contract)."""
    # worker0 exits 0 immediately; worker1 never exits in incarnation 0,
    # both finish in incarnation 1.
    t = FakeTable({0: [[0], [0]], 1: [[None], [0]]})
    now = {"t": 0.0}
    gang = t.gang(
        2, max_restarts=1, poll_interval=1.0, drain_timeout=30.0,
        clock=lambda: now["t"], print_fn=lambda *a: None,
    )
    gang.sleep = lambda s: now.__setitem__("t", now["t"] + max(s, 1.0))
    assert gang.run() == 0
    assert gang.restarts == 1
    assert t.procs[(1, 0)].killed  # the straggler was killed, gang restarted


def test_gang_staggered_completion_inside_drain_window_is_clean():
    t = FakeTable({0: [[0]], 1: [[None, None, 0]]})
    now = {"t": 0.0}
    gang = t.gang(
        2, max_restarts=1, poll_interval=1.0, drain_timeout=30.0,
        clock=lambda: now["t"],
    )
    gang.sleep = lambda s: now.__setitem__("t", now["t"] + max(s, 1.0))
    assert gang.run() == 0
    assert gang.restarts == 0


def test_independent_member_relaunches_alone():
    # Round 17: worker1 dies rc=9; with independent=True ONLY worker1
    # relaunches — worker0's incarnation-0 process keeps running to its
    # clean exit (never killed), no gang restart.
    t = FakeTable({
        0: [[None, None, None, None, 0]],
        1: [[None, 9], [None, 0]],
    })
    lines = []
    gang = t.gang(2, max_restarts=2, independent=True, print_fn=lines.append)
    assert gang.run() == 0
    assert gang.restarts == 1
    assert t.spawned == [(0, 0), (1, 0), (1, 1)]  # worker0 spawned ONCE
    assert not t.procs[(0, 0)].killed
    (line,) = [l for l in lines if l.startswith("Restart: restart=")]
    assert "independent=True" in line and "members=[worker1]" in line


def test_independent_budget_exhausted_fails_stop():
    # Budget spent by per-member relaunches: the next failure kills the
    # survivors and fail-stops (rc 1) like an exhausted gang retry loop.
    t = FakeTable({
        0: [[None, None, None, None, None, None]],
        1: [[None, 7], [None, 7]],
    })
    lines = []
    gang = t.gang(2, max_restarts=1, independent=True, print_fn=lines.append)
    assert gang.run() == 1
    assert gang.restarts == 1
    assert t.spawned == [(0, 0), (1, 0), (1, 1)]
    assert t.procs[(0, 0)].killed  # fail-stop kills the survivor
    assert any("budget exhausted" in l for l in lines)


def test_independent_skips_straggler_verdict():
    # A member finishing long after its peers is the POINT of a
    # collective-free gang — no drain-window straggler kill.
    t = FakeTable({0: [[0]], 1: [[None] * 50 + [0]]})
    now = {"t": 0.0}
    gang = t.gang(
        2, max_restarts=1, independent=True, poll_interval=1.0,
        drain_timeout=5.0, clock=lambda: now["t"],
        print_fn=lambda *a: None,
    )
    gang.sleep = lambda s: now.__setitem__("t", now["t"] + max(s, 1.0))
    assert gang.run() == 0
    assert gang.restarts == 0
    assert not t.procs[(1, 0)].killed


def test_independent_health_grace_after_relaunch():
    # After an independent relaunch the member's health verdicts are
    # suppressed for member_grace_s — a restarting member's silence must
    # not be re-verdicted into a restart loop.
    class DeadHealth:
        def classify(self, wid):
            return "dead" if wid == 1 else "ok"

        def stop(self):
            pass

    t = FakeTable({0: [[0]], 1: [[None], [None, None, 0]]})
    now = {"t": 0.0}
    gang = t.gang(
        2, max_restarts=3, independent=True, member_grace_s=100.0,
        health_factory=lambda: DeadHealth(), poll_interval=1.0,
        clock=lambda: now["t"], print_fn=lambda *a: None,
    )
    gang.sleep = lambda s: now.__setitem__("t", now["t"] + max(s, 1.0))
    assert gang.run() == 0
    # Exactly one restart: the relaunched member finished inside its
    # grace window despite the detector still reporting it dead.
    assert gang.restarts == 1
    assert t.spawned == [(0, 0), (1, 0), (1, 1)]


def test_independent_refuses_resize_composition():
    t = FakeTable({0: [[0]], 1: [[0]], 2: [[0]]})
    with pytest.raises(ValueError, match="independent"):
        t.gang(3, max_restarts=2, independent=True, min_workers=1)


def test_gang_kills_workers_when_detector_setup_fails():
    """A non-verdict failure (detector port grabbed between incarnations,
    spawn raising) must not orphan already-started workers: they hold the
    checkpoint dir and would outlive the dead driver."""
    t = FakeTable({0: [[None]], 1: [[None]]})

    def bad_factory():
        raise OSError("heartbeat port in use")

    gang = t.gang(2, max_restarts=1, health_factory=bad_factory)
    with pytest.raises(OSError, match="port in use"):
        gang.run()
    assert t.procs[(0, 0)].killed and t.procs[(1, 0)].killed


def test_gang_backoff_doubles_across_restarts():
    t = FakeTable({0: [[None, 1], [None, 1], [None, 1], [None, 0]]})
    sleeps = []
    gang = t.gang(
        1, max_restarts=3, backoff=1.0,
        poll_interval=0.0, sleep=sleeps.append,
    )
    assert gang.run() == 0
    assert gang.restarts == 3
    assert [s for s in sleeps if s > 0] == [1.0, 2.0, 4.0]


# ---------------------------------------------------------------------------
# Stall vs dead classification (injected progress counters — no sockets).
# ---------------------------------------------------------------------------


class FakeCoordinator:
    def __init__(self, seen, prog):
        self.seen, self.prog = seen, prog
        self.stopped = False

    def ms_since_seen(self, i):
        return self.seen[i]

    def ms_since_progress(self, i):
        return self.prog[i]

    def stop(self):
        self.stopped = True


def _health(seen, prog, *, timeout_ms=5000, stall_timeout_ms=10_000,
            grace_ms=25_000, now=1.0):
    h = HeartbeatHealth.__new__(HeartbeatHealth)
    h._coord = FakeCoordinator(seen, prog)
    h._timeout_ms = timeout_ms
    h._stall_ms = stall_timeout_ms
    h._grace_ms = grace_ms
    clock = {"t": now}
    h._clock = lambda: clock["t"]
    h._start = 0.0
    h._clock_box = clock
    return h


def test_classify_stall_vs_dead_matrix():
    h = _health(
        seen={0: 100, 1: 100, 2: 9_999_999, 3: -1},
        prog={0: 500, 1: 60_000, 2: 100, 3: -1},
    )
    assert h.classify(0) == "ok"  # beating, progressing
    assert h.classify(1) == "stalled"  # beating, progress frozen 60s
    assert h.classify(2) == "dead"  # silence past timeout
    assert h.classify(3) == "ok"  # never seen, inside grace
    h._clock_box["t"] = 30.0  # 30 s > 25 s grace
    assert h.classify(3) == "dead"  # never came up


def test_classify_never_progressed_is_not_stalled():
    # A sender that never reported progress (startup import/compile, or an
    # old payload) must not read as a stall.
    h = _health(seen={0: 100}, prog={0: -1})
    assert h.classify(0) == "ok"


def test_classify_stall_detection_disabled():
    h = _health(seen={0: 100}, prog={0: 999_999}, stall_timeout_ms=0)
    assert h.classify(0) == "ok"


def test_gang_recovers_from_injected_stall():
    """A live-but-stalled verdict (injected progress counter) triggers the
    same kill + gang-restart path as a death — the acceptance case."""
    t = FakeTable({0: [[None, None], [0]], 1: [[None, None], [0]]})
    incarnations = []

    class InjectedHealth:
        def __init__(self, verdicts):
            self.verdicts = verdicts
            self.stopped = False

        def classify(self, wid):
            return self.verdicts.get(wid, "ok")

        def stop(self):
            self.stopped = True

    def health_factory():
        # incarnation 0: worker1 beats but its progress counter is frozen;
        # incarnation 1: healthy.
        h = InjectedHealth({1: "stalled"} if not incarnations else {})
        incarnations.append(h)
        return h

    lines = []
    gang = t.gang(
        2, max_restarts=1, print_fn=lines.append,
        health_factory=health_factory,
    )
    assert gang.run() == 0
    assert gang.restarts == 1
    assert any("worker1=stalled" in l for l in lines)
    assert t.procs[(1, 0)].killed  # the stalled member was killed, not waited on
    # a fresh detector per incarnation, each torn down afterwards
    assert len(incarnations) == 2 and all(h.stopped for h in incarnations)


# ---------------------------------------------------------------------------
# Bounded jax.distributed.initialize (cluster.bounded_initialize).
# ---------------------------------------------------------------------------

_CLUSTER = ClusterConfig.from_lists(["127.0.0.1:29001", "127.0.0.1:29002"])


def test_bounded_initialize_retries_then_succeeds():
    attempts, msgs = [], []

    def flaky_init(**kw):
        attempts.append(kw)
        if len(attempts) < 3:
            raise RuntimeError("barrier timed out")

    bounded_initialize(
        _CLUSTER, 1, timeout_s=7, attempts=3, backoff=0.0,
        initialize_fn=flaky_init, sleep=lambda s: None, print_fn=msgs.append,
    )
    assert len(attempts) == 3
    assert attempts[0] == dict(
        coordinator_address="127.0.0.1:29001",
        num_processes=2,
        process_id=1,
        initialization_timeout=7,
    )
    assert any("attempt 1/3" in m for m in msgs)


def test_bounded_initialize_shuts_down_between_attempts():
    """jax assigns its global distributed client BEFORE connect(), so a
    timed-out attempt leaves half-initialized state and a bare re-call
    dies with 'initialize should only be called once' — the wrapper must
    tear down between attempts for the retry to be real."""
    events = []

    def flaky_init(**kw):
        events.append("init")
        if events.count("init") < 2:
            raise RuntimeError("barrier timed out")

    def shutdown():
        events.append("shutdown")

    bounded_initialize(
        _CLUSTER, 0, timeout_s=5, attempts=3, backoff=0.0,
        initialize_fn=flaky_init, shutdown_fn=shutdown,
        sleep=lambda s: None, print_fn=lambda *a: None,
    )
    assert events == ["init", "shutdown", "init"]


def test_bounded_initialize_exhausts_with_clear_error():
    attempts, shutdowns = [], []

    def dead_init(**kw):
        attempts.append(kw)
        raise TimeoutError("no coordinator")

    with pytest.raises(BootstrapError) as exc:
        bounded_initialize(
            _CLUSTER, 0, timeout_s=5, attempts=2, backoff=0.0,
            initialize_fn=dead_init, shutdown_fn=lambda: shutdowns.append(1),
            sleep=lambda s: None, print_fn=lambda *a: None,
        )
    assert len(attempts) == 2
    assert "127.0.0.1:29001" in str(exc.value) and "2 attempt(s)" in str(exc.value)
    # torn down between attempts AND after the final failure — a later
    # bootstrap in the same process must not inherit the half-initialized
    # global client.
    assert len(shutdowns) == 2


def test_bounded_initialize_defaults_from_cluster_config():
    attempts = []

    def dead_init(**kw):
        attempts.append(kw)
        raise RuntimeError("down")

    cluster = ClusterConfig(
        worker_svrs=("h:1", "h:2"), connect_timeout_s=11, connect_attempts=1
    )
    with pytest.raises(BootstrapError):
        bounded_initialize(
            cluster, 0, initialize_fn=dead_init, sleep=lambda s: None,
            print_fn=lambda *a: None,
        )
    assert len(attempts) == 1
    assert attempts[0]["initialization_timeout"] == 11


# ---------------------------------------------------------------------------
# Supervisor: stall trips should_stop; progress reporting plumbing.
# ---------------------------------------------------------------------------


class FakeHeartbeatCoordinator:
    def __init__(self, failed=0, stalled=0):
        self._failed, self._stalled = failed, stalled

    def failed_count(self):
        return self._failed

    def stalled_count(self, stall_timeout_ms):
        return self._stalled


def test_supervisor_stall_trips_should_stop():
    from distributed_tensorflow_tpu.train import Supervisor

    sup = Supervisor(is_chief=True)
    sup.attach_heartbeat(FakeHeartbeatCoordinator(stalled=1), stall_timeout_ms=5000)
    assert sup.should_stop

    sup2 = Supervisor(is_chief=True)
    sup2.attach_heartbeat(FakeHeartbeatCoordinator(stalled=1))  # detection off
    assert not sup2.should_stop

    sup3 = Supervisor(is_chief=True)
    sup3.attach_heartbeat(FakeHeartbeatCoordinator(failed=1), stall_timeout_ms=5000)
    assert sup3.should_stop


def test_supervisor_report_progress_forwards():
    from distributed_tensorflow_tpu.train import Supervisor

    sup = Supervisor(is_chief=True)
    sup.report_progress(5)  # no reporter attached: no-op
    seen = []
    sup.attach_progress(seen.append)
    sup.report_progress(7)
    sup.report_progress(21)
    assert seen == [7, 21]


def test_process_context_report_progress_targets_sender():
    from distributed_tensorflow_tpu.cluster import ProcessContext

    class Sender:
        def __init__(self):
            self.values = []

        def set_progress(self, p):
            self.values.append(p)

    class CoordinatorOnly:
        pass  # no set_progress: a chief-side coordinator, not a sender

    sender = Sender()
    ctx = ProcessContext(
        job_name="worker", task_index=1, num_processes=2,
        is_chief=False, is_ps=False, heartbeat=sender,
    )
    ctx.report_progress(3)
    assert sender.values == [3]

    chief_sender = Sender()
    ctx2 = ProcessContext(
        job_name="worker", task_index=0, num_processes=2,
        is_chief=True, is_ps=False,
        heartbeat=CoordinatorOnly(), heartbeat_sender=chief_sender,
    )
    ctx2.report_progress(9)
    assert chief_sender.values == [9]

    ctx3 = ProcessContext(
        job_name="worker", task_index=0, num_processes=1,
        is_chief=True, is_ps=False,
    )
    ctx3.report_progress(1)  # nothing armed: no-op


# ---------------------------------------------------------------------------
# Env knobs + bootstrap threading (the two wiring satellites).
# ---------------------------------------------------------------------------


def test_config_from_env_elastic_knobs(monkeypatch):
    from distributed_tensorflow_tpu.launch import config_from_env

    monkeypatch.setenv("DTF_MAX_RESTARTS", "4")
    monkeypatch.setenv("DTF_STALL_TIMEOUT_MS", "45000")
    cfg = config_from_env()
    assert cfg.max_restarts == 4
    assert cfg.stall_timeout_ms == 45000


def test_cluster_from_env_heartbeat_knobs(monkeypatch):
    from distributed_tensorflow_tpu.launch import cluster_from_env

    monkeypatch.setenv("DTF_HEARTBEAT_PORT", "7777")
    monkeypatch.setenv("DTF_HEARTBEAT_TIMEOUT_MS", "2500")
    monkeypatch.setenv("DTF_HEARTBEAT_HOST", "10.0.0.9")
    cluster = cluster_from_env(_CLUSTER)
    assert cluster.heartbeat_port == 7777
    assert cluster.heartbeat_timeout_ms == 2500
    assert cluster.heartbeat_host == "10.0.0.9"
    assert cluster.worker_svrs == _CLUSTER.worker_svrs  # base preserved

    monkeypatch.setenv("DTF_HEARTBEAT_PORT", "0")  # explicit disable
    monkeypatch.delenv("DTF_HEARTBEAT_HOST")
    assert cluster_from_env(_CLUSTER).heartbeat_port is None
    for var in ("DTF_HEARTBEAT_PORT", "DTF_HEARTBEAT_TIMEOUT_MS"):
        monkeypatch.delenv(var)
    assert cluster_from_env(_CLUSTER) is _CLUSTER  # no overrides: untouched


def test_bootstrap_from_argv_threads_cluster_heartbeat(monkeypatch):
    """The round-7 wiring fix: launch.run's bootstrap_from_argv path must
    arm the detector from ClusterConfig — no caller-built context needed.
    Proven by recording what bootstrap hands the native sender."""
    from distributed_tensorflow_tpu.runtime import native

    created = []

    class RecordingWorker:
        def __init__(self, host, port, worker_id, interval_ms=1000):
            created.append((host, port, worker_id, interval_ms))

        def set_progress(self, p):
            pass

        def stop(self):
            pass

    monkeypatch.setattr(native, "HeartbeatWorker", RecordingWorker)
    from distributed_tensorflow_tpu.cluster import bootstrap_from_argv

    cluster = ClusterConfig(
        worker_svrs=("127.0.0.1:29001", "127.0.0.1:29002"),
        heartbeat_port=7311,
        heartbeat_timeout_ms=2000,
        heartbeat_host="127.0.0.1",  # agent-hosted: every task a sender
    )
    ctx = bootstrap_from_argv(
        cluster,
        ["--job_name=worker", "--task_index=1"],
        initialize_distributed=False,
        print_fn=lambda *a: None,
    )
    assert created == [("127.0.0.1", 7311, 1, 400)]  # interval = timeout//5
    assert ctx.heartbeat is not None
    ctx.close()


def test_bootstrap_without_heartbeat_unchanged(monkeypatch):
    from distributed_tensorflow_tpu.cluster import bootstrap_from_argv

    ctx = bootstrap_from_argv(
        _CLUSTER,
        ["--job_name=worker", "--task_index=1"],
        initialize_distributed=False,
        print_fn=lambda *a: None,
    )
    assert ctx.heartbeat is None and ctx.heartbeat_sender is None


# ---------------------------------------------------------------------------
# launch_local: elastic driver over real (trivial) subprocesses.
# ---------------------------------------------------------------------------


def test_launch_local_elastic_clean_gang(tmp_path):
    import sys

    from distributed_tensorflow_tpu.tools.launch_local import launch

    lines = []
    rc = launch(
        [sys.executable, "-c", "import sys; sys.exit(0)"],
        num_workers=2,
        logdir=str(tmp_path),
        max_restarts=2,
        poll_interval=0.05,
        print_fn=lines.append,
    )
    assert rc == 0
    assert not any(str(l).startswith("Restart: restart=") for l in lines)


def test_launch_local_elastic_exhausts_budget(tmp_path):
    import sys

    from distributed_tensorflow_tpu.tools.launch_local import launch

    lines = []
    rc = launch(
        [sys.executable, "-c", "import sys; sys.exit(3)"],
        num_workers=1,
        logdir=str(tmp_path),
        max_restarts=1,
        backoff=0.05,
        poll_interval=0.05,
        print_fn=lines.append,
    )
    assert rc == 1
    assert any("restart=1/1" in str(l) for l in lines)
    assert any("budget exhausted" in str(l) for l in lines)
    # relaunch appended to the same log (the failure is not erased)
    assert (tmp_path / "worker0.log").exists()
    # restart tfevents sidecar written by the driver
    assert any(".elastic" in f.name for f in tmp_path.iterdir())


def test_launch_local_rejects_unsupervised_elastic(tmp_path):
    import sys

    from distributed_tensorflow_tpu.tools.launch_local import launch

    with pytest.raises(ValueError, match="wait=True"):
        launch(
            [sys.executable, "-c", "pass"],
            num_workers=1,
            logdir=str(tmp_path),
            max_restarts=2,
            wait=False,
        )


def test_launch_local_refuses_a_chip_gang_on_one_host(tmp_path, monkeypatch):
    """A chip belongs to one process: several local workers on anything
    but JAX_PLATFORMS=cpu would each open every local chip, so the
    launcher refuses before spawning; a CPU gang says what it is."""
    from distributed_tensorflow_tpu.tools.launch_local import launch

    import sys

    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    with pytest.raises(RuntimeError, match="has imported JAX"):
        launch(["true"], 1, logdir=str(tmp_path / "a"))
    monkeypatch.delitem(sys.modules, "jax")  # a lean driver never imports it
    with pytest.raises(RuntimeError, match="would each open every local chip"):
        launch(["true"], 2, logdir=str(tmp_path / "a"))
    lines = []
    assert launch(
        ["true"], 2, logdir=str(tmp_path / "b"),
        env={"JAX_PLATFORMS": "cpu"}, print_fn=lines.append,
    ) == 0
    assert lines[0] == "launch_local: 2 worker processes on cpu"


def test_launch_local_cli_defaults_from_env(monkeypatch):
    """A pod scheduler's DTF_* env arms the elastic driver with no flag
    changes (the TrainConfig.max_restarts / config_from_env mirror)."""
    import argparse

    from distributed_tensorflow_tpu.tools import launch_local

    monkeypatch.setenv("DTF_MAX_RESTARTS", "3")
    monkeypatch.setenv("DTF_HEARTBEAT_PORT", "7411")
    monkeypatch.setenv("DTF_STALL_TIMEOUT_MS", "60000")
    seen = {}

    def fake_launch(command, workers, ps, logdir, **kw):
        seen.update(kw, workers=workers)
        return 0

    monkeypatch.setattr(launch_local, "launch", fake_launch)
    assert launch_local.main(["--workers", "2", "--", "echo", "hi"]) == 0
    assert seen["max_restarts"] == 3
    assert seen["heartbeat_port"] == 7411
    assert seen["stall_timeout_ms"] == 60000
    assert seen["heartbeat_grace_ms"] is None  # default: 5x timeout


def test_launch_local_fail_stop_path_unchanged(tmp_path):
    """max_restarts=0 keeps the pre-round-7 one-shot semantics: every task
    runs to completion exactly once, non-zero rc if any worker failed."""
    import sys

    from distributed_tensorflow_tpu.tools.launch_local import launch

    lines = []
    rc = launch(
        [sys.executable, "-c", "import sys; sys.exit(1)"],
        num_workers=1,
        logdir=str(tmp_path),
        print_fn=lines.append,
    )
    assert rc == 1
    assert any("worker0: exit 1" in str(l) for l in lines)
    assert not any("Restart" in str(l) for l in lines)


# ---------------------------------------------------------------------------
# Round 8: shrink-to-fit resize (min_workers / rejoin_timeout_s).
# ---------------------------------------------------------------------------


class ResizeTable:
    """Fake process table for resize scenarios: per-worker incarnation
    scripts (as FakeTable), plus an injectable availability flag and a
    record of every spawn's topology ((worker,) for the original path,
    (worker, rank, world, ranks) for a resized incarnation)."""

    def __init__(self, scripts, unavailable=()):
        self.scripts = scripts
        self.available = {i: i not in unavailable for i in scripts}
        self.spawned: list[tuple] = []
        self.procs: dict[tuple[int, int], FakeProc] = {}

    def agent(self, i):
        def _spawn(*topo):
            inc = sum(1 for s in self.spawned if s[0] == i)
            self.spawned.append((i,) + topo)
            p = FakeProc(self.scripts[i][min(inc, len(self.scripts[i]) - 1)])
            self.procs[(i, inc)] = p
            return p

        return ElasticAgent(
            f"worker{i}",
            _spawn,
            worker_id=i,
            available_fn=lambda: self.available[i],
            topo_spawn_fn=_spawn,
        )

    def gang(self, n, **kw):
        kw.setdefault("sleep", lambda s: None)
        kw.setdefault("jitter", 0.0)
        kw.setdefault("rejoin_timeout_s", 0.0)
        return ElasticGang([self.agent(i) for i in range(n)], **kw)


def test_gang_shrinks_when_slot_not_replaced():
    """Acceptance: kill-without-replacement resizes to M >= min_workers,
    charges the budget ONCE, and emits the structured Resize: line plus
    the world_size tfevents scalar."""
    t = ResizeTable(
        {0: [[None, None], [None, 0]], 1: [[None, 9]]}, unavailable={1}
    )
    lines, writer = [], FakeWriter()
    gang = t.gang(
        2, max_restarts=2, min_workers=1, print_fn=lines.append,
        summary_writer=writer,
    )
    assert gang.run() == 0
    assert gang.restarts == 1  # the resize charged the budget exactly once
    assert gang.resizes == 1 and gang.world_size == 1
    # Incarnation 0 spawns via the ORIGINAL path; the shrunk incarnation
    # respawns only the survivor, at compact rank 0 of world 1.
    assert t.spawned == [(0,), (1,), (0, 0, 1, (0,))]
    (line,) = [l for l in lines if l.startswith("Resize: world=")]
    assert "world=1 from=2" in line and "direction=shrink" in line
    assert "dropped=[worker1]" in line
    # world_size scalar stream: initial world at step 0, the resize at its
    # restart ordinal.
    assert ("world_size", 2.0, 0) in writer.scalars
    assert ("world_size", 1.0, 1) in writer.scalars


def test_gang_below_floor_fail_stops():
    """Below min_workers the gang fail-stops (round-6 semantics): rc 1,
    denial line, no relaunch below the floor."""
    t = ResizeTable(
        {0: [[None, None], [None, 7]], 1: [[None, 9]]}, unavailable={0, 1}
    )
    lines = []
    gang = t.gang(2, max_restarts=5, min_workers=1, print_fn=lines.append)
    assert gang.run() == 1
    assert gang.resizes == 1  # shrank to 1, then the survivor's host died
    assert any(
        l.startswith("Resize: denied world=0 min_workers=1") for l in lines
    )
    # Nothing spawned past the world-1 incarnation.
    assert t.spawned == [(0,), (1,), (0, 0, 1, (0,))]


def test_gang_replacement_within_window_preserves_fixed_size():
    """A replacement registering INSIDE rejoin_timeout_s keeps round 7's
    fixed-size restart path bit-for-bit: original spawn calls (no
    topology arguments), no Resize: line, same budget accounting."""
    t = ResizeTable({0: [[None, None], [None, 0]], 1: [[None, 9], [None, 0]]})
    t.available[1] = False
    now = {"t": 0.0}

    def sleep(s):
        now["t"] += max(s, 0.5)
        if now["t"] > 5.0:  # replacement arrives 5s in; window is 30s
            t.available[1] = True

    lines = []
    gang = t.gang(
        2, max_restarts=2, min_workers=1, rejoin_timeout_s=30.0,
        poll_interval=1.0, sleep=sleep, clock=lambda: now["t"],
        print_fn=lines.append,
    )
    assert gang.run() == 0
    assert gang.restarts == 1 and gang.resizes == 0
    assert t.spawned == [(0,), (1,), (0,), (1,)]  # original path throughout
    assert not any(l.startswith("Resize:") for l in lines)


def test_gang_grows_back_when_replacement_registers():
    """Acceptance (grow half): while running degraded, a benched slot's
    replacement registering triggers a grow back to the original world —
    original ranks, original spawn path — charging the budget once more."""
    t = ResizeTable(
        {0: [[None, None], [None, None], [None, 0]], 1: [[None, 9], [None, 0]]},
        unavailable={1},
    )
    lines, writer = [], FakeWriter()
    gang = t.gang(
        2, max_restarts=3, min_workers=1, print_fn=lines.append,
        summary_writer=writer,
    )
    # Replacement registers once the gang is running degraded.
    real_sleep = gang.sleep

    def sleep(s):
        if gang.resizes >= 1:
            t.available[1] = True
        real_sleep(s)

    gang.sleep = sleep
    assert gang.run() == 0
    assert gang.restarts == 2 and gang.resizes == 2 and gang.world_size == 2
    # shrink → degraded incarnation → grow at original ranks (plain spawns).
    assert t.spawned == [(0,), (1,), (0, 0, 1, (0,)), (0,), (1,)]
    grow = [l for l in lines if "direction=grow" in l]
    assert len(grow) == 1 and "rejoined=[worker1]" in grow[0]
    assert ("world_size", 2.0, 2) in writer.scalars
    # The grow's Restart: line names the rejoined member as its cause.
    assert any("worker1=rejoined" in l for l in lines)


def test_gang_resize_needs_topo_spawn():
    """An agent without topo_spawn_fn cannot be respawned at a non-original
    topology — loud error, not a silently wrong world size."""
    # worker1 dies, unavailable; worker0 has no topo_spawn_fn.
    procs = {0: [[None, None]], 1: [[None, 3]]}
    made = []

    def mk(i):
        it = iter(procs[i])

        def _spawn():
            made.append(i)
            return FakeProc(next(it))

        return ElasticAgent(
            f"worker{i}", _spawn, worker_id=i,
            available_fn=lambda: i != 1,
        )

    gang = ElasticGang(
        [mk(0), mk(1)], max_restarts=2, min_workers=1, jitter=0.0,
        sleep=lambda s: None, print_fn=lambda *a: None,
    )
    with pytest.raises(RuntimeError, match="topo_spawn_fn"):
        gang.run()


def test_gang_min_workers_validation():
    agents = [ElasticAgent("w0", lambda: FakeProc([0]))]
    with pytest.raises(ValueError, match="min_workers"):
        ElasticGang(agents, min_workers=0)
    with pytest.raises(ValueError, match="min_workers"):
        ElasticGang(agents, min_workers=2)
    with pytest.raises(ValueError, match="rejoin_timeout_s"):
        ElasticGang(agents, rejoin_timeout_s=-1.0)


def test_gang_health_factory_receives_world():
    """A resized incarnation's detector must expect the REDUCED member
    count: world-aware factories get the incarnation's world size."""
    worlds = []

    class NullHealth:
        def classify(self, wid):
            return "ok"

        def stop(self):
            pass

    def factory(world):
        worlds.append(world)
        return NullHealth()

    t = ResizeTable(
        {0: [[None, None], [None, 0]], 1: [[None, 9]]}, unavailable={1}
    )
    gang = t.gang(
        2, max_restarts=2, min_workers=1, health_factory=factory,
        print_fn=lambda *a: None,
    )
    assert gang.run() == 0
    assert worlds == [2, 1]


# ---------------------------------------------------------------------------
# Round 8 wiring: env knobs, cluster subset, driver flags.
# ---------------------------------------------------------------------------


def test_config_from_env_resize_knobs(monkeypatch):
    from distributed_tensorflow_tpu.launch import config_from_env

    monkeypatch.setenv("DTF_MIN_WORKERS", "2")
    monkeypatch.setenv("DTF_REJOIN_TIMEOUT_S", "12.5")
    cfg = config_from_env()
    assert cfg.min_workers == 2
    assert cfg.rejoin_timeout_s == 12.5


@pytest.mark.parametrize(
    "var,value",
    [
        ("DTF_MIN_WORKERS", "two"),
        ("DTF_REJOIN_TIMEOUT_S", "soon"),
        ("DTF_MAX_RESTARTS", "3.5"),
    ],
)
def test_config_from_env_invalid_values_raise(monkeypatch, var, value):
    from distributed_tensorflow_tpu.launch import config_from_env

    monkeypatch.setenv(var, value)
    with pytest.raises(ValueError, match=var):
        config_from_env()


def test_config_from_env_negative_min_workers_rejected(monkeypatch):
    from distributed_tensorflow_tpu.launch import config_from_env

    monkeypatch.setenv("DTF_MIN_WORKERS", "-1")
    with pytest.raises(ValueError, match="min_workers"):
        config_from_env()


def test_cluster_subset_selects_and_validates():
    from distributed_tensorflow_tpu.config import ClusterConfig

    cluster = ClusterConfig.from_lists(["h0:1", "h1:2", "h2:3"])
    sub = cluster.subset((2, 0))
    assert sub.worker_svrs == ("h2:3", "h0:1")
    assert sub.coordinator_address == "h2:3"  # new rank 0's host
    assert sub.num_processes == 2
    with pytest.raises(ValueError, match="at least one"):
        cluster.subset(())
    with pytest.raises(ValueError, match="unique"):
        cluster.subset((1, 1))
    with pytest.raises(ValueError, match="out of range"):
        cluster.subset((0, 3))


def test_cluster_from_env_world_size_and_ranks(monkeypatch):
    from distributed_tensorflow_tpu.launch import cluster_from_env

    base = ClusterConfig.from_lists(["h0:1", "h1:2", "h2:3"])
    monkeypatch.setenv("DTF_WORLD_SIZE", "2")
    assert cluster_from_env(base).worker_svrs == ("h0:1", "h1:2")

    monkeypatch.setenv("DTF_WORKER_RANKS", "1")
    monkeypatch.setenv("DTF_WORLD_SIZE", "1")
    shrunk = cluster_from_env(base)
    assert shrunk.worker_svrs == ("h1:2",)
    assert shrunk.num_processes == 1

    # Contradiction and malformed values are loud.
    monkeypatch.setenv("DTF_WORLD_SIZE", "2")
    with pytest.raises(ValueError, match="contradicts"):
        cluster_from_env(base)
    monkeypatch.setenv("DTF_WORLD_SIZE", "two")
    with pytest.raises(ValueError, match="DTF_WORLD_SIZE"):
        cluster_from_env(base)
    monkeypatch.delenv("DTF_WORLD_SIZE")
    monkeypatch.setenv("DTF_WORKER_RANKS", "1,x")
    with pytest.raises(ValueError, match="DTF_WORKER_RANKS"):
        cluster_from_env(base)
    monkeypatch.setenv("DTF_WORKER_RANKS", "7")
    with pytest.raises(ValueError, match="out of range"):
        cluster_from_env(base)
    monkeypatch.delenv("DTF_WORKER_RANKS")
    monkeypatch.setenv("DTF_WORLD_SIZE", "0")
    with pytest.raises(ValueError, match=">= 1"):
        cluster_from_env(base)


def test_cluster_from_env_world_size_needs_worker_svrs(monkeypatch):
    from distributed_tensorflow_tpu.launch import cluster_from_env

    monkeypatch.setenv("DTF_WORLD_SIZE", "2")
    with pytest.raises(ValueError, match="worker_svrs"):
        cluster_from_env(ClusterConfig())


def test_launch_local_shrinks_on_lost_marker(tmp_path):
    """Driver end-to-end over real (trivial) subprocesses: a worker that
    dies with its .lost marker present is benched; the survivor relaunches
    at world 1 with the topology env set."""
    import sys

    from distributed_tensorflow_tpu.tools.launch_local import launch

    script = (
        "import os, sys\n"
        "task = [a for a in sys.argv if a.startswith('--task_index')]"
        "[0].split('=')[1]\n"
        "wd = sys.argv[1]\n"
        "print('WORLD', os.environ.get('DTF_WORLD_SIZE', 'orig'),\n"
        "      'RANKS', os.environ.get('DTF_WORKER_RANKS', '-'), flush=True)\n"
        "if task == '1' and not os.path.exists(os.path.join(wd, 'died')):\n"
        "    open(os.path.join(wd, 'died'), 'w').close()\n"
        "    open(os.path.join(wd, 'logs', 'worker1.lost'), 'w').close()\n"
        "    sys.exit(5)\n"
        "sys.exit(0)\n"
    )
    lines = []
    rc = launch(
        [sys.executable, "-c", script, str(tmp_path)],
        num_workers=2,
        logdir=str(tmp_path / "logs"),
        max_restarts=2,
        min_workers=1,
        rejoin_timeout_s=1.0,
        backoff=0.05,
        poll_interval=0.05,
        print_fn=lambda *a: lines.append(" ".join(str(x) for x in a)),
    )
    assert rc == 0, lines
    assert any(
        l.startswith("Resize: world=1 from=2") and "dropped=[worker1]" in l
        for l in lines
    ), lines
    w0 = (tmp_path / "logs" / "worker0.log").read_bytes().decode()
    # Incarnation 1: original env; incarnation 2: shrunk topology env.
    assert "WORLD orig RANKS -" in w0 and "WORLD 1 RANKS 0" in w0, w0


def test_launch_local_resize_flag_validation(tmp_path):
    import sys

    from distributed_tensorflow_tpu.tools.launch_local import launch

    with pytest.raises(ValueError, match="exceeds num_workers"):
        launch([sys.executable, "-c", "pass"], num_workers=1,
               logdir=str(tmp_path), max_restarts=1, min_workers=2)
    with pytest.raises(ValueError, match="max_restarts"):
        launch([sys.executable, "-c", "pass"], num_workers=2,
               logdir=str(tmp_path), max_restarts=0, min_workers=1)
    with pytest.raises(ValueError, match="drive_mode"):
        launch([sys.executable, "-c", "pass"], num_workers=2,
               logdir=str(tmp_path), max_restarts=1, min_workers=1,
               drive_mode="explode")


def test_launch_local_cli_resize_defaults_from_env(monkeypatch):
    from distributed_tensorflow_tpu.tools import launch_local

    monkeypatch.setenv("DTF_MAX_RESTARTS", "2")
    monkeypatch.setenv("DTF_MIN_WORKERS", "1")
    monkeypatch.setenv("DTF_REJOIN_TIMEOUT_S", "7.5")
    seen = {}

    def fake_launch(command, workers, ps, logdir, **kw):
        seen.update(kw, workers=workers)
        return 0

    monkeypatch.setattr(launch_local, "launch", fake_launch)
    assert launch_local.main(["--workers", "2", "--", "echo", "hi"]) == 0
    assert seen["min_workers"] == 1
    assert seen["rejoin_timeout_s"] == 7.5
    assert seen["drive_mode"] == "none"


# ---------------------------------------------------------------------------
# Progress watchdog — the stall verdict (round 22).
# ---------------------------------------------------------------------------


def _stall_gang(table, heartbeats, **kw):
    """FakeTable.gang, but with per-worker heartbeat_fn wired (the table
    helper predates the watchdog and does not thread it)."""
    kw.setdefault("sleep", lambda s: None)
    kw.setdefault("jitter", 0.0)
    agents = [
        ElasticAgent(
            f"worker{i}",
            table.spawner(i),
            worker_id=i,
            heartbeat_fn=heartbeats.get(i),
        )
        for i in range(len(table.scripts))
    ]
    return ElasticGang(agents, **kw)


def test_stall_verdict_kills_member_and_recovers():
    """A member that is alive but whose heartbeat age exceeds
    stall_after_s draws the stalled verdict: Stall: line + stall scalar,
    SIGKILL, and recovery through the ORDINARY gang restart."""
    # Incarnation 0: both alive forever (worker1 stalled); inc 1: exit 0.
    t = FakeTable({0: [[None], [0]], 1: [[None], [0]]})
    lines, writer = [], FakeWriter()
    gang = _stall_gang(
        t,
        {0: lambda: 1.0, 1: lambda: 99.0},  # worker1's beat is stale
        max_restarts=1, stall_after_s=5.0,
        print_fn=lines.append, summary_writer=writer,
    )
    assert gang.run() == 0
    assert gang.restarts == 1
    assert t.procs[(1, 0)].killed  # the stalled member was SIGKILLed
    (stall,) = [l for l in lines if l.startswith("Stall:")]
    assert "member=worker1" in stall
    assert "heartbeat_age_s=99.0" in stall and "stall_after_s=5.0" in stall
    (restart,) = [l for l in lines if l.startswith("Restart: restart=")]
    assert "worker1=stalled" in restart
    assert ("stall", 99.0, 0) in writer.scalars


def test_stall_never_beaten_or_fresh_age_not_judged():
    """None age (no heartbeat_fn / never beaten / probe failed) and ages
    below the threshold are NOT judgeable evidence; stall_after_s=0 (the
    default) disables the verdict entirely even for huge ages."""
    t = FakeTable({0: [[None, 0]], 1: [[None, None, 0]]})
    gang = _stall_gang(
        t, {0: None, 1: lambda: 0.5},  # worker0 unwired, worker1 fresh
        max_restarts=1, stall_after_s=5.0, print_fn=lambda *a: None,
    )
    assert gang.run() == 0 and gang.restarts == 0
    t2 = FakeTable({0: [[None, 0]]})
    gang2 = _stall_gang(  # default stall_after_s=0.0: watchdog off
        t2, {0: lambda: 1e9}, max_restarts=1, print_fn=lambda *a: None,
    )
    assert gang2.run() == 0 and gang2.restarts == 0


def test_stall_rc_verdict_takes_precedence():
    """A member that DIED is judged by its exit code, never double-
    verdicted as stalled (its heartbeat is naturally stale too)."""
    t = FakeTable({0: [[None], [0]], 1: [[9], [0]]})
    lines = []
    gang = _stall_gang(
        t, {0: lambda: 1.0, 1: lambda: 99.0},
        max_restarts=1, stall_after_s=5.0, print_fn=lines.append,
    )
    assert gang.run() == 0
    assert not any(l.startswith("Stall:") for l in lines)
    (restart,) = [l for l in lines if l.startswith("Restart: restart=")]
    assert "worker1=rc=9" in restart


def test_stall_broken_probe_is_not_a_verdict():
    """heartbeat_fn raising is a broken probe, not a stall."""
    def _boom():
        raise OSError("probe host gone")

    t = FakeTable({0: [[None, 0]]})
    gang = _stall_gang(
        t, {0: _boom}, max_restarts=1, stall_after_s=5.0,
        print_fn=lambda *a: None,
    )
    assert gang.run() == 0 and gang.restarts == 0


def test_stall_validation_rejects_negative():
    t = FakeTable({0: [[0]]})
    with pytest.raises(ValueError):
        _stall_gang(t, {}, stall_after_s=-1.0)
