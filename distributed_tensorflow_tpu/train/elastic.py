"""Elastic gang-restart: supervised multi-host recovery (round 7).

The reference's only failure behavior was gRPC blocking forever (SURVEY.md
§5 "Failure detection"); round 6 upgraded that to *fail-stop* — durable
CRC-verified checkpoints, preemption exit, anomaly rollback, and a chief
that detects a dead worker and ends the job cleanly (docs/multihost.md).
This module closes the loop from fail-stop to **fail-recover**: production
TPU training treats worker death as routine (PaLM-style runs restart the
gang from the newest checkpoint automatically; TorchElastic-style agents
supervise each rank under a restart budget), and round 6's durable
checkpoints are exactly the substrate that makes automatic restart correct.

Topology
--------
One :class:`ElasticAgent` per gang member, held by a driver (an
:class:`ElasticGang`): the agent spawns its worker process and watches two
signals —

- the **exit code** (a non-zero or premature exit is a death), and
- the **heartbeat verdict** from an agent-hosted detector
  (:class:`HeartbeatHealth` over ``runtime/native.py``'s UDP coordinator):
  beats stopped past ``timeout_ms`` is *dead*; beats flowing but the
  payload's monotonic progress counter frozen past ``stall_timeout_ms`` is
  *live-but-stalled* — the failure mode an exit code can never show (a rank
  hung in a collective keeps its native sender thread beating forever, and
  before round 7 the job simply hung with it).

On any failure the gang is restarted as a unit: every member is killed
(checkpoint state is durable; the dead epoch is repaid, not lost), the
restart budget (``TrainConfig.max_restarts``) is charged, the gang waits an
exponentially backed-off, jittered delay (``resilience.retry`` — the same
state machine checkpoint I/O uses), and every member is relaunched. The
relaunched processes re-bootstrap ``jax.distributed`` under
``cluster.bounded_initialize`` (bounded timeout + retry, so members that
come up before their coordinator get retried attempts instead of an
indefinite hang) and resume from the newest VALID checkpoint via
``Supervisor.prepare_or_restore``. Each restart emits a structured
``Restart:`` line and a ``restart`` tfevents scalar; an exhausted budget
falls back to round 6's fail-stop (non-zero driver exit, checkpoints
intact).

The detector is hosted by the AGENT, out-of-band of the job
(``cluster.bootstrap(heartbeat_host=...)``: every task, chief included,
becomes a plain sender) — in-band detection cannot recover a stall, because
the chief is stuck in the same collective as the stalled rank.

``tools/launch_local.py --max-restarts N`` is this module's multi-process
driver (the reference's nohup-per-task workflow, now supervised);
``tests/test_elastic.py`` pins the state machine on a fake process table
and ``tests/integration/test_fault_injection.py`` proves the SIGKILL →
gang-restart → resume → rc 0 path end to end.

Shrink-to-fit resize (round 8)
------------------------------
Round 7 only ever relaunched at the ORIGINAL world size: a permanently
lost host meant an infinite restart loop until the budget burned out.
With ``min_workers < len(agents)`` the gang **resizes instead of merely
restarting**: after a failure verdict, each failed member's slot gets up
to ``rejoin_timeout_s`` for a replacement to register
(``ElasticAgent.available``); slots still vacant at the deadline are
BENCHED and the surviving members relaunch alone at the reduced world
size — down to the ``min_workers`` floor, below which the gang fail-stops
(round 6 semantics). Relaunched members get compact ranks ``0..M-1`` via
``topo_spawn_fn(rank, world, ranks)``; the workers re-bootstrap
``jax.distributed`` at the new ``num_processes``
(``launch.cluster_from_env`` reads the driver-set ``DTF_WORLD_SIZE`` /
``DTF_WORKER_RANKS``) and ``Supervisor.prepare_or_restore`` restores the
old-world checkpoint onto the new mesh through the round-5 canonical
layer. While degraded, every poll also probes the benched slots: a
replacement registering triggers a GROW — the same save→kill→relaunch→
cross-restore cycle back toward the original world. Every resize
(either direction) charges the restart budget once and emits a
structured ``Resize:`` line plus a ``world_size`` tfevents scalar; a
replacement that registers INSIDE the rejoin window keeps round 7's
fixed-size restart path bit-for-bit (identical spawns, no ``Resize:``
line). ``min_workers`` defaults to the full gang size, which disables
resizing entirely — the round-7 machine, unchanged.

Serving-fleet reuse (round 16)
------------------------------
``serve_fleet.py`` supervises N TextServer replicas with the SAME
primitives — one :class:`ElasticAgent` per replica (spawn/poll/kill),
:class:`HttpHealth` verdicts over each replica's ``/healthz``,
``resilience.backoff_delay`` for the jittered relaunch schedule, the
same restart budget + bench-below-floor discipline — but WITHOUT gang
semantics: serving replicas share no collectives, so one death never
poisons the others, and members fail and restart independently while
the fleet keeps serving (the paper's async-beats-sync thesis applied
to the serving tier; docs/serving.md §fleet).

Independent members (round 17)
------------------------------
``ElasticGang(independent=True)`` imports that serving-fleet discipline
back into TRAINING gangs whose members share no collectives — the
stale-tolerant DiLoCo mailbox gang (train/local_sgd.DeltaExchange):
members exchange outer deltas through a filesystem mailbox at their own
pace, so one member's death cannot wedge a peer in a collective. A
failure verdict therefore relaunches ONLY the failed members (the
survivors keep training; the relaunched member resumes from its
checkpoint and rejoins the mailbox at the current round, its first
contribution staleness-weighted like any late delta). The restart
budget is charged per relaunch batch and exhaustion fail-stops exactly
like the gang path; resizing (``min_workers < len(agents)``) does not
compose — an independent member that never comes back is simply a peer
that stops posting. Drain/straggler verdicts are off (a slow member
finishing after its peers is the POINT); health-based verdicts get a
``member_grace_s`` window after each relaunch so a restarting member's
silence is not immediately re-verdicted.
"""

from __future__ import annotations

import os
import signal as _signal
import time
from typing import Callable, Sequence

from distributed_tensorflow_tpu.observability import journal as obs_journal
from distributed_tensorflow_tpu.observability.metrics import MetricsRegistry
from distributed_tensorflow_tpu.train import failpoints, resilience
from distributed_tensorflow_tpu.utils.summary import lifecycle_event



def children_platform(env: dict, count: int, who: str) -> str:
    """The platform ``count`` child processes started with ``env`` will
    run on, after checking that no chip gets a second owner. A chip
    belongs to one process: a parent that has touched JAX holds it, and a
    child that needs it then fails or hangs; and without a per-process
    device choice (not built — ROADMAP R3) every accelerator child on one
    host would open every local chip. So local children are CPU processes
    by an explicit ``JAX_PLATFORMS=cpu`` in their environment, or there is
    exactly one of them under a parent that never imported JAX. ``who``
    names the launcher in the refusal."""
    import sys

    platform = env.get("JAX_PLATFORMS", "")
    if platform == "cpu":
        return "cpu"
    if "jax" in sys.modules:
        raise RuntimeError(
            f"{who}: this process has imported JAX and may hold the chip, "
            "so children that are not CPU processes would fail or hang "
            "opening it; put JAX_PLATFORMS=cpu in their environment, or "
            "launch from a process that never imports JAX"
        )
    if count > 1:
        raise RuntimeError(
            f"{who}: {count} processes on platform "
            f"{platform or 'default'!r} would each open every local chip; "
            "per-process device choice is not built (ROADMAP R3) — "
            "launch one, or CPU processes with JAX_PLATFORMS=cpu"
        )
    return platform or "default"


class WorkerFailure(RuntimeError):
    """One or more gang members died or stalled. ``verdicts`` maps member
    name → verdict string (``rc=N``, ``dead``, ``stalled``, ``straggler``
    — still running past ``drain_timeout`` after a peer finished — or
    ``rejoined``: a benched member's replacement registered while the
    gang ran degraded, so the incarnation is retired to grow back)."""

    def __init__(self, verdicts: dict):
        self.verdicts = dict(verdicts)
        super().__init__(
            " ".join(f"{n}={v}" for n, v in sorted(self.verdicts.items()))
        )


class GangBelowFloor(WorkerFailure):
    """Resize planning left fewer than ``min_workers`` survivors: the gang
    fail-stops (round 6 semantics) instead of training on a mesh smaller
    than the operator said the job tolerates."""


class HeartbeatHealth:
    """Progress-aware health verdicts over the agent-hosted UDP detector.

    Owns a fresh ``HeartbeatCoordinator`` (one per gang incarnation — the
    gang recreates this each cycle so a relaunch never inherits the killed
    incarnation's stale last-seen clocks). ``classify(worker_id)`` returns:

    - ``"dead"`` — reported once then silent past ``timeout_ms``, or never
      reported and the grace window (default 5× timeout) has elapsed;
    - ``"stalled"`` — beating, but the payload's progress counter frozen
      past ``stall_timeout_ms`` (0 disables stall detection). Workers that
      never reported progress are not judged — startup import/compile must
      not read as a stall;
    - ``"ok"`` — otherwise.
    """

    def __init__(
        self,
        port: int,
        expected_workers: int,
        *,
        timeout_ms: int = 5000,
        stall_timeout_ms: int = 0,
        grace_ms: int | None = None,
        clock=time.monotonic,
    ):
        from distributed_tensorflow_tpu.runtime import native

        self._coord = native.HeartbeatCoordinator(
            port, expected_workers, timeout_ms=timeout_ms, grace_ms=grace_ms
        )
        self._timeout_ms = int(timeout_ms)
        self._stall_ms = int(stall_timeout_ms)
        self._grace_ms = int(grace_ms if grace_ms is not None else 5 * timeout_ms)
        self._clock = clock
        self._start = clock()

    def age_ms(self, worker_id: int) -> float:
        """Milliseconds since the member's last beat (-1: never seen) —
        the per-worker heartbeat-age gauge the gang exports (round 10)."""
        return float(self._coord.ms_since_seen(worker_id))

    def classify(self, worker_id: int) -> str:
        since = self._coord.ms_since_seen(worker_id)
        if since < 0:  # never reported
            elapsed_ms = (self._clock() - self._start) * 1000.0
            return "dead" if elapsed_ms > self._grace_ms else "ok"
        if since > self._timeout_ms:
            return "dead"
        if self._stall_ms > 0:
            since_progress = self._coord.ms_since_progress(worker_id)
            if since_progress > self._stall_ms:
                return "stalled"
        return "ok"

    def stop(self) -> None:
        self._coord.stop()


class HttpHealth:
    """:class:`HeartbeatHealth`'s verdicts over an HTTP ``/healthz``
    endpoint (observability/exporter.py) instead of the UDP detector —
    the probe the serving fleet router (serve_fleet.py) runs against its
    replicas, usable against any exporter-armed process.

    ``probe()`` fetches and parses the health document (returns None on
    any failure; the last good document stays at ``.last`` — it carries
    the ROUTING signals: ``queue_saturation``, ``slots_busy``,
    ``draining``). ``classify()`` mirrors the heartbeat verdicts:

    - ``"dead"`` — was reachable then unreachable past ``dead_after_s``,
      or never reachable and the startup ``grace_s`` elapsed (restore +
      first compile must not read as death);
    - ``"stalled"`` — reachable, but the payload's ``heartbeat_age_s``
      (time since the engine's last tick) exceeds ``stall_after_s``
      (0 disables) — the exporter thread answering while the engine loop
      is wedged, liveness without progress;
    - ``"ok"`` — otherwise.

    ``url`` may be a callable returning the URL (or None while unknown) —
    replicas that bind an ephemeral port publish it after startup, and an
    unknown URL counts as never-reachable. ``fetch``/``clock`` are
    injectable so the fast-tier router tests run without sockets."""

    def __init__(
        self,
        url,
        *,
        timeout_s: float = 2.0,
        dead_after_s: float = 5.0,
        grace_s: float = 60.0,
        stall_after_s: float = 0.0,
        fetch=None,
        clock=time.monotonic,
    ):
        self._url = url
        self._timeout_s = float(timeout_s)
        self._dead_after_s = float(dead_after_s)
        self._grace_s = float(grace_s)
        self._stall_after_s = float(stall_after_s)
        self._fetch = fetch if fetch is not None else self._http_fetch
        self._clock = clock
        self.last: dict | None = None
        self._last_ok: float | None = None
        self._start = clock()

    def _http_fetch(self, url: str) -> dict:
        import json as _json
        import urllib.request

        with urllib.request.urlopen(url, timeout=self._timeout_s) as resp:
            return _json.load(resp)

    def reset(self) -> None:
        """Fresh incarnation (a relaunched replica): forget the old
        endpoint's history and restart the never-reachable grace clock."""
        self.last = None
        self._last_ok = None
        self._start = self._clock()

    def probe(self) -> dict | None:
        url = self._url() if callable(self._url) else self._url
        if not url:
            return None
        try:
            # Failpoint inside the try: an injected raise IS a probe
            # failure — the classify() verdicts see exactly what a real
            # unreachable/hung endpoint produces.
            failpoints.fire("elastic.health")
            doc = self._fetch(url)
        except Exception:  # noqa: BLE001 — any probe failure is "no answer"
            return None
        if not isinstance(doc, dict):
            return None
        self.last = doc
        self._last_ok = self._clock()
        return doc

    def classify(self) -> str:
        doc = self.probe()
        now = self._clock()
        if doc is None:
            if self._last_ok is None:
                return "dead" if now - self._start > self._grace_s else "ok"
            return (
                "dead" if now - self._last_ok > self._dead_after_s else "ok"
            )
        if self._stall_after_s > 0:
            age = doc.get("heartbeat_age_s")
            if isinstance(age, (int, float)) and age > self._stall_after_s:
                return "stalled"
        return "ok"


class ElasticAgent:
    """Supervises ONE gang member: spawn, poll the exit code, kill.

    ``spawn_fn()`` returns a process handle exposing ``poll() -> rc|None``
    and ``kill()`` (``subprocess.Popen`` satisfies it; the fast-tier tests
    drive the whole machine with a fake process table). ``worker_id`` is
    the member's slot in the heartbeat detector.

    Resize hooks (round 8; both optional — absent, the agent is the
    round-7 fixed-slot member):

    - ``available_fn() -> bool`` — is this member's slot backed by a live
      host right now? Polled after a death (the rejoin window) and while
      the member sits benched (the grow trigger). ``None`` means always
      available — a dead member can always be relaunched in place, which
      is exactly round 7's fixed-size restart.
    - ``topo_spawn_fn(rank, world, ranks)`` — spawn this member at a
      NON-original topology: compact rank ``rank`` of ``world``, where
      ``ranks[r]`` is the original worker_id holding rank ``r`` (the
      driver exports it so workers can re-derive their cluster subset).
      Only consulted when the gang's current roster differs from the
      original; the original roster always spawns via ``spawn_fn()`` so a
      fully regrown gang is byte-identical to a fresh launch.

    Progress watchdog (round 22): ``heartbeat_fn() -> float | None``
    returns the seconds since this member's last progress beat (the
    launcher wires an mtime probe of ``<logdir>/worker<i>.heartbeat``),
    or None when the member has never beaten — startup/first-compile is
    not judged. The gang's stall verdict reads it through
    :meth:`heartbeat_age`."""

    def __init__(
        self,
        name: str,
        spawn_fn: Callable,
        *,
        worker_id: int | None = None,
        available_fn: Callable[[], bool] | None = None,
        topo_spawn_fn: Callable | None = None,
        heartbeat_fn: Callable[[], float | None] | None = None,
    ):
        self.name = name
        self.worker_id = worker_id
        self._spawn_fn = spawn_fn
        self.available_fn = available_fn
        self.topo_spawn_fn = topo_spawn_fn
        self.heartbeat_fn = heartbeat_fn
        self.handle = None

    def available(self) -> bool:
        """Is this member's slot backed by a live host? (See class doc.)"""
        return True if self.available_fn is None else bool(self.available_fn())

    def start(self, rank: int | None = None, world: int | None = None,
              ranks: tuple | None = None):
        failpoints.fire("elastic.relaunch")
        if rank is None:
            self.handle = self._spawn_fn()
        else:
            if self.topo_spawn_fn is None:
                raise RuntimeError(
                    f"{self.name}: gang resized to world={world} but this "
                    "agent has no topo_spawn_fn — pass one (or keep "
                    "min_workers at the full gang size to disable resizing)"
                )
            self.handle = self.topo_spawn_fn(rank, world, ranks)
        return self.handle

    def poll(self):
        """Exit code, or None (running / not yet started)."""
        return None if self.handle is None else self.handle.poll()

    def heartbeat_age(self) -> float | None:
        """Seconds since the member's last progress beat, or None (no
        ``heartbeat_fn`` wired, never beaten, or the probe failed —
        none of which is judgeable evidence of a stall)."""
        if self.heartbeat_fn is None:
            return None
        try:
            age = self.heartbeat_fn()
        except Exception:  # noqa: BLE001 — a broken probe is not a verdict
            return None
        return None if age is None else float(age)

    def request_dump(self) -> bool:
        """Best-effort SIGUSR1 to the member: its ``faulthandler`` dump
        (armed via ``resilience.arm_stall_dump`` / ``$DTF_STALL_DUMP``)
        lands all-thread stacks in the logdir. faulthandler's handler is
        C-level, so a rank wedged inside a collective CAN still dump; a
        SIGSTOPped one cannot (the signal queues until SIGCONT) — the
        stall verdict never waits on the dump."""
        pid = getattr(self.handle, "pid", None)
        usr1 = getattr(_signal, "SIGUSR1", None)
        if pid is None or usr1 is None:
            return False
        try:
            os.kill(pid, usr1)
            return True
        except OSError:
            return False

    def kill(self) -> None:
        """Hard-kill a live member (SIGKILL semantics — a rank hung in a
        collective ignores SIGTERM forever; its state is durable in the
        checkpoint, so the restart repays at most one epoch)."""
        if self.handle is None or self.handle.poll() is not None:
            return
        self.handle.kill()
        wait = getattr(self.handle, "wait", None)
        if wait is not None:  # reap, so the driver never accumulates zombies
            try:
                wait(timeout=30)
            except Exception:  # noqa: BLE001 — unkillable is the OS's problem
                pass


class ElasticGang:
    """The driver: N agents supervised as one gang under a restart budget.

    ``run()`` starts every member and polls until either every member has
    exited 0 (return 0) or a failure verdict lands — non-zero exit, dead,
    or stalled — at which point every live member is killed and the gang is
    relaunched after an exponentially backed-off, jittered delay, at most
    ``max_restarts`` times (``resilience.retry`` is the backoff state
    machine; ``max_restarts=0`` preserves round 6's fail-stop exactly:
    first failure → kill survivors → return 1). Each restart emits a
    structured ``Restart:`` line and, when a ``summary_writer`` is given, a
    ``restart`` tfevents scalar (value = restart ordinal).

    ``health_factory`` builds a fresh :class:`HeartbeatHealth` per gang
    incarnation (fresh detector state — a relaunch must not inherit the
    killed incarnation's silence); it may take one positional argument
    (the incarnation's world size) so a resized gang's detector expects
    the right member count. Once the first member exits 0, the rest
    must finish within ``drain_timeout`` seconds or the still-running
    members are verdicted ``straggler`` (a peer wedged in a collective the
    finished member will never rejoin beats forever — without the drain
    window the gang would hang with no verdict). ``sleep``/``clock``/
    ``poll_interval`` are injectable so the fast-tier tests run the whole
    machine without wall time or real processes.

    Resize (round 8): ``min_workers < len(agents)`` arms shrink-to-fit —
    see the module docstring for the full state machine. ``min_workers``
    defaults to the full gang size (resizing disabled: the round-7
    machine bit-for-bit). ``rejoin_timeout_s`` is how long a failed
    member's slot may stay vacant before the gang gives up on a
    replacement and relaunches without it; 0 decides immediately from
    one ``available()`` probe. The current roster is ``active`` (rank
    order); benched members are probed every poll and re-admitted — the
    grow path — by the same kill→relaunch→restore cycle. Every resize,
    either direction, charges one unit of the restart budget: a
    flapping host cannot spin the gang for free."""

    def __init__(
        self,
        agents: Sequence[ElasticAgent],
        *,
        max_restarts: int = 0,
        backoff: float = 1.0,
        max_backoff: float = 30.0,
        jitter: float = 0.25,
        health_factory: Callable[..., HeartbeatHealth] | None = None,
        poll_interval: float = 0.5,
        drain_timeout: float = 300.0,
        min_workers: int | None = None,
        rejoin_timeout_s: float = 0.0,
        independent: bool = False,
        member_grace_s: float = 60.0,
        stall_after_s: float = 0.0,
        print_fn=print,
        summary_writer=None,
        journal=None,
        metrics: MetricsRegistry | None = None,
        sleep=time.sleep,
        clock=time.monotonic,
        rng=None,
    ):
        self.agents = list(agents)
        self.max_restarts = int(max_restarts)
        self.backoff = float(backoff)
        self.max_backoff = float(max_backoff)
        self.jitter = float(jitter)
        self.health_factory = health_factory
        self.poll_interval = float(poll_interval)
        self.drain_timeout = float(drain_timeout)
        self.min_workers = (
            len(self.agents) if min_workers is None else int(min_workers)
        )
        if not 1 <= self.min_workers <= len(self.agents):
            raise ValueError(
                f"min_workers must be in [1, {len(self.agents)}] "
                f"(= gang size), got {self.min_workers}"
            )
        self.rejoin_timeout_s = float(rejoin_timeout_s)
        if self.rejoin_timeout_s < 0:
            raise ValueError(
                f"rejoin_timeout_s must be >= 0, got {self.rejoin_timeout_s}"
            )
        self.independent = bool(independent)
        self.member_grace_s = float(member_grace_s)
        # Progress watchdog (round 22): a member whose process is ALIVE
        # but whose heartbeat file has not moved for stall_after_s gets a
        # "stalled" verdict — the SIGSTOP / wedged-collective class that
        # rc= polls and health probes can never see (mirror of the
        # round-21 breaker's frozen-replica reasoning). 0 disables. Size
        # it above the worst-case epoch + first-compile latency — the
        # never-beaten startup phase is not judged, but a long compile
        # BETWEEN beats is.
        self.stall_after_s = float(stall_after_s)
        if self.stall_after_s < 0:
            raise ValueError(
                f"stall_after_s must be >= 0, got {self.stall_after_s}"
            )
        if self.independent and self._elastic:
            raise ValueError(
                "independent=True does not compose with shrink-to-fit "
                "resizing (min_workers < gang size): independent members "
                "relaunch alone — a member that never comes back is a "
                "peer that stops posting, not a smaller mesh"
            )
        # clock() time until which each member's health verdicts are
        # suppressed (armed at its independent relaunch — a restarting
        # member's silence must not read as a fresh death).
        self._member_grace_until: dict[str, float] = {}
        self.print_fn = print_fn
        self.summary_writer = summary_writer
        # Telemetry (round 10): Restart:/Resize: lines become journal
        # events (rendered back byte-identically); the registry carries
        # restart/resize counters, the world-size gauge, and per-worker
        # heartbeat age. Defaults keep the round-7/8 surface untouched.
        self.journal = journal if journal is not None else obs_journal.get_journal()
        self.metrics = metrics if metrics is not None else MetricsRegistry()
        self.sleep = sleep
        self.clock = clock
        self.rng = rng
        self.restarts = 0  # restarts actually performed
        self.resizes = 0  # topology changes actually performed
        # Roster state: active members in rank order; benched members are
        # slots whose host did not come back inside the rejoin window.
        self.active: list[ElasticAgent] = list(self.agents)
        self.benched: list[ElasticAgent] = []

    @property
    def world_size(self) -> int:
        return len(self.active)

    @property
    def _elastic(self) -> bool:
        return self.min_workers < len(self.agents)

    # -- one gang incarnation --------------------------------------------

    def _make_health(self, world: int):
        """health_factory, passing the incarnation's world size when the
        factory takes a positional argument (round-7 zero-arg factories
        keep working unchanged)."""
        if self.health_factory is None:
            return None
        import inspect

        try:
            params = inspect.signature(self.health_factory).parameters.values()
            takes_world = any(
                p.kind
                in (
                    inspect.Parameter.POSITIONAL_ONLY,
                    inspect.Parameter.POSITIONAL_OR_KEYWORD,
                    inspect.Parameter.VAR_POSITIONAL,
                )
                for p in params
            )
        except (TypeError, ValueError):  # builtins without signatures
            takes_world = False
        return self.health_factory(world) if takes_world else self.health_factory()

    def _cycle(self) -> int:
        health = None
        first_done = None  # clock() when the first member exited 0
        # Identity roster (never resized, or fully regrown): the round-7
        # spawn path byte-for-byte — agents spawn via spawn_fn() with
        # their original worker_id as the detector slot. A resized roster
        # spawns with compact ranks 0..M-1 (topo_spawn_fn) and the
        # detector tracks those ranks (workers report worker_id =
        # task_index, which IS the compact rank after a resize).
        identity = self.active == self.agents
        ranks = tuple(
            a.worker_id if a.worker_id is not None else self.agents.index(a)
            for a in self.active
        )
        try:
            for rank, agent in enumerate(self.active):
                if identity:
                    agent.start()
                else:
                    agent.start(rank, len(self.active), ranks)
            health = self._make_health(len(self.active))
            while True:
                rcs = {a.name: a.poll() for a in self.active}
                verdicts = {
                    name: f"rc={rc}"
                    for name, rc in rcs.items()
                    if rc is not None and rc != 0
                }
                if health is not None:
                    for rank, a in enumerate(self.active):
                        wid = a.worker_id if identity else rank
                        if rcs[a.name] is None and wid is not None:
                            if hasattr(health, "age_ms"):
                                self.metrics.gauge(
                                    "heartbeat_age_ms",
                                    labels={"worker": a.name},
                                ).set(health.age_ms(wid))
                            if (
                                self.independent
                                and self._member_grace_until.get(a.name, 0)
                                > self.clock()
                            ):
                                continue  # relaunching: not judged yet
                            v = health.classify(wid)
                            if v != "ok":
                                verdicts[a.name] = v
                # Stall verdict (round 22): alive, past any rc/health
                # verdict, but the progress heartbeat is stale. Emit the
                # Stall: line, ask the member for its faulthandler dump
                # (best-effort), SIGKILL it, and hand the verdict to the
                # EXISTING recovery machinery (gang restart, shrink/
                # rejoin, or independent relaunch — nothing new below).
                if self.stall_after_s > 0:
                    for a in self.active:
                        if rcs[a.name] is not None or a.name in verdicts:
                            continue
                        age = a.heartbeat_age()
                        if age is not None and age > self.stall_after_s:
                            lifecycle_event(
                                "stall",
                                print_fn=self.print_fn,
                                journal=self.journal,
                                writer=self.summary_writer,
                                scalar=("stall", float(age), self.restarts),
                                member=a.name,
                                age_s=round(float(age), 3),
                                stall_after_s=self.stall_after_s,
                            )
                            self.metrics.counter("stalls_total").inc()
                            a.request_dump()
                            a.kill()
                            verdicts[a.name] = "stalled"
                if verdicts and self.independent:
                    # Independent members (module docstring): relaunch
                    # ONLY the failed members; survivors keep running.
                    # Budget exhaustion falls through to the gang-kill
                    # fail-stop below.
                    if self.restarts < self.max_restarts:
                        self._restart_members(verdicts)
                        continue
                    for a in self.agents:
                        a.kill()
                    raise WorkerFailure(verdicts)
                # Grow trigger: a benched slot's replacement registered
                # while the gang ran degraded. Retire the incarnation
                # (kill + relaunch at the bigger world) — unless someone
                # already finished cleanly, in which case the gang is
                # draining and growing would restart a completed job.
                if (
                    not verdicts
                    and self.benched
                    and not any(rc == 0 for rc in rcs.values())
                ):
                    back = [a for a in self.benched if a.available()]
                    if back:
                        verdicts = {a.name: "rejoined" for a in back}
                # Premature-exit guard: once any member finishes (rc 0),
                # the rest must drain within drain_timeout — a peer blocked
                # in a collective the finished member will never rejoin
                # would otherwise beat forever ("ok" to health) and hang
                # the gang with no verdict at all. Staggered-but-honest
                # completion finishes well inside the window. OFF for
                # independent members: they share no collectives, and a
                # slow member finishing long after its peers is exactly
                # the staleness the mailbox gang tolerates.
                if (
                    not verdicts
                    and not self.independent
                    and any(rc == 0 for rc in rcs.values())
                ):
                    if first_done is None:
                        first_done = self.clock()
                    elif self.clock() - first_done > self.drain_timeout:
                        verdicts = {
                            name: "straggler"
                            for name, rc in rcs.items()
                            if rc is None
                        }
                if verdicts:
                    # Gang semantics: one bad member poisons the incarnation
                    # (its peers are blocked in collectives it will never
                    # join) — kill every survivor and hand the verdicts up.
                    for a in self.agents:
                        a.kill()
                    raise WorkerFailure(verdicts)
                if all(rc == 0 for rc in rcs.values()):
                    return 0
                self.sleep(self.poll_interval)
        except WorkerFailure:
            raise
        except BaseException:
            # Not a gang verdict: spawn/detector failure (e.g. the
            # heartbeat port got grabbed between incarnations) or a driver
            # bug. The already-started members must not outlive the driver
            # as orphans holding the checkpoint dir.
            for agent in self.agents:
                agent.kill()
            raise
        finally:
            if health is not None:
                health.stop()

    def _restart_members(self, verdicts: dict) -> None:
        """Independent-mode relaunch: kill + backoff + respawn ONLY the
        verdicted members (one restart charged for the batch); arms each
        member's health grace window. Callers have already checked the
        budget."""
        self.restarts += 1
        self.metrics.counter("restarts_total").inc()
        delay = resilience.backoff_delay(
            self.restarts - 1,
            backoff=self.backoff,
            max_backoff=self.max_backoff,
            jitter=self.jitter,
            rng=self.rng,
        )
        lifecycle_event(
            "restart",
            print_fn=self.print_fn,
            journal=self.journal,
            writer=self.summary_writer,
            scalar=("restart", float(self.restarts), self.restarts),
            restart=self.restarts,
            max_restarts=self.max_restarts,
            cause=str(WorkerFailure(verdicts)),
            backoff_s=float(delay),
            independent=True,
            members=sorted(verdicts),
        )
        failed = [a for a in self.active if a.name in verdicts]
        for a in failed:
            a.kill()
        self.sleep(delay)
        for a in failed:
            a.start()
            self._member_grace_until[a.name] = (
                self.clock() + self.member_grace_s
            )

    def _plan_topology(self, exc: WorkerFailure) -> None:
        """Recompute the roster after a failure verdict (no-op unless
        ``min_workers < len(agents)``): give each failed member's slot up
        to ``rejoin_timeout_s`` to come back (``available()``), bench the
        slots that did not, re-admit benched slots that did — then either
        raise :class:`GangBelowFloor` (fewer than ``min_workers`` left) or
        record the resize with a structured ``Resize:`` line and a
        ``world_size`` tfevents scalar. Rosters rebuild in ORIGINAL agent
        order, so a fully regrown gang restores the original ranks (and
        spawns via the original, pre-resize path)."""
        if not self._elastic:
            return
        prev = list(self.active)
        failed = [
            a
            for a in self.active
            if exc.verdicts.get(a.name) not in (None, "rejoined")
        ]
        # Rejoin window: poll the failed slots until each has a
        # replacement or the budget runs out. available_fn=None (always
        # available) resolves instantly — the fixed-size restart.
        missing = [a for a in failed if not a.available()]
        if missing and self.rejoin_timeout_s > 0:
            deadline = self.clock() + self.rejoin_timeout_s
            wait = min(self.poll_interval, self.rejoin_timeout_s) or (
                self.rejoin_timeout_s
            )
            while missing and self.clock() < deadline:
                self.sleep(wait)
                missing = [a for a in missing if not a.available()]
        bench = set(missing)
        roster = []
        for a in self.agents:  # original order: a regrow restores ranks
            if a in bench:
                continue
            if a in self.benched and not a.available():
                continue
            roster.append(a)
        if roster == prev:
            return  # replacement(s) arrived in time: fixed-size restart
        if len(roster) < self.min_workers:
            floor = GangBelowFloor(exc.verdicts)
            floor.world = len(roster)
            raise floor
        dropped = [a.name for a in prev if a not in roster]
        rejoined = [a.name for a in roster if a not in prev]
        self.active = roster
        self.benched = [a for a in self.agents if a not in roster]
        self.resizes += 1
        self.metrics.counter("resizes_total").inc()
        self.metrics.gauge("world_size").set(len(roster))
        direction = (
            "shrink"
            if len(roster) < len(prev)
            else ("grow" if len(roster) > len(prev) else "swap")
        )
        # Structured, greppable — same key=value shape as Restart:. One
        # lifecycle_event fans out: stdout line + journal event + the
        # world_size tfevents scalar (utils/summary.py, round 10).
        lifecycle_event(
            "resize",
            print_fn=self.print_fn,
            journal=self.journal,
            writer=self.summary_writer,
            scalar=("world_size", float(len(roster)), self.restarts),
            world=len(roster),
            from_world=len(prev),
            min_workers=self.min_workers,
            direction=direction,
            dropped=dropped,
            rejoined=rejoined,
            restart=self.restarts,
            max_restarts=self.max_restarts,
        )

    def _on_retry(self, exc: WorkerFailure, attempt: int, delay: float) -> None:
        self.restarts = attempt + 1
        self.metrics.counter("restarts_total").inc()
        # Structured, greppable — same key=value shape as Preemption:/
        # Rollback:; the lifecycle_event fans out stdout + journal +
        # the restart tfevents scalar.
        lifecycle_event(
            "restart",
            print_fn=self.print_fn,
            journal=self.journal,
            writer=self.summary_writer,
            scalar=("restart", float(self.restarts), self.restarts),
            restart=self.restarts,
            max_restarts=self.max_restarts,
            cause=str(exc),
            backoff_s=float(delay),
        )
        # After the Restart bookkeeping: decide WHAT relaunches (may wait
        # the rejoin window, may shrink/grow, may raise GangBelowFloor —
        # which aborts the retry loop into run()'s fail-stop).
        self._plan_topology(exc)

    def run(self) -> int:
        """Supervise to completion: 0 when every member exited 0 (possibly
        after restarts and resizes), 1 when the budget is exhausted or the
        roster fell below ``min_workers`` (fail-stop, with a final
        structured line; checkpoints intact)."""
        from distributed_tensorflow_tpu.observability import tracing

        # One trace id per supervision (round 12): every Restart:/Resize:
        # journal event of this gang's life joins under it, so a shared
        # driver journal separates overlapping gangs.
        with tracing.trace(tracing.current_trace()):
            return self._run_supervised()

    def _run_supervised(self) -> int:
        self.metrics.gauge("world_size").set(len(self.active))
        if self.summary_writer is not None and self._elastic:
            # Initial world size, so the scalar stream starts at the
            # launched topology (resizes append to it at their restart
            # ordinal). Only in elastic mode: a fixed-size gang's tfevents
            # stay byte-identical to round 7.
            self.summary_writer.add_scalar(
                "world_size", float(len(self.active)), 0
            )
        try:
            if self.independent:
                # One incarnation for the whole run: member failures are
                # handled INSIDE _cycle (relaunch-alone) under the same
                # budget; a WorkerFailure escaping means the budget is
                # spent — the except below fail-stops it like an
                # exhausted retry loop.
                return self._cycle()
            return resilience.retry(
                self._cycle,
                attempts=self.max_restarts + 1,
                backoff=self.backoff,
                max_backoff=self.max_backoff,
                jitter=self.jitter,
                retry_on=(WorkerFailure,),
                describe="gang restart",
                on_retry=self._on_retry,
                sleep=self.sleep,
                rng=self.rng,
            )
        except GangBelowFloor as exc:
            lifecycle_event(
                "resize_denied",
                print_fn=self.print_fn,
                journal=self.journal,
                world=exc.world,
                min_workers=self.min_workers,
                restarts=self.restarts,
                max_restarts=self.max_restarts,
                cause=str(exc),
            )
            if self.summary_writer is not None:
                self.summary_writer.flush()
            return 1
        except WorkerFailure as exc:
            lifecycle_event(
                "restart_exhausted",
                print_fn=self.print_fn,
                journal=self.journal,
                restarts=self.restarts,
                max_restarts=self.max_restarts,
                cause=str(exc),
            )
            if self.summary_writer is not None:
                self.summary_writer.flush()
            return 1
        finally:
            self.metrics.flush_to(self.journal, component="elastic")
            self.journal.flush()
            if self.summary_writer is not None and self.restarts:
                self.summary_writer.flush()
