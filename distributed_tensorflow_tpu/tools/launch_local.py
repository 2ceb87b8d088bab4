"""Local cluster launcher — the reference's nohup-per-task workflow, automated
and (round 7) supervised.

The reference ran every topology by hand-launching one process per task::

    nohup python tfdist_between.py --job_name=ps --task_index=0 > ps.log 2>&1 &
    nohup python tfdist_between.py --job_name=worker --task_index=0 > w0.log ...

(reference README.md:34-35, 58-60; C17 in SURVEY.md §2). This tool does the
same thing in one command, against any script that accepts the standard
``--job_name/--task_index`` flags::

    python -m distributed_tensorflow_tpu.tools.launch_local \
        --workers 2 --ps 1 --logdir ./task_logs -- python examples/between_sync.py

One OS process per task, stdout/stderr redirected to ``<logdir>/<role><i>.log``
exactly like the nohup recipe, non-zero exit if any worker fails. ps tasks
are launched too (they no-op and exit, preserving launcher compatibility).

``--max-restarts N`` (round 7) upgrades the one-shot spawner into the
elastic agent's driver (train/elastic.py): each worker gets a supervising
:class:`ElasticAgent`; a member that exits non-zero — or, with
``--heartbeat-port``, goes heartbeat-dead or live-but-stalled past
``--stall-timeout-ms`` — triggers a GANG restart: every worker is killed
and relaunched after a jittered exponential backoff, at most N times, with
a structured ``Restart:`` line and a ``restart`` tfevents scalar per event.
Relaunched workers re-bootstrap ``jax.distributed`` (bounded retried
initialize, ``cluster.bounded_initialize``) and resume from the newest
valid checkpoint — arm ``DTF_CHECKPOINT`` so there is something to resume.
The driver hosts the heartbeat detector itself (out-of-band of the job)
and points the workers at it via ``DTF_HEARTBEAT_HOST``/``_PORT``;
``max_restarts=0`` (default) preserves the old fail-stop behavior exactly.

``--min-workers M`` (round 8) arms shrink-to-fit resize on top: a worker
whose slot is LOST — marker file ``<logdir>/worker<i>.lost`` present, the
driver's host-availability probe — and not replaced within
``--rejoin-timeout-s`` is benched, and the survivors relaunch alone at
the reduced world size (down to M; below: fail-stop). Resized
incarnations are spawned with compact ``--task_index`` ranks and
``DTF_WORLD_SIZE``/``DTF_WORKER_RANKS`` in the env, which
``launch.cluster_from_env`` resolves to the surviving sub-cluster
(``ClusterConfig.subset``) so the workers re-bootstrap
``jax.distributed`` at the new ``num_processes`` and cross-restore the
old-world checkpoint. Deleting the ``.lost`` marker registers a
replacement: the gang grows back at the next poll. An external scheduler
manages the markers in production; ``--drive-mode
kill-without-replace|kill-then-replace`` makes the driver itself stage
the scenario (SIGKILL the highest worker after ``--drive-after-s``, mark
it lost, and — in then-replace mode — clear the marker after
``--drive-replace-after-s``) for demos and the integration tests.
"""

from __future__ import annotations

import argparse
import os
import subprocess
import sys
import threading
import time


def _spawn_task(
    command: list[str],
    role: str,
    index: int,
    logdir: str,
    env: dict,
    mode: str = "wb",
    log_index: int | None = None,
):
    """One task process, stdout+stderr to ``<logdir>/<role><i>.log``. The
    first incarnation truncates (the pre-round-7 behavior, unchanged); a
    gang RELAUNCH passes ``mode="ab"`` so the restarted incarnation's log
    continues the same file instead of erasing the failure it is
    recovering from. ``log_index`` keeps the log under the member's
    ORIGINAL id when a resize remaps ``index`` to a compact rank (one
    member, one log file, across every topology it serves in)."""
    log_path = os.path.join(
        logdir, f"{role}{index if log_index is None else log_index}.log"
    )
    f = open(log_path, mode)
    try:
        return subprocess.Popen(
            command + [f"--job_name={role}", f"--task_index={index}"],
            stdout=f,
            stderr=subprocess.STDOUT,
            env=env,
        )
    finally:
        # Popen inherited the descriptor; closing ours leaks nothing and a
        # relaunch reopens fresh (no shared offsets across incarnations).
        f.close()


def lost_marker(logdir: str, worker: int) -> str:
    """Path of worker ``i``'s host-lost marker: present = no host backs
    the slot (the driver's availability probe); deleting it registers a
    replacement. The file-based contract keeps the probe scriptable by
    any external scheduler."""
    return os.path.join(logdir, f"worker{worker}.lost")


def heartbeat_file(logdir: str, worker: int) -> str:
    """Path of worker ``i``'s progress-heartbeat file (round 22): the
    trainer mtime-bumps it at every step/epoch boundary
    (Supervisor.report_progress via ``DTF_HEARTBEAT_FILE``); the driver's
    watchdog reads its age. File-based like the lost marker — any
    external scheduler can watch it."""
    return os.path.join(logdir, f"worker{worker}.heartbeat")


def _launch_elastic(
    command: list[str],
    num_workers: int,
    logdir: str,
    base_env: dict,
    *,
    max_restarts: int,
    heartbeat_port: int | None,
    heartbeat_timeout_ms: int,
    heartbeat_grace_ms: int | None,
    stall_timeout_ms: int,
    stall_after_s: float = 0.0,
    backoff: float = 1.0,
    poll_interval: float = 0.5,
    min_workers: int | None = None,
    rejoin_timeout_s: float = 30.0,
    independent: bool = False,
    drive_mode: str | None = None,
    drive_after_s: float = 8.0,
    drive_replace_after_s: float = 10.0,
    metrics_port: int | None = None,
    print_fn=print,
) -> int:
    from distributed_tensorflow_tpu.train.elastic import (
        ElasticAgent,
        ElasticGang,
        HeartbeatHealth,
    )

    env = dict(base_env)
    health_factory = None
    summary_writer = None
    if heartbeat_port:
        # The driver hosts the detector (out-of-band of the job); workers
        # learn where to beat from the env, chief included
        # (cluster.bootstrap heartbeat_host mode).
        env["DTF_HEARTBEAT_HOST"] = "127.0.0.1"
        env["DTF_HEARTBEAT_PORT"] = str(heartbeat_port)
        env["DTF_HEARTBEAT_TIMEOUT_MS"] = str(heartbeat_timeout_ms)
        try:
            from distributed_tensorflow_tpu.runtime import native

            native.load_library()

            def health_factory(world=num_workers):
                # world: the incarnation's member count — a shrunk gang's
                # detector must expect M compact ranks, not N.
                return HeartbeatHealth(
                    heartbeat_port,
                    world,
                    timeout_ms=heartbeat_timeout_ms,
                    stall_timeout_ms=stall_timeout_ms,
                    grace_ms=heartbeat_grace_ms,
                )

        except (ImportError, OSError) as exc:
            # Same degrade set as cluster.bootstrap: a corrupt/wrong-arch
            # .so raises OSError from ctypes, not ImportError.
            print_fn(
                f"elastic: heartbeat detector unavailable ({exc}); "
                "supervising exit codes only"
            )
            env.pop("DTF_HEARTBEAT_HOST")
            env.pop("DTF_HEARTBEAT_PORT")
            env.pop("DTF_HEARTBEAT_TIMEOUT_MS")
    try:
        from distributed_tensorflow_tpu.utils.summary import SummaryWriter

        summary_writer = SummaryWriter(logdir, filename_suffix=".elastic")
    except OSError:  # pragma: no cover — unwritable logdir already raised
        summary_writer = None
    # The driver's event journal (round 10): <logdir>/events.jsonl carries
    # every Restart:/Resize: as a typed event plus the gang's metrics
    # snapshot — tools/obs_report.py replays the run from it.
    from distributed_tensorflow_tpu.observability import EventJournal

    run_id = f"elastic-{os.getpid()}"
    journal = EventJournal.in_dir(logdir, run_id=run_id, world=num_workers)
    # Per-rank worker journals (round 12): workers that bootstrap (or
    # call journal.configure_from_env) land their own
    # <logdir>/events-rank<i>.jsonl next to the driver's events.jsonl —
    # the files obs_report --gang merges into the fleet timeline.
    env["DTF_JOURNAL_DIR"] = logdir
    env["DTF_RUN_ID"] = run_id

    launched: set[int] = set()

    def _worker_env(i: int) -> dict:
        wenv = dict(env)
        wenv["DTF_RANK"] = str(i)  # the member's ORIGINAL id (log convention)
        # Progress watchdog (round 22): the trainer mtime-bumps this file
        # at step/epoch boundaries; SIGUSR1 makes the member dump all
        # stacks to the .stalldump before the watchdog kills it.
        wenv["DTF_HEARTBEAT_FILE"] = heartbeat_file(logdir, i)
        wenv["DTF_STALL_DUMP"] = os.path.join(logdir, f"worker{i}.stalldump")
        return wenv

    def _clear_heartbeat(i: int) -> None:
        # A fresh incarnation must start never-beaten — a stale mtime from
        # the previous life would age straight into a spurious stall
        # verdict (or mask a hung restart with a recent-looking beat).
        try:
            os.remove(heartbeat_file(logdir, i))
        except OSError:
            pass

    def _make_spawn(i: int):
        def _spawn():
            mode = "ab" if i in launched else "wb"
            launched.add(i)
            _clear_heartbeat(i)
            return _spawn_task(
                command, "worker", i, logdir, _worker_env(i), mode=mode
            )

        return _spawn

    def _make_topo_spawn(i: int):
        def _spawn(rank: int, world: int, ranks):
            # A resized incarnation: compact --task_index, the topology in
            # the env (launch.cluster_from_env → ClusterConfig.subset), the
            # log continuing under the member's ORIGINAL id.
            launched.add(i)
            _clear_heartbeat(i)
            tenv = _worker_env(i)
            tenv["DTF_WORLD_SIZE"] = str(world)
            tenv["DTF_WORKER_RANKS"] = ",".join(str(r) for r in ranks)
            return _spawn_task(
                command, "worker", rank, logdir, tenv, mode="ab", log_index=i
            )

        return _spawn

    def _make_heartbeat(i: int):
        def _age() -> float | None:
            # Wall-clock age of the member's last progress beat; None
            # (never judged) while the file doesn't exist yet — startup
            # and first-compile latency never read as a stall.
            try:
                return time.time() - os.path.getmtime(heartbeat_file(logdir, i))
            except OSError:
                return None

        return _age

    def _make_available(i: int):
        def _available():
            return not os.path.exists(lost_marker(logdir, i))

        return _available

    elastic_resize = min_workers is not None and 0 < min_workers < num_workers
    agents = [
        ElasticAgent(
            f"worker{i}",
            _make_spawn(i),
            worker_id=i,
            available_fn=_make_available(i) if elastic_resize else None,
            topo_spawn_fn=_make_topo_spawn(i) if elastic_resize else None,
            heartbeat_fn=_make_heartbeat(i),
        )
        for i in range(num_workers)
    ]
    gang = ElasticGang(
        agents,
        max_restarts=max_restarts,
        backoff=backoff,
        health_factory=health_factory,
        poll_interval=poll_interval,
        min_workers=min_workers if elastic_resize else None,
        rejoin_timeout_s=rejoin_timeout_s,
        independent=independent,
        stall_after_s=stall_after_s,
        print_fn=print_fn,
        summary_writer=summary_writer,
        journal=journal,
    )
    if drive_mode:
        # Scenario driver (demos + integration tests): SIGKILL the highest
        # worker after a delay and mark its host lost; then-replace mode
        # later clears the marker, which the gang reads as a replacement
        # registering (grow trigger).
        victim = num_workers - 1

        def _drive():
            time.sleep(drive_after_s)
            open(lost_marker(logdir, victim), "w").close()
            handle = agents[victim].handle
            if handle is not None:
                try:
                    handle.kill()
                except Exception:  # noqa: BLE001 — already exited
                    pass
            if drive_mode == "kill-then-replace":
                time.sleep(drive_replace_after_s)
                try:
                    os.remove(lost_marker(logdir, victim))
                except OSError:
                    pass

        threading.Thread(target=_drive, daemon=True).start()
    exporter = None
    if metrics_port:
        # Live driver endpoint (round 12): /metrics scrapes the gang's
        # registry (restarts/resizes/world_size/heartbeat ages) while it
        # supervises; /healthz reports the roster the scheduler needs.
        from distributed_tensorflow_tpu.observability import MetricsExporter

        exporter = MetricsExporter(
            gang.metrics,
            port=int(metrics_port),
            health_fn=lambda: {
                "world_size": gang.world_size,
                "restarts": gang.restarts,
                "resizes": gang.resizes,
                "benched": [a.name for a in gang.benched],
            },
        )
        print_fn(f"metrics: http://127.0.0.1:{exporter.start()}/metrics")
    try:
        rc = gang.run()
    finally:
        if exporter is not None:
            exporter.stop()
    journal.close()
    for agent in agents:
        code = agent.poll()
        print_fn(f"{agent.name}: exit {code}")
    return rc


def launch(
    command: list[str],
    num_workers: int,
    num_ps: int = 0,
    logdir: str = "./task_logs",
    env: dict | None = None,
    wait: bool = True,
    *,
    max_restarts: int = 0,
    heartbeat_port: int | None = None,
    heartbeat_timeout_ms: int = 5000,
    # Never-beaten grace before a worker reads as dead. The default (5x
    # timeout via HeartbeatHealth) is 25 s at the default timeout — on a
    # loaded host a cold Python+jax import can exceed that, so raise this
    # (or the timeout) when startup is slow; the integration test uses a
    # 30 s timeout for a 150 s grace.
    heartbeat_grace_ms: int | None = None,
    stall_timeout_ms: int = 0,
    # Progress watchdog (round 22, train/elastic.py): no trainer heartbeat
    # on <logdir>/worker<i>.heartbeat for this long → Stall: verdict,
    # SIGKILL, recovery through the elastic path. Needs NO detector port —
    # the file-mtime path catches the frozen/wedged class (SIGSTOP, hung
    # collective) that exit codes and liveness probes can't see. Size it
    # above the worst-case gap between beats (an epoch + a fresh compile).
    # 0 disables (default).
    stall_after_s: float = 0.0,
    backoff: float = 1.0,
    poll_interval: float = 0.5,
    # Shrink-to-fit resize (round 8; only with max_restarts > 0). None/0
    # disables: the round-7 fixed-size gang.
    min_workers: int | None = None,
    rejoin_timeout_s: float = 30.0,
    # Independent member supervision (round 17, train/elastic.py): a
    # failed member relaunches ALONE while the others keep running — for
    # collective-free gangs (the stale-tolerant DiLoCo mailbox). Does
    # not compose with min_workers resizing.
    independent: bool = False,
    drive_mode: str | None = None,
    drive_after_s: float = 8.0,
    drive_replace_after_s: float = 10.0,
    # Live /metrics + /healthz on the elastic driver (round 12,
    # observability/exporter.py). None/0 = nothing listens.
    metrics_port: int | None = None,
    print_fn=print,
) -> int:
    if max_restarts > 0 and not wait:
        # Supervision IS waiting: silently spawning unsupervised workers
        # would drop the requested restart budget on the floor.
        raise ValueError("max_restarts > 0 requires wait=True (the elastic "
                         "agent supervises the gang to completion)")
    if min_workers and min_workers > num_workers:
        raise ValueError(
            f"min_workers={min_workers} exceeds num_workers={num_workers}"
        )
    if min_workers and not max_restarts:
        raise ValueError(
            "min_workers needs max_restarts > 0 (resizing is a relaunch — "
            "a one-shot gang has no budget to relaunch with)"
        )
    if drive_mode not in (None, "", "none", "kill-without-replace",
                          "kill-then-replace"):
        raise ValueError(
            f"unknown drive_mode {drive_mode!r}; use "
            "kill-without-replace or kill-then-replace"
        )
    if drive_mode in ("", "none"):
        drive_mode = None
    os.makedirs(logdir, exist_ok=True)
    base_env = dict(os.environ)
    if env:
        base_env.update(env)
    # Every task here runs on THIS host, and a chip has one owner.
    from distributed_tensorflow_tpu.train.elastic import children_platform

    platform = children_platform(base_env, num_workers, "launch_local")
    print_fn(f"launch_local: {num_workers} worker processes on {platform}")
    # ps tasks no-op and exit on TPU: launch one-shot, never supervised —
    # a clean ps exit must not read as a gang failure, and a gang restart
    # must not respawn them.
    ps_procs = [
        ("ps%d" % i, _spawn_task(command, "ps", i, logdir, base_env))
        for i in range(num_ps)
    ]
    if max_restarts > 0:
        rc = _launch_elastic(
            command,
            num_workers,
            logdir,
            base_env,
            max_restarts=max_restarts,
            heartbeat_port=heartbeat_port,
            heartbeat_timeout_ms=heartbeat_timeout_ms,
            heartbeat_grace_ms=heartbeat_grace_ms,
            stall_timeout_ms=stall_timeout_ms,
            stall_after_s=stall_after_s,
            backoff=backoff,
            poll_interval=poll_interval,
            min_workers=min_workers,
            rejoin_timeout_s=rejoin_timeout_s,
            independent=independent,
            drive_mode=drive_mode,
            drive_after_s=drive_after_s,
            drive_replace_after_s=drive_replace_after_s,
            metrics_port=metrics_port,
            print_fn=print_fn,
        )
        for name, p in ps_procs:
            print_fn(f"{name}: exit {p.wait()}")
        return rc
    # Fail-stop path (max_restarts=0): the pre-round-7 behavior, unchanged —
    # wait for every task, non-zero if any worker failed.
    procs = ps_procs + [
        ("worker%d" % i, _spawn_task(command, "worker", i, logdir, base_env))
        for i in range(num_workers)
    ]
    if not wait:
        return 0
    rc = 0
    for name, p in procs:
        code = p.wait()
        print_fn(f"{name}: exit {code}")
        if code != 0 and name.startswith("worker"):
            rc = 1
    return rc


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--workers", type=int, required=True)
    parser.add_argument("--ps", type=int, default=0)
    parser.add_argument("--logdir", type=str, default="./task_logs")
    # CLI defaults come from the DTF_* env knobs (launch.config_from_env /
    # cluster_from_env's pod-scheduler surface): a scheduler that sets
    # DTF_MAX_RESTARTS=3 arms the elastic driver with no flag changes.
    parser.add_argument(
        "--max-restarts",
        type=int,
        default=int(os.environ.get("DTF_MAX_RESTARTS", "0") or 0),
        help="elastic gang-restart budget (train/elastic.py); 0 = the "
        "one-shot fail-stop launcher (default: $DTF_MAX_RESTARTS or 0)",
    )
    parser.add_argument(
        "--heartbeat-port",
        type=int,
        default=int(os.environ.get("DTF_HEARTBEAT_PORT", "0") or 0) or None,
        help="driver-hosted UDP failure detector port (workers are pointed "
        "at it via DTF_HEARTBEAT_HOST/_PORT); only used with --max-restarts "
        "(default: $DTF_HEARTBEAT_PORT)",
    )
    parser.add_argument(
        "--heartbeat-timeout-ms",
        type=int,
        default=int(os.environ.get("DTF_HEARTBEAT_TIMEOUT_MS", "5000") or 5000),
    )
    parser.add_argument(
        "--heartbeat-grace-ms",
        type=int,
        default=None,
        help="never-beaten grace before a worker reads as dead (default: "
        "5x the timeout); raise it when cold startup — imports, jax "
        "rendezvous, first compile — outlasts that window",
    )
    parser.add_argument(
        "--stall-timeout-ms",
        type=int,
        default=int(os.environ.get("DTF_STALL_TIMEOUT_MS", "0") or 0),
        help="recover a worker whose heartbeats flow but whose progress "
        "counter is frozen past this window (0 disables; default: "
        "$DTF_STALL_TIMEOUT_MS)",
    )
    parser.add_argument(
        "--stall-after-s",
        type=float,
        default=float(os.environ.get("DTF_STALL_AFTER_S", "0") or 0),
        help="file-based progress watchdog (round 22): kill and recover a "
        "worker whose <logdir>/worker<i>.heartbeat has not advanced for "
        "this long — catches the frozen/wedged class without any detector "
        "port; size above the worst epoch+compile gap (0 disables; "
        "default: $DTF_STALL_AFTER_S)",
    )
    parser.add_argument("--backoff", type=float, default=1.0)
    parser.add_argument(
        "--min-workers",
        type=int,
        default=int(os.environ.get("DTF_MIN_WORKERS", "0") or 0),
        help="shrink-to-fit floor (round 8): a lost-and-unreplaced worker "
        "shrinks the gang down to this size instead of restart-looping; "
        "0 disables resizing (default: $DTF_MIN_WORKERS or 0)",
    )
    parser.add_argument(
        "--rejoin-timeout-s",
        type=float,
        default=float(os.environ.get("DTF_REJOIN_TIMEOUT_S", "30") or 30),
        help="how long a failed worker's slot may wait for a replacement "
        "(delete <logdir>/worker<i>.lost to register one) before the gang "
        "resizes without it (default: $DTF_REJOIN_TIMEOUT_S or 30)",
    )
    parser.add_argument(
        "--independent",
        action="store_true",
        help="relaunch failed members ALONE instead of restarting the "
        "gang (round 17 — collective-free gangs like the stale-tolerant "
        "DiLoCo mailbox; needs --max-restarts, excludes --min-workers)",
    )
    parser.add_argument(
        "--drive-mode",
        choices=("none", "kill-without-replace", "kill-then-replace"),
        default="none",
        help="scenario driver: SIGKILL the highest worker after "
        "--drive-after-s and mark its host lost; then-replace clears the "
        "marker after --drive-replace-after-s so the gang regrows",
    )
    parser.add_argument("--drive-after-s", type=float, default=8.0)
    parser.add_argument("--drive-replace-after-s", type=float, default=10.0)
    parser.add_argument(
        "--metrics-port",
        type=int,
        default=int(os.environ.get("DTF_METRICS_PORT", "0") or 0) or None,
        help="serve the elastic driver's live /metrics (Prometheus) and "
        "/healthz on this port while the gang runs (observability/"
        "exporter.py); 0/unset disables (default: $DTF_METRICS_PORT)",
    )
    parser.add_argument("command", nargs=argparse.REMAINDER,
                        help="-- command to launch per task")
    args = parser.parse_args(argv)
    command = args.command
    if command and command[0] == "--":
        command = command[1:]
    if not command:
        parser.error("missing command after --")
    return launch(
        command,
        args.workers,
        args.ps,
        args.logdir,
        max_restarts=args.max_restarts,
        heartbeat_port=args.heartbeat_port,
        heartbeat_timeout_ms=args.heartbeat_timeout_ms,
        heartbeat_grace_ms=args.heartbeat_grace_ms,
        stall_timeout_ms=args.stall_timeout_ms,
        stall_after_s=args.stall_after_s,
        backoff=args.backoff,
        min_workers=args.min_workers or None,
        rejoin_timeout_s=args.rejoin_timeout_s,
        independent=args.independent,
        drive_mode=args.drive_mode,
        drive_after_s=args.drive_after_s,
        drive_replace_after_s=args.drive_replace_after_s,
        metrics_port=args.metrics_port,
    )


if __name__ == "__main__":
    sys.exit(main())
