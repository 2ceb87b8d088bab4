"""Fault injection: kill a live worker mid-run; the chief must end the job
cleanly with a restorable checkpoint — not hang (RUN_SLOW tier).

The reference's only failure behavior was implicit: a dead worker left the
chief's gRPC calls blocking forever, and recovery meant a *restarted* worker
re-attaching to still-live PS state via ``prepare_or_wait_for_session``
(reference tfdist_between.py:83). This framework upgrades that to explicit
liveness (C++ UDP heartbeat, runtime/csrc/dtf_runtime.cc) + a
failure-reactive Supervisor stop + real checkpoints; this test is the
end-to-end proof:

1. chief + 1 worker bootstrap with heartbeats; chief trains epoch-at-a-time
   with checkpointing and ``Supervisor.attach_heartbeat``;
2. the test SIGKILLs the worker mid-run;
3. the chief's ``should_stop`` trips at the next epoch boundary → clean exit
   (rc 0) with a ``step_N`` checkpoint on disk;
4. a restarted trainer restores from that checkpoint and continues — the
   re-attach semantics, now surviving chief death too.
"""

import os
import signal
import subprocess
import sys
import time

import pytest

pytestmark = pytest.mark.skipif(
    not os.environ.get("RUN_SLOW"), reason="fault injection smoke (set RUN_SLOW=1)"
)

_REPO = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)

_CHIEF = r"""
import os, sys, time
os.environ["JAX_PLATFORMS"] = "cpu"
import jax
import numpy as np
from distributed_tensorflow_tpu.cluster import bootstrap
from distributed_tensorflow_tpu.config import ClusterConfig, TrainConfig
from distributed_tensorflow_tpu.data.mnist import DataSet, Datasets
from distributed_tensorflow_tpu.models import MLP
from distributed_tensorflow_tpu.train import Trainer
from distributed_tensorflow_tpu.train.supervisor import Supervisor
from distributed_tensorflow_tpu.utils.logging import StepLogger

ckpt = sys.argv[1]
# Heartbeat-only bootstrap (async-style independent streams: the reference's
# async workers never synchronized in-band either).
cluster = ClusterConfig.from_lists(["127.0.0.1:29791", "127.0.0.1:29792"])
ctx = bootstrap(cluster, "worker", 0, initialize_distributed=False,
                heartbeat_port=19461, heartbeat_timeout_ms=1500)
assert ctx.heartbeat is not None
# prepare_or_wait analog: block until the worker has reported once, so the
# never-seen grace period can't fire while the worker is still importing.
deadline = time.time() + 120  # generous: a loaded CI host imports jax slowly
while ctx.heartbeat.ms_since_seen(1) < 0 and time.time() < deadline:
    time.sleep(0.1)
assert ctx.heartbeat.ms_since_seen(1) >= 0, "worker never came up"

rng = np.random.default_rng(0)
imgs = rng.random((2000, 784), dtype=np.float32)
labs = np.eye(10, dtype=np.float32)[rng.integers(0, 10, 2000)]
ds = Datasets(train=DataSet(imgs, labs, seed=1),
              validation=None, test=DataSet(imgs[:200], labs[:200], seed=2))
sup = Supervisor(is_chief=True, checkpoint_dir=ckpt)
sup.attach_heartbeat(ctx.heartbeat)
tr = Trainer(MLP(hidden_dim=16, compute_dtype=jax.numpy.float32), ds,
             TrainConfig(epochs=10**6, scan_epoch=True, log_frequency=10**9,
                         logs_path="", checkpoint_dir=ckpt),
             supervisor=sup, print_fn=lambda *a: None)
print("CHIEF_TRAINING", flush=True)
logger = StepLogger(freq=10**9, print_fn=lambda *a: None)
epoch = 0
while not sup.should_stop:
    tr.run_epoch(epoch, logger)
    sup.save(tr.state, tr.strategy.global_step(tr.state))
    epoch += 1
sup.stop()
ctx.heartbeat.stop()
if ctx.heartbeat_sender is not None:
    ctx.heartbeat_sender.stop()
print("CHIEF_STOPPED", tr.strategy.global_step(tr.state), "epochs", epoch, flush=True)
"""

_WORKER = r"""
import os, sys, time
os.environ["JAX_PLATFORMS"] = "cpu"
from distributed_tensorflow_tpu.cluster import bootstrap
from distributed_tensorflow_tpu.config import ClusterConfig

cluster = ClusterConfig.from_lists(["127.0.0.1:29791", "127.0.0.1:29792"])
ctx = bootstrap(cluster, "worker", 1, initialize_distributed=False,
                heartbeat_port=19461)
assert ctx.heartbeat is not None
print("WORKER_UP", flush=True)
time.sleep(600)  # "training" until killed
"""


_ELASTIC_WORKER = r"""
import os, signal, sys, time, warnings
os.environ["JAX_PLATFORMS"] = "cpu"
import jax
import numpy as np
from distributed_tensorflow_tpu.cluster import bootstrap
from distributed_tensorflow_tpu.config import ClusterConfig, TrainConfig
from distributed_tensorflow_tpu.launch import cluster_from_env

ckpt, workdir = sys.argv[1], sys.argv[2]
task = int([a.split("=")[1] for a in sys.argv if a.startswith("--task_index")][0])
# The elastic driver (tools/launch_local.py --max-restarts) hosts the
# detector and points the gang at it via DTF_HEARTBEAT_*; cluster_from_env
# is the documented wiring (the pod-scheduler surface).
cluster = cluster_from_env(
    ClusterConfig.from_lists(["127.0.0.1:29795", "127.0.0.1:29796"])
)
ctx = bootstrap(cluster, "worker", task, initialize_distributed=False)
if os.environ.get("DTF_HEARTBEAT_HOST"):
    assert ctx.heartbeat is not None, "elastic sender did not arm"
done = os.path.join(workdir, "DONE")

if task == 1:
    # Gang peer: beats + moving progress until the trainer finishes.
    print("PEER_UP", flush=True)
    deadline = time.time() + 240
    step = 0
    while not os.path.exists(done) and time.time() < deadline:
        step += 1
        ctx.report_progress(step)
        time.sleep(0.2)
    ctx.close()
    sys.exit(0 if os.path.exists(done) else 3)

# task 0: the trainer. Restores must be clean — a RuntimeWarning from the
# checkpoint fallback path (corrupt/partial step skipped) fails the run.
warnings.filterwarnings("error", message=".*checkpoint step_.*")
from distributed_tensorflow_tpu.data.mnist import DataSet, Datasets
from distributed_tensorflow_tpu.models import MLP
from distributed_tensorflow_tpu.train import Trainer
from distributed_tensorflow_tpu.utils.logging import StepLogger

rng = np.random.default_rng(0)
imgs = rng.random((2000, 784), dtype=np.float32)
labs = np.eye(10, dtype=np.float32)[rng.integers(0, 10, 2000)]
ds = Datasets(train=DataSet(imgs, labs, seed=1), validation=None,
              test=DataSet(imgs[:200], labs[:200], seed=2))
tr = Trainer(MLP(hidden_dim=16, compute_dtype=jax.numpy.float32), ds,
             TrainConfig(epochs=6, scan_epoch=True, log_frequency=10**9,
                         logs_path="", checkpoint_dir=ckpt),
             print_fn=lambda *a: None)
tr.supervisor.attach_progress(ctx.report_progress)
spe = 2000 // 100  # steps per epoch
marker = os.path.join(workdir, "killed_once")
if not os.path.exists(marker):
    # First incarnation: fresh start, 3 checkpointed epochs, then die hard
    # mid-run (SIGKILL: no handler, no final save — the crash case).
    assert tr.start_step == 0, tr.start_step
    logger = StepLogger(freq=10**9, print_fn=lambda *a: None)
    for epoch in range(3):
        tr.run_epoch(epoch, logger)
        step = tr.strategy.global_step(tr.state)
        tr.supervisor.report_progress(step)
        tr.supervisor.save(tr.state, step, layout=tr.strategy.layout_meta())
    print("TRAINER_DYING", flush=True)
    open(marker, "w").close()
    os.kill(os.getpid(), signal.SIGKILL)
# Relaunched incarnation: resumed EXACTLY at the killed boundary (newest
# valid checkpoint, warning-free restore), then trains to the target.
assert tr.start_step == 3 * spe, tr.start_step
res = tr.run(epochs=3)
assert res["global_step"] == 6 * spe, res
open(done, "w").close()
print("TRAINER_DONE", res["global_step"], flush=True)
ctx.close()
"""


_PREEMPTED = r"""
import os, sys
os.environ["JAX_PLATFORMS"] = "cpu"
import jax
import numpy as np
from distributed_tensorflow_tpu.config import TrainConfig
from distributed_tensorflow_tpu.data.mnist import DataSet, Datasets
from distributed_tensorflow_tpu.models import MLP
from distributed_tensorflow_tpu.train import Trainer

ckpt = sys.argv[1]
rng = np.random.default_rng(0)
imgs = rng.random((2000, 784), dtype=np.float32)
labs = np.eye(10, dtype=np.float32)[rng.integers(0, 10, 2000)]
ds = Datasets(train=DataSet(imgs, labs, seed=1), validation=None,
              test=DataSet(imgs[:200], labs[:200], seed=2))
tr = Trainer(MLP(hidden_dim=16, compute_dtype=jax.numpy.float32), ds,
             TrainConfig(epochs=10**6, scan_epoch=True, log_frequency=10**9,
                         logs_path="", checkpoint_dir=ckpt, keep_last_n=3),
             print_fn=print)
print("TRAINER_RUNNING", flush=True)
res = tr.run()  # handle_preemption=True (default): SIGTERM exits the loop
print("TRAINER_STOPPED", res["global_step"], flush=True)
"""


def test_sigterm_preemption_clean_exit_with_verified_checkpoint(tmp_path):
    """The TPU-pod preemption contract (docs/resilience.md): the scheduler
    SIGTERMs the process, the trainer finishes the epoch in flight, saves
    a CRC-verified checkpoint, and exits rc 0 — proved here end to end on
    a real subprocess (the reference had no answer to preemption at all:
    no saver, no signal handling)."""
    env = dict(os.environ)
    env["PYTHONPATH"] = env.get("PYTHONPATH", "") + os.pathsep + _REPO
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
    ckpt = str(tmp_path / "ck")

    proc = subprocess.Popen(
        [sys.executable, "-c", _PREEMPTED, ckpt],
        stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT,
        env=env,
        text=True,
    )
    import threading

    lines: list = []
    drain = threading.Thread(
        target=lambda: [lines.append(l) for l in proc.stdout], daemon=True
    )
    drain.start()
    try:
        deadline = time.time() + 120
        while time.time() < deadline:
            if any("TRAINER_RUNNING" in l for l in list(lines)):
                break
            assert proc.poll() is None, (
                "trainer died before running:\n" + "".join(lines)
            )
            time.sleep(0.2)
        else:
            raise AssertionError(
                "trainer never reached the loop:\n" + "".join(lines)
            )
        time.sleep(3)  # let at least one epoch land
        proc.send_signal(signal.SIGTERM)
        proc.wait(timeout=120)
    finally:
        if proc.poll() is None:
            proc.kill()
    drain.join(timeout=10)
    out = "".join(lines)

    assert proc.returncode == 0, f"SIGTERM did not exit cleanly:\n{out}"
    assert "Preemption: signal=15" in out, out
    # Round 22: the signal lands mid-epoch, and the handler's emergency
    # save persists the last completed-epoch snapshot IMMEDIATELY — the
    # Preemption: line reports the step that is durable at signal time,
    # before the loop ever reaches its boundary save.
    preempt_line = next(l for l in out.splitlines() if "Preemption:" in l)
    assert "saved_step=" in preempt_line, preempt_line
    emergency_step = int(preempt_line.split("saved_step=")[1].split()[0])
    assert "TRAINER_STOPPED" in out, out

    from distributed_tensorflow_tpu.train.supervisor import (
        latest_checkpoint_step,
    )

    # Final checkpoint exists AND passes CRC verification; it matches the
    # step the trainer reported at exit (saved at the boundary it left).
    step = latest_checkpoint_step(ckpt, verify=True)
    assert step is not None and step > 0, f"no verified checkpoint:\n{out}"
    reported = int(out.split("TRAINER_STOPPED")[1].split()[0])
    assert step == reported, (step, reported)
    # The emergency step is CRC-valid too (the boundary save may have
    # advanced past it; both are committed, newest wins on restore).
    from distributed_tensorflow_tpu.train import resilience as R

    assert emergency_step <= reported, (emergency_step, reported)
    assert R.verify_files(ckpt, emergency_step) is True, emergency_step


def test_worker_kill_stops_chief_with_restorable_checkpoint(tmp_path):
    env = dict(os.environ)
    env["PYTHONPATH"] = env.get("PYTHONPATH", "") + os.pathsep + _REPO
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
    ckpt = str(tmp_path / "ck")

    chief = subprocess.Popen(
        [sys.executable, "-c", _CHIEF, ckpt],
        stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT,
        env=env,
        text=True,
    )
    worker = subprocess.Popen(
        [sys.executable, "-c", _WORKER],
        stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT,
        env=env,
        text=True,
    )
    import threading

    # Drain both stdouts on threads so readiness waits have a REAL deadline
    # (a bare readline() blocks past any time check) and nothing deadlocks
    # on a full pipe.
    chief_lines: list = []
    worker_lines: list = []

    def _drain(proc, sink):
        for line in proc.stdout:
            sink.append(line)

    threads = {}
    for proc, sink in ((chief, chief_lines), (worker, worker_lines)):
        t = threading.Thread(target=_drain, args=(proc, sink), daemon=True)
        t.start()
        threads[proc] = t

    def _wait_for(sink, token, proc, timeout=120.0):
        end = time.time() + timeout
        while time.time() < end:
            if any(token in l for l in list(sink)):
                return True
            if proc.poll() is not None:
                # Let the drain thread consume the pipe's tail before
                # concluding — poll() can precede the buffered output.
                threads[proc].join(timeout=10)
                return any(token in l for l in list(sink))
            time.sleep(0.2)
        return False

    try:
        # Wait for BOTH sides' own readiness lines before scheduling the
        # kill: under load (this test runs right after the heavy converged-
        # parity oracle) jax imports can take >12s on either process, and
        # killing a worker the chief never saw trips the chief's "worker
        # never came up" assert instead of the heartbeat-loss path this
        # test exists to prove.
        assert _wait_for(worker_lines, "WORKER_UP", worker), (
            "worker never reported ready:\n" + "".join(worker_lines)
        )
        assert _wait_for(chief_lines, "CHIEF_TRAINING", chief), (
            "chief never reached training:\n" + "".join(chief_lines)
        )
        # Steady state (chief sees heartbeats, training underway), then kill
        # without ceremony.
        time.sleep(8)
        worker.send_signal(signal.SIGKILL)
        chief.wait(timeout=120)
    finally:
        for p in (chief, worker):
            if p.poll() is None:
                p.kill()
    worker.wait(timeout=10)
    # Join the drain threads (EOF after process exit) — a fixed sleep could
    # truncate the captured tail on a loaded host.
    for t in threads.values():
        t.join(timeout=10)
    out = "".join(chief_lines)

    assert chief.returncode == 0, f"chief did not exit cleanly:\n{out}"
    assert "CHIEF_TRAINING" in out and "CHIEF_STOPPED" in out, out

    # The checkpoint the chief left must be restorable and carry progress.
    from distributed_tensorflow_tpu.config import TrainConfig
    from distributed_tensorflow_tpu.data.mnist import DataSet, Datasets
    from distributed_tensorflow_tpu.models import MLP
    from distributed_tensorflow_tpu.train import Trainer
    from distributed_tensorflow_tpu.train.supervisor import latest_checkpoint_step

    import jax.numpy as jnp
    import numpy as np

    step = latest_checkpoint_step(ckpt)
    assert step is not None and step > 0, f"no checkpoint written (out:\n{out})"

    rng = np.random.default_rng(0)
    imgs = rng.random((2000, 784), dtype=np.float32)
    labs = np.eye(10, dtype=np.float32)[rng.integers(0, 10, 2000)]
    ds = Datasets(
        train=DataSet(imgs, labs, seed=1),
        validation=None,
        test=DataSet(imgs[:200], labs[:200], seed=2),
    )
    tr = Trainer(
        MLP(hidden_dim=16, compute_dtype=jnp.float32),
        ds,
        TrainConfig(
            epochs=1,
            scan_epoch=True,
            log_frequency=10**9,
            logs_path="",
            checkpoint_dir=ckpt,
        ),
        print_fn=lambda *a: None,
    )
    assert tr.start_step == step  # restored, not re-initialized
    res = tr.run(epochs=1)  # restarted worker re-attaches and continues
    assert res["global_step"] > step


def test_elastic_agent_gang_restarts_after_sigkill(tmp_path):
    """Round 7 acceptance: a 2-process gang under the elastic agent
    (tools/launch_local.py --max-restarts) whose trainer is SIGKILLed
    mid-run RESTARTS — both members killed and relaunched after backoff —
    resumes from the newest CRC-verified checkpoint with a
    RuntimeWarning-free restore (the worker script turns restore-fallback
    warnings into errors), and finishes rc 0 at the expected step count.
    Supervision is exit-code + agent-hosted heartbeat (the driver hosts
    the detector; a generous timeout so a loaded host's slow jax import
    can't read as death — the kill is detected via the exit code
    instantly either way)."""
    from distributed_tensorflow_tpu.tools.launch_local import launch

    env = dict(os.environ)
    env["PYTHONPATH"] = env.get("PYTHONPATH", "") + os.pathsep + _REPO
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
    ckpt = str(tmp_path / "ck")
    workdir = str(tmp_path / "wd")
    os.makedirs(workdir)
    lines: list = []
    rc = launch(
        [sys.executable, "-c", _ELASTIC_WORKER, ckpt, workdir],
        num_workers=2,
        logdir=str(tmp_path / "logs"),
        env=env,
        max_restarts=2,
        heartbeat_port=19481,
        heartbeat_timeout_ms=30_000,  # grace 150 s > worst-case jax import
        backoff=0.5,
        poll_interval=0.3,
        print_fn=lambda *a: lines.append(" ".join(str(x) for x in a)),
    )
    out = "\n".join(lines)
    assert rc == 0, f"gang did not recover (rc={rc}):\n{out}"
    restart_lines = [l for l in lines if l.startswith("Restart: restart=")]
    assert len(restart_lines) == 1, out
    assert "worker0=rc=-9" in restart_lines[0], restart_lines[0]

    # Both incarnations of the trainer are in the (appended) log.
    with open(tmp_path / "logs" / "worker0.log") as f:
        w0 = f.read()
    assert "TRAINER_DYING" in w0 and "TRAINER_DONE 120" in w0, w0

    # The final checkpoint is CRC-verified at the target step: 6 epochs ×
    # 20 steps, across a death at step 60.
    from distributed_tensorflow_tpu.train.supervisor import (
        latest_checkpoint_step,
    )

    assert latest_checkpoint_step(ckpt, verify=True) == 120

    # The driver wrote the restart tfevents scalar sidecar.
    assert any(
        ".elastic" in name for name in os.listdir(tmp_path / "logs")
    )


def test_elastic_max_restarts_zero_keeps_fail_stop(tmp_path):
    """max_restarts=0 preserves round 6's fail-stop bit-for-bit: the same
    SIGKILL ends the job non-zero after ONE incarnation — no restart, no
    Restart: line — with the pre-kill checkpoints intact and verified."""
    env = dict(os.environ)
    env["PYTHONPATH"] = env.get("PYTHONPATH", "") + os.pathsep + _REPO
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
    ckpt = str(tmp_path / "ck")
    workdir = str(tmp_path / "wd")
    os.makedirs(workdir)

    from distributed_tensorflow_tpu.tools.launch_local import launch

    lines: list = []
    rc = launch(
        [sys.executable, "-c", _ELASTIC_WORKER, ckpt, workdir],
        num_workers=1,  # just the trainer: the peer would (rightly) wait
        logdir=str(tmp_path / "logs"),
        env=env,
        max_restarts=0,
        print_fn=lambda *a: lines.append(" ".join(str(x) for x in a)),
    )
    out = "\n".join(lines)
    assert rc == 1, f"fail-stop must propagate the failure:\n{out}"
    assert not any("Restart" in l for l in lines), out
    # One incarnation only: it died, nothing relaunched it.
    assert os.path.exists(os.path.join(workdir, "killed_once"))
    assert not os.path.exists(os.path.join(workdir, "DONE"))

    from distributed_tensorflow_tpu.train.supervisor import (
        latest_checkpoint_step,
    )

    assert latest_checkpoint_step(ckpt, verify=True) == 60


_SHRINK_WORKER = r"""
import os, signal, sys, time
os.environ["JAX_PLATFORMS"] = "cpu"
import jax
from distributed_tensorflow_tpu.cluster import bootstrap
from distributed_tensorflow_tpu.config import ClusterConfig, TrainConfig
from distributed_tensorflow_tpu.data import read_data_sets
from distributed_tensorflow_tpu.launch import cluster_from_env
from distributed_tensorflow_tpu.models import MLP
from distributed_tensorflow_tpu.parallel import SyncDataParallel, make_mesh
from distributed_tensorflow_tpu.train import Trainer

ckpt, logdir = sys.argv[1], sys.argv[2]
task = int([a.split("=")[1] for a in sys.argv if a.startswith("--task_index")][0])
# The elastic driver communicates a resized topology via DTF_WORLD_SIZE /
# DTF_WORKER_RANKS; cluster_from_env -> ClusterConfig.subset is the
# documented resolution (round 8).
base = ClusterConfig.from_lists(["127.0.0.1:29797", "127.0.0.1:29798"])
cluster = cluster_from_env(base)
world = cluster.num_processes
ranks = os.environ.get("DTF_WORKER_RANKS", "")
orig = int(ranks.split(",")[task]) if ranks else task
ctx = bootstrap(cluster, "worker", task)
# synthetic=True pins the deterministic dataset the 0.72@170-epoch
# gb=200 crossing was measured on (real IDX files, if present, have a
# different curve).
ds = read_data_sets("MNIST_data", one_hot=True, synthetic=True)
cfg = TrainConfig(epochs=1, batch_size=100, scan_epoch=True,
                  log_frequency=10**9, logs_path="", checkpoint_dir=ckpt,
                  keep_last_n=3)
spe = ds.train.num_examples // 200  # global batch 100 x 2 = 200, preserved

if world == 2:
    # Phase 1: genuine 2-process sync dp over jax.distributed.
    assert jax.process_count() == 2
    mesh = make_mesh((2,), ("data",))
    tr = Trainer(MLP(), ds, cfg, strategy=SyncDataParallel(mesh),
                 is_chief=ctx.is_chief, print_fn=lambda *a: None)
    assert tr.start_step == 0 and tr.global_batch == 200
    print(f"PHASE1 start_step=0 world=2 orig={orig}", flush=True)
    tr.run(epochs=5)
    if orig == 1:
        # The lost host: mark the slot vacant, die without ceremony.
        open(os.path.join(logdir, "worker1.lost"), "w").close()
        os.kill(os.getpid(), signal.SIGKILL)
    sys.exit(0)

# Phase 2: the survivor, relaunched alone. The old-world checkpoint
# restores through the canonical layer (dense sync -> single is a pure
# re-shard) and the recorded global batch 200 is ADOPTED (config says
# 100 x 1), so steps/epoch stays 275 and the trajectory continues.
assert world == 1 and orig == 0 and jax.process_count() == 1
lines = []
tr = Trainer(MLP(), ds, cfg, is_chief=True,
             print_fn=lambda *a: lines.append(" ".join(str(x) for x in a)))
assert tr.start_step == 5 * spe, tr.start_step
assert tr.global_batch == 200, tr.global_batch
assert any(l.startswith("Restore: global_batch=200 preserved") for l in lines), lines
print(f"PHASE2 start_step={tr.start_step} world=1 orig=0", flush=True)
res = tr.run(epochs=165)  # 170 total at gb=200 (0.72 crossing ~145)
assert res["global_step"] == 170 * spe, res
print("ORACLE", res["accuracy"], flush=True)
assert res["accuracy"] >= 0.72, res
print("SHRINK_DONE", flush=True)
"""


_REGROW_WORKER = r"""
import os, signal, sys, time
os.environ["JAX_PLATFORMS"] = "cpu"
import jax
import numpy as np
from distributed_tensorflow_tpu.cluster import bootstrap
from distributed_tensorflow_tpu.config import ClusterConfig, TrainConfig
from distributed_tensorflow_tpu.data.mnist import DataSet, Datasets
from distributed_tensorflow_tpu.launch import cluster_from_env
from distributed_tensorflow_tpu.models import MLP
from distributed_tensorflow_tpu.parallel import SyncDataParallel, make_mesh
from distributed_tensorflow_tpu.train import Trainer

ckpt, logdir, workdir = sys.argv[1], sys.argv[2], sys.argv[3]
task = int([a.split("=")[1] for a in sys.argv if a.startswith("--task_index")][0])
base = ClusterConfig.from_lists(["127.0.0.1:29801", "127.0.0.1:29802"])
cluster = cluster_from_env(base)
world = cluster.num_processes
ranks = os.environ.get("DTF_WORKER_RANKS", "")
orig = int(ranks.split(",")[task]) if ranks else task
ctx = bootstrap(cluster, "worker", task)

rng = np.random.default_rng(0)
imgs = rng.random((2000, 784), dtype=np.float32)
labs = np.eye(10, dtype=np.float32)[rng.integers(0, 10, 2000)]
ds = Datasets(train=DataSet(imgs, labs, seed=1), validation=None,
              test=DataSet(imgs[:200], labs[:200], seed=2))
cfg = TrainConfig(epochs=1, batch_size=100, scan_epoch=True,
                  log_frequency=10**9, logs_path="", checkpoint_dir=ckpt)
model = lambda: MLP(hidden_dim=16, compute_dtype=jax.numpy.float32)
spe = 2000 // 200  # global batch 200, preserved across every phase
killed = os.path.join(workdir, "killed_once")

if world == 2:
    assert jax.process_count() == 2
    mesh = make_mesh((2,), ("data",))
    tr = Trainer(model(), ds, cfg, strategy=SyncDataParallel(mesh),
                 is_chief=ctx.is_chief, print_fn=lambda *a: None)
    if not os.path.exists(killed):
        # Phase 1: fresh gang, 3 checkpointed epochs, then worker1's host
        # is lost (marker + SIGKILL).
        assert tr.start_step == 0, tr.start_step
        print(f"PHASE1 start_step=0 world=2 orig={orig}", flush=True)
        tr.run(epochs=3)
        if orig == 1:
            open(killed, "w").close()
            open(os.path.join(logdir, "worker1.lost"), "w").close()
            os.kill(os.getpid(), signal.SIGKILL)
        sys.exit(0)
    # Phase 3: regrown gang at the original world — resumed from the
    # degraded incarnation's checkpoint, steps monotone.
    assert tr.start_step == 6 * spe, tr.start_step
    print(f"PHASE3 start_step={tr.start_step} world=2 orig={orig}", flush=True)
    res = tr.run(epochs=3)
    assert res["global_step"] == 9 * spe, res
    if orig == 0:
        open(os.path.join(workdir, "DONE"), "w").close()
    print("REGROW_DONE", res["global_step"], flush=True)
    sys.exit(0)

# Phase 2: degraded world=1 survivor; after 3 epochs its lost peer's
# replacement registers (marker removed) and this process WAITS for the
# gang to retire it into the regrown incarnation.
assert world == 1 and orig == 0 and jax.process_count() == 1
tr = Trainer(model(), ds, cfg, is_chief=True, print_fn=lambda *a: None)
assert tr.start_step == 3 * spe, tr.start_step
assert tr.global_batch == 200, tr.global_batch
print(f"PHASE2 start_step={tr.start_step} world=1 orig=0", flush=True)
res = tr.run(epochs=3)
assert res["global_step"] == 6 * spe, res
os.remove(os.path.join(logdir, "worker1.lost"))  # replacement registers
print("PHASE2_DONE awaiting regrow", flush=True)
deadline = time.time() + 240
while time.time() < deadline:  # the gang SIGKILLs us to grow
    time.sleep(0.2)
sys.exit(9)  # never retired: the grow path failed
"""


_DILOCO_WORKER = r"""
import os, signal, sys, time
os.environ["JAX_PLATFORMS"] = "cpu"
import jax
import numpy as np
from distributed_tensorflow_tpu.cluster import bootstrap
from distributed_tensorflow_tpu.config import ClusterConfig, TrainConfig
from distributed_tensorflow_tpu.data import copy_corpus
from distributed_tensorflow_tpu.launch import cluster_from_env, config_from_env
from distributed_tensorflow_tpu.models.gpt import GPTLM
from distributed_tensorflow_tpu.train import LMTrainer

ckpt, logdir = sys.argv[1], sys.argv[2]
task = int([a.split("=")[1] for a in sys.argv if a.startswith("--task_index")][0])
base = ClusterConfig.from_lists(["127.0.0.1:29811", "127.0.0.1:29812"])
cluster = cluster_from_env(base)
world = cluster.num_processes
ranks = os.environ.get("DTF_WORKER_RANKS", "")
orig = int(ranks.split(",")[task]) if ranks else task
ctx = bootstrap(cluster, "worker", task)

model = GPTLM(vocab_size=61, max_len=16, model_dim=32, num_heads=4,
              num_layers=2, compute_dtype=jax.numpy.float32)
ds = copy_corpus(num=768, half_len=8, vocab=61, n_val=64, n_test=64, seed=0)
# The DiLoCo knobs arrive via the documented env surface (DTF_SYNC_EVERY/
# DTF_OUTER_LR/DTF_OUTER_MOMENTUM — the pod-scheduler wiring, launch.py).
cfg = config_from_env(TrainConfig(
    epochs=1, batch_size=64, optimizer="adam", learning_rate=3e-3,
    log_frequency=10**9, logs_path="", scan_epoch=True,
    dp_mode="diloco", checkpoint_dir=ckpt))
assert cfg.sync_every == 4 and cfg.outer_lr == 1.0, cfg
spe = (768 - 128) // 64  # 10 steps/epoch, world-invariant (batch is GLOBAL)

if world == 2:
    # Phase 1: a REAL 2-process DiLoCo gang over jax.distributed — one
    # worker copy per process on the data mesh axis.
    from distributed_tensorflow_tpu.parallel import make_mesh

    assert jax.process_count() == 2
    mesh = make_mesh((2,), ("data",))
    tr = LMTrainer(model, ds, cfg, mesh=mesh, is_chief=ctx.is_chief,
                   print_fn=lambda *a: None)
    assert tr.start_step == 0, tr.start_step
    print(f"PHASE1 start_step=0 world=2 orig={orig}", flush=True)
    tr.run(epochs=3)
    if orig == 1:
        # The lost host: mark the slot vacant, die without ceremony —
        # mid-outer-round as far as the gang is concerned.
        open(os.path.join(logdir, "worker1.lost"), "w").close()
        os.kill(os.getpid(), signal.SIGKILL)
    sys.exit(0)

# Phase 2: the survivor alone at world=1 (a 1-wide data mesh — same
# engine). The replicas=2 checkpoint restores through the canonical
# layer (copies merge at the mean) and the WORLD-INVARIANT outer state
# (theta_start anchor + Nesterov momentum) carries VERBATIM — the next
# outer round's pseudo-gradient is computed against the saved anchor
# over the survivor: "the outer update proceeds over survivors".
assert world == 1 and orig == 0 and jax.process_count() == 1
from distributed_tensorflow_tpu.parallel import make_mesh

mesh = make_mesh((1,), ("data",))
tr = LMTrainer(model, ds, cfg, mesh=mesh, is_chief=True,
               print_fn=lambda *a: None)
assert tr.start_step == 3 * spe, tr.start_step
# The carried momentum is NONZERO: a re-derived (fresh-round) outer
# state would be all zeros — this is the resize-carries-outer-state
# proof, in-process.
mom = max(float(np.abs(np.asarray(l)).max())
          for l in jax.tree.leaves(tr.state.opt_state.momentum))
assert mom > 0, "outer momentum was not carried across the resize"
print(f"PHASE2 start_step={tr.start_step} world=1 orig=0 momentum={mom:.5f}",
      flush=True)
res = tr.run(epochs=9)  # 12 epochs total across the kill
assert res["global_step"] == 12 * spe, res
print("ORACLE", res["perplexity"], flush=True)
print("DILOCO_DONE", res["global_step"], flush=True)
"""


def test_elastic_shrink_to_fit_resumes_at_world_one_and_reaches_oracle(tmp_path):
    """Round 8 acceptance (shrink half): SIGKILL one of two workers
    mid-run with NO replacement — the gang resizes to world=1, the
    survivor restores the dp=2 checkpoint through the canonical layer
    with the GLOBAL BATCH preserved (200 = 100x2, adopted over the
    config's 100x1), and still reaches the reference's 0.72 oracle on
    the synthetic MNIST."""
    from distributed_tensorflow_tpu.tools.launch_local import launch

    env = dict(os.environ)
    env["PYTHONPATH"] = env.get("PYTHONPATH", "") + os.pathsep + _REPO
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
    ckpt = str(tmp_path / "ck")
    logdir = str(tmp_path / "logs")
    lines: list = []
    rc = launch(
        [sys.executable, "-c", _SHRINK_WORKER, ckpt, logdir],
        num_workers=2,
        logdir=logdir,
        env=env,
        max_restarts=2,
        min_workers=1,
        rejoin_timeout_s=2.0,
        backoff=0.5,
        poll_interval=0.3,
        print_fn=lambda *a: lines.append(" ".join(str(x) for x in a)),
    )
    out = "\n".join(lines)
    assert rc == 0, f"gang did not recover degraded (rc={rc}):\n{out}"
    resize = [l for l in lines if l.startswith("Resize: world=")]
    assert len(resize) == 1, out
    assert "world=1 from=2" in resize[0] and "direction=shrink" in resize[0]
    assert "dropped=[worker1]" in resize[0]

    with open(tmp_path / "logs" / "worker0.log") as f:
        w0 = f.read()
    assert "PHASE1 start_step=0 world=2" in w0, w0
    assert "PHASE2 start_step=1375 world=1" in w0, w0  # 5 x 275, monotone
    assert "SHRINK_DONE" in w0, w0
    oracle = float(w0.split("ORACLE")[1].split()[0])
    assert oracle >= 0.72, oracle

    # Final checkpoint is CRC-verified at the full 170-epoch step count.
    from distributed_tensorflow_tpu.train.supervisor import (
        latest_checkpoint_step,
    )

    assert latest_checkpoint_step(ckpt, verify=True) == 170 * 275

    # The driver's world_size tfevents scalar sidecar was written.
    assert any(".elastic" in name for name in os.listdir(logdir))


def test_diloco_gang_survives_worker_kill_and_reaches_target(tmp_path):
    """Round 14 acceptance: the 1977-era PS experiment table rerun on
    modern failures — a DiLoCo LM gang (train/local_sgd.py, H=4 inner
    steps per outer round, knobs via DTF_SYNC_EVERY/DTF_OUTER_*) loses a
    worker to SIGKILL mid-run, the round-8 elastic driver resizes to the
    survivor, the outer update proceeds over the survivor gang with the
    outer state (anchor + momentum) carried VERBATIM through the
    cross-world restore, and training still reaches the convergence
    target (held-out ppl — calibrated 11.5 at step 120 on this corpus,
    asserted with margin). Async-beats-sync-under-failure, end to end:
    the sync-dp analog of this scenario simply stops (round-6 fail-stop)
    unless the same elastic machinery restarts it — DiLoCo additionally
    keeps its H× comm reduction through the whole episode."""
    import jax as _jax

    if not hasattr(_jax.sharding, "AxisType"):
        pytest.skip("this jax lacks the mesh APIs the diloco gang needs")

    from distributed_tensorflow_tpu.tools.launch_local import launch

    env = dict(os.environ)
    env["PYTHONPATH"] = env.get("PYTHONPATH", "") + os.pathsep + _REPO
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
    env["DTF_SYNC_EVERY"] = "4"
    env["DTF_OUTER_LR"] = "1.0"
    env["DTF_OUTER_MOMENTUM"] = "0.9"
    ckpt = str(tmp_path / "ck")
    logdir = str(tmp_path / "logs")
    lines: list = []
    rc = launch(
        [sys.executable, "-c", _DILOCO_WORKER, ckpt, logdir],
        num_workers=2,
        logdir=logdir,
        env=env,
        max_restarts=2,
        min_workers=1,
        rejoin_timeout_s=2.0,
        backoff=0.5,
        poll_interval=0.3,
        print_fn=lambda *a: lines.append(" ".join(str(x) for x in a)),
    )
    out = "\n".join(lines)
    assert rc == 0, f"diloco gang did not recover (rc={rc}):\n{out}"
    resize = [l for l in lines if l.startswith("Resize: world=")]
    assert len(resize) == 1, out
    assert "world=1 from=2" in resize[0] and "direction=shrink" in resize[0]

    with open(tmp_path / "logs" / "worker0.log") as f:
        w0 = f.read()
    assert "PHASE1 start_step=0 world=2" in w0, w0
    assert "PHASE2 start_step=30 world=1" in w0, w0  # 3 x 10, monotone
    # Outer momentum crossed the resize (nonzero — a fresh round would
    # log 0).
    carried = float(w0.split("momentum=")[1].split()[0])
    assert carried > 0, w0
    assert "DILOCO_DONE 120" in w0, w0
    oracle = float(w0.split("ORACLE")[1].split()[0])
    assert oracle <= 16.0, oracle  # calibrated 11.5; margin for numerics

    # Final checkpoint CRC-manifest-verified at the full step count —
    # the outer state round-trips through a verified save.
    from distributed_tensorflow_tpu.train.supervisor import (
        latest_checkpoint_step,
    )

    assert latest_checkpoint_step(ckpt, verify=True) == 120


_THROTTLE_WORKER = r"""
import os, sys, time
os.environ["JAX_PLATFORMS"] = "cpu"
import jax
import numpy as np
from distributed_tensorflow_tpu.config import TrainConfig
from distributed_tensorflow_tpu.data import copy_corpus
from distributed_tensorflow_tpu.launch import config_from_env
from distributed_tensorflow_tpu.models.gpt import GPTLM
from distributed_tensorflow_tpu.train import LMTrainer
from distributed_tensorflow_tpu.train.local_sgd import DeltaExchange

ckpt, mbox = sys.argv[1], sys.argv[2]
task = int([a.split("=")[1] for a in sys.argv if a.startswith("--task_index")][0])
# The round-17 levers arrive via the documented env surface
# (DTF_DELTA_DTYPE / DTF_STALE_LIMIT / DTF_SYNC_EVERY — launch.py).
cfg = config_from_env(TrainConfig(
    epochs=12, batch_size=64, optimizer="adam", learning_rate=3e-3,
    log_frequency=10**9, logs_path="", scan_epoch=False,
    dp_mode="diloco", diloco_workers=1, outer_lr=1.0, outer_momentum=0.0,
    checkpoint_dir=ckpt if task == 0 else None))
assert cfg.sync_every == 4 and cfg.delta_dtype == "int8" and cfg.stale_limit == 3, cfg
ex = DeltaExchange(mbox, task, 2, stale_limit=cfg.stale_limit,
                   delta_dtype=cfg.delta_dtype)
# Per-member data shard (the DiLoCo contract): same distribution,
# different stream.
ds = copy_corpus(num=768, half_len=8, vocab=61, n_val=64, n_test=64, seed=task)
model = GPTLM(vocab_size=61, max_len=16, model_dim=32, num_heads=4,
              num_layers=2, compute_dtype=jax.numpy.float32)
events = []
class J:
    def emit(self, kind, **f):
        events.append({"kind": kind, **f}); return f
    def flush(self): pass
tr = LMTrainer(model, ds, cfg, is_chief=(task == 0),
               print_fn=lambda *a: None, delta_exchange=ex, journal=J())
# Pace the gang: worker 1 is the deliberately THROTTLED member at 2x
# its peer's step time — it keeps falling rounds behind, so its mailbox
# posts arrive STALE (ages 1..stale_limit) at worker 0's boundaries
# (and vice versa, worker 0's posts run AHEAD of worker 1, clamping to
# age 0 there). The ratio stays under 1+stale_limit so the slow member
# keeps CONTRIBUTING rather than falling out of the window — the
# tolerance under proof.
orig = ds.train.next_batch
delay = 0.1 if task == 0 else 0.2
def paced(*a, **k):
    time.sleep(delay)
    return orig(*a, **k)
ds.train.next_batch = paced
res = tr.run()
dx = [e for e in events if e["kind"] == "delta_exchange"]
peer_rounds = sum(1 for e in dx if len(e["contributors"]) > 1)
stale = sum(e["stale_contributions"] for e in dx)
print("ROUNDS", len(dx), "PEER", peer_rounds, "STALE", stale, flush=True)
print("ORACLE", res["perplexity"], flush=True)
sys.exit(0)
"""


def test_diloco_stale_gang_tolerates_throttled_worker(tmp_path):
    """Round 17 acceptance: the stale-tolerant mailbox gang
    (train/local_sgd.DeltaExchange + TrainConfig.stale_limit) with one
    member deliberately THROTTLED to a fraction of its peer's speed. The
    fast member never stalls — every boundary applies whatever peer
    deltas are within the staleness window, weighted 1/(1+age)
    (staleness_weight) — and still reaches the calibrated held-out ppl
    target (measured ~9.2 at step 120 with the throttled peer
    contributing stale deltas; asserted with margin). The synchronous
    analog of this gang trains at the slow member's pace by
    construction: in-graph DiLoCo's boundary IS a blocking collective.
    The elastic driver supervises with independent=True (round 17) so
    the late finisher is never verdicted a straggler."""
    from distributed_tensorflow_tpu.tools.launch_local import launch

    env = dict(os.environ)
    env["PYTHONPATH"] = env.get("PYTHONPATH", "") + os.pathsep + _REPO
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
    env["DTF_SYNC_EVERY"] = "4"
    env["DTF_DELTA_DTYPE"] = "int8"
    env["DTF_STALE_LIMIT"] = "3"
    ckpt = str(tmp_path / "ck")
    mbox = str(tmp_path / "mbox")
    logdir = str(tmp_path / "logs")
    lines: list = []
    rc = launch(
        [sys.executable, "-c", _THROTTLE_WORKER, ckpt, mbox],
        num_workers=2,
        logdir=logdir,
        env=env,
        max_restarts=1,
        independent=True,
        backoff=0.5,
        poll_interval=0.3,
        print_fn=lambda *a: lines.append(" ".join(str(x) for x in a)),
    )
    out = "\n".join(lines)
    assert rc == 0, f"stale gang did not finish cleanly (rc={rc}):\n{out}"

    with open(tmp_path / "logs" / "worker0.log") as f:
        w0 = f.read()
    with open(tmp_path / "logs" / "worker1.log") as f:
        w1 = f.read()
    # 12 epochs x 10 steps at H=4 → 30 rounds per member.
    assert "ROUNDS 30" in w0 and "ROUNDS 30" in w1, w0 + w1
    # The fast member consumed peer deltas, and some arrived STALE
    # (ages 1..3) — the mechanism under proof. The gang never waited:
    # rounds where the peer was beyond the window simply ran without it.
    # (The age gap grows with the speed ratio, so the slow member
    # eventually leaves a FIXED window — the proof is that it
    # contributed while inside it and the gang ran on either way.)
    peer0 = int(w0.split("PEER")[1].split()[0])
    stale0 = int(w0.split("STALE")[1].split()[0])
    assert peer0 >= 2, w0
    assert stale0 >= 1, w0
    # The throttled member itself consumed its fast peer's
    # ahead-of-round posts (clamped fresh, each exactly once — several
    # per boundary while it lags, none once the fast peer finished and
    # its last posts left the window).
    peer1 = int(w1.split("PEER")[1].split()[0])
    assert peer1 >= 10, w1
    # Convergence target (calibrated ~9.7; margin for numerics/pacing).
    oracle = float(w0.split("ORACLE")[1].split()[0])
    assert oracle <= 14.0, oracle

    # The chief's final checkpoint is CRC-manifest-verified at the full
    # step count — the mailbox gang rides the durable-checkpoint layer.
    from distributed_tensorflow_tpu.train.supervisor import (
        latest_checkpoint_step,
    )

    assert latest_checkpoint_step(ckpt, verify=True) == 120


def test_elastic_regrow_after_replacement_registers(tmp_path):
    """Round 8 acceptance (grow half): the same kill, but the replacement
    registers while the gang runs degraded (lost-marker removed) — the
    gang grows back to world=2 and training continues with steps
    monotone across BOTH resizes (0 -> 30 @2, 30 -> 60 @1, 60 -> 90 @2)."""
    from distributed_tensorflow_tpu.tools.launch_local import launch

    env = dict(os.environ)
    env["PYTHONPATH"] = env.get("PYTHONPATH", "") + os.pathsep + _REPO
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
    ckpt = str(tmp_path / "ck")
    logdir = str(tmp_path / "logs")
    workdir = str(tmp_path / "wd")
    os.makedirs(workdir)
    lines: list = []
    rc = launch(
        [sys.executable, "-c", _REGROW_WORKER, ckpt, logdir, workdir],
        num_workers=2,
        logdir=logdir,
        env=env,
        max_restarts=3,
        min_workers=1,
        rejoin_timeout_s=2.0,
        backoff=0.5,
        poll_interval=0.3,
        print_fn=lambda *a: lines.append(" ".join(str(x) for x in a)),
    )
    out = "\n".join(lines)
    assert rc == 0, f"gang did not regrow (rc={rc}):\n{out}"
    shrink = [l for l in lines if "direction=shrink" in l]
    grow = [l for l in lines if "direction=grow" in l]
    assert len(shrink) == 1 and "dropped=[worker1]" in shrink[0], out
    assert len(grow) == 1 and "rejoined=[worker1]" in grow[0], out
    assert os.path.exists(os.path.join(workdir, "DONE")), out

    # Steps are monotone across both resizes, phase by phase.
    with open(os.path.join(logdir, "worker0.log")) as f:
        w0 = f.read()
    assert "PHASE1 start_step=0 world=2" in w0, w0
    assert "PHASE2 start_step=30 world=1" in w0, w0
    assert "PHASE3 start_step=60 world=2" in w0, w0
    assert "REGROW_DONE 90" in w0, w0

    from distributed_tensorflow_tpu.train.supervisor import (
        latest_checkpoint_step,
    )

    assert latest_checkpoint_step(ckpt, verify=True) == 90


_STALL_WORKER = r"""
import os, signal, sys, time
os.environ["JAX_PLATFORMS"] = "cpu"
import jax
import numpy as np

ckpt, workdir = sys.argv[1], sys.argv[2]
task = int([a.split("=")[1] for a in sys.argv if a.startswith("--task_index")][0])
done = os.path.join(workdir, "DONE")

if task == 1:
    # Gang peer: keeps ITS progress file fresh the whole run (the
    # watchdog must judge members individually — a healthy peer is never
    # collateral of the frozen one's verdict).
    from distributed_tensorflow_tpu.train.resilience import touch_heartbeat
    print("PEER_UP", flush=True)
    deadline = time.time() + 240
    while not os.path.exists(done) and time.time() < deadline:
        touch_heartbeat(os.environ["DTF_HEARTBEAT_FILE"])
        time.sleep(0.2)
    sys.exit(0 if os.path.exists(done) else 3)

# task 0: the trainer. The Supervisor picks up DTF_HEARTBEAT_FILE from the
# elastic driver's env and bumps it at every report_progress.
from distributed_tensorflow_tpu.config import TrainConfig
from distributed_tensorflow_tpu.data.mnist import DataSet, Datasets
from distributed_tensorflow_tpu.models import MLP
from distributed_tensorflow_tpu.train import Trainer
from distributed_tensorflow_tpu.utils.logging import StepLogger

rng = np.random.default_rng(0)
imgs = rng.random((2000, 784), dtype=np.float32)
labs = np.eye(10, dtype=np.float32)[rng.integers(0, 10, 2000)]
ds = Datasets(train=DataSet(imgs, labs, seed=1), validation=None,
              test=DataSet(imgs[:200], labs[:200], seed=2))
tr = Trainer(MLP(hidden_dim=16, compute_dtype=jax.numpy.float32), ds,
             TrainConfig(epochs=6, scan_epoch=True, log_frequency=10**9,
                         logs_path="", checkpoint_dir=ckpt),
             print_fn=lambda *a: None)
spe = 2000 // 100  # 20 steps/epoch
logger = StepLogger(freq=10**9, print_fn=lambda *a: None)
marker = os.path.join(workdir, "froze_once")
if not os.path.exists(marker):
    # First incarnation: 3 checkpointed epochs, then FREEZE (SIGSTOP is
    # uncatchable: the process stays alive with rc=None and its heartbeat
    # file stops advancing — invisible to exit codes and liveness probes,
    # only the progress watchdog can verdict it).
    assert tr.start_step == 0, tr.start_step
    for epoch in range(3):
        tr.run_epoch(epoch, logger)
        step = tr.strategy.global_step(tr.state)
        tr.supervisor.report_progress(step)
        tr.supervisor.save(tr.state, step, layout=tr.strategy.layout_meta())
    tr.supervisor.wait_pending()
    open(marker, "w").close()
    print("TRAINER_FREEZING", flush=True)
    os.kill(os.getpid(), signal.SIGSTOP)
    # Only reached if something SIGCONTs us — the watchdog SIGKILLs first.
    time.sleep(600)
    sys.exit(7)
# Second incarnation: resume from the newest CRC-verified checkpoint and
# finish the remaining epochs.
assert tr.start_step == 3 * spe, tr.start_step
for epoch in range(3, 6):
    tr.run_epoch(epoch, logger)
    step = tr.strategy.global_step(tr.state)
    tr.supervisor.report_progress(step)
    tr.supervisor.save(tr.state, step, layout=tr.strategy.layout_meta())
tr.supervisor.wait_pending()
print("TRAINER_DONE", tr.strategy.global_step(tr.state), flush=True)
open(done, "w").close()
sys.exit(0)
"""


def test_stall_watchdog_recovers_sigstopped_member_without_detector(tmp_path):
    """Round 22 acceptance (tentpole 3): a gang member frozen with
    SIGSTOP mid-run — alive to every exit-code poll, no UDP detector
    wired at all — is verdicted by the file-based progress watchdog
    alone (``--stall-after-s``): Stall: line, SIGKILL, ordinary gang
    restart, resume from the newest CRC-verified checkpoint, rc 0. Zero
    manual intervention."""
    from distributed_tensorflow_tpu.tools.launch_local import launch

    env = dict(os.environ)
    env["PYTHONPATH"] = env.get("PYTHONPATH", "") + os.pathsep + _REPO
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
    ckpt = str(tmp_path / "ck")
    workdir = str(tmp_path / "wd")
    os.makedirs(workdir)
    lines: list = []
    rc = launch(
        [sys.executable, "-c", _STALL_WORKER, ckpt, workdir],
        num_workers=2,
        logdir=str(tmp_path / "logs"),
        env=env,
        max_restarts=2,
        stall_after_s=10.0,  # > one epoch + save; << the 240 s deadline
        backoff=0.5,
        poll_interval=0.3,
        print_fn=lambda *a: lines.append(" ".join(str(x) for x in a)),
    )
    out = "\n".join(lines)
    assert rc == 0, f"gang did not recover from the freeze (rc={rc}):\n{out}"
    stall_lines = [l for l in lines if l.startswith("Stall: member=worker0")]
    assert len(stall_lines) == 1, out
    assert "stall_after_s=10.0" in stall_lines[0], stall_lines[0]
    restart_lines = [l for l in lines if l.startswith("Restart: restart=")]
    assert len(restart_lines) == 1, out
    assert "worker0=stalled" in restart_lines[0], restart_lines[0]

    with open(tmp_path / "logs" / "worker0.log") as f:
        w0 = f.read()
    assert "TRAINER_FREEZING" in w0 and "TRAINER_DONE 120" in w0, w0

    from distributed_tensorflow_tpu.train.supervisor import (
        latest_checkpoint_step,
    )

    assert latest_checkpoint_step(ckpt, verify=True) == 120
