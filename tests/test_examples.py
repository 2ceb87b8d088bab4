"""The example scripts — the reference's four entry points — driven as real
OS processes (the actual user surface)."""

import os
import subprocess
import sys

import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_EX = os.path.join(_REPO, "examples")


def _run(script, *args, env_extra=None, timeout=300):
    env = dict(os.environ)
    env.update(
        {
            "JAX_PLATFORMS": "cpu",
            "DTF_EPOCHS": "1",
            "DTF_SCAN": "1",
            "DTF_LOGS": "",
            "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
        }
    )
    env.update(env_extra or {})
    return subprocess.run(
        [sys.executable, os.path.join(_EX, script), *args],
        capture_output=True,
        text=True,
        env=env,
        timeout=timeout,
        cwd=_EX,
    )


def test_single_example_end_to_end():
    r = _run("single.py")
    assert r.returncode == 0, r.stdout + r.stderr
    assert "Test-Accuracy:" in r.stdout
    assert r.stdout.rstrip().endswith("Done")


def test_between_sync_worker():
    r = _run("between_sync.py", "--job_name=worker", "--task_index=0")
    assert r.returncode == 0, r.stdout + r.stderr
    assert "worker setting up ..." in r.stdout
    assert "Ready to go" in r.stdout
    assert "Done" in r.stdout


def test_between_async_worker():
    r = _run("between_async.py", "--job_name=worker", "--task_index=0",
             env_extra={"DTF_SCAN": "0"})
    assert r.returncode == 0, r.stdout + r.stderr
    assert "Done" in r.stdout


def test_ps_role_noop():
    r = _run("between_sync.py", "--job_name=ps", "--task_index=0")
    assert r.returncode == 0, r.stdout + r.stderr
    assert "ps setting up ..." in r.stdout
    assert "Done" not in r.stdout  # no training happened


def test_resilient_example_runs_and_resumes(tmp_path):
    # The round-6 resilience demo: first run trains fresh with durable
    # checkpoints (manifest sidecars, retention), second run resumes from
    # the newest VALID step via the same DTF_CHECKPOINT override.
    ck = str(tmp_path / "ck")
    r = _run("resilient.py", env_extra={"DTF_CHECKPOINT": ck})
    assert r.returncode == 0, r.stdout + r.stderr
    assert "fresh start" in r.stdout
    assert "Test-Accuracy:" in r.stdout
    from distributed_tensorflow_tpu.train.supervisor import (
        latest_checkpoint_step,
    )

    step = latest_checkpoint_step(ck, verify=True)
    assert step is not None and step > 0  # manifest-verified save landed
    r2 = _run("resilient.py", env_extra={"DTF_CHECKPOINT": ck})
    assert r2.returncode == 0, r2.stdout + r2.stderr
    assert f"resuming from step {step}" in r2.stdout


def test_serve_example_trains_checkpoints_and_serves():
    # The serving loop end to end as a user would run it: train with a
    # BPE vocab + checkpoint_dir, then TextServer.from_checkpoint serves
    # greedy and nucleus batches through continuous batching.
    r = _run("serve_text.py", "1", "8", timeout=600)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "trained: perplexity" in r.stdout
    assert r.stdout.count("greedy  ") == 3
    assert r.stdout.count("nucleus ") == 3
    assert r.stdout.rstrip().endswith("Done")


@pytest.mark.heavy  # round-14 audit: compile-tail; representative sibling stays fast-tier
def test_lm_example_trains_and_generates():
    # The example now drives the LMTrainer lifecycle: 2 epochs exercises
    # the loop contract (Step lines, perplexity eval) plus generation.
    r = _run("lm.py", "2", "8", timeout=600)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "Test-Perplexity:" in r.stdout
    assert "greedy continuation:" in r.stdout
    assert r.stdout.rstrip().endswith("Done")
