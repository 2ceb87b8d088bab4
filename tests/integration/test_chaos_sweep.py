"""Chaos sweep in the RUN_SLOW tier (round 19): one representative
failpoint schedule per durability seam — checkpoint (subprocess SIGKILL
mid-manifest-commit), delta exchange (torn committed post), fleet
mailbox (torn result) — swept over two seeds via the real CLI, asserting
rc 0 and the per-cell no-data-loss verdicts in the JSON summary. The
full in-process matrix runs fast-tier (tests/test_failpoints.py); this
proves the driver end-to-end, subprocess kill scenario included.
"""

import json
import os
import subprocess
import sys

import pytest

pytestmark = pytest.mark.skipif(
    not os.environ.get("RUN_SLOW"),
    reason="chaos sweep end-to-end (set RUN_SLOW=1)",
)

_REPO = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)

_SCHEDULES = "ckpt-kill-mid-save,delta-torn,fleet-torn-result"


@pytest.mark.heavy
def test_chaos_sweep_representative_schedules(tmp_path):
    out = str(tmp_path / "chaos.json")
    env = dict(os.environ)
    env.pop("DTF_FAILPOINTS", None)  # the sweep arms its own schedules
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = _REPO + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run(
        [
            sys.executable,
            "-m",
            "distributed_tensorflow_tpu.tools.chaos_sweep",
            "--schedules",
            _SCHEDULES,
            "--seeds",
            "0,1",
            "--json",
            out,
        ],
        env=env,
        capture_output=True,
        text=True,
        timeout=540,
        cwd=_REPO,
    )
    assert proc.returncode == 0, proc.stderr[-4000:]
    summary = json.load(open(out))
    assert summary["ok"] and summary["failed"] == 0
    assert summary["jitter_deterministic"] is True
    cells = summary["cells"]
    assert len(cells) == 6  # 3 schedules x 2 seeds
    assert all(c["ok"] for c in cells)
    # The seed moved the fault: the two kill cells hit different saves.
    kills = [c for c in cells if c["schedule"] == "ckpt-kill-mid-save"]
    assert {c["killed_at_save"] for c in kills} == {3, 4}
    assert all(c["restored_step"] == c["killed_at_save"] for c in kills)
    torn = [c for c in cells if c["schedule"] == "delta-torn"]
    assert {c["torn_round"] for c in torn} == {1, 2}
