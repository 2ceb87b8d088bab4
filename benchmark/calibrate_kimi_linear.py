"""Readings for the limits of a ``kimi_linear_train`` cell, many seeds in
one process (set-up paid once); ``calibrate_hybrid.py``'s counterpart for
this kind. Not run by the benchmark's own runs.

    python3 benchmark/calibrate_kimi_linear.py --workload <cell> --seeds 1,2,3 \\
        --what program[,control_ref,half_batch,no_routed,no_carry,no_delta,no_shared_key] \\
        --out FILE

``program``      the program against the reference (the lower reading).
``scan_default`` in ``program``'s place, not beside it: the program with
                 the delta rule's products at the default precision, which
                 on the chip rounds their float32 operands to bfloat16 (a
                 precision below the one the configuration states for the
                 recurrence; the model has no option for it, the tool
                 wraps ``kda_chunked``).
``control_ref``  the reference with int8 products, forward and backward
                 alike, put in the program's place (the upper reading).
``half_batch``, ``no_routed``, ``no_carry``, ``no_delta``, ``no_shared_key``
                 the fault planted in the reference put in the program's
                 place: half of the batch left out; the routed experts'
                 contribution left out; the state not carried across the
                 boundaries of 64-token blocks; the delta correction left
                 out (``S = S' + beta k v^T``); the shared 64-wide part
                 left out of latent attention's keys.
One reference run a seed serves them all. One JSON line per seed and
``what``, appended to ``--out`` as soon as it is read (``seconds``: since
the seed began; ``worst_leaf``: the leaf behind ``moment_rel_err``).
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark.lib import harness  # noqa: E402

PROGRAMS = ("program", "scan_default")


def worst_leaf(ref: dict, what: str) -> str:
    """The leaf ``train_cell.compare``'s ``moment_rel_err`` is the reading
    of: the largest norm of the two moments' difference against the
    reference's norm of that leaf or of the median leaf."""
    err, moment = ref["moment_err"][what], ref["moment"]
    median = float(np.median(list(moment.values())))
    return max(moment, key=lambda k: err[k] / max(moment[k], median))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--what", required=True)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    w = harness.workload(args.workload)
    traffic, cfg = harness.traffic(w["traffic"]), harness.config(w["config"])
    devices, _ = harness.require_chips(w["chips"])
    harness.configure_cache()

    from distributed_tensorflow_tpu.utils.logging import StepLogger

    from benchmark.lib import kimi_linear_train_cell as cell, traffic as traffic_lib

    steps = traffic["steps_per_dispatch"]
    batch = cell.global_batch(traffic, w["chips"])
    logger = StepLogger(freq=10 ** 9, print_fn=lambda *a: None)
    whats = args.what.split(",")
    ran = [w for w in whats if w in PROGRAMS]
    if len(ran) > 1:
        raise SystemExit(f"one of {PROGRAMS} a call: one trainer fits the chip")
    if ran == ["scan_default"]:
        from distributed_tensorflow_tpu.models import hybrid

        chunked = hybrid.kda_chunked
        hybrid.kda_chunked = lambda *a, precision=None, **kw: chunked(*a, **kw)
    trainer = None
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        rows = traffic_lib.train_rows(traffic, cfg["vocab_size"], seed, steps * batch)
        ref_kw, program = {"keep_moment": True}, None
        if ran:
            if trainer is None:
                trainer = cell.build_trainer(cfg, traffic, w["chips"], devices, rows)
            else:
                trainer.datasets = type(trainer.datasets)(
                    cell.Rows(rows), trainer.datasets.validation,
                    trainer.datasets.test)
            cell.give_weights(trainer, cfg, seed)
            program = cell.program_readings(trainer, cfg, seed, logger)
            trainer.state = None
            gc.collect()
            ref_kw["against"] = {ran[0]: program.pop("moment_tree")}
        # One moment beside the reference's on the host at a time (2.4 GB
        # each at the cell's size; the machine has 40): every other run is
        # compared with the reference's moment as soon as it is made.
        ref = cell.reference_readings(cfg, traffic, seed, rows, steps, **ref_kw)
        ref_kw.clear()
        for what in whats:
            if what in PROGRAMS:
                got = program
            else:
                got = cell.reference_readings(
                    cfg, traffic, seed, rows, steps,
                    against={"reference": ref["moment_tree"]},
                    **({"precision": "int8"} if what == "control_ref"
                       else {"fault": what}))
                ref["moment_err"][what] = got["moment_err"]["reference"]
            line = json.dumps(dict(
                seed=seed, what=what, numbers=cell.compare(got, ref, what),
                loss=got["loss"], ref_loss=ref["loss"],
                worst_leaf=worst_leaf(ref, what),
                seconds=time.perf_counter() - t0))
            print(line, flush=True)
            with open(args.out, "a") as f:
                f.write(line + "\n")
            del got
            gc.collect()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
