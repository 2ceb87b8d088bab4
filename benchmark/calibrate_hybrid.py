"""Readings for the limits of a ``hybrid_train`` cell, many seeds in one
process (set-up paid once); ``calibrate.py``'s counterpart for this kind.
Not run by the benchmark's own runs.

    python3 benchmark/calibrate_hybrid.py --workload <cell> --seeds 1,2,3 \\
        --what program[,control_ref,half_batch,no_routed,no_carry] --out FILE

``program``      the program against the reference (the lower reading).
``control_ref``  the reference with int8 products, forward and backward
                 alike, put in the program's place (the upper reading).
``half_batch``, ``no_routed``, ``no_carry``  the fault planted in the
                 reference put in the program's place: half of the batch
                 left out; the routed experts' contribution left out; the
                 state not carried across chunk boundaries.
One reference run a seed serves them all. One JSON line per seed and
``what``, appended to ``--out``.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark.lib import harness  # noqa: E402


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

    from benchmark.lib import hybrid_train_cell as cell, traffic as traffic_lib

    steps = traffic["steps_per_dispatch"]
    batch = cell.global_batch(traffic, w["chips"])
    logger = StepLogger(freq=10 ** 9, print_fn=lambda *a: None)
    whats = args.what.split(",")
    trainer = None
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        rows = traffic_lib.train_rows(traffic, cfg["vocab_size"], seed, steps * batch)
        runs = {}
        if "program" in whats:
            if trainer is None:
                trainer = cell.build_trainer(cfg, traffic, w["chips"], devices, rows)
            else:
                trainer.datasets = type(trainer.datasets)(
                    cell.Rows(rows), trainer.datasets.validation,
                    trainer.datasets.test)
            cell.give_weights(trainer, cfg, seed)
            runs["program"] = cell.program_readings(trainer, cfg, seed, logger)
            trainer.state = None
            gc.collect()
        for what in whats:
            if what not in runs:
                runs[what] = cell.reference_readings(
                    cfg, traffic, seed, rows, steps, keep_moment=True,
                    **({"precision": "int8"} if what == "control_ref"
                       else {"fault": what}))
        ref = cell.reference_readings(
            cfg, traffic, seed, rows, steps,
            against={k: r["moment_tree"] for k, r in runs.items()})
        for what, got in runs.items():
            line = json.dumps(dict(
                seed=seed, what=what, numbers=cell.compare(got, ref, what),
                loss=got["loss"], ref_loss=ref["loss"],
                seconds=time.perf_counter() - t0))
            print(line, flush=True)
            with open(args.out, "a") as f:
                f.write(line + "\n")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
