"""Readings for the limits of "How correct is decided", many seeds in one
process (set-up paid once). Not run by the benchmark's own runs.

    python3 benchmark/calibrate.py --workload <cell> --seeds 1,2,3 \\
        --what program[,control_ref,half_batch,no_exchange] [--seconds S] --out FILE

``program``     the program against the reference (the lower reading).
``control``     training: the program with its own int8 matmul path
                (``TrainConfig.matmul_dtype``) against the reference;
                serving: at the served prompts and tokens, the token the
                reference puts first in int8 (the upper reading).
``control_ref`` training: the reference computed in int8, forward and
                backward products alike, put in the program's place.
``half_batch``, ``no_exchange``  training: the fault planted in the
                reference put in the program's place.
One JSON line per seed, appended to ``--out``.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time
import types

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark.lib import harness  # noqa: E402


def train(ctx, seeds, whats, write) -> None:
    """For each seed one reference run, and one run of each of ``whats``
    against it."""
    from distributed_tensorflow_tpu.utils.logging import StepLogger

    from benchmark.lib import traffic as traffic_lib, train_cell as tc

    cfg, traffic, chips = ctx.cfg, ctx.traffic, ctx.chips
    steps = traffic["steps_per_dispatch"]
    batch = tc.global_batch(traffic, chips)
    logger = StepLogger(freq=10 ** 9, print_fn=lambda *a: None)
    trainer = None
    own = [w for w in whats if w in ("program", "control")]
    if len(own) > 1:
        raise SystemExit("program and control need a trainer each: two calls")
    for seed in seeds:
        rows = traffic_lib.train_rows(traffic, cfg["vocab_size"], seed, steps * batch)
        runs = {}
        if own:
            if trainer is None:
                kw = {"matmul_dtype": "int8"} if own[0] == "control" else {}
                trainer = tc.build_trainer(cfg, traffic, chips, ctx.devices, rows, kw)
            else:
                trainer.datasets = type(trainer.datasets)(
                    tc.Rows(rows), trainer.datasets.validation, trainer.datasets.test)
            tc.give_weights(trainer, cfg, seed)
            runs[own[0]] = tc.program_readings(trainer, cfg, seed, logger)
            trainer.state = None
            gc.collect()
        for what in whats:
            if what not in runs:
                runs[what] = tc.reference_readings(
                    cfg, traffic, seed, rows, ctx.devices, steps, keep_moment=True,
                    **({"precision": "int8"} if what == "control_ref"
                       else {"fault": what}))
        ref = tc.reference_readings(
            cfg, traffic, seed, rows, ctx.devices, steps,
            against={w: r["moment_tree"] for w, r in runs.items()})
        for what, got in runs.items():
            write(dict(seed=seed, what=what, numbers=tc.compare(got, ref, what),
                       loss=got["loss"], ref_loss=ref["loss"]))


def serve(ctx, seeds, what, write) -> None:
    from benchmark.lib import serve_cell as sc, train_cell as tc, weights

    journal = sc.Collector()
    server = None
    for seed in seeds:
        ctx.seed = seed
        if server is None:
            server = sc.build_server(ctx.cfg, ctx.traffic, seed, journal)
        else:
            # The program's own live weight swap: no program recompiles.
            server.request_swap(tc.to_program_params(weights.make(ctx.cfg, seed)))
            server.step()
        ctx.t0 = time.perf_counter()
        run, sample = sc.serve_window(ctx, server, journal)
        while server.step():  # let what is left finish: the next seed
            pass              # starts on an empty server
        # The reference needs the chip's memory: the server keeps only
        # its weights and pool here, the reference adds its own weights.
        gaps = sc.reference_gaps(ctx.cfg, seed, sample)
        rec = dict(seed=seed, what=what, checked_tokens=int(sum(g.size for g in gaps)),
                   max_logit_gap=sc.widest_gap(gaps),
                   end_to_end=run.end_to_end, finished=len(run.requests))
        if what == "control":
            low = sc.reference_gaps(ctx.cfg, seed, sample, precision="int8")
            rec["control_max_logit_gap"] = sc.widest_gap(low)
            rec["control_gaps_each"] = [float(g.max()) for g in low]
        write(rec)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--what", required=True,
                    help="training: a comma-separated list, one reference run "
                         "a seed serves them all")
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    cell = harness.workload(args.workload)
    traffic = harness.traffic(cell["traffic"])
    devices, peak = harness.require_chips(cell["chips"])
    harness.configure_cache()
    ctx = types.SimpleNamespace(
        cell=cell["name"], cfg=harness.config(cell["config"]), traffic=traffic,
        chips=cell["chips"], seed=0, seconds=args.seconds, devices=devices,
        peaks=peak, t0=time.perf_counter(), compiles=harness.CompileCounter(),
        tracer=harness.Tracer(False, 0.0), mark=lambda name: None,
    )

    def write(rec: dict) -> None:
        line = json.dumps(rec)
        print(line, flush=True)
        with open(args.out, "a") as f:
            f.write(line + "\n")

    seeds = [int(s) for s in args.seeds.split(",")]
    if traffic["kind"] == "train":
        train(ctx, seeds, args.what.split(","), write)
    else:
        serve(ctx, seeds, args.what, write)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
