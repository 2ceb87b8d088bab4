"""Readings for ``serve-qwen3next-reason``'s limits, and the rate sweep
that finds its knee; many seeds or rates in one process. Not run by the
benchmark's own runs.

    python3 benchmark/calibrate_qwen3next.py --seeds 1,2,3 \\
        --what program[,control,state_bf16,zero_state,no_conv_state,no_decay,decay_doubled,decay_after_write,no_gate,no_shared_gate,no_routed] \\
        [--others-on N] [--seconds S] [--warmup W] [--rate R] --out FILE
    python3 benchmark/calibrate_qwen3next.py --rates 1.0,1.5,2.0 --seeds 1 \\
        [--seconds S] [--stop-depth N] --out FILE

Each reads the cell's gap numbers (``qwen3next_serve_cell.gap_numbers``)
under ``<what>_<number>``:

``program``  the served tokens against the reference (the lower reading).
``control``  at the served prompts and tokens, the token the reference
             puts first in int8 (the upper reading).
``state_bf16`` the same with the reference's delta-rule state rounded to
             bfloat16 after every token (the nearest precision below the
             configuration's float32 state).
A fault (``lib/reference_qwen3next.FAULTS``): the served tokens against
the reference with that fault planted. ``--others-on N`` reads the control
and the faults on the first N seeds only, ``program`` on every seed.
``--rate`` offers that many requests a second in place of the traffic
file's.

With ``--rates``: for each offered rate, one window on one server (as
``calibrate_laguna.py`` sweeps the coding cell): requests finished, the
mean of busy slots, queue waits, the queue's depth at the close,
``tpot_p50_ms``; ``--stop-depth N`` ends the sweep after the first rate
that leaves N requests queued at the close.

A server is built for each seed (the compile cache serves its programs
again); its window is the cell's, ``--seconds`` long after ``--warmup``
seconds of traffic. One JSON line per seed or rate, appended to ``--out``.
"""

from __future__ import annotations

import argparse
import copy
import gc
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import calibrate_laguna  # noqa: E402
from benchmark.lib import harness  # noqa: E402

CELL = "serve-qwen3next-reason"


def calibrate(cell, cfg, traffic, seeds, whats, seconds, devices, peak, out,
              others_on=None):
    from benchmark.lib import flops_laguna, flops_qwen3next
    from benchmark.lib import qwen3next_serve_cell as qc

    for i, seed in enumerate(seeds):
        t0 = time.perf_counter()
        journal = qc.Collector()
        server = qc.build_server(cfg, traffic, journal)
        tree = qc.give_weights(server, cfg, seed)
        ctx = calibrate_laguna._ctx(cell, cfg, traffic, seed, seconds, devices,
                                    peak)
        run, sample = qc.serve_window(ctx, server, journal)
        del server
        gc.collect()
        rec = {"seed": seed, "failed": run.failed, "attempted": run.attempted,
               "tpot_p50_ms": run.end_to_end.get("tpot_p50_ms"),
               "experts_touched_mean": flops_laguna.experts_touched_mean(run),
               "active_mean": flops_qwen3next.active_mean(run),
               "memory_peak_bytes": run.memory_peak_bytes,
               "sample": [[int(r["req"].tokens.size), len(r["out"])] for r in sample],
               "serve_s": time.perf_counter() - t0}
        for what in whats:
            if what != "program" and others_on is not None and i >= others_on:
                continue
            t1 = time.perf_counter()
            kw = ({} if what == "program" else {"precision": "int8"}
                  if what == "control" else {"precision": what}
                  if what == "state_bf16" else {"fault": what})
            numbers = qc.gap_numbers(qc.sample_gaps(tree, cfg, traffic, sample, **kw))
            rec.update({f"{what}_{k}": v for k, v in numbers.items()})
            rec[what + "_s"] = time.perf_counter() - t1
        del tree
        gc.collect()
        calibrate_laguna._write(out, rec)


def sweep(cell, cfg, base, seed, rates, seconds, devices, peak, out,
          stop_depth=None):
    from benchmark.lib import qwen3next_serve_cell as qc

    journal = qc.Collector()
    server = qc.build_server(cfg, base, journal)
    qc.give_weights(server, cfg, seed)
    for rate in rates:
        traffic = copy.deepcopy(base)
        traffic["arrivals"]["rate_per_s"] = rate
        ctx = calibrate_laguna._ctx(cell, cfg, traffic, seed, seconds, devices,
                                    peak)
        run, _ = qc.serve_window(ctx, server, journal)
        depth = server.metrics.gauge("queue_depth").value
        while server.step():
            pass
        waits = [r["queue_s"] for r in run.requests if r["queue_s"] is not None]
        active = [ev["args"]["active"] for ev in run.spans
                  if ev.get("name") == "decode_chunk"]
        calibrate_laguna._write(out, dict(
            rate_per_s=rate, finished=len(run.requests), window_s=run.window_s,
            finished_per_s=len(run.requests) / run.window_s,
            slots_busy_mean=float(np.mean(active)) if active else 0.0,
            queue_wait_p50_s=float(np.median(waits)) if waits else None,
            queue_wait_max_s=float(np.max(waits)) if waits else None,
            queue_depth_at_close=depth, end_to_end=run.end_to_end,
            memory_peak_bytes=run.memory_peak_bytes,
            step_wall_s=run.counters["step_wall_s"]))
        if stop_depth is not None and depth >= stop_depth:
            break  # past the knee: a higher rate only grows the backlog


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--what", default="program")
    ap.add_argument("--rates")
    ap.add_argument("--seconds", type=float, default=45.0)
    ap.add_argument("--warmup", type=float)
    ap.add_argument("--rate", type=float, help="requests/s offered")
    ap.add_argument("--others-on", type=int,
                    help="read the control and the faults on this many seeds")
    ap.add_argument("--stop-depth", type=int,
                    help="sweep: stop after a rate that leaves this many queued")
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    cell = harness.workload(CELL)
    traffic = harness.traffic(cell["traffic"])
    if args.warmup is not None:
        traffic["arrivals"]["warmup_s"] = args.warmup
    if args.rate is not None:
        traffic["arrivals"]["rate_per_s"] = args.rate
    devices, peak = harness.require_chips(cell["chips"])
    harness.configure_cache()
    cfg = harness.config(cell["config"])
    seeds = [int(s) for s in args.seeds.split(",")]
    if args.rates:
        sweep(cell, cfg, traffic, seeds[0],
              [float(r) for r in args.rates.split(",")], args.seconds,
              devices, peak, args.out, args.stop_depth)
    else:
        calibrate(cell, cfg, traffic, seeds, args.what.split(","),
                  args.seconds, devices, peak, args.out, args.others_on)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
