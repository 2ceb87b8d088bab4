"""The rate sweep that finds a serving cell's knee: one server, one
window per offered rate, on the chip. Run once when a cell is defined;
the rate then goes into the traffic file as a number (0.8 of the knee
for a cell below it).

    python3 benchmark/sweep.py --workload <cell> --rates 0.45,0.55,0.65 \\
        --seconds 45 --seed 1 --out FILE

Prints, for each rate: requests finished in the window, the mean number
of busy slots, queue waits, and the queue's depth when the window closed
(a backlog that grows from rate to rate is past the knee).
"""

from __future__ import annotations

import argparse
import copy
import json
import os
import sys
import time
import types

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark.lib import harness  # noqa: E402


def main(argv=None) -> int:
    from benchmark.lib import serve_cell as sc

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=45.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    cell = harness.workload(args.workload)
    base = harness.traffic(cell["traffic"])
    devices, peak = harness.require_chips(cell["chips"])
    harness.configure_cache()
    journal = sc.Collector()
    cfg = harness.config(cell["config"])
    server = sc.build_server(cfg, base, args.seed, journal)
    for rate in (float(r) for r in args.rates.split(",")):
        traffic = copy.deepcopy(base)
        traffic["arrivals"]["rate_per_s"] = rate
        ctx = types.SimpleNamespace(
            cell=cell["name"], cfg=cfg, traffic=traffic, chips=cell["chips"],
            seed=args.seed, seconds=args.seconds, devices=devices, peaks=peak,
            t0=time.perf_counter(), compiles=harness.CompileCounter(),
            tracer=harness.Tracer(False, 0.0), mark=lambda name: None)
        run, _ = sc.serve_window(ctx, server, journal)
        depth = server.metrics.gauge("queue_depth").value
        while server.step():
            pass
        waits = [r["queue_s"] for r in run.requests]
        active = [ev["args"]["active"] for ev in run.spans
                  if ev.get("name") == "decode_chunk"]
        rec = dict(
            rate_per_s=rate, finished=len(run.requests), window_s=run.window_s,
            finished_per_s=len(run.requests) / run.window_s,
            slots_busy_mean=float(np.mean(active)) if active else 0.0,
            queue_wait_p50_s=float(np.median(waits)) if waits else None,
            queue_wait_max_s=float(np.max(waits)) if waits else None,
            queue_depth_at_close=depth, end_to_end=run.end_to_end)
        line = json.dumps(rec)
        print(line, flush=True)
        with open(args.out, "a") as f:
            f.write(line + "\n")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
