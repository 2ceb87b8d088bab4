"""The benchmark's one command.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process. Prints one JSON object as the last line of standard output
(see benchmark/README.md). Exits 3, with no result line, where JAX finds
no TPU, fewer chips than the cell asks for, or a chip that
benchmark/lib/peaks.py does not know.
"""

from __future__ import annotations

import time

T0 = time.perf_counter()  # set-up is counted from here

import argparse  # noqa: E402
import importlib  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark.lib import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = harness.workload(args.workload)
    traffic = harness.traffic(cell["traffic"])
    devices, peak = harness.require_chips(cell["chips"])
    harness.configure_cache()
    ctx = types.SimpleNamespace(
        cell=cell["name"], cfg=harness.config(cell["config"]), traffic=traffic,
        chips=cell["chips"], seed=args.seed, seconds=args.seconds,
        devices=devices, peaks=peak, t0=T0,
        compiles=harness.CompileCounter(),
        tracer=harness.Tracer(bool(args.trace), traffic["trace_seconds"]),
        mark=harness.Marks(T0),
    )
    ctx.mark("import_and_devices")
    driver = importlib.import_module(f"benchmark.lib.{traffic['kind']}_cell")
    run = driver.run(ctx)
    ctx.mark("trace_reduction")
    ctx.mark.report()
    return harness.emit(run, devices, bool(args.trace))


if __name__ == "__main__":
    raise SystemExit(main())
