"""Least time for one decode step (every matmul weight once in bf16 and
the live K and V rows of the resident requests, at the HBM peak) / the
device time of one decode step. The bytes the algorithm needs, not the
bytes this implementation moves."""
import statistics

from benchmark.lib import flops


def read(run):
    if run.trace is None:
        return None
    runs = run.trace.module_seconds(run.traffic["programs"]["decode"])
    chunks = [ev for ev in run.spans if ev.get("name") == "decode_chunk"]
    if not runs or not chunks:
        return None
    step_s = statistics.median(runs) / run.counters["chunk"]
    live_rows = run.counters["decode_key_rows"] / (len(chunks) * run.counters["chunk"])
    least = flops.decode_step_min_bytes(run.cfg, live_rows) / run.peaks["hbm_bytes_per_s"]
    return 100.0 * least / step_s
