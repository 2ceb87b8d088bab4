"""Least time for one decode step / its device time. The least bytes a
step must read (``lib/flops_laguna.decode_step_min_bytes``): every weight
but the routed experts once, the routed experts the step's tokens chose
once each (the mean of the ``decode_chunk`` spans' ``experts_touched``),
and the live K and V rows of the residents, a full layer's every earlier
position and a window layer's last ``window`` at most, at the HBM peak.
None where the program counts no experts touched."""
import statistics

from benchmark.lib import flops_laguna


def read(run):
    if run.trace is None:
        return None
    runs = run.trace.module_seconds(run.traffic["programs"]["decode"])
    touched = flops_laguna.experts_touched_mean(run)
    if not runs or touched is None:
        return None
    chunk = run.counters["chunk"]
    steps = flops_laguna.decode_steps(run)
    rows_full = run.counters["decode_key_rows_full"] / steps
    rows_window = run.counters["decode_key_rows_window"] / steps
    least = flops_laguna.decode_step_min_bytes(
        run.cfg, touched, rows_full, rows_window) / run.peaks["hbm_bytes_per_s"]
    return 100.0 * least / (statistics.median(runs) / chunk)
