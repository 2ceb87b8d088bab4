"""Median device-idle gap between the end of one step program and the
start of the next, in the trace."""
import statistics


def read(run):
    if run.trace is None:
        return None
    gaps = run.trace.module_gaps(run.traffic["programs"]["step"])
    return 1e3 * statistics.median(gaps) if gaps else None
