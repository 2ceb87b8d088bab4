"""Device time of one decode step: median duration of the decode-chunk
program's runs in the trace / steps in a chunk."""
import statistics


def read(run):
    if run.trace is None:
        return None
    runs = run.trace.module_seconds(run.traffic["programs"]["decode"])
    return 1e3 * statistics.median(runs) / run.counters["chunk"] if runs else None
