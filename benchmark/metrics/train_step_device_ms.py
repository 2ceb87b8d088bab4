"""Device time of one optimizer step: median duration of the step
program's runs in the trace / steps per dispatch."""
import statistics


def read(run):
    if run.trace is None:
        return None
    runs = run.trace.module_seconds(run.traffic["programs"]["step"])
    if not runs:
        return None
    return 1e3 * statistics.median(runs) / run.counters["steps_per_dispatch"]
