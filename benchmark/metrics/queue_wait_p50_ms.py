"""Median wait in the server's queue before admission (the program's own
admission event), over the requests that finished in the window."""
import statistics


def read(run):
    waits = [r["queue_s"] for r in run.requests if r["queue_s"] is not None]
    return 1e3 * statistics.median(waits) if waits else None
