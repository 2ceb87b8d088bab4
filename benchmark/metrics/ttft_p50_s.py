"""Median time from the instant a request was due to its first token."""
import statistics


def read(run):
    v = [r["ttft_s"] for r in run.requests]
    return statistics.median(v) if v else None
