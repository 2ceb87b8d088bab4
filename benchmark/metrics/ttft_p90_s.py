"""90th percentile of the time from due to first token (a window holds
some 25 requests: no higher tail has samples beyond it)."""
import numpy as np


def read(run):
    v = [r["ttft_s"] for r in run.requests]
    return float(np.percentile(v, 90)) if v else None
