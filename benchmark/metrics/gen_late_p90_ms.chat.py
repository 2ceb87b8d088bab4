"""90th percentile of how late the load generator sent a request against
its due time (it can only send between two step calls)."""
import numpy as np


def read(run):
    v = [r["late_s"] for r in run.requests]
    return 1e3 * float(np.percentile(v, 90)) if v else None
