"""Fused backward flash kernel: least time for one call / mean device
time of its calls in the trace."""
from benchmark.lib import kernels


def read(run):
    return kernels.flash_roofline_pct(run, backward=True)
