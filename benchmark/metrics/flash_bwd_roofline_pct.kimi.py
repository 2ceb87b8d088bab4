"""Backward flash kernels at two head sizes: least time for one backward
(five products over the causal pairs) / mean device time of one in the
trace: the fused kernel's call, or a dq call and a dkv call together where
the program takes the two-kernel split."""
from benchmark.lib import kernels_two_sizes


def read(run):
    return kernels_two_sizes.flash_roofline_pct(run, backward=True)
