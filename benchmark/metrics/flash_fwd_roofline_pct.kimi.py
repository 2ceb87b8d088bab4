"""Forward flash kernel at two head sizes: least time for one call (q.k at
192 and p.v at 128 over the causal pairs at the bf16 peak, or q, k, v, o
once at the HBM peak, whichever is longer) / mean device time of its calls
in the trace."""
from benchmark.lib import kernels_two_sizes


def read(run):
    return kernels_two_sizes.flash_roofline_pct(run, backward=False)
