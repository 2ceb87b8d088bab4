"""Forward flash kernel: least time for one call (operations over the
causal pairs at the bf16 peak, or q, k, v, o once at the HBM peak,
whichever is longer) / mean device time of its calls in the trace."""
from benchmark.lib import kernels


def read(run):
    return kernels.flash_roofline_pct(run, backward=False)
