"""The prefill's forward flash kernel: the least time of all its calls in
the trace / their device time. A call's least time from its shapes
(``lib/flops_laguna.flash_fwd_cost``): q.k and p.v over the pairs a query
may see, a full layer's every earlier key and a window layer's band only,
at the bf16 peak, or q, k, v and o once at the HBM peak, whichever is
longer. Which kind a call is, is read from its heads."""
from benchmark.lib import flops_laguna


def read(run):
    return flops_laguna.flash_fwd_roofline_pct(run)
