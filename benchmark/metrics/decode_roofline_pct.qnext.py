"""Least time for one decode step / its device time. The least bytes a
step must move (``lib/flops_qwen3next.decode_step_min_bytes``): every
weight but the held routed experts and the embedding table once, the
routed experts the step's tokens chose once each (the mean of the
``decode_chunk`` spans' ``experts_touched``), the live K and V rows of
the full layers, and the active slots' recurrent and convolution states
read and written, at the HBM peak. None where the program counts no
experts touched."""
from benchmark.lib import flops_qwen3next


def read(run):
    return flops_qwen3next.decode_roofline_pct(run)
