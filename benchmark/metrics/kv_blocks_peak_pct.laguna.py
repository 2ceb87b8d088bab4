"""Most blocks of the full layers' pool in use at a step boundary of the
window / blocks in the pool (as ``kv_blocks_peak_pct.chat``; the window
layers' rings are allocated once and are not in it)."""
from benchmark.lib import harness

read = harness.metric_reader("kv_blocks_peak_pct.chat")
