"""Peak device memory on the fullest chip (live buffers + the running
program's reserved temporaries), GB."""
from benchmark.lib import harness

read = harness.metric_reader("hbm_peak_gb.chat")
