"""The fullest held expert's rows over the mean held expert's, in the last
dispatch: read
as ``moe_expert_load_max_over_mean.nemo`` is, by that file's reader."""
from benchmark.lib import harness

read = harness.metric_reader("moe_expert_load_max_over_mean.nemo")
