"""Share of the device's busy time under ``moe_route`` and ``moe_dispatch``
(what routing costs beside the experts' products): read
as ``moe_overhead_share_pct.nemo`` is, by that file's reader."""
from benchmark.lib import harness

read = harness.metric_reader("moe_overhead_share_pct.nemo")
