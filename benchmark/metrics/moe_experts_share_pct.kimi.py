"""Share of the device's busy time in the experts' products (the scopes
``moe_experts`` and ``moe_shared`` and the ``ragged-dot`` kernels XLA leaves
outside every scope): read
as ``moe_experts_share_pct.nemo`` is, by that file's reader."""
from benchmark.lib import harness

read = harness.metric_reader("moe_experts_share_pct.nemo")
