"""Share of the traced device-busy time inside prefill programs (as
``prefill_share_pct.chat``, of the program the traffic file names)."""
from benchmark.lib import harness

read = harness.metric_reader("prefill_share_pct.chat")
