"""Programs lowered inside the measured window (must read 0)."""
from benchmark.lib import harness

read = harness.metric_reader("compiles_in_window.chat")
