"""Device time of one decode step: median duration of the decode-chunk
program's runs in the trace / steps in a chunk (as ``decode_step_ms.chat``)."""
from benchmark.lib import harness

read = harness.metric_reader("decode_step_ms.chat")
