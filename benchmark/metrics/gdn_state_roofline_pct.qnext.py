"""The state update's least time / its device time, a decode step. Least
time from the bytes it must move (``lib/flops_qwen3next.state_update_cost``:
the active slots x the delta-rule layers x a read and a write of each
float32 state and of its convolution rows, whatever the program reads) at
the HBM peak, or its operations at the bf16 peak, whichever is longer;
device time: the decode program's operations under ``gdn_state`` and
``gdn_conv`` / its steps in the trace. None where no operation carries
the scopes or the spans count no slot-steps."""
from benchmark.lib import flops, flops_qwen3next


def read(run):
    dec = (run.counters.get("by_program") or {}).get("decode")
    active = flops_qwen3next.active_mean(run)
    if not dec or not active:
        return None
    took = sum(dec["by_scope"].get(s, 0.0) for s in ("gdn_state", "gdn_conv"))
    if took <= 0:
        return None
    per_step = took / (dec["runs"] * run.counters["chunk"])
    least = flops.roofline_seconds(
        *flops_qwen3next.state_update_cost(run.cfg, active), run.peaks)
    return 100.0 * least / per_step
