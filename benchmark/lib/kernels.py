"""Finding the flash-attention kernels in a trace, and their roofline.

The program gives its Pallas kernels no stable name yet (PERF.md, Open
questions): in the trace they are ``custom-call`` operations. The
forward kernel returns (o, lse): two results, the second with a last
dimension of 1. The fused backward returns (dq partials, dk, dv): three
results. Shapes are [batch*heads, seq, head_dim] on one chip.
"""

from __future__ import annotations

from benchmark.lib import flops, trace


def flash_calls(summary, backward: bool):
    """(batch*heads, seq, head_dim), [seconds per call] of the forward or
    backward flash kernel's calls on chip 0."""
    shape, calls = None, []
    for key, hlo in summary.hlo_of.items():
        if summary.opcode_of.get(key) != "custom-call":
            continue
        res = trace.result_shapes(hlo)
        is_bwd = len(res) == 3 and len(res[1][1]) == 3
        is_fwd = len(res) == 2 and len(res[0][1]) == 3 and res[1][1][-1:] == (1,)
        if (backward and is_bwd) or (not backward and is_fwd):
            shape = res[1][1] if backward else res[0][1]
            calls += summary.op_calls.get(key, [])
    return shape, calls


def flash_roofline_pct(run, backward: bool):
    if run.trace is None:
        return None
    shape, calls = flash_calls(run.trace, backward)
    if not calls:
        return None
    bh, seq, dh = shape
    cost = flops.flash_bwd_cost if backward else flops.flash_fwd_cost
    least = flops.roofline_seconds(*cost(1, bh, seq, dh), run.peaks)
    return 100.0 * least / (sum(calls) / len(calls))
