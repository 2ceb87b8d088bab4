"""The flash kernels at two head sizes in a trace, and their roofline.

The kernels are found by the names the program gives them
(``observability/names.py``: ``flash_fwd``; ``flash_bwd_fused``, or the
two-kernel split ``flash_bwd_dq`` + ``flash_bwd_dkv`` where the fused
kernel's dq partials would not fit), as the trace shows them: a
``custom-call`` whose HLO name holds the kernel's. Head sizes are read
from the results' shapes: o is ``[batch*heads, seq, v_dim]``, dq and dk
``[.., qk_dim]``. A trace without such a kernel (a program that lacks
them) reads None.
"""

from __future__ import annotations

from benchmark.lib import flops, flops_kimi_linear, trace

FWD = "flash_fwd"
BWD_FUSED = "flash_bwd_fused"
BWD_SPLIT = ("flash_bwd_dq", "flash_bwd_dkv")


def calls_of(summary, kernel: str):
    """(result shapes of the kernel's first call, [seconds per call]) over
    the ``custom-call`` operations on chip 0 whose name holds ``kernel``."""
    shapes, calls = None, []
    for key, hlo in summary.hlo_of.items():
        if summary.opcode_of.get(key) != "custom-call" or kernel not in key:
            continue
        if shapes is None:
            shapes = [dims for _, dims in trace.result_shapes(hlo)]
        calls += summary.op_calls.get(key, [])
    return shapes, calls


def _mean(xs):
    return sum(xs) / len(xs)


def flash_roofline_pct(run, backward: bool):
    """100 x the least time of one call / the mean device time of one
    call (forward), or of one backward (the fused kernel's call, or a dq
    call and a dkv call together)."""
    if run.trace is None:
        return None
    if not backward:
        shapes, calls = calls_of(run.trace, FWD)
        if not calls:
            return None
        (bh, seq, v_dim), qk_dim = shapes[0], _qk_dim(run)
        took = _mean(calls)
        cost = flops_kimi_linear.flash_fwd_cost(bh, seq, qk_dim, v_dim)
    else:
        shapes, calls = calls_of(run.trace, BWD_FUSED)
        if calls:
            (_, bh, seq, qk_dim), v_dim = shapes[0], shapes[2][-1]
            took = _mean(calls)
        else:
            (dq, dq_calls), (dkv, dkv_calls) = (
                calls_of(run.trace, k) for k in BWD_SPLIT)
            if not dq_calls or not dkv_calls:
                return None
            (bh, seq, qk_dim), v_dim = dq[0], dkv[1][-1]
            took = _mean(dq_calls) + _mean(dkv_calls)
        cost = flops_kimi_linear.flash_bwd_cost(bh, seq, qk_dim, v_dim)
    return 100.0 * flops.roofline_seconds(*cost, run.peaks) / took


def _qk_dim(run) -> int:
    """The forward kernel returns o and lse, neither as wide as q and k:
    their width is the configuration's."""
    return run.cfg["qk_nope_head_dim"] + run.cfg["qk_rope_head_dim"]
