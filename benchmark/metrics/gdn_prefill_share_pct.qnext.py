"""Share of the prefill program's device time in the delta-rule layers'
chunked scan (scope ``gdn_scan``: the decayed scores' kernels, the
triangular solves and the carry over chunks), over the runs of the
prefill program in the trace. None where no operation carries the
scope."""
from benchmark.lib import program_scopes


def read(run):
    progs = run.counters.get("by_program") or {}
    return program_scopes.share_pct(progs.get("prefill"), ("gdn_scan",))
