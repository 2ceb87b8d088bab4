"""Share of the decode program's device time in the window layers'
attention core (scope ``attn_window``: the softmax over each slot's
ring, its reads of the ring included). None where no operation carries
the scope."""
from benchmark.lib import program_scopes


def read(run):
    progs = run.counters.get("by_program") or {}
    return program_scopes.share_pct(progs.get("decode"), ("attn_window",))
