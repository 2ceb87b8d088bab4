"""Share of the decode program's device time in the full layers'
attention core (scope ``attn_full``: the walk of the block pool's live
blocks and its running softmax). None where no operation carries the
scope."""
from benchmark.lib import program_scopes


def read(run):
    progs = run.counters.get("by_program") or {}
    return program_scopes.share_pct(progs.get("decode"), ("attn_full",))
