"""Share of the decode program's device time in the expert layers: its
operations under ``moe_route``, ``moe_dispatch``, ``moe_experts`` and
``moe_shared``, and the routed experts' ``ragged-dot`` kernels, which XLA
leaves outside every scope (``lib/program_scopes.py`` over the runs of
the decode chunk in the trace)."""
from benchmark.lib import program_scopes


def read(run):
    progs = run.counters.get("by_program") or {}
    return program_scopes.share_pct(
        progs.get("decode"),
        ("moe_route", "moe_dispatch", "moe_experts", "moe_shared"),
        ("ragged-dot",))
