"""Share of the device's busy time under ``moe_route`` and
``moe_dispatch``: what routing costs beside the experts' products (the
router, the sort, the gather of routed rows, the weighted return)."""
from benchmark.lib import scope_shares


def read(run):
    return scope_shares.share_pct(run, "moe_route", "moe_dispatch")
