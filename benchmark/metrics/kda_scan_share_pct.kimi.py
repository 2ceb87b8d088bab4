"""Share of the device's busy time under the scope ``kda_scan`` (the
delta-rule layers' chunked scan: forward, backward and recompute)."""
from benchmark.lib import scope_shares


def read(run):
    return scope_shares.share_pct(run, "kda_scan")
