"""Share of the device's busy time under the scope ``ssm_scan`` (the
state-space layers' chunked scan: forward, backward and recompute)."""
from benchmark.lib import scope_shares


def read(run):
    return scope_shares.share_pct(run, "ssm_scan")
