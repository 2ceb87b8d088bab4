"""Share of the device's busy time under ``kda_conv`` and ``kda_gate``:
the delta-rule layers' convolutions and their decay and normalisation
arithmetic, what is neither a projection nor the scan."""
from benchmark.lib import scope_shares


def read(run):
    return scope_shares.share_pct(run, "kda_conv", "kda_gate")
