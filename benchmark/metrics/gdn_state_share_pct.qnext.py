"""Share of the decode program's device time in the delta-rule layers'
one-token state update and their convolution with its shift of the
slot's last rows (scopes ``gdn_state`` and ``gdn_conv``,
``lib/program_scopes.py`` over the runs of the decode chunk in the
trace). None where no operation carries the scopes."""
from benchmark.lib import program_scopes


def read(run):
    progs = run.counters.get("by_program") or {}
    return program_scopes.share_pct(progs.get("decode"),
                                    ("gdn_state", "gdn_conv"))
