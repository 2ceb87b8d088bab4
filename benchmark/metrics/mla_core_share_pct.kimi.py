"""Share of the device's busy time under ``attn_core``: latent
attention's softmax attention at 192-wide keys and 128-wide values (the
flash kernels and the copies round them)."""
from benchmark.lib import scope_shares


def read(run):
    return scope_shares.share_pct(run, "attn_core")
