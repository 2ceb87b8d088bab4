"""Share of the device's busy time in the experts' products: the scopes
``moe_experts`` and ``moe_shared``, and the routed experts' ragged-product
kernels, which XLA names ``ragged-dot-none`` and leaves outside every
scope."""
from benchmark.lib import scope_shares


def read(run):
    return scope_shares.share_pct(
        run, "moe_experts", "moe_shared", unscoped_op_names=("ragged-dot",))
