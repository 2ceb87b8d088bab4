"""Shares of the device's busy time by the program's scopes, for the
metrics that read a traced run's ``counters["by_scope"]`` (the driver's
``benchmark/scopes.reduce_xplane`` of the profile: forward, backward and
recompute together)."""

from __future__ import annotations


def share_pct(run, *scopes: str, unscoped_op_names: tuple = ()):
    """The summed share of ``scopes``, plus that of the unscoped
    operations whose ``op_name`` starts with one of ``unscoped_op_names``
    (XLA renames what it rewrites: a ragged product's kernel arrives as
    ``ragged-dot-none``, outside every scope). None where the run has no
    reduction by scope, where no scope of these appears in it, or where
    such operations were asked for and none is found by name: the rest
    alone would read as the whole and hide a kernel that was renamed."""
    by_scope = run.counters.get("by_scope")
    if not by_scope:
        return None
    totals = by_scope.get("scope_total_pct", {})
    found = [totals[s] for s in scopes if s in totals]
    if unscoped_op_names:
        kernels = [
            g["share_pct"] for g in by_scope.get("unscoped_by_op_name", [])
            if g["op_name"].startswith(unscoped_op_names)]
        if not kernels:
            return None
        found += kernels
    return float(sum(found)) if found else None
