"""Whole step: model operations per token (forward + backward, no
recomputation) x tokens per second of the traced dispatches / (chips x
peak). Tokens and seconds are of whole traced dispatches."""


def read(run):
    c, t = run.counters, run.trace
    if t is None or not c.get("traced_dispatches"):
        return None
    tokens = c["traced_dispatches"] * c["tokens_per_dispatch"]
    rate = tokens / c["traced_host_s"]
    return 100.0 * rate * c["flops_per_token"] / (
        run.chips * run.peaks["bf16_flops_per_s"])
