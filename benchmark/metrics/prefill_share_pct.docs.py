"""Share of the traced device-busy time inside prefill programs."""


def read(run):
    t = run.trace
    if t is None or t.busy_s <= 0:
        return None
    runs = t.module_seconds(run.traffic["programs"]["prefill"])
    return 100.0 * sum(runs) / t.busy_s if runs else None
