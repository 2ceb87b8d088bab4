"""The fullest held expert's rows over the mean held expert's, in the
last dispatch (the program's gauges ``moe_expert_rows_max`` and
``moe_expert_rows_mean``: 1 is an even routing)."""


def read(run):
    mean = run.counters.get("moe_expert_rows_mean")
    if not mean:
        return None
    return run.counters["moe_expert_rows_max"] / mean
