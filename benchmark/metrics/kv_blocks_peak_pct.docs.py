"""Most KV blocks in use at a step boundary of the window / blocks in
the pool (the allocator's own gauge)."""


def read(run):
    c = run.counters
    if not c.get("kv_blocks_total"):
        return None
    return 100.0 * c["kv_blocks_peak"] / c["kv_blocks_total"]
