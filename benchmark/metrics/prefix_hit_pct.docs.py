"""Prompt blocks found in the prefix cache / prompt blocks looked up, in
the window (the server's prefix_cache_hits and _misses counters)."""


def read(run):
    c = run.counters
    looked = c.get("prefix_hit_blocks", 0) + c.get("prefix_miss_blocks", 0)
    return 100.0 * c["prefix_hit_blocks"] / looked if looked else None
