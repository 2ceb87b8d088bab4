"""Share of a whole-table read of the KV pool that the window's decode
steps still make: 100 x sum of ``kv_blocks_read`` / sum of (slots x a
table's blocks) over the program's decode_chunk spans. ``kv_blocks_read``
is the entries of the live-block list that one step of one layer walks
(the resident blocks, rounded up to whole tiles); slots x table blocks is
what a step read of every layer before the list. None where a span lacks
the argument (a program from before PR 29)."""


def read(run):
    chunks = [ev["args"] for ev in run.spans if ev.get("name") == "decode_chunk"]
    if not chunks or any("kv_blocks_read" not in a for a in chunks):
        return None
    server = run.traffic["server"]
    table = -(-run.cfg["n_positions"] // server["block_size"])
    return 100.0 * sum(a["kv_blocks_read"] for a in chunks) / (
        len(chunks) * server["slots"] * table)
