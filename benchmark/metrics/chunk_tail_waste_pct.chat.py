"""Share of the window's decode slot-steps that delivered no token: 100 x
(1 - sum of ``emitted`` / sum of ``slot_steps``) over the program's
decode_chunk spans. A slot that finishes inside a chunk rides masked to
the chunk's end; this is that tail. None where a span lacks the two
arguments (a program from before PR 26)."""


def read(run):
    chunks = [ev["args"] for ev in run.spans if ev.get("name") == "decode_chunk"]
    if not chunks or any(
            "emitted" not in a or "slot_steps" not in a for a in chunks):
        return None
    steps = sum(a["slot_steps"] for a in chunks)
    if not steps:
        return None
    return 100.0 * (1.0 - sum(sum(a["emitted"]) for a in chunks) / steps)
