"""Median time between consecutive deliveries of tokens to one request:
what a streaming client sees between bursts. A delivery to a request is
the end (``ts_us + dur_us``) of a decode_chunk span of the window whose
``rids`` hold the request and whose ``emitted`` for it is above 0; the
gaps of every request resident in the window are pooled. None where a
span lacks ``emitted`` (a program from before PR 26)."""
import statistics


def read(run):
    chunks = [ev for ev in run.spans if ev.get("name") == "decode_chunk"]
    if not chunks or any("emitted" not in ev["args"] for ev in chunks):
        return None
    delivered: dict = {}
    for ev in sorted(chunks, key=lambda ev: ev["ts_us"]):
        for rid, n in zip(ev["args"]["rids"], ev["args"]["emitted"]):
            if n > 0:
                delivered.setdefault(rid, []).append(ev["ts_us"] + ev["dur_us"])
    gaps = [b - a for ends in delivered.values() for a, b in zip(ends, ends[1:])]
    return 1e-3 * statistics.median(gaps) if gaps else None
