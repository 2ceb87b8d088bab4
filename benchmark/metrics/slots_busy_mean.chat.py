"""Mean number of resident requests over the window's decode chunks (the
``active`` field of the program's decode_chunk spans)."""


def read(run):
    active = [ev["args"]["active"] for ev in run.spans
              if ev.get("name") == "decode_chunk"]
    return sum(active) / len(active) if active else None
