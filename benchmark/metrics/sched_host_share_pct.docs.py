"""Host time inside TextServer.step outside its dispatch-and-fetch spans
(the program's own prefill and decode_chunk spans) / window."""


def read(run):
    wall = run.counters.get("step_wall_s")
    if not wall:
        return None
    inside = sum(ev["dur_us"] for ev in run.spans) * 1e-6
    return 100.0 * max(wall - inside, 0.0) / run.window_s
