"""Time in collective operations on the core's own instruction stream
(where nothing else runs meanwhile) / traced window, mean over chips."""


def read(run):
    t = run.trace
    if t is None or t.collective_exposed_s <= 0:
        return None
    return 100.0 * t.collective_exposed_s / t.window_s
