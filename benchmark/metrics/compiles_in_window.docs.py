"""Programs lowered inside the measured window (must read 0)."""


def read(run):
    return float(run.compiles_in_window)
