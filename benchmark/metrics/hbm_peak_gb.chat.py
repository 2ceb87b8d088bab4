"""Peak device memory on the fullest chip (live buffers + the running
program's reserved temporaries), GB."""


def read(run):
    return run.memory_peak_bytes / 1e9
