"""Device: `memory_stats()["peak_bytes_in_use"]` of the fullest chip, read
after the window and before the reference runs."""


def read(run):
    return run.peak_bytes / 2**30 if run.peak_bytes else None
