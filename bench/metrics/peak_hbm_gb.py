"""Largest ``peak_bytes_in_use`` over the cell's devices, read as the window
ends and before the correctness check, in GB (1e9 bytes)."""


def read(run):
    return run.peak_bytes / 1e9 if run.peak_bytes else None
