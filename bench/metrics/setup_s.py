"""Seconds from the process's start to the measured window: imports, the
problem built on the device, the solve compiled (or loaded from the
compilation cache) and one warm-up solve."""


def read(run):
    return run.setup_s
