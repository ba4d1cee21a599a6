"""Time to the stated residual: the window's wall time over the solves
completed in it, every solve counted."""


def read(run):
    return run.window_s / run.solves
