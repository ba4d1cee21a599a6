"""Milliseconds per iteration: the window's wall time over all iterations
of the solves completed in it."""


def read(run):
    return 1e3 * run.window_s / sum(run.iterations)
