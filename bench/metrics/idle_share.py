"""Share of the traced window in which the device runs no operation, from
the union of the operations' intervals, averaged over the devices."""


def read(run):
    t = run.trace
    if t is None:
        return None
    return 100.0 * (1.0 - t.busy_s / t.window_s)
