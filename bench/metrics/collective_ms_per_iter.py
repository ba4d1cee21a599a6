"""Device time of the collectives (all-reduce, collective-permute, ...) per
traced iteration: the union of their intervals on each device, averaged
over the devices.  Nothing to read where the trace holds no collective."""


def read(run):
    t = run.trace
    if t is None or t.n_collective_ops == 0:
        return None
    return 1e3 * t.collective_s / run.traced_iterations
