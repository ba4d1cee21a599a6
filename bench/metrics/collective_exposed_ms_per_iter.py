"""The part of the collectives' device time in which no other operation
runs on that device, per traced iteration, averaged over the devices."""


def read(run):
    t = run.trace
    if t is None or t.n_collective_ops == 0:
        return None
    return 1e3 * t.exposed_s / run.traced_iterations
