"""Share of the HBM roofline over the iteration's device operations: the
solver's least HBM bytes per iteration and chip (``counts/<solver>.py``)
times the traced iterations, over the union of the non-collective
operations' device time times the chip's HBM bandwidth (``peaks.json``).
The bound is HBM bandwidth: the iteration does about one flop per byte."""


def read(run):
    t = run.trace
    if t is None or run.peak is None or t.compute_busy_s <= 0:
        return None
    moved = run.least_bytes_per_iter * run.traced_iterations
    return 100.0 * moved / (t.compute_busy_s * run.peak["hbm_bytes_per_s"])
