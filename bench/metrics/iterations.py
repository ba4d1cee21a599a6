"""BiCGStab iterations per solve (``SolveResult.iterations``), mean over the
window's solves."""


def read(run):
    return sum(run.iterations) / len(run.iterations)
