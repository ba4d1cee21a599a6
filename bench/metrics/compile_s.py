"""Seconds to lower and compile the solve (``jit(...).lower().compile()``),
served from the persistent compilation cache after a cell's first run."""


def read(run):
    return run.compile_s
