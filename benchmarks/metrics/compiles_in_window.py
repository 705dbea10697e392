"""Benchmark loop: backend compilations (``jax.monitoring``) between the
window's first and last stamp.  The step is compiled ahead of time, so
anything other than 0 is a fault and makes the run incorrect."""


def read(run):
    return run.compiles_in_window
