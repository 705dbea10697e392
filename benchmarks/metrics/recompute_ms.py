"""Models: device milliseconds a step in forward work that ``nn.remat`` re-runs
inside backward (phase ``recompute``: under jax's ``rematted_computation``):
the work ``mfu`` leaves out by construction.  0 where nothing remats."""

from benchmarks import scopes


def read(run):
    return scopes.phase_ms(run, "recompute")
