"""Optimizer and collectives: device milliseconds a step in XLA operations
that hold the inner optax update alone (phase ``optimizer``: under
``hvd_optimizer``); what rides in a gradient matmul is ``mixed_phase_ms``."""

from benchmarks import scopes


def read(run):
    return scopes.phase_ms(run, "optimizer")
