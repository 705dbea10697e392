"""Models: device milliseconds a step in XLA operations of the backward pass
(phase ``backward``: under ``transpose(``; forward arithmetic the compiler
re-does inside a backward fusion counts here), kernels and collectives apart."""

from benchmarks import scopes


def read(run):
    return scopes.phase_ms(run, "backward")
