"""Models: device milliseconds a step in XLA operations of the forward pass
(``scope_table`` phase ``forward``: under ``jvp(`` and not ``transpose(``),
kernels and collectives apart -- ``benchmarks/scopes.py``."""

from benchmarks import scopes


def read(run):
    return scopes.phase_ms(run, "forward")
