"""Optimizer and collectives: milliseconds a step of collective time after the
last ``backward`` operation ends: what no reordering inside backward hides."""

from benchmarks import scopes


def read(run):
    return scopes.position_ms(run, "tail")
