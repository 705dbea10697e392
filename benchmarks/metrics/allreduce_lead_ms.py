"""Optimizer and collectives: milliseconds a step from the start of the first
bucket's first collective to the end of the last ``backward`` operation on
the same chip (0 if the collective starts later): the backward that was left
for the all-reduce to hide behind."""

from benchmarks import scopes


def read(run):
    return scopes.position_ms(run, "lead")
