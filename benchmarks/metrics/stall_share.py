"""Benchmark loop: share of the window beyond ``steps x median period``,
in percent -- what long steps cost together (the tail, kept per layer)."""

from benchmarks import rates


def read(run):
    return 100.0 * rates.stall_share(run.stamps)
