"""Models: device milliseconds a step in fusions that hold two phases (a
weight-gradient matmul with adamw in its epilogue is ``backward+optimizer``);
the ``scopes:`` line gives the time by pair."""

from benchmarks import scopes


def read(run):
    return scopes.phase_ms(run, "mixed")
