"""Models: device milliseconds a step in XLA operations with no name of the
program's or flax's: compiler-made copies, what the user's step does outside
every scope (``apply_updates``, the loss's mean), and operations the table
does not hold (the ``scopes:`` line gives their share)."""

from benchmarks import scopes


def read(run):
    return scopes.phase_ms(run, "unscoped")
