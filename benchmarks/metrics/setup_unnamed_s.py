"""Start-up: ``setup_s`` less the union of every record the other ``setup_*``
metrics read: interpreter start, ``import jax``, the TPU runtime's attach, the
family's host-side work, eager dispatches, warm-up calls, waits for arrivals."""

from benchmarks import setup_spans


def read(run):
    return setup_spans.metric(run, "setup_unnamed_s")
