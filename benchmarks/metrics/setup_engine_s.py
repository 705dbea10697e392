"""Start-up: seconds the native engine's ``make`` and load took (the program's
span ``hvd_setup_engine``, ``core/engine.lib``), where a run loads it before
its window opens; None where it never does."""

from benchmarks import setup_spans


def read(run):
    return setup_spans.metric(run, "setup_engine_s")
