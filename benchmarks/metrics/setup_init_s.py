"""Start-up: seconds ``hvd.init()`` took, the call that did the work (the
program's span ``hvd_setup_init``: topology, the global mesh, and whatever
compiled inside it), before the window opens."""

from benchmarks import setup_spans


def read(run):
    return setup_spans.metric(run, "setup_init_s")
