"""Start-up: seconds ``import horovod_tpu`` took, the package's ``__init__`` from its
first line to its last (the program's span ``hvd_setup_import``; jax's own
import is the caller's where jax was loaded before), before the window opens."""

from benchmarks import setup_spans


def read(run):
    return setup_spans.metric(run, "setup_import_s")
