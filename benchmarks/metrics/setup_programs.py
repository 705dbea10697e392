"""Start-up: ``hvd_compile_backend`` records before the window opens: programs
compiled or loaded, the eager one-primitive ones included."""

from benchmarks import setup_spans


def read(run):
    return setup_spans.metric(run, "setup_programs")
