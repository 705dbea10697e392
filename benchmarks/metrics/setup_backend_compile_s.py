"""Start-up: seconds in XLA / Mosaic compiling, or in the persistent cache's load
in its place, before the window opens (the union of the
``hvd_compile_backend`` records)."""

from benchmarks import setup_spans


def read(run):
    return setup_spans.metric(run, "setup_backend_compile_s")
