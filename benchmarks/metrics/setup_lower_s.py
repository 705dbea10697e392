"""Start-up: seconds spent lowering jaxprs to MLIR modules before the window
opens (the union of the ``hvd_compile_lower`` records).  No cache keeps this."""

from benchmarks import setup_spans


def read(run):
    return setup_spans.metric(run, "setup_lower_s")
