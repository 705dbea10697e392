"""Start-up: seconds spent tracing functions to jaxprs before the window opens:
the UNION of the ``hvd_compile_trace`` records' intervals (a jit traced inside
a jit lies inside its caller's record).  No cache keeps this."""

from benchmarks import setup_spans


def read(run):
    return setup_spans.metric(run, "setup_trace_s")
