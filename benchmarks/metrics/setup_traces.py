"""Start-up: ``hvd_compile_trace`` records before the window opens: how many
functions were traced, each inner ``jax.jit`` once a signature (a kernel
traced anew for each of 24 layers reads here)."""

from benchmarks import setup_spans


def read(run):
    return setup_spans.metric(run, "setup_traces")
