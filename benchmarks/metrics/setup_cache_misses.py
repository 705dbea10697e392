"""Start-up: backend compiles the persistent cache did not hold
(``hvd_compile_backend`` records with ``cache="miss"``) before the window
opens: 0 on a warm start."""

from benchmarks import setup_spans


def read(run):
    return setup_spans.metric(run, "setup_cache_misses")
