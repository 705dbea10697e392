"""Serving backend (``TransformerBackend``): the window's longest single wait for
the device (the program's span ``hvd_srv_wait``), in milliseconds.  A stalled
call whose wait is long was the device's or its runtime's; one whose wait is
not shows in ``longest_host_ms``."""

from benchmarks import serve_spans


def read(run):
    return serve_spans.metric(run, "longest_wait_ms")
