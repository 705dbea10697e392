"""Serving backend (``TransformerBackend``): host milliseconds a decode call
spends in the jitted call itself, which returns when the step is enqueued
(the program's span ``hvd_srv_dispatch`` under ``hvd_srv_decode``), the median
over the window's calls, from the program's span ring
(``benchmarks/serve_spans.py``)."""

from benchmarks import serve_spans


def read(run):
    return serve_spans.metric(run, "decode_dispatch_ms")
