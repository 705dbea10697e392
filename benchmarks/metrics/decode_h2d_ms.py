"""Serving backend (``TransformerBackend``): host milliseconds a decode call
spends copying the slots' last tokens and lengths to the device (the
program's span ``hvd_srv_h2d`` under ``hvd_srv_decode``), the median over the
window's calls, from the program's span ring (``benchmarks/serve_spans.py``)."""

from benchmarks import serve_spans


def read(run):
    return serve_spans.metric(run, "decode_h2d_ms")
