"""Serving backend (``TransformerBackend``): host milliseconds a decode call
waits for the device: until the step's tokens, 32 bytes that arrive when the
program has run, are on the host (the program's span ``hvd_srv_wait`` under
``hvd_srv_decode``: the step, its launch and the tokens' copy), the median
over the window's calls, from the program's span ring
(``benchmarks/serve_spans.py``)."""

from benchmarks import serve_spans


def read(run):
    return serve_spans.metric(run, "decode_wait_ms")
