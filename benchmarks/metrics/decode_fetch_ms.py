"""Serving backend (``TransformerBackend``): host milliseconds a decode call
spends bringing the step's ``[slots, vocab]`` float32 logits and, sparse, the
pair counts to the host, the tokens being there already (the program's span
``hvd_srv_fetch`` under ``hvd_srv_decode``), the median over the window's
calls, from the program's span ring (``benchmarks/serve_spans.py``)."""

from benchmarks import serve_spans


def read(run):
    return serve_spans.metric(run, "decode_fetch_ms")
