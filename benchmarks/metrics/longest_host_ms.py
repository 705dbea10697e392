"""Serving backend (``TransformerBackend``): the longest stretch any one backend
call of the window (``hvd_srv_prefill``, ``hvd_srv_decode``, ``hvd_srv_verify``)
spent outside its wait for the device (``hvd_srv_wait``), in milliseconds: a
stall of the host shows here, one of the device in ``longest_wait_ms``."""

from benchmarks import serve_spans


def read(run):
    return serve_spans.metric(run, "longest_host_ms")
