"""Input (``horovod_tpu/data.py``): host milliseconds a step inside
``hvd_loader_wait`` (the consumer's ``q.get()``: no batch was ready), from
the host plane of the traced window."""

from benchmarks import scopes


def read(run):
    return scopes.span_ms(run, "loader_wait")
