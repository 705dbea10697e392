"""Input (``horovod_tpu/data.py``): host milliseconds a step inside
``hvd_h2d_put`` (``prefetch_to_device`` issuing the copy), from the host
plane of the traced window."""

from benchmarks import scopes


def read(run):
    return scopes.span_ms(run, "h2d_put")
