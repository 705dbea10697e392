"""Models (``models/mamba.py``): device milliseconds a step under
``hvd_ssm_scan``: softplus of dt, the decay sums and exponentials, the intra-chunk and inter-chunk products, the hand-over of the state from chunk to chunk, the D skip (``ops/ssd_scan.py``)."""

from benchmarks.metrics import ssm_ms


def read(run):
    p = ssm_ms.parts(run)
    return None if p is None else p["scan"]
