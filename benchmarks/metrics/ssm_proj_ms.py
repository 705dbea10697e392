"""Models (``models/mamba.py``): device milliseconds a step under
``hvd_ssm_proj``: the mixer's input projection (z, xBC and dt in one) and its output projection."""

from benchmarks.metrics import ssm_ms


def read(run):
    p = ssm_ms.parts(run)
    return None if p is None else p["proj"]
