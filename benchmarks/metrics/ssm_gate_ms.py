"""Models (``models/mamba.py``): device milliseconds a step under
``hvd_ssm_gate``: y * silu(z) and the RMSNorm over the inner channels."""

from benchmarks.metrics import ssm_ms


def read(run):
    p = ssm_ms.parts(run)
    return None if p is None else p["gate"]
