"""Models (``models/mamba.py``): device milliseconds a step under
``hvd_ssm_conv``: the causal depthwise convolution over xBC, its bias and the silu."""

from benchmarks.metrics import ssm_ms


def read(run):
    p = ssm_ms.parts(run)
    return None if p is None else p["conv"]
