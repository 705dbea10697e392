"""Models (``models/moe.py``): device milliseconds a step under
``hvd_moe_experts``: the three grouped matmuls, forward and backward
(kernels the compiler makes of ``lax.ragged_dot``), the activation between
them and the weights' casts."""

from benchmarks.metrics import moe_ms


def read(run):
    p = moe_ms.parts(run)
    return None if p is None else p["experts"]
