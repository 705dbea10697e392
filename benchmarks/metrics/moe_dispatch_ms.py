"""Models (``models/moe.py``): device milliseconds a step moving rows
between token order and expert order: ``hvd_moe_dispatch`` (tokens gathered
into expert order) plus ``hvd_moe_combine`` (results back to token order and
their gate-weighted sum), and whatever the layer does under neither name."""

from benchmarks.metrics import moe_ms


def read(run):
    p = moe_ms.parts(run)
    return None if p is None else \
        p["dispatch"] + p["combine"] + p["elsewhere"]
