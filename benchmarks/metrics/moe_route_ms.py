"""Models (``models/moe.py``): device milliseconds a step under
``hvd_moe_route``: the router's product, softmax, top-k, the sort of the
(token, expert) pairs by expert, the per-expert counts, the two auxiliary
losses."""

from benchmarks.metrics import moe_ms


def read(run):
    p = moe_ms.parts(run)
    return None if p is None else p["route"]
