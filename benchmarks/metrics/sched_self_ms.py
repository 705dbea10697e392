"""Scheduler (``serving/engine.py``): the engine's own milliseconds of a step,
``hvd_srv_step`` less what the backend calls inside it cover (padding a
prompt to its bucket, token bookkeeping, eviction); the median over the
window's steps, from the program's span ring (``benchmarks/serve_spans.py``)."""

from benchmarks import serve_spans


def read(run):
    return serve_spans.metric(run, "sched_self_ms")
