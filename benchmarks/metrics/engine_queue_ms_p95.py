"""Scheduler (``serving/engine.py``): how long a request stood in the engine's
queue, ``submit()`` to the start of its prefill call (the program's span
``hvd_srv_queued``), in milliseconds, 95th percentile over the requests that
fell due inside the window.  From the engine, not from a bisection of
prefill end times; with ``arrival_late_ms_p95`` (due time to ``submit()``) it
is what ``queue_ms_p95`` is made of."""

from benchmarks import serve_spans


def read(run):
    return serve_spans.metric(run, "engine_queue_ms_p95")
