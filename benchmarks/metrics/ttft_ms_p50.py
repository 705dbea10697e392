"""Scheduler (``serving/engine.py``): time to first token, from the moment
a request was DUE (not from ``submit()``) to the host-clock stamp of its
first token, in milliseconds; the 50th percentile over every request that
fell due inside the window.  It holds the wait in the queue, the prefill,
and every stall a step in progress put on a request that fell due."""

from benchmarks import serving


def read(run):
    if not hasattr(run, "records"):      # a training run: not this metric's
        return None
    return serving.percentile(run.ttft_ms(), 50)
