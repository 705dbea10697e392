"""Scheduler (``serving/engine.py``): time to first token, from the moment
a request was DUE (not from ``submit()``) to the host-clock stamp of its
first token, in milliseconds; the 95th percentile over every request that
fell due inside the window.  Per layer only: over the 84 requests of a
30 s window below capacity it stands on four of them (``ttft_ms_mean`` is
judged there); above capacity no tail is judged."""

from benchmarks import serving


def read(run):
    if not hasattr(run, "records"):      # a training run: not this metric's
        return None
    return serving.percentile(run.ttft_ms(), 95)
