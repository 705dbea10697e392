"""Scheduler (``serving/engine.py``): time to first token, from the moment
a request was DUE (not from ``submit()``) to the host-clock stamp of its
first token, in milliseconds; the 90th percentile (linear,
``numpy.percentile``) over every request that fell due inside the window:
of the 84 requests of a 30 s window below capacity eight lie above it.  Per
layer: its runs spread by 0.8% while the host is quiet and by 8.5% while it
is not, and no bound fits both (PERF.md section 2); ``ttft_ms_mean`` is
judged."""

from benchmarks import serving


def read(run):
    if not hasattr(run, "records"):      # a training run: not this metric's
        return None
    return serving.percentile(run.ttft_ms(), 90)
