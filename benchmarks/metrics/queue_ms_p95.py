"""Scheduler (``serving/engine.py``): from a request's due time to the
start of its prefill call, in milliseconds, 95th percentile over the
requests that fell due inside the window and were admitted."""

from benchmarks import serving


def read(run):
    if not hasattr(run, "records"):      # a training run: not this metric's
        return None
    return serving.percentile(
        [1e3 * (r.admitted - r.due) for r in run.counted
         if r.admitted is not None], 95)
