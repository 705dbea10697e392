"""Scheduler (``serving/engine.py``): the gap between successive tokens of
one request, in milliseconds, the 50th percentile over every token emitted
inside the window after its request's first.  A prefill that held the
decoding slots is inside the gap it stretched."""

from benchmarks import serving


def read(run):
    if not hasattr(run, "records"):      # a training run: not this metric's
        return None
    return serving.percentile(run.token_gaps_ms(), 50)
