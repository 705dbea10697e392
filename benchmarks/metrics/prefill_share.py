"""Scheduler (``serving/engine.py``): share of the window's host time spent
inside the backend's prefill calls, one request at a time, during which no
slot decodes; in percent."""


def read(run):
    if not hasattr(run, "records"):      # a training run: not this metric's
        return None
    return 100.0 * sum(e[2] - e[1] for e in run.steps_in_window("prefill")) \
        / run.seconds
