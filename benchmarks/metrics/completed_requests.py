"""Scheduler (``serving/engine.py``): requests that fell due inside the
window and had emitted their last token when it closed."""


def read(run):
    if not hasattr(run, "records"):      # a training run: not this metric's
        return None
    return float(sum(r.done and r.stamps[-1] < run.close_t
                     for r in run.counted))
