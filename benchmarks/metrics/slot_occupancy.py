"""Scheduler (``serving/engine.py``): slots holding a request in a decode
step, over the slots there are, in percent; the mean over the window's
decode steps, each weighted by its host time."""


def read(run):
    if not hasattr(run, "records"):      # a training run: not this metric's
        return None
    steps = run.steps_in_window("decode")
    took = sum(e[2] - e[1] for e in steps)
    if not took:
        return None
    return 100.0 * sum(e[3] * (e[2] - e[1]) for e in steps) \
        / (run.built.num_slots * took)
