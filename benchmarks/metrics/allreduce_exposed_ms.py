"""Optimizer and collectives: the part of ``allreduce_ms`` during which no
other operation ran on that chip -- what the all-reduce costs the step."""


def read(run):
    t = run.trace
    if t is None or run.chips < 2:
        return None
    return 1e3 * t.collective_exposed_s / run.traced_steps
