"""Optimizer and collectives: milliseconds a step during which a
collective was in flight on a chip (device trace, union of the collective
operations' intervals, mean over chips)."""


def read(run):
    t = run.trace
    if t is None or run.chips < 2:
        return None
    return 1e3 * t.collective_s / run.traced_steps
