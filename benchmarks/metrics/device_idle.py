"""Device: share of the traced window in which no operation ran on a chip
(1 - union of busy intervals / window, mean over chips), in percent."""


def read(run):
    t = run.trace
    if t is None:
        return None
    return 100.0 * (1.0 - t.busy_s / t.window_s)
