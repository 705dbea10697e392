"""Optimizer and collectives: how many of the planner's buckets
(``hvd_bucket_<k>``) had a collective run on the device.  Against the
``plan:`` line's ``chain_depth`` it says whether XLA's combiner kept them."""

from benchmarks import scopes


def read(run):
    j = scopes.across_chips(run)
    if j is None:
        return None
    return float(sum(1 for k in j.buckets if k != "(none)"))
