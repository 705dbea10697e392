"""Benchmark loop: the median of five segment rates of the window
(benchmarks/rates.py), in the cell's unit a second -- how fast the steps are
when nothing stalls them.  The end-to-end rate beside it is over the whole
window; the two differ by what ``stall_share`` reads."""

from benchmarks import rates


def read(run):
    return rates.segment_median_rate(run.stamps, run.built.units_per_call)
