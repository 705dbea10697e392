"""Scheduler (``serving/engine.py``): time to first token from the due time,
in milliseconds, the mean over every request that fell due inside the
window.  Judged end to end below capacity: a 30 s window there holds 84
requests, the mean stands on all of them (the wait of the one in five that
queued is most of it: the median is 0.40 s, the mean 0.54 s), and
``ttft_ms_p90`` / ``ttft_ms_p95`` beside it per layer stand on eight and on
four (PERF.md section 2)."""

import statistics


def read(run):
    if not hasattr(run, "records"):      # a training run: not this metric's
        return None
    ttft = run.ttft_ms()
    return statistics.fmean(ttft) if ttft else None
