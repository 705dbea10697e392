"""Benchmark loop (``benchmarks/serving.py``): how late the generator handed
a request to ``engine.submit``, due time to submission, in milliseconds, 95th
percentile over the window's requests.  The loop is one thread, so this is
the rest of the step that was running when the request fell due; it is
inside every latency, which is timed from the due time."""

from benchmarks import serving


def read(run):
    if not hasattr(run, "records"):      # a training run: not this metric's
        return None
    return serving.percentile(
        [1e3 * (r.submitted - r.due) for r in run.counted], 95)
