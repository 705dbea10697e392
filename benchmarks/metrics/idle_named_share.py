"""Device: of the traced window's seconds inside ``hvd_srv_step`` in which no
operation ran on the chip, the percentage that lies inside a leaf span of the
program (``hvd_srv_h2d``, ``_dispatch``, ``_wait``, ``_fetch``): what putting
the program's spans on the profiler's clock is worth.  Under 95% a span is
missing (``benchmarks/serve_spans.py``; the rest is the scheduler's own time
and the seams between leaves)."""

from benchmarks import serve_spans


def read(run):
    return serve_spans.metric(run, "idle_named_share")
