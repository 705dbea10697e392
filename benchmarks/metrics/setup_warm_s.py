"""Start-up of a served cell: seconds inside the backend's calls
(``hvd_srv_prefill`` / ``_decode`` / ``_verify``) before the window opens, less
the compile records inside them: the warm-up requests and the lead-in served."""

from benchmarks import setup_spans


def read(run):
    return setup_spans.metric(run, "setup_warm_s")
