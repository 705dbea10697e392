"""Start-up: seconds the serving backend took to make its pool on the device
(the program's span ``hvd_setup_pool`` around ``init_kv_cache``)."""

from benchmarks import setup_spans


def read(run):
    return setup_spans.metric(run, "setup_pool_s")
