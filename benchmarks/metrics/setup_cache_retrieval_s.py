"""Start-up: seconds the persistent compile cache took to read and load the
programs it held (the sum of ``retrieval_s`` over the ``hvd_compile_backend``
records; inside ``setup_backend_compile_s``)."""

from benchmarks import setup_spans


def read(run):
    return setup_spans.metric(run, "setup_cache_retrieval_s")
