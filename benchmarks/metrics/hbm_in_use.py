"""Device: ``memory_stats()["peak_bytes_in_use"]`` of the fullest chip after
the window, in 10**9 bytes -- live buffers alone (state, batches, outputs)."""


def read(run):
    return run.memory["peak_bytes_in_use"] / 1e9 if run.memory else None
