"""Device: ``memory_stats()["peak_bytes_reserved"]`` of the fullest chip
after the window, in 10**9 bytes -- the scratch the loaded programs reserve
for their temporaries, which this runtime keeps out of ``bytes_in_use``."""


def read(run):
    return run.memory["peak_bytes_reserved"] / 1e9 if run.memory else None
