"""What the fullest chip had to hold, in 10**9 bytes: its buffers at their
peak (``peak_bytes_in_use``) plus the scratch its loaded programs reserve
(``peak_bytes_reserved``), read after the window.  ``hbm_in_use`` and
``hbm_reserved`` report the two apart; run.py's ``memory:`` line sets the
step's own ``memory_analysis()`` beside them."""


def read(run):
    return run.peak_bytes / 1e9 if run.peak_bytes else None
