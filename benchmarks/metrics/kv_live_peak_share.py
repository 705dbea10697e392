"""Serving backend: as ``kv_live_share``, at the decode step of the window
that held most cached tokens live."""


def read(run):
    if not hasattr(run, "records"):      # a training run: not this metric's
        return None
    live = run.kv_live_tokens()
    if live is None:
        return None
    return 100.0 * live[1] / (run.built.num_slots
                              * int(run.traffic["max_seq_len"]))
