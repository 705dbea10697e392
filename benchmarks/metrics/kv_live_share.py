"""Serving backend (``TransformerBackend``'s dense per-slot cache): the
cached tokens that slots in use hold, over the ``num_slots * max_seq_len``
positions the pool reserves, in percent; the mean over the window's decode
steps, each weighted by its host time.  Memory in use against memory
reserved: ``peak_hbm`` counts the reservation (and the decode program's
copy of it), this counts what the traffic fills."""


def read(run):
    if not hasattr(run, "records"):      # a training run: not this metric's
        return None
    live = run.kv_live_tokens()
    if live is None:
        return None
    return 100.0 * live[0] / (run.built.num_slots
                              * int(run.traffic["max_seq_len"]))
