"""Serving backend (``TransformerBackend``'s pool of rings and summaries):
the rows that slots in use hold -- a slot's current window's ring rows and
the summaries of the chunks it has closed (``flops_eva.held_rows``) -- over
the ``num_slots * (window + max_seq_len / chunk)`` rows the pool reserves, in
percent; the mean over the window's decode steps, each weighted by its host
time.  ``kv_live_share`` for a cache that is no row a position."""

from benchmarks import flops_eva


def read(run):
    if not hasattr(run, "records"):      # a training run: not this metric's
        return None
    steps = [e for e in run.steps_in_window("decode") if len(e) >= 6]
    took = sum(e[2] - e[1] for e in steps)
    if not took or "window_size" not in run.config:
        return None
    # a logged length counts the pending token: length - 1 positions cached
    held = sum((e[2] - e[1]) * sum(flops_eva.held_rows(run.config, n - 1)
                                   for n in e[5]["lengths"]) for e in steps)
    reserved = run.built.num_slots * flops_eva.reserved_rows(
        run.config, int(run.traffic["max_seq_len"]))
    return 100.0 * held / took / reserved
