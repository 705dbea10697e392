"""Serving backend (``TransformerBackend``, ``models/transformer.py``): host
milliseconds of one decode call, from handing it the slots' last tokens to
having every slot's next token and logits on the host; the median over the
window's calls."""

import statistics


def read(run):
    if not hasattr(run, "records"):      # a training run: not this metric's
        return None
    took = [1e3 * (e[2] - e[1]) for e in run.steps_in_window("decode")]
    return statistics.median(took) if took else None
