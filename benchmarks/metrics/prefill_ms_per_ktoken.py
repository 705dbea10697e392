"""Serving backend (``TransformerBackend``, ``models/transformer.py``): host
milliseconds inside the window's prefill calls over the thousands of prompt
tokens they admitted (the tokens asked for, not the buckets' padding)."""


def read(run):
    if not hasattr(run, "records"):      # a training run: not this metric's
        return None
    calls = run.steps_in_window("prefill")
    tokens = sum(e[4] for e in calls)
    return 1e6 * sum(e[2] - e[1] for e in calls) / tokens if tokens else None
