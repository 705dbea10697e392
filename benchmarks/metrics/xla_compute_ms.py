"""Models (``models/transformer.py``, ``models/resnet.py``): device
milliseconds a step in every operation that is neither a kernel (a Mosaic
custom call, ours or the compiler's) nor a collective -- what XLA made of
the model and the optimizer."""


def read(run):
    t = run.trace
    if t is None:
        return None
    return 1e3 * t.kind_s.get("xla", 0.0) / run.traced_steps
