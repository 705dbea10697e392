"""Kernels (``ops/flash_attention``): device milliseconds a step inside the
Pallas kernels (forward, dq, dk/dv), from the trace."""


def read(run):
    t = run.trace
    if t is None or not run.built.flash_calls:
        return None
    return 1e3 * t.kind_s.get("flash", 0.0) / run.traced_steps
