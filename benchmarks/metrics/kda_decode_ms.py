"""Models (``models/kda.py``, ``KDAMixer``), served: device milliseconds a
traced decode step spends under the KDA layers' mixer paths (``kda``): the
projections, the convolution over the carried tail, the decay and step size,
one step of the delta rule on every slot's state, the norm and gate out.
From the trace joined to the decode program's own names
(``benchmarks/serve_scopes.py``)."""

from benchmarks import serve_scopes
from benchmarks.metrics.mla_decode_ms import seconds

MODULE = "kda"


def per_call(run, component: str):
    s = seconds(run, "decode", component)
    if not s:
        return None
    return 1e3 * s / serve_scopes.of(run).calls["decode"]


def per_ktoken(run, component: str, kernel: str | None = None):
    """Device ms of the traced prefill programs under ``component`` (and in
    the pathless kernels named ``kernel``) a thousand prompt tokens admitted
    (the prompts' own lengths; the bucket's padding is in the time)."""
    s = seconds(run, "prefill", component, kernel)
    if not s:
        return None
    tokens = sum(e[4] for e in serve_scopes.traced(run, "prefill"))
    return 1e3 * s / (tokens / 1e3) if tokens else None


def read(run):
    return per_call(run, MODULE)
