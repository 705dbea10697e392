"""Models (``models/moe.py``), served: device milliseconds the traced
prefill programs spend in the expert layers (``moe_decode_ms``'s rule: every
operation under a layer's ``moe_mlp`` path and the pathless grouped-matmul
kernels), a thousand prompt tokens admitted (the prompts' own lengths, as
``prefill_ms_per_ktoken`` counts them; the bucket's padding is work too and
is in the time)."""

from benchmarks import serve_scopes
from benchmarks.metrics import moe_decode_ms


def read(run):
    s = moe_decode_ms.seconds(run, "prefill")
    if not s:
        return None
    tokens = sum(e[4] for e in serve_scopes.traced(run, "prefill"))
    return 1e3 * s / (tokens / 1e3) if tokens else None
