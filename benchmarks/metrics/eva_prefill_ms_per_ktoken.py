"""Models (``models/transformer.py``, ``EvaAttention``), served: device
milliseconds the traced prefill programs spend under the layers' ``attn``
paths (projections, rotation, chunk summaries, the merged attention with the
flash forward kernels it launches, the ring's layout), a thousand prompt
bytes admitted (the prompts' own lengths; the bucket's padding is work too
and is in the time)."""

from benchmarks import serve_scopes
from benchmarks.metrics import eva_decode_ms


def read(run):
    from horovod_tpu.utils import profiling
    s = eva_decode_ms.seconds(run, "prefill", kernel=profiling.FLASH_FWD)
    if not s:
        return None
    tokens = sum(e[4] for e in serve_scopes.traced(run, "prefill"))
    return 1e3 * s / (tokens / 1e3) if tokens else None
