"""Models (``models/transformer.py``, ``LatentAttention``), served: device
milliseconds the traced prefill programs spend under the layers' ``attn``
paths (projections down and up, K and V built from the latent, the output
projection) and in the flash forward kernel they call, a thousand prompt
tokens admitted (the prompts' own lengths; the bucket's padding is work too
and is in the time)."""

from benchmarks import serve_scopes
from benchmarks.metrics import mla_decode_ms


def read(run):
    from horovod_tpu.utils import profiling
    s = mla_decode_ms.seconds(run, "prefill", kernel=profiling.FLASH_FWD)
    if not s:
        return None
    tokens = sum(e[4] for e in serve_scopes.traced(run, "prefill"))
    return 1e3 * s / (tokens / 1e3) if tokens else None
