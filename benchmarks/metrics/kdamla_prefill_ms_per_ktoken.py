"""Models (``models/transformer.py``, ``LatentAttention`` beside KDA
layers), served: device milliseconds the traced prefill programs spend under
the latent layer's ``attn`` path and in the flash forward kernel it calls, a
thousand prompt tokens admitted."""

from benchmarks.metrics import kda_decode_ms


def read(run):
    from horovod_tpu.utils import profiling
    return kda_decode_ms.per_ktoken(run, "attn", profiling.FLASH_FWD)
