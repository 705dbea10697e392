"""Models (``models/transformer.py``, ``LatentAttention`` beside KDA
layers), served: device milliseconds a traced decode step spends under the
latent layer's ``attn`` path (the projections, the per-head norm, the
absorbed products over the cached latents, the head-wise gate, the output
projection).  ``mla_decode_ms.srv``'s reading in a model where the other
layers' mixers are not ``attn``."""

from benchmarks.metrics import kda_decode_ms


def read(run):
    return kda_decode_ms.per_call(run, "attn")
