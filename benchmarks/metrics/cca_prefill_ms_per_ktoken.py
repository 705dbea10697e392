"""Models (``models/cca.py``, ``CCAMixer``), served: device milliseconds the
traced prefill programs spend under the layers' mixer paths (``cca``:
projections, convolutions with the tail handed over the prompt's row blocks,
norm and rotary, the attention itself -- the flash forward's kernel is
launched under the path -- and the output projection), a thousand prompt
tokens admitted."""

from benchmarks.metrics import cca_decode_ms, kda_decode_ms


def read(run):
    from horovod_tpu.utils import profiling
    return kda_decode_ms.per_ktoken(run, cca_decode_ms.MODULE,
                                    profiling.FLASH_FWD)
