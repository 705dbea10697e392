"""Kernels (``ops/flash_attention``): device milliseconds a step in the
backward's one fused kernel (``hvd_flash_bwd``).  A program from before
that kernel has no such name: the metric is then left out."""

from benchmarks import scopes


def read(run):
    from horovod_tpu.utils import profiling
    if not hasattr(profiling, "FLASH_BWD"):
        return None
    return scopes.pass_ms(run, "FLASH_BWD")
