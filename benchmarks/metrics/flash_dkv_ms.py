"""Kernels (``ops/flash_attention``): device milliseconds a step in the
backward's dk/dv kernel (``hvd_flash_dkv``)."""

from benchmarks import scopes


def read(run):
    return scopes.pass_ms(run, "FLASH_DKV")
