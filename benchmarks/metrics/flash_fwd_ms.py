"""Kernels (``ops/flash_attention``): device milliseconds a step in the
forward kernel (``hvd_flash_fwd``)."""

from benchmarks import scopes


def read(run):
    return scopes.pass_ms(run, "FLASH_FWD")
