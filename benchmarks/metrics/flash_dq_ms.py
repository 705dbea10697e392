"""Kernels (``ops/flash_attention``): device milliseconds a step in the
backward's dq kernel (``hvd_flash_dq``)."""

from benchmarks import scopes


def read(run):
    return scopes.pass_ms(run, "FLASH_DQ")
