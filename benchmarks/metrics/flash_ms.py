"""Kernels (``ops/flash_attention``): device milliseconds a step inside the
flash kernels, forward and backward: the kernels of the traced window that
the program's scope table names as flash passes (``scopes.Joined.pass_s``
over ``profiling.FLASH_PASSES``), whatever other kernels the step runs."""

from benchmarks import scopes


def read(run):
    return scopes.flash_ms(run)
