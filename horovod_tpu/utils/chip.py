"""What a measurement needs to know about the accelerator it runs on.

Two small things the chip-facing entry points share (``chip_smoke.py``,
``benchmarks/``, ``python -m horovod_tpu.serving``):

* :func:`require_tpu` — a device phase that finds no chip raises instead of
  timing the CPU backend under a device metric's name;
* :func:`enable_compile_cache` — JAX's persistent compilation cache at a
  path that can be placed from outside and never moves on its own.
"""

from __future__ import annotations

import os

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def require_tpu(what: str) -> None:
    """Raise unless JAX's default backend is a TPU.  ``what`` names the
    phase, so the error says which measurement was refused."""
    import jax

    from horovod_tpu.utils import profiling

    # a span (``hvd_setup_backend``): the first ask of the backend attaches
    # the TPU runtime, seconds of a start; after the caller's own
    # ``jax.devices()`` it is a mark of where that ended
    with profiling.span(profiling.SETUP_BACKEND):
        backend = jax.default_backend()
    if backend != "tpu":
        raise RuntimeError(
            f"{what} measures the accelerator and found none: JAX's default "
            f"backend is {backend!r} (JAX_PLATFORMS="
            f"{os.environ.get('JAX_PLATFORMS', '')!r}).  Run it on a machine "
            f"with a TPU; a CPU timing is not reported under a device "
            f"metric's name.")


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    With ``JAX_COMPILATION_CACHE_DIR`` set, JAX already reads it and this
    sets nothing.  Otherwise the cache lives at ``.jax_cache/`` in the
    checkout — a fixed path, because the path is part of how a cached
    program is found again.  Call before the first compile: it also
    registers the compile ledger's listener (``profiling.listen``), so that
    every compile after it leaves its ``hvd_compile_*`` records, with the
    cache's hit or miss.

    The names a program gives its work (``op_name``: module paths, the
    ``hvd_*`` scopes of utils/profiling.py) are made part of the key.  JAX
    leaves them out by default, and a step whose arithmetic an edit did not
    change would then come back from the cache under the names it had
    before the edit: ``profiling.scope_table`` would read another version's
    scopes out of this one's executable."""
    import jax

    from horovod_tpu.utils import profiling

    profiling.listen()
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if path:
        return path
    path = os.path.join(_REPO_ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
