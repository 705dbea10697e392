"""Utility subpackage: env-var knobs and profiling helpers.

Submodules resolve lazily (PEP 562) to keep the package root light —
``hvd.profiling.trace(...)`` (the one spelling the docs use; the module is
``horovod_tpu/utils/profiling.py``) works without anything importing the
profiling module eagerly.
"""

import importlib

_SUBMODULES = ("backoff", "chip", "env", "manifest", "profiling")


def __getattr__(name: str):
    if name in _SUBMODULES:
        value = importlib.import_module(f"horovod_tpu.utils.{name}")
        globals()[name] = value
        return value
    raise AttributeError(
        f"module 'horovod_tpu.utils' has no attribute {name!r}")


def __dir__():
    return sorted(set(globals()) | set(_SUBMODULES))
