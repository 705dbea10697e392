"""Models (``models/mamba.py``): device milliseconds a step in the Mamba-2
mixers, every operation under a layer's ``mamba`` module: XLA's own
operations and the kernels launched there, both by module path
(``scopes.Joined.module_s`` and ``kernel_module_s``).

:func:`parts` is what the other ``ssm_*`` readers read: the same time by the
four scopes the mixer wraps its work in (``utils/profiling.py``:
``hvd_ssm_proj`` / ``_conv`` / ``_scan`` / ``_gate``; backward and recomputed
operations keep the name), and what is under the mixer's path and under none
of them, which should be nothing.  A program without those names (every
commit before PR 33) gives None."""

from benchmarks import scopes

ROLES = {"proj": "SSM_PROJ", "conv": "SSM_CONV", "scan": "SSM_SCAN",
         "gate": "SSM_GATE"}


def parts(run):
    """{"proj", "conv", "scan", "gate", "elsewhere"}: device milliseconds a
    step, or None."""
    return scopes.by_scope(run, ROLES)


def read(run):
    p = parts(run)
    if p is None:
        return None
    print("ssm_ms: " + " ".join(f"{k}={v:.3f}" for k, v in p.items()))
    return sum(p.values())
