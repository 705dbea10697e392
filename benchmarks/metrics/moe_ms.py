"""Models (``models/moe.py``): device milliseconds a step in the expert
layers, every operation under a layer's module path: XLA's own operations
and the kernels launched there, both by module path
(``scopes.Joined.module_s`` and ``kernel_module_s``), and the grouped-matmul
kernels the compiler makes of ``lax.ragged_dot``, which lose their
``op_name`` and with it their path: the program's scope table names them
``hvd_moe_experts``, and they are counted by that name (``pathless_s``; a
kernel that has a path is counted by its path alone).

:func:`parts` is what the other ``moe_*`` readers read: the same time by the
four scopes the layer wraps its work in (``utils/profiling.py``:
``hvd_moe_route`` / ``_dispatch`` / ``_experts`` / ``_combine``), and what is
under the layer's path and under none of them, which should be nothing.  A
program without those names (every commit before PR 26) gives None."""

from benchmarks import scopes

ROLES = {"route": "MOE_ROUTE", "dispatch": "MOE_DISPATCH",
         "experts": "MOE_EXPERTS", "combine": "MOE_COMBINE"}


def parts(run):
    """{"route", "dispatch", "experts", "combine", "elsewhere"}: device
    milliseconds a step, or None."""
    out = scopes.by_scope(run, ROLES)
    if out is None:
        return None
    from horovod_tpu.utils import profiling
    out["experts"] += 1e3 * scopes.of(run).pathless_s(profiling.MOE_EXPERTS) \
        / run.traced_steps
    return out


def read(run):
    p = parts(run)
    if p is None:
        return None
    print("moe_ms: " + " ".join(f"{k}={v:.3f}" for k, v in p.items()))
    return sum(p.values())
