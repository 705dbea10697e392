"""Models (``models/moe.py``): device milliseconds a step in the expert
layers, every operation under a layer's module path: XLA's own operations by
module (``scopes.Joined.module_s``) and the grouped-matmul kernels the
program's scope table names ``hvd_moe_experts`` (``pass_s``).

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
    j = scopes.of(run)
    if j is None:
        return None
    from horovod_tpu.utils import profiling
    names = {role: getattr(profiling, const, None)
             for role, const in ROLES.items()}
    if None in names.values():
        return None
    ms = lambda seconds: 1e3 * seconds / run.traced_steps  # noqa: E731
    under = lambda name: sum(  # noqa: E731
        v for m, v in j.module_s.items() if name in m.split("/"))
    out = {role: ms(under(name)) for role, name in names.items()}
    # a grouped matmul that the compiler made a kernel of is no XLA op
    out["experts"] += ms(j.pass_s.get(names["experts"], 0.0))
    # the layers' own paths: what stands before a scope's name
    layers = {m.split("/" + name)[0] for name in names.values()
              for m in j.module_s if name in m.split("/")}
    inside = sum(v for m, v in j.module_s.items()
                 if any(m == p or m.startswith(p + "/") for p in layers))
    out["elsewhere"] = ms(inside) - sum(
        ms(under(name)) for name in names.values())
    return out


def read(run):
    p = parts(run)
    if p is None:
        return None
    print("moe_ms: " + " ".join(f"{k}={v:.3f}" for k, v in p.items()))
    return sum(p.values())
