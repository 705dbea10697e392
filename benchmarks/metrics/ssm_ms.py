"""Models (``models/mamba.py``): device milliseconds a step in the Mamba-2
mixers, every operation under a layer's ``mamba`` module: XLA's own
operations by module (``scopes.Joined.module_s``; the mixer has no kernel).

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
    # the mixers' own paths: what stands before a scope's name
    mixers = {m.split("/" + name)[0] for name in names.values()
              for m in j.module_s if name in m.split("/")}
    inside = sum(v for m, v in j.module_s.items()
                 if any(m == p or m.startswith(p + "/") for p in mixers))
    out["elsewhere"] = ms(inside) - sum(out.values())
    return out


def read(run):
    p = parts(run)
    if p is None:
        return None
    print("ssm_ms: " + " ".join(f"{k}={v:.3f}" for k, v in p.items()))
    return sum(p.values())
