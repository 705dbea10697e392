"""A traced serving window joined to the names the program gave its work,
program by program: what ``scopes.join`` is to a training window, for the
readers of a serving cell that ask about a scope (``hvd_moe_*``) or a kernel
(``hvd_flash_fwd``) and not about the decode program's attention alone
(``serve_trace.Summary.decode_attn_s``).

Several programs run in a serving trace and instruction names repeat across
them, so a device operation is filed under the execution that holds it (the
chip's ``XLA Modules`` line), that execution under its program, and only
then under its name in THAT program's scope table: the decode program's, or
the prefill program's of its bucket.  The host reads every step's result
before it issues the next, so the executions of the trace are, one for one
and in order, the calls the loop logged while tracing
(``ServeRun.traced_steps_log``), which is how a prefill execution finds its
bucket.

A family that can be asked for its prefill programs' tables hands
``prefill_scopes(bucket)`` beside ``decode_scopes()`` (families/
cohere2_moe_serve.py); for one that cannot, and for a program that names
nothing, :func:`of` is None and the readers leave their metrics out.
"""

from __future__ import annotations

import bisect
import dataclasses
import json

from benchmarks import serve_trace, trace


@dataclasses.dataclass
class Joined:
    calls: dict             # "decode" / "prefill" -> executions joined
    # program -> folded module -> seconds of own time, XLA's operations and
    # the kernels that have a module path
    module_s: dict
    # program -> kernel name -> seconds of own time (every kernel, by name)
    kernel_s: dict
    # program -> kernel name -> seconds of the kernels WITHOUT a module path
    pathless_s: dict
    joined_share: float     # of the two programs' own time, found in a table

    def under(self, program: str, component: str, kernel: str | None = None
              ) -> float:
        """Seconds of ``program`` under module paths that hold
        ``component``, and in the pathless kernels named ``kernel``."""
        return sum(v for m, v in self.module_s[program].items()
                   if component in m.split("/")) \
            + (self.pathless_s[program].get(kernel, 0.0) if kernel else 0.0)


def join(planes: list[dict], program_names: dict, tables: dict, logged: list
         ) -> Joined | None:
    """``tables``: {"decode": table, ("prefill", bucket): table};
    ``logged``: the loop's log of the traced calls, in order."""
    plane = next((p for p in sorted(
        (p for p in planes if p["name"].startswith("/device:TPU:")),
        key=lambda p: int(p["name"].rsplit(":", 1)[1]))
        if trace._line(p, "XLA Modules")), None)
    if plane is None:
        return None
    runs = trace._line(plane, "XLA Modules")
    kinds = [serve_trace._program_of(e[0], program_names) for e in runs]
    # each execution's table: the decode program's, or its bucket's
    by_kind = {k: [e for e in logged if e[0] == k]
               for k in ("decode", "prefill")}
    for k, calls in by_kind.items():
        if len(calls) != kinds.count(k):
            print(f"serve_scopes: the loop logged {len(calls)} {k} calls "
                  f"while tracing and the trace holds {kinds.count(k)}: "
                  f"not joined")
            return None
    seen = {"decode": 0, "prefill": 0}
    run_table = []
    for kind in kinds:
        if kind == "decode":
            run_table.append(tables.get("decode"))
        elif kind == "prefill":
            bucket = by_kind["prefill"][seen["prefill"]][3]
            run_table.append(tables.get(("prefill", bucket)))
        else:
            run_table.append(None)
        if kind in seen:
            seen[kind] += 1
    starts = [e[1] for e in runs]
    ops = trace._line(plane, "XLA Ops")
    programs = ("decode", "prefill")
    module_s = {p: {} for p in programs}
    kernel_s = {p: {} for p in programs}
    pathless_s = {p: {} for p in programs}
    found = everything = 0.0
    for (name, start, _, stats), own in zip(ops, trace.own_times(ops)):
        k = bisect.bisect_right(starts, start) - 1
        if k < 0 or start >= runs[k][1] + runs[k][2] \
                or kinds[k] not in programs:
            continue
        program, table = kinds[k], run_table[k]
        everything += own
        scope = table.get(name) if table else None
        if scope is None:
            continue
        found += own
        t = own / 1e9
        if trace.kind_of(stats) == trace.KERNEL:
            which = scope.kernel or "(unnamed)"
            kernel_s[program][which] = kernel_s[program].get(which, 0.0) + t
            if not scope.module:
                pathless_s[program][which] = \
                    pathless_s[program].get(which, 0.0) + t
                continue
        where = scope.module or "(none)"
        module_s[program][where] = module_s[program].get(where, 0.0) + t
    return Joined(calls={k: kinds.count(k) for k in programs},
                  module_s=module_s, kernel_s=kernel_s,
                  pathless_s=pathless_s,
                  joined_share=found / everything if everything else 0.0)


def of(run) -> Joined | None:
    """The run's traced seconds joined to its programs' names: made once,
    printed once, then a lookup.  None on an untraced run, a training run, a
    rehearsal, or a family that hands no prefill tables."""
    if "_serve_scopes" not in vars(run):
        joined = None
        built = getattr(run, "built", None)
        if (getattr(run, "trace", None) is not None and run.peaks
                and getattr(run, "trace_dir", None)
                and hasattr(built, "prefill_scopes")):
            logged = run.traced_steps_log
            tables = {"decode": built.decode_scopes()}
            for bucket in sorted({e[3] for e in logged if e[0] == "prefill"}):
                tables["prefill", bucket] = built.prefill_scopes(bucket)
            if all(t is not None for t in tables.values()):
                joined = join(trace.load(run.trace_dir),
                              built.program_names, tables, logged)
        if joined is not None:
            top = lambda d: {k: round(1e3 * v, 3) for k, v in sorted(  # noqa: E731
                d.items(), key=lambda kv: -kv[1])[:12]}
            print("serve_scopes_by_program: " + json.dumps({
                "calls": joined.calls,
                "joined_share": round(joined.joined_share, 5),
                **{f"{p}_module_ms": top(joined.module_s[p])
                   for p in joined.module_s},
                **{f"{p}_kernel_ms": top(joined.kernel_s[p])
                   for p in joined.kernel_s}}))
        run._serve_scopes = joined
    return run._serve_scopes


def traced(run, kind: str) -> list:
    """The calls of ``kind`` the loop logged while tracing."""
    return [e for e in run.traced_steps_log if e[0] == kind]
