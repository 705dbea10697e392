"""Join a traced window to the names the program gave its own work.

``benchmarks/trace.py`` tells three kinds of device operation apart by
opcode.  The program says more: ``horovod_tpu.utils.profiling.scope_table``
reads, from the compiled step's own text, each instruction's phase (forward,
backward, recompute, optimizer), module, all-reduce bucket and kernel name.
A trace event is named by its instruction, so the two join by name: the
program's vocabulary, the benchmark's clock.  Every name is the program's;
none is written here (``profiling``'s constants are read, and a program
without them, as every commit before PR 24 is, joins to nothing: the readers
then return None and the line leaves their metrics out).

One rule files a device operation: what the scope table says of it.  Being a
Mosaic custom call decides its kind (``trace.KERNEL``) and nothing else: a
kernel is kept under its kernel name (``pass_s``: a flash pass, the
compiler's grouped matmul, ``(unnamed)``) and, where the table gives it a
module path, under that path too (``kernel_module_s``), so a reader of a
scope (``hvd_ssm_scan``, ``hvd_moe_dispatch``) counts XLA's operations and
the kernels under it, and a kernel is nobody's by being a kernel.

The window is ``trace.reduce``'s: on each chip from the start of the first
whole execution of the step program to the end of the last, own times as
``trace.own_times`` gives them, means over the chips.  So by construction
the phases, and the modules, of the ``xla`` kind sum to
``Summary.kind_s["xla"]``, and the kernels by name to
``kind_s[trace.KERNEL]``, as the kernels by path do with the pathless ones.

The compiled step and the trace's directory are fields of ``Run``
(``compiled``, ``trace_dir``), which ``run.main`` assigns.  :func:`harness`
still looks for them in the frame of a ``main`` that called the reader, as
it had to before ``Run`` had the fields: ``run.py`` never takes that path
now, and it goes with the assertion of tests/test_bench_scopes.py (tier-1,
no benchmark file) that holds it.
"""

from __future__ import annotations

import dataclasses
import json
import re
import sys

from benchmarks import trace


def harness(run) -> tuple:
    """(the compiled step, the trace's directory), either of them None:
    ``Run``'s two fields, and only where one is missing a caller's frame."""
    compiled = getattr(run, "compiled", None)
    trace_dir = getattr(run, "trace_dir", None)
    frame = sys._getframe(1)
    while frame is not None and (compiled is None or trace_dir is None):
        if frame.f_code.co_name == "main" and "trace_dir" in frame.f_locals:
            compiled = compiled or frame.f_locals.get("step")
            trace_dir = trace_dir or frame.f_locals.get("trace_dir")
        frame = frame.f_back
    return compiled, trace_dir


def table_of(compiled) -> dict | None:
    """The program's scope table, or None where the program has none."""
    try:
        from horovod_tpu.utils.profiling import scope_table
    except ImportError:
        return None
    return scope_table(compiled) if compiled is not None else None


def host_span_names() -> dict:
    """The program's host spans by the role a reader asks for."""
    from horovod_tpu.utils import profiling
    return {role: getattr(profiling, const, None) for role, const in (
        ("loader_wait", "LOADER_WAIT"), ("loader_produce", "LOADER_PRODUCE"),
        ("h2d_put", "H2D_PUT"))}


@dataclasses.dataclass
class Joined:
    chips: int
    calls: int                # executions of the step program in the window
    phase_s: dict             # label -> seconds of xla own time, mean over chips
    module_s: dict            # folded module -> seconds of xla own time
    pass_s: dict              # kernel name -> seconds of kernel own time
    buckets: dict             # bucket -> {calls, bytes, seconds, start_s}
    lead_s: float             # a call: first bucket's start to backward's end
    tail_s: float             # a call: collective time after backward's end
    joined_share: float       # of all own time, found in the table
    span_s: dict              # host span role -> seconds inside the window
    # (kernel name, folded module; "" where it has none) -> seconds of kernel
    # own time: what pass_s sums by name, by where the program launched it
    kernel_s: dict = dataclasses.field(default_factory=dict)

    @property
    def kernel_module_s(self) -> dict:
        """Folded module -> seconds of kernel own time.  A kernel with no
        module path (the compiler's ``ragged-dot``, which loses its
        ``op_name``) is in none: see :meth:`pathless_s`."""
        out: dict[str, float] = {}
        for (_, module), v in self.kernel_s.items():
            if module:
                out[module] = out.get(module, 0.0) + v
        return out

    def pathless_s(self, kernel: str) -> float:
        """Seconds in the kernels named ``kernel`` that have no module path:
        what a scope's reader may add by name without counting twice."""
        return self.pass_s.get(kernel, 0.0) - sum(
            v for (k, module), v in self.kernel_s.items()
            if k == kernel and module)

    def phase(self, label: str) -> float:
        return self.phase_s.get(label, 0.0)

    @property
    def mixed_s(self) -> float:
        return sum(v for k, v in self.phase_s.items() if "+" in k)


def _window(plane: dict):
    """``trace.reduce``'s window of one chip: (executions, ops, async ops),
    or None where the plane holds fewer than three executions."""
    modules = trace._line(plane, "XLA Modules")
    total: dict[str, float] = {}
    for name, _, dur, _ in modules:
        key = re.sub(r"\(\d+\)$", "", name)
        total[key] = total.get(key, 0.0) + dur
    if not total:
        return None
    step = max(total, key=total.get)
    runs = [e for e in modules if re.sub(r"\(\d+\)$", "", e[0]) == step]
    if len(runs) < 3:
        return None
    runs = runs[1:-1]
    lo, hi = runs[0][1], runs[-1][1] + runs[-1][2]
    inside = lambda e: e[1] >= lo and e[1] + e[2] <= hi  # noqa: E731
    return (runs, [e for e in trace._line(plane, "XLA Ops") if inside(e)],
            [e for e in trace._line(plane, "Async XLA Ops") if inside(e)])


def _clip(intervals, lo, hi):
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def _one_chip(runs: list, ops: list, async_ops: list, table: dict) -> dict:
    """One chip's window by the table: nanoseconds of own time by phase,
    module, kernel and bucket, and where the collectives lie."""
    phase: dict[str, float] = {}
    module: dict[str, float] = {}
    passes: dict[str, float] = {}
    kernels: dict[tuple, float] = {}
    buckets: dict[str, dict] = {}
    found = everything = 0.0
    backward_ends, bucketed_starts = [], []
    for (name, start, dur, stats), own in zip(ops, trace.own_times(ops)):
        scope = table.get(name)
        everything += own
        if scope is not None:
            found += own
        kind = trace.kind_of(stats)
        if kind == "xla":
            label = scope.label if scope else "unscoped"
            phase[label] = phase.get(label, 0.0) + own
            where = (scope.module if scope else "") or "(none)"
            module[where] = module.get(where, 0.0) + own
            if scope and scope.phases == ("backward",) and own > 0:
                backward_ends.append(start + dur)
        elif kind == trace.KERNEL:
            which = (scope.kernel if scope else None) or "(unnamed)"
            passes[which] = passes.get(which, 0.0) + own
            where = (which, scope.module if scope else "")
            kernels[where] = kernels.get(where, 0.0) + own
        else:
            which = (scope.bucket if scope else None) or "(none)"
            b = buckets.setdefault(which, {"calls": 0, "bytes": 0,
                                           "seconds": 0.0, "starts": []})
            b["calls"] += 1
            b["bytes"] += scope.bytes if scope else 0
            b["seconds"] += own
            b["starts"].append(start)
            if which != "(none)":
                bucketed_starts.append(start)
    coll = trace._collective_intervals(ops, async_ops)
    lead = tail = 0.0
    for _, a, d, _ in runs:
        last = max((t for t in backward_ends if a <= t <= a + d),
                   default=None)
        if last is None:
            continue
        first = min((t for t in bucketed_starts if a <= t <= a + d),
                    default=last)
        lead += max(0.0, last - first)
        tail += trace.length(_clip(coll, last, a + d))
    for b in buckets.values():
        # where in its step the bucket begins: the earliest of its
        # collectives in each execution, mean over the executions
        offsets = [min(inside) - a for _, a, d, _ in runs
                   if (inside := [t for t in b["starts"] if a <= t <= a + d])]
        b["start"] = sum(offsets) / len(offsets) if offsets else 0.0
        del b["starts"]
    return {"calls": len(runs), "phase": phase, "module": module,
            "passes": passes, "kernels": kernels, "buckets": buckets,
            "lead": lead / len(runs),
            "tail": tail / len(runs), "found": found,
            "everything": everything}


def join(planes: list[dict], table: dict, spans: dict | None = None
         ) -> Joined | None:
    """None when the trace holds no TPU plane with a step program in it."""
    devices = sorted((p for p in planes
                      if re.match(r"^/device:TPU:\d+$", p["name"])),
                     key=lambda p: int(p["name"].rsplit(":", 1)[1]))
    windows = [w for w in map(_window, devices) if w is not None]
    if not windows:
        return None
    chips = [_one_chip(*w, table) for w in windows]
    n, calls = len(chips), chips[0]["calls"]

    def mean(key: str) -> dict:
        names = sorted({k for c in chips for k in c[key]})
        return {k: sum(c[key].get(k, 0.0) for c in chips) / n / 1e9
                for k in names}

    buckets = {}
    for k in sorted({k for c in chips for k in c["buckets"]}):
        have = [c["buckets"][k] for c in chips if k in c["buckets"]]
        buckets[k] = {
            "calls": have[0]["calls"] // calls,
            "bytes": have[0]["bytes"] // calls,
            "seconds": sum(b["seconds"] for b in have) / n / 1e9 / calls,
            "start_s": sum(b["start"] for b in have) / len(have) / 1e9}
    first_runs = windows[0][0]
    lo, hi = first_runs[0][1], first_runs[-1][1] + first_runs[-1][2]
    span_s = {role: sum(
        e[2] for p in planes if not p["name"].startswith("/device:")
        for line in p["lines"] for e in line["events"]
        if e[0] == name and lo <= e[1] <= hi) / 1e9
        for role, name in (spans or {}).items() if name is not None}
    everything = sum(c["everything"] for c in chips)
    return Joined(
        chips=n, calls=calls, phase_s=mean("phase"), module_s=mean("module"),
        pass_s=mean("passes"), buckets=buckets,
        lead_s=sum(c["lead"] for c in chips) / n / 1e9,
        tail_s=sum(c["tail"] for c in chips) / n / 1e9,
        joined_share=(sum(c["found"] for c in chips) / everything
                      if everything else 0.0),
        span_s=span_s, kernel_s=mean("kernels"))


def describe(j: Joined, steps: int) -> str:
    """The ``scopes:`` line: milliseconds an optimizer step."""
    ms = lambda s: round(1e3 * s / steps, 3)  # noqa: E731
    per_call = j.calls / steps          # a call may hold several steps
    heaviest = sorted(j.module_s.items(), key=lambda kv: -kv[1])[:10]
    return "scopes: " + json.dumps({
        "phase_ms": {k: ms(v) for k, v in sorted(
            j.phase_s.items(), key=lambda kv: -kv[1])},
        "module_ms": {k: ms(v) for k, v in heaviest},
        "flash_pass_ms": {k: ms(v) for k, v in j.pass_s.items()},
        "kernel_module_ms": {k: ms(v) for k, v in j.kernel_module_s.items()},
        "buckets": {k: {"calls": b["calls"], "bytes": b["bytes"],
                        "ms": round(1e3 * b["seconds"] * per_call, 3),
                        "start_ms": round(1e3 * b["start_s"], 3)}
                    for k, b in j.buckets.items()},
        "allreduce_lead_ms": round(1e3 * j.lead_s * per_call, 3),
        "allreduce_tail_ms": round(1e3 * j.tail_s * per_call, 3),
        "host_span_ms": {k: ms(v) for k, v in j.span_s.items()},
        "joined_share_pct": round(100 * j.joined_share, 3)})


def of(run) -> Joined | None:
    """The run's traced window joined to its program's names: made once,
    printed once, then a lookup.  None on an untraced run, a trace with no
    TPU plane (a rehearsal), or a program that names nothing."""
    if "_scopes" not in vars(run):
        joined = None
        if run.trace is not None:
            compiled, trace_dir = harness(run)
            table = table_of(compiled)
            if table is not None and trace_dir is not None:
                joined = join(trace.load(trace_dir), table,
                              host_span_names())
        if joined is not None:
            print(describe(joined, run.traced_steps))
        run._scopes = joined
    return run._scopes


def phase_ms(run, label: str) -> float | None:
    j = of(run)
    if j is None:
        return None
    seconds = j.mixed_s if label == "mixed" else j.phase(label)
    return 1e3 * seconds / run.traced_steps


def pass_ms(run, kernel_const: str) -> float | None:
    """``kernel_const`` names the constant of ``profiling`` that holds the
    pass's scope (``FLASH_FWD``)."""
    j = of(run)
    if j is None or not run.built.flash_calls:
        return None
    from horovod_tpu.utils import profiling
    return 1e3 * j.pass_s.get(getattr(profiling, kernel_const), 0.0) \
        / run.traced_steps


def flash_ms(run) -> float | None:
    """Milliseconds a step in the flash kernels: the passes the program
    names (``profiling.FLASH_PASSES``) and no other kernel of the window."""
    j = of(run)
    if j is None or not run.built.flash_calls:
        return None
    from horovod_tpu.utils import profiling
    return 1e3 * sum(j.pass_s.get(k, 0.0) for k in profiling.FLASH_PASSES) \
        / run.traced_steps


def by_scope(run, roles: dict) -> dict | None:
    """A module's device milliseconds a step by the scopes it wraps its work
    in.  ``roles`` maps a role to the constant of ``profiling`` that holds
    its scope's name (``{"scan": "SSM_SCAN"}``).  A scope's time is XLA's
    operations and the kernels whose module path holds its name;
    ``"elsewhere"`` is what lies under the paths that own the scopes (what
    stands before a scope's name: the mixer, the layer) and under none of
    them.  None without a join, or for a program without the names."""
    j = of(run)
    if j is None:
        return None
    from horovod_tpu.utils import profiling
    names = {role: getattr(profiling, const, None)
             for role, const in roles.items()}
    if None in names.values():
        return None
    ms = lambda seconds: 1e3 * seconds / run.traced_steps  # noqa: E731
    timed = [(m, v) for d in (j.module_s, j.kernel_module_s)
             for m, v in d.items()]
    under = lambda name: sum(  # noqa: E731
        v for m, v in timed if name in m.split("/"))
    out = {role: ms(under(name)) for role, name in names.items()}
    owners = {m.split("/" + name)[0] for name in names.values()
              for m, _ in timed if name in m.split("/")}
    inside = sum(v for m, v in timed
                 if any(m == p or m.startswith(p + "/") for p in owners))
    out["elsewhere"] = ms(inside) - sum(out.values())
    return out


def across_chips(run) -> Joined | None:
    """The join of a cell that has collectives to place: several chips."""
    return of(run) if run.chips >= 2 else None


def position_ms(run, which: str) -> float | None:
    """``lead`` or ``tail`` (:class:`Joined`), milliseconds a step."""
    j = across_chips(run)
    if j is None:
        return None
    return 1e3 * getattr(j, f"{which}_s") * j.calls / run.traced_steps


def span_ms(run, role: str) -> float | None:
    j = of(run)
    if j is None or role not in j.span_s:
        return None
    return 1e3 * j.span_s[role] / run.traced_steps
