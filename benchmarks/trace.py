"""From a profiler trace (``.xplane.pb``) to numbers: the reduction every
device-trace metric and the ``breakdown`` read.

Two stages, so that the second can be checked on a small recorded trace
(``benchmarks/tests``): :func:`load` turns the profiler's file into plain
lists (planes -> lines -> events), :func:`reduce` turns those into a
:class:`Summary`.

What the reduction takes from a TPU trace (looked at by hand, PR 23, jax
0.9.0 / libtpu 0.0.34): each chip is a plane ``/device:TPU:<n>``.  Its line
``XLA Modules`` has one event per execution of a compiled program, named
``jit_<fn>(<fingerprint>)``; the first and last are cut by the start and
stop of the trace.  Its line ``XLA Ops`` has one event per HLO operation,
named by the instruction's whole text (``%fusion.364 = bf16[...] fusion(...)``),
nested where an operation (a ``while``) holds others; a Mosaic kernel,
Pallas's or one the compiler made itself (XLA:TPU's ``ragged-dot``), is a
``custom-call`` whose ``custom_call_target`` is ``tpu_custom_call``, and a
collective is told by its opcode, not its name.  Its
line ``Async XLA Ops`` has one event from each ``*-start`` to its
``*-done`` (prefetch copies, and collectives the compiler made
asynchronous).  Host threads are lines of the plane ``/host:CPU``; the
loop's ``TraceAnnotation`` spans are events on the ``python3`` line.

The traced window of a chip runs from the start of the first WHOLE
execution of the step program (the module that took most time) to the end
of the last whole one.  An operation's own time is its duration less that
of the operations nested in it, so own times add up to the busy time.
"""

from __future__ import annotations

import contextlib
import dataclasses
import glob
import os
import re

COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all)"
    r"(-start|-done)?$")
# Spans the loop writes on the host with jax.profiler.TraceAnnotation.
HOST_SPANS = ("input_wait", "dispatch", "loss_fetch")
HLO_TEXT = re.compile(r"^%(\S+) = ")
KERNEL_TARGET = "tpu_custom_call"
# The kind of a Mosaic kernel.  Being one decides the kind and nothing else:
# which kernel it is, and whose, the program's scope table says
# (benchmarks/scopes.py).  The key is still spelled as when every kernel was
# a flash kernel: tests/test_bench_scopes.py (tier-1, no benchmark file)
# asks ``kind_of`` and ``kind_s`` for it by that string.  Everything under
# benchmarks/ says ``trace.KERNEL``, so the spelling changes on this line.
KERNEL = "flash"


def short_event(text: str) -> tuple[str, dict]:
    """An HLO instruction's text cut to its name, with what the reduction
    reads from the rest kept as stats: the opcode (an instruction's name
    need not say what it is: jax's ``psum`` compiles to ``%psum.406 = f32[..]
    all-reduce(..)``) and a custom call's target."""
    m = HLO_TEXT.match(text)
    if not m:
        return text, {}
    rest = text[m.end():]
    if rest.startswith("("):              # a tuple shape: skip to its end
        depth = 0
        for i, ch in enumerate(rest):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                break
        rest = rest[i + 1:].lstrip()
    else:
        rest = rest.partition(" ")[2]
    stats = {"opcode": re.match(r"[\w\-]*", rest).group(0)}
    target = re.search(r'custom_call_target="([^"]*)"', text)
    if target:
        stats["custom_call_target"] = target.group(1)
    return m.group(1), stats


@contextlib.contextmanager
def record(logdir: str):
    import jax

    jax.profiler.start_trace(logdir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def load(logdir: str, keep_stats: bool = False) -> list[dict]:
    """Every plane of the newest ``.xplane.pb`` under ``logdir`` as
    ``{"name", "lines": [{"name", "events": [[name, start_ns, dur_ns,
    {stat: value}], ...]}]}``, device operations under their short names
    (:func:`short_event`)."""
    from jax.profiler import ProfileData

    files = sorted(glob.glob(os.path.join(
        logdir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        return []
    planes = []
    for plane in ProfileData.from_file(files[-1]).planes:
        lines = []
        for line in plane.lines:
            events = []
            for e in line.events:
                name, stats = short_event(e.name)
                if name == e.name and keep_stats:
                    stats = {k: v for k, v in e.stats
                             if isinstance(v, (str, int, float))}
                events.append([name, float(e.start_ns),
                               float(e.duration_ns), stats])
            lines.append({"name": line.name, "events": events})
        planes.append({"name": plane.name, "lines": lines})
    return planes


def union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def length(intervals) -> float:
    return sum(b - a for a, b in intervals)


def subtract(intervals, holes) -> list[tuple[float, float]]:
    """Parts of ``intervals`` (a union) that no interval of ``holes`` (a
    union) covers."""
    out = []
    for a, b in intervals:
        cur = a
        for ha, hb in holes:
            if hb <= cur or ha >= b:
                continue
            if ha > cur:
                out.append((cur, ha))
            cur = max(cur, hb)
        if cur < b:
            out.append((cur, b))
    return out


def own_times(events: list) -> list[float]:
    """Each event's duration less that of the events nested directly in
    it; ``events`` sorted by (start, -duration)."""
    own = [e[2] for e in events]
    stack: list[int] = []
    for i, (_, start, dur, _) in enumerate(events):
        while stack and start >= events[stack[-1]][1] + events[stack[-1]][2]:
            stack.pop()
        if stack:
            own[stack[-1]] -= dur
        stack.append(i)
    return own


def kind_of(stats: dict) -> str:
    """``collective``, :data:`KERNEL` (a Pallas or compiler-made Mosaic
    kernel, which XLA sees as a custom call) or ``xla`` (every other
    operation the compiler made)."""
    if COLLECTIVE.match(stats.get("opcode", "")):
        return "collective"
    if stats.get("custom_call_target") == KERNEL_TARGET:
        return KERNEL
    return "xla"


@dataclasses.dataclass
class Summary:
    chips: int
    calls: int                 # executions of the step program in the window
    window_s: float            # traced window, mean over chips
    busy_s: float              # union of operations inside it, mean over chips
    kind_s: dict               # kind -> seconds of own time, mean over chips
    collective_s: float        # union of collective intervals, mean over chips
    collective_exposed_s: float  # ... not covered by any other operation
    device_ops: list           # [[name, seconds]] chip 0, by own time
    idle_gaps: list            # [[what the host was doing, seconds]] chip 0


def _line(plane: dict, name: str) -> list:
    for line in plane["lines"]:
        if line["name"] == name:
            return sorted(line["events"], key=lambda e: (e[1], -e[2]))
    return []


def _collective_intervals(ops: list, async_ops: list
                          ) -> list[tuple[float, float]]:
    """A synchronous collective is its own event; an asynchronous one runs
    from the start of its ``-start`` to the end of its ``-done``, which the
    ``Async XLA Ops`` line gives as one event (paired by hand where that
    line is missing)."""
    spans = [(s, s + d) for _, s, d, stats in async_ops
             if COLLECTIVE.match(stats.get("opcode", ""))]
    open_starts: dict = {}
    for name, start, dur, stats in ops:
        m = COLLECTIVE.match(stats.get("opcode", ""))
        if not m:
            continue
        tail = name.rpartition(".")[2]      # start and done share a number
        if m.group(2) == "-start":
            open_starts[(m.group(1), tail)] = start
        elif m.group(2) == "-done":
            begun = open_starts.pop((m.group(1), tail), start)
            spans.append((begun, start + dur))
        else:
            spans.append((start, start + dur))
    return union(spans)


def reduce(planes: list[dict]) -> Summary | None:
    """None when the trace holds no TPU plane with a step program in it (a
    CPU rehearsal): the readers then report nothing."""
    devices = [p for p in planes if re.match(r"^/device:TPU:\d+$", p["name"])]
    host_spans = [(e[1], e[1] + e[2], e[0]) for p in planes
                  if not p["name"].startswith("/device:")
                  for line in p["lines"] for e in line["events"]
                  if e[0] in HOST_SPANS]
    per_chip = []
    for plane in sorted(devices, key=lambda p: int(p["name"].rsplit(":", 1)[1])):
        modules = _line(plane, "XLA Modules")
        if not modules:
            continue
        total: dict[str, float] = {}
        for name, _, dur, _ in modules:
            key = re.sub(r"\(\d+\)$", "", name)
            total[key] = total.get(key, 0.0) + dur
        step = max(total, key=total.get)
        runs = [e for e in modules if re.sub(r"\(\d+\)$", "", e[0]) == step]
        if len(runs) < 3:
            continue
        runs = runs[1:-1]      # the trace's start and stop cut the outer two
        lo, hi = runs[0][1], runs[-1][1] + runs[-1][2]
        inside = lambda e: e[1] >= lo and e[1] + e[2] <= hi  # noqa: E731
        ops = [e for e in _line(plane, "XLA Ops") if inside(e)]
        async_ops = [e for e in _line(plane, "Async XLA Ops") if inside(e)]
        own = own_times(ops)
        kinds: dict[str, float] = {}
        by_name: dict[str, float] = {}
        op_kinds = [kind_of(e[3]) for e in ops]
        for (name, _, _, _), t, k in zip(ops, own, op_kinds):
            kinds[k] = kinds.get(k, 0.0) + t
            by_name[name] = by_name.get(name, 0.0) + t
        busy = union([(e[1], e[1] + e[2]) for e in ops])
        coll = _collective_intervals(ops, async_ops)
        others = union([(e[1], e[1] + e[2])
                        for e, t, k in zip(ops, own, op_kinds)
                        if k != "collective" and t > 0
                        and e[3].get("opcode") not in ("while", "conditional")])
        per_chip.append({
            "calls": len(runs), "window": hi - lo, "busy": length(busy),
            "kinds": kinds, "by_name": by_name, "coll": length(coll),
            "exposed": length(subtract(coll, others)),
            "gaps": subtract([(lo, hi)], busy)})
    if not per_chip:
        return None
    n = len(per_chip)
    mean = lambda key: sum(c[key] for c in per_chip) / n / 1e9  # noqa: E731
    first = per_chip[0]

    def host_doing(a: float, b: float) -> str:
        mid = (a + b) / 2
        for lo, hi, name in host_spans:
            if lo <= mid <= hi:
                return name
        return "host_other"

    gaps = sorted(first["gaps"], key=lambda g: g[0] - g[1])[:10]
    names = sorted(first["by_name"].items(), key=lambda kv: -kv[1])[:10]
    all_kinds = sorted({k for c in per_chip for k in c["kinds"]})
    return Summary(
        chips=n, calls=first["calls"], window_s=mean("window"),
        busy_s=mean("busy"),
        kind_s={k: sum(c["kinds"].get(k, 0.0) for c in per_chip) / n / 1e9
                for k in all_kinds},
        collective_s=mean("coll"), collective_exposed_s=mean("exposed"),
        device_ops=[[k, v / 1e9] for k, v in names],
        idle_gaps=[[host_doing(a, b), (b - a) / 1e9] for a, b in gaps])


def describe(planes: list[dict], events_per_line: int = 6) -> str:
    """What a trace holds, for looking at one by hand."""
    out = []
    for p in planes:
        out.append(f"plane {p['name']!r}")
        for line in p["lines"]:
            ev = line["events"]
            out.append(f"  line {line['name']!r}: {len(ev)} events")
            for e in ev[:events_per_line]:
                out.append(f"    {e[0]!r} start={e[1]:.0f} dur={e[2]:.0f} "
                           f"stats={e[3]}")
    return "\n".join(out)


if __name__ == "__main__":
    import json
    import sys

    print(describe(load(sys.argv[1], keep_stats=True)))
    loaded = load(sys.argv[1])
    if len(sys.argv) > 2:       # keep it: a fixture for the reduction's test
        with open(sys.argv[2], "w") as f:
            json.dump(loaded, f)
