"""What the serving program says of itself: the spans ``ServingEngine`` and
its backend write (``horovod_tpu/utils/profiling.py``, the ``hvd_srv_*``
names), read for the per-layer metrics whose ``source`` is ``program_span``.
``benchmarks/serving.Timed`` times the backend from outside; these readers
ask the program.

Two sources, one vocabulary.  The RING (``profiling.spans()``: every span a
record with start and end on ``time.perf_counter``, the loop's clock, its
id, its cause and the counts of its boundary) gives the window's numbers:
the records that began inside ``run.open_t..run.close_t``, the profiler
off.  The TRACE of a ``--trace 1`` run (``run.trace_dir``) has the same
spans as ``TraceAnnotation`` events on the host plane, on the profiler's
clock beside the device plane: there every second in which no operation ran
on the chip is given to the INNERMOST of the program's spans that covers
it.  A decode step leaves ONE idle gap, from the device's last operation to
the next step's first; it lies over the tail of ``hvd_srv_wait``, the whole
of ``hvd_srv_fetch``, the scheduler's own work, ``hvd_srv_h2d`` and
``hvd_srv_dispatch``, so a gap is divided among the spans it lies over and
not handed whole to the one at its middle.

A program without the spans (an older checkout under these files) reads as
nothing: :func:`of` returns None and every metric is left out of the line.
"""

from __future__ import annotations

import dataclasses
import json
import re
import statistics

from horovod_tpu.utils import profiling

from benchmarks import serving, trace

_UNREAD = object()


@dataclasses.dataclass
class Spans:
    metrics: dict               # metric stem -> value; absent: not read
    line: dict                  # what the ``serve_host:`` line prints


def of(run) -> Spans | None:
    """The program's spans of one serving run, read once; the first reading
    of a traced run prints the ``serve_host:`` line.  None for a training
    run and for a program that writes no spans."""
    if not hasattr(run, "records") or not hasattr(profiling, "spans"):
        return None
    got = vars(run).get("_program_spans", _UNREAD)
    if got is _UNREAD:
        got = run._program_spans = _read(run)
        if got is not None and run.trace_dir is not None:
            print(f"serve_host: {json.dumps(got.line)}", flush=True)
    return got


def metric(run, stem: str):
    got = of(run)
    return None if got is None else got.metrics.get(stem)


def _ms(seconds: float) -> float:
    return 1e3 * seconds


def short(name: str) -> str:
    """``hvd_srv_h2d`` as the lines and the metrics' stems say it: h2d."""
    return name.removeprefix("hvd_srv_")


def leaves_of(calls: list, children: dict) -> dict[str, list[float]]:
    """Leaf name -> its seconds in each of ``calls`` that has that leaf."""
    took: dict[str, list[float]] = {name: [] for name in profiling.SRV_LEAVES}
    for call in calls:
        for leaf in children.get(call.id, ()):
            if leaf.name in took:
                took[leaf.name].append(leaf.seconds)
    return took


def self_seconds(step, calls: list) -> float:
    """A step's duration less what the backend calls inside it cover (the
    calls of one engine never overlap): padding, token bookkeeping,
    eviction, the collective tick."""
    return step.seconds - sum(c.seconds for c in calls
                              if step.start <= c.start and c.end <= step.end)


def _read(run) -> Spans | None:
    ring = profiling.spans()
    if not ring:
        return None
    window = [r for r in ring if run.open_t <= r.start < run.close_t]
    children: dict[int, list] = {}
    for r in ring:
        children.setdefault(r.cause, []).append(r)
    by_name: dict[str, list] = {}
    for r in window:
        by_name.setdefault(r.name, []).append(r)
    calls = [r for r in window if r.name in profiling.SRV_CALLS]
    decode = leaves_of(by_name.get(profiling.SRV_DECODE, []), children)
    prefill = leaves_of(by_name.get(profiling.SRV_PREFILL, []), children)
    metrics: dict = {}
    for name, took in decode.items():
        if took:
            metrics[f"decode_{short(name)}_ms"] = _ms(statistics.median(took))
    steps = by_name.get(profiling.SRV_STEP, [])
    if steps:
        metrics["sched_self_ms"] = _ms(statistics.median(
            self_seconds(s, calls) for s in steps))
    counted = {r.request.rid for r in run.counted}
    queued = [r.seconds for r in ring
              if r.name == profiling.SRV_QUEUED and r.rid in counted]
    if queued:
        metrics["engine_queue_ms_p95"] = serving.percentile(
            [_ms(q) for q in queued], 95)
    by_id = {c.id: c for c in calls}
    waited = {c.id: sum(w.seconds for w in children.get(c.id, ())
                        if w.name == profiling.SRV_WAIT) for c in calls}
    outside = {k: by_id[k].seconds - w for k, w in waited.items()}
    longest = {}
    if any(waited.values()):
        for stem, took in (("longest_wait_ms", waited),
                           ("longest_host_ms", outside)):
            worst = by_id[max(took, key=took.get)]
            metrics[stem] = _ms(took[worst.id])
            # which call it was and how its time lay: what a stall leaves
            longest[stem] = {
                "span": worst.name, "at_s": round(worst.start - run.open_t, 3),
                **{k: v for k, v in worst.fields.items() if k != "rids"},
                "leaf_ms": {short(r.name): round(_ms(r.seconds), 3)
                            for r in children.get(worst.id, ())}}
    mean_ms = lambda took: {  # noqa: E731
        short(k): round(_ms(statistics.fmean(v)), 4)
        for k, v in took.items() if v}
    # the stretch under the profiler comes after the drain: the same
    # leaves with the session open say what tracing costs a call
    traced_decode = leaves_of(
        [r for r in ring if r.name == profiling.SRV_DECODE
         and r.start >= run.end_t], children)
    line = {"window_records": len(window),
            # False: the ring dropped records of the window before this
            # reading, and the numbers stand on what was left
            "ring_whole": len(ring) < profiling.SPAN_CAPACITY
            or ring[0].start <= run.open_t,
            "decode_calls": len(by_name.get(profiling.SRV_DECODE, [])),
            "decode_leaf_mean_ms": mean_ms(decode),
            "prefill_calls": len(by_name.get(profiling.SRV_PREFILL, [])),
            "prefill_leaf_mean_ms": mean_ms(prefill),
            "traced_decode_leaf_mean_ms": mean_ms(traced_decode), **longest}
    if run.trace_dir is not None:
        idle = idle_by_span(trace.load(run.trace_dir))
        if idle is not None:
            line["idle_s_by_span"] = {k: round(v, 6) for k, v in sorted(
                idle.items(), key=lambda kv: -kv[1])}
            in_step = sum(v for k, v in idle.items() if k != OUTSIDE)
            in_leaves = sum(idle.get(k, 0.0) for k in profiling.SRV_LEAVES)
            if in_step:
                metrics["idle_named_share"] = 100.0 * in_leaves / in_step
    return Spans(metrics=metrics, line=line)


OUTSIDE = "outside_hvd_srv_step"


def intersect(a: list, b: list) -> list[tuple[float, float]]:
    """The parts two unions of intervals share."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if lo < hi:
            out.append((lo, hi))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def idle_by_span(planes: list[dict]) -> dict[str, float] | None:
    """Seconds of the traced window in which no operation ran on the chip,
    by the innermost program span that covers them: a leaf; a backend call
    outside its leaves; ``hvd_srv_step`` outside its calls (the scheduler's
    own time); :data:`OUTSIDE` every step (the loop: no request to serve, a
    submission).  None when the trace holds no TPU plane with an execution
    in it (a rehearsal on the CPU) or none of the program's spans."""
    devices = sorted(
        (p for p in planes if re.match(r"^/device:TPU:\d+$", p["name"])),
        key=lambda p: int(p["name"].rsplit(":", 1)[1]))
    plane = next((p for p in devices if trace._line(p, "XLA Modules")), None)
    host = host_spans(planes)
    if plane is None or not host.get(profiling.SRV_STEP):
        return None
    runs = trace._line(plane, "XLA Modules")
    window = [(runs[0][1], max(e[1] + e[2] for e in runs))]
    busy = trace.union([(e[1], e[1] + e[2])
                        for e in trace._line(plane, "XLA Ops")])
    idle = trace.subtract(window, busy)
    cover = lambda names: trace.union(  # noqa: E731
        [iv for n in names for iv in host.get(n, [])])
    leaves, calls = cover(profiling.SRV_LEAVES), cover(profiling.SRV_CALLS)
    steps = cover([profiling.SRV_STEP])
    own = {name: host.get(name, []) for name in profiling.SRV_LEAVES}
    for name in profiling.SRV_CALLS:
        own[name] = trace.subtract(trace.union(host.get(name, [])), leaves)
    own[profiling.SRV_STEP] = trace.subtract(steps, calls)
    own[OUTSIDE] = trace.subtract(window, steps)
    return {name: trace.length(intersect(idle, trace.union(spans))) / 1e9
            for name, spans in own.items() if spans}


def host_spans(planes: list[dict]) -> dict[str, list[tuple[float, float]]]:
    """The program's ``with`` spans on a trace's host planes, by name, as
    sorted (start, end) in the profiler's nanoseconds."""
    names = {profiling.SRV_STEP, *profiling.SRV_CALLS, *profiling.SRV_LEAVES}
    found: dict[str, list] = {}
    for p in planes:
        if p["name"].startswith("/device:"):
            continue
        for line in p["lines"]:
            for e in line["events"]:
                if e[0] in names:
                    found.setdefault(e[0], []).append((e[1], e[1] + e[2]))
    return {name: sorted(spans) for name, spans in found.items()}
