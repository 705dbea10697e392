"""From a profiler trace of a few seconds of serving to numbers: what
``trace.reduce`` is to a training window.  ``trace.load`` reads the file;
the interval arithmetic is ``trace.py``'s.

A serving trace differs from a training one in two ways.  Several programs
run in it (a prefill for each bucket, the decode step), and instruction
names repeat across programs, so a device operation is filed under the
program whose execution holds it (the chip's ``XLA Modules`` line) and only
then under its name.  And the host reads every step's result before it
issues the next, so no execution is cut by the start or the stop of the
trace: the window is from the start of the first execution to the end of
the last, and the decode executions in it are, one for one and in order,
the decode calls the loop logged while tracing.

Idle gaps are named by what the host was doing at their middle, the
innermost of the loop's spans (``serving.HOST_SPANS``): inside ``decode``
or ``prefill`` the host was in the backend's call (transfers, dispatch, the
fetch of logits); ``engine_step`` alone is the scheduler's own bookkeeping;
``wait_arrival`` is no request to serve.
"""

from __future__ import annotations

import bisect
import dataclasses
import re

from benchmarks import trace
from benchmarks.metrics.attn_glue_ms import is_glue

HOST_SPANS = ("prefill", "decode", "submit", "wait_arrival", "engine_step")


@dataclasses.dataclass
class Summary:
    chips: int
    window_s: float
    busy_s: float
    program_s: dict             # decode / prefill / other -> device seconds
    program_calls: dict         # ... -> executions
    decode_attn_s: float | None     # own time under the layers' attn modules
    decode_module_s: dict       # folded module -> own seconds, decode alone
    joined_share: float | None  # of decode's own time, found in the table
    device_ops: list            # [[program:name, seconds]] by own time
    idle_gaps: list             # [[what the host was doing, seconds]]

    def describe(self) -> dict:
        top = sorted(self.decode_module_s.items(), key=lambda kv: -kv[1])
        return {"window_s": self.window_s, "busy_s": self.busy_s,
                "program_ms": {k: 1e3 * v for k, v in self.program_s.items()},
                "program_calls": self.program_calls,
                "decode_attn_ms": None if self.decode_attn_s is None
                else 1e3 * self.decode_attn_s,
                "joined_share": self.joined_share,
                "decode_module_ms": {k: round(1e3 * v, 3)
                                     for k, v in top[:12]}}


def _program_of(name: str, program_names: dict) -> str:
    bare = re.sub(r"\(\d+\)$", "", name)
    for kind, jit_name in program_names.items():
        if bare == jit_name:
            return kind
    return "other"


def reduce(planes: list[dict], program_names: dict, decode_table=None
           ) -> Summary | None:
    """None when the trace holds no TPU plane with an execution in it (a
    CPU rehearsal): the readers then report nothing."""
    devices = sorted(
        (p for p in planes if re.match(r"^/device:TPU:\d+$", p["name"])),
        key=lambda p: int(p["name"].rsplit(":", 1)[1]))
    plane = next((p for p in devices if trace._line(p, "XLA Modules")), None)
    if plane is None:
        return None
    runs = trace._line(plane, "XLA Modules")
    lo, hi = runs[0][1], max(e[1] + e[2] for e in runs)
    starts = [e[1] for e in runs]
    kinds = [_program_of(e[0], program_names) for e in runs]
    ops = trace._line(plane, "XLA Ops")
    own = trace.own_times(ops)

    def run_of(start: float) -> int | None:
        k = bisect.bisect_right(starts, start) - 1
        return k if k >= 0 and start < runs[k][1] + runs[k][2] else None

    program_s = dict.fromkeys(("decode", "prefill", "other"), 0.0)
    program_calls = {k: kinds.count(k) for k in program_s}
    by_name: dict[str, float] = {}
    module_s: dict[str, float] = {}
    joined = decode_own = 0.0
    for (name, start, _, _), t in zip(ops, own):
        k = run_of(start)
        kind = kinds[k] if k is not None else "other"
        program_s[kind] += t / 1e9
        by_name[f"{kind}:{name}"] = by_name.get(f"{kind}:{name}", 0.0) + t
        if kind == "decode":
            decode_own += t
            scope = decode_table.get(name) if decode_table else None
            if scope is not None:
                joined += t
                module_s[scope.module] = module_s.get(scope.module, 0.0) \
                    + t / 1e9
    busy = trace.union([(e[1], e[1] + e[2]) for e in ops])
    # the loop's spans by name, innermost names first; spans of one name
    # never overlap, so each is looked up by bisection
    by_span = {name: sorted((e[1], e[1] + e[2]) for p in planes
                            if not p["name"].startswith("/device:")
                            for line in p["lines"] for e in line["events"]
                            if e[0] == name) for name in HOST_SPANS}

    def host_doing(a: float, b: float) -> str:
        mid = (a + b) / 2
        for name, spans in by_span.items():
            k = bisect.bisect_right(spans, (mid, float("inf"))) - 1
            if k >= 0 and spans[k][0] <= mid <= spans[k][1]:
                return name
        return "host_other"

    # gaps summed by what the host was doing: there are thousands of them,
    # one or two a step, and the ten longest alone would all be waits
    gap_s: dict[str, float] = {}
    for a, b in trace.subtract([(lo, hi)], busy):
        what = host_doing(a, b)
        gap_s[what] = gap_s.get(what, 0.0) + (b - a) / 1e9
    names = sorted(by_name.items(), key=lambda kv: -kv[1])[:10]
    return Summary(
        chips=len(devices), window_s=(hi - lo) / 1e9,
        busy_s=trace.length(busy) / 1e9, program_s=program_s,
        program_calls=program_calls,
        decode_attn_s=sum(v for m, v in module_s.items() if is_glue(m))
        if module_s else None,
        decode_module_s=module_s,
        joined_share=joined / decode_own if decode_table and decode_own
        else None,
        device_ops=[[k, v / 1e9] for k, v in names],
        idle_gaps=sorted(([k, v] for k, v in gap_s.items()),
                         key=lambda kv: -kv[1])[:10])
