"""The reduction from a trace to numbers, on a small recorded trace and on
hand-made events.

``data/dsc1p3b-s2048.one-chip.trace.json.gz`` is ``trace.load``'s output
for a trace of six calls of cell ``dsc1p3b-s2048`` on one TPU v5e (my chip
run, PR 23), cut to the lines the reduction reads.
"""

import gzip
import json
import os

import pytest

from benchmarks import trace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


@pytest.fixture(scope="module")
def recorded():
    with gzip.open(os.path.join(
            DATA, "dsc1p3b-s2048.one-chip.trace.json.gz"), "rt") as f:
        return json.load(f)


def test_recorded_trace_reduces_to_whole_steps(recorded):
    s = trace.reduce(recorded)
    assert (s.chips, s.calls) == (1, 3)       # 5 executions, outer two cut
    # three steps of 0.3358 s, back to back
    assert s.window_s == pytest.approx(3 * 0.33576, rel=1e-3)
    assert 0.0 <= 1.0 - s.busy_s / s.window_s < 1e-3
    # own times add up to the busy time
    assert sum(s.kind_s.values()) == pytest.approx(s.busy_s, rel=1e-6)
    # the kernels, summed independently of the reduction: 18 a step
    dev = next(p for p in recorded if p["name"] == "/device:TPU:0")
    modules = next(l for l in dev["lines"] if l["name"] == "XLA Modules")
    lo = modules["events"][1][1]
    hi = modules["events"][3][1] + modules["events"][3][2]
    ops = next(l for l in dev["lines"] if l["name"] == "XLA Ops")["events"]
    kernels = [e for e in ops if e[3].get("custom_call_target")
               == "tpu_custom_call" and lo <= e[1] and e[1] + e[2] <= hi]
    assert len(kernels) == 3 * 18
    assert s.kind_s[trace.KERNEL] == pytest.approx(
        sum(e[2] for e in kernels) / 1e9, rel=1e-9)
    assert s.collective_s == 0.0
    assert s.device_ops[0][0].startswith("fusion.")
    assert all(len(name) < 64 for name, _ in s.device_ops)
    assert {name for name, _ in s.idle_gaps} <= {
        "loss_fetch", "dispatch", "input_wait", "host_other"}


def test_short_event_keeps_opcode_and_kernel_marker():
    name, stats = trace.short_event(
        '%attn.22 = (f32[128,2048,128]{2,1,0:T(8,128)}, f32[128,8,2048]{2,1,0}) '
        'custom-call(s32[3]{0:T(128)S(1)} %copy-done.311), '
        'custom_call_target="tpu_custom_call", operand_layout...')
    assert (name, stats) == ("attn.22", {
        "opcode": "custom-call", "custom_call_target": "tpu_custom_call"})
    # jax's psum keeps its name and compiles to an all-reduce
    name, stats = trace.short_event(
        '%psum.406 = f32[2048,32256]{1,0:T(8,128)} all-reduce(%fusion.120), '
        'channel_id=1, replica_groups={{0,1,2,3}}')
    assert (name, stats) == ("psum.406", {"opcode": "all-reduce"})
    assert trace.kind_of(stats) == "collective"
    assert trace.short_event("jit_step(123)") == ("jit_step(123)", {})


def ev(name, start, dur, **stats):
    return [name, float(start), float(dur), stats]


def test_own_times_subtract_nested_operations():
    events = [ev("while.1", 0, 100), ev("fusion.1", 10, 30),
              ev("fusion.2", 50, 40), ev("copy.1", 100, 5)]
    assert trace.own_times(events) == [30.0, 30.0, 40.0, 5.0]


def test_exposed_collective_time_on_hand_made_events():
    """Two chips, three executions each (the outer two are dropped).  In
    the middle one: compute 0-60, a synchronous all-reduce 60-80 (all of it
    exposed), an asynchronous one from 20 to 50 under compute (hidden), and
    compute 80-100."""
    def chip(n):
        modules = [ev("jit_step(1)", -100, 100), ev("jit_step(1)", 0, 100),
                   ev("jit_step(1)", 100, 100)]
        ops = [ev("fusion.1", 0, 60),
               ev("all-reduce-start.2", 20, 1, opcode="all-reduce-start"),
               ev("all-reduce-done.2", 49, 1, opcode="all-reduce-done"),
               ev("psum.1", 60, 20, opcode="all-reduce"),
               ev("fusion.2", 80, 20, opcode="fusion")]
        async_ops = [ev("all-reduce-start.2", 20, 30,
                        opcode="all-reduce-start")]
        return {"name": f"/device:TPU:{n}", "lines": [
            {"name": "XLA Modules", "events": modules},
            {"name": "XLA Ops", "events": ops},
            {"name": "Async XLA Ops", "events": async_ops}]}

    s = trace.reduce([chip(0), chip(1)])
    assert (s.chips, s.calls) == (2, 1)
    assert s.window_s == pytest.approx(100e-9)
    assert s.collective_s == pytest.approx(50e-9)          # 20-50 and 60-80
    assert s.collective_exposed_s == pytest.approx(20e-9)  # 60-80 alone
    assert s.busy_s == pytest.approx(100e-9)


def test_a_trace_without_a_tpu_plane_reduces_to_nothing():
    assert trace.reduce([{"name": "/host:CPU", "lines": []}]) is None
