"""``benchmarks/scopes.py``: the join of a traced window to the program's
scope table, on the recorded one-chip trace with a hand-made table and on
hand-made events (as ``benchmarks/tests/test_trace.py`` does for exposure),
and the readers built on it.  Here, and not under ``benchmarks/tests``, so
that the tier-1 run holds them."""

import gzip
import importlib.util
import json
import os
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import scopes, trace  # noqa: E402
from horovod_tpu.utils import profiling  # noqa: E402
from horovod_tpu.utils.profiling import Scope  # noqa: E402

NEW_READERS = ("fwd_ms", "bwd_ms", "recompute_ms", "optimizer_ms",
               "mixed_phase_ms", "unscoped_ms", "flash_fwd_ms", "flash_dq_ms",
               "flash_dkv_ms", "allreduce_buckets", "allreduce_lead_ms",
               "allreduce_tail_ms", "loader_wait_ms", "h2d_ms",
               "attn_glue_ms")


def reader(stem):
    path = os.path.join(ROOT, "benchmarks", "metrics", f"{stem}.py")
    spec = importlib.util.spec_from_file_location(f"_metric_{stem}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.fixture(scope="module")
def recorded():
    with gzip.open(os.path.join(
            ROOT, "benchmarks", "tests", "data",
            "dsc1p3b-s2048.one-chip.trace.json.gz"), "rt") as f:
        return json.load(f)


def scope(phases=("unscoped",), opcode="fusion", module="", **kw):
    return Scope(opcode=opcode, op_name="", phases=tuple(phases),
                 module=module, **kw)


def hand_made_table(planes):
    """A table for the recorded trace's own instruction names: fusions by
    the last digit of their number, kernels in turn by pass, and every
    tenth name left out."""
    dev = next(p for p in planes if p["name"] == "/device:TPU:0")
    ops = next(l for l in dev["lines"] if l["name"] == "XLA Ops")["events"]
    labels = [("forward",), ("backward",), ("backward", "optimizer"),
              ("optimizer",), ("recompute",), ("forward", "backward"),
              ("unscoped",)]
    table, kernels = {}, 0
    for i, name in enumerate(sorted({e[0] for e in ops})):
        stats = next(e[3] for e in ops if e[0] == name)
        if i % 10 == 9:
            continue
        if trace.kind_of(stats) == "flash":
            table[name] = scope(("forward",), "custom-call",
                                kernel=profiling.FLASH_PASSES[kernels % 3])
            kernels += 1
        else:
            table[name] = scope(labels[i % len(labels)],
                                stats.get("opcode", ""), f"m{i % 3}")
    return table


def test_join_of_the_recorded_trace_sums_to_the_reduction(recorded):
    table = hand_made_table(recorded)
    j = scopes.join(recorded, table)
    s = trace.reduce(recorded)
    assert (j.chips, j.calls) == (s.chips, s.calls) == (1, 3)
    # by construction: the phases are the xla kind, the passes the kernels
    assert sum(j.phase_s.values()) == pytest.approx(s.kind_s["xla"],
                                                    rel=1e-12)
    assert sum(j.pass_s.values()) == pytest.approx(s.kind_s["flash"],
                                                   rel=1e-12)
    assert sum(j.module_s.values()) == pytest.approx(s.kind_s["xla"],
                                                     rel=1e-12)
    assert set(j.phase_s) == {"forward", "backward", "backward+optimizer",
                              "optimizer", "recompute", "forward+backward",
                              "unscoped"}
    assert j.mixed_s == pytest.approx(
        j.phase("backward+optimizer") + j.phase("forward+backward"))
    assert set(j.pass_s) <= set(profiling.FLASH_PASSES) | {"(unnamed)"}
    assert 0.5 < j.joined_share < 1.0       # a tenth of the names is missing
    assert j.buckets == {} and j.lead_s == j.tail_s == 0.0
    line = scopes.describe(j, 3)
    assert line.startswith("scopes: ")
    shown = json.loads(line[len("scopes: "):])
    assert sum(shown["phase_ms"].values()) == pytest.approx(
        1e3 * s.kind_s["xla"] / 3, abs=0.01)
    assert len(shown["module_ms"]) <= 10


def test_join_with_the_whole_table_finds_every_operation(recorded):
    dev = next(p for p in recorded if p["name"] == "/device:TPU:0")
    ops = next(l for l in dev["lines"] if l["name"] == "XLA Ops")["events"]
    j = scopes.join(recorded, {e[0]: scope(("forward",)) for e in ops})
    assert j.joined_share == pytest.approx(1.0)
    assert set(j.phase_s) == {"forward"}


def ev(name, start, dur, **stats):
    return [name, float(start), float(dur), stats]


def chip(n, shift=0.0):
    """Three executions of 100 ns (the outer two are dropped).  In the
    middle one: forward 0-30, backward 30-50, bucket 0's all-reduce 50-60
    under nothing, backward 60-80 (the last), bucket 1's all-reduce 80-95
    and the loss's own 95-97, the optimizer 97-100."""
    modules = [ev("jit_step(1)", -100, 100), ev("jit_step(1)", 0, 100),
               ev("jit_step(1)", 100, 100)]
    ops = [ev("fusion.1", 0, 30, opcode="fusion"),
           ev("fusion.2", 30, 20 + shift, opcode="fusion"),
           ev("all-reduce.1", 50 + shift, 10 - shift, opcode="all-reduce"),
           ev("fusion.3", 60, 20, opcode="fusion"),
           ev("psum.2", 80, 15, opcode="all-reduce"),
           ev("psum.3", 95, 2, opcode="all-reduce"),
           ev("fusion.4", 97, 3, opcode="fusion")]
    return {"name": f"/device:TPU:{n}", "lines": [
        {"name": "XLA Modules", "events": modules},
        {"name": "XLA Ops", "events": ops},
        {"name": "Async XLA Ops", "events": []}]}


TABLE = {
    "fusion.1": scope(("forward",), module="Net/layer_N"),
    "fusion.2": scope(("backward",), module="Net/layer_N"),
    "fusion.3": scope(("backward",), module="Net/head"),
    "fusion.4": scope(("backward", "optimizer"), module="hvd_optimizer"),
    "all-reduce.1": scope(("collective",), "all-reduce", bucket="0",
                          bytes=400),
    "psum.2": scope(("collective",), "all-reduce", bucket="1", bytes=800),
    "psum.3": scope(("collective",), "all-reduce", bytes=4),
}


def test_lead_and_tail_on_hand_made_events():
    j = scopes.join([chip(0), chip(1, shift=4.0)], TABLE)
    assert (j.chips, j.calls) == (2, 1)
    # bucket 0 starts at 50 (54 on chip 1), the last backward ends at 80
    assert j.lead_s == pytest.approx((30e-9 + 26e-9) / 2)
    # after 80: psum.2 and psum.3, 17 ns on either chip
    assert j.tail_s == pytest.approx(17e-9)
    assert j.phase("forward") == pytest.approx(30e-9)
    assert j.phase("backward") == pytest.approx((40e-9 + 44e-9) / 2)
    assert j.mixed_s == pytest.approx(3e-9)
    assert set(j.buckets) == {"0", "1", "(none)"}
    assert j.buckets["0"]["calls"] == 1 and j.buckets["0"]["bytes"] == 400
    assert j.buckets["0"]["seconds"] == pytest.approx((10e-9 + 6e-9) / 2)
    assert j.buckets["0"]["start_s"] == pytest.approx((50e-9 + 54e-9) / 2)
    assert j.buckets["1"]["start_s"] == pytest.approx(80e-9)
    assert j.module_s["Net/layer_N"] == pytest.approx((50e-9 + 54e-9) / 2)
    assert j.joined_share == 1.0


def test_a_collective_that_starts_after_backward_leads_by_nothing():
    late = dict(TABLE, **{"fusion.3": scope(("forward",))})
    j = scopes.join([chip(0)], late)          # the last backward ends at 50
    assert j.lead_s == 0.0
    assert j.tail_s == pytest.approx(27e-9)   # every collective is after it


def test_host_spans_inside_the_window_are_summed():
    host = {"name": "/host:CPU", "lines": [{"name": "python", "events": [
        ev("hvd_loader_wait", -50, 5), ev("hvd_loader_wait", 10, 7),
        ev("hvd_h2d_put", 20, 2), ev("input_wait", 9, 14),
        ev("hvd_loader_wait", 250, 9)]}]}
    j = scopes.join([chip(0), host], TABLE, scopes.host_span_names())
    assert j.span_s == {"loader_wait": pytest.approx(7e-9),
                        "loader_produce": 0.0, "h2d_put": pytest.approx(2e-9)}


def test_a_trace_without_a_tpu_plane_joins_to_nothing():
    assert scopes.join([{"name": "/host:CPU", "lines": []}], TABLE) is None
    short = chip(0)
    short["lines"][0]["events"] = short["lines"][0]["events"][:2]
    assert scopes.join([short], TABLE) is None


def fake_run(trace_summary=None, chips=4, flash=True):
    built = types.SimpleNamespace(steps_per_call=1,
                                  flash_calls=[{}] if flash else [])
    return types.SimpleNamespace(trace=trace_summary, built=built,
                                 chips=chips, traced_steps=1)


@pytest.mark.parametrize("stem", NEW_READERS)
def test_a_reader_reports_nothing_without_a_device_trace(stem):
    """A rehearsal's trace has no TPU plane: ``run.trace`` is None."""
    assert reader(stem).read(fake_run()) is None


def test_the_harness_hands_over_what_run_py_holds(recorded, tmp_path):
    """``Run`` has no field for the compiled step or the trace directory;
    they are locals of ``run.main``."""
    def main():
        step = "compiled step"                      # noqa: F841
        trace_dir = str(tmp_path)                   # noqa: F841
        return (lambda run: scopes.harness(run))(fake_run())

    assert main() == ("compiled step", str(tmp_path))
    assert scopes.harness(fake_run()) == (None, None)
    given = fake_run()
    given.compiled, given.trace_dir = "c", "d"      # a later Run's fields win
    assert scopes.harness(given) == ("c", "d")


def test_readers_read_the_join_made_once(recorded, monkeypatch, capsys):
    dev = next(p for p in recorded if p["name"] == "/device:TPU:0")
    ops = next(l for l in dev["lines"] if l["name"] == "XLA Ops")["events"]
    table = {e[0]: scope(("forward",), "custom-call",
                         kernel=profiling.FLASH_DQ)
             if trace.kind_of(e[3]) == "flash" else scope(("backward",))
             for e in ops}
    loads = []
    monkeypatch.setattr(trace, "load",
                        lambda d: loads.append(d) or recorded)
    monkeypatch.setattr(scopes, "table_of", lambda compiled: table)
    summary = trace.reduce(recorded)
    run = fake_run(summary, chips=1)
    run.traced_steps = summary.calls
    run.compiled, run.trace_dir = object(), "somewhere"
    xla = 1e3 * summary.kind_s["xla"] / summary.calls
    flash = 1e3 * summary.kind_s["flash"] / summary.calls
    assert reader("bwd_ms").read(run) == pytest.approx(xla)
    assert reader("fwd_ms").read(run) == 0.0
    assert reader("mixed_phase_ms").read(run) == 0.0
    assert reader("flash_dq_ms").read(run) == pytest.approx(flash)
    assert reader("flash_fwd_ms").read(run) == 0.0
    assert reader("allreduce_buckets").read(run) is None     # one chip
    assert reader("loader_wait_ms").read(run) == 0.0
    assert loads == ["somewhere"]                   # joined once
    assert capsys.readouterr().out.count("scopes: ") == 1
    run.built.flash_calls = []                      # a model with no kernel
    assert reader("flash_dq_ms").read(run) is None


@pytest.mark.parametrize("kernel,bwd,dq", [
    ("FLASH_BWD", 1.0, 0.0),    # the one backward pass: the old names read 0
    ("FLASH_DQ", 0.0, 1.0),     # an older program's trace: the new one does
])
def test_flash_bwd_ms_reads_the_fused_pass(recorded, monkeypatch, kernel,
                                           bwd, dq):
    dev = next(p for p in recorded if p["name"] == "/device:TPU:0")
    ops = next(l for l in dev["lines"] if l["name"] == "XLA Ops")["events"]
    table = {e[0]: scope(("backward",), "custom-call",
                         kernel=getattr(profiling, kernel))
             if trace.kind_of(e[3]) == "flash" else scope(("backward",))
             for e in ops}
    monkeypatch.setattr(trace, "load", lambda d: recorded)
    monkeypatch.setattr(scopes, "table_of", lambda compiled: table)
    summary = trace.reduce(recorded)
    run = fake_run(summary, chips=1)
    run.traced_steps = summary.calls
    run.compiled, run.trace_dir = object(), "somewhere"
    flash = 1e3 * summary.kind_s["flash"] / summary.calls
    assert reader("flash_bwd_ms").read(run) == pytest.approx(bwd * flash)
    assert reader("flash_dq_ms").read(run) == pytest.approx(dq * flash)
    assert reader("flash_dkv_ms").read(run) == 0.0
    # a program from before the fused pass has no such name: left out
    monkeypatch.delattr(profiling, "FLASH_BWD")
    assert reader("flash_bwd_ms").read(run) is None


@pytest.mark.parametrize("module,glue", [
    ("Transformer/layer_N/attn", True),            # rope, casts, copies
    ("Transformer/layer_N/attn/q_norm", True),     # OLMoE's QK-norm
    ("Transformer/layer_N/attn/q", False),         # the four projections
    ("Transformer/layer_N/attn/o", False),
    ("Transformer/layer_N/mlp/up", False),
    ("hvd_optimizer", False),
    ("", False),
])
def test_attn_glue_ms_is_the_attention_module_less_its_projections(
        recorded, monkeypatch, module, glue):
    """Every XLA op of the recorded window under one module: the reader
    takes all of the window's XLA time, or none of it."""
    dev = next(p for p in recorded if p["name"] == "/device:TPU:0")
    ops = next(l for l in dev["lines"] if l["name"] == "XLA Ops")["events"]
    table = {e[0]: scope(("backward",), module=module) for e in ops}
    monkeypatch.setattr(trace, "load", lambda d: recorded)
    monkeypatch.setattr(scopes, "table_of", lambda compiled: table)
    summary = trace.reduce(recorded)
    run = fake_run(summary, chips=1)
    run.traced_steps = summary.calls
    run.compiled, run.trace_dir = object(), "somewhere"
    xla = 1e3 * summary.kind_s["xla"] / summary.calls
    assert reader("attn_glue_ms").is_glue(module) is glue
    assert reader("attn_glue_ms").read(run) == pytest.approx(glue * xla)


def test_a_program_without_a_scope_table_joins_to_nothing(monkeypatch):
    """What the parent commit is to these readers."""
    monkeypatch.delattr(profiling, "scope_table")
    assert scopes.table_of(object()) is None
    run = fake_run(object())
    run.compiled, run.trace_dir = object(), "somewhere"
    assert reader("fwd_ms").read(run) is None
