"""A kernel's time is read under the name the program gave it (PR 34): on
hand-made events and a hand-made scope table, one window that holds the
flash kernels, a Mosaic kernel launched under ``hvd_ssm_scan``, one under
``hvd_moe_dispatch``, the compiler's ``ragged-dot`` (a name, no path), a
kernel with both, and one the table does not hold.

Under ``benchmarks/tests`` and not ``tests/``: a ``benchmark`` PR adds no file
there (PERF.md, Open questions)."""

import dataclasses
import importlib.util
import os
import types

import pytest

from benchmarks import scopes, trace
from horovod_tpu.utils import profiling
from horovod_tpu.utils.profiling import Scope

METRICS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "metrics")
NS = 1e-9 * 1e3            # a nanosecond of a one-step window, in ms a step


def reader(stem):
    spec = importlib.util.spec_from_file_location(
        f"_metric_{stem}", os.path.join(METRICS, f"{stem}.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def ev(name, start, dur, **stats):
    return [name, float(start), float(dur), stats]


def kernel(name, start, dur):
    return ev(name, start, dur, opcode="custom-call",
              custom_call_target=trace.KERNEL_TARGET)


MIXER = "Transformer/layer_N/mamba"
LAYER = "Transformer/layer_N/moe_mlp"
# name, nanoseconds, what the program's table says of it (None: not in it)
OPS = [
    ("fusion.1", 20, dict(module=f"{MIXER}/{profiling.SSM_SCAN}")),
    ("ssd_fwd.1", 30, dict(opcode="custom-call",
                           module=f"{MIXER}/{profiling.SSM_SCAN}")),
    ("hvd_flash_fwd.1", 10, dict(opcode="custom-call",
                                 module="Transformer/layer_N/attn",
                                 kernel=profiling.FLASH_FWD)),
    ("hvd_flash_bwd.1", 15, dict(opcode="custom-call",
                                 module="Transformer/layer_N/attn",
                                 kernel=profiling.FLASH_BWD)),
    ("ragged-dot.1", 8, dict(opcode="custom-call",
                             kernel=profiling.MOE_EXPERTS)),
    ("fusion.2", 4, dict(module=f"{LAYER}/{profiling.MOE_DISPATCH}")),
    ("permute.1", 5, dict(opcode="custom-call",
                          module=f"{LAYER}/{profiling.MOE_DISPATCH}")),
    ("fusion.3", 3, dict(module=f"{LAYER}/{profiling.MOE_EXPERTS}")),
    ("gmm.1", 2, dict(opcode="custom-call", kernel=profiling.MOE_EXPERTS,
                      module=f"{LAYER}/{profiling.MOE_EXPERTS}")),
    ("mystery.1", 1, None),
]
KERNELS = {"ssd_fwd.1", "hvd_flash_fwd.1", "hvd_flash_bwd.1", "ragged-dot.1",
           "permute.1", "gmm.1", "mystery.1"}
TABLE = {name: Scope(op_name="", phases=("forward",),
                     **{"opcode": "fusion", "module": "", **said})
         for name, _, said in OPS if said is not None}


def planes():
    """Three executions of 100 ns; the outer two are dropped, the middle one
    holds ``OPS`` back to back."""
    ops, at = [], 0
    for name, dur, _ in OPS:
        ops.append(kernel(name, at, dur) if name in KERNELS
                   else ev(name, at, dur, opcode="fusion"))
        at += dur
    modules = [ev("jit_step(1)", s, 100) for s in (-100, 0, 100)]
    return [{"name": "/device:TPU:0", "lines": [
        {"name": "XLA Modules", "events": modules},
        {"name": "XLA Ops", "events": ops},
        {"name": "Async XLA Ops", "events": []}]}]


@pytest.fixture
def run(monkeypatch):
    monkeypatch.setattr(trace, "load", lambda d: planes())
    monkeypatch.setattr(scopes, "table_of", lambda compiled: TABLE)
    summary = trace.reduce(planes())
    assert summary.calls == 1
    built = types.SimpleNamespace(
        steps_per_call=1,
        flash_calls=[dict(b=1, h=1, s=8, d=8, causal=True)],
        # the scan's least: 10 ns of operations, 20 ns of bytes
        notes={"ssd_scan_flops_per_step_a_chip": 197e12 * 10e-9,
               "ssd_scan_bytes_per_step_a_chip": 819e9 * 20e-9})
    return types.SimpleNamespace(
        trace=summary, built=built, chips=1, traced_steps=1, config={},
        compiled=object(), trace_dir="somewhere",
        peaks={"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9})


def test_a_kernel_under_the_scan_is_the_scans_and_not_flashs(run, capsys):
    """The case the next ``perf_opt`` stands on: whatever a scan kernel
    fuses, its time stays inside ``ssm_scan_ms``."""
    assert reader("ssm_scan_ms").read(run) == pytest.approx((20 + 30) * NS)
    assert reader("ssm_ms").parts(run)["elsewhere"] == pytest.approx(0.0)
    assert reader("ssm_ms").read(run) == pytest.approx(50 * NS)
    assert reader("flash_fwd_ms").read(run) == pytest.approx(10 * NS)
    assert reader("flash_bwd_ms").read(run) == pytest.approx(15 * NS)
    assert reader("flash_ms").read(run) == pytest.approx(25 * NS)
    # least 20 ns (bytes) over XLA's 20 and the kernel's 30: under 100
    capsys.readouterr()
    assert reader("ssm_scan_roofline").read(run) == pytest.approx(40.0)
    assert "bound_by=bytes" in capsys.readouterr().out
    # with XLA's remainder alone, as before PR 34, it would have read 100
    assert scopes.of(run).module_s[f"{MIXER}/{profiling.SSM_SCAN}"] \
        == pytest.approx(20e-9)


def test_a_kernel_under_the_dispatch_is_the_dispatchs(run):
    parts = reader("moe_ms").parts(run)
    assert parts["dispatch"] == pytest.approx((4 + 5) * NS)
    assert parts["combine"] == parts["route"] == 0.0
    assert parts["elsewhere"] == pytest.approx(0.0)
    assert reader("moe_dispatch_ms").read(run) == pytest.approx(9 * NS)


def test_the_compilers_grouped_matmul_is_in_the_experts_once(run):
    """``ragged-dot.1`` has a name and no path, ``gmm.1`` has both: XLA's
    3, then 8 by name and 2 by path, neither twice."""
    j = scopes.of(run)
    assert j.pass_s[profiling.MOE_EXPERTS] == pytest.approx(10e-9)
    assert j.pathless_s(profiling.MOE_EXPERTS) == pytest.approx(8e-9)
    assert reader("moe_experts_ms").read(run) == pytest.approx(
        (3 + 2 + 8) * NS)
    assert reader("moe_ms").read(run) == pytest.approx((9 + 13) * NS)


def test_flash_ms_is_its_two_passes_whatever_else_is_a_kernel(run):
    fwd, bwd = (reader(s).read(run) for s in ("flash_fwd_ms", "flash_bwd_ms"))
    assert reader("flash_ms").read(run) == pytest.approx(fwd + bwd)
    kernels = 1e3 * run.trace.kind_s[trace.KERNEL]
    assert kernels == pytest.approx((30 + 10 + 15 + 8 + 5 + 2 + 1) * NS)
    assert reader("flash_ms").read(run) < kernels
    # the larger of 7 causal products of 2*8*8*8/2 operations and 12
    # arrays of 8*8 bf16 numbers (bytes, at this toy size), over 25 ns
    least = max(7 * 2 * 8 * 8 * 8 * 0.5 / 197e12, 12 * 8 * 8 * 2 / 819e9)
    assert reader("flash_roofline").read(run) == pytest.approx(
        100 * least / 25e-9)
    run.built.flash_calls = []                  # a model with no attention
    assert reader("flash_ms").read(run) is None
    assert reader("flash_roofline").read(run) is None


def test_kernels_by_name_and_by_path_both_sum_to_the_kind(run):
    j, kind = scopes.of(run), run.trace.kind_s[trace.KERNEL]
    assert sum(j.pass_s.values()) == pytest.approx(kind)
    unnamed = j.pass_s["(unnamed)"]             # ssd_fwd, permute, mystery
    assert unnamed == pytest.approx((30 + 5 + 1) * 1e-9)
    assert sum(v for k, v in j.pass_s.items() if k != "(unnamed)") \
        == pytest.approx(kind - unnamed)
    pathless = (8 + 1) * 1e-9                   # ragged-dot, mystery
    assert sum(j.kernel_module_s.values()) == pytest.approx(kind - pathless)
    assert sum(j.kernel_s.values()) == pytest.approx(kind)
    # XLA's operations are by module as before, no kernel among them
    assert sum(j.module_s.values()) == pytest.approx(
        run.trace.kind_s["xla"]) == pytest.approx((20 + 4 + 3) * 1e-9)
    assert set(j.kernel_module_s) == {
        f"{MIXER}/{profiling.SSM_SCAN}", "Transformer/layer_N/attn",
        f"{LAYER}/{profiling.MOE_DISPATCH}", f"{LAYER}/{profiling.MOE_EXPERTS}"}
    assert j.joined_share == pytest.approx(97 / 98)     # less mystery.1
    shown = scopes.describe(j, 1)
    assert '"kernel_module_ms"' in shown and '"flash_pass_ms"' in shown


def test_a_join_made_before_kernels_had_paths_reads_as_it_did():
    """What tests/test_bench_moe.py and test_bench_hybrid.py build: a
    ``Joined`` with ``pass_s`` and no ``kernel_s``."""
    j = scopes.Joined(chips=1, calls=1, phase_s={}, module_s={},
                      pass_s={profiling.MOE_EXPERTS: 4e-9}, buckets={},
                      lead_s=0.0, tail_s=0.0, joined_share=1.0, span_s={})
    assert j.kernel_module_s == {}
    assert j.pathless_s(profiling.MOE_EXPERTS) == 4e-9
    assert j.pathless_s(profiling.FLASH_FWD) == 0.0


def test_run_carries_what_the_readers_need():
    """``run.main`` assigns the two fields; no reader needs a frame."""
    from benchmarks import run as run_py
    fields = {f.name: f.default for f in dataclasses.fields(run_py.Run)}
    assert fields["compiled"] is None and fields["trace_dir"] is None
    given = types.SimpleNamespace(compiled="c", trace_dir="d")

    def elsewhere():                # no ``main`` with a ``trace_dir`` above
        return scopes.harness(given)

    assert elsewhere() == ("c", "d")
    assert scopes.harness(types.SimpleNamespace()) == (None, None)
