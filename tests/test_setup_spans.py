"""Start-up read from inside the program (PR 54): the ``hvd_setup_*`` spans,
the compile ledger ``profiling.listen`` keeps (``hvd_compile_trace`` /
``_lower`` / ``_backend``), the store that outlives the ring's turnover,
what an operator gets of them (``startup_summary()``, ``span_summary()``'s
``after_first_token``) and the benchmark's reader
(``benchmarks/setup_spans.py``, thirteen metrics that move ``setup_s``).
Here, and not under ``benchmarks/tests``, so that the tier-1 run holds them."""

import json
import os
import shutil
import subprocess
import sys
import time
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from horovod_tpu.utils import profiling  # noqa: E402
from horovod_tpu.utils.profiling import Record  # noqa: E402

from benchmarks import setup_spans  # noqa: E402
from benchmarks.run import load_module  # noqa: E402

LAYER = "start-up (basics.init, core/engine.py, jit tracing and compile)"
TRAINING_CELLS = ["dsc1p3b-s2048", "resnet50-imagenet", "dsc1p3b-s16384",
                  "dsc1p3b-dp4", "olmoe-s4096", "granite4hm-s8192"]
SERVING_CELLS = ["dsc1p3b-code-0.8knee", "cmdaplus-code8k-open",
                 "axk1-longdoc16k-open", "evabyte-code32k-open",
                 "ling3f-longdoc32k-open", "zaya1-reason8k-open",
                 "sdar30b-chat4k-open", "xing4-chat4k-open"]
EVERY_CELL = TRAINING_CELLS[:5] + SERVING_CELLS[:1] + TRAINING_CELLS[5:] \
    + SERVING_CELLS[1:]        # the manifest's own order
# metric -> (unit, source, its cells)
NEW = {
    "setup_import_s": ("s", "program_span", EVERY_CELL),
    "setup_init_s": ("s", "program_span", TRAINING_CELLS),
    # read by no cell: ``basics.init`` does not load the native engine and
    # no cell's run does before its window.  The reader and its file are
    # there for a cell that will; the manifest has no entry (PERF.md, section 3)
    "setup_engine_s": ("s", "program_span", []),
    "setup_pool_s": ("s", "program_span", SERVING_CELLS),
    "setup_trace_s": ("s", "program_span", EVERY_CELL),
    "setup_traces": ("count", "program_counter", EVERY_CELL),
    "setup_lower_s": ("s", "program_span", EVERY_CELL),
    "setup_backend_compile_s": ("s", "program_span", EVERY_CELL),
    "setup_cache_retrieval_s": ("s", "program_span", EVERY_CELL),
    "setup_cache_misses": ("count", "program_counter", EVERY_CELL),
    "setup_programs": ("count", "program_counter", EVERY_CELL),
    "setup_warm_s": ("s", "program_span", SERVING_CELLS),
    "setup_unnamed_s": ("s", "program_span", EVERY_CELL),
}


def child(script: str, *argv, timeout=300, **env) -> dict:
    """``script`` in a process of its own; its last line of stdout as JSON."""
    base = {k: v for k, v in os.environ.items() if k != "PYTHONSTARTUP"}
    proc = subprocess.run([sys.executable, "-c", script, *argv], cwd=ROOT,
                          env={**base, "JAX_PLATFORMS": "cpu", **env},
                          capture_output=True, text=True, timeout=timeout)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def compiles_since(t: float) -> list:
    return [r for r in profiling.spans()
            if r.name in profiling.COMPILE_STAGES and r.end > t]


# -- the compile ledger ------------------------------------------------------

def test_a_jit_s_first_call_writes_its_three_stages_and_its_second_none():
    import jax
    import jax.numpy as jnp

    assert profiling.listen() and profiling.listen()    # once, however often

    def ledger_probe(x):
        return jnp.tanh(x) * 3 + x.sum()

    probe = jax.jit(ledger_probe)
    x = jnp.arange(7, dtype=jnp.float32)    # (its own compiles: before t)
    t = time.perf_counter()
    with profiling.span("test_outer", bucket=96) as outer:
        with profiling.span("test_inner") as inner:
            probe(x).block_until_ready()
    after = time.perf_counter()
    own = [r for r in compiles_since(t) if "ledger_probe" in
           r.fields["fun_name"]]
    assert [r.name for r in own] == list(profiling.COMPILE_STAGES)
    trace, lower, backend = own
    assert trace.fields == {"fun_name": "ledger_probe"}
    assert lower.fields == {"fun_name": "jit(ledger_probe)"}
    # ("off" unless an earlier test of this process gave jax a cache)
    assert backend.fields.pop("cache") in ("off", "miss")
    assert backend.fields == {"fun_name": "jit(ledger_probe)"}
    for r in own:
        # on the ring's clock, inside the span that was open, caused by it
        assert t <= r.start < r.end <= after and r.seconds > 0
        assert r.cause == inner.id and r.rid is None
    assert trace.end <= lower.end <= backend.end
    # up the chain of causes: the first bucket on the way
    by_id = {r.id: r for r in profiling.spans()}
    assert by_id[inner.id].cause == outer.id
    assert profiling.under(backend, by_id) == {"bucket": 96}
    # the traces inside it are records of their own, inside its interval
    inner_traces = [r for r in compiles_since(t) if r.name ==
                    profiling.COMPILE_TRACE and r is not trace]
    assert inner_traces and all(trace.start <= r.start and r.end <= trace.end
                                for r in inner_traces)
    t = time.perf_counter()
    probe(x).block_until_ready()
    assert compiles_since(t) == []
    # a compile no span of ours encloses is the caller's own: cause 0
    t = time.perf_counter()
    jax.jit(lambda v: v * 5 - 2)(x).block_until_ready()
    assert {r.cause for r in compiles_since(t)} == {0}


CACHED = """
import json, sys
import jax, jax.numpy as jnp
from horovod_tpu.utils import chip, profiling
jax.config.update("jax_compilation_cache_dir", sys.argv[1])
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
chip.enable_compile_cache()         # registers the ledger
def cached_probe(x):
    return jnp.cos(x) @ x.T
jax.jit(cached_probe)(jnp.ones((8, 8))).block_until_ready()
print(json.dumps([r.fields for r in profiling.spans()
                  if r.name == profiling.COMPILE_BACKEND
                  and "cached_probe" in r.fields["fun_name"]]))
"""


def test_a_second_process_reads_its_program_from_the_cache_and_says_so(
        tmp_path):
    # (enable_compile_cache leaves a directory given from outside alone)
    first, = child(CACHED, str(tmp_path), JAX_COMPILATION_CACHE_DIR=str(
        tmp_path))
    assert first == {"fun_name": "jit(cached_probe)", "cache": "miss"}
    second, = child(CACHED, str(tmp_path), JAX_COMPILATION_CACHE_DIR=str(
        tmp_path))
    assert second["cache"] == "hit" and second["retrieval_s"] > 0
    assert isinstance(second["saved_s"], float)
    assert set(second) == {"fun_name", "cache", "retrieval_s", "saved_s"}


# -- the start-up spans ------------------------------------------------------

INIT = """
import json, sys
import horovod_tpu as hvd
loaded_before = "jax" in sys.modules
from horovod_tpu.utils import profiling
with profiling.span("caller") as caller:
    hvd.init()
hvd.init()
import jax, jax.numpy as jnp
jax.jit(lambda x: x + 1)(jnp.ones(3))
from horovod_tpu.core import engine
with profiling.span("asker") as asker:
    engine.lib()
engine.lib()
print(json.dumps({
    "loaded_before": loaded_before, "caller": caller.id, "asker": asker.id,
    "records": [[r.name, r.id, r.cause, r.start, r.end, r.fields]
                for r in profiling.spans()],
    "summary": profiling.startup_summary()}))
"""


def test_import_init_and_the_engine_s_load_are_spans_and_nest_by_cause():
    got = child(INIT)
    assert got["loaded_before"] is False    # the package's import is lazy
    records = [Record(r[0], r[3], r[4], r[1], r[2], None, r[5])
               for r in got["records"]]
    setup = {name: [r for r in records if r.name == name]
             for name in profiling.SETUP_SPANS}
    # once each: a second init() and a second lib() write nothing
    assert [len(setup[n]) for n in profiling.SETUP_SPANS] == [1, 1, 1, 0, 0]
    imported, = setup[profiling.SETUP_IMPORT]
    init, = setup[profiling.SETUP_INIT]
    engine, = setup[profiling.SETUP_ENGINE]
    assert imported.fields == {"jax_loaded": False} and imported.cause == 0
    assert imported.end <= init.start < init.end <= engine.start
    # who asked: init under its caller, the engine's load under its asker
    # (``init`` itself never loads the engine: the eager API does)
    assert init.cause == got["caller"] and engine.cause == got["asker"]
    assert engine.fields["built"] in (True, False)
    # init registered the ledger: the jit after it left its three stages
    assert {r.name for r in records} >= set(profiling.COMPILE_STAGES)
    said = got["summary"]
    assert said["spans"] == {profiling.SETUP_IMPORT: 1,
                             profiling.SETUP_INIT: 1,
                             profiling.SETUP_ENGINE: 1}
    assert said["init_s"] == pytest.approx(init.seconds)
    assert said["engine_s"] == pytest.approx(engine.seconds)
    assert said["pool_s"] == said["warm_s"] == 0
    assert said["programs"] >= 1 and said["traces"] >= 1
    assert said["named_s"] <= records[-1].end - imported.start


def test_the_first_ask_of_the_backend_is_a_span(monkeypatch):
    import jax

    from horovod_tpu.utils import chip

    t = time.perf_counter()
    with pytest.raises(RuntimeError, match="found none"):
        chip.require_tpu("a test")      # here the backend is the CPU's
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with profiling.span("a_caller") as caller:
        chip.require_tpu("a test")
    asked = [r for r in profiling.spans()
             if r.name == profiling.SETUP_BACKEND and r.start >= t]
    assert [r.cause for r in asked] == [0, caller.id]
    said = profiling.startup_summary(since=t)
    assert said["spans"] == {profiling.SETUP_BACKEND: 2}
    assert said["backend_s"] == pytest.approx(sum(r.seconds for r in asked))


JAX_FREE = """
import json, sys
import horovod_tpu
from horovod_tpu.utils import profiling as p
import horovod_tpu.serving.worker, horovod_tpu.relay
with p.span("a_span", n=1):
    pass
assert p.listen() is False          # no jax here: nothing to listen to
first = [r.name for r in p.spans()]
for k in range(100_000):            # a process that recompiles for ever
    p._on_duration("/jax/core/compile/backend_compile_duration", 1e-4,
                   fun_name=f"jit(f{k})")
held = len(p._startup)
for _ in range(p.SPAN_CAPACITY + 1):
    p.open_span("filler").close()
after = p.spans()
print(json.dumps({
    "jax": "jax" in sys.modules, "first": first, "held": held,
    "bound": p.STARTUP_CAPACITY, "ring": p.SPAN_CAPACITY,
    "after": len(after), "oldest": [after[0].name, after[1].name,
                                    after[1].fields],
    "fillers": sum(r.name == "filler" for r in after),
    "summary_programs": p.startup_summary()["programs"]}))
"""


def test_start_up_records_outlive_the_ring_and_their_store_is_bounded():
    got = child(JAX_FREE)
    # importing the package, profiling, the worker and the relay, writing a
    # span and feeding the ledger by hand: no jax
    assert got["jax"] is False
    assert got["first"] == [profiling.SETUP_IMPORT, "a_span"]
    assert got["bound"] == profiling.STARTUP_CAPACITY == 65536
    # 100 001 start-up records were written: the store stopped at its
    # bound, the rest went through the ring
    assert got["held"] == got["bound"]
    # SPAN_CAPACITY + 1 later spans turned the whole ring over, and the
    # process's first records are still handed out, oldest first
    assert got["fillers"] == got["ring"]
    assert got["after"] == got["bound"] + got["ring"]
    assert got["oldest"] == [profiling.SETUP_IMPORT,
                             profiling.COMPILE_BACKEND,
                             {"fun_name": "jit(f0)", "cache": "off"}]
    assert got["summary_programs"] == got["bound"] - 1


# -- what an operator gets ---------------------------------------------------

@pytest.fixture(scope="module")
def toy_engine():
    import jax

    from horovod_tpu.models.transformer import Transformer, TransformerConfig
    from horovod_tpu.serving.engine import (ServingConfig, ServingEngine,
                                            TransformerBackend)

    cfg = TransformerConfig(vocab_size=512, num_layers=2, num_heads=2,
                            head_dim=32, embed_dim=64, mlp_dim=128,
                            max_seq_len=128)
    model = Transformer(cfg)
    params = jax.jit(model.init)(jax.random.PRNGKey(0),
                                 jax.numpy.zeros((1, 16), jax.numpy.int32))
    began = time.perf_counter()
    eng = ServingEngine(
        TransformerBackend(model, params, cfg, 4, 128),
        ServingConfig(num_slots=4, buckets=(32, 64), max_seq_len=128),
        clock=time.perf_counter)
    eng.submit(list(range(1, 20)), 3)       # warms bucket 32 and the decode
    eng.run_until_idle()
    return eng, began


def test_the_pool_is_a_span_with_its_bytes(toy_engine):
    eng, began = toy_engine
    pool = [r for r in profiling.spans()
            if r.name == profiling.SETUP_POOL and r.start >= began][0]
    # K and V: [layers, slots, positions, heads, head_dim] of bfloat16
    assert pool.fields == {"bytes": 2 * 2 * 4 * 128 * 2 * 32 * 2,
                           "pool_form": "heads"}
    assert pool.fields["bytes"] == eng.backend.kk.nbytes \
        + eng.backend.vv.nbytes
    assert pool.seconds > 0


def test_a_window_on_warmed_shapes_writes_no_compile_record(toy_engine):
    eng, _ = toy_engine
    t = time.perf_counter()
    for n in (12, 21, 30):
        eng.submit(list(range(1, n)), 6)
    eng.run_until_idle()
    assert compiles_since(t) == []


def test_a_recompile_in_service_is_named_with_its_bucket(toy_engine):
    eng, began = toy_engine
    before = eng.span_summary()[profiling.COMPILE_BACKEND][
        "after_first_token"]
    # the engine's own warm-up: the decode program compiled after the first
    # prefill had completed, and nothing of bucket 64 yet (this engine's:
    # the ring is the process's, and a file run before this one in the same
    # worker may have compiled a bucket of 64 after ITS first prefill)
    own = profiling.compiles_after(profiling.spans(), began)["programs"]
    assert {p["fun_name"] for p in before["programs"]} >= {"jit(_decode_fn)"}
    assert {p["fun_name"] for p in own} >= {"jit(_decode_fn)"}
    assert not [p for p in own if p.get("bucket") == 64]
    t = time.perf_counter()
    eng.submit(list(range(1, 50)), 3)       # past the warmed bucket
    eng.run_until_idle()
    row = eng.span_summary()[profiling.COMPILE_BACKEND]
    assert set(row) == {"count", "total_s", "p50_ms", "p95_ms", "max_ms",
                        "after_first_token"}
    after = row["after_first_token"]
    assert after["count"] == before["count"] + 1
    late = after["programs"][-1]
    assert late["fun_name"] == "jit(_prefill_fn)" and late["bucket"] == 64
    assert late["span"] == profiling.SRV_PREFILL
    assert late["cache"] in ("off", "miss", "hit")
    assert late["seconds"] > 0
    # the same compile, as the ledger holds it: under the dispatch leaf of
    # that prefill call
    by_id = {r.id: r for r in profiling.spans()}
    backend, = [r for r in compiles_since(t)
                if r.name == profiling.COMPILE_BACKEND]
    assert by_id[backend.cause].name == profiling.SRV_DISPATCH
    call = by_id[by_id[backend.cause].cause]
    assert call.name == profiling.SRV_PREFILL and call.fields["bucket"] == 64
    # and any process's one call: the serving calls less their compiles
    said = profiling.startup_summary(since=began)
    assert said["pool_s"] > 0 and 0 < said["warm_s"] < said["named_s"]
    assert said["longest"][0]["seconds"] >= said["longest"][-1]["seconds"]
    assert {p["fun_name"] for p in said["longest"]} >= {"jit(_prefill_fn)",
                                                        "jit(_decode_fn)"}


# -- the benchmark's reader, on a hand-made list -----------------------------

def rec(name, start, end, id=0, cause=0, **fields):
    return Record(name, float(start), float(end), id, cause, None, fields)


HAND = [
    rec("hvd_setup_import", 1.0, 1.5, 1, jax_loaded=True),
    rec("hvd_setup_init", 2.0, 4.0, 2),
    # a jit traced inside a jit: 3.0-3.4 lies inside 2.5-3.5, inside init
    rec("hvd_compile_trace", 3.0, 3.4, 3, 2, fun_name="inner"),
    rec("hvd_compile_trace", 2.5, 3.5, 4, 2, fun_name="outer"),
    rec("hvd_compile_lower", 3.5, 3.75, 5, 2, fun_name="jit(outer)"),
    rec("hvd_compile_backend", 3.75, 4.0, 6, 2, fun_name="jit(outer)",
        cache="hit", retrieval_s=0.125, saved_s=9.0),
    rec("hvd_setup_pool", 5.0, 5.5, 7, bytes=1024),
    # a warm-up prefill of 2 s, a second of compile inside its dispatch
    rec("hvd_compile_backend", 6.5, 7.5, 8, 10, fun_name="jit(_prefill_fn)",
        cache="miss"),
    rec("hvd_srv_dispatch", 6.25, 7.75, 10, 11),
    rec("hvd_srv_prefill", 6.0, 8.0, 11, 20, bucket=2048),
    rec("hvd_srv_decode", 8.0, 8.5, 12, 21, slots=1),
    rec("hvd_srv_step", 5.75, 8.75, 21),
    # these end after the opening stamp (10.0): left out
    rec("hvd_srv_decode", 9.75, 10.25, 13, 22, slots=1),
    rec("hvd_compile_backend", 10.5, 11.0, 14, 0, fun_name="jit(late)",
        cache="miss"),
    rec("hvd_compile_trace", 10.25, 10.5, 15, 0, fun_name="late"),
]
WANT = {"setup_import_s": 0.5, "setup_init_s": 2.0, "setup_pool_s": 0.5,
        "setup_trace_s": 1.0, "setup_traces": 2, "setup_lower_s": 0.25,
        "setup_backend_compile_s": 1.25, "setup_cache_retrieval_s": 0.125,
        "setup_cache_misses": 1, "setup_programs": 2,
        # the calls' 2.5 s less the second of compile inside them
        "setup_warm_s": 1.5,
        # named: import 0.5 + init 2 + pool 0.5 + the calls 2.5; the traces,
        # the lowering and both compiles lie inside init and the prefill
        "setup_unnamed_s": 10.0 - 5.5}


@pytest.mark.parametrize("name", sorted(WANT))
def test_the_parts_of_a_hand_made_start(name):
    got = setup_spans.parts(HAND, 0.0, 10.0)
    assert got[name] == pytest.approx(WANT[name])
    assert type(got[name]) is type(WANT[name])


def test_the_parts_and_the_unnamed_rest_sum_to_setup_s(capsys):
    got = setup_spans.parts(HAND, 0.0, 10.0)
    assert "setup_engine_s" not in got      # no such span: not read, not 0
    line = got["line"]
    assert line["setup_s"] == 10.0 and line["named_s"] == 5.5
    assert line["named_s"] + line["setup_unnamed_s"] == line["setup_s"]
    assert line["spans"] == {"hvd_setup_import": 1, "hvd_setup_init": 1,
                             "hvd_setup_pool": 1, "hvd_srv_prefill": 1,
                             "hvd_srv_decode": 1}
    # the longest first, each with who caused it
    assert line["longest_backend"] == [
        {"fun_name": "jit(_prefill_fn)", "cache": "miss", "seconds": 1.0,
         "bucket": 2048, "span": "hvd_srv_prefill"},
        {"fun_name": "jit(outer)", "cache": "hit", "seconds": 0.25,
         "span": "hvd_setup_init"}]
    # what no record covers, the longest stretches first, each between the
    # records it lies between: here all five, so they sum to the unnamed rest
    gaps = line["longest_unnamed"]
    assert sum(g["seconds"] for g in gaps) == line["setup_unnamed_s"] == 4.5
    assert gaps[0] == {"seconds": 1.5, "at_s": 8.5, "after": "hvd_srv_decode",
                       "before": None}     # the window opens
    assert gaps[1]["after"] == "hvd_compile_backend jit(outer)"
    assert gaps[1]["before"] == "hvd_setup_pool"
    assert gaps[2] == {"seconds": 1.0, "at_s": 0.0, "after": None,
                       "before": "hvd_setup_import"}    # the process starts
    # the engine's load, where a run has one (no cell's has): read like init
    loaded = setup_spans.parts(
        HAND + [rec("hvd_setup_engine", 4.0, 4.5, 30, built=True)], 0.0, 10.0)
    assert loaded["setup_engine_s"] == 0.5
    assert loaded["setup_unnamed_s"] == got["setup_unnamed_s"] - 0.5
    # a start cut at 2.75: the init span and the outer trace count from there
    cut = setup_spans.parts(HAND, 2.75, 10.0)
    assert "setup_import_s" not in cut
    assert cut["setup_init_s"] == 1.25 and cut["setup_trace_s"] == 0.75
    # through a run, once: the line is printed by a traced run's first reading
    run = types.SimpleNamespace(stamps=[10.0, 11.0], setup_s=10.0,
                                trace_dir="somewhere")
    import unittest.mock
    with unittest.mock.patch.object(profiling, "spans", lambda: list(HAND)):
        assert setup_spans.metric(run, "setup_traces") == 2
    with unittest.mock.patch.object(profiling, "spans", lambda: []):
        assert setup_spans.metric(run, "setup_programs") == 2   # read once
    said = [ln for ln in capsys.readouterr().out.splitlines()
            if ln.startswith("setup: ")]
    assert len(said) == 1 and json.loads(said[0][7:])["named_s"] == 5.5
    served = types.SimpleNamespace(open_t=10.0, setup_s=10.0, trace_dir=None)
    assert setup_spans.opening_stamp(served) == 10.0


def test_a_program_without_the_ledger_reads_as_nothing(monkeypatch):
    run = types.SimpleNamespace(stamps=[10.0, 11.0], setup_s=10.0,
                                trace_dir=None)
    monkeypatch.delattr(profiling, "startup_summary")   # the parent's file
    for name in NEW:
        assert load_module("metrics", name).read(run) is None
    monkeypatch.undo()
    # and a program that has it and wrote nothing before the window
    empty = types.SimpleNamespace(stamps=[10.0, 11.0], setup_s=10.0,
                                  trace_dir=None)
    monkeypatch.setattr(profiling, "spans", lambda: HAND[-2:])
    assert setup_spans.of(empty) is None


# -- through the harness -----------------------------------------------------

def manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module", params=["tiny-lm-1", "tiny-serve-1"])
def rehearsed(request, tmp_path_factory):
    """One ``--trace 1`` walk of a toy cell through ``benchmarks/run.py`` on
    the CPU, as ``benchmarks/tests/test_discovery.py`` drives one, with the
    ``setup_*`` entries of a cell of its kind."""
    cell = request.param
    like = SERVING_CELLS[0] if "serve" in cell else TRAINING_CELLS[0]
    base = tmp_path_factory.mktemp("setup") / "manifest"
    shutil.copytree(os.path.join(ROOT, "benchmarks", "tests", "rehearsal"),
                    base)
    with open(base / "BENCHMARK.json") as f:
        m = json.load(f)
    real = manifest()
    m["end_to_end"] = [e for e in real["end_to_end"] if e["name"] == "setup_s"]
    m["per_layer"] = [{k: v for k, v in e.items() if k != "workloads"}
                      for e in real["per_layer"]
                      if e["name"] in NEW and like in e["workloads"]]
    with open(base / "BENCHMARK.json", "w") as f:
        json.dump(m, f)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=1")
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
         "--manifest", str(base / "BENCHMARK.json"), "--workload", cell,
         "--seed", str(2**31 + 54), "--seconds", "2", "--trace", "1",
         "--out", str(base / "out"), "--rehearse-on-cpu"],
        env=env, cwd=ROOT, capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = proc.stdout.strip().splitlines()
    marker = "REHEARSAL on cpu, no result: "
    assert lines[-1].startswith(marker), lines[-1]
    return cell, json.loads(lines[-1][len(marker):]), lines


def test_a_toy_cell_s_setup_metrics_are_on_its_result_line(rehearsed):
    cell, result, lines = rehearsed
    assert result["correct"]
    served = "serve" in cell
    want = {n for n, (_, _, cells) in NEW.items()
            if (SERVING_CELLS[0] if served else TRAINING_CELLS[0]) in cells}
    got = {k: v["value"] for k, v in result["metrics"].items()}
    assert set(got) == want
    assert all(result["metrics"][n]["unit"] == NEW[n][0] for n in want)
    assert all(v >= 0 for v in got.values())
    assert got["setup_trace_s"] > 0 and got["setup_backend_compile_s"] > 0
    assert got["setup_traces"] > got["setup_programs"] >= 2
    assert got["setup_cache_misses"] <= got["setup_programs"]
    if served:
        assert got["setup_pool_s"] > 0 and got["setup_warm_s"] > 0
    # one line more, once, before the result line
    said = [k for k, ln in enumerate(lines) if ln.startswith("setup: ")]
    assert len(said) == 1 and said[0] < len(lines) - 1
    line = json.loads(lines[said[0]][len("setup: "):])
    assert line["named_s"] + line["setup_unnamed_s"] == pytest.approx(
        line["setup_s"], abs=0.01)
    assert 0 < line["named_s"] < line["setup_s"]
    assert {k: line[k] for k in want} == pytest.approx(got, abs=1e-3)
    longest = line["longest_backend"]
    assert 1 <= len(longest) <= 5 and all(
        set(p) >= {"fun_name", "cache", "seconds"} for p in longest)
    if served:      # a program compiled under a warm-up call says which
        assert any(p.get("span") == profiling.SRV_PREFILL and "bucket" in p
                   for p in longest)
    else:
        assert any(p["fun_name"] == "jit(train_step)" for p in longest)
        assert line["spans"] == {"hvd_setup_import": 1, "hvd_setup_init": 1}


@pytest.mark.parametrize("name", sorted(NEW))
def test_the_manifest_s_entry_comes_after_the_parent_s_and_lists_its_cells(
        name):
    m = manifest()
    names = [e["name"] for e in m["per_layer"]]
    parents_last = names.index("moe_experts_touched_share.srv")
    unit, source, cells = NEW[name]
    assert os.path.exists(os.path.join(ROOT, "benchmarks", "metrics",
                                       name + ".py"))
    assert name in setup_spans.PARTS or name == "setup_unnamed_s"
    if not cells:
        assert name not in names
        return
    entry = m["per_layer"][names.index(name)]
    assert names.index(name) > parents_last
    assert entry == {"name": name, "unit": unit, "better": "lower",
                     "source": source, "layer": LAYER, "moves": "setup_s",
                     "workloads": cells}
    assert set(cells) <= {w["name"] for w in m["workloads"]}


def test_every_cell_has_a_part_of_its_setup_and_setup_s_has_its_parts():
    m = manifest()
    moved = [e for e in m["per_layer"] if e["moves"] == "setup_s"]
    assert [e["name"] for e in moved] == [
        n for n, (_, _, cells) in NEW.items() if cells]     # the table's order
    assert [w["name"] for w in m["workloads"]] == EVERY_CELL
    # (the parent's 112 and these 12; later PRs append theirs)
    assert 112 + len(moved) == 124 <= len(m["per_layer"])
