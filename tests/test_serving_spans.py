"""The serving path's own spans (PR 39): ``profiling.span`` and its bounded
ring, where ``ServingEngine`` and the model backends write them, the
engine's bounded statistics, and the benchmark's readers of them
(``benchmarks/serve_spans.py``, nine ``program_span`` metrics) on a toy run
through ``benchmarks/serving.py``.  Here, and not under ``benchmarks/tests``,
so that the tier-1 run holds them."""

import json
import os
import statistics
import subprocess
import sys
import time
import types

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from horovod_tpu.serving.engine import (  # noqa: E402
    PagedTransformerBackend, ServingConfig, ServingEngine, StubBackend,
    TransformerBackend)
from horovod_tpu.utils import profiling  # noqa: E402

from benchmarks import serve_spans  # noqa: E402
from benchmarks.run import load_module  # noqa: E402

NEW = {"decode_h2d_ms.srv": "serving backend", "decode_dispatch_ms.srv":
       "serving backend", "decode_wait_ms.srv": "serving backend",
       "decode_fetch_ms.srv": "serving backend", "sched_self_ms.srv":
       "scheduler", "engine_queue_ms_p95.srv": "scheduler",
       "longest_wait_ms.srv": "serving backend", "longest_host_ms.srv":
       "serving backend", "idle_named_share.srv": "device"}
SERVING_CELLS = ["dsc1p3b-code-0.8knee", "cmdaplus-code8k-open",
                 "axk1-longdoc16k-open", "evabyte-code32k-open",
                 "ling3f-longdoc32k-open", "zaya1-reason8k-open",
                 "sdar30b-chat4k-open", "xing4-chat4k-open"]


# the start-up spans and the compile ledger (PR 54) are kept in a store of
# their own beside the ring and handed out with it: tests/test_setup_spans.py
STARTUP = set(profiling.SETUP_SPANS + profiling.COMPILE_STAGES)


def in_the_ring(records: list) -> list:
    return [r for r in records if r.name not in STARTUP]


def since(mark: int) -> list:
    return [r for r in profiling.spans() if r.id > mark]


def mark() -> int:
    return profiling.open_span("mark").id


def stub_engine(**kw) -> ServingEngine:
    return ServingEngine(
        StubBackend(4, **kw),
        ServingConfig(num_slots=4, buckets=(16,), max_seq_len=64))


# -- the mechanism, and the engine's side -------------------------------------

JAX_FREE = """
import json, sys
import horovod_tpu.serving as serving
from horovod_tpu.utils import profiling as p
eng = serving.ServingEngine(serving.StubBackend(2), serving.ServingConfig(
    num_slots=2, buckets=(16,), max_seq_len=64))
reqs = [eng.submit([1, 2, 3 + i], 3) for i in range(5)]
too_long = eng.submit(list(range(40)), 3)
eng.run_until_idle()
summary = eng.span_summary()
print(json.dumps({
    "jax": "jax" in sys.modules,
    "records": [[r.name, r.id, r.cause, r.rid, r.start, r.end, r.fields]
                for r in p.spans()],
    "rids": [r.rid for r in reqs], "rejected": too_long.rid,
    "summary": summary}))
"""


@pytest.fixture(scope="module")
def jax_free():
    env = {k: v for k, v in os.environ.items() if k != "PYTHONSTARTUP"}
    proc = subprocess.run([sys.executable, "-c", JAX_FREE], cwd=ROOT, env=env,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr[-2000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_a_stub_fleet_s_process_never_imports_jax(jax_free):
    assert jax_free["jax"] is False
    # (and the package's own import, the one start-up span of such a process)
    assert {"hvd_srv_request", "hvd_srv_queued", "hvd_srv_step",
            "hvd_srv_prefill", "hvd_srv_decode", "hvd_setup_import"} \
        == set(jax_free["summary"])
    row = jax_free["summary"]["hvd_srv_decode"]
    assert set(row) == {"count", "total_s", "p50_ms", "p95_ms", "max_ms"}
    assert row["count"] > 0 and row["p50_ms"] <= row["p95_ms"] <= row["max_ms"]


def test_a_request_s_records_share_its_rid_and_nest_by_cause(jax_free):
    records = [dict(zip(("name", "id", "cause", "rid", "start", "end",
                         "fields"), r)) for r in jax_free["records"]]
    for rid in jax_free["rids"]:
        own = {r["name"]: r for r in records if r["rid"] == rid}
        assert set(own) == {"hvd_srv_request", "hvd_srv_queued",
                            "hvd_srv_prefill"}
        request, queued, prefill = (own["hvd_srv_request"],
                                    own["hvd_srv_queued"],
                                    own["hvd_srv_prefill"])
        assert request["cause"] == 0
        assert queued["cause"] == prefill["cause"] == request["id"]
        assert queued["start"] == request["start"]
        assert queued["end"] == prefill["start"]
        assert request["start"] <= prefill["start"] < prefill["end"] \
            <= request["end"]
        assert request["fields"] == {"prompt": 3, "finish": "max_new_tokens",
                                     "tokens": 3}
        assert prefill["fields"] == {"bucket": 16, "length": 3, "prompt": 3,
                                     "hit": 0}
    refused = [r for r in records if r["rid"] == jax_free["rejected"]]
    assert [r["name"] for r in refused] == ["hvd_srv_request"]
    assert refused[0]["fields"]["finish"] == "rejected"
    # two slots: the rids a decode call names are the requests in them
    decode = [r for r in records if r["name"] == "hvd_srv_decode"]
    assert all(r["fields"]["slots"] == len(r["fields"]["rids"]) <= 2
               for r in decode)
    steps = {r["id"] for r in records if r["name"] == "hvd_srv_step"}
    assert all(r["cause"] in steps for r in decode)


def test_a_step_s_self_time_is_its_duration_less_its_children():
    eng = stub_engine(step_s=0.004, prefill_s_per_token=0.001)
    m = mark()
    for i in range(3):
        eng.submit([5, 6, 7, 8 + i], 4)
    eng.run_until_idle()
    records = since(m)
    steps = [r for r in records if r.name == profiling.SRV_STEP]
    calls = [r for r in records if r.name in profiling.SRV_CALLS]
    assert len(steps) == 3 and len(calls) == 3 + 3
    first = steps[0]            # three prefills, then a decode
    inside = [c for c in calls if first.start <= c.start <= first.end]
    assert [c.name for c in inside] == [profiling.SRV_PREFILL] * 3 + [
        profiling.SRV_DECODE]
    own = serve_spans.self_seconds(first, calls)
    assert own == pytest.approx(
        first.seconds - sum(c.seconds for c in inside))
    # the sleeps are the backend's: 3 x 4 ms of prefill and a 4 ms step
    assert sum(c.seconds for c in inside) >= 0.016
    assert 0 < own < first.seconds / 2
    for s in steps[1:]:
        assert 0 < serve_spans.self_seconds(s, calls) < s.seconds


def test_the_ring_holds_its_capacity_and_no_more():
    k = 10
    for _ in range(profiling.SPAN_CAPACITY + k):
        profiling.open_span("filler").close()
    ring = in_the_ring(profiling.spans())
    assert len(ring) == profiling.SPAN_CAPACITY >= 65536
    assert [r.id for r in ring] == list(range(ring[0].id,
                                              ring[0].id + len(ring)))
    assert ring[-1].name == "filler"


def test_every_list_the_engine_holds_is_bounded():
    """200 000 stub tokens of 100 000 requests: more first tokens, more
    later tokens and more spans than the capacity."""
    eng = ServingEngine(StubBackend(8), ServingConfig(
        num_slots=8, buckets=(16,), max_seq_len=64))
    finished = 0
    for i in range(100_000):
        eng.submit([1, 2, 3], 2)
        if i % 8 == 7:
            finished += len(eng.step())
    finished += len(eng.run_until_idle())
    assert finished == 100_000 and eng.counters["tokens"] == 200_000
    cap = profiling.SPAN_CAPACITY
    assert len(eng._ttft_s) == len(eng._token_s) == cap
    assert len(in_the_ring(profiling.spans())) == cap
    assert not eng.queue and not eng._undelivered
    # what the ring keeps, the garbage collector need not walk: a full
    # ring of tracked objects lengthens every full collection, a pause of
    # the serving loop
    import gc

    # twice: a pass untracks a tuple once it has found its items untracked,
    # so a record that holds its fields as an inner tuple may need the second
    # (which of the two a pass meets first depends on what else the worker's
    # process holds: the one pass failed in two whole runs of three, PR 42)
    gc.collect()
    gc.collect()
    assert not any(gc.is_tracked(kept) for kept in profiling._ring)
    stats = eng.stats()
    assert stats["completed"] == 100_000 and stats["ttft_p99_ms"] > 0
    assert set(eng.span_summary()) - STARTUP == {
        "hvd_srv_request", "hvd_srv_queued", "hvd_srv_step",
        "hvd_srv_prefill", "hvd_srv_decode"}


def test_stats_on_a_short_run_are_what_sorting_every_latency_gave():
    ticks = iter(np.cumsum(np.random.default_rng(5).exponential(0.01, 4000)))
    eng = ServingEngine(
        StubBackend(4), ServingConfig(num_slots=4, buckets=(16,),
                                      max_seq_len=64),
        clock=lambda: float(next(ticks)))
    done = []
    for i in range(40):
        eng.submit([1, 2, 3 + i % 5], 2 + i % 6)
        done += eng.step()
    done += eng.run_until_idle()
    assert len(done) == 40

    def nearest_rank(xs, q):    # the parent's _pctile, on every latency
        xs = sorted(xs)
        return xs[min(len(xs) - 1, int(q / 100.0 * len(xs)))] * 1e3

    ttft = [r.ttft_s for r in done]
    gaps = [g for r in done for g in r.token_lat_s]
    stats = eng.stats()
    assert stats["ttft_p50_ms"] == nearest_rank(ttft, 50)
    assert stats["ttft_p99_ms"] == nearest_rank(ttft, 99)
    assert stats["token_p50_ms"] == nearest_rank(gaps, 50)
    assert stats["token_p99_ms"] == nearest_rank(gaps, 99)
    from horovod_tpu.serving.engine import _STATS_KEYS
    assert set(stats) == set(_STATS_KEYS)


# -- the model backends' four leaves ------------------------------------------

@pytest.fixture(scope="module")
def toy_model():
    import jax

    from horovod_tpu.models.transformer import Transformer, TransformerConfig

    cfg = TransformerConfig(vocab_size=8192, num_layers=4, num_heads=4,
                            head_dim=64, embed_dim=256, mlp_dim=1024,
                            max_seq_len=256)
    model = Transformer(cfg)
    params = jax.jit(model.init)(jax.random.PRNGKey(0),
                                 jax.numpy.zeros((1, 16), jax.numpy.int32))
    return model, params, cfg


@pytest.mark.parametrize("recorded", [True, False])
@pytest.mark.parametrize("kind", ["dense", "paged"])
def test_a_backend_call_is_four_leaves_in_order(toy_model, kind, recorded):
    """``recorded``: a backend call's ``hvd_srv_fetch`` carries no byte of
    the logits, which stay on the device; under
    ``ServingConfig.record_logits`` the engine's own fetch of them (every
    slot's, a decode step) is a fifth span after the four (PR 52)."""
    from benchmarks.serving import Timed

    model, params, cfg = toy_model
    backend = TransformerBackend(model, params, cfg, 8, 256) \
        if kind == "dense" else PagedTransformerBackend(
            model, params, cfg, 8, 256, cache_pages=8, page_size=16)
    timed = Timed(backend)      # as a cell wraps it: the leaves' cause is
    eng = ServingEngine(        # still the engine's span around the call
        timed if kind == "dense" else backend,
        ServingConfig(num_slots=8, buckets=(32, 64), max_seq_len=256,
                      record_logits=recorded),
        clock=time.perf_counter)
    for i in range(10):         # compiles both buckets and the decode step
        eng.submit(list(range(1, 20 + 4 * i)), 3)
    eng.run_until_idle()
    m = mark()
    del timed.log[:]
    for i in range(16):
        eng.submit(list(range(1, 20 + 4 * (i % 10))), 8)
    eng.run_until_idle()
    records = since(m)
    for name, expected in ((profiling.SRV_PREFILL, 16),
                           (profiling.SRV_DECODE, None)):
        calls = [r for r in records if r.name == name]
        assert len(calls) == expected or expected is None and calls
        covered = []
        for call in calls:
            leaves = [r for r in records if r.cause == call.id]
            assert [r.name for r in leaves] == list(profiling.SRV_LEAVES) \
                + [profiling.SRV_FETCH] * recorded
            edges = [call.start] + [t for r in leaves
                                    for t in (r.start, r.end)] + [call.end]
            assert edges == sorted(edges)
            # a dense model counts no pairs: the logits are all a fetch holds
            assert [r.fields["bytes"] > 0 for r in leaves[3:]] \
                == [False] + [True] * recorded
            covered.append(sum(r.seconds for r in leaves) / call.seconds)
        assert statistics.median(covered) >= 0.97
    if kind == "dense":
        # what Timed logs from outside, the program's spans say themselves
        decodes = [r for r in records if r.name == profiling.SRV_DECODE]
        logged = [e for e in timed.log if e[0] == "decode"]
        assert [(r.fields["slots"], r.fields["live_tokens"])
                for r in decodes] == [(e[3], e[4]) for e in logged]
        prefills = [r for r in records if r.name == profiling.SRV_PREFILL]
        assert [(r.fields["bucket"], r.fields["length"])
                for r in prefills] == [(e[3], e[4]) for e in timed.log
                                       if e[0] == "prefill"]
        fetched = [r.fields["bytes"] for r in records
                  if r.name == profiling.SRV_FETCH and r.cause in
                  {d.id for d in decodes}]
        # the logits, every slot's; the tokens came with the wait
        assert set(fetched) == ({0, 8 * 8192 * 4} if recorded else {0})


def test_the_profiler_s_host_plane_carries_the_same_names(toy_model, tmp_path):
    from benchmarks import trace

    model, params, cfg = toy_model
    eng = ServingEngine(
        TransformerBackend(model, params, cfg, 8, 256),
        ServingConfig(num_slots=8, buckets=(32, 64), max_seq_len=256),
        clock=time.perf_counter)
    eng.submit(list(range(1, 30)), 3)
    eng.run_until_idle()
    with profiling.trace(str(tmp_path)):
        for i in range(3):
            eng.submit(list(range(1, 30 + i)), 4)
        eng.run_until_idle()
    planes = trace.load(str(tmp_path), keep_stats=True)
    host = serve_spans.host_spans(planes)
    assert set(host) == {profiling.SRV_STEP, profiling.SRV_PREFILL,
                         profiling.SRV_DECODE, *profiling.SRV_LEAVES}
    assert len(host[profiling.SRV_PREFILL]) == 3
    assert len(host[profiling.SRV_WAIT]) == 3 + len(host[profiling.SRV_DECODE])
    # nested on the profiler's clock as in the ring: every leaf in a call
    calls = host[profiling.SRV_PREFILL] + host[profiling.SRV_DECODE]
    for a, b in host[profiling.SRV_FETCH]:
        assert any(lo <= a and b <= hi for lo, hi in calls)
    # the counts of the boundary ride along as the event's stats
    stats = [e[3] for p in planes for line in p["lines"]
             for e in line["events"] if e[0] == profiling.SRV_PREFILL]
    assert all(s["bucket"] == 32 and "rid" in s for s in stats)
    # no TPU plane in a CPU trace: no idle seconds are made up
    assert serve_spans.idle_by_span(planes) is None


# -- the benchmark's readers --------------------------------------------------

def test_idle_seconds_go_to_the_innermost_span_that_covers_them():
    """A hand-made trace: one step of 100 us holding a decode call of 80
    with its four leaves; the device is busy for 30 us inside the wait."""
    us = 1e3
    host = [["hvd_srv_step", 0 * us, 100 * us, {}],
            ["hvd_srv_decode", 10 * us, 80 * us, {}],
            ["hvd_srv_h2d", 10 * us, 10 * us, {}],
            ["hvd_srv_dispatch", 22 * us, 8 * us, {}],
            ["hvd_srv_wait", 30 * us, 40 * us, {}],
            ["hvd_srv_fetch", 70 * us, 20 * us, {}],
            ["engine_step", 0, 100 * us, {}]]
    device = {"name": "/device:TPU:0", "lines": [
        {"name": "XLA Modules", "events": [
            ["jit__decode_fn(1)", -50 * us, 10 * us, {}],
            ["jit__decode_fn(1)", 35 * us, 30 * us, {}],
            ["jit__decode_fn(1)", 120 * us, 10 * us, {}]]},
        {"name": "XLA Ops", "events": [
            ["fusion.1", -50 * us, 10 * us, {}],
            ["fusion.1", 35 * us, 30 * us, {}],
            ["fusion.1", 120 * us, 10 * us, {}]]}]}
    planes = [device, {"name": "/host:CPU", "lines": [
        {"name": "python3", "events": host}]}]
    idle = serve_spans.idle_by_span(planes)
    want = {"hvd_srv_h2d": 10, "hvd_srv_dispatch": 8, "hvd_srv_wait": 10,
            "hvd_srv_fetch": 20, "hvd_srv_decode": 2, "hvd_srv_step": 20,
            serve_spans.OUTSIDE: 40 + 20}
    assert {k: round(v * 1e6, 6) for k, v in idle.items()} == want
    assert serve_spans.intersect([(0, 5), (7, 9)], [(3, 8)]) == [(3, 5),
                                                                  (7, 8)]


def test_a_program_without_the_spans_reads_as_nothing(monkeypatch):
    run = types.SimpleNamespace(records=[], trace_dir=None, open_t=0.0,
                                close_t=1.0, end_t=1.0, counted=[])
    monkeypatch.delattr(profiling, "spans")     # the parent's profiling.py
    for name in NEW:
        assert load_module("metrics", name.split(".")[0]).read(run) is None
    training = types.SimpleNamespace(trace=None)
    assert serve_spans.of(training) is None


TINY = {"family": "decoder_serve", "hidden_size": 384, "intermediate_size":
        1536, "num_attention_heads": 4, "num_key_value_heads": 4,
        "num_hidden_layers": 6, "vocab_size": 8192, "rms_norm_eps": 1e-6,
        "rope_theta": 10000.0, "initializer_range": 0.05,
        "tie_word_embeddings": False}
TRAFFIC = {"why": "rehearsal", "unit": "tokens", "rate": 4.0,
           "lead_in_s": 0.5, "drain_s": 20, "num_slots": 4,
           "max_seq_len": 128, "prefill_buckets": [32, 64],
           "arrivals": {"kind": "poisson_lognormal", "schedule_seed": 7,
                        "prompt_tokens": {"median": 24, "sigma": 0.6,
                                          "min": 8, "max": 64},
                        "output_tokens": {"median": 8, "sigma": 0.5,
                                          "min": 3, "max": 16}},
           "stream": {"kind": "markov_zipf_tokens", "zipf_a": 1.1,
                      "follow_prob": 0.5, "max_run": 8},
           "ttft_limit_ms": 5000.0, "tpot_limit_ms": 2000.0,
           "compare_requests": 3}


def manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def toy_run(tmp_path_factory):
    """One ``--trace 1`` walk of a tiny serving cell through
    ``benchmarks/serving.py`` on the CPU, as tests/test_bench_cohere2.py
    drives one, with every metric ``dsc1p3b-code-0.8knee`` reports."""
    base = tmp_path_factory.mktemp("spans")
    (base / "configs").mkdir()
    (base / "traffic").mkdir()
    (base / "configs" / "tiny-serve.json").write_text(json.dumps(TINY))
    (base / "traffic" / "tiny-open.json").write_text(json.dumps(TRAFFIC))
    real = manifest()
    m = {"command": real["command"], "paths": ["."], "run_seconds": 3,
         "configs": [{"name": "tiny-serve", "source": "toy", "reduced": [],
                      "file": "configs/tiny-serve.json", "why": "rehearsal"}],
         "workloads": [{"name": "tiny-serve-1", "config": "tiny-serve",
                        "traffic": "tiny-open", "chips": 1,
                        "why": "rehearsal"}],
         **{g: [{k: v for k, v in e.items() if k != "workloads"}
                for e in real[g] if "workloads" not in e
                or SERVING_CELLS[0] in e["workloads"]]
            for g in ("end_to_end", "per_layer")}}
    (base / "BENCHMARK.json").write_text(json.dumps(m))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=1")
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
         "--manifest", str(base / "BENCHMARK.json"), "--workload",
         "tiny-serve-1", "--seed", str(2**31 + 39), "--seconds", "3",
         "--trace", "1", "--out", str(base / "out"), "--rehearse-on-cpu"],
        env=env, cwd=ROOT, capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = proc.stdout.strip().splitlines()
    marker = "REHEARSAL on cpu, no result: "
    assert lines[-1].startswith(marker), lines[-1]
    return json.loads(lines[-1][len(marker):]), lines


@pytest.mark.parametrize("name", sorted(NEW))
def test_each_new_metric_reads_a_number_off_a_toy_run(toy_run, name):
    result, _ = toy_run
    assert result["correct"]
    if name == "idle_named_share.srv":
        # a share of the device's idle seconds: never from a CPU trace
        assert name not in result["metrics"]
        return
    got = result["metrics"][name]
    assert got["unit"] == "ms" and 0 < got["value"] < 5000


def timed_window(toy_model):
    """A window of decode calls on the toy model as a cell's run holds one:
    ``Timed``'s log beside the ring, both on ``time.perf_counter``."""
    from benchmarks import serving

    model, params, cfg = toy_model
    timed = serving.Timed(TransformerBackend(model, params, cfg, 8, 256))
    eng = ServingEngine(
        timed, ServingConfig(num_slots=8, buckets=(32, 64), max_seq_len=256),
        clock=time.perf_counter)
    for i in range(10):         # compiles both buckets and the decode step
        eng.submit(list(range(1, 20 + 4 * i)), 3)
    eng.run_until_idle()
    del timed.log[:]
    open_t = serving.clock()
    for i in range(16):
        eng.submit(list(range(1, 20 + 4 * (i % 10))), 12)
    eng.run_until_idle()
    close_t = serving.clock()
    return serving.ServeRun(
        cell={}, config={}, traffic={}, built=None, chips=1, peaks=None,
        setup_s=0.0, compiles_in_window=0, memory=None, open_t=open_t,
        close_t=close_t, end_t=close_t, records=[], steps=list(timed.log))


def test_the_four_decode_leaves_sum_to_the_decode_step(toy_run, toy_model):
    # The 3% is held where one clock makes it hold whatever the host's
    # speed: in this process, call by call.  A decode call's four leaves lie
    # inside ``Timed``'s stamp of it, which lies inside the engine's span of
    # it, so leaves <= stamp <= span for EVERY call; and the leaves' means
    # sum to the mean of the spans but for the statements between them (a
    # sum of means is linear: a host given the CPU by turns stretches both
    # sides by the same seconds, where the medians of five different lists,
    # which is what the metrics are, part by more than 3% under load).
    run = timed_window(toy_model)
    ring = profiling.spans()
    spans = [r for r in ring if r.name == profiling.SRV_DECODE
             and run.open_t <= r.start < run.close_t]
    stamps = [e[2] - e[1] for e in run.steps_in_window("decode")]
    assert len(spans) == len(stamps) > 20
    children: dict[int, list] = {}
    for r in ring:
        children.setdefault(r.cause, []).append(r)
    took = {name: [] for name in profiling.SRV_LEAVES}
    for span, stamp in zip(spans, stamps):
        leaves = children[span.id]
        assert [r.name for r in leaves] == list(profiling.SRV_LEAVES)
        assert sum(r.seconds for r in leaves) <= stamp <= span.seconds
        for r in leaves:
            took[r.name].append(r.seconds)
    assert sum(statistics.fmean(v) for v in took.values()) == pytest.approx(
        statistics.fmean(stamps), rel=0.03)
    # each ``decode_*_ms.srv`` is the median of its own leaf over these
    # calls, and ``decode_step_ms.srv`` that of the stamps: no leaf read
    # under another's name, missing, or counted twice
    read = lambda stem: load_module("metrics", stem).read(run)  # noqa: E731
    for name, seconds in took.items():
        assert read(f"decode_{serve_spans.short(name)}_ms") == pytest.approx(
            1e3 * statistics.median(seconds), rel=1e-9)
    assert read("decode_step_ms") == pytest.approx(
        1e3 * statistics.median(stamps), rel=1e-9)

    result, lines = toy_run
    m = {k: v["value"] for k, v in result["metrics"].items()}
    assert m["decode_wait_ms.srv"] > m["decode_fetch_ms.srv"]
    # the engine's queue is inside the harness's: due time -> prefill start
    assert m["engine_queue_ms_p95.srv"] <= m["queue_ms_p95.srv"]
    assert m["longest_wait_ms.srv"] >= m["decode_wait_ms.srv"]
    # one line more, once, before the result line
    hosts = [k for k, ln in enumerate(lines) if ln.startswith("serve_host: ")]
    assert len(hosts) == 1 and hosts[0] < len(lines) - 1
    said = json.loads(lines[hosts[0]][len("serve_host: "):])
    # every decode call the harness timed in the window is a span of the
    # program's there (a call that straddles an edge may fall to one side:
    # the span opens outside ``Timed``'s stamp).  How MANY there are is the
    # host's speed and no property of the spans: a loaded host batches the
    # same requests into fewer steps
    window = next(ln for ln in lines if ln.startswith("window: "))
    timed = int(window.split(" decode_steps=")[1].split()[0])
    assert said["ring_whole"] and timed > 0
    assert abs(said["decode_calls"] - timed) <= 2
    assert set(said["decode_leaf_mean_ms"]) == set(
        said["prefill_leaf_mean_ms"]) == {"h2d", "dispatch", "wait", "fetch"}
    assert said["traced_decode_leaf_mean_ms"]["wait"] > 0
    assert "idle_s_by_span" not in said     # a CPU trace names no idle


def test_the_manifest_s_new_entries_come_after_the_parent_s():
    m = manifest()
    names = [e["name"] for e in m["per_layer"]]
    parents_last = names.index("moe_held_pair_share.srv")
    layers = {e["layer"] for e in m["per_layer"] if e["name"] not in NEW}
    for name, layer in NEW.items():
        entry = m["per_layer"][names.index(name)]
        assert names.index(name) > parents_last
        assert entry["source"] == "program_span"
        assert entry["moves"] == "ttft_ms_mean"
        assert entry["workloads"] == SERVING_CELLS
        assert entry["layer"].startswith(layer) and entry["layer"] in layers
        assert entry["unit"] == ("%" if name.startswith("idle") else "ms")
        assert os.path.exists(os.path.join(
            ROOT, "benchmarks", "metrics", name.split(".")[0] + ".py"))
    # every name of profiling.py's serving block is one the readers use
    source = open(os.path.join(ROOT, "benchmarks", "serve_spans.py")).read()
    assert all(f"profiling.{const}" in source for const in (
        "SRV_STEP", "SRV_PREFILL", "SRV_DECODE", "SRV_QUEUED", "SRV_WAIT",
        "SRV_CALLS", "SRV_LEAVES"))
