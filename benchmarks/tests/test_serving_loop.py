"""The serving loop on the CPU: latencies from the due time, the rehearsal of
a tiny ``decoder_serve`` cell through ``run.py``, the comparison's control,
and ``correct`` coming out false when the timed path is broken underneath.
Nothing is measured here."""

import argparse
import json
import os
import subprocess
import sys
import time
import types

import numpy as np
import pytest

from benchmarks import arrivals, serving
from benchmarks.run import Harness, load_cell, load_module

from test_rehearsal import HERE, ROOT

SERVING_CELLS = {"dsc1p3b-code-0.8knee"}
REHEARSAL = os.path.join(HERE, "rehearsal", "BENCHMARK.json")


def test_latency_is_counted_from_the_due_time_when_submit_is_late():
    from horovod_tpu.serving import ServingConfig, ServingEngine
    from horovod_tpu.serving.engine import StubBackend

    timed = serving.Timed(StubBackend(1, step_s=0.05))
    engine = ServingEngine(timed, ServingConfig(num_slots=1, buckets=(16,),
                                                max_seq_len=64),
                           clock=time.perf_counter)
    # the second request falls due while the first one's step holds the loop
    sched = arrivals.Schedule(np.array([0.0, 0.01]), np.array([8, 8]),
                              np.array([3, 3]))
    ids = [np.arange(8), np.arange(8) + 1]
    t0, records, nxt, end = serving.drive(
        engine, sched, ids, first=0, offset_s=0.0, close_s=0.3, drain_s=5.0)
    serving.stamp_admissions(records, timed.log)
    run = serving.ServeRun(
        cell={}, config={}, traffic={}, built=None, chips=1, peaks=None,
        setup_s=0.0, compiles_in_window=0, memory=None, open_t=t0,
        close_t=t0 + 0.3, end_t=end, records=records, steps=list(timed.log))
    late = records[1]
    assert nxt == 2 and all(r.done for r in records)
    assert late.due == pytest.approx(t0 + 0.01)
    assert late.submitted - late.due > 0.02        # handed over a step late
    from_submit = 1e3 * late.request.ttft_s
    from_due = run.ttft_ms()[1]
    assert from_due == pytest.approx(
        from_submit + 1e3 * (late.submitted - late.due))
    assert late.admitted is not None and late.admitted >= late.submitted
    # one slot: the second waits for the first's three tokens
    assert late.admitted >= records[0].stamps[-1]
    assert run.tokens_in_window == 6 and len(run.counted) == 2


def test_a_request_of_one_token_is_finished_by_its_prefill():
    """The code trace's outputs start at one token (4% of them): such a
    request has a first token and no gap, and every reader takes it."""
    from horovod_tpu.serving import ServingConfig, ServingEngine
    from horovod_tpu.serving.engine import StubBackend

    timed = serving.Timed(StubBackend(2, step_s=0.01))
    engine = ServingEngine(timed, ServingConfig(num_slots=2, buckets=(16,),
                                                max_seq_len=64),
                           clock=time.perf_counter)
    sched = arrivals.Schedule(np.array([0.0, 0.0]), np.array([8, 8]),
                              np.array([1, 4]))
    t0, records, _, end = serving.drive(
        engine, sched, [np.arange(8), np.arange(8) + 1], first=0,
        offset_s=0.0, close_s=0.2, drain_s=5.0)
    serving.stamp_admissions(records, timed.log)
    run = serving.ServeRun(
        cell={}, config={}, built=types.SimpleNamespace(num_slots=2),
        traffic={"ttft_limit_ms": 1e3, "tpot_limit_ms": 1e3,
                 "max_seq_len": 64}, chips=1, peaks=None,
        setup_s=0.0, compiles_in_window=0, memory=None, open_t=t0,
        close_t=t0 + 0.2, end_t=end, records=records, steps=list(timed.log))
    one, four = records
    assert one.done and len(one.stamps) == 1 and len(four.stamps) == 4
    assert one.request.finish_reason == "max_new_tokens"
    assert len(run.ttft_ms()) == 2 and len(run.token_gaps_ms()) == 3
    assert run.tokens_in_window == 5
    for stem in ("goodput_share", "ttft_ms_p90", "ttft_ms_mean",
                 "tpot_ms_p95", "kv_live_share", "kv_live_peak_share",
                 "completed_requests", "slot_occupancy"):
        assert load_module("metrics", stem).read(run) is not None, stem
    assert load_module("metrics", "goodput_share").read(run) == 100.0
    # three decode steps held one slot's 9, 10, 11 cached tokens live
    mean, peak = run.kv_live_tokens()
    assert peak == 11 and 9 <= mean <= 11


def serving_manifest(directory) -> str:
    """The rehearsal's cells with every metric of the real manifest that a
    serving cell reports."""
    import shutil

    base = os.path.join(str(directory), "manifest")
    shutil.copytree(os.path.join(HERE, "rehearsal"), base)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        real = json.load(f)
    path = os.path.join(base, "BENCHMARK.json")
    with open(path) as f:
        manifest = json.load(f)
    for group in ("end_to_end", "per_layer"):
        manifest[group] = [
            {k: v for k, v in m.items() if k != "workloads"}
            for m in real[group]
            if "workloads" not in m or SERVING_CELLS & set(m["workloads"])]
    with open(path, "w") as f:
        json.dump(manifest, f)
    return path


def rehearse(trace, seed, out):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=1")
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
         "--manifest", serving_manifest(out), "--workload", "tiny-serve-1",
         "--seed", str(seed), "--seconds", "3", "--trace", str(trace),
         "--out", str(out), "--rehearse-on-cpu"],
        env=env, cwd=ROOT, capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-2000:]
    last = proc.stdout.strip().splitlines()[-1]
    marker = "REHEARSAL on cpu, no result: "
    assert last.startswith(marker), last
    return json.loads(last[len(marker):]), proc.stdout


@pytest.mark.parametrize("trace", [0, 1])
def test_a_serving_cell_runs_and_prints_a_line_of_the_result_shape(
        trace, tmp_path):
    result, stdout = rehearse(trace, 2**31 + 7, tmp_path / "a")
    assert set(result) >= {"correct", "attempted", "failed", "metrics",
                           "device"}
    assert result["correct"], stdout[-3000:]
    assert result["failed"] == 0 and result["attempted"] >= 10
    names = set(result["metrics"])
    if trace:
        assert {"ttft_ms_p50.srv", "tpot_ms_p95.srv", "queue_ms_p95.srv",
                "slot_occupancy.srv", "prefill_share.srv",
                "goodput_share.srv", "arrival_late_ms_p95.srv",
                "completed_requests.srv", "decode_step_ms.srv",
                "prefill_ms_per_ktoken.srv", "compiles_in_window.srv",
                "ttft_ms_p90.srv", "ttft_ms_p95.srv", "kv_live_share.srv",
                "kv_live_peak_share.srv"} <= names
        # memory in use against memory reserved: never more than the pool
        assert 0 < result["metrics"]["kv_live_share.srv"]["value"] \
            <= result["metrics"]["kv_live_peak_share.srv"]["value"] <= 100
        # device metrics are never made up from a CPU trace
        assert not names & {"device_idle.srv", "decode_attn_roofline.srv",
                            "hbm_in_use", "hbm_reserved"}
        assert "busy_s" not in result["device"]
    else:
        assert names == {"ttft_ms_mean", "setup_s"}
    assert list(result)[-1] == "compared"
    assert set(result["compared"]) == {
        "served_token_gap_below_reference_best", "compiles_in_window",
        "rejected", "unfinished_after_drain"}
    assert "drain_s=" in stdout and "reference: " in stdout
    files = os.listdir(tmp_path / "a" / "tiny-serve-1")
    record, = [f for f in files if f.endswith(".requests.json")]
    with open(tmp_path / "a" / "tiny-serve-1" / record) as f:
        record = json.load(f)
    counted = [r for r in record["requests"]
               if record["open_t"] <= r["due"] < record["close_t"]]
    assert len(counted) == result["attempted"]
    assert all(r["finish"] == "max_new_tokens" for r in counted)
    if not trace:
        # the schedule is the file's: another seed sends the same work
        _, other = rehearse(0, 5, tmp_path / "b")
        line = lambda out: next(  # noqa: E731
            x for x in out.splitlines() if x.startswith("arrivals: "))
        assert line(other) == line(stdout)
        # the rate on the ``window:`` line: every token of the window after
        # its first stamp over the time from that stamp to the last
        stamps = sorted(s for r in record["requests"] for s in r["stamps"]
                        if record["open_t"] <= s < record["close_t"])
        printed = float(stdout.split(" tokens_per_s=")[1].split()[0])
        assert printed == pytest.approx(
            sum(s > stamps[0] for s in stamps) / (stamps[-1] - stamps[0]),
            abs=0.006)
        # the mean that is judged, from the due times in the file
        first = [1e3 * (r["stamps"][0] - r["due"]) for r in counted]
        assert result["metrics"]["ttft_ms_mean"]["value"] == pytest.approx(
            sum(first) / len(first))


@pytest.fixture(scope="module")
def tiny():
    _, cell, config, traffic = load_cell(REHEARSAL, "tiny-serve-1")
    return cell, config, traffic, load_module("families", config["family"])


def harness(tiny, family, out, seed=11, config=None, **mix):
    import jax

    cell, _, traffic, _ = tiny
    config = config or tiny[1]
    traffic = {**traffic, **mix}
    args = argparse.Namespace(seed=seed, seconds=2.0, trace=0, out=str(out))
    return Harness(args=args, manifest={}, cell=cell, config=config,
                   traffic=traffic, chips=1, family=family,
                   dev=jax.devices()[0], devices=jax.devices()[:1],
                   peaks=None, compile_events=[])


def broken(family, fault):
    """The family with its backend's decode call wrapped by ``fault``."""
    def serve(*a):
        served = family.serve(*a)
        inner = served.engine.backend.inner
        inner.decode = fault(inner, inner.decode)
        return served
    return types.SimpleNamespace(serve=serve)


def altered_token(inner, decode):
    """One slot's token altered where it is produced, a few steps in."""
    calls = [0]

    def faulty(last_tokens, lengths):
        nxt, logits = decode(last_tokens, lengths)
        calls[0] += 1
        if calls[0] > 40:               # past the warm-up's steps
            nxt = np.array(nxt)
            nxt[0] = (nxt[0] + 1) % logits.shape[-1]
        return nxt, logits
    return faulty


def state_unchanged(inner, decode):
    """A decode step that returns the cache as it found it: the position
    it should have written stays stale."""
    def faulty(last_tokens, lengths):
        kk, vv = inner.kk.copy(), inner.vv.copy()
        out = decode(last_tokens, lengths)
        inner.kk, inner.vv = kk, vv
        return out
    return faulty


def half_the_slots(inner, decode):
    """Half of the batch left out: the upper slots get the lower slots'
    tokens."""
    def faulty(last_tokens, lengths):
        nxt, logits = decode(last_tokens, lengths)
        nxt = np.array(nxt)
        half = len(nxt) // 2 + len(nxt) % 2
        nxt[half:] = nxt[:len(nxt) - half]
        return nxt, logits
    return faulty


def test_a_sound_run_is_correct(tiny, tmp_path):
    outcome = serving.measure(harness(tiny, tiny[3], tmp_path))
    assert outcome.correct and outcome.failed == 0
    gap, limit = outcome.compared["served_token_gap_below_reference_best"]
    assert gap < limit / 3


@pytest.mark.parametrize("fault", [altered_token, state_unchanged,
                                   half_the_slots])
def test_a_run_whose_timed_path_is_broken_is_not_correct(tiny, fault,
                                                         tmp_path):
    # every slot in use and every finished request compared, so that the
    # half of the slots that is broken is in the sample
    outcome = serving.measure(
        harness(tiny, broken(tiny[3], fault), tmp_path, rate=40.0,
                compare_requests=1000))
    gap, limit = outcome.compared["served_token_gap_below_reference_best"]
    assert not outcome.correct and gap > limit, outcome.compared


# The toy is given the real model's 24 layers (float8's error compounds
# with depth) and outputs long enough that a run compares some 550 served
# tokens, as a run of the cells does.  Read so on the CPU (PR 36), the
# control is 0.31-0.63 on every one of the eleven seeds tried (1-10 and
# 2**31 + 6; the lowest, 0.308, is seed 8's) and the program 0.015-0.039:
# no seed was left out for reading under the limit; four are kept for time.
# With the toy's own outputs (118 tokens a run) two seeds of eleven read
# under it (0.19, 0.23): a widest gap grows with the tokens it is taken over.
# At the cells' own size the control reads 5.5-7.1 (benchmarks/control.py on
# the chip; PERF.md section 6).
CONTROL_MIX = {"max_seq_len": 128, "compare_requests": 1000}
CONTROL_OUTPUTS = {"median": 30, "sigma": 0.5, "min": 1, "max": 60}


@pytest.mark.parametrize("seed", [1, 2, 3, 2**31 + 6])
def test_the_control_goes_through_the_comparison_and_is_not_correct(
        tiny, seed, tmp_path):
    """The reference with float8 operands, put in the program's place and
    judged by the run's own comparison, is not correct; the program on the
    same requests is."""
    import jax.numpy as jnp

    _, config, traffic, family = tiny
    config = dict(config, num_hidden_layers=24)
    mix = dict(CONTROL_MIX, arrivals=dict(traffic["arrivals"],
                                          output_tokens=CONTROL_OUTPUTS))
    outcome = serving.measure(harness(tiny, family, tmp_path, seed=seed,
                                      config=config, **mix))
    gap, limit = outcome.compared["served_token_gap_below_reference_best"]
    finished = [(np.asarray(r.request.prompt), np.asarray(r.request.tokens))
                for r in outcome.run.counted if r.done]
    control, = family.compare_served(config, {**traffic, **mix}, finished,
                                     seed, control=jnp.float8_e4m3fn)
    print(f"program {gap} limit {limit} control {control}")
    assert outcome.correct and 3 * gap < limit
    assert not control["ok"] and control["error"] > limit
    assert control["tokens"] > 400


FAMILY = '''"""A serving family dropped in by a test."""

from benchmarks.families import decoder_serve


def serve(config, traffic, chips, seed):
    served = decoder_serve.serve(config, traffic, chips, seed)
    served.notes["dropped_in"] = True
    return served
'''

READER = '''"""A reader dropped in by a test."""


def read(run):
    return float(len(run.counted))
'''


def test_a_dropped_in_serving_family_is_found_by_what_it_defines(tmp_path):
    """A family file that defines ``serve``, a configuration that names it,
    a mix that extends another and a reader: new files and new manifest
    entries, no edit to a file that exists, and the serving loop runs."""
    path = serving_manifest(tmp_path)
    base = os.path.dirname(path)
    with open(os.path.join(base, "configs", "tiny-serve.json")) as f:
        config = json.load(f)
    config["family"] = "dropped_serve"
    with open(os.path.join(base, "configs", "dropped-serve.json"), "w") as f:
        json.dump(config, f)
    with open(os.path.join(base, "traffic", "dropped-open.json"), "w") as f:
        json.dump({"extends": "tiny-open", "rate": 9.0, "drain_s": 0}, f)
    with open(path) as f:
        manifest = json.load(f)
    manifest["configs"].append({"name": "dropped-serve", "source": "test",
                                "file": "configs/dropped-serve.json",
                                "reduced": [], "why": "test"})
    manifest["workloads"].append({
        "name": "dropped", "config": "dropped-serve",
        "traffic": "dropped-open", "chips": 1, "why": "test"})
    manifest["per_layer"].append({
        "name": "dropped_counted.srv", "unit": "count", "better": "higher",
        "source": "program_counter", "layer": "test",
        "moves": "ttft_ms_mean", "workloads": ["dropped"]})
    with open(path, "w") as f:
        json.dump(manifest, f)
    dropped = {os.path.join(ROOT, "benchmarks", "families",
                            "dropped_serve.py"): FAMILY,
               os.path.join(ROOT, "benchmarks", "metrics",
                            "dropped_counted.py"): READER}
    for name, text in dropped.items():
        with open(name, "w") as f:
            f.write(text)
    try:
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        proc = subprocess.run(
            [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
             "--manifest", path, "--workload", "dropped", "--seed", "3",
             "--seconds", "2", "--trace", "1", "--out",
             str(tmp_path / "out"), "--rehearse-on-cpu"],
            env=env, cwd=ROOT, capture_output=True, text=True, timeout=900)
    finally:
        for name in dropped:
            os.remove(name)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert '"dropped_in": true' in proc.stdout
    assert "arrivals: rate_per_s=9.0" in proc.stdout
    result = json.loads(proc.stdout.strip().splitlines()[-1].split(
        "no result: ")[1])
    assert result["metrics"]["dropped_counted.srv"]["value"] \
        == result["attempted"]
    # the mix does not drain: what is unfinished at the close is not owed
    assert "unfinished_after_drain" not in result["compared"]
