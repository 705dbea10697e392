"""One ``--rehearse-on-cpu`` walk of a tiny serving cell through
``benchmarks/run.py``, for the ``tests/test_bench_*.py`` of the served cells.

A manifest of one tiny cell beside files of its own names: the harness finds
the family, the reference, the traffic and the readers by name, as it finds
the real cell's.  The walk is made once a file (a module-scoped fixture) and
read three times: the run as served, the run's own comparison given one
request of the same record with its third served token altered, and the
lines the family prints.  The altered record is judged in the walk's
process, by the function the harness judged the served one with, right
after it: a second walk would cost the interpreter, the compiles and the
window again to learn what ``compare`` says of a wrong token."""

import json
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# ``benchmarks/run.py`` as ``__main__``, its serving measurement handed a
# family whose ``compare`` also judges, and prints, the altered record.
WALK = """
import json, os, runpy, sys
sys.argv[0] = 'benchmarks/run.py'
from benchmarks import serving
add, modulus = json.loads(os.environ['ALTER_A_SERVED_TOKEN'])
measure = serving.measure
def measure_and_judge_an_altered_record(h):
    serve = h.family.serve
    def served_by(*args, **kwargs):
        served = serve(*args, **kwargs)
        judge = served.compare
        def compare(finished, seed):
            checks = judge(finished, seed)
            prompt, served = finished[0]
            served = served.copy()
            served[2] = (served[2] + add) % modulus
            print('altered: judged=' + json.dumps(judge([(prompt, served)],
                                                        seed)), flush=True)
            return checks
        served.compare = compare
        return served
    h.family.serve = served_by
    return measure(h)
serving.measure = measure_and_judge_an_altered_record
runpy.run_path('benchmarks/run.py', run_name='__main__')
"""


def walk(base, name, tiny, traffic, cell, alter=(101, 256)):
    """(the result line, the altered record's checks, stdout) of one traced
    walk of ``tiny`` under ``traffic`` with the metrics of ``cell``;
    ``alter`` is what is added to the first request's third served token,
    and the vocabulary it wraps in."""
    (base / "configs").mkdir(parents=True)
    (base / "traffic").mkdir()
    (base / "configs" / f"{name}.json").write_text(json.dumps(tiny))
    (base / "traffic" / "tiny-open.json").write_text(json.dumps(traffic))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        real = json.load(f)
    m = {"command": real["command"], "paths": ["."], "run_seconds": 3,
         "configs": [{"name": name, "source": "toy", "reduced": [],
                      "file": f"configs/{name}.json", "why": "rehearsal"}],
         "workloads": [{"name": f"{name}-1", "config": name,
                        "traffic": "tiny-open", "chips": 1,
                        "why": "rehearsal"}],
         **{g: [{k: v for k, v in e.items() if k != "workloads"}
                for e in real[g]
                if "workloads" not in e or cell in e["workloads"]]
            for g in ("end_to_end", "per_layer")}}
    (base / "BENCHMARK.json").write_text(json.dumps(m))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=1",
               ALTER_A_SERVED_TOKEN=json.dumps(alter))
    proc = subprocess.run(
        [sys.executable, "-c", WALK,
         "--manifest", str(base / "BENCHMARK.json"), "--workload",
         f"{name}-1", "--seed", str(2**31 + 7), "--seconds", "3",
         "--trace", "1", "--out", str(base / "out"), "--rehearse-on-cpu"],
        env=env, cwd=ROOT, capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    last = proc.stdout.strip().splitlines()[-1]
    marker = "REHEARSAL on cpu, no result: "
    assert last.startswith(marker), last
    altered = json.loads(
        proc.stdout.split("altered: judged=")[1].splitlines()[0])
    return json.loads(last[len(marker):]), altered, proc.stdout


def assert_the_altered_record_is_not_correct(walked):
    """The served record passes and the same record with a token altered
    does not.  That a check which is not ok makes the run not ``correct``,
    found by the harness's own sample of what the ENGINE served, is held
    once, with no model:
    ``tests/test_bench_cohere2.py::test_measure_says_of_a_run_what_its_comparison_says``."""
    result, altered, stdout = walked
    assert result["correct"], stdout[-3000:]
    by_name = {c["name"]: c for c in altered}
    judged = by_name["served_token_gap_below_reference_best"]
    assert not judged["ok"] and judged["error"] > judged["tolerance"], altered
