"""``run.py`` end to end at a tiny preset on the CPU: both ``--trace`` modes,
one and four virtual devices.  Nothing is measured; the marked line it
prints has the result line's shape."""

import json
import os
import shutil
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def rehearsal_manifest(directory) -> str:
    """A copy of ``rehearsal/`` (toy configurations, traffic and cells)
    whose manifest takes every metric entry of the real BENCHMARK.json,
    in every cell, so that the rehearsal walks the readers there are."""
    base = os.path.join(str(directory), "manifest")
    shutil.copytree(os.path.join(HERE, "rehearsal"), base)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        real = json.load(f)
    path = os.path.join(base, "BENCHMARK.json")
    with open(path) as f:
        manifest = json.load(f)
    for group in ("end_to_end", "per_layer"):
        manifest[group] = [{k: v for k, v in m.items() if k != "workloads"}
                           for m in real[group]]
    with open(path, "w") as f:
        json.dump(manifest, f)
    return path


def rehearse(workload, trace, devices, out, manifest=None, seconds=2):
    manifest = manifest or rehearsal_manifest(out)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}")
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
         "--manifest", manifest, "--workload", workload, "--seed",
         str(2**31 + 7), "--seconds", str(seconds), "--trace", str(trace),
         "--out", str(out), "--rehearse-on-cpu"],
        env=env, cwd=ROOT, capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-2000:]
    last = proc.stdout.strip().splitlines()[-1]
    marker = "REHEARSAL on cpu, no result: "
    assert last.startswith(marker), last
    return json.loads(last[len(marker):]), proc.stdout


@pytest.mark.parametrize("workload,devices,trace", [
    ("tiny-lm-1", 1, 0), ("tiny-lm-1", 1, 1), ("tiny-lm-long", 1, 0),
    ("tiny-lm-dp4", 4, 0), ("tiny-lm-dp4", 4, 1), ("tiny-resnet-1", 1, 0)])
def test_a_cell_runs_and_prints_a_line_of_the_result_shape(
        workload, devices, trace, tmp_path):
    result, stdout = rehearse(workload, trace, devices, tmp_path,
                              seconds=6 if "resnet" in workload else 2)
    assert set(result) >= {"correct", "attempted", "failed", "metrics",
                           "device"}
    assert result["device"]["count"] == devices
    assert result["failed"] == 0 and result["attempted"] >= 5
    names = set(result["metrics"])
    if trace:
        assert {"stall_share.lm", "steady_rate.lm", "dispatch_ms.lm",
                "input_wait_ms.lm", "compiles_in_window.lm"} <= names
        # device metrics are never made up from a CPU trace
        assert not names & {"flash_ms", "device_idle.lm", "mfu.lm",
                            "allreduce_ms", "xla_compute_ms.lm",
                            "hbm_in_use", "hbm_reserved"}
    else:
        assert "setup_s" in names
        assert ("img_per_s" in names) != ("tokens_per_s" in names)
    steps = [f for f in os.listdir(tmp_path / workload)
             if f.endswith(".steps.json")]
    assert len(steps) == 1
    with open(tmp_path / workload / steps[0]) as f:
        record = json.load(f)
    assert len(record["periods_s"]) * record["steps_per_call"] \
        == result["attempted"]
    assert len(record["fetch_s"]) == len(record["periods_s"])
    if not trace:       # the rate is every unit of the window over its time
        rate = next(v["value"] for k, v in result["metrics"].items()
                    if k.endswith("_per_s"))
        assert rate == pytest.approx(
            len(record["periods_s"]) * record["units_per_call"]
            / sum(record["periods_s"]), rel=1e-9)
    assert "plan: overlap_plan=" in stdout and "reference: " in stdout
    assert "memory: " in stdout and "host_gap_s=" in stdout
    # what ``correct`` was decided from, each number beside its limit, last
    assert list(result)[-1] == "compared"
    assert {"loss", "compiles_in_window", "non_finite_losses"} \
        <= set(result["compared"])
    assert all(len(pair) == 2 for pair in result["compared"].values())
    if "lm" in workload:
        assert result["correct"], stdout[-3000:]


def test_a_machine_without_a_tpu_gets_no_result(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
         "--manifest", rehearsal_manifest(tmp_path), "--workload",
         "tiny-lm-1", "--seed", "1",
         "--seconds", "1", "--trace", "0", "--out", str(tmp_path)],
        env=env, cwd=ROOT, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert not proc.stdout.strip().startswith("{")
    assert "found none" in proc.stderr
