"""Example smoke tests of the jax examples under the launcher (reference
.travis.yml:113-131: ``mpirun -np 2``), the faults-enabled restart among them
(``tests/test_examples.py`` has the account and the helpers)."""

import json
import os
import subprocess
import sys

import pytest

from _timing import scaled
from test_examples import REPO, _final_metrics, _run_np2


def test_jax_mnist_np2(tmp_path):
    out = _run_np2("jax_mnist.py", "--epochs", "1", "--batch-size", "4",
                   "--ckpt-dir", str(tmp_path / "ck2"))
    assert "[0]: " in out and "[1]: " in out   # launcher rank tagging
    vals = _final_metrics(out)
    assert vals[0] == vals[1], vals            # identical final metrics


def test_weak_scaling_benchmark_np2():
    """The weak-scaling harness (scaling-efficiency ingredient (b))
    runs under the launcher and reports per-rank rate
    plus the ~2V wire model."""
    out = _run_np2("weak_scaling_benchmark.py", "--grad-mb", "1",
                   "--compute-reps", "1", "--steps", "3", "--warmup", "1")
    rows = [json.loads(line.split("]: ", 1)[1])
            for line in out.splitlines() if '"steps_per_s_per_rank"' in line]
    assert {r["rank"] for r in rows} == {0, 1}
    for r in rows:
        assert r["workers"] == 2
        assert r["wire_model_mb_per_rank_per_step"] == 1.0
        assert r["steps_per_s_per_rank"] > 0


def test_jax_mnist_advanced_np2():
    """The full callback stack (warmup, metric averaging, broadcast,
    schedules) under the launcher — reference CI runs keras_mnist_advanced
    under mpirun (.travis.yml:113-131)."""
    out = _run_np2("jax_mnist_advanced.py", timeout=scaled(560))
    assert "[0]: " in out and "[1]: " in out
    assert "finished gradual learning rate warmup" in out
    vals = _final_metrics(out)
    assert vals[0] == vals[1], vals


def test_jax_mnist_fault_injected_restart(tmp_path):
    """Faults-enabled smoke of the flagship example (docs/fault_tolerance.md):
    the injector kills rank 0 mid-epoch-1, the supervisor relaunches, the
    run resumes from the epoch-0 checkpoint and completes."""
    ck = str(tmp_path / "elastic_ck")
    env = {**os.environ, "PYTHONPATH": REPO,
           "HVD_TPU_RESTART_BACKOFF": "0.1",
           # Pin the worker's virtual chip count so the batch math is
           # stable: 4096 samples / (64 × 8 chips) = 8 batches per epoch;
           # step 10 is inside epoch 1, after the epoch-0 checkpoint
           # committed.
           "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
           "HVD_TPU_FAULT_KILL_RANK": "0",
           "HVD_TPU_FAULT_KILL_STEP": "10"}
    env.pop("JAX_PLATFORMS", None)
    out = subprocess.run(
        [sys.executable, "-m", "horovod_tpu.run", "-np", "1",
         "--max-restarts", "1", "--ckpt-dir", ck, "--",
         sys.executable, os.path.join(REPO, "examples", "jax_mnist.py"),
         "--epochs", "2", "--batch-size", "64", "--ckpt-dir", ck],
        capture_output=True, text=True, timeout=scaled(420), env=env,
        cwd=REPO)
    assert out.returncode == 0, (out.stdout[-3000:], out.stderr[-2000:])
    assert "killing rank 0 at step 10" in out.stdout + out.stderr
    assert "restarting (attempt 1" in out.stderr, out.stderr[-1500:]
    assert "resumed from epoch 0" in out.stdout, out.stdout[-2500:]
    assert "epoch 1:" in out.stdout


@pytest.mark.slow
def test_jax_imagenet_resnet50_np2_resume(tmp_path):
    """Checkpoint/resume + epoch broadcast across real process boundaries:
    run 1 trains epoch 0 and saves; run 2 broadcasts the resume epoch from
    rank 0, restores, and trains only epoch 1."""
    ck = str(tmp_path / "r50np2")
    out1 = _run_np2("jax_imagenet_resnet50.py", "--epochs", "1",
                    "--steps-per-epoch", "1", "--batch-size", "2",
                    "--ckpt-dir", ck, timeout=scaled(560))
    assert "epoch 0" in out1
    vals = _final_metrics(out1)
    assert vals[0] == vals[1], vals
    out2 = _run_np2("jax_imagenet_resnet50.py", "--epochs", "2",
                    "--steps-per-epoch", "1", "--batch-size", "2",
                    "--ckpt-dir", ck, timeout=scaled(560))
    assert "resumed from epoch 0" in out2
    assert "epoch 1:" in out2 and "epoch 0:" not in out2
    vals = _final_metrics(out2)
    assert vals[0] == vals[1], vals
