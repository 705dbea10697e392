"""Example smoke tests — the reference runs its examples end-to-end in CI
(.travis.yml:113-131, shrunk via sed); we do the same with tiny arguments
on the virtual 8-chip mesh, plus launcher-driven ``-np 2`` runs of the
flagship examples (the reference's primary test mode, ``mpirun -np 2``)
asserting rank-tagged output and identical final metrics on every rank."""

import json
import os
import re
import subprocess
import sys

import pytest

from _timing import scaled

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_np2(script, *args, timeout=None):
    """Run an example under the launcher (mpirun -np 2 analog)."""
    env = {**os.environ, "PYTHONPATH": REPO}
    env.pop("JAX_PLATFORMS", None)   # launcher pins cpu for children
    out = subprocess.run(
        [sys.executable, "-m", "horovod_tpu.run", "-np", "2", "--",
         sys.executable, os.path.join(REPO, "examples", script), *args],
        capture_output=True, text=True,
        timeout=timeout or scaled(420), env=env, cwd=REPO)
    assert out.returncode == 0, (out.stdout[-3000:], out.stderr[-2000:])
    return out.stdout


def _final_metrics(out: str, np_: int = 2) -> dict[int, str]:
    """Parse every rank's '[rank r/n] final ...' line; assert all present."""
    vals: dict[int, str] = {}
    for line in out.splitlines():
        m = re.search(r"\[rank (\d+)/(\d+)\] final (.+)$", line)
        if m:
            assert int(m.group(2)) == np_
            vals[int(m.group(1))] = m.group(3).strip()
    assert set(vals) == set(range(np_)), \
        f"missing rank-tagged finals in:\n{out[-2500:]}"
    return vals


def _run(script, *args, timeout=420, env=None):
    env = {
        **os.environ,
        "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
        "JAX_PLATFORMS": "cpu",
        "PYTHONPATH": REPO,
        **(env or {}),
    }
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "examples", script), *args],
        capture_output=True, text=True, timeout=timeout, env=env, cwd=REPO)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


def test_jax_mnist(tmp_path):
    out = _run("jax_mnist.py", "--epochs", "1", "--batch-size", "4",
               "--ckpt-dir", str(tmp_path / "ck"))
    assert "epoch 0" in out and "loss=" in out


def test_jax_mnist_advanced():
    out = _run("jax_mnist_advanced.py")
    assert "finished gradual learning rate warmup" in out


def test_torch_mnist():
    out = _run("torch_mnist.py", "--epochs", "1")
    assert "epoch 0" in out


def test_jax_word2vec():
    out = _run("jax_word2vec.py", "--steps", "5", "--vocab", "500",
               "--dim", "32")
    assert "step 0" in out


def test_jax_longseq_transformer():
    out = _run("jax_longseq_transformer.py", "--seq-len", "512", "--layers",
               "1", "--heads", "4", "--embed", "64", "--steps", "1")
    assert "step 0" in out
    # The planner owns the layout: causal multi-shard work rides zigzag,
    # and the run prints the full plan next to the numbers.
    assert "context plan" in out and "layout=zigzag" in out


def test_jax_longseq_transformer_plain_env_override():
    # HVD_TPU_CTX_LAYOUT pins the plain layout without touching code —
    # the env rung of the kwarg > env > planner resolution order.
    out = _run("jax_longseq_transformer.py", "--seq-len", "512", "--layers",
               "1", "--heads", "4", "--embed", "64", "--steps", "1",
               env={"HVD_TPU_CTX_LAYOUT": "plain"})
    assert "step 0" in out and "layout=plain" in out


@pytest.mark.slow
def test_jax_imagenet_resnet50(tmp_path):
    out = _run("jax_imagenet_resnet50.py", "--epochs", "1",
               "--steps-per-epoch", "1", "--batch-size", "1",
               "--ckpt-dir", str(tmp_path / "r50"), timeout=560)
    assert "epoch 0" in out


def test_tensorflow_mnist():
    out = _run("tensorflow_mnist.py", "--epochs", "1", "--batch-size", "64")
    assert "epoch 0" in out and "loss=" in out


def test_tf_keras_mnist():
    out = _run("tf_keras_mnist.py", "--epochs", "1", "--warmup-epochs", "1",
               "--batch-size", "64")
    assert "finished gradual learning rate warmup" in out


def test_jax_moe_transformer():
    out = _run("jax_moe_transformer.py", "--steps", "12")
    assert "improved=True" in out


def test_jax_pipeline_transformer():
    out = _run("jax_pipeline_transformer.py", "--steps", "12")
    assert "improved=True" in out


def test_jax_fsdp_transformer():
    out = _run("jax_fsdp_transformer.py", "--steps", "12")
    assert "improved=True" in out
    # The K-fold memory shrink is the point of FSDP — assert it happened.
    m = re.search(r"\((\d+\.\d)x shrink\)", out)
    assert m and float(m.group(1)) > 2.0, out


def test_torch_mnist_resume(tmp_path):
    ck = str(tmp_path / "tck")
    _run("torch_mnist.py", "--epochs", "1", "--ckpt-dir", ck)
    out = _run("torch_mnist.py", "--epochs", "2", "--ckpt-dir", ck)
    assert "resumed from epoch 0" in out
    assert "epoch 1:" in out and "epoch 0:" not in out


# ---- launcher-driven multi-process runs (reference .travis.yml:113-131) ----

def test_jax_mnist_np2(tmp_path):
    out = _run_np2("jax_mnist.py", "--epochs", "1", "--batch-size", "4",
                   "--ckpt-dir", str(tmp_path / "ck2"))
    assert "[0]: " in out and "[1]: " in out   # launcher rank tagging
    vals = _final_metrics(out)
    assert vals[0] == vals[1], vals            # identical final metrics


def test_torch_mnist_np2(tmp_path):
    out = _run_np2("torch_mnist.py", "--epochs", "1",
                   "--ckpt-dir", str(tmp_path / "tck2"))
    assert "[0]: " in out and "[1]: " in out
    vals = _final_metrics(out)
    assert vals[0] == vals[1], vals


def test_torch_synthetic_benchmark_np2():
    """The reference's north-star throughput harness
    (pytorch_synthetic_benchmark.py protocol) runs under the launcher and
    reports per-worker and total img/sec from rank 0."""
    out = _run_np2("torch_synthetic_benchmark.py", "--model", "mlp",
                   "--hidden", "64", "--num-warmup-batches", "2",
                   "--num-batches-per-iter", "2", "--num-iters", "2")
    assert re.search(r"Img/sec per worker: [\d.]+", out), out[-2000:]
    assert re.search(r"Total img/sec on 2 worker\(s\)", out), out[-2000:]


def test_tensorflow_mnist_np2():
    out = _run_np2("tensorflow_mnist.py", "--epochs", "1",
                   "--batch-size", "32")
    assert "[0]: " in out and "[1]: " in out
    vals = _final_metrics(out)
    assert vals[0] == vals[1], vals


def test_jax_longseq_transformer_zigzag_remat():
    """Remat composes with zigzag ring attention: jax.checkpoint wraps a
    block whose attention does ppermute collectives inside shard_map.
    The planner drops remat at these sizes, so force it through the env
    knob (kwarg > env > planner)."""
    out = _run("jax_longseq_transformer.py", "--seq-len", "512", "--layers",
               "1", "--heads", "4", "--embed", "64", "--steps", "1",
               env={"HVD_TPU_CTX_REMAT": "1"})
    assert "step 0" in out and "'remat': True" in out


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
