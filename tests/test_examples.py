"""Example smoke tests — the reference runs its examples end-to-end in CI
(.travis.yml:113-131, shrunk via sed); we do the same with tiny arguments
on the virtual 8-chip mesh, plus launcher-driven ``-np 2`` runs of the
flagship examples (the reference's primary test mode, ``mpirun -np 2``)
asserting rank-tagged output and identical final metrics on every rank.

Three files, so that ``--dist loadfile`` can give them to three workers
(every case is a child interpreter or two: their time is the interpreters'
start, the frameworks' import and the compiles): this one, the jax examples
in one process, and the helpers; ``test_examples_frameworks.py``, the torch
and tensorflow examples alone and under the launcher; and
``test_examples_launched.py``, the jax examples under the launcher."""

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


def test_jax_longseq_transformer_zigzag_remat():
    """Remat composes with zigzag ring attention: jax.checkpoint wraps a
    block whose attention does ppermute collectives inside shard_map.
    The planner drops remat at these sizes, so force it through the env
    knob (kwarg > env > planner)."""
    out = _run("jax_longseq_transformer.py", "--seq-len", "512", "--layers",
               "1", "--heads", "4", "--embed", "64", "--steps", "1",
               env={"HVD_TPU_CTX_REMAT": "1"})
    assert "step 0" in out and "'remat': True" in out


@pytest.mark.slow
def test_jax_imagenet_resnet50(tmp_path):
    out = _run("jax_imagenet_resnet50.py", "--epochs", "1",
               "--steps-per-epoch", "1", "--batch-size", "1",
               "--ckpt-dir", str(tmp_path / "r50"), timeout=560)
    assert "epoch 0" in out


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
