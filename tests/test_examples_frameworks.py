"""Example smoke tests of the torch and tensorflow front ends, alone and
under the launcher (``tests/test_examples.py`` has the account and the
helpers)."""

import re

from test_examples import _final_metrics, _run, _run_np2


def test_torch_mnist():
    out = _run("torch_mnist.py", "--epochs", "1")
    assert "epoch 0" in out


def test_torch_mnist_resume(tmp_path):
    ck = str(tmp_path / "tck")
    _run("torch_mnist.py", "--epochs", "1", "--ckpt-dir", ck)
    out = _run("torch_mnist.py", "--epochs", "2", "--ckpt-dir", ck)
    assert "resumed from epoch 0" in out
    assert "epoch 1:" in out and "epoch 0:" not in out


def test_tensorflow_mnist():
    out = _run("tensorflow_mnist.py", "--epochs", "1", "--batch-size", "64")
    assert "epoch 0" in out and "loss=" in out


def test_tf_keras_mnist():
    out = _run("tf_keras_mnist.py", "--epochs", "1", "--warmup-epochs", "1",
               "--batch-size", "64")
    assert "finished gradual learning rate warmup" in out


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
