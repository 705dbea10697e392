"""TensorFlow MNIST with a custom training loop — analog of reference
examples/tensorflow_mnist.py (MonitoredTrainingSession pattern, :23-123),
re-idiomized for TF-2 eager: ``DistributedGradientTape`` averages
gradients, ``broadcast_variables`` replaces the
``BroadcastGlobalVariablesHook``, rank 0 owns checkpointing.

Run: python examples/tensorflow_mnist.py
"""

from __future__ import annotations

import argparse

import numpy as np
import tensorflow as tf
import keras

import horovod_tpu.tensorflow as hvd


def synthetic_mnist(n=4096, seed=0):
    """Deterministic stand-in for the MNIST download (no egress in CI)."""
    rng = np.random.RandomState(seed)
    x = rng.rand(n, 28, 28, 1).astype(np.float32)
    y = (x.mean(axis=(1, 2, 3)) * 10).astype(np.int32) % 10
    return x, y


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--epochs", type=int, default=2)
    ap.add_argument("--batch-size", type=int, default=64)
    ap.add_argument("--lr", type=float, default=0.001)
    args = ap.parse_args()

    # Horovod: initialize (reference tensorflow_mnist.py:23).
    hvd.init()

    model = keras.Sequential([
        keras.layers.Conv2D(32, 5, activation="relu"),
        keras.layers.MaxPooling2D(2),
        keras.layers.Conv2D(64, 5, activation="relu"),
        keras.layers.MaxPooling2D(2),
        keras.layers.Flatten(),
        keras.layers.Dense(1024, activation="relu"),
        keras.layers.Dense(10),
    ])
    # Horovod: scale the LR by total workers (reference :52-54).
    opt = keras.optimizers.Adam(args.lr * hvd.size())
    loss_fn = keras.losses.SparseCategoricalCrossentropy(from_logits=True)

    # Horovod: shard data by rank (reference pytorch_imagenet :93-96).
    x_all, y_all = synthetic_mnist()
    x = x_all[hvd.rank()::hvd.size()]
    y = y_all[hvd.rank()::hvd.size()]

    first_batch = True
    for epoch in range(args.epochs):
        perm = np.random.RandomState(epoch).permutation(len(x))
        epoch_loss = 0.0
        steps = 0
        for i in range(0, len(x) - args.batch_size + 1, args.batch_size):
            idx = perm[i:i + args.batch_size]
            xb = tf.constant(x[idx])
            yb = tf.constant(y[idx])
            # Horovod: wrap the tape so gradient() allreduces.
            with hvd.DistributedGradientTape(tf.GradientTape()) as tape:
                logits = model(xb, training=True)
                loss = loss_fn(yb, logits)
            grads = tape.gradient(loss, model.trainable_variables)
            opt.apply_gradients(zip(grads, model.trainable_variables))
            if first_batch:
                # Horovod: broadcast initial state once variables exist
                # (reference BroadcastGlobalVariablesHook, :101-133).
                hvd.broadcast_variables(
                    model.variables + opt.variables, root_rank=0)
                first_batch = False
            epoch_loss += float(loss)
            steps += 1
        # Horovod: average the epoch metric across workers.
        mean_loss = float(hvd.allreduce(
            tf.constant(epoch_loss / max(steps, 1)), name="epoch_loss"))
        if hvd.rank() == 0:
            print(f"epoch {epoch}: loss={mean_loss:.4f}")

    # Every rank reports the globally-averaged final metric (identical by
    # construction — multi-process CI asserts this, tests/test_examples_frameworks.py).
    print(f"[rank {hvd.rank()}/{hvd.size()}] final loss={mean_loss:.6f}",
          flush=True)

    if hvd.rank() == 0:
        model.save("/tmp/hvd_tpu_tf_mnist.keras")
        print("saved /tmp/hvd_tpu_tf_mnist.keras")


if __name__ == "__main__":
    main()
