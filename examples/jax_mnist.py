"""MNIST data-parallel training — the hello-world example.

Analog of reference examples/tensorflow_mnist.py (MonitoredTrainingSession
pattern) and examples/pytorch_mnist.py: init, shard the data by rank, scale
the LR by worker count, wrap the optimizer, broadcast initial state, train,
checkpoint on rank 0 only.

Run (single host, all local chips):  python examples/jax_mnist.py
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import PartitionSpec as P

import horovod_tpu as hvd
from horovod_tpu import faults
from horovod_tpu.models import MnistCNN


def synthetic_mnist(n=4096, seed=0):
    """Deterministic stand-in for the MNIST download (no egress in CI)."""
    rng = np.random.RandomState(seed)
    x = rng.rand(n, 28, 28, 1).astype(np.float32)
    y = (x.mean(axis=(1, 2, 3)) * 10).astype(np.int32) % 10
    return x, y


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--epochs", type=int, default=2)
    ap.add_argument("--batch-size", type=int, default=64,
                    help="per-chip batch size")
    ap.add_argument("--lr", type=float, default=0.001)
    ap.add_argument("--ckpt-dir", default="/tmp/hvd_tpu_mnist")
    args = ap.parse_args()

    # Horovod: initialize (reference tensorflow_mnist.py:23).
    hvd.init()

    model = MnistCNN()
    rng = jax.random.PRNGKey(42)
    params = model.init(rng, jnp.zeros((1, 28, 28, 1)))

    # Horovod: scale the LR by total workers (reference :52-54).
    opt = hvd.DistributedOptimizer(
        optax.sgd(hvd.scale_learning_rate(args.lr), momentum=0.9))
    opt_state = opt.init(params)

    # Horovod: broadcast initial state from rank 0 (reference
    # BroadcastGlobalVariablesHook, :88-92).
    params = hvd.broadcast_parameters(params, root_rank=0)

    global_batch = args.batch_size * hvd.num_chips()

    @jax.jit
    @hvd.shard(in_specs=(P(), P(), hvd.batch_spec(4), hvd.batch_spec(1)),
               out_specs=(P(), P(), P()))
    def train_step(params, opt_state, x, y):
        def loss_fn(p):
            logits = model.apply(p, x)
            return optax.softmax_cross_entropy_with_integer_labels(
                logits, y).mean()

        loss, grads = jax.value_and_grad(loss_fn)(params)
        updates, opt_state = opt.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    # Data sharding by process (reference DistributedSampler pattern,
    # pytorch_mnist.py:93-96): each process keeps its slice; within the
    # process the mesh shards the per-host batch over local chips.
    x_all, y_all = synthetic_mnist()
    batches = hvd.data.ShardedBatches(x_all, y_all,
                                      batch_per_chip=args.batch_size,
                                      shuffle=True)
    if len(batches) == 0:
        raise SystemExit(
            f"per-process shard is smaller than the global batch "
            f"({global_batch}); lower --batch-size or add data.")

    @jax.jit
    @hvd.shard(in_specs=(P(), hvd.batch_spec(4), hvd.batch_spec(1)),
               out_specs=P())
    def eval_correct(params, x, y):
        # Per-shard correct-count, psum-reduced: global accuracy in one
        # compiled collective (reference evaluates test accuracy,
        # keras_mnist.py:84-86 / MetricAverageCallback flow).
        preds = jnp.argmax(model.apply(params, x), axis=-1)
        return hvd.allreduce(jnp.sum(preds == y), average=False)

    # Elastic supervision (docs/fault_tolerance.md): epoch-granular
    # checkpoints through the manifest-committed CheckpointManager, resume
    # from the newest complete one (the launcher's --max-restarts path
    # exports HVD_TPU_RESUME_DIR but the manager re-scans the same root),
    # a SIGTERM drain that saves before exiting, and the fault-injection
    # clock so HVD_TPU_FAULT_* scenarios replay deterministically.
    manager = hvd.checkpoint.CheckpointManager(args.ckpt_dir)
    hvd.checkpoint.install_preemption_handler()
    start_epoch, gstep = 0, 0
    ckpt = manager.restore_latest(
        template={"params": params, "opt_state": opt_state})
    if ckpt is not None:
        params, opt_state = ckpt.state["params"], ckpt.state["opt_state"]
        start_epoch = int(ckpt.metadata.get("completed_epoch", -1)) + 1
        gstep = ckpt.step + 1
        if hvd.rank() == 0:
            print(f"resumed from epoch {start_epoch - 1}", flush=True)

    # Host loading runs on a background thread and the next batch's
    # host-to-device transfer overlaps the current step (the overlap the
    # reference got from DataLoader workers + CUDA streams).  On a real
    # TPU run pass sharding=(hvd.data_sharding(4), hvd.data_sharding(1))
    # to land batches pre-sharded (safe everywhere: on the CPU simulation
    # backend sharded puts complete synchronously — prefetch_to_device).
    loss, acc = None, float("nan")
    for epoch in range(start_epoch, args.epochs):
        t0 = time.time()
        loss = None
        for xb, yb in hvd.data.prefetch_to_device(
                hvd.data.BackgroundLoader(batches)):
            faults.step(gstep)
            if hvd.checkpoint.preemption_requested():
                # Drain: one complete checkpoint, then a clean exit the
                # launcher recognizes (epoch-granular resume — the
                # in-progress epoch is repeated).
                manager.save(gstep, {"params": params,
                                     "opt_state": opt_state},
                             metadata={"completed_epoch": epoch - 1})
                manager.drain()
                raise SystemExit(0)
            params, opt_state, loss = train_step(params, opt_state, xb, yb)
            gstep += 1
        manager.save(gstep, {"params": params, "opt_state": opt_state},
                     metadata={"completed_epoch": epoch})
        correct = sum(
            int(eval_correct(params, jnp.asarray(xb), jnp.asarray(yb)))
            for xb, yb in batches)
        acc = correct / (len(batches) * global_batch)
        if hvd.rank() == 0:
            print(f"epoch {epoch}: loss={float(loss):.4f} acc={acc:.3f} "
                  f"({time.time() - t0:.1f}s)")

    # Every rank reports the globally-averaged final metric (identical by
    # construction — multi-process CI asserts this, tests/test_examples_launched.py).
    final_loss = float(np.asarray(hvd.allreduce(
        jnp.asarray(0.0 if loss is None else float(loss)))))
    print(f"[rank {hvd.rank()}/{hvd.size()}] final loss={final_loss:.6f} "
          f"acc={acc:.4f}", flush=True)

    # Horovod: checkpoint on rank 0 only (reference :108-110); the manager
    # already committed the final epoch above.
    manager.drain()
    if hvd.rank() == 0:
        print("done; checkpoint written to", args.ckpt_dir)


if __name__ == "__main__":
    main()
