"""Synthetic throughput harness — img/sec with a fusion-threshold sweep.

Analog of reference examples/pytorch_synthetic_benchmark.py:14-107: synthetic
data, N warmup batches, ``num-iters × num-batches-per-iter`` timed batches,
reporting img/sec mean ± 1.96σ per device and in total.  Adds ``--sweep`` to
re-run across HOROVOD_FUSION_THRESHOLD values (SURVEY §7 milestone 6).
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
from horovod_tpu import models
from horovod_tpu.utils import chip


def build_step(model, opt, steps_per_call=1):
    def train_step(carry, x, y):
        params, batch_stats, opt_state = carry

        def loss_fn(p):
            variables = {"params": p, **batch_stats}
            if batch_stats:  # static at trace time
                logits, mutated = model.apply(
                    variables, x, train=True, mutable=["batch_stats"])
            else:
                logits, mutated = model.apply(variables, x, train=True), {}
            return optax.softmax_cross_entropy_with_integer_labels(
                logits, y).mean(), mutated

        (loss, new_stats), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params)
        updates, opt_state = opt.update(grads, opt_state, params)
        return (optax.apply_updates(params, updates), new_stats, opt_state), \
            loss

    def k_steps(params, batch_stats, opt_state, x, y):
        # Device loop: the synthetic protocol reuses one batch, so x/y ride
        # as scan-invariant args and each dispatched program runs
        # steps_per_call full steps (same amortization as bench.py).
        (params, batch_stats, opt_state), losses = jax.lax.scan(
            lambda c, _: train_step(c, x, y),
            (params, batch_stats, opt_state), None, length=steps_per_call)
        return params, batch_stats, opt_state, losses[-1]

    return jax.jit(hvd.shard(
        k_steps,
        in_specs=(P(), P(), P(), hvd.batch_spec(4), hvd.batch_spec(1)),
        out_specs=(P(), P(), P(), P())),
        donate_argnums=(0, 1, 2))


# Canonical benchmark resolution per model family (tf_cnn_benchmarks uses
# 299² for inception3, 224² for everything else).
_IMAGE_SIZE = {"InceptionV3": 299}


def run(args, threshold: int | None = None) -> float:
    if threshold is not None:
        import os

        os.environ["HOROVOD_FUSION_THRESHOLD"] = str(threshold)
    model_cls = getattr(models, args.model)
    try:  # synthetic throughput: disable dropout on models that carry it
        model = model_cls(num_classes=1000, dtype=jnp.bfloat16,
                          dropout_rate=0.0)
    except TypeError:
        model = model_cls(num_classes=1000, dtype=jnp.bfloat16)
    size = args.image_size or _IMAGE_SIZE.get(args.model, 224)
    rng = jax.random.PRNGKey(0)
    variables = model.init(rng, jnp.zeros((2, size, size, 3)), train=True)
    params = variables["params"]
    has_stats = "batch_stats" in variables
    batch_stats = ({"batch_stats": variables["batch_stats"]}
                   if has_stats else {})
    opt = hvd.DistributedOptimizer(
        optax.sgd(0.01, momentum=0.9),
        compression=getattr(hvd.Compression, args.compression))
    opt_state = opt.init(params)
    step = build_step(model, opt, args.steps_per_call)

    gb = args.batch_size * hvd.num_chips()
    x = jnp.asarray(np.random.rand(gb, size, size, 3), jnp.float32)
    y = jnp.asarray(np.random.randint(0, 1000, gb))

    def one():
        nonlocal params, batch_stats, opt_state
        params, batch_stats, opt_state, loss = step(params, batch_stats,
                                                    opt_state, x, y)
        return loss

    loss = None
    for _ in range(args.num_warmup_batches):
        loss = one()
    if loss is not None:
        float(loss)  # hard sync via host fetch

    # Each timed window closes with a host fetch of the loss, which waits
    # for every step dispatched in it (same protocol as bench.py).
    img_secs = []
    for _ in range(args.num_iters):
        t0 = time.time()
        for _ in range(args.num_batches_per_iter):
            loss = one()
        float(loss)
        img_secs.append(gb * args.num_batches_per_iter * args.steps_per_call
                        / (time.time() - t0))

    img_sec_mean = np.mean(img_secs)
    img_sec_conf = 1.96 * np.std(img_secs)
    if hvd.rank() == 0:
        n = hvd.num_chips()
        print(f"Img/sec per chip: {img_sec_mean / n:.1f} "
              f"+-{img_sec_conf / n:.1f}")
        print(f"Total img/sec on {n} chip(s): {img_sec_mean:.1f} "
              f"+-{img_sec_conf:.1f}")
    return float(img_sec_mean)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="ResNet50",
                    help="any horovod_tpu.models class: ResNet50/101, "
                         "VGG16/19, InceptionV3, ...")
    ap.add_argument("--image-size", type=int, default=None,
                    help="input resolution (default: canonical per model)")
    def positive_int(s):
        v = int(s)
        if v < 1:
            raise argparse.ArgumentTypeError("must be >= 1")
        return v

    ap.add_argument("--steps-per-call", type=positive_int, default=1,
                    help="training steps per dispatched program (lax.scan "
                         "device loop; amortizes per-dispatch latency)")
    ap.add_argument("--batch-size", type=int, default=32)
    ap.add_argument("--num-warmup-batches", type=int, default=10)
    ap.add_argument("--num-iters", type=int, default=10)
    ap.add_argument("--num-batches-per-iter", type=int, default=10)
    ap.add_argument("--sweep", action="store_true",
                    help="sweep HOROVOD_FUSION_THRESHOLD")
    ap.add_argument("--compression", default="none",
                    choices=("none", "fp16", "bf16", "int8"),
                    help="gradient wire compression (int8 = shared-scale "
                         "quantization with error feedback; effects show "
                         "on multi-chip meshes where collectives move "
                         "bytes)")
    args = ap.parse_args()
    chip.enable_compile_cache()
    hvd.init()
    if args.sweep:
        for mb in (1, 8, 64, 256):
            rate = run(args, threshold=mb * 1024 * 1024)
            if hvd.rank() == 0:
                print(f"fusion_threshold={mb}MiB -> {rate:.1f} img/s")
    else:
        run(args)


if __name__ == "__main__":
    main()
