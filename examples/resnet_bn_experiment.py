"""ResNet-50 BN-statistics roofline experiment (VERDICT r3 item 4).

The round-2 profile attributes ~31 ms of the 46.9 ms ResNet-50 step to
BatchNorm statistics + normalize traffic (21.5 ms `convert_reduce`
reductions + 9.6 ms elementwise), and argues the step sits at ~92 % of
an HBM roofline those bytes define.  This harness TESTS that claim with
a bytes-cutting A/B that changes nothing else: the same full training
step (forward + backward + DistributedOptimizer update) with

* ``stats``   — normal training BN (`train=True`): per-batch mean/var
  reductions, stats updates, and the stats terms in BN backward;
* ``nostats`` — running-average BN (`train=False` normalization inside
  the gradient step): identical convolutions, activations, residuals,
  and optimizer — only the statistics machinery is gone.

If the roofline story is right, ``nostats`` should claw back a large
fraction of the ~31 ms (≈ +2/3 of the gap to the conv-only floor); if
throughput barely moves, the floor is elsewhere and the claim dies.
What the `resnet50-imagenet` cell reads today is in PERF.md.

Run on the real chip:  python examples/resnet_bn_experiment.py
"""

from __future__ import annotations

import argparse
import json
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=128)
    ap.add_argument("--warmup", type=int, default=5)
    ap.add_argument("--iters", type=int, default=5)
    ap.add_argument("--batches-per-iter", type=int, default=5)
    ap.add_argument("--steps-per-call", type=int, default=8)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    from jax.sharding import PartitionSpec as P

    import horovod_tpu as hvd
    from horovod_tpu.models import ResNet50

    hvd.init()
    model = ResNet50(num_classes=1000, dtype=jnp.bfloat16)
    rng = jax.random.PRNGKey(0)
    x = jax.random.normal(rng, (args.batch, 224, 224, 3), jnp.float32)
    y = jax.random.randint(rng, (args.batch,), 0, 1000)
    variables = model.init(rng, x[:2], train=True)
    opt = hvd.DistributedOptimizer(optax.sgd(0.01, momentum=0.9))

    def measure(train_flag: bool) -> float:
        # Fresh copies per variant: the donated step consumes its inputs,
        # and the A and B runs must start from identical state.
        params = jax.tree.map(jnp.array, variables["params"])
        batch_stats = jax.tree.map(jnp.array, variables["batch_stats"])
        opt_state = opt.init(params)

        def train_step(carry, x, y):
            params, batch_stats, opt_state = carry

            def loss_fn(p):
                if train_flag:
                    logits, mutated = model.apply(
                        {"params": p, "batch_stats": batch_stats}, x,
                        train=True, mutable=["batch_stats"])
                    new_stats = mutated["batch_stats"]
                else:
                    logits = model.apply(
                        {"params": p, "batch_stats": batch_stats}, x,
                        train=False)
                    new_stats = batch_stats
                return optax.softmax_cross_entropy_with_integer_labels(
                    logits, y).mean(), new_stats

            (loss, new_stats), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params)
            updates, opt_state = opt.update(grads, opt_state, params)
            return (optax.apply_updates(params, updates), new_stats,
                    opt_state), loss

        def k_steps(params, batch_stats, opt_state, x, y):
            (params, batch_stats, opt_state), losses = jax.lax.scan(
                lambda c, _: train_step(c, x, y),
                (params, batch_stats, opt_state), None,
                length=args.steps_per_call)
            return params, batch_stats, opt_state, losses[-1]

        step = jax.jit(hvd.shard(
            k_steps,
            in_specs=(P(), P(), P(), hvd.batch_spec(4), hvd.batch_spec(1)),
            out_specs=(P(), P(), P(), P())),
            donate_argnums=(0, 1, 2))

        loss = None
        for _ in range(args.warmup):
            params, batch_stats, opt_state, loss = step(
                params, batch_stats, opt_state, x, y)
        float(loss)
        rates = []
        for _ in range(args.iters):
            t0 = time.perf_counter()
            for _ in range(args.batches_per_iter):
                params, batch_stats, opt_state, loss = step(
                    params, batch_stats, opt_state, x, y)
            float(loss)
            dt = time.perf_counter() - t0
            rates.append(args.batch * args.batches_per_iter
                         * args.steps_per_call / dt)
        return float(np.mean(rates))

    stats = measure(True)
    nostats = measure(False)
    if hvd.rank() == 0:
        print(json.dumps({
            "metric": "resnet50_bn_stats_ab",
            "img_s_with_stats": round(stats, 1),
            "img_s_no_stats": round(nostats, 1),
            "speedup": round(nostats / stats, 3),
            "ms_per_step_with": round(args.batch / stats * 1e3, 2),
            "ms_per_step_without": round(args.batch / nostats * 1e3, 2),
        }))


if __name__ == "__main__":
    main()
