"""Step wrapper (``ops/collective_ops.shard`` under ``jax.jit``): host
milliseconds an optimizer step took to enqueue."""


def read(run):
    return 1e3 * sum(run.dispatch_s) / run.steps
