"""``jax.shard_map`` as ONE program, for the tests of the sharded paths.

Called eagerly, a shard_map runs its body an operation at a time, each
compiled and dispatched over the eight virtual devices, and ``jax.grad``
of it does the same for the backward: tens of seconds for a toy attention.
Under ``jax.jit``, which is how every user runs it (``jit(hvd.shard(step))``),
it is traced and compiled once."""

import jax


def shard_map(f, **kw):
    return jax.jit(jax.shard_map(f, **kw))
