"""Collective correctness vs local math — the reference's core test matrix
(test_tensorflow.py:56-247 allreduce, :386-433 allgather, :435-507 broadcast,
:626+ fp16 compression), rebuilt for the in-mesh SPMD path on a virtual
8-chip mesh."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

DTYPES = [jnp.float32, jnp.int32, jnp.bfloat16]


def _per_chip_values(hvd, shape, dtype, seed=0):
    """A distinct deterministic tensor per chip, stacked on axis 0."""
    n = hvd.num_chips()
    rng = np.random.RandomState(seed)
    x = rng.randint(-10, 10, size=(n,) + shape).astype(np.float64)
    return jnp.asarray(x, dtype=dtype)


def test_allreduce_sum(hvd):
    for dtype in DTYPES:
        x = _per_chip_values(hvd, (4, 5), dtype)
        fn = hvd.shard(lambda v: hvd.allreduce(v, average=False),
                       in_specs=P("hvd"), out_specs=P("hvd"))
        out = fn(x)
        expected = jnp.sum(x.astype(jnp.float32), axis=0, keepdims=True)
        expected = jnp.broadcast_to(expected, (hvd.num_chips(), 4, 5))
        # Out is stacked per-chip results along the sharded axis0; per-chip
        # shape (4,5) stacked back. Shard axis0: input rows are per-chip.
        np.testing.assert_allclose(np.asarray(out, np.float32).reshape(8, -1)[0],
                                   np.asarray(expected, np.float32).reshape(8, -1)[0],
                                   rtol=1e-2)
        for r in range(8):
            np.testing.assert_allclose(np.asarray(out, np.float32)[r],
                                       np.asarray(expected, np.float32)[r],
                                       rtol=1e-2)


def test_allreduce_average(hvd):
    x = _per_chip_values(hvd, (3,), jnp.float32, seed=1)
    fn = hvd.shard(lambda v: hvd.allreduce(v, average=True),
                   in_specs=P("hvd"), out_specs=P("hvd"))
    out = np.asarray(fn(x))
    expected = np.mean(np.asarray(x), axis=0)
    for r in range(8):
        np.testing.assert_allclose(out[r], expected, rtol=1e-5)


def test_allreduce_fp16_compression(hvd):
    x = _per_chip_values(hvd, (16,), jnp.float32, seed=2) / 8.0
    fn = hvd.shard(
        lambda v: hvd.allreduce(v, average=False, compression=hvd.Compression.fp16),
        in_specs=P("hvd"), out_specs=P("hvd"))
    out = np.asarray(fn(x))
    expected = np.sum(np.asarray(x), axis=0)
    assert out.dtype == np.float32  # decompressed back
    for r in range(8):
        np.testing.assert_allclose(out[r], expected, rtol=1e-2, atol=1e-2)


def test_grouped_allreduce_fused(hvd):
    """Fused variant batching many tensors — analog of the reference's fused
    tests (test_tensorflow.py:87-120) that force fusion-buffer batching."""
    shapes = [(3,), (2, 2), (5,), (1,)]
    xs = [_per_chip_values(hvd, s, jnp.float32, seed=10 + i)
          for i, s in enumerate(shapes)]

    def step(*vs):
        outs = hvd.grouped_allreduce(list(vs), average=False)
        return tuple(outs)

    fn = hvd.shard(step, in_specs=tuple(P("hvd") for _ in xs),
                   out_specs=tuple(P("hvd") for _ in xs))
    outs = fn(*xs)
    for x, out in zip(xs, outs):
        expected = np.sum(np.asarray(x), axis=0)
        for r in range(8):
            np.testing.assert_allclose(np.asarray(out)[r], expected, rtol=1e-5)


def test_grouped_allreduce_small_threshold(hvd):
    """Tiny fusion threshold forces multiple buckets (threshold sweep path,
    reference HOROVOD_FUSION_THRESHOLD)."""
    xs = [_per_chip_values(hvd, (64,), jnp.float32, seed=20 + i)
          for i in range(4)]

    def step(*vs):
        return tuple(hvd.grouped_allreduce(list(vs), average=False,
                                           threshold_bytes=64 * 4))

    fn = hvd.shard(step, in_specs=tuple(P("hvd") for _ in xs),
                   out_specs=tuple(P("hvd") for _ in xs))
    outs = fn(*xs)
    for x, out in zip(xs, outs):
        expected = np.sum(np.asarray(x), axis=0)
        np.testing.assert_allclose(np.asarray(out)[3], expected, rtol=1e-5)


def test_chained_allreduce_matches_uncained_and_isolates_nonfinite(hvd):
    """The overlap chain (round 5, collective_ops._chained_allreduce) is
    numerics-neutral: chained buckets produce the same sums as the
    unchained structure, and a non-finite gradient in one bucket must NOT
    leak into any other tensor (the gate is where(isfinite(s), s, 0)*0 —
    exactly 0.0 even when the chained-on reduction is inf/NaN)."""
    xs = [_per_chip_values(hvd, (8,), jnp.float32, seed=40 + i)
          for i in range(6)]

    # depths 3 and 0, forced through the planner seam
    chain3 = hvd.AdaptivePlanner(default_depth=3)

    def step_chain(*vs):
        return tuple(hvd.grouped_allreduce(list(vs), average=False,
                                           planner=chain3))

    def step_plain(*vs):
        return tuple(hvd.grouped_allreduce(
            list(vs), average=False,
            planner=hvd.AdaptivePlanner(default_depth=0)))

    specs = tuple(P("hvd") for _ in xs)
    # (each under jit, as a training step holds them: one program, and the
    # compiler given its chance to fold the gate away)
    chained = jax.jit(hvd.shard(step_chain, in_specs=specs, out_specs=specs))
    a = chained(*xs)
    b = jax.jit(hvd.shard(step_plain, in_specs=specs, out_specs=specs))(*xs)
    for ca, cb in zip(a, b):
        np.testing.assert_array_equal(np.asarray(ca), np.asarray(cb))

    # An empty inexact leaf must not break the gate (it is skipped as a
    # gate source — review r5: reshape(-1)[0] on size 0 raised at trace).
    # Replicated spec: XLA pins zero-size arrays replicated regardless.
    with_empty = xs + [jnp.zeros((0,), jnp.float32)]
    specs7 = tuple(P("hvd") for _ in xs) + (P(),)

    def step_empty(*vs):
        return tuple(hvd.grouped_allreduce(list(vs), average=False,
                                           planner=chain3))

    out7 = jax.jit(hvd.shard(step_empty, in_specs=specs7, out_specs=specs7))(
        *with_empty)
    assert out7[-1].shape == (0,)

    # Poison the LAST leaf (reduced in the FIRST chained bucket — reverse
    # order — so its result gates every later bucket): the other five
    # tensors must come back finite and exact.
    xs_bad = list(xs)
    xs_bad[-1] = xs_bad[-1].at[0, 0].set(jnp.nan).at[1, 1].set(jnp.inf)
    out = chained(*xs_bad)
    for x, o in zip(xs[:-1], out[:-1]):
        expected = np.sum(np.asarray(x), axis=0)
        for r in range(hvd.num_chips()):
            np.testing.assert_allclose(np.asarray(o)[r], expected, rtol=1e-5)
    assert not np.isfinite(np.asarray(out[-1])).all()  # poison stayed put


def test_grouped_allreduce_mixed_dtypes(hvd):
    """Dtype changes must break buckets (reference fuses same-dtype only)."""
    a = _per_chip_values(hvd, (4,), jnp.float32, seed=30)
    b = _per_chip_values(hvd, (4,), jnp.bfloat16, seed=31)
    c = _per_chip_values(hvd, (4,), jnp.float32, seed=32)

    def step(x, y, z):
        return tuple(hvd.grouped_allreduce([x, y, z], average=False))

    fn = hvd.shard(step, in_specs=(P("hvd"),) * 3, out_specs=(P("hvd"),) * 3)
    oa, ob, oc = fn(a, b, c)
    np.testing.assert_allclose(np.asarray(oa)[0], np.sum(np.asarray(a), 0), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(ob, np.float32)[0],
                               np.sum(np.asarray(b, np.float32), 0), rtol=1e-1)
    np.testing.assert_allclose(np.asarray(oc)[5], np.sum(np.asarray(c), 0), rtol=1e-5)


def test_allgather(hvd):
    x = _per_chip_values(hvd, (2, 3), jnp.float32, seed=3)
    fn = hvd.shard(hvd.allgather, in_specs=P("hvd"), out_specs=P("hvd"))
    out = fn(x)
    # each chip gathers all 8 × (2,3) → (16,3); stacked over chips → (128, 3)
    out = np.asarray(out).reshape(8, 16, 3)
    expected = np.asarray(x).reshape(16, 3)
    for r in range(8):
        np.testing.assert_allclose(out[r], expected, rtol=1e-6)


def test_broadcast(hvd):
    for root in (0, 3, 7):
        x = _per_chip_values(hvd, (4,), jnp.float32, seed=4 + root)
        fn = hvd.shard(lambda v: hvd.broadcast(v, root_rank=root),
                       in_specs=P("hvd"), out_specs=P("hvd"))
        out = np.asarray(fn(x))
        expected = np.asarray(x)[root]
        for r in range(8):
            np.testing.assert_allclose(out[r], expected, rtol=1e-6)


def test_broadcast_int(hvd):
    x = _per_chip_values(hvd, (4,), jnp.int32, seed=9)
    fn = hvd.shard(lambda v: hvd.broadcast(v, root_rank=2),
                   in_specs=P("hvd"), out_specs=P("hvd"))
    out = np.asarray(fn(x))
    assert out.dtype == np.int32
    for r in range(8):
        np.testing.assert_array_equal(out[r], np.asarray(x)[2])


def test_allreduce_grad(hvd):
    """grad(allreduce) == allreduce(grad) — reference test_tensorflow.py:321-346."""
    x = _per_chip_values(hvd, (4,), jnp.float32, seed=5)

    def loss(v):
        summed = hvd.allreduce(v, average=False)
        return jnp.sum(summed * summed)

    fn = hvd.shard(jax.grad(loss), in_specs=P("hvd"), out_specs=P("hvd"))
    g = np.asarray(fn(x))
    s = np.sum(np.asarray(x), axis=0)
    # d/dx_r sum_over_chips? Each chip computes sum(s*s) locally; total
    # implicit objective is per-chip; cotangent of psum fans back via psum:
    # grad = psum(2*s) = 8 * 2 * s... per-chip grad of its own loss is 2*s
    # propagated through psum -> psum of 2*s across chips = 16*s.
    expected = 2 * s * 8
    for r in range(8):
        np.testing.assert_allclose(g[r], expected, rtol=1e-4)


def test_broadcast_grad(hvd):
    """grad(broadcast): root accumulates everyone's cotangent; non-root gets
    zero — reference tensorflow/mpi_ops.py:146-161, test :591-624."""
    root = 1
    x = _per_chip_values(hvd, (3,), jnp.float32, seed=6)

    def loss(v):
        b = hvd.broadcast(v, root_rank=root)
        return jnp.sum(b * 2.0)

    fn = hvd.shard(jax.grad(loss), in_specs=P("hvd"), out_specs=P("hvd"))
    g = np.asarray(fn(x))
    for r in range(8):
        if r == root:
            np.testing.assert_allclose(g[r], np.full(3, 2.0 * 8), rtol=1e-5)
        else:
            np.testing.assert_allclose(g[r], np.zeros(3), atol=1e-6)


def test_allgather_grad(hvd):
    """grad(allgather) slices this rank's piece of the cotangent (after
    summing replicas) — reference tests :470-507."""
    x = _per_chip_values(hvd, (2,), jnp.float32, seed=7)  # global (8, 2)
    w = jnp.arange(16.0).reshape(8, 2)

    def loss(v):  # v is this chip's (1, 2) block
        g = hvd.allgather(v)  # (8, 2)
        return jnp.sum(g * w)

    fn = hvd.shard(jax.grad(loss), in_specs=P("hvd"), out_specs=P("hvd"))
    g = np.asarray(fn(x))  # stacked back to (8, 2)
    # every chip's loss contains the term w[r]·x_r; the all_gather transpose
    # slices this chip's cotangent and psum accumulates the 8 copies
    for r in range(8):
        np.testing.assert_allclose(g[r], 8 * np.asarray(w)[r], rtol=1e-5)


def test_eager_single_process(hvd):
    """Eager process-level collectives degenerate correctly at size()==1
    (the reference behaves identically under mpirun -np 1)."""
    x = jnp.arange(6.0).reshape(2, 3)
    np.testing.assert_allclose(np.asarray(hvd.allreduce(x, average=True)), np.asarray(x))
    np.testing.assert_allclose(np.asarray(hvd.allgather(x)), np.asarray(x))
    np.testing.assert_allclose(np.asarray(hvd.broadcast(x, 0)), np.asarray(x))


def test_sparse_allreduce(hvd):
    """Sparse path = allgather of values+indices (reference
    tensorflow/__init__.py:67-78)."""
    vals = _per_chip_values(hvd, (2, 4), jnp.float32, seed=8)
    idx = jnp.tile(jnp.array([[0, 2]], jnp.int32), (hvd.num_chips(), 1))

    def step(v, i):
        gv, gi = hvd.allreduce_sparse(v[0], i[0], average=False)
        return hvd.sparse_to_dense(gv, gi.reshape(-1), 4)[None]

    fn = hvd.shard(step, in_specs=(P("hvd"), P("hvd")), out_specs=P("hvd"))
    out = np.asarray(fn(vals, idx)).reshape(8, 4, 4)
    dense = np.zeros((4, 4), np.float32)
    v = np.asarray(vals)
    for r in range(8):
        dense[0] += v[r, 0]
        dense[2] += v[r, 1]
    for r in range(8):
        np.testing.assert_allclose(out[r], dense, rtol=1e-5)


def test_alltoall_in_mesh(hvd):
    """Compiled alltoall: each worker's dim-0 block j goes to worker j
    (lax.all_to_all over the data axis)."""
    import jax
    from jax.sharding import PartitionSpec as P

    n = hvd.size() if hvd.size() > 1 else 8  # virtual chips
    fn = hvd.shard(lambda v: hvd.alltoall(v),
                   in_specs=P("hvd"), out_specs=P("hvd"))
    # global [n*n]: worker i holds rows [i*n, (i+1)*n); after alltoall
    # worker i holds row j*n+i for each j -> global out[k] = (k%n)*n + k//n
    x = jnp.arange(n * n, dtype=jnp.float32)
    out = np.asarray(fn(x))
    expect = np.array([(k % n) * n + k // n for k in range(n * n)],
                      dtype=np.float32)
    np.testing.assert_array_equal(out, expect)


def test_alltoall_in_mesh_rejects_splits(hvd):
    from jax.sharding import PartitionSpec as P

    fn = hvd.shard(lambda v: hvd.alltoall(v, splits=[1] * 8),
                   in_specs=P("hvd"), out_specs=P("hvd"))
    with pytest.raises(Exception, match="eager path"):
        fn(jnp.arange(8, dtype=jnp.float32))


def test_grouped_allreduce_eager_fuses(hvd, monkeypatch):
    """Eager grouped_allreduce must run ONE process collective per bucket,
    not one per tensor (round-1 verdict: the per-tensor loop was exactly
    the latency the fusion buffer amortises)."""
    from horovod_tpu.ops import collective_ops

    calls = []
    real = collective_ops._eager_process_reduce

    def counting(x):
        calls.append(np.shape(x))
        return real(x)

    monkeypatch.setattr(collective_ops, "_eager_process_reduce", counting)
    tensors = [jnp.full((3, 2), float(i)) for i in range(6)]
    outs = hvd.grouped_allreduce(tensors, average=False)
    for i, o in enumerate(outs):
        np.testing.assert_allclose(np.asarray(o), np.full((3, 2), float(i)))
    assert len(calls) == 1, f"expected 1 fused call, got {len(calls)}"

    # dtype change forces a second bucket (reference same-dtype fusion rule)
    calls.clear()
    mixed = [jnp.ones((4,), jnp.float32), jnp.ones((4,), jnp.float32),
             jnp.ones((4,), jnp.int32)]
    hvd.grouped_allreduce(mixed, average=False)
    assert len(calls) == 2, f"expected 2 buckets, got {len(calls)}"
