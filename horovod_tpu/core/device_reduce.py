"""Device-side process-level allreduce — the bandwidth-optimal eager data plane.

The reference's eager data plane delegates to ``MPI_Allreduce``
(reference operations.cc:1242-1268), a ring/recursive-halving reduction that
moves ~2n bytes per rank regardless of job size.  Round 2 of this rebuild
used allgather+host-sum instead — (P-1)*n received bytes per rank and a
host-CPU serial reduction.  This module restores bandwidth-optimality with
a reduce-scatter -> allgather over a one-device-per-process mesh:

* the reduce-scatter is spelled ``all_to_all`` + local sum (bandwidth-equal
  to ``lax.psum_scatter``: each rank receives (P-1)/P * n wire bytes) so the
  ACCUMULATION DTYPE is ours to choose — fp16/bf16 wires sum once in float32
  and round once, the half.cc staging semantics (the reference's custom
  fp16-sum MPI op, reference half.cc:43-76, exists for exactly this);
* the allgather of the reduced chunk moves another (P-1)/P * n;
* total ~2n * (P-1)/P per rank — the MPI ring number — with the reduction
  itself running on device, not the host.

int8 wire (per-rank scales, core/qwire.py): the quantized payload chunks
ride the same all_to_all (1 byte/elem); each rank dequant-sums its chunk in
f32 against the all-gathered per-tensor scales, then REQUANTIZES onto the
deterministic grid ``s2[t] = sum_p scale_p[t]`` (the sum always fits:
|sum_p s_p*q_p| <= s2*127, no amax round needed) so the return leg is int8
too.  Per-element error doubles from ``sum_p s_p/2`` to ``sum_p s_p``
(stage-2 rounding) — still one int8 grid step of the reduced value, carried
by error feedback on the optimizer path.  Non-finite ranks ship an inf/nan
scale, which makes ``s2`` non-finite and the dequantized output NaN on every
rank: overflowed gradients are never laundered into finite values.

Eligibility: every process must reach the same collectives in the same
order (the coordinator guarantees this for engine batches; eager callers
are SPMD by the same contract as ``multihost_utils``), and the dtype must
be device-representable without x64 — 8-byte dtypes stay on the legacy
allgather+host-sum path (core/executors.py).  Set
``HVD_TPU_EAGER_REDUCE=gather`` to force the legacy path everywhere (used
by the wire-byte microbench to measure the improvement).
"""

from __future__ import annotations

import os
import threading

import numpy as np

AXIS = "proc"

_lock = threading.Lock()
_mesh = None
_dense_cache: dict = {}
_int8_cache: dict = {}
_seg_cache: dict = {}
_gather_cache: dict = {}
_bcast_cache: dict = {}


def enabled() -> bool:
    """Device reduction is the default; HVD_TPU_EAGER_REDUCE=gather disables."""
    return os.environ.get("HVD_TPU_EAGER_REDUCE", "device") != "gather"


def require_full_job(op: str) -> None:
    """The legacy multihost_utils transport spans EVERY jax process; under
    a rank-subset job (init(ranks=...)) it would enroll non-members — the
    one shared guard every legacy-transport fallback calls before touching
    multihost_utils."""
    from horovod_tpu import basics

    if basics.is_initialized() and basics.subset_active():
        raise NotImplementedError(
            f"{op}: the legacy gather transport (HVD_TPU_EAGER_REDUCE="
            f"gather) spans all jax processes and cannot serve a "
            f"rank-subset job (init(ranks=...)); use the device data "
            f"plane (default)")


def reset() -> None:
    """Drop the cached mesh and compiled reducers (basics.shutdown)."""
    global _mesh
    with _lock:
        _mesh = None
        _dense_cache.clear()
        _int8_cache.clear()
        _seg_cache.clear()
        _gather_cache.clear()
        _bcast_cache.clear()


def _members() -> tuple:
    """jax process ids in the job, in rank order (subset-aware)."""
    import jax

    from horovod_tpu import basics

    if basics.is_initialized():
        return tuple(basics.member_process_ids())
    return tuple(range(jax.process_count()))


def _process_mesh():
    """(P,) mesh over the first local device of every JOB process.

    One device per process carries the eager wire: eager collectives have
    process-level semantics (one contribution per process, like one
    reference rank per host), so the remaining local devices take no part.
    Rank-subset jobs (``init(ranks=...)``) mesh only the member processes —
    the device data plane serves subsets natively, unlike the legacy
    ``multihost_utils`` transport which always spans the full jax job.
    """
    global _mesh
    import jax
    from jax.sharding import Mesh

    with _lock:
        if _mesh is None:
            first = {}
            for d in jax.devices():
                first.setdefault(d.process_index, d)
            devs = np.array([first[p] for p in _members()])
            _mesh = Mesh(devs, (AXIS,))
        return _mesh


def _my_position(mesh) -> int:
    import jax

    members = _members()
    assert mesh.size == len(members)
    return members.index(jax.process_index())


def _my_row_array(mesh, row: np.ndarray, n_cols: int):
    """Global (P, n_cols) array sharded on rows; this process owns one row."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    dev = mesh.devices.flat[_my_position(mesh)]
    local = jax.device_put(row.reshape(1, n_cols), dev)
    return jax.make_array_from_single_device_arrays(
        (mesh.size, n_cols), NamedSharding(mesh, P(AXIS, None)), [local])


def _replicated(mesh, arr: np.ndarray):
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    dev = mesh.devices.flat[_my_position(mesh)]
    local = jax.device_put(arr, dev)
    return jax.make_array_from_single_device_arrays(
        arr.shape, NamedSharding(mesh, P()), [local])


def _acc_dtype(dtype):
    import jax.numpy as jnp

    if dtype in (np.dtype(np.float16), np.dtype(np.float32)) or \
            dtype.name == "bfloat16":
        return jnp.float32
    if dtype.kind == "u":
        return jnp.uint32
    return jnp.int32  # ints and bool (bool sums like the host path: logical or)


def _dense_reducer(mesh, n_pad: int, dtype):
    """Compiled all_to_all -> f32/int32 local sum -> all_gather, (P,n)->(n,)."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import PartitionSpec as P

    key = (mesh.size, n_pad, dtype.name)
    fn = _dense_cache.get(key)
    if fn is not None:
        return fn
    P_n = mesh.size
    chunk = n_pad // P_n
    acc = _acc_dtype(dtype)

    def f(row):
        blocks = row.reshape(P_n, chunk)
        mine = lax.all_to_all(blocks, AXIS, split_axis=0, concat_axis=0)
        red = jnp.sum(mine.astype(acc), axis=0).astype(row.dtype)
        return lax.all_gather(red, AXIS, tiled=True)

    fn = jax.jit(jax.shard_map(
        f, mesh=mesh, in_specs=P(AXIS, None), out_specs=P(),
        check_vma=False))
    _dense_cache[key] = fn
    return fn


def process_allreduce(flat: np.ndarray) -> np.ndarray:
    """Sum ``flat`` (identical size/dtype on every process) across processes
    on device; ~2n wire bytes per rank.  Caller guarantees SPMD call order.

    8-byte dtypes are not representable without x64 — callers route those to
    the legacy host path."""
    if flat.dtype.itemsize == 8:
        raise ValueError("8-byte dtypes ride the legacy host path")
    mesh = _process_mesh()
    P_n = mesh.size
    n = flat.size
    if n == 0:
        return flat.copy()
    chunk = -(-n // P_n)
    n_pad = chunk * P_n
    row = np.zeros(n_pad, flat.dtype)
    row[:n] = flat.ravel()
    out = _dense_reducer(mesh, n_pad, flat.dtype)(
        _my_row_array(mesh, row, n_pad))
    return np.asarray(out.addressable_data(0))[:n]


def process_allgather(arr: np.ndarray) -> np.ndarray:
    """Gather each process's ``arr`` (identical shape/dtype everywhere) into
    a ``(P,) + arr.shape`` array over the job's device mesh — the device
    analog of ``multihost_utils.process_allgather``, subset-aware.
    8-byte dtypes (not device-representable without x64) ride internally
    as a uint8 view and are re-viewed on arrival."""
    if arr.dtype.itemsize == 8:
        wire = np.ascontiguousarray(arr).view(np.uint8)
        out = process_allgather(wire.reshape(-1))
        return np.ascontiguousarray(out).view(arr.dtype).reshape(
            (out.shape[0],) + arr.shape)
    import jax
    import jax.numpy as jnp  # noqa: F401  (kernel below traces lazily)
    from jax import lax
    from jax.sharding import PartitionSpec as P

    mesh = _process_mesh()
    n = arr.size
    key = (mesh.size, n, arr.dtype.name)
    fn = _gather_cache.get(key)
    if fn is None:
        def f(row):  # (1, n) local → (P, n) replicated
            return lax.all_gather(row[0], AXIS, tiled=False)

        fn = jax.jit(jax.shard_map(
            f, mesh=mesh, in_specs=P(AXIS, None), out_specs=P(),
            check_vma=False))
        _gather_cache[key] = fn
    out = fn(_my_row_array(mesh, np.ascontiguousarray(arr).reshape(1, n), n))
    return np.asarray(out.addressable_data(0)).reshape(
        (mesh.size,) + arr.shape)


def process_broadcast(arr: np.ndarray, root: int) -> np.ndarray:
    """Every process receives job-rank ``root``'s value, via a masked
    reduce-scatter -> allgather over the job mesh (~2n wire bytes; the mask
    zeroes every contribution but the root's, so the sum IS the broadcast
    — exact for every dtype since all other contributions are zero).
    8-byte dtypes ride internally as a uint8 view (byte sums cannot wrap:
    only the root contributes non-zero bytes)."""
    if arr.dtype.itemsize == 8:
        wire = np.ascontiguousarray(arr).view(np.uint8)
        return np.ascontiguousarray(
            process_broadcast(wire.reshape(-1), root)).view(
                arr.dtype).reshape(arr.shape)
    from horovod_tpu import basics

    me = basics.rank() if basics.is_initialized() else None
    if me is None:
        import jax

        me = _members().index(jax.process_index())
    src = arr if me == root else np.zeros_like(arr)
    return process_allreduce(np.ascontiguousarray(src).ravel()).reshape(
        arr.shape)


def _int8_reducer(mesh, n_pad: int, nt: int):
    """Compiled quantized reduce: int8 chunks a2a -> f32 dequant-sum ->
    requantize on s2=sum_p(scale_p) -> int8 all_gather -> dequant.  Returns
    the summed values in f32, replicated."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import PartitionSpec as P

    key = (mesh.size, n_pad, nt)
    fn = _int8_cache.get(key)
    if fn is not None:
        return fn
    P_n = mesh.size
    chunk = n_pad // P_n

    def f(qrow, srow, seg):
        # qrow (1, n_pad) int8; srow (1, nt) f32; seg (n_pad,) int32 repl.
        allsc = lax.all_gather(srow.reshape(nt), AXIS, tiled=False)  # (P, nt)
        s2 = jnp.sum(allsc, axis=0)                                  # (nt,)
        blocks = qrow.reshape(P_n, chunk)
        mine = lax.all_to_all(blocks, AXIS, split_axis=0, concat_axis=0)
        idx = lax.axis_index(AXIS)
        segc = lax.dynamic_slice_in_dim(seg, idx * chunk, chunk)     # (chunk,)
        se = jnp.take(allsc, segc, axis=1)                           # (P, chunk)
        red = jnp.sum(se * mine.astype(jnp.float32), axis=0)         # (chunk,)
        s2c = jnp.take(s2, segc)
        q2 = jnp.clip(jnp.round(red / s2c), -127.0, 127.0)
        # Non-finite red (a rank shipped an inf/nan scale) quantizes to 0;
        # the final dequant against the equally non-finite s2 restores NaN.
        q2 = jnp.where(jnp.isfinite(q2), q2, 0.0).astype(jnp.int8)
        g = lax.all_gather(q2, AXIS, tiled=True)                     # (n_pad,)
        return g.astype(jnp.float32) * jnp.take(s2, seg)

    fn = jax.jit(jax.shard_map(
        f, mesh=mesh,
        in_specs=(P(AXIS, None), P(AXIS, None), P()),
        out_specs=P(), check_vma=False))
    _int8_cache[key] = fn
    return fn


def process_allreduce_int8(scales: np.ndarray, qs: list[np.ndarray],
                           sizes: list[int]) -> np.ndarray:
    """Device-side quantized allreduce over per-rank (scale, int8) payloads
    (the WIRE_INT8 contract, core/qwire.py).  Returns the f32 SUM, flat.

    Per-element error <= sum_p scale_p[t] (one stage-2 int8 grid step of
    the reduced value on top of each rank's local rounding, already bounded
    by sum_p scale_p/2); values exactly on the grid at both stages — e.g.
    all-equal tensors — reduce exactly."""
    mesh = _process_mesh()
    P_n = mesh.size
    nt = len(sizes)
    n = int(sum(sizes))
    if n == 0:
        return np.zeros(0, np.float32)
    chunk = -(-n // P_n)
    n_pad = chunk * P_n
    qrow = np.zeros(n_pad, np.int8)
    qrow[:n] = np.concatenate([q.ravel() for q in qs]) if qs else []
    # The segment map depends only on (sizes, P): cache the device-resident
    # replicated array so the gradient hot path doesn't re-upload a 4-byte-
    # per-element index on every call (4x the int8 payload itself).
    seg_key = (P_n, tuple(sizes))
    seg_arr = _seg_cache.get(seg_key)
    if seg_arr is None:
        # Padding elements carry q=0 under tensor 0's scale: they
        # contribute 0 and are sliced off after the gather.
        seg = np.zeros(n_pad, np.int32)
        seg[:n] = np.repeat(np.arange(nt, dtype=np.int32),
                            np.asarray(sizes, np.int64))
        seg_arr = _replicated(mesh, seg)
        _seg_cache[seg_key] = seg_arr
    out = _int8_reducer(mesh, n_pad, nt)(
        _my_row_array(mesh, qrow, n_pad),
        _my_row_array(mesh, np.asarray(scales, np.float32), nt),
        seg_arr)
    return np.asarray(out.addressable_data(0))[:n]
