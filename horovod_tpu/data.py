"""Input-pipeline sharding helpers — the DistributedSampler pattern.

The reference ships no loader of its own; its contract is "shard your data
by rank" via ``DistributedSampler(num_replicas=hvd.size(), rank=hvd.rank())``
(reference README.md:218-219, examples/pytorch_imagenet_resnet50.py:93-96).
These helpers implement that contract for array/iterator pipelines feeding
JAX, at both granularities:

* process-level sharding (``shard_arrays`` / ``ShardedBatches``) — each host
  loads only its slice (what DistributedSampler does);
* within the host, ``hvd.shard``'s batch specs split the per-host batch over
  local chips, so the global batch is ``batch_per_chip × num_chips()``.
"""

from __future__ import annotations

import queue
import threading
from typing import Any, Callable, Iterable, Iterator, Sequence

import numpy as np

from horovod_tpu import basics
from horovod_tpu.utils import profiling


def shard_arrays(*arrays, drop_remainder: bool = True):
    """Return each array's slice for this process (strided, like
    DistributedSampler without shuffle).

    With ``drop_remainder`` every process gets the same length (required for
    SPMD lockstep — mismatched step counts hang collectives, the failure
    mode the reference's stall checker exists to diagnose).
    """
    rank, size = basics.rank(), basics.size()
    outs = []
    n_min = min(len(a) for a in arrays) if arrays else 0
    per = n_min // size if drop_remainder else None
    for a in arrays:
        s = a[rank::size]
        outs.append(s[:per] if per is not None else s)
    return outs[0] if len(outs) == 1 else tuple(outs)


class ShardedBatches:
    """Iterate epoch batches of a process-sharded dataset.

    ``batch_per_chip`` follows the reference's per-accelerator batch-size
    convention; the yielded batch is sized for all chips this process
    drives (feed it straight to an ``hvd.shard``-wrapped step).
    """

    def __init__(self, *arrays: Sequence, batch_per_chip: int,
                 shuffle: bool = True, seed: int = 0,
                 drop_remainder: bool = True):
        self.arrays = shard_arrays(*arrays, drop_remainder=drop_remainder)
        if len(arrays) == 1:
            self.arrays = (self.arrays,)
        self.batch = batch_per_chip * basics.local_num_chips()
        self.shuffle = shuffle
        self.seed = seed
        self._epoch = 0

    def __len__(self) -> int:
        return len(self.arrays[0]) // self.batch

    def __iter__(self) -> Iterator[tuple]:
        n = len(self.arrays[0])
        idx = np.arange(n)
        if self.shuffle:
            # Same convention as DistributedSampler.set_epoch: reshuffle per
            # epoch, deterministically, identically across restarts.
            rng = np.random.RandomState(self.seed + self._epoch)
            rng.shuffle(idx)
        self._epoch += 1
        for lo in range(0, n - self.batch + 1, self.batch):
            sel = idx[lo:lo + self.batch]
            yield tuple(np.asarray(a)[sel] for a in self.arrays)


class BackgroundLoader:
    """Run a batch producer on a daemon thread behind a bounded queue.

    The reference delegated loading to framework DataLoaders whose worker
    processes overlapped IO with compute; on TPU the analog is simply
    keeping the host's Python loop out of the device's way.  Wraps any
    iterable (e.g. :class:`ShardedBatches`, or a generator doing real IO /
    augmentation): production runs ahead of consumption up to ``depth``
    batches, so host-side loading overlaps device steps.

    A producer exception is re-raised on the consumer thread at the point
    of ``next()`` — never swallowed.  Iterating again restarts the source
    (a new epoch for ``ShardedBatches``).
    """

    _DONE = object()

    def __init__(self, source: Iterable, depth: int = 2):
        if depth < 1:
            raise ValueError("depth must be >= 1")
        self._source = source
        self._depth = depth

    def __len__(self) -> int:
        return len(self._source)  # type: ignore[arg-type]

    def __iter__(self) -> Iterator:
        q: queue.Queue = queue.Queue(maxsize=self._depth)
        stop = threading.Event()

        def put_or_stop(item) -> bool:
            # Every producer put honors the stop event — including the
            # terminal DONE/exception ones, or an abandoning consumer with
            # a full queue would strand this thread (and its queued
            # batches) forever.
            while not stop.is_set():
                try:
                    q.put(item, timeout=0.1)
                    return True
                except queue.Full:
                    continue
            return False

        def produce() -> None:
            try:
                source = iter(self._source)
                while True:
                    # what the source costs, on this thread's line of a
                    # profiler trace; the wait for a free slot is outside
                    with profiling.annotate(profiling.LOADER_PRODUCE):
                        item = next(source, self._DONE)
                    if not put_or_stop(item) or item is self._DONE:
                        return
            except BaseException as e:  # noqa: BLE001 — relayed to consumer
                put_or_stop(e)

        t = threading.Thread(target=produce, name="hvd-loader", daemon=True)
        t.start()
        try:
            while True:
                # no batch was ready: the consumer's share of an input wait
                with profiling.annotate(profiling.LOADER_WAIT):
                    item = q.get()
                if item is self._DONE:
                    return
                if isinstance(item, BaseException):
                    raise item
                yield item
        finally:
            stop.set()


def prefetch_to_device(iterator: Iterable, size: int = 2,
                       sharding: Any = None,
                       device_put: Callable | None = None) -> Iterator:
    """Double-buffer host batches onto the device(s).

    Eagerly issues ``jax.device_put`` for up to ``size`` upcoming batches
    before yielding the current one, so the host-to-device transfer of
    batch N+1 rides under the compute of batch N (the reference relied on
    framework loaders + CUDA streams for the same overlap; XLA's async
    dispatch gives it to us once the puts are issued early).

    ``sharding`` may be a ``jax.sharding.Sharding`` (e.g. the result of
    ``hvd.data_sharding(ndim)``) applied to every leaf, or a pytree of
    shardings matching the batch structure.  Without it, leaves land on
    the default device and the jitted step's in_specs perform the split.

    On the CPU *simulation* backend
    (``--xla_force_host_platform_device_count``), sharded puts complete
    SYNCHRONOUSLY before yielding: async multi-device transfer programs
    interleaved with a compiled step's collectives can starve XLA's
    in-process collective rendezvous past its hard abort (rendezvous.cc
    termination timeout, "Expected N threads to join the rendezvous,
    but only N-1 arrived").  Overlap is a no-op on a simulated backend,
    so nothing is lost — and ``sharding=`` is safe everywhere.
    """
    import jax

    put = device_put or jax.device_put
    # CPU sim: see the note above — complete each sharded transfer before
    # any step may run its collectives.
    sync = sharding is not None and jax.default_backend() == "cpu"
    buf: list = []
    it = iter(iterator)

    def enqueue(n: int) -> None:
        for _ in range(n):
            try:
                batch = next(it)
            except StopIteration:
                return
            # the copy was issued (and, on the CPU simulation, finished)
            with profiling.annotate(profiling.H2D_PUT):
                out = (put(batch, sharding) if sharding is not None
                       else put(batch))
                if sync:
                    jax.block_until_ready(out)
            buf.append(out)

    enqueue(size)
    while buf:
        yield buf.pop(0)
        enqueue(1)
