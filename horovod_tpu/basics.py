"""Process identity and topology — the TPU-native replacement for ``mpirun``.

The reference derives rank/local_rank/cross_rank by ``MPI_Init_thread`` plus
communicator splits (``MPI_Comm_split_type(SHARED)`` for the node-local
communicator, ``MPI_Comm_split`` for the cross-node one — reference:
horovod/common/operations.cc:1465-1532), with one OS process per accelerator
launched by ``mpirun``.

On TPU there is no launcher: the pod runtime hands every JAX process its
coordinates (``jax.process_index()``/``jax.process_count()``) and each process
drives *all* the chips attached to its host.  That single difference shapes the
whole design, so we expose BOTH granularities explicitly:

* **process level** (``rank``/``size``/``local_rank``/``local_size``) — mirrors
  the reference's process semantics for everything that happens in eager
  Python: data sharding, rank-0 checkpointing, logging, eager collectives.
  ``rank()==0`` is the reference's coordinator rank.
* **chip level** (``num_chips``/``chip_ranks``) — the data-parallel width used
  *inside* compiled programs.  The SPMD mesh axis ``"hvd"`` spans all chips;
  learning-rate scaling and gradient averaging divide by ``num_chips()``, the
  analog of the reference's ``hvd.size()`` when one process drove one GPU.

``cross_rank``/``cross_size`` map the reference's inter-node communicator onto
TPU slice topology (slice index / number of slices) and feed the hierarchical
ICI+DCN reduction (see parallel/hierarchy.py).
"""

from __future__ import annotations

import atexit
import dataclasses
import os
import sys
import threading

# jax is imported inside the functions that need it: the package root
# resolves lazily (see __init__.py) so that engine-only consumers and
# freshly spawned worker ranks don't pay the jax import before their
# control-plane rendezvous.


class NotInitializedError(RuntimeError):
    """Raised when the API is used before ``init()``.

    Mirrors the reference's ``CheckInitialized`` → ``NOT_INITIALIZED_ERROR``
    (horovod/common/operations.cc:256-263, 1929-1934).
    """

    def __init__(self) -> None:
        super().__init__(
            "horovod_tpu has not been initialized; call horovod_tpu.init() first."
        )


@dataclasses.dataclass(frozen=True)
class Topology:
    """Immutable snapshot of the pod-slice topology taken at ``init()``."""

    rank: int              # this process's index among job processes
    size: int              # number of job processes
    local_rank: int        # index of this process among processes on its host
    local_size: int        # processes on this host (JAX: 1 per host)
    cross_rank: int        # slice index of this process's chips
    cross_size: int        # number of slices in the job
    num_chips: int         # total accelerator count (data-parallel width)
    local_num_chips: int   # chips driven by this process
    chips_per_slice: int
    member_pids: tuple     # jax process indices forming this job (subset or
                           # all; rank == member_pids.index(process_index))


_lock = threading.Lock()
_topology: Topology | None = None


def _detect_slices(devices) -> tuple[int, int]:
    """Return (slice_index_of_first_local_device, num_slices).

    Multi-slice TPU jobs expose ``device.slice_index``; single-slice jobs and
    CPU simulation do not, in which case every chip is in slice 0.  This is the
    analog of the reference's cross-node communicator split
    (operations.cc:1499-1532) with "slice" standing in for "node": ICI links
    chips within a slice, DCN links slices.
    """
    import jax

    slice_ids = sorted({getattr(d, "slice_index", 0) for d in devices})
    local = jax.local_devices()
    my_slice = getattr(local[0], "slice_index", 0) if local else 0
    return slice_ids.index(my_slice), max(len(slice_ids), 1)


def init(*, distributed: bool | None = None, coordinator_address: str | None = None,
         num_processes: int | None = None, process_id: int | None = None,
         mesh_axes: dict[str, int] | None = None,
         ranks: list[int] | None = None, comm=None) -> None:
    """Initialize horovod_tpu — the analog of ``hvd.init()``.

    Unlike the reference (which boots MPI, reference operations.cc:1435-1663),
    no launcher is required: topology comes from the TPU pod runtime.  For
    multi-host jobs outside a managed pod environment, pass
    ``coordinator_address``/``num_processes``/``process_id`` (or set the
    standard JAX env vars) and we call ``jax.distributed.initialize``.

    ``mesh_axes`` adds model-parallel axes (name → size) to the global mesh
    next to the data axis, e.g. ``{"tp": 4}``; data-parallel width becomes
    ``num_chips / prod(mesh_axes)``.

    ``ranks`` restricts the job to a subset of the jax processes — the
    analog of ``hvd.init(comm=[ranks])`` building a sub-communicator
    (reference common/__init__.py:58-84, operations.cc:1469-1483): this
    process's ``rank()`` becomes its position in the list and ``size()``
    the list length; the global mesh and eager collectives span only the
    member processes' devices.  Every member must pass the same list.
    Unlike the reference (which falls back to MPI_COMM_WORLD with a
    warning), a NON-member calling ``init(ranks=...)`` raises — there is
    no world communicator to fall back to once the mesh is restricted.
    Collectives that still require the full jax job under a subset (the
    legacy ``HVD_TPU_EAGER_REDUCE=gather`` transport) raise clearly.

    ``comm`` is the reference's parameter spelling (``hvd.init(comm=[0, 2])``,
    common/__init__.py:58-67): a list is treated exactly like ``ranks``;
    an mpi4py communicator has no TPU analog and raises with direction.

    Safe to call more than once (subsequent calls are no-ops), matching
    ``InitializeHorovodOnce`` (reference operations.cc:1907-1925).
    """
    global _topology
    import jax

    if comm is not None:
        if ranks is not None:
            raise ValueError("pass either ranks= or comm=, not both")
        if hasattr(comm, "Get_rank"):  # duck-typed mpi4py communicator
            raise NotImplementedError(
                "init(comm=<mpi4py communicator>) has no TPU analog (there "
                "is no MPI underneath); pass the member process indices as "
                "a list instead — init(comm=[0, 2]) or init(ranks=[0, 2])")
        try:
            comm = [int(r) for r in comm]
        except TypeError:
            raise TypeError(
                f"init(comm=...) takes a list of process indices (reference "
                f"common/__init__.py:58-67), got {type(comm).__name__}")
        # Reference parity: an empty list means the full job (COMM_WORLD,
        # reference common/__init__.py:65-66).
        ranks = comm or None

    with _lock:
        if _topology is not None:
            return
        # the work as a span (``hvd_setup_init``; a repeated call writes
        # none), and the compile ledger listening before anything compiles
        from horovod_tpu.utils import profiling

        profiling.listen()
        with profiling.span(profiling.SETUP_INIT):
            # Decide on jax.distributed BEFORE touching any jax API that would
            # initialise the XLA backend (initialize() refuses to run after that).
            if coordinator_address is None:
                coordinator_address = os.environ.get("JAX_COORDINATOR_ADDRESS")
            if num_processes is None and "JAX_NUM_PROCESSES" in os.environ:
                num_processes = int(os.environ["JAX_NUM_PROCESSES"])
            if process_id is None and "JAX_PROCESS_ID" in os.environ:
                process_id = int(os.environ["JAX_PROCESS_ID"])
            want_dist = distributed
            if want_dist is None:
                want_dist = coordinator_address is not None
            if want_dist:
                try:
                    jax.distributed.initialize(
                        coordinator_address=coordinator_address,
                        num_processes=num_processes,
                        process_id=process_id,
                    )
                except RuntimeError:
                    # Either the user already initialised jax.distributed (fine —
                    # topology below is still correct) or the backend was touched
                    # first in a genuinely single-process run.
                    if jax.process_count() == 1 and (num_processes or 1) > 1:
                        raise
            pid, nproc = jax.process_index(), jax.process_count()
            if ranks is not None:
                members = tuple(int(r) for r in ranks)
                if len(set(members)) != len(members) or not members or any(
                        r < 0 or r >= nproc for r in members):
                    raise ValueError(
                        f"init(ranks={list(ranks)}): ranks must be distinct "
                        f"process indices in [0, {nproc})")
                if pid not in members:
                    raise ValueError(
                        f"process {pid} is not in init(ranks={list(ranks)}); "
                        f"every member passes the same list and non-members "
                        f"must not init this job (no COMM_WORLD fallback on "
                        f"the TPU rebuild — the mesh is restricted to members)")
                rank_, size_ = members.index(pid), len(members)
            else:
                members = tuple(range(nproc))
                rank_, size_ = pid, nproc
            devices = [d for d in jax.devices()
                       if getattr(d, "process_index", 0) in set(members)]
            local = jax.local_devices()
            cross_rank, cross_size = _detect_slices(devices)
            # JAX runs one process per host, so the host-local "communicator"
            # contains exactly this process; local_rank mirrors the reference's
            # node-local rank used for device pinning (N/A on TPU, kept for API
            # parity with reference common/__init__.py:104-121).
            topo = Topology(
                rank=rank_,
                size=size_,
                local_rank=0,
                local_size=1,
                cross_rank=cross_rank,
                cross_size=cross_size,
                num_chips=len(devices),
                local_num_chips=len(local),
                chips_per_slice=max(len(devices) // max(cross_size, 1), 1),
                member_pids=members,
            )
            # Build the global mesh BEFORE publishing topology so a mesh failure
            # leaves the process cleanly un-initialized (re-init can retry);
            # mirrors comm setup at reference operations.cc:1484-1532.
            from horovod_tpu import mesh as _mesh

            _mesh.build_global_mesh(mesh_axes, cross_size=cross_size,
                                    devices=devices)
            _topology = topo
            if nproc > 1:
                _note_exit_status()
    atexit.register(_at_exit)  # reference common/__init__.py:69


# ``sys.exit``'s argument once the interpreter's top level has taken it
# (``_Leaving``), and the ``sys.exit`` that ``_note_exit_status`` wrapped.
_exit_status = None
_sys_exit = None


class _Leaving(SystemExit):
    """What ``sys.exit`` raises in the main thread of a ``jax.distributed``
    process.  An exit handler is told neither the exit code nor that there
    is one, so the exception notes its own when the interpreter's top level
    has taken it: it is freed there, with no frame of Python running.  One
    that was caught (a CLI's ``except SystemExit:``) is freed under the
    frame that caught it and notes nothing, and the process goes on to the
    exit it makes later.  ``raise SystemExit(n)`` and ``exit()`` are not
    seen."""

    def __del__(self):
        global _exit_status
        try:
            sys._getframe(1)
        except ValueError:
            _exit_status = self.code


def _note_exit_status() -> None:
    global _sys_exit
    if _sys_exit is not None:       # once, however often init() runs
        return
    _sys_exit = sys.exit

    def exit(status=None):
        if threading.current_thread() is threading.main_thread():
            raise _Leaving(status)
        _sys_exit(status)           # ends that thread, not the process

    sys.exit = exit


def _crash_code() -> int:
    """The exit code of a process that is leaving by an uncaught exception
    or ``sys.exit(<not 0>)``, else 0."""
    crash = getattr(sys, "last_exc", None) or getattr(sys, "last_value", None)
    # (an interactive session shows an exception and carries on)
    if isinstance(crash, Exception) and not hasattr(sys, "ps1"):
        return 1
    if _exit_status is None or isinstance(_exit_status, int):
        return (_exit_status or 0) & 0xFF
    return 1


def _at_exit() -> None:
    """``shutdown`` at interpreter exit, and a crashed rank's way out.

    jax's own exit handler runs after this one and waits at
    ``jax.distributed``'s shutdown barrier until every process of the job
    is there (five minutes by default): right for a job whose ranks finish
    at different times, but a rank that has crashed would sit there, alive
    to the launcher, while the others train on or wait in a collective for
    it.  It leaves at once with its code instead, so that the launcher sees
    the first abnormal exit when it happens and ends the job
    (``run.py``).  ``os._exit`` runs none of the handlers registered before
    ``init()``: the logging handlers, where a crashed rank's last lines
    are, are flushed here, and what else a handler held is lost with the
    rank."""
    shutdown()
    code = _crash_code()
    if not code:
        return
    from jax._src import distributed

    if distributed.global_state.client is not None:
        import logging

        logging.shutdown()
        sys.stdout.flush()
        sys.stderr.flush()
        os._exit(code)


def shutdown() -> None:
    """Tear down background machinery — analog of ``horovod_shutdown``
    (reference operations.cc:1947-1985).  Idempotent."""
    global _topology
    with _lock:
        if _topology is None:
            return
        _topology = None
    from horovod_tpu.core import engine as _engine

    _engine.shutdown_engine()
    from horovod_tpu.core import device_reduce as _device_reduce

    _device_reduce.reset()
    from horovod_tpu import mesh as _mesh

    _mesh.reset()


def is_initialized() -> bool:
    return _topology is not None


def _apply_resize(new_rank: int, new_size: int) -> None:
    """Elastic membership update (elastic.reconfigure): republish
    ``rank()``/``size()`` for the surviving membership so data sharding,
    rank-0 gating, and LR scaling see the new world.  A no-op before
    ``init()`` (engine-only workers track membership through the engine
    itself).  The device topology (num_chips, mesh) is left as initialized
    — the compiled SPMD plane cannot re-form in-process and elastic mode
    documents that scope (docs/fault_tolerance.md)."""
    global _topology
    with _lock:
        if _topology is None:
            return
        _topology = dataclasses.replace(_topology, rank=new_rank,
                                        size=new_size)


def _topo() -> Topology:
    if _topology is None:
        raise NotInitializedError()
    return _topology


def rank() -> int:
    """Process rank (0 is the coordinator; use for checkpoint/log gating)."""
    return _topo().rank


def size() -> int:
    """Number of processes (data shards for host-side input pipelines)."""
    return _topo().size


def local_rank() -> int:
    return _topo().local_rank


def local_size() -> int:
    return _topo().local_size


def cross_rank() -> int:
    """Slice index — reference's inter-node rank (operations.cc:1516-1532)."""
    return _topo().cross_rank


def cross_size() -> int:
    """Number of slices — reference's inter-node size."""
    return _topo().cross_size


def num_chips() -> int:
    """Total accelerators = data-parallel width (use for LR scaling)."""
    return _topo().num_chips


def local_num_chips() -> int:
    return _topo().local_num_chips


def chips_per_slice() -> int:
    return _topo().chips_per_slice


def member_process_ids() -> tuple:
    """jax process indices forming this job (all processes unless
    ``init(ranks=...)`` restricted it); this process's ``rank()`` is its
    position here."""
    return _topo().member_pids


def subset_active() -> bool:
    """True when ``init(ranks=...)`` restricted the job to a process subset."""
    t = _topo()
    import jax

    return len(t.member_pids) != jax.process_count()


def stall_report() -> list:
    """Structured stall report from the eager control plane's coordinator:
    ``[(tensor_name, [missing ranks]), ...]`` for every collective stuck
    past the stall-warning window (``HOROVOD_STALL_WARNING_TIME``).

    The reference logs this condition as an unparseable WARNING string
    (CheckForStalledTensors, operations.cc:1366-1412); here monitoring/
    test code reads it programmatically.  Empty off the coordinator, when
    nothing is stalled, or when the eager engine was never started (the
    compiled SPMD path cannot stall asymmetrically — XLA lockstep)."""
    _topo()
    from horovod_tpu.core import engine as _engine

    return _engine.stall_report()


def failure_report() -> dict | None:
    """Structured peer-failure report from the eager control plane — the
    peer-death analog of :func:`stall_report` / ``hvd.divergence_report()``
    (docs/fault_tolerance.md "Fast failure detection").

    ``None`` while every peer is healthy (or the eager engine never
    started); after a peer death is detected — socket EOF from a SIGKILLed
    or preempted rank, heartbeat silence past
    ``HVD_TPU_HEARTBEAT_TIMEOUT_MS``, a hardened-frame CRC/desync
    violation, or a mixed-build version skew — every surviving rank
    returns::

        {"failed_rank": 1, "cause": "connection_reset",
         "detail": "rank 1 closed the control-plane connection (EOF)",
         "last_heard_ms": 4.2, "last_collective": "grad.step3"}

    Pending collectives fail with :class:`hvd.CollectiveError` carrying the
    same report, and after ``HVD_TPU_ABORT_GRACE_MS`` the process exits
    with the restartable code (75) so ``python -m horovod_tpu.run
    --max-restarts N`` relaunches from the last complete checkpoint."""
    _topo()
    from horovod_tpu.core import engine as _engine

    return _engine.failure_report()


def coord_state() -> dict | None:
    """The coordinator state replicated onto this rank — non-``None`` only
    on the designated standby of an elastic job (docs/fault_tolerance.md
    "Coordinator failover").

    The coordinator streams its authoritative-only state to the standby in
    ``STATE`` frames each monitor tick; this returns the newest snapshot::

        {"epoch": 3, "joins_admitted": 1, "verify_checked": 120,
         "verify_tick": 124, "lru_order": [5, 2, 0, ...]}

    ``epoch`` is the load-bearing field — a promotion resumes from
    ``max(local, replicated) + 1`` so stale frames from the previous reign
    are rejected wire-level.  The rest aligns the successor's verifier and
    response-cache bookkeeping and gives tests a replication probe.  The
    coordinator reports its own outbound snapshot; plain (non-standby)
    workers and engines that never started report ``None``."""
    _topo()
    from horovod_tpu.core import engine as _engine

    return _engine.coord_state()


def cache_stats() -> dict:
    """Response-cache counters for this rank's eager control plane
    (docs/response_cache.md): ``{"hits", "misses", "evictions",
    "bypassed_ticks", "entries", "capacity"}``.

    ``hits`` counts collectives whose negotiated verdict was served from the
    coordinated response cache (announced as a bit instead of full request
    metadata); ``bypassed_ticks`` counts coordination cycles this rank
    announced entirely via the bit vector.  All zeros when the eager engine
    was never started or ``HOROVOD_CACHE_CAPACITY=0`` — the compiled
    ``hvd.shard`` path never negotiates, so it never caches."""
    _topo()
    from horovod_tpu.core import engine as _engine

    return _engine.cache_stats()


def control_plane_stats() -> dict:
    """Control-plane topology and tick-latency stats for this rank's eager
    engine (docs/benchmarks.md "Control-plane scaling")::

        {"role": "tree_root", "depth": 2, "fanout": 64,
         "tick_p50_ms": 0.8, "tick_p99_ms": 2.1,
         "frames_per_tick": 64.0, "ticks": 1200, "frames_rx": 76800}

    ``role`` names this rank's position in the control-plane topology
    (``star_coordinator`` / ``star_worker`` below the tree threshold,
    ``tree_root`` / ``tree_member`` above it, ``loopback`` single-process,
    ``none`` before the eager engine starts).  ``tick_p50_ms`` /
    ``tick_p99_ms`` are negotiated coordination-tick latencies over a
    rolling window; ``frames_per_tick`` is the scaling number — O(groups)
    on a tree root where the star coordinator pays O(size).  Each tick
    also lands as a TICK instant on the Chrome timeline
    (``HOROVOD_TIMELINE``)."""
    _topo()
    from horovod_tpu.core import engine as _engine

    return _engine.control_plane_stats()


def mpi_threads_supported() -> bool:
    """API-parity shim for reference common/__init__.py:147-154.

    There is no MPI on the TPU path; the runtime is always safe to drive from
    multiple Python threads, so this is unconditionally True.
    """
    _topo()
    return True
