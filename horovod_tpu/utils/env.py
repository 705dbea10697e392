"""Environment-variable knob system.

The reference framework configures its runtime exclusively through ``HOROVOD_*``
environment variables read once at background-thread startup (reference:
horovod/common/operations.cc:1447-1618, operations.h:53-58).  We keep the same
names (so reference users' launch scripts keep working) and add ``HVD_TPU_*``
aliases; the TPU-specific defaults differ where the hardware does:

* ``HOROVOD_FUSION_THRESHOLD`` — fusion-buffer byte budget (default 64 MiB,
  reference operations.cc:167).  On TPU this bounds the size of the flat
  bucket we concatenate gradients into before a single ``psum``.
* ``HOROVOD_CYCLE_TIME`` — background coordination tick in ms (default 5.0,
  reference operations.cc:155).  With the response cache on, cache-hit
  enqueues wake the cycle immediately; the tick paces uncached names only.
* ``HOROVOD_CACHE_CAPACITY`` — eager response-cache entries (default 1024,
  mirroring the cache upstream grew in 0.16, one minor version past our
  0.15.1 snapshot; 0 disables).  Once a collective's (op, name, dtype,
  shape, root) signature has been coordinated once, ranks re-announce it as
  a bit in a compact bit vector and the coordinator answers from cache —
  no negotiation metadata, no cycle-tail latency (docs/response_cache.md).
* ``HOROVOD_TIMELINE`` — path for the Chrome-tracing timeline (reference
  operations.cc:1556-1560).
* ``HOROVOD_STALL_CHECK_DISABLE`` — disable the 60 s stall warning
  (reference operations.cc:1603-1606).
* ``HOROVOD_HIERARCHICAL_ALLREDUCE`` — two-level reduction; on TPU this means
  intra-slice ICI reduce-scatter + inter-slice DCN allreduce + ICI all-gather
  (reference operations.cc:1025-1177 did NCCL-intra + MPI-inter).
* ``HVD_TPU_CONNECT_TIMEOUT`` — control-plane rendezvous budget in seconds
  (default 300; read in core/src/controller.cc): both the worker connect
  retry and the coordinator accept quorum share it, so a dead peer becomes
  an error on every rank instead of a hang.
* ``HVD_TPU_STALL_ABORT_SECONDS`` — stall escalation (warn -> abort): when
  set > 0 and a tensor has been pending longer, the coordinator aborts the
  job with the restartable exit code (default 75, EX_TEMPFAIL; override
  with ``HVD_TPU_STALL_ABORT_EXIT_CODE``) so ``python -m horovod_tpu.run
  --max-restarts N`` relaunches instead of the job hanging forever
  (docs/fault_tolerance.md).  0/unset keeps the warn-only reference
  behaviour.
* ``HVD_TPU_HEARTBEAT_MS`` — control-plane heartbeat interval (default
  250; 0 disables).  A native monitor thread on every rank pings its peers
  each interval; socket EOF/RST and heartbeat silence both become a
  structured peer-failure report (``hvd.failure_report()``), a coordinated
  abort of every survivor's pending collectives, and — after
  ``HVD_TPU_ABORT_GRACE_MS`` — the restartable exit, dropping detection of
  a SIGKILLed/preempted rank from the 60 s stall window to sub-second
  (docs/fault_tolerance.md "Fast failure detection").
* ``HVD_TPU_HEARTBEAT_TIMEOUT_MS`` — silence past this (default 10000)
  declares a still-connected peer dead (network partition, wedged host).
  Only consulted when nothing is waiting unread in the socket buffer, so a
  merely CPU-starved job is never declared dead.
* ``HVD_TPU_ABORT_GRACE_MS`` — delay (default 1000) between a peer-failure
  abort and the process's restartable exit (code 75), giving training code
  time to observe ``hvd.failure_report()``.  Negative: report only, never
  exit.
* ``HVD_TPU_WIRE_VERSION`` — testing override of the advertised hardened-
  frame protocol version (core/src/message.h); mismatched peers are
  rejected at the connect handshake with a structured version-skew error.
* ``HVD_TPU_ELASTIC`` — in-place elastic recovery (default off): a dead
  non-coordinator rank triggers a coordinated RECONFIG shrink (survivors
  re-form the engine in the same process) instead of the full
  abort-and-restart; the launcher's ``--elastic`` mode relaunches only the
  dead rank, which rejoins via JOIN (docs/fault_tolerance.md "In-place
  recovery").
* ``HVD_TPU_MIN_SIZE`` — survivor-count floor (default 1) below which an
  elastic job falls back to the legacy exit-75 full restart.
* ``HVD_TPU_STANDBY`` — pin the coordinator-failover standby to a specific
  rank (default: the lowest non-coordinator rank that advertised a standby
  listen port in its HELLO).  The coordinator streams its authoritative
  state to the standby each monitor tick; on coordinator death the standby
  promotes itself to rank 0 on its pre-announced port and the survivors
  re-rendezvous there (docs/fault_tolerance.md "Coordinator failover").
* ``HVD_TPU_COORD_FILE`` — path where the ACTIVE coordinator publishes its
  ``host port epoch`` endpoint (exported automatically by ``python -m
  horovod_tpu.run --elastic``).  ``elastic.join`` re-reads it every retry,
  so a relaunched rank finds the promoted standby after a succession
  instead of knocking on the dead rank 0's port forever.
* ``HVD_TPU_RECONFIG_TIMEOUT_MS`` — bound (default 30000) on in-place
  reconfiguration (resize acknowledgement + re-rendezvous); expiry falls
  back to abort-and-restart, keeping the nothing-blocks-forever guarantee.
* ``HVD_TPU_TREE_ENABLE`` — hierarchical coordinator tree (default off):
  workers split into per-aggregator groups whose relay sidecars fold each
  group's tick into ONE frame for rank 0, dropping the root's per-tick
  load from O(size) to O(groups) (core/src/tree.cc, docs/benchmarks.md
  "Control-plane scaling").  Even when enabled, jobs below
  ``HVD_TPU_TREE_THRESHOLD`` run the rank-0 star bit-for-bit unchanged.
* ``HVD_TPU_TREE_FANOUT`` — worker ranks per aggregator group (default
  64; the 4096-rank fleet-simulator sweep lands at 128 — root cost is
  per-aggregator-frame, so bigger fleets want wider groups).
* ``HVD_TPU_TREE_THRESHOLD`` — job size at which an enabled tree activates
  (default 256, where the measured star tick starts crowding the 5 ms
  cycle budget).
* ``HVD_TPU_TREE_AGG_MAP`` — aggregator endpoints,
  ``"0=host:port|host:port,1=host:port,..."`` (primary, optional standby
  after ``|``; one entry per group).  Exported automatically by ``python
  -m horovod_tpu.run`` when the tree activates; set by hand only for
  multi-host relay placement (tree.py has the format/parse helpers).
  An enabled tree with no map falls back to the star — the map's presence
  is part of activation, so ranks can never disagree about topology.
* ``HVD_TPU_TREE_EXCHANGE_TIMEOUT_MS`` / ``HVD_TPU_TREE_DETACH_TIMEOUT_MS``
  / ``HVD_TPU_TREE_REATTACH_BUDGET_MS`` / ``HVD_TPU_TREE_PROMOTE_SILENCE_MS``
  — tree failure-detection tuning (read in core/src/tree.cc): a member's
  per-tick exchange bound (default 30000), how long the root carries a
  silent aggregator before declaring its group lost (default 10000), a
  member's budget for re-attaching to the promoted standby (default
  30000), and the member-knock silence after which a standby concludes
  its primary is wedged — not merely slow — and promotes (default 1000;
  this, not EOF, bounds recovery from a SIGSTOP'd aggregator).
* ``HVD_TPU_DEVICE_HEADROOM_MB`` — device-memory headroom estimate (MB)
  the schedule planner budgets against, overriding the
  ``device.memory_stats()`` probe.  Needed on AOT/CPU/sim paths (no
  stats) and recommended on multi-host jobs (a live probe could diverge
  across ranks; the override keeps the plan identical everywhere).
* ``HVD_TPU_FAULT_*`` — deterministic fault injection (faults.py),
  including the wire-level chaos injectors
  ``HVD_TPU_FAULT_WIRE_{DROP,CORRUPT,PARTITION,HALFCLOSE}`` =
  ``"<rank>[:<frame>][@<epoch>]"`` (the ``@<epoch>`` suffix keys a plan to
  one membership epoch so an elastic shrink past the fault runs clean) and
  the persist-path injectors ``HVD_TPU_FAULT_PERSIST_KILL_STEP`` (die
  after the payload is durable but before ``_COMMIT``),
  ``HVD_TPU_FAULT_TORN_MANIFEST_STEP`` (truncated ``_COMMIT``),
  ``HVD_TPU_FAULT_ENOSPC_STEP`` (commit raises ``ENOSPC``) and
  ``HVD_TPU_FAULT_SLOW_DISK_MS`` (added latency per commit).
* ``HVD_TPU_CKPT_ASYNC`` — async persist (default off): ``save`` only
  snapshots device state to host at the step barrier; a background persist
  thread writes the payload and the ``_COMMIT`` manifest, so the train loop
  stalls for the snapshot only, not the disk write
  (docs/fault_tolerance.md "Async & peer-replicated checkpointing").
* ``HVD_TPU_CKPT_REPLICATE`` — peer replication (default off): each save
  also pushes the pickled snapshot over the control plane (SHARD_PUT
  frames) to a neighbor rank's host memory; an elastic restore consults
  the in-memory replica first and touches disk only when no replica from
  the current membership epoch survives (replication.py).
* ``HVD_TPU_CKPT_STALENESS_STEPS`` — bounded-staleness assertion window
  (default 0 = unchecked): tooling and the checkpoint soak fail if the
  newest complete checkpoint ever lags the training step by more than this
  many steps.
* ``HVD_TPU_BULK_PLANE`` — rank-to-rank bulk data plane (default ON): each
  rank binds a second TCP listener whose port rides its HELLO; replica
  shards stream peer-to-peer under coordinator-issued tickets instead of
  relaying through the rank-0 star (dataplane.py,
  docs/fault_tolerance.md "Bulk data plane").  ``0`` forces every shard
  transfer onto the legacy SHARD_PUT relay.
* ``HVD_TPU_BULK_CHUNK_BYTES`` — CRC32-framed chunk size on a bulk stream
  (default 1 MiB).  Each chunk is independently checksummed so a corrupt
  link is detected mid-transfer, not after megabytes of garbage land.
* ``HVD_TPU_BULK_TIMEOUT_MS`` — per-socket-operation bound (default 5000)
  on bulk connect/send/recv, so a partitioned peer aborts the transfer —
  falling down the direct -> relay -> disk chain — instead of hanging it.
* ``HVD_TPU_BULK_MAX_BYTES`` — hard ceiling (default 1 GiB) on a single
  bulk stream's advertised total; an oversized header is rejected as a
  structured error naming the peer and transfer id, never buffered.
* ``HVD_TPU_FAULT_BULK_{DROP,CORRUPT,TRUNCATE}`` — data-plane chaos
  injectors (faults.py): ``"<rank>[:<nth>]"`` makes rank <rank>'s <nth>
  bulk send vanish, carry a flipped chunk CRC, or close mid-stream —
  exercising the fallback chain deterministically.
* ``HVD_TPU_CTX_LAYOUT`` — long-context sequence layout override for
  ``plan_context`` (``auto``/``plain``/``zigzag``; default ``auto``: causal
  multi-shard workloads route to zigzag, everything else to plain).
  Malformed values degrade to ``auto`` with a warning.
* ``HVD_TPU_CTX_BLOCK_Q`` / ``HVD_TPU_CTX_BLOCK_K`` — pin the flash kernel
  tile sizes the ContextPlan would otherwise derive (and VMEM-fit-clamp)
  from the workload.  Overrides are still clamped to the VMEM budget —
  the knob cannot reintroduce the r5 block_k=4096 S=32768 OOM.  Unset or
  malformed: planner-derived.
* ``HVD_TPU_CTX_REMAT`` — force the long-context remat policy (``1`` =
  full-layer remat, ``0`` = none) instead of the planner's
  activation-bytes-vs-headroom decision.  Unset: planner-decided.
* ``HVD_TPU_SERVE_SLOTS`` — KV-cache slots per serving replica (default
  8): the continuous-batching scheduler's fixed decode batch width
  (docs/inference.md "Serving loop").
* ``HVD_TPU_SERVE_BUCKETS`` — prefill length menu as ascending CSV
  (default ``16,32,64,128``): a prompt compiles against the smallest
  bucket that holds it, bounding the prefill compile cache at
  len(buckets) programs.  Malformed entries degrade to the default with
  a warning.
* ``HVD_TPU_SERVE_MAX_LEN`` — per-slot KV-cache length (default 256);
  sequences reaching it are evicted with ``finish_reason="max_seq_len"``.
* ``HVD_TPU_SERVE_QUEUE_HIGH`` — autoscaler GROW threshold: queued
  requests per replica (default 16).
* ``HVD_TPU_SERVE_P99_MS`` — autoscaler GROW threshold on p99
  time-to-first-token in ms (default 500; 0 disables the latency
  trigger).
* ``HVD_TPU_SERVE_IDLE_S`` — autoscaler SHRINK trigger: seconds of empty
  queue + idle slots before releasing a replica (default 5).
* ``HVD_TPU_SERVE_MIN_REPLICAS`` / ``HVD_TPU_SERVE_MAX_REPLICAS`` —
  replica-count clamp for the autoscaler (defaults 1 / 8).
* ``HVD_TPU_SERVE_COOLDOWN_S`` — minimum seconds between autoscale
  decisions (default 2; a join costs a RECONFIG round, so the policy
  must not flap).
* ``HVD_TPU_SERVE_QPS`` / ``HVD_TPU_SERVE_DURATION_S`` — the
  self-generated Poisson workload a ``run.py --serve`` replica drives
  (defaults 20 QPS for 3 s).
* ``HVD_TPU_SERVE_BACKEND`` — ``transformer`` (default: small real model
  on the KV-cache decode path) or ``stub`` (jax-free token automaton)
  for ``python -m horovod_tpu.serving`` replicas.
* ``HVD_TPU_SERVE_MODEL`` — path of a JSON file of ``TransformerConfig``
  fields (``TransformerConfig.from_dict``) for the ``transformer``
  backend to serve in place of its small default model: layer types, a
  sliding window, a parallel block, sparse experts and the experts held
  here are all fields (docs/inference.md).
"""

from __future__ import annotations

import os

DEFAULT_FUSION_THRESHOLD = 64 * 1024 * 1024
DEFAULT_CYCLE_TIME_MS = 5.0
# Reference pads fused hierarchical buffers to local_size * 64 elements
# (FUSION_BUFFER_ATOMIC_UNIT, operations.h:50).  On TPU we pad flat fusion
# buffers to the lane width (128) so XLA keeps the reduction fully vectorised.
FUSION_BUFFER_ATOMIC_UNIT = 128
STALL_WARNING_TIME_SECONDS = 60.0


def _get(name: str, default: str | None = None) -> str | None:
    """Look up HOROVOD_<name>, falling back to HVD_TPU_<name>."""
    return os.environ.get("HOROVOD_" + name, os.environ.get("HVD_TPU_" + name, default))


def fusion_threshold_bytes() -> int:
    raw = _get("FUSION_THRESHOLD")
    return int(raw) if raw else DEFAULT_FUSION_THRESHOLD


def cycle_time_ms() -> float:
    raw = _get("CYCLE_TIME")
    return float(raw) if raw else DEFAULT_CYCLE_TIME_MS


def timeline_path() -> str | None:
    return _get("TIMELINE")


DEFAULT_CACHE_CAPACITY = 1024


def cache_capacity() -> int:
    """``HOROVOD_CACHE_CAPACITY`` — response-cache entries (0 disables;
    default 1024, upstream 0.16's default).  docs/response_cache.md."""
    raw = _get("CACHE_CAPACITY")
    return int(raw) if raw not in (None, "") else DEFAULT_CACHE_CAPACITY


def stall_check_disabled() -> bool:
    return _get("STALL_CHECK_DISABLE") is not None


def stall_warning_seconds() -> float:
    """Stall-warning window; reference hardcodes 60 s (operations.cc:253) —
    exposed as a knob here mainly so tests can shrink it."""
    raw = _get("STALL_WARNING_TIME")
    return float(raw) if raw else STALL_WARNING_TIME_SECONDS


# Restartable abort (EX_TEMPFAIL): the launcher's supervision treats this
# exit as "transient, relaunch me" — the stall escalation and any rank that
# wants an explicit restart use it.
STALL_ABORT_EXIT_CODE = 75


def stall_abort_seconds() -> float:
    """Stall warn->abort escalation threshold; 0 (default) disables."""
    raw = _get("STALL_ABORT_SECONDS")
    return float(raw) if raw else 0.0


def stall_abort_exit_code() -> int:
    raw = _get("STALL_ABORT_EXIT_CODE")
    return int(raw) if raw else STALL_ABORT_EXIT_CODE


DEFAULT_HEARTBEAT_MS = 250.0
DEFAULT_HEARTBEAT_TIMEOUT_MS = 10000.0
DEFAULT_ABORT_GRACE_MS = 1000.0


def heartbeat_ms() -> float:
    """Control-plane heartbeat interval (``HVD_TPU_HEARTBEAT_MS``; 0
    disables peer-death detection).  Read natively in core/src/c_api.cc;
    this accessor exists for tests and tooling that reason about bounds."""
    raw = _get("HEARTBEAT_MS")
    return float(raw) if raw not in (None, "") else DEFAULT_HEARTBEAT_MS


def heartbeat_timeout_ms() -> float:
    """Heartbeat-silence death threshold (``HVD_TPU_HEARTBEAT_TIMEOUT_MS``)."""
    raw = _get("HEARTBEAT_TIMEOUT_MS")
    return float(raw) if raw not in (None, "") \
        else DEFAULT_HEARTBEAT_TIMEOUT_MS


def abort_grace_ms() -> float:
    """Grace between a peer-failure abort and the restartable process exit
    (``HVD_TPU_ABORT_GRACE_MS``; negative = report only, never exit)."""
    raw = _get("ABORT_GRACE_MS")
    return float(raw) if raw not in (None, "") else DEFAULT_ABORT_GRACE_MS


def hierarchical_allreduce() -> bool:
    raw = _get("HIERARCHICAL_ALLREDUCE")
    return bool(raw) and raw not in ("0", "false", "False")


DEFAULT_TREE_FANOUT = 64
DEFAULT_TREE_THRESHOLD = 256


def tree_enable() -> bool:
    """``HVD_TPU_TREE_ENABLE`` — opt into the hierarchical coordinator tree
    (default off: the rank-0 star stays bit-for-bit the shipped behaviour).
    Even when enabled, the tree activates only at ``tree_threshold()`` ranks
    and above — below it the plan is inactive and the star runs."""
    raw = _get("TREE_ENABLE")
    return bool(raw) and raw not in ("0", "false", "False")


def tree_fanout() -> int:
    """``HVD_TPU_TREE_FANOUT`` — worker ranks per aggregator group (default
    64).  Root per-tick cost is per-aggregator-frame, so larger fleets want
    wider groups: the fleet-simulator sweep (docs/benchmarks.md) lands at
    128 for 4096 ranks.  Values < 2 deactivate the tree."""
    raw = _get("TREE_FANOUT")
    return int(raw) if raw not in (None, "") else DEFAULT_TREE_FANOUT


def tree_threshold() -> int:
    """``HVD_TPU_TREE_THRESHOLD`` — job size at which an enabled tree
    activates (default 256, the width where the star's measured tick starts
    crowding the 5 ms cycle budget; docs/benchmarks.md).  Below it the
    rank-0 star runs unchanged."""
    raw = _get("TREE_THRESHOLD")
    return int(raw) if raw not in (None, "") else DEFAULT_TREE_THRESHOLD


def verify_schedule() -> bool:
    """``HVD_TPU_VERIFY_SCHEDULE`` — debug-mode cross-rank schedule
    verification (analysis/schedule.py): every submitted collective extends
    a rolling hash the coordinator compares across ranks, turning a
    divergent collective order into an immediate coordinated abort with a
    structured report instead of a stall-timeout hang."""
    raw = _get("VERIFY_SCHEDULE")
    return bool(raw) and raw not in ("0", "false", "False")


DEFAULT_VERIFY_INTERVAL_TICKS = 10


def verify_interval_ticks() -> int:
    """Coordinator ticks between cross-rank schedule checks
    (``HVD_TPU_VERIFY_INTERVAL_TICKS``; default 10 — ~50 ms at the default
    5 ms cycle, cheap enough to leave on for whole debug runs)."""
    raw = _get("VERIFY_INTERVAL_TICKS")
    return int(raw) if raw else DEFAULT_VERIFY_INTERVAL_TICKS


DEFAULT_MIN_SIZE = 1
DEFAULT_RECONFIG_TIMEOUT_MS = 30000.0


def elastic_enabled() -> bool:
    """``HVD_TPU_ELASTIC`` — in-place elastic recovery
    (docs/fault_tolerance.md "In-place recovery"): when a non-coordinator
    rank dies, survivors shrink to the new membership in the same process
    (RECONFIG broadcast + engine re-form) instead of exiting 75 for a full
    relaunch; the launcher's ``--elastic`` mode relaunches only the dead
    rank, which rejoins via JOIN.  Coordinator death and shrinks below
    ``HVD_TPU_MIN_SIZE`` keep the full-restart path.  Read natively in
    core/src/c_api.cc."""
    raw = _get("ELASTIC")
    return bool(raw) and raw not in ("0", "false", "False")


def min_size() -> int:
    """``HVD_TPU_MIN_SIZE`` — the survivor-count floor (default 1) below
    which an elastic job stops shrinking and falls back to the legacy
    abort-and-restart path (exit 75)."""
    raw = _get("MIN_SIZE")
    return int(raw) if raw not in (None, "") else DEFAULT_MIN_SIZE


def standby_rank() -> int:
    """``HVD_TPU_STANDBY`` — pinned coordinator-failover standby rank, or
    -1 for the default policy (lowest non-coordinator rank that advertised
    a standby listen port).  Read natively in core/src/controller.cc; this
    accessor exists for tests and tooling.  Malformed values degrade to the
    default policy."""
    raw = _get("STANDBY")
    if raw in (None, ""):
        return -1
    try:
        value = int(raw)
        return value if value >= 1 else -1
    except ValueError:
        return -1


def reconfig_timeout_ms() -> float:
    """``HVD_TPU_RECONFIG_TIMEOUT_MS`` — bound (default 30000) on the
    whole in-place reconfiguration: an unacknowledged resize event, or a
    re-rendezvous that cannot complete within it, falls back to
    abort-and-restart so nothing blocks forever (the PR-4 guarantee)."""
    raw = _get("RECONFIG_TIMEOUT_MS")
    return float(raw) if raw not in (None, "") \
        else DEFAULT_RECONFIG_TIMEOUT_MS


def ckpt_async() -> bool:
    """``HVD_TPU_CKPT_ASYNC`` — split checkpointing into *snapshot*
    (device->host at the step barrier) and *persist* (a background thread
    writes the payload and the ``_COMMIT`` manifest).  Default off: ``save``
    keeps the synchronous complete-or-invisible semantics PR 3 shipped."""
    raw = _get("CKPT_ASYNC")
    return bool(raw) and raw not in ("0", "false", "False")


def ckpt_replicate() -> bool:
    """``HVD_TPU_CKPT_REPLICATE`` — peer-replicate each rank's snapshot to
    a neighbor rank's host memory over the control plane (SHARD_PUT
    frames), so an elastic restore can skip disk entirely when a replica
    from the current membership epoch survives (replication.py)."""
    raw = _get("CKPT_REPLICATE")
    return bool(raw) and raw not in ("0", "false", "False")


def ckpt_staleness_steps() -> int:
    """``HVD_TPU_CKPT_STALENESS_STEPS`` — bounded-staleness window for the
    checkpoint soak and monitoring: the newest complete checkpoint must
    never lag the training step by more than this many steps.  0 (default)
    disables the assertion."""
    raw = _get("CKPT_STALENESS_STEPS")
    try:
        return max(0, int(raw)) if raw not in (None, "") else 0
    except ValueError:
        return 0


DEFAULT_BULK_CHUNK_BYTES = 1 << 20
DEFAULT_BULK_TIMEOUT_MS = 5000.0
DEFAULT_BULK_MAX_BYTES = 1 << 30


def bulk_plane() -> bool:
    """``HVD_TPU_BULK_PLANE`` — the rank-to-rank bulk data plane (default
    ON).  When on, replication shard payloads stream directly between peer
    bulk listeners under coordinator-issued tickets; the coordinator star
    carries only the control frames.  Off: every transfer takes the legacy
    SHARD_PUT relay through rank 0."""
    raw = _get("BULK_PLANE")
    return raw is None or raw not in ("0", "false", "False")


def bulk_chunk_bytes() -> int:
    """``HVD_TPU_BULK_CHUNK_BYTES`` — bulk-stream chunk size (default
    1 MiB); each chunk carries its own CRC32 so corruption is caught at
    chunk granularity."""
    raw = _get("BULK_CHUNK_BYTES")
    try:
        value = int(raw) if raw not in (None, "") else DEFAULT_BULK_CHUNK_BYTES
    except ValueError:
        return DEFAULT_BULK_CHUNK_BYTES
    return max(4096, value)


def bulk_timeout_ms() -> float:
    """``HVD_TPU_BULK_TIMEOUT_MS`` — per-operation socket bound (default
    5000) on the bulk plane: connect, each chunk send/recv, and the final
    ack all share it, so a dead or partitioned peer becomes an abort-and-
    fallback, never a hang."""
    raw = _get("BULK_TIMEOUT_MS")
    try:
        return float(raw) if raw not in (None, "") else DEFAULT_BULK_TIMEOUT_MS
    except ValueError:
        return DEFAULT_BULK_TIMEOUT_MS


def bulk_max_bytes() -> int:
    """``HVD_TPU_BULK_MAX_BYTES`` — ceiling (default 1 GiB) on one bulk
    stream's advertised payload; larger headers are structured errors."""
    raw = _get("BULK_MAX_BYTES")
    try:
        return int(raw) if raw not in (None, "") else DEFAULT_BULK_MAX_BYTES
    except ValueError:
        return DEFAULT_BULK_MAX_BYTES


def device_headroom_mb() -> float | None:
    """``HVD_TPU_DEVICE_HEADROOM_MB`` — device-memory headroom estimate
    (MB) the schedule planner budgets its chain live-range cost against,
    overriding the ``device.memory_stats()`` probe.  Set it on AOT/CPU/sim
    paths where no device exposes memory stats, and on multi-host jobs
    where a live probe could diverge across ranks (the plan must be
    identical everywhere — SPMD).  Unset/malformed: None (probe, or treat
    headroom as unknown); negative values clamp to 0 (no headroom)."""
    raw = _get("DEVICE_HEADROOM_MB")
    if raw in (None, ""):
        return None
    try:
        value = float(raw)
    except ValueError:
        import warnings

        name = ("HOROVOD_DEVICE_HEADROOM_MB"
                if "HOROVOD_DEVICE_HEADROOM_MB" in os.environ
                else "HVD_TPU_DEVICE_HEADROOM_MB")
        warnings.warn(
            f"{name}={raw!r} is not a number; ignoring the override "
            f"(headroom stays unknown)", RuntimeWarning, stacklevel=2)
        return None
    return max(value, 0.0)


_CTX_LAYOUTS = ("auto", "plain", "zigzag")


def ctx_layout() -> str:
    """``HVD_TPU_CTX_LAYOUT`` — long-context layout override consulted by
    ``ops.schedule_plan.plan_context``: ``plain``/``zigzag`` pin the
    sequence layout, ``auto`` (the default) lets the planner route causal
    multi-shard workloads to zigzag.  Malformed values degrade to ``auto``
    with a warning (launch-script typos must not fork the layout)."""
    raw = _get("CTX_LAYOUT")
    if raw in (None, ""):
        return "auto"
    value = raw.strip().lower()
    if value in _CTX_LAYOUTS:
        return value
    import warnings

    name = ("HOROVOD_CTX_LAYOUT" if "HOROVOD_CTX_LAYOUT" in os.environ
            else "HVD_TPU_CTX_LAYOUT")
    warnings.warn(
        f"{name}={raw!r} is not one of {_CTX_LAYOUTS}; falling back to "
        f"'auto'", RuntimeWarning, stacklevel=2)
    return "auto"


def _ctx_block(which: str) -> int | None:
    raw = _get("CTX_BLOCK_" + which)
    if raw in (None, ""):
        return None
    try:
        value = int(raw)
        if value <= 0:
            raise ValueError("non-positive block")
    except ValueError:
        import warnings

        name = ("HOROVOD_CTX_BLOCK_" + which
                if "HOROVOD_CTX_BLOCK_" + which in os.environ
                else "HVD_TPU_CTX_BLOCK_" + which)
        warnings.warn(
            f"{name}={raw!r} is not a positive integer; ignoring the "
            f"override (planner-derived tile)", RuntimeWarning, stacklevel=3)
        return None
    return value


def ctx_block_q() -> int | None:
    """``HVD_TPU_CTX_BLOCK_Q`` — pin the ContextPlan's flash ``block_q``
    (still VMEM-fit-clamped).  Unset/malformed: planner-derived."""
    return _ctx_block("Q")


def ctx_block_k() -> int | None:
    """``HVD_TPU_CTX_BLOCK_K`` — pin the ContextPlan's flash ``block_k``
    (still VMEM-fit-clamped, so the knob cannot reintroduce the r5
    block_k=4096 S=32768 OOM).  Unset/malformed: planner-derived."""
    return _ctx_block("K")


def ctx_remat_override() -> bool | None:
    """``HVD_TPU_CTX_REMAT`` — force the long-context remat policy (``1``
    full-layer remat, ``0`` none) instead of the planner's
    activation-vs-headroom decision.  Unset: None (planner-decided)."""
    raw = _get("CTX_REMAT")
    if raw in (None, ""):
        return None
    return raw not in ("0", "false", "False")


def _serve_number(name: str, default, cast, floor=None):
    """Shared numeric parse for the HVD_TPU_SERVE_* family: unset or
    malformed degrades to the default (with a warning for malformed) —
    a bad knob must never take a serving replica down."""
    raw = _get(name)
    if raw in (None, ""):
        return default
    try:
        value = cast(raw)
        if floor is not None and value < floor:
            raise ValueError("below floor")
    except ValueError:
        import warnings

        warnings.warn(
            f"HVD_TPU_{name}={raw!r} is not a valid value; using the "
            f"default {default}", RuntimeWarning, stacklevel=3)
        return default
    return value


def serve_model() -> str | None:
    """``HVD_TPU_SERVE_MODEL`` — path of a JSON file of
    ``TransformerConfig`` fields the ``transformer`` serving backend
    builds its model from; unset: the small default model."""
    return _get("SERVE_MODEL") or None


def serve_slots() -> int:
    """``HVD_TPU_SERVE_SLOTS`` — KV-cache slots per serving replica
    (default 8): the fixed decode batch width."""
    return _serve_number("SERVE_SLOTS", 8, int, floor=1)


def serve_buckets() -> tuple[int, ...]:
    """``HVD_TPU_SERVE_BUCKETS`` — ascending prefill length menu (CSV;
    default ``16,32,64,128``).  Malformed: default + warning."""
    raw = _get("SERVE_BUCKETS")
    if raw in (None, ""):
        return (16, 32, 64, 128)
    try:
        buckets = tuple(sorted(int(b) for b in raw.split(",") if b.strip()))
        if not buckets or any(b <= 0 for b in buckets):
            raise ValueError("empty or non-positive bucket")
    except ValueError:
        import warnings

        warnings.warn(
            f"HVD_TPU_SERVE_BUCKETS={raw!r} is not an ascending int CSV; "
            "using the default (16,32,64,128)", RuntimeWarning, stacklevel=3)
        return (16, 32, 64, 128)
    return buckets


def serve_max_len() -> int:
    """``HVD_TPU_SERVE_MAX_LEN`` — per-slot KV-cache length (default
    256); the over-length eviction bound."""
    return _serve_number("SERVE_MAX_LEN", 256, int, floor=2)


def serve_queue_high() -> float:
    """``HVD_TPU_SERVE_QUEUE_HIGH`` — autoscaler GROW threshold in queued
    requests per replica (default 16)."""
    return _serve_number("SERVE_QUEUE_HIGH", 16.0, float, floor=0.0)


def serve_p99_ms() -> float:
    """``HVD_TPU_SERVE_P99_MS`` — autoscaler GROW threshold on p99 TTFT
    in ms (default 500; 0 disables the latency trigger)."""
    return _serve_number("SERVE_P99_MS", 500.0, float, floor=0.0)


def serve_idle_s() -> float:
    """``HVD_TPU_SERVE_IDLE_S`` — idle seconds before the autoscaler
    SHRINKs (default 5)."""
    return _serve_number("SERVE_IDLE_S", 5.0, float, floor=0.0)


def serve_min_replicas() -> int:
    """``HVD_TPU_SERVE_MIN_REPLICAS`` — autoscaler floor (default 1)."""
    return _serve_number("SERVE_MIN_REPLICAS", 1, int, floor=1)


def serve_max_replicas() -> int:
    """``HVD_TPU_SERVE_MAX_REPLICAS`` — autoscaler ceiling (default 8)."""
    return _serve_number("SERVE_MAX_REPLICAS", 8, int, floor=1)


def serve_cooldown_s() -> float:
    """``HVD_TPU_SERVE_COOLDOWN_S`` — minimum seconds between autoscale
    decisions (default 2)."""
    return _serve_number("SERVE_COOLDOWN_S", 2.0, float, floor=0.0)


def serve_prefix_pages() -> int:
    """``HVD_TPU_SERVE_PREFIX_PAGES`` — shared-prefix KV cache slack in
    pages beyond the slots' own working set (default 0 = cache off):
    evicted requests' prompt-prefix chunks stay resident in up to this
    many pages for later admissions to attach to
    (serving/prefix_cache.py)."""
    return _serve_number("SERVE_PREFIX_PAGES", 0, int, floor=0)


def serve_page_tokens() -> int:
    """``HVD_TPU_SERVE_PAGE_TOKENS`` — tokens per KV page, the unit of
    prefix sharing (default 16).  ``HVD_TPU_SERVE_MAX_LEN`` must be a
    multiple when the prefix cache is on."""
    return _serve_number("SERVE_PAGE_TOKENS", 16, int, floor=1)


def serve_spec_k() -> int:
    """``HVD_TPU_SERVE_SPEC_K`` — speculative decoding draft window: the
    engine proposes this many tokens per slot per step (n-gram prompt
    lookup) and verifies them in one fixed-shape batched step (default
    0 = speculation off)."""
    return _serve_number("SERVE_SPEC_K", 0, int, floor=0)


def serve_slo_ms() -> float:
    """``HVD_TPU_SERVE_SLO_MS`` — default TTFT SLO in ms a routed model
    is judged against (serving/router.py ``ModelSpec``; default 100)."""
    return _serve_number("SERVE_SLO_MS", 100.0, float, floor=0.0)


def serve_qps() -> float:
    """``HVD_TPU_SERVE_QPS`` — Poisson arrival rate a ``--serve`` replica
    drives at itself (default 20)."""
    return _serve_number("SERVE_QPS", 20.0, float, floor=0.001)


def serve_duration_s() -> float:
    """``HVD_TPU_SERVE_DURATION_S`` — workload duration for a ``--serve``
    replica (default 3)."""
    return _serve_number("SERVE_DURATION_S", 3.0, float, floor=0.01)
