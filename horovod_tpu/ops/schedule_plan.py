"""Trace-time schedule planning for the compiled allreduce path.

``grouped_allreduce`` issues one psum per gradient.  Left alone, XLA's
all-reduce combiner merges them into one tuple all-reduce that can only run
after all of backward; dependency-chaining them into buckets
(ops/collective_ops.py:_chained_allreduce) lets the early buckets' reductions
run during the rest of backward, at the price of longer gradient live ranges.
Whether that trade pays differs by program, so it is decided **per traced
program**, here, from what is static at trace time: tensor shapes and dtypes
(the :class:`GradientManifest`), the data-parallel width (``lax.axis_size``
is a concrete Python int under trace), and a device-memory headroom estimate
(:func:`probe_headroom_mb`).  :class:`AdaptivePlanner` maps those to a
:class:`BucketPlan` -- the chain's depth, or the free-combining bypass -- and
``grouped_allreduce`` executes what the plan says.  No argument and no
environment name selects another policy: the same program at the same width
with the same headroom gets the same plan.

* At data-parallel **width 1** ``psum`` is the identity: there is nothing to
  overlap and a chain only constrains the scheduler (-4.3% img/s on the
  single-chip ResNet, round 5's chip).  The planner bypasses it.
* The chain raises peak HBM (a 468M transformer ran out of memory by 79 MB
  under a depth-4 chain and fit without it, same round).  The planner
  estimates the chain's extra live bytes and halves the depth, down to the
  bypass, until the estimate fits the headroom.
* With real width and slack headroom it keeps :data:`DEFAULT_CHAIN_DEPTH`.

Width 1 is not "nothing to decide".  With no collective between a gradient
and its update, XLA:TPU fuses the optimizer's arithmetic into the matmul
that produces the gradient, and such a fusion costs more than the matmul
and the update one after the other (PERF.md, PR 25: 25 ms of a 336 ms
decoder step; the four-chip program, where the all-reduce stands between
them, never had it).  So at width 1 the plan also names the gradients to
**materialise before the update** (``BucketPlan.materialized``):
``grouped_allreduce`` holds each behind its own
``jax.lax.optimization_barrier`` -- per leaf, never the tree as one tuple,
so no gradient lives longer than it does across chips -- and fusion cannot
cross it.  :func:`materialized_leaves` is the rule; it reads the manifest
alone.

``DistributedOptimizer`` and ``grouped_allreduce`` take ``planner=``, any
object with ``plan(manifest, width, headroom_mb) -> BucketPlan``.  It is the
seam through which tests and ``examples/overlap_audit.py`` force a depth
(``AdaptivePlanner(default_depth=0)``) to compare programs; no shipped code
path passes one.

Every decision is observable: :func:`overlap_plan` returns the last plan,
rank 0 logs one line per distinct decision, and -- when the native engine
is up with ``HOROVOD_TIMELINE`` set -- an ``OVERLAP_PLAN`` instant lands on
the timeline next to the CACHE_HIT/NEGOTIATED markers
(core/src/timeline.cc).
"""

from __future__ import annotations

import dataclasses
import logging
import threading

import jax
import jax.numpy as jnp

from horovod_tpu.utils import env

_log = logging.getLogger("horovod_tpu")

# Buckets in the chain where nothing argues for fewer (real width, headroom
# unknown or ample).
DEFAULT_CHAIN_DEPTH = 4

# Fraction of the total gradient bytes the dependency chain keeps extra-live
# at peak, per unit of (depth-1)/depth.  Calibrated against one
# measurement: the 468M transformer carries ~936 MB of bf16 gradients and
# OOMed by 79 MB under the depth-4 chain — 936 MB * (3/4) * (1/8) ≈ 88 MB,
# a deliberately conservative (over-)estimate of the measured deficit.  The
# (depth-1)/depth factor makes the estimate monotone in depth and exactly
# zero at depth <= 1, so degrading the chain provably shrinks the bill.
CHAIN_LIVE_FRACTION = 1.0 / 8.0

# Probed headroom is quantized DOWN to this granularity before planning.
# The plan must be identical on every rank of an SPMD job; coarse
# quantization absorbs small cross-host allocator jitter (for guarantees,
# set HVD_TPU_DEVICE_HEADROOM_MB — the probe is best-effort).
HEADROOM_QUANTUM_MB = 256.0


@dataclasses.dataclass(frozen=True)
class GradientManifest:
    """Static description of the gradient set a plan covers — per-tensor
    wire bytes and dtype names, known exactly at trace time."""

    nbytes: tuple[int, ...]
    dtypes: tuple[str, ...]

    @classmethod
    def from_tensors(cls, tensors) -> "GradientManifest":
        nbytes, dtypes = [], []
        for t in tensors:
            dt = jnp.result_type(t)
            size = 1
            for d in jnp.shape(t):
                size *= int(d)
            nbytes.append(size * dt.itemsize)
            dtypes.append(dt.name)
        return cls(nbytes=tuple(nbytes), dtypes=tuple(dtypes))

    @property
    def count(self) -> int:
        return len(self.nbytes)

    @property
    def total_bytes(self) -> int:
        return sum(self.nbytes)


@dataclasses.dataclass(frozen=True)
class BucketPlan:
    """One planner decision for one traced allreduce group.

    ``chain_depth`` <= 1 (or a single tensor) means the free-combining
    bypass: plain per-tensor psums whose batching XLA's combiner owns."""

    planner: str
    chain_depth: int
    width: int
    tensor_count: int
    total_bytes: int
    headroom_mb: float | None
    chain_extra_bytes: int
    reason: str
    # Width 1 only: indices (tensor order) of the gradients held behind a
    # per-leaf ``optimization_barrier`` before the update, and their bytes
    # (:func:`materialized_leaves`); empty wherever a collective already
    # stands between a gradient and its update.
    materialized: tuple[int, ...] = ()
    materialized_bytes: int = 0
    # Where ``headroom_mb`` came from (:func:`headroom_record`): a record
    # of circumstance and no part of the decision, so plans compare without
    # it; filled by :func:`plan_overlap`, not by a planner.
    headroom_source: str | None = dataclasses.field(default=None,
                                                    compare=False)
    headroom_probe: dict | None = dataclasses.field(default=None,
                                                    compare=False)
    plans_before: int = dataclasses.field(default=0, compare=False)

    @property
    def chained(self) -> bool:
        return self.chain_depth > 1 and self.tensor_count > 1

    def holding(self, manifest: GradientManifest, held) -> "BucketPlan":
        """This plan with the gradients ``held`` (indices into
        ``manifest``) materialised before the update."""
        held = tuple(held)
        return dataclasses.replace(
            self, materialized=held,
            materialized_bytes=sum(manifest.nbytes[i] for i in held))

    def as_dict(self) -> dict:
        d = dataclasses.asdict(self)
        d["chained"] = self.chained
        # the count says what the indices would, in one number a line
        d["materialized_leaves"] = len(d.pop("materialized"))
        return d


# Width 1: the smallest gradient worth materialising before its update.
# Fused into the matmul that produces the gradient, an update saves one
# write and one read of it (8 B a parameter) and is the cheaper form for as
# long as XLA:TPU keeps the update's state (parameter, moments) in on-chip
# memory across the fusion; past a leaf size it streams them from HBM inside
# the matmul's epilogue instead, which costs more than the matmul and a
# standalone update at the HBM roofline.  So:
#
#     gain(leaf) = epilogue_penalty(leaf) - 2 * leaf_bytes / HBM_bandwidth
#
# and the constant is where the measured gain changes sign on a TPU v5e (my
# chip runs, PR 25; PERF.md section 6).  A dense toy step (16 384 tokens,
# leaves of 0.5-64 MiB, adamw and sgd+momentum) loses 7-49 us a leaf by
# materialising at 8 and 12 MiB and gains 25-89 us a leaf at 16-32 MiB
# under both (at 64 MiB +326 under adamw, -44 under sgd: one reading each);
# its compiled text shows why (all three adamw outputs of a fused 8 MiB
# leaf live in fast memory, two of three at 12-16 MiB, one at 24).  The two
# real programs agree: every ResNet-50 leaf (at most 9.4 MB) costs 3-87 us
# more materialised (-0.9% img/s with all 161 held, -0.3% with the 29 of
# 1 MiB or more), and the 1.3B-width decoder's matrices (16 MiB-264 MB) gain
# 0.14-3.0 ms each, 25 ms of a 336 ms step.
MATERIALIZE_MIN_BYTES = 16 * 1024 * 1024


def materialized_leaves(manifest: GradientManifest) -> tuple[int, ...]:
    """The width-1 rule: indices of the gradients to hold behind a barrier
    before the update -- every leaf of :data:`MATERIALIZE_MIN_BYTES` or
    more.  Reads the manifest alone."""
    return tuple(i for i, n in enumerate(manifest.nbytes)
                 if n >= MATERIALIZE_MIN_BYTES)


def chain_extra_bytes(total_bytes: int, depth: int) -> int:
    """Estimated extra peak-HBM bytes of a ``depth``-bucket chain over
    free combining (the model :data:`CHAIN_LIVE_FRACTION` documents)."""
    if depth <= 1:
        return 0
    return int(total_bytes * CHAIN_LIVE_FRACTION * (depth - 1) / depth)


class AdaptivePlanner:
    """Manifest + width + headroom -> :class:`BucketPlan`: chain only where
    it can pay for itself.

    * width 1 -> bypass (psum is identity; chaining only constrains the
      scheduler), and the large gradients materialised before the update;
    * headroom deficit -> halve the depth until the estimated extra
      live-range bytes fit, down to bypass;
    * real width, slack headroom -> a chain of ``default_depth`` buckets.

    A deterministic function of its arguments: the plan is made under trace
    on every rank of an SPMD job and must agree everywhere.
    ``default_depth`` is for tests and the overlap audit, which compare the
    programs of different depths; everything else constructs it bare.
    """

    name = "adaptive"

    def __init__(self, default_depth: int = DEFAULT_CHAIN_DEPTH):
        self.default_depth = int(default_depth)

    def plan(self, manifest, width, headroom_mb):
        def mk(depth, reason):
            return BucketPlan(
                planner=self.name, chain_depth=depth, width=width,
                tensor_count=manifest.count,
                total_bytes=manifest.total_bytes, headroom_mb=headroom_mb,
                chain_extra_bytes=chain_extra_bytes(manifest.total_bytes,
                                                    depth),
                reason=reason)

        if width <= 1:
            held = materialized_leaves(manifest)
            return mk(0, f"width-1 bypass: psum is identity, nothing to "
                         f"overlap — free-combining structure; {len(held)} "
                         f"of {manifest.count} gradients are "
                         f"{MATERIALIZE_MIN_BYTES} B or more, where an "
                         f"update fused into the matmul costs more than "
                         f"one apart: materialised before the update"
                      ).holding(manifest, held)
        if manifest.count <= 1:
            return mk(0, "single gradient tensor: nothing to chain")
        depth = self.default_depth
        if depth <= 1:
            return mk(0, f"default depth {depth} disables the chain")
        if headroom_mb is None:
            return mk(depth, f"width {width}, headroom unknown: keeping "
                             f"default depth {depth}")
        budget = headroom_mb * 1024.0 * 1024.0
        if chain_extra_bytes(manifest.total_bytes, depth) <= budget:
            return mk(depth, f"width {width}, headroom {headroom_mb:.0f} MB "
                             f"covers the chain: keeping depth {depth}")
        start = depth
        while depth > 1 and chain_extra_bytes(manifest.total_bytes,
                                              depth) > budget:
            depth //= 2
        if depth <= 1:
            return mk(0, f"headroom deficit: even a 2-bucket chain "
                         f"(+{chain_extra_bytes(manifest.total_bytes, 2)} B) "
                         f"exceeds {headroom_mb:.0f} MB — free-combining "
                         f"fallback")
        return mk(depth, f"headroom deficit: degraded depth {start} -> "
                         f"{depth} to fit {headroom_mb:.0f} MB")


# ---------------------------------------------------------------------------
# Headroom probe
# ---------------------------------------------------------------------------

_probe_lock = threading.Lock()
_probe_cache: list = []  # [float | None] once probed — one answer per process
_probe_read: list = []   # [dict | None] beside it: what that one probe read
_plans_made = 0          # every plan_overlap / plan_context call so far


def probe_headroom_mb() -> float | None:
    """Device-memory headroom estimate in MB, or None when unknowable.

    ``HVD_TPU_DEVICE_HEADROOM_MB`` wins when set (the deterministic path —
    recommended for multi-host jobs and required for AOT/CPU/sim, where no
    addressable device reports memory stats).  Otherwise probe
    ``device.memory_stats()`` on the addressable devices (JAX TPU exposes
    ``bytes_limit`` / ``bytes_in_use``), take the minimum free estimate,
    and quantize DOWN to :data:`HEADROOM_QUANTUM_MB` so allocator jitter
    cannot fork the plan across ranks.  The probe result is cached for the
    process lifetime: repeated traces of the same program must see the
    same answer (plan stability), not a headroom that drifts as buffers
    come and go.
    """
    override = env.device_headroom_mb()
    if override is not None:
        return override
    with _probe_lock:
        if _probe_cache:
            return _probe_cache[0]
        headroom = None
        frees, read = [], []
        for dev in jax.local_devices():
            # None on backends that keep no allocator statistics (CPU);
            # a probe that raises is a fault to see, not "unknown".
            stats = dev.memory_stats()
            if not stats:
                continue
            limit = stats.get("bytes_limit")
            in_use = stats.get("bytes_in_use")
            if limit is None or in_use is None:
                continue
            frees.append(max(int(limit) - int(in_use), 0))
            read.append({"bytes_in_use": int(in_use),
                         "bytes_limit": int(limit)})
        if frees:
            mb = min(frees) / (1024.0 * 1024.0)
            headroom = (mb // HEADROOM_QUANTUM_MB) * HEADROOM_QUANTUM_MB
        _probe_cache.append(headroom)
        # the fullest device's counters and how many plans the process had
        # made by then: a later plan whose own ``plans_before`` is larger was
        # handed a headroom that an earlier program's trace fixed
        _probe_read.append(
            {**read[frees.index(min(frees))], "plans_before": _plans_made}
            if frees else None)
        return headroom


def headroom_record(given: bool = False) -> dict:
    """What a plan records about the headroom it was made with:
    ``headroom_source`` -- ``"given"`` (the caller passed a number), ``"env"``
    (``HVD_TPU_DEVICE_HEADROOM_MB``) or ``"probe"`` -- for a probe
    ``headroom_probe``: the ``bytes_in_use`` and ``bytes_limit`` of the
    fullest device and the ``plans_before`` it when the process's one cached
    probe was taken; and this plan's own ``plans_before``.  Call after the
    headroom was read, once a plan; record only, nothing here decides."""
    global _plans_made
    with _probe_lock:
        before, _plans_made = _plans_made, _plans_made + 1
        probe = _probe_read[0] if _probe_read else None
    if given:
        source = "given"
    elif env.device_headroom_mb() is not None:
        source = "env"
    else:
        source = "probe"
    return {"headroom_source": source,
            "headroom_probe": probe if source == "probe" else None,
            "plans_before": before}


# ---------------------------------------------------------------------------
# Entry point + observability
# ---------------------------------------------------------------------------

_plan_lock = threading.Lock()
_last_plan: BucketPlan | None = None
_logged_keys: set = set()


def plan_overlap(tensors, width: int, planner=None) -> BucketPlan:
    """Make (and record) the bucket plan for one traced allreduce group:
    :class:`AdaptivePlanner`'s, from the tensors, the width and
    :func:`probe_headroom_mb`.  ``planner`` (the seam the module docstring
    describes) stands in for it when given."""
    if planner is None:
        planner = AdaptivePlanner()
    manifest = GradientManifest.from_tensors(tensors)
    plan = dataclasses.replace(
        planner.plan(manifest, width, probe_headroom_mb()),
        **headroom_record())
    _record(plan)
    return plan


def overlap_plan() -> dict | None:
    """The most recent :class:`BucketPlan` as a dict (``hvd.overlap_plan()``),
    or None before any compiled allreduce group has been planned.  Keys:
    planner, chain_depth, chained, width, tensor_count, total_bytes,
    headroom_mb, chain_extra_bytes, reason, materialized_leaves and
    materialized_bytes (the gradients held behind a barrier before the
    update at width 1), and where the headroom came from
    (:func:`headroom_record`): headroom_source, headroom_probe,
    plans_before."""
    with _plan_lock:
        return _last_plan.as_dict() if _last_plan is not None else None


def _record(plan: BucketPlan) -> None:
    global _last_plan
    key = (plan.planner, plan.chain_depth, plan.width, plan.tensor_count,
           plan.total_bytes, plan.headroom_mb, plan.materialized)
    with _plan_lock:
        _last_plan = plan
        fresh = key not in _logged_keys
        if fresh:
            _logged_keys.add(key)
    if not fresh:
        return  # retraces of the same program repeat the same decision
    if _is_rank0():
        hr = ("unknown" if plan.headroom_mb is None
              else f"{plan.headroom_mb:.0f}MB")
        _log.info(
            "overlap plan: planner=%s width=%d headroom=%s depth=%d "
            "tensors=%d bytes=%d — %s", plan.planner, plan.width, hr,
            plan.chain_depth, plan.tensor_count, plan.total_bytes,
            plan.reason)
    _emit_timeline(plan)


def _is_rank0() -> bool:
    try:
        from horovod_tpu import basics

        return basics.rank() == 0
    except Exception:  # before init: single-process semantics
        return True


def _emit_timeline(plan: BucketPlan) -> None:
    """OVERLAP_PLAN instant on the native timeline — only when the engine
    is already up (peek, never boot) and rank 0 has a timeline file."""
    try:
        from horovod_tpu.core import engine

        eng = engine.peek_engine()
        if eng is None:
            return
        hr = ("unknown" if plan.headroom_mb is None
              else f"{plan.headroom_mb:.0f}MB")
        eng.timeline_instant(
            "overlap_plan",
            f"OVERLAP_PLAN planner={plan.planner} width={plan.width} "
            f"headroom={hr} depth={plan.chain_depth}")
    except Exception:  # observability must never break tracing
        pass


def _reset_for_tests() -> None:
    """Drop the cached probe/log state (test isolation only)."""
    global _last_plan, _last_context_plan, _plans_made
    with _probe_lock:
        _probe_cache.clear()
        _probe_read.clear()
        _plans_made = 0
    with _plan_lock:
        _last_plan = None
        _logged_keys.clear()
        _last_context_plan = None
        _context_logged_keys.clear()


# ---------------------------------------------------------------------------
# ContextPlan: long-context layout planning (ring/zigzag flash attention)
# ---------------------------------------------------------------------------
# The same trace-time discipline as BucketPlan, applied to sequence
# parallelism: shard width, plain-vs-zigzag layout, the flash kernel's
# block_q/block_k, and the remat policy are one decision from one memory
# model, not four hand-set knobs.  The motivating failure (round 5's
# chip): block_k=4096 wins at S=8192 but VMEM-OOMs the remat
# backward at S=32768 — tile choices must be VMEM-fit-clamped per workload.

# Deterministic remat fallback when no headroom estimate exists (CPU/sim/
# AOT with no HVD_TPU_DEVICE_HEADROOM_MB): remat engages past this many MB
# of estimated per-chip activations.  The value is the r5-measured HBM
# slack of the 32K single-chip row; with ring sharding active the per-chip
# activation estimate shrinks by 1/width and typically drops below it —
# which is exactly the "ring path drops full-layer remat" behavior.
DEFAULT_CTX_REMAT_THRESHOLD_MB = 2048.0


@dataclasses.dataclass(frozen=True)
class ContextWorkload:
    """Static description of one long-context training workload — every
    field is a Python int/bool at trace time, so the plan is a
    deterministic function of (workload, width, headroom) on every rank
    (the SPMD discipline :class:`AdaptivePlanner` documents)."""

    seq_len: int
    num_heads: int
    head_dim: int
    batch: int = 1
    embed_dim: int = 0       # 0 -> num_heads * head_dim
    mlp_dim: int = 0         # 0 -> 4 * model_dim
    num_layers: int = 1
    causal: bool = True
    dtype_bytes: int = 2     # bf16 activations

    @property
    def model_dim(self) -> int:
        return self.embed_dim or self.num_heads * self.head_dim

    @property
    def ff_dim(self) -> int:
        return self.mlp_dim or 4 * self.model_dim

    def activation_mb(self, width: int) -> float:
        """Estimated per-chip live activation bytes without remat: the
        residual stream, the attention q/k/v/out set, and the MLP hidden —
        per layer, per local token.  Coarse on purpose (it prices a binary
        remat decision, not an allocator)."""
        per_token = (2 * self.model_dim + 4 * self.num_heads * self.head_dim
                     + 2 * self.ff_dim) * self.dtype_bytes
        s_local = max(self.seq_len // max(width, 1), 1)
        return (self.num_layers * self.batch * s_local * per_token
                / (1024.0 * 1024.0))


@dataclasses.dataclass(frozen=True)
class ContextPlan:
    """One planner decision for one long-context workload: the sequence
    layout (``plain``/``zigzag``), the VMEM-fit flash tile sizes, and the
    remat policy — consumed by ``parallel/context.py`` and
    ``models/transformer.py``."""

    planner: str
    width: int
    seq_local: int
    layout: str
    block_q: int
    block_k: int
    remat: bool
    causal: bool
    headroom_mb: float | None
    est_vmem_kb: int
    est_activation_mb: float
    reason: str
    # where ``headroom_mb`` came from (:func:`headroom_record`): a record
    # of circumstance, so plans compare without it
    headroom_source: str | None = dataclasses.field(default=None,
                                                    compare=False)
    headroom_probe: dict | None = dataclasses.field(default=None,
                                                    compare=False)
    plans_before: int = dataclasses.field(default=0, compare=False)

    def as_dict(self) -> dict:
        return dataclasses.asdict(self)


def plan_context(workload: ContextWorkload, width: int,
                 headroom_mb: float | None = None, *,
                 layout: str | None = None,
                 block_q: int | None = None,
                 block_k: int | None = None,
                 remat: bool | None = None) -> ContextPlan:
    """Make (and record) the long-context plan for one traced program.

    Resolution order per field — most explicit wins: a keyword argument in
    code, the ``HVD_TPU_CTX_*`` env override, then the planner decision.
    Tile overrides are still VMEM-fit-clamped (the whole point: a knob
    must not be able to reintroduce the r5 block_k=4096 S=32768 OOM).
    ``headroom_mb`` defaults to :func:`probe_headroom_mb` — the same
    memory model the bucket planner budgets against.
    """
    # (the function re-export in ops/__init__ shadows the submodule name,
    # so import the pieces, not the module)
    from horovod_tpu.ops.flash_attention import (
        _VMEM_MIN_BLOCK, VMEM_FIT_BUDGET_MB, _default_block_k,
        _vmem_estimate_bytes)

    if width < 1:
        raise ValueError(f"context width must be >= 1, got {width}")
    if workload.seq_len % width:
        raise ValueError(
            f"seq_len {workload.seq_len} not divisible by context width "
            f"{width}")
    s_local = workload.seq_len // width
    given = headroom_mb is not None
    if not given:
        headroom_mb = probe_headroom_mb()

    why = []
    layout = layout if layout is not None else env.ctx_layout()
    if layout in (None, "auto"):
        zig_ok = workload.seq_len % (2 * width) == 0 and width > 1
        if workload.causal and zig_ok:
            layout = "zigzag"
            why.append("causal multi-shard -> zigzag (balanced causal "
                       "triangle; plain would idle early ranks)")
        else:
            layout = "plain"
            why.append("plain layout ("
                       + ("width 1" if width <= 1 else
                          "non-causal" if not workload.causal else
                          "seq_len not divisible by 2*width")
                       + ("; causal step skipping active"
                          if workload.causal and width > 1 else "") + ")")
    else:
        why.append(f"layout pinned to {layout}")
    if layout == "zigzag" and workload.seq_len % (2 * width):
        raise ValueError(
            f"zigzag needs seq_len divisible by 2*width "
            f"({workload.seq_len} vs width={width})")

    # Per-kernel-call K length: zigzag splits the shard into two chunks.
    chunk = s_local // 2 if layout == "zigzag" else s_local
    chunk = max(chunk, 1)
    bq = block_q if block_q is not None else env.ctx_block_q()
    bk = block_k if block_k is not None else env.ctx_block_k()
    pinned = bq is not None or bk is not None
    if bq is None:
        bq = min(1024, chunk)
    if bk is None:
        bk = _default_block_k(chunk, workload.head_dim)
    bq, bk = min(bq, chunk), min(bk, chunk)
    # VMEM-fit clamp against the same resident-set model the kernel entry
    # points enforce — but silently: a planned reduction IS the plan, only
    # hand-set values that trip the kernel-side clamp deserve the warning.
    budget = int(VMEM_FIT_BUDGET_MB * 2 ** 20)
    fit_bq, fit_bk = bq, bk
    while _vmem_estimate_bytes(fit_bq, fit_bk, workload.head_dim,
                                   1024, workload.dtype_bytes) > budget:
        if fit_bk > _VMEM_MIN_BLOCK and fit_bk >= fit_bq:
            fit_bk //= 2
        elif fit_bq > _VMEM_MIN_BLOCK:
            fit_bq //= 2
        elif fit_bk > _VMEM_MIN_BLOCK:
            fit_bk //= 2
        else:
            break
    if (fit_bq, fit_bk) != (bq, bk):
        why.append(f"VMEM fit: block_q/block_k {bq}/{bk} -> "
                   f"{fit_bq}/{fit_bk}"
                   + (" (overriding pinned tiles)" if pinned else ""))
    bq, bk = fit_bq, fit_bk
    est_vmem_kb = _vmem_estimate_bytes(
        bq, bk, workload.head_dim, 1024, workload.dtype_bytes) // 1024

    act_mb = workload.activation_mb(width)
    remat = remat if remat is not None else env.ctx_remat_override()
    if remat is None:
        act_budget = (headroom_mb if headroom_mb is not None
                      else DEFAULT_CTX_REMAT_THRESHOLD_MB)
        remat = act_mb > act_budget
        why.append(
            f"activations ~{act_mb:.0f}MB vs "
            + (f"headroom {headroom_mb:.0f}MB" if headroom_mb is not None
               else f"default budget {act_budget:.0f}MB")
            + (" -> full-layer remat" if remat
               else " -> remat dropped (ring shards the sequence)"))
    else:
        why.append(f"remat pinned to {remat}")

    plan = ContextPlan(
        planner="context", width=width, seq_local=s_local, layout=layout,
        block_q=bq, block_k=bk, remat=bool(remat), causal=workload.causal,
        headroom_mb=headroom_mb, est_vmem_kb=est_vmem_kb,
        est_activation_mb=round(act_mb, 3), reason="; ".join(why),
        **headroom_record(given))
    _record_context(plan)
    return plan


_last_context_plan: ContextPlan | None = None
_context_logged_keys: set = set()


def context_plan() -> dict | None:
    """The most recent :class:`ContextPlan` as a dict
    (``hvd.context_plan()``), or None before any long-context program has
    been planned.  Keys: planner, width, seq_local, layout, block_q,
    block_k, remat, causal, headroom_mb, est_vmem_kb, est_activation_mb,
    reason, and headroom_source, headroom_probe, plans_before
    (:func:`headroom_record`)."""
    with _plan_lock:
        return (_last_context_plan.as_dict()
                if _last_context_plan is not None else None)


def _record_context(plan: ContextPlan) -> None:
    global _last_context_plan
    key = (plan.width, plan.seq_local, plan.layout, plan.block_q,
           plan.block_k, plan.remat, plan.causal, plan.headroom_mb)
    with _plan_lock:
        _last_context_plan = plan
        fresh = key not in _context_logged_keys
        if fresh:
            _context_logged_keys.add(key)
    if not fresh:
        return  # retraces of the same program repeat the same decision
    if _is_rank0():
        hr = ("unknown" if plan.headroom_mb is None
              else f"{plan.headroom_mb:.0f}MB")
        _log.info(
            "context plan: width=%d s_local=%d layout=%s block_q=%d "
            "block_k=%d remat=%s headroom=%s — %s", plan.width,
            plan.seq_local, plan.layout, plan.block_q, plan.block_k,
            plan.remat, hr, plan.reason)
    try:
        from horovod_tpu.core import engine

        eng = engine.peek_engine()
        if eng is not None:
            eng.timeline_instant(
                "context_plan",
                f"CONTEXT_PLAN width={plan.width} layout={plan.layout} "
                f"block_q={plan.block_q} block_k={plan.block_k} "
                f"remat={plan.remat}")
    except Exception:  # observability must never break tracing
        pass
