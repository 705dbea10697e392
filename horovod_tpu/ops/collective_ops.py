"""Named-tensor collectives — the XLA data plane.

This is the analog of the reference's op layer
(horovod/tensorflow/mpi_ops.py:57-182, horovod/torch/mpi_ops.py) and of the
execution half of ``PerformOperation`` (horovod/common/operations.cc:714-1362),
with one structural difference that defines the whole rebuild: on TPU the
collectives are *compiled*, not dispatched.  ``jax.lax.psum`` / ``all_gather``
/ masked-``psum`` broadcast inside a ``shard_map`` over the global mesh become
XLA AllReduce/AllGather HLOs that the compiler schedules, fuses, and overlaps
on ICI — there is no background thread, fusion memcpy, or readiness
negotiation on this path because SPMD lockstep makes every chip reach the
collective in the same program order (SURVEY §7 hard-part (a)).

Two calling contexts are supported by every op:

* **in-mesh** (inside ``shard_map``/``pmap`` with the data axis bound): the op
  lowers straight to ``lax`` collectives over the chip axis.  This is the hot
  path used by ``DistributedOptimizer`` and the train-step builders.
* **eager** (plain Python, no trace): process-level semantics — each process
  contributes its host value, like one reference rank per host.  Used for
  bootstrap (broadcast_parameters), metrics averaging, and the torch binding.
  Ragged ``allgather`` (per-rank dim-0 sizes, reference's ``MPI_Allgatherv``
  path operations.cc:1273-1332) is supported here, where shapes may be dynamic.

Gradient semantics match the reference's registered gradients
(tensorflow/mpi_ops.py:95-182): grad(allreduce)=allreduce, grad(allgather)=
reduce-scatter of the gathered grad, grad(broadcast)=psum zeroed off-root —
all of which fall out of JAX autodiff on the primitives we use, rather than
being hand-registered.
"""

from __future__ import annotations

import functools
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import multihost_utils
from jax.sharding import PartitionSpec

from horovod_tpu import basics, mesh
from horovod_tpu.ops.compression import Compression
from horovod_tpu.ops import fusion
from horovod_tpu.utils import profiling

Average = True  # default matches reference allreduce(average=True)


def _record_schedule(op: str, name: str | None, tensor) -> None:
    """Feed the runtime schedule verifier (HVD_TPU_VERIFY_SCHEDULE,
    analysis/schedule.py) at trace/call time: trace order IS program
    order, so a rank whose Python program issues different collectives is
    caught even though the collective itself compiles to an XLA op the
    native engine never sees.  No-op (one env check) when verification is
    off."""
    from horovod_tpu.analysis import schedule

    if not schedule.verify_enabled():
        return
    try:
        dtype = jnp.result_type(tensor)
        shape = jnp.shape(tensor)
    except Exception:  # non-array payloads (pytrees handled by callers)
        dtype, shape = "?", ()
    schedule.record(f"compiled-{op}", name or "<unnamed>", dtype, shape)


def _private_axis_env_names() -> tuple[str, ...]:
    """The one touch of private JAX API, isolated so tests can simulate its
    drift (symbol renamed/removed) without disturbing jax internals."""
    from jax._src import core as _core
    return tuple(_core.get_axis_env().axis_sizes.keys())


def _bound_axis_names() -> tuple[str, ...]:
    """Mesh axis names bound by an enclosing shard_map/pmap trace."""
    try:
        return tuple(_private_axis_env_names())
    except Exception:  # private-API drift fallback
        # Probe every axis name we could plausibly be traced under: the
        # horovod_tpu conventions AND the axes of whatever mesh is active —
        # both our global mesh and jax's thread-local physical mesh — so a
        # shard_map over a custom user mesh (axis named neither "hvd" nor
        # "dcn"/"ici") still gets in-mesh semantics if this private API ever
        # drifts (pinned by tests/test_mesh_axes.py).
        candidates = [*mesh.data_axes(), mesh.DATA_AXIS, mesh.DCN_AXIS,
                      mesh.ICI_AXIS]
        try:
            candidates.extend(mesh.global_mesh().axis_names)
        except Exception:
            pass
        try:
            from jax._src import mesh as _jmesh
            active = _jmesh.thread_resources.env.physical_mesh
            candidates.extend(active.axis_names)
        except Exception:
            pass
        found = []
        for name in candidates:
            try:
                lax.axis_size(name)
                found.append(name)
            except NameError:
                pass
        return tuple(dict.fromkeys(found))


def _in_mesh_axes() -> tuple[str, ...] | None:
    """Return the data-parallel axis names collectives should reduce over, or
    None when called eagerly (no mesh axis bound → process-level semantics).

    Preference order: the global mesh's data axes when bound; a bound
    (dcn, ici) hierarchical pair; a bound "hvd" axis; a single bound axis of
    any name (custom user meshes).  Multiple bound axes that match none of
    these are ambiguous between data and model axes — reduce over the global
    mesh convention only.
    """
    bound = _bound_axis_names()
    if not bound:
        return None
    ours = mesh.data_axes()
    if all(a in bound for a in ours):
        return ours
    if mesh.DCN_AXIS in bound and mesh.ICI_AXIS in bound:
        return (mesh.DCN_AXIS, mesh.ICI_AXIS)
    if mesh.DATA_AXIS in bound:
        return (mesh.DATA_AXIS,)
    if len(bound) == 1:
        return bound
    return None


def _data_width(axes: tuple[str, ...]) -> int:
    """Number of workers spanned by the data axes (NOT total devices: the
    mesh may carry extra model-parallel axes that collectives don't cross)."""
    n = 1
    for a in axes:
        n *= lax.axis_size(a)
    return n


def _mesh_allreduce(x, axes: tuple[str, ...]):
    """One in-mesh sum: flat psum on 1-D data meshes; two-level
    ICI-scatter → DCN-reduce → ICI-gather on multi-slice (dcn, ici) meshes
    (parallel/hierarchy.py; reference operations.cc:1025-1177 analog)."""
    if len(axes) == 1:
        return lax.psum(x, axes[0])
    from horovod_tpu.parallel import hierarchy

    return hierarchy.hierarchical_allreduce(x.reshape(-1), axes).reshape(x.shape)


def _require_not_traced(name: str) -> None:
    core = jax.core
    if isinstance(jnp.zeros(()), core.Tracer):  # pragma: no cover - safety net
        raise RuntimeError(
            f"horovod_tpu.{name} was called inside jit without the data mesh "
            f"axis in scope; wrap your step with horovod_tpu.shard (or "
            f"shard_map over the global mesh) so collectives have an axis to "
            f"reduce over."
        )


# ---------------------------------------------------------------------------
# allreduce
# ---------------------------------------------------------------------------

def allreduce(tensor, average: bool = True, name: str | None = None,
              compression=Compression.none, prescale_factor: float = 1.0):
    """Sum (or average) ``tensor`` across all workers.

    In-mesh: one ``lax.psum`` over the chip axis (the reference's fused
    MPI_Allreduce/ncclAllReduce, operations.cc:954-1311).  Eager: process-level
    reduction.  ``compression`` casts to the wire dtype around the collective
    (reference tensorflow/__init__.py:80-87); ``Compression.int8`` routes to
    the quantized in-mesh collective (shared scale, no error feedback at
    this granularity — use DistributedOptimizer for that).
    """
    _record_schedule("allreduce", name, tensor)
    if compression is Compression.int8:
        if prescale_factor != 1.0:
            tensor = tensor * prescale_factor
        (reduced,), _ = quantized_grouped_allreduce([tensor], average=average)
        return reduced
    axes = _in_mesh_axes()
    compressed, ctx = compression.compress(tensor)
    if prescale_factor != 1.0:
        compressed = compressed * prescale_factor
    if axes is not None:
        reduced = _mesh_allreduce(compressed, axes)
        if average:
            reduced = reduced / _data_width(axes)
    else:
        _require_not_traced("allreduce")
        reduced = _eager_process_reduce(compressed)
        if average:
            reduced = reduced / basics.size()
    return compression.decompress(reduced, ctx)


def quantized_grouped_allreduce(tensors: Sequence, errors: Sequence | None = None,
                                average: bool = True,
                                threshold_bytes: int | None = None
                                ) -> tuple[list, list]:
    """Fused allreduce on an int8 wire — 4x fewer bytes than float32
    (beyond the reference's cast-based Compression, reference
    compression.py:42-63).

    Scales are agreed per TENSOR (one stacked ``pmax`` covers all of them),
    never per fused bucket — a bias gradient packed next to a large logits
    gradient keeps its own quantization grid instead of rounding to zero.
    Values quantize to at most ``±floor(127/width)`` levels so the int8
    ``psum`` cannot overflow at any partial sum, and the sum dequantizes
    back.  ``errors`` carries error feedback: each chip's local
    quantization residual is returned and should be passed back on the
    next call (added to the fresh gradients), so the lost precision
    re-enters instead of biasing training —
    ``DistributedOptimizer(compression=Compression.int8)`` manages this
    automatically.  Works in both calling contexts: in-mesh (sum-fitting
    int8 psum, hierarchical on (dcn, ici) meshes) and eager/process-level
    (per-rank (scale, int8) payloads over the process allgather —
    core/qwire.py).

    Returns ``(reduced, residuals)``, both lists matching ``tensors``.
    """
    axes = _in_mesh_axes()
    if axes is None:
        # Eager/process-level: per-rank local scales over the process
        # allgather — the same (scale ‖ int8) payload as the native
        # engine's WIRE_INT8 (core/qwire.py).  Error feedback works here
        # too: residuals are returned and ``errors`` re-enter.
        _require_not_traced("quantized_grouped_allreduce")
        return _eager_quantized_reduce(list(tensors), errors,
                                       average=average)
    width = _data_width(axes)
    if len(axes) >= 2:
        # Hierarchical (dcn, ici) mesh: each TIER sum-fits independently
        # (reference operations.cc:1025-1177 hierarchy, re-derived for the
        # int8 wire).  The quantization grid only has to fit the ICI-tier
        # sum — ±(127//ici_size) levels instead of ±(127//total_width) — so
        # any width whose tiers are each <= 127 is admissible: width 512 as
        # (dcn=64, ici=8) quantizes at ±15 levels where a flat 127-cap
        # would refuse outright (and width 64 as (8, 8) gets ±15 instead
        # of the flat path's ±1).
        dcn_n, ici_n = (lax.axis_size(axes[0]), lax.axis_size(axes[1]))
        if max(dcn_n, ici_n) > 127:
            raise ValueError(
                f"hierarchical int8 allreduce sum-fits at most 127 workers "
                f"per tier (mesh here: dcn={dcn_n}, ici={ici_n}); reshape "
                f"the mesh or use Compression.bf16.")
        qcap = max(127 // ici_n, 1)
    elif width > 127:
        raise ValueError(
            f"int8 quantized allreduce sum-fits at most 127 workers on the "
            f"wire (data width here: {width}); build a hierarchical "
            f"(dcn, ici) mesh (each tier <= 127 — see parallel/hierarchy.py) "
            f"or use Compression.bf16.")
    else:
        qcap = max(127 // width, 1)
    for t in tensors:
        if not jnp.issubdtype(t.dtype, jnp.floating):
            raise ValueError(
                f"int8 quantization applies to floating gradients, got "
                f"{t.dtype}")
    if errors is not None:
        tensors = [t + e.astype(t.dtype) for t, e in zip(tensors, errors)]

    # One collective agrees every tensor's scale: stack the local amaxes
    # into a vector and pmax it.  Non-finite local amaxes are sanitized to
    # +inf FIRST — XLA's max has IEEE maxNum semantics and would silently
    # drop a NaN operand, laundering an overflowed gradient into a finite
    # reduced value.
    local_amax = jnp.stack([
        (jnp.max(jnp.abs(t)) if t.size else jnp.zeros((), t.dtype))
        .astype(jnp.float32)
        for t in tensors])
    local_amax = jnp.where(jnp.isfinite(local_amax), local_amax, jnp.inf)
    amaxes = lax.pmax(local_amax, axes)
    qs, scales, resid = [], [], []
    for i, t in enumerate(tensors):
        # Guard in the working dtype: an f32-tiny floor would underflow to
        # 0 after an fp16/bf16 cast, turning all-zero tensors into 0/0=NaN.
        finite = jnp.isfinite(amaxes[i])
        scale = jnp.where(
            finite,
            jnp.maximum(amaxes[i].astype(t.dtype) / qcap,
                        jnp.finfo(t.dtype).tiny),
            amaxes[i].astype(t.dtype))
        # Non-finite gradients ship q=0 under the inf scale so the
        # dequantized tensor is NaN (inf*0) on EVERY chip — overflow
        # checks keep firing instead of seeing laundered finite values.
        q = jnp.where(finite,
                      jnp.clip(jnp.round(t / scale), -qcap, qcap),
                      jnp.zeros_like(t)).astype(jnp.int8)
        qs.append(q)
        scales.append(scale)
        # Residual resets on a non-finite step: carrying a NaN residual
        # would poison error feedback long after the loss-scaler recovers.
        resid.append(jnp.where(finite, t - q.astype(t.dtype) * scale,
                               jnp.zeros_like(t)))

    if len(axes) >= 2:
        # Tiered sum-fit: int8 reduce-scatter on ICI (|partial| <=
        # ici*qcap <= 127), REQUANTIZE the shard onto the DCN tier's own
        # sum-fitting grid, int8 psum across DCN, all_gather back.  The
        # requantization factor qcap2/s1_max is applied to unitless GRID
        # COUNTS, so one factor serves every tensor in a fused bucket and
        # per-tensor scales still dequantize outside.  Extra error from
        # the stage-2 rounding: <= dcn * s1_max/(2*qcap2) counts per
        # element (in value terms, that times the tensor's scale) — the
        # price of sum-fitting only per tier; error feedback carries the
        # stage-1 residuals as usual.
        dcn_ax, ici_ax = axes
        qcap2 = max(127 // dcn_n, 1)
        s1_max = ici_n * qcap

        def _tiered(flat):
            n = flat.shape[0]
            pad = (-n) % ici_n
            if pad:
                flat = jnp.pad(flat, (0, pad))
            shard = lax.psum_scatter(flat, ici_ax, tiled=True)
            red = shard.astype(jnp.float32)
            if dcn_n > 1:
                q2 = jnp.round(red * (qcap2 / s1_max)).astype(jnp.int8)
                red = lax.psum(q2, dcn_ax).astype(jnp.float32) \
                    * (s1_max / qcap2)
            out = lax.all_gather(red, ici_ax, tiled=True)
            return out[:n] if pad else out

        summed = fusion.fused_apply(qs, _tiered, threshold_bytes)
    else:
        # |any partial or total sum| <= width*qcap <= 127: no int8 overflow
        # on the flat psum.
        summed = fusion.fused_apply(
            qs, lambda flat: _mesh_allreduce(flat, axes), threshold_bytes)
    inv = (1.0 / width) if average else 1.0
    # Dequantize in f32: for fp16 gradients the intermediate sum (up to
    # width*amax) can overflow to inf in the gradient dtype even when the
    # averaged result is representable, so fold the average into the scale
    # and multiply in f32 before casting back.
    reduced = [(s.astype(jnp.float32)
                * (scales[i].astype(jnp.float32) * inv)).astype(t.dtype)
               for i, (s, t) in enumerate(zip(summed, tensors))]
    return reduced, resid


CHAIN_GATE_SCOPE = "hvd_chain_gate"


def _chained_allreduce(vals: list, axes, n_buckets: int) -> list:
    """Per-tensor psums in ``n_buckets`` dependency-chained groups, reverse
    tree order (≈ backward availability: output-side layers' gradients
    exist first).

    Left alone, XLA's all-reduce combiner merges every gradient psum into
    ONE tuple all-reduce that can only run after ALL of backward — zero
    comm/compute overlap (the round-4 audit).  Chaining bucket ``i+1``'s
    inputs on bucket ``i``'s output makes the bucket all-reduces
    uncombinable (merging would form a cycle), so the backend schedules the
    early buckets' reductions DURING the rest of backward — the property
    the reference's whole hook-in-backward architecture exists for
    (reference horovod/common/operations.cc:203-216,
    horovod/torch/__init__.py:83-112).  With the async-collective-fusion
    compiler options (:func:`overlap_compiler_options`) the v5e backend
    additionally turns them into async continuation fusions (measured on
    the real DistributedOptimizer step, deviceless v5e:2x4 AOT audit:
    16 of 17 surviving all-reduces scheduled before the last backward
    fusion at default flags; with the async options, 4 explicit
    async-pair splits on top — examples/overlap_audit.py,
    round 5).

    The gate is ``where(isfinite(s), s, 0) * 0``: exactly 0.0 even for
    inf/NaN gradients (no cross-bucket poisoning), yet data-dependent and
    fold-proof (the compiler cannot prove the select's output finite —
    plain ``s * 0`` would also work but ``optimization_barrier`` does NOT:
    the TPU pipeline has removed it by the time the all-reduce combiner
    runs.  It is still there when instruction fusion runs, which is all
    the width-1 plan asks of it below: deviceless v5e compiles, PR 25).
    Non-float leaves pass through ungated (the combiner may merge those;
    harmless).

    Memory trade: pulling the reductions into backward extends gradient
    live ranges, raising peak HBM by up to a few hundred MB on large
    models (measured: 468M/B=16 OOMs by 79 MB with the default chain and
    fits without it — round 5's chip).  The schedule planner
    (ops/schedule_plan.py) budgets exactly this cost against the probed
    device headroom and degrades the depth — or bypasses the chain — when
    it would not fit, so chain memory pressure is a planner input, not a
    hand-tuning chore (docs/troubleshooting.md OOM entry).
    """
    n = len(vals)
    bounds = np.linspace(0, n, n_buckets + 1).astype(int)
    out: dict[int, jax.Array] = {}
    gate = None
    rev = list(range(n))[::-1]
    k = 0       # the bucket's place in the chain: what its scope says
    for lo, hi in zip(bounds[:-1], bounds[1:]):
        idx = rev[lo:hi]
        if not idx:
            continue
        bucket = []
        for i in idx:
            v = vals[i]
            if gate is not None and jnp.issubdtype(v.dtype, jnp.inexact):
                v = v + gate.astype(v.dtype)
            bucket.append(v)
        with jax.named_scope(profiling.bucket_scope(k)):
            red = [_mesh_allreduce(v, axes) for v in bucket]
        k += 1
        # The gate sums a scalar from EVERY inexact reduction in the
        # bucket, so the next bucket depends on all of them — merging any
        # of this bucket's ARs forward would form a cycle structurally,
        # not just for the first tensor.
        scalars = [r.reshape(-1)[0].astype(jnp.float32) for r in red
                   if jnp.issubdtype(r.dtype, jnp.inexact) and r.size > 0]
        if scalars:
            # Named so a structural probe can count the chain's gates in a
            # lowered program apart from every other is_finite (logsumexp
            # emits one of its own) — examples/overlap_audit.py.
            with jax.named_scope(CHAIN_GATE_SCOPE):
                s = sum(scalars)
                gate = jnp.where(jnp.isfinite(s), s, 0.0) * 0.0
        for i, r in zip(idx, red):
            out[i] = r
    return [out[i] for i in range(n)]


# The load-bearing flag set for async bucket all-reduces (measured on the
# v5e:2x4 AOT audit, round 5).  One source of truth:
# overlap_compiler_options() serves runtime callers, and the deviceless
# AOT audit (examples/overlap_audit.py) imports this constant directly so
# its recorded numbers always describe the shipped flags.
OVERLAP_XLA_OPTIONS = {
    "xla_enable_async_all_reduce": "true",
    "xla_tpu_enable_async_collective_fusion": "true",
    "xla_tpu_enable_async_collective_fusion_fuse_all_reduce": "true",
}


def overlap_compiler_options() -> dict:
    """Compiler options that let the TPU backend EXECUTE the chained bucket
    all-reduces asynchronously inside backward: pass to ``jax.jit(...,
    compiler_options=hvd.overlap_compiler_options())`` on the train step.
    Without them the chained buckets still schedule interleaved with
    backward but run synchronously; with them the v5e backend emits
    AsyncCollectiveStart continuation fusions (measured —
    examples/overlap_audit.py).  Empty off-TPU (the options are
    TPU-backend-specific and other compile paths reject unknown keys)."""
    if jax.default_backend() != "tpu":
        return {}
    return dict(OVERLAP_XLA_OPTIONS)


def grouped_allreduce(tensors: Sequence, average: bool = True,
                      compression=Compression.none,
                      threshold_bytes: int | None = None,
                      planner=None) -> list:
    """Fused allreduce of many tensors (reference fusion-buffer semantics,
    operations.cc:1807-1842).  In-mesh on a single axis: one psum per
    tensor, dependency-chained into buckets per the trace-time schedule
    planner (ops/schedule_plan.py), which chains at real data width with
    slack headroom, bypasses the chain at width 1 (psum is identity there),
    and degrades the depth under device-memory pressure (see
    ``_chained_allreduce`` for what a chain is).  At width 1 the plan also
    lists the gradients to materialise before they are used
    (``BucketPlan.materialized``); each of those comes back behind its own
    ``optimization_barrier``, the identity on values, which keeps XLA from
    fusing its consumer (the optimizer's update) into the matmul that
    produces it.  The decision is observable via ``hvd.overlap_plan()``.
    ``planner=`` is the test seam ops/schedule_plan.py describes: an object
    with ``plan(manifest, width, headroom_mb) -> BucketPlan`` that stands
    in for the planner.
    ``threshold_bytes`` is ignored on this path (docs/tensor-fusion.md).
    Hierarchical (multi-axis) meshes, the eager path, and the int8 path
    in any context: flat ``threshold_bytes``-bounded buckets
    (ops/fusion.py)."""
    _record_schedule(f"grouped_allreduce[{len(tensors)}]", None,
                     tensors[0] if len(tensors) else ())
    if compression is Compression.int8:
        # Stateless quantized path (no error feedback): residuals dropped.
        reduced, _ = quantized_grouped_allreduce(
            tensors, average=average, threshold_bytes=threshold_bytes)
        return reduced
    axes = _in_mesh_axes()
    comp = [compression.compress(t) for t in tensors]
    held: tuple = ()    # gradients the plan materialises before the update
    if axes is not None:
        denom = _data_width(axes)
        if len(axes) == 1:
            # Single-axis compiled path: one psum per tensor — NO concat
            # packing (a flat fusion buffer duplicates the backend's
            # batching and charges a pack+unpack pass over every gradient
            # byte — removing it measured +2.5 MFU points on the 162M
            # transformer on round 4's chip).  Whether the psums
            # are dependency-chained into buckets (overlapping backward,
            # round 5) or left free-combining is the schedule planner's
            # call, made here at trace time from the gradient manifest,
            # the data width, and the device headroom (round 9) — see
            # ops/schedule_plan.py and _chained_allreduce.
            from horovod_tpu.ops import schedule_plan

            plan = schedule_plan.plan_overlap(
                [c for c, _ in comp], width=denom, planner=planner)
            if plan.chained:
                reduced = _chained_allreduce([c for c, _ in comp], axes,
                                             plan.chain_depth)
            else:
                with jax.named_scope(
                        profiling.bucket_scope(profiling.BUCKET_ALL)):
                    reduced = [_mesh_allreduce(c, axes) for c, _ in comp]
            held = plan.materialized
        else:
            # Hierarchical (e.g. (dcn, ici)) route: each tensor lowers to
            # a psum_scatter→psum→all_gather CHAIN (parallel/hierarchy.py)
            # that the AR combiner does not merge across tensors — keep
            # the flat buckets here so many small leaves ride few tiered
            # chains instead of one latency-bound chain each.  (The
            # combiner measurement above covers only plain AllReduce.)
            reduced = fusion.fused_apply(
                [c for c, _ in comp],
                lambda flat: _mesh_allreduce(flat, axes), threshold_bytes)
    else:
        _require_not_traced("grouped_allreduce")
        denom = basics.size()
        # Same flat-bucket fusion as the in-mesh branch: one process
        # collective per bucket instead of one per tensor — the per-call
        # latency the reference's fusion buffer exists to amortise
        # (operations.cc:743-767).
        reduced = fusion.fused_apply(
            [c for c, _ in comp], _eager_process_reduce, threshold_bytes)
    if average:
        reduced = [r / denom for r in reduced]
    out = [compression.decompress(r, ctx) for r, (_, ctx) in zip(reduced, comp)]
    for i in held:
        out[i] = lax.optimization_barrier(out[i])
    return out


def _eager_quantized_reduce(tensors, errors, average: bool):
    """Process-level int8 allreduce over the shared payload codec
    (core/qwire.py).  Returns ``(reduced, residuals)`` in each input's own
    dtype, with the local quantization error as the residual."""
    from horovod_tpu.core import qwire

    size = basics.size()
    arrs = [np.asarray(t) for t in tensors]
    for a in arrs:
        if a.dtype.kind != "f" and a.dtype.name != "bfloat16":
            raise ValueError(
                f"int8 quantization applies to floating gradients, got "
                f"{a.dtype}")
    if errors is not None:
        arrs = [a + np.asarray(e).astype(a.dtype)
                for a, e in zip(arrs, errors)]
    sizes = [a.size for a in arrs]
    from horovod_tpu.core import device_reduce

    if size > 1 and device_reduce.enabled():
        # Device route: int8 reduce-scatter + on-device dequant-sum +
        # requantized int8 return leg (~2n wire bytes; see
        # core/device_reduce.py for the error model).
        scales, qs = qwire.quantize_int8(arrs)
        acc = device_reduce.process_allreduce_int8(scales, qs, sizes)
    else:
        payload, scales, qs = qwire.pack_int8(arrs)
        if size == 1:
            rows = payload[None]
        else:
            _require_full_job("quantized allreduce")
            rows = np.asarray(multihost_utils.process_allgather(
                jnp.asarray(payload)[None], tiled=False)).reshape(size, -1)
        acc = qwire.unpack_sum_int8(rows, sizes)
    if average:
        acc = acc / size
    reduced, resid, off = [], [], 0
    for t, a in enumerate(arrs):
        n_t = sizes[t]
        reduced.append(jnp.asarray(
            acc[off:off + n_t].astype(a.dtype).reshape(a.shape)))
        if np.isfinite(scales[t]):
            local = np.asarray(a, np.float32).ravel() \
                - scales[t] * qs[t].astype(np.float32)
        else:
            # Residual resets on a non-finite step (see in-mesh path): a
            # NaN residual would poison error feedback indefinitely.
            local = np.zeros(n_t, np.float32)
        resid.append(jnp.asarray(local.astype(a.dtype).reshape(a.shape)))
        off += n_t
    return reduced, resid


def _require_full_job(op: str) -> None:
    from horovod_tpu.core import device_reduce

    device_reduce.require_full_job(op)


def _process_gather(arr: np.ndarray) -> np.ndarray:
    """(P,) + arr.shape gather over job processes (device plane when
    enabled — subset-safe; legacy multihost_utils otherwise)."""
    from horovod_tpu.core import device_reduce

    if device_reduce.enabled():
        return device_reduce.process_allgather(arr)
    _require_full_job("allgather")
    return np.asarray(multihost_utils.process_allgather(
        jnp.asarray(arr)[None], tiled=False)).reshape(
            (basics.size(),) + arr.shape)


def _eager_process_reduce(x):
    if basics.size() == 1:
        return jnp.asarray(x)
    from horovod_tpu.core import device_reduce

    # jnp.asarray first: jax-wide dtype rules apply either way (64-bit
    # downcasts without x64), keeping device and legacy results identical.
    arr = np.asarray(jnp.asarray(x))
    if device_reduce.enabled():
        floating = arr.dtype.kind == "f" or arr.dtype.name == "bfloat16"
        if floating and arr.dtype.itemsize != 8:
            # Reduce-scatter -> allgather on device (~2n wire bytes per
            # rank, core/device_reduce.py) — the reference's MPI_Allreduce
            # ring economics instead of allgather+host-sum.
            return jnp.asarray(device_reduce.process_allreduce(
                arr.ravel()).reshape(arr.shape))
        # ints/bool (the public API PROMOTES via jnp.sum — int8 sums to
        # int32, bool to counts) and x64 floats (f64 rides the gather's
        # internal byte view): gather on the device plane, sum on host in
        # the promoted/full-precision dtype.  Metric-sized payloads.
        return jnp.sum(jnp.asarray(device_reduce.process_allgather(arr)),
                       axis=0)
    _require_full_job("allreduce")
    gathered = multihost_utils.process_allgather(jnp.asarray(x)[None], tiled=False)
    return jnp.sum(gathered.reshape((basics.size(),) + jnp.shape(x)), axis=0)


# ---------------------------------------------------------------------------
# allgather
# ---------------------------------------------------------------------------

def allgather(tensor, name: str | None = None):
    """Concatenate each worker's tensor along dim 0.

    In-mesh: ``lax.all_gather(tiled=True)`` — requires equal per-chip shapes
    (XLA static-shape constraint).  Eager: supports per-process *different*
    dim-0 sizes, reproducing the reference's ``MPI_Allgatherv`` (response
    carries per-rank dim-0 sizes, operations.cc:576-612, 1273-1332) by
    gathering sizes first, padding to the max, then slicing.
    """
    _record_schedule("allgather", name, tensor)
    axes = _in_mesh_axes()
    if axes is not None:
        flat_axis = axes if len(axes) > 1 else axes[0]
        return lax.all_gather(tensor, flat_axis, tiled=True)
    _require_not_traced("allgather")
    tensor = jnp.asarray(tensor)
    if basics.size() == 1:
        return tensor
    dim0 = jnp.shape(tensor)[0] if tensor.ndim else 1
    sizes = _process_gather(np.asarray([dim0], np.int32)).reshape(-1)
    max_d = int(sizes.max())
    pad = [(0, max_d - dim0)] + [(0, 0)] * (tensor.ndim - 1)
    padded = jnp.pad(tensor, pad)
    gathered = jnp.asarray(_process_gather(np.asarray(padded)))
    gathered = gathered.reshape((basics.size(), max_d) + tensor.shape[1:])
    pieces = [gathered[r, : int(sizes[r])] for r in range(basics.size())]
    return jnp.concatenate(pieces, axis=0)


# ---------------------------------------------------------------------------
# alltoall
# ---------------------------------------------------------------------------

def alltoall(tensor, splits=None, name: str | None = None):
    """Scatter dim-0 blocks to every worker and concatenate the blocks
    received (the Ulysses building block — parallel/ulysses.py does the
    in-mesh head↔sequence exchange with the same primitive).

    In-mesh: ``lax.all_to_all`` — one XLA AllToAll on ICI; even splits only
    (static shapes).  Eager: negotiated through the native engine with
    optional per-rank ``splits`` (ragged), ops/async_ops.py:alltoall.
    """
    _record_schedule("alltoall", name, tensor)
    axes = _in_mesh_axes()
    if axes is not None:
        if splits is not None:
            raise ValueError(
                "explicit splits are only supported on the eager path; "
                "in-mesh alltoall is compiled with static (even) shapes")
        flat_axis = axes if len(axes) > 1 else axes[0]
        return lax.all_to_all(tensor, flat_axis, split_axis=0, concat_axis=0)
    _require_not_traced("alltoall")
    from horovod_tpu.ops import async_ops

    return jnp.asarray(async_ops.alltoall(np.asarray(tensor), splits, name))


# ---------------------------------------------------------------------------
# broadcast
# ---------------------------------------------------------------------------

def broadcast(tensor, root_rank: int = 0, name: str | None = None):
    """Every worker receives ``root_rank``'s value (reference MPI_Bcast path,
    operations.cc:1333-1353).

    In-mesh this is a masked ``psum``: zero every shard except the root's and
    sum — one AllReduce on ICI, and autodiff yields exactly the reference's
    registered broadcast gradient (psum of the cotangent, zeroed off-root;
    tensorflow/mpi_ops.py:146-161) with no custom rule.
    """
    _record_schedule("broadcast", name, tensor)
    axes = _in_mesh_axes()
    if axes is not None:
        # axis_index over a tuple gives the linearized index across the
        # (possibly factored dcn×ici) data axes.
        idx = lax.axis_index(axes if len(axes) > 1 else axes[0])
        orig_dtype = tensor.dtype
        work = tensor
        if not jnp.issubdtype(orig_dtype, jnp.inexact):
            work = work.astype(jnp.float32) if orig_dtype == jnp.bool_ else work
        masked = jnp.where(idx == root_rank, work,
                           jnp.zeros_like(work))
        out = lax.psum(masked, axes)
        return out.astype(orig_dtype)
    _require_not_traced("broadcast")
    if basics.size() == 1:
        return jnp.asarray(tensor)
    from horovod_tpu.core import device_reduce

    if device_reduce.enabled():
        arr = np.asarray(jnp.asarray(tensor))
        return jnp.asarray(device_reduce.process_broadcast(arr, root_rank))
    _require_full_job("broadcast")
    return multihost_utils.broadcast_one_to_all(
        jnp.asarray(tensor), is_source=basics.rank() == root_rank)


# ---------------------------------------------------------------------------
# sparse (IndexedSlices analog)
# ---------------------------------------------------------------------------

def allreduce_sparse(values, indices, dense_dim0: int | None = None,
                     average: bool = True):
    """Sparse gradient reduction — the reference's ``tf.IndexedSlices`` path,
    which allgathers values and indices instead of allreducing a dense tensor
    (reference tensorflow/__init__.py:67-78).

    Returns (gathered_values, gathered_indices); with ``average`` the values
    are pre-divided by the worker count, matching the reference.  Callers that
    want a dense result can scatter-add into ``dense_dim0`` rows via
    ``sparse_to_dense``.
    """
    axes = _in_mesh_axes()
    n = _data_width(axes) if axes is not None else basics.size()
    if average:
        values = values / n
    return allgather(values), allgather(indices)


def sparse_to_dense(values, indices, dense_dim0: int):
    out = jnp.zeros((dense_dim0,) + values.shape[1:], values.dtype)
    return out.at[indices].add(values)


# ---------------------------------------------------------------------------
# shard: the SPMD wrapper users put around a train step
# ---------------------------------------------------------------------------

def shard(fn=None, *, in_specs=None, out_specs=None, check_vma: bool = False):
    """Wrap ``fn`` in a ``shard_map`` over the global mesh so in-mesh
    collectives (``allreduce`` etc.) have the chip axis in scope.

    This replaces the reference's implicit "every process runs the script"
    SPMD model: instead of N processes each executing the step, one traced
    program executes on N chips.  Defaults shard/replicate nothing
    (``in_specs``/``out_specs`` of ``P()``); pass e.g.
    ``in_specs=(P(), hvd.batch_spec(ndim))`` for data parallelism.
    """
    if fn is None:
        return functools.partial(shard, in_specs=in_specs, out_specs=out_specs,
                                 check_vma=check_vma)
    m = mesh.global_mesh()
    P = PartitionSpec
    return jax.shard_map(
        fn, mesh=m,
        in_specs=P() if in_specs is None else in_specs,
        out_specs=P() if out_specs is None else out_specs,
        check_vma=check_vma)


def batch_spec(ndim: int, batch_dim: int = 0) -> PartitionSpec:
    return mesh.data_spec(ndim, batch_dim)
