"""High-level training API — DistributedOptimizer and state broadcast.

This is the TPU-native analog of the reference's L4 surface:

* ``DistributedOptimizer`` — wraps any optax ``GradientTransformation`` so its
  update first averages gradients across all workers with fused (bucketed)
  allreduce, exactly what the reference's wrappers do for TF/torch/Keras
  (reference tensorflow/__init__.py:135-225, torch/__init__.py:42-150,
  keras/_impl.py:20-61).  Compression and a backward-pass-style bucketing
  order are supported: buckets are issued as soon as their gradients exist
  (the reference's backward-hook structure).  Round 5: the bucket psums
  are dependency-chained so XLA's combiner cannot re-merge them, which
  puts the early buckets' all-reduces INSIDE backward in the schedule;
  with ``hvd.overlap_compiler_options()`` at jit time the TPU backend
  executes them as async continuation fusions — real comm/compute
  overlap, reproducing the reference's defining runtime property
  (examples/overlap_audit.py, tests/test_overlap.py).  Whether the
  overlap pays on four chips is the ``dsc1p3b-dp4`` cell's to say
  (PERF.md).
* ``broadcast_parameters`` / ``broadcast_optimizer_state`` — pytree-wide
  broadcast from a root worker, the state-bootstrap contract every reference
  binding ships (torch/__init__.py:153-301, tensorflow/__init__.py:90-133,
  keras callbacks).  Works both in-mesh (masked psum) and eagerly across
  processes.
* ``broadcast_object`` — arbitrary-Python-object broadcast (the reference
  tensor-izes scalars for optimizer state, torch/__init__.py:197-247; we
  serialize through numpy the same way).

Momentum/LR-rescale semantics: like the reference, averaging gradients (not
summing) keeps hyperparameters comparable to single-worker training; scale the
learning rate by ``hvd.num_chips()`` per the linear-scaling recipe the
reference documents (README.md:195-200) — see ``scale_learning_rate``.
"""

from __future__ import annotations

import pickle
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
import optax

from horovod_tpu import basics
from horovod_tpu.ops import collective_ops
from horovod_tpu.ops.compression import Compression
from horovod_tpu.utils import profiling


class DistributedState(NamedTuple):
    inner: Any


class DistributedEFState(NamedTuple):
    """State when int8 compression is active: inner optimizer state plus the
    per-parameter error-feedback residual (quantization error carried into
    the next step's gradients)."""

    inner: Any
    error: Any


def DistributedOptimizer(optimizer: optax.GradientTransformation,
                         *,
                         average: bool = True,
                         compression=Compression.none,
                         threshold_bytes: int | None = None,
                         sharded_state: bool = False,
                         planner=None,
                         ) -> optax.GradientTransformation:
    """Wrap ``optimizer`` so updates see globally-averaged gradients.

    Drop-in: ``opt = hvd.DistributedOptimizer(optax.sgd(lr))`` — the analog of
    the reference's ``hvd.DistributedOptimizer(tf.train.AdagradOptimizer(...))``
    (reference README.md:159-163).  In-mesh on a single axis, gradients
    reduce with one ``psum`` per tensor and XLA's all-reduce combiner
    supplies the fusion (measured equivalent to the reference's fusion
    buffer, minus a pack/unpack pass — docs/tensor-fusion.md);
    ``threshold_bytes`` / ``HOROVOD_FUSION_THRESHOLD`` shape the flat
    buckets everywhere they remain: the eager path, hierarchical
    multi-axis meshes, and the int8 quantization groups (ops/fusion.py).

    Use inside a step wrapped by :func:`horovod_tpu.shard` (in-mesh) or in a
    plain eager loop (process-level reduction) — same dual contexts as
    ``allreduce``.

    ``sharded_state=True`` switches to ZeRO-1: the gradient averaging
    becomes a reduce-scatter, the optimizer state lives sharded 1/K per
    device, and updates all-gather back (parallel/zero.py; in-mesh only,
    elementwise transforms).

    Comm/compute overlap on the single-axis path is decided per traced
    program by the schedule planner (ops/schedule_plan.py): at trace time
    the gradient manifest (per-tensor bytes/dtypes of the flattened
    ``grads``), the probed data-parallel width, and the device-memory
    headroom pick a chain depth — chaining the bucket psums so the
    backend schedules early buckets' all-reduces during backward, and
    bypassing the chain where it cannot help (width 1) or cannot fit
    (headroom deficit).  At width 1 the same plan names the large
    gradients to materialise before the inner update (each behind its own
    ``optimization_barrier``, values unchanged), so XLA does not fuse the
    update into the epilogue of the matmul that produces the gradient
    (docs/tensor-fusion.md).  Nothing else selects the schedule: no
    argument and no environment name overrides the planner.  ``planner=``
    is the seam tests and ``examples/overlap_audit.py`` use to force a
    depth (``AdaptivePlanner(default_depth=0)``) and compare programs: any
    object with ``plan(manifest, width, headroom_mb) -> BucketPlan``.  Pass
    ``compiler_options=hvd.overlap_compiler_options()`` to ``jax.jit`` to
    make the chained all-reduces asynchronous
    (collective_ops._chained_allreduce); inspect the decision with
    ``hvd.overlap_plan()``.
    """
    if sharded_state:
        if (compression is not Compression.none
                or threshold_bytes is not None
                or planner is not None):
            raise ValueError(
                "sharded_state=True uses a reduce-scatter of the flat "
                "gradient vector; compression/threshold_bytes/planner do "
                "not apply to that path — drop them or use the replicated "
                "optimizer.")
        from horovod_tpu.parallel.zero import zero_optimizer

        return zero_optimizer(optimizer, average=average)

    if compression is Compression.int8:
        # int8 wire with error feedback: the quantization residual is state
        # (DistributedEFState.error) and re-enters the next step's
        # gradients, so precision lost to the 8-bit wire accumulates back
        # instead of biasing training.
        def init(params):
            return DistributedEFState(
                inner=optimizer.init(params),
                error=jax.tree.map(jnp.zeros_like, params))

        def update(grads, state, params=None, **extra):
            leaves, treedef = jax.tree.flatten(grads)
            err_leaves = jax.tree.leaves(state.error)
            with jax.named_scope(profiling.ALLREDUCE):
                reduced, resid = collective_ops.quantized_grouped_allreduce(
                    leaves, err_leaves, average=average,
                    threshold_bytes=threshold_bytes)
            grads = jax.tree.unflatten(treedef, reduced)
            with jax.named_scope(profiling.OPTIMIZER):
                updates, inner = optimizer.update(grads, state.inner, params,
                                                  **extra)
            return updates, DistributedEFState(
                inner=inner, error=jax.tree.unflatten(treedef, resid))

        return optax.GradientTransformation(init, update)

    def init(params):
        return DistributedState(inner=optimizer.init(params))

    def update(grads, state, params=None, **extra):
        leaves, treedef = jax.tree.flatten(grads)
        with jax.named_scope(profiling.ALLREDUCE):
            reduced = collective_ops.grouped_allreduce(
                leaves, average=average, compression=compression,
                threshold_bytes=threshold_bytes, planner=planner)
        grads = jax.tree.unflatten(treedef, reduced)
        with jax.named_scope(profiling.OPTIMIZER):
            updates, inner = optimizer.update(grads, state.inner, params,
                                              **extra)
        return updates, DistributedState(inner=inner)

    return optax.GradientTransformation(init, update)


class MasterWeightsState(NamedTuple):
    """State for :func:`master_weights`: the wrapped optimizer's state plus
    the full-precision master copy of every parameter."""

    inner: Any
    master: Any


def master_weights(optimizer: optax.GradientTransformation,
                   master_dtype=jnp.float32) -> optax.GradientTransformation:
    """Mixed-precision wrapper: low-precision resident params, full-precision
    master weights inside the optimizer state.

    The standard LLM-trainer recipe for killing per-use dtype converts: keep
    the *resident* parameters in the compute dtype (initialize the model
    with ``param_dtype=jnp.bfloat16``), so the forward pass reads them
    straight into the MXU with no f32→bf16 cast and the backward emits bf16
    gradients with no bf16→f32 upcast — while all optimizer math (moments,
    weight decay, the update itself) runs on an f32 master copy carried in
    this wrapper's state, so training numerics match f32-resident params.

    Per step: incoming (possibly bf16) gradients are upcast once, the inner
    optimizer updates the master, and the emitted update is the bf16 delta
    ``bf16(master') - param`` — ``optax.apply_updates`` then lands the
    resident params exactly on ``bf16(master')`` (the delta-add round-trips
    exactly whenever update ≪ param, by Sterbenz's lemma; in the rare
    other case the resident copy is within 1 ulp and the master still
    carries the truth, so no drift accumulates).

    Compose inside :func:`DistributedOptimizer` so the wire carries the
    half-width gradients::

        opt = hvd.DistributedOptimizer(hvd.master_weights(optax.adamw(lr)))

    Also composes with ``compression=Compression.int8`` (tested): the
    error-feedback residuals then live in the gradient dtype (bf16 when
    params are bf16-resident), so the carried residual is itself
    bf16-rounded — one extra quantization level below the int8 wire's,
    negligible against it.

    The reference has no analog (fp16 on its wire was compression-only,
    compression.py:42-63); this is TPU-first mixed precision in the
    spirit of its ``Compression.fp16`` — but for residency, not just wire.
    """

    def init(params):
        master = jax.tree.map(lambda p: p.astype(master_dtype), params)
        return MasterWeightsState(inner=optimizer.init(master), master=master)

    def update(grads, state, params=None, **extra):
        if params is None:
            raise ValueError(
                "master_weights requires params: call "
                "opt.update(grads, state, params)")
        g = jax.tree.map(lambda t: t.astype(master_dtype), grads)
        updates, inner_state = optimizer.update(g, state.inner, state.master,
                                                **extra)
        master = optax.apply_updates(state.master, updates)
        emitted = jax.tree.map(
            lambda m, p: (m.astype(p.dtype) - p).astype(p.dtype),
            master, params)
        return emitted, MasterWeightsState(inner=inner_state, master=master)

    return optax.GradientTransformation(init, update)


def scale_learning_rate(lr: float, backward_passes_per_step: int = 1) -> float:
    """Linear LR scaling by total chip count (reference README.md:195-200)."""
    return lr * basics.num_chips() * backward_passes_per_step


def accumulate_gradients(grad_fn, params, batch, num_microbatches: int):
    """Gradient accumulation over microbatches — ``backward_passes_per_step``
    for the compiled path.

    The reference's torch optimizer accumulates ``backward_passes_per_step``
    backward passes before one fused allreduce+step (torch/__init__.py:62-112);
    on TPU the idiomatic form is a ``lax.scan`` device loop over microbatches
    inside one compiled program, trading peak activation memory for steps.

    ``grad_fn(params, microbatch) -> (loss, grads)`` (e.g. from
    ``jax.value_and_grad(..., has_aux=...)`` composed however you like);
    ``batch`` is a pytree whose leaves' leading axis is split into
    ``num_microbatches`` equal chunks.  Returns ``(mean_loss, mean_grads)``
    — identical numerics to one full-batch pass for mean-reduced losses, so
    it composes with ``DistributedOptimizer`` unchanged (average over chips
    of a mean over microbatches).
    """
    if num_microbatches < 1:
        raise ValueError("num_microbatches must be >= 1")

    def split(a):
        if a.shape[0] % num_microbatches != 0:
            raise ValueError(
                f"leading axis {a.shape[0]} not divisible by "
                f"num_microbatches={num_microbatches}")
        return a.reshape((num_microbatches, a.shape[0] // num_microbatches)
                         + a.shape[1:])

    mb = jax.tree.map(split, batch)
    first = jax.tree.map(lambda a: a[0], mb)
    shapes = jax.eval_shape(grad_fn, params, first)
    zeros = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), shapes)

    def body(acc, chunk):
        # Tree-structured adds: has_aux grad_fns return ((loss, aux), grads),
        # so the loss slot is itself a pytree — aux accumulates (and is
        # averaged) alongside the loss.
        out = grad_fn(params, chunk)
        return jax.tree.map(jnp.add, acc, out), None

    (total_loss, total_grads), _ = jax.lax.scan(body, zeros, mb)
    inv = 1.0 / num_microbatches
    return (jax.tree.map(lambda v: v * inv, total_loss),
            jax.tree.map(lambda g: g * inv, total_grads))


# ---------------------------------------------------------------------------
# State bootstrap: broadcast parameters / optimizer state from a root
# ---------------------------------------------------------------------------

def broadcast_parameters(params, root_rank: int = 0):
    """Broadcast a pytree of arrays from ``root_rank`` to all workers.

    The analog of reference ``broadcast_parameters`` (torch/__init__.py:153-182)
    and ``BroadcastGlobalVariablesHook`` (tensorflow/__init__.py:101-133).
    Returns the synchronized pytree (JAX arrays are immutable, so unlike the
    reference there is no in-place variant — assign the result).
    """
    return jax.tree.map(
        lambda t: collective_ops.broadcast(t, root_rank=root_rank), params)


def broadcast_optimizer_state(opt_state, root_rank: int = 0):
    """Broadcast optimizer state (reference torch/__init__.py:185-301).

    The reference must tensor-ize Python scalars hiding in torch param_groups;
    optax state is already a pytree of arrays plus static structure, so array
    leaves broadcast collectively and non-array leaves (step schedules etc.)
    broadcast as objects.
    """
    def bcast_leaf(t):
        if isinstance(t, (jax.Array, np.ndarray)) or jnp.isscalar(t):
            return collective_ops.broadcast(jnp.asarray(t), root_rank=root_rank)
        return broadcast_object(t, root_rank=root_rank)

    return jax.tree.map(bcast_leaf, opt_state)


def allgather_object(obj):
    """Gather one picklable object per process, rank-ordered (modern
    reference ``hvd.allgather_object``); engine-level ragged gather."""
    from horovod_tpu.core.objects import allgather_object as _ao

    if basics.size() == 1:
        return [obj]
    return _ao(obj)


def elastic_loop(step_fn, state, *, num_steps: int, manager=None,
                 checkpoint_every: int = 1, metadata_fn=None,
                 resume: bool = True, on_resume=None):
    """Drive ``step_fn`` with fault hooks and preemption-safe checkpoints.

    The minimal elastic training driver (torchrun-lineage supervision for
    our synchronous SPMD world — docs/fault_tolerance.md): each step runs
    ``state = step_fn(step, state)`` after advancing the fault-injection
    clock; every ``checkpoint_every`` completed steps the ``manager``
    (checkpoint.CheckpointManager) records a complete checkpoint; a
    preemption signal drains a final synchronous checkpoint and exits 0
    so ``python -m horovod_tpu.run`` knows the state is durable.

    With ``resume=True`` (default) the loop first restores the newest
    complete checkpoint and continues from the step after it — restart
    equals continuation, which is what makes the launcher's
    ``--max-restarts`` relaunch bit-exact.  ``on_resume(ckpt)`` (an
    :class:`~horovod_tpu.checkpoint.ElasticCheckpoint`) lets the caller
    re-seat rng/data-iterator position from the resume metadata.

    Under ``HVD_TPU_ELASTIC=1`` (docs/fault_tolerance.md "In-place
    recovery") a :class:`~horovod_tpu.core.engine.MembershipChanged`
    signal from a step — a peer died and the survivors shrank, or a
    relaunched rank rejoined — is recovered WITHOUT leaving this process:
    the loop calls ``elastic.reconfigure()`` (re-forming the engine under
    the new membership and firing ``on_reconfigure`` callbacks, where LR
    re-scaling and data re-sharding belong), restores from the last
    complete checkpoint, and continues from the step after it; with no
    manager, the aborted step simply replays.  Without elastic mode the
    signal propagates like any failure and the launcher's full-restart
    supervision takes over.

    Returns the final state.
    """
    import sys as _sys

    from horovod_tpu import checkpoint as _checkpoint
    from horovod_tpu import faults as _faults
    from horovod_tpu import replication as _replication
    from horovod_tpu.core.engine import MembershipChanged as _Resized

    def _restore_latest(manager, state):
        # A peer can die DURING the restore agreement (checkpoint
        # ._restore_from_peers raises MembershipChanged from its wait
        # loops): reconfigure and retry at the new epoch instead of
        # letting a cascading failure abort a recoverable job.
        while True:
            try:
                return manager.restore_latest(template=state)
            except _Resized:
                from horovod_tpu import elastic as _elastic

                if not _elastic.enabled():
                    raise
                _elastic.reconfigure()

    start_step = 0
    if manager is not None:
        _checkpoint.install_preemption_handler()
        if resume:
            ckpt = _restore_latest(manager, state)
            if ckpt is not None:
                state = ckpt.state
                start_step = ckpt.step + 1
                if on_resume is not None:
                    on_resume(ckpt)

    def _metadata(step):
        md = {"step": step}
        if metadata_fn is not None:
            md.update(metadata_fn(step))
        return md

    def _drain_exit(step, state):
        if step >= 0:  # step -1 == preempted before any step completed
            manager.save(step, state, metadata=_metadata(step))
        manager.drain()
        _sys.exit(0)

    step = start_step
    while step < num_steps:
        if manager is not None and _checkpoint.preemption_requested():
            _drain_exit(step - 1, state)
        if _replication.enabled():
            # Pump relayed SHARD_PUT frames into the host-memory replica
            # store every step — a restore after a peer dies can only use
            # what this rank already drained.
            _replication.drain()
        _faults.step(step)
        try:
            state = step_fn(step, state)
        except _Resized:
            from horovod_tpu import elastic as _elastic

            if not _elastic.enabled():
                raise
            # In-place recovery: re-form the engine under the new
            # membership (same process), then resume from the last
            # complete checkpoint so every surviving rank — and any
            # joiner restoring at its own loop entry — re-enters the
            # step sequence at the same point with matching collective
            # names.  reconfigure() raises when WE were the rank removed
            # (the engine's restartable exit is already scheduled).
            _elastic.reconfigure()
            if manager is not None:
                ckpt = _restore_latest(manager, state)
                if ckpt is not None:
                    state = ckpt.state
                    step = ckpt.step + 1
                    if on_resume is not None:
                        on_resume(ckpt)
                    continue
            # No checkpoint to rewind to: the failed step's collectives
            # were aborted before completing, so replaying it is safe.
            continue
        except Exception:
            # A peer that drained on the same preemption signal tears the
            # collectives down under us (coordinated engine shutdown);
            # when OUR flag is up too, that failure IS the drain — save
            # the last completed step's state and exit clean.  Anything
            # else propagates: real failures must abort the job so the
            # launcher's supervision can restart it.
            if manager is not None and _checkpoint.preemption_requested():
                _drain_exit(step - 1, state)
            raise
        if manager is not None:
            if _checkpoint.preemption_requested():
                _drain_exit(step, state)
            if (step + 1) % max(checkpoint_every, 1) == 0 \
                    or step == num_steps - 1:
                manager.save(step, state, metadata=_metadata(step))
        step += 1
    if manager is not None:
        manager.drain()
    return state


def broadcast_object(obj, root_rank: int = 0):
    """Broadcast an arbitrary picklable object across processes.

    Mirrors the reference's scalar-wrapping trick (torch/__init__.py:197-228):
    pickle → uint8 tensor → broadcast(size) → broadcast(payload) → unpickle.
    """
    if basics.size() == 1:
        return obj
    if basics.rank() == root_rank:
        payload = np.frombuffer(pickle.dumps(obj), dtype=np.uint8)
        n = np.array([payload.size])
    else:
        payload = None
        n = np.array([0])
    n = int(np.asarray(collective_ops.broadcast(jnp.asarray(n), root_rank))[0])
    if payload is None:
        payload = np.zeros((n,), dtype=np.uint8)
    payload = payload[:n] if payload.size >= n else np.pad(payload,
                                                           (0, n - payload.size))
    out = np.asarray(collective_ops.broadcast(jnp.asarray(payload), root_rank))
    return pickle.loads(out.tobytes())
