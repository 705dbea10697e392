"""horovod_tpu — a TPU-native distributed training framework.

A from-scratch rebuild of the capabilities of Horovod v0.15.1 (reference at
/root/reference) designed for TPU hardware: process identity comes from the
pod-slice topology instead of ``mpirun`` (basics.py); the collective data
plane is XLA AllReduce/AllGather/CollectivePermute compiled over a
``jax.sharding.Mesh`` riding ICI/DCN instead of MPI/NCCL (ops/); gradient
fusion is a trace-time flat-bucket transform instead of a background-thread
staging buffer (ops/fusion.py); and the dynamic/eager API keeps a native C++
coordination engine for cross-host op ordering (core/), which SPMD lockstep
makes unnecessary on the compiled path.

Typical use (JAX, data-parallel — analog of reference README.md:148-226)::

    import horovod_tpu as hvd
    hvd.init()
    step = hvd.shard(my_step, in_specs=..., out_specs=...)
    # inside my_step: grads = hvd.grouped_allreduce(grads)  # fused psum

The package root resolves its surface lazily (PEP 562): ``import
horovod_tpu`` costs milliseconds, and the heavy jax import is paid on first
use of an attribute that needs it.  This matters operationally — engine-only
consumers (the C++ control-plane tests, torch/TF eager workers before
``init()``) boot fast, and N freshly spawned ranks don't all pay a
multi-second jax import just to reach their rendezvous window.
"""

from __future__ import annotations

import importlib
import sys
import time

# the package's import as a span of its own (``hvd_setup_import``, written
# at this file's end): from this line, and whether jax was there before,
# since then jax's import is the caller's and not in it
_import_began, _jax_loaded = time.perf_counter(), "jax" in sys.modules

__version__ = "0.1.0"

# attribute name -> module that defines it.  Submodules (callbacks, data,
# checkpoint, ...) resolve through importlib directly.
_ATTR_HOME = {}
for _mod, _names in {
    "horovod_tpu.basics": (
        "NotInitializedError", "cache_stats", "chips_per_slice",
        "control_plane_stats", "coord_state", "cross_rank",
        "cross_size", "failure_report", "init", "is_initialized",
        "local_num_chips", "local_rank", "local_size", "member_process_ids",
        "mpi_threads_supported", "num_chips", "rank", "shutdown", "size",
        "stall_report", "subset_active",
    ),
    "horovod_tpu.analysis.schedule": ("divergence_report",),
    "horovod_tpu.replication": ("replication_stats",),
    "horovod_tpu.serving.engine": ("serving_stats",),
    "horovod_tpu.core.engine": ("CollectiveError", "MembershipChanged"),
    "horovod_tpu.elastic": ("coordinator_endpoint", "on_reconfigure",
                            "resize_event"),
    "horovod_tpu.mesh": (
        "DATA_AXIS", "data_sharding", "data_spec", "global_mesh",
        "replicated_sharding",
    ),
    "horovod_tpu.ops": (
        "AdaptivePlanner", "BucketPlan", "Compression", "ContextPlan",
        "ContextWorkload", "GradientManifest", "allgather",
        "allgather_async", "allreduce",
        "allreduce_async", "allreduce_sparse", "alltoall", "alltoall_async",
        "barrier", "batch_spec", "broadcast", "broadcast_async",
        "context_plan",
        "flash_attention", "grouped_allreduce", "make_flash_attention",
        "overlap_compiler_options", "overlap_plan", "plan_context", "poll",
        "quantized_grouped_allreduce",
        "shard",
        "softmax_cross_entropy", "sparse_to_dense", "synchronize",
    ),
    "horovod_tpu.training": (
        "DistributedOptimizer", "accumulate_gradients", "allgather_object",
        "broadcast_object", "broadcast_optimizer_state",
        "broadcast_parameters", "elastic_loop", "master_weights",
        "scale_learning_rate",
    ),
}.items():
    for _n in _names:
        _ATTR_HOME[_n] = _mod
del _mod, _names, _n

# Attributes that resolve to a module rather than a symbol inside one.
_MODULE_ATTRS = {"profiling": "horovod_tpu.utils.profiling"}

_SUBMODULES = frozenset({
    "basics", "callbacks", "checkpoint", "core", "data", "dataplane",
    "elastic", "faults", "flax", "keras", "mesh", "models", "ops",
    "parallel", "relay", "replication", "run", "serving", "tensorflow",
    "torch", "training", "tree", "utils",
})

# NOTE: __all__ deliberately excludes the lazy submodules — a star-import
# must not eagerly pull in every optional framework binding (torch/TF may
# not even be installed where the jax path runs).
__all__ = sorted(_ATTR_HOME) + ["__version__"]


def __getattr__(name: str):
    home = _ATTR_HOME.get(name)
    if home is not None:
        value = getattr(importlib.import_module(home), name)
    elif name in _MODULE_ATTRS:
        value = importlib.import_module(_MODULE_ATTRS[name])
    elif name in _SUBMODULES:
        try:
            value = importlib.import_module(f"horovod_tpu.{name}")
        except ModuleNotFoundError as e:
            # An optional framework (torch/TF) missing from the environment
            # must read as "attribute absent" so hasattr()/getattr(default)
            # probing keeps working; a missing module *inside* horovod_tpu
            # is a real bug and propagates.
            if e.name is not None and e.name.startswith("horovod_tpu"):
                raise
            raise AttributeError(
                f"horovod_tpu.{name} is unavailable: {e}") from e
    else:
        raise AttributeError(f"module 'horovod_tpu' has no attribute {name!r}")
    globals()[name] = value  # cache: __getattr__ runs once per name
    return value


def __dir__():
    return sorted(set(__all__) | _SUBMODULES | set(_MODULE_ATTRS))


def _record_import() -> None:
    from horovod_tpu.utils import profiling

    inside = profiling.current_span()
    profiling.open_span(profiling.SETUP_IMPORT, start=_import_began,
                        cause=inside.id if inside else 0,
                        jax_loaded=_jax_loaded).close()


_record_import()
