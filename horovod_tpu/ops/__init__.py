from horovod_tpu.ops.collective_ops import (  # noqa: F401
    allgather,
    allreduce,
    allreduce_sparse,
    alltoall,
    batch_spec,
    broadcast,
    grouped_allreduce,
    overlap_compiler_options,
    quantized_grouped_allreduce,
    shard,
    sparse_to_dense,
)
from horovod_tpu.ops.compression import Compression  # noqa: F401
from horovod_tpu.ops.schedule_plan import (  # noqa: F401
    AdaptivePlanner,
    BucketPlan,
    ContextPlan,
    ContextWorkload,
    GradientManifest,
    context_plan,
    overlap_plan,
    plan_context,
)
from horovod_tpu.ops.flash_attention import (  # noqa: F401
    flash_attention,
    make_flash_attention,
)
from horovod_tpu.ops.losses import softmax_cross_entropy  # noqa: F401
from horovod_tpu.ops.async_ops import (  # noqa: F401
    allgather_async,
    allreduce_async,
    alltoall_async,
    barrier,
    broadcast_async,
    poll,
    synchronize,
)
