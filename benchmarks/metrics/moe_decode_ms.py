"""Models (``models/moe.py``), served: device milliseconds a traced decode
step spends in the expert layers, every operation under a layer's
``moe_mlp`` path (route, dispatch, the grouped matmuls, combine, the shared
experts) and the grouped-matmul kernels the compiler makes of
``lax.ragged_dot``, which have a name (``hvd_moe_experts``) and no path.
From the trace joined to the decode program's own names
(``benchmarks/serve_scopes.py``)."""

from benchmarks import serve_scopes

MODULE = "moe_mlp"


def seconds(run, program: str):
    """Device seconds of ``program`` in the expert layers, or None."""
    j = serve_scopes.of(run)
    if j is None or not j.calls[program]:
        return None
    from horovod_tpu.utils import profiling
    return j.under(program, MODULE, profiling.MOE_EXPERTS)


def read(run):
    s = seconds(run, "decode")
    if not s:
        return None
    return 1e3 * s / serve_scopes.of(run).calls["decode"]
