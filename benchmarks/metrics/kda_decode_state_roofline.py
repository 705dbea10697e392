"""Serving backend (the pool's KDA states), served: the least time the chip
could take for the recurrence of the traced decode steps -- each live slot's
float32 state read and written once a KDA layer, over peak HBM bandwidth
(``benchmarks/flops_kda.py``) -- over the device time of the decode program
under ``hvd_kda_scan`` (the step on every slot's state, live or not, and
its write into the pool), in percent."""

from benchmarks import flops_kda, serve_scopes
from benchmarks.metrics import kda_decode_ms


def read(run):
    if not hasattr(run, "records") or run.peaks is None:
        return None
    from horovod_tpu.utils import profiling
    scope = getattr(profiling, "KDA_SCAN", None)    # a program before PR 49
    took = scope and kda_decode_ms.seconds(run, "decode", scope)
    steps = serve_scopes.traced(run, "decode")
    if not took or not steps:
        return None
    least = flops_kda.decode_state_bytes(
        run.config, [e[3] for e in steps]) / run.peaks["hbm_bytes_per_s"]
    print(f"kda_decode_state_roofline: bound_by=bytes "
          f"least_ms={1e3 * least:.3f} took_ms={1e3 * took:.3f} "
          f"decode_calls={len(steps)}")
    return 100.0 * least / took
