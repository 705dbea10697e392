"""Serving backend (the pool's rows of a ``"cca"`` layer), served: the least
time the chip could take to read the keys and values the traced decode steps
needed -- each live slot's cached length times the 1024 bytes of K and V a
position holds a layer (``benchmarks/flops_cca.py``), over peak HBM
bandwidth -- over the device time of the decode program under
``hvd_cca_attn`` (a head's norm and rotary, the rows' write, the products
and the softmax over every slot's whole extent, live or not), in percent.
Bound by bytes."""

from benchmarks import flops_cca, serve_scopes
from benchmarks.metrics import kda_decode_ms


def read(run):
    if not hasattr(run, "records") or run.peaks is None:
        return None
    from horovod_tpu.utils import profiling
    scope = getattr(profiling, "CCA_ATTN", None)    # a program before PR 52
    took = scope and kda_decode_ms.seconds(run, "decode", scope)
    steps = serve_scopes.traced(run, "decode")
    if not took or not steps:
        return None
    least = flops_cca.decode_attention_bytes(
        run.config, [e[4] for e in steps]) / run.peaks["hbm_bytes_per_s"]
    print(f"cca_decode_attn_roofline: bound_by=bytes "
          f"least_ms={1e3 * least:.3f} took_ms={1e3 * took:.3f} "
          f"decode_calls={len(steps)}")
    return 100.0 * least / took
