"""Serving backend (``eva_decode_attention`` over the pool of rings and
summaries): the least time the chip could take for the attention of the
traced decode steps -- the bytes of the ring rows and summaries each live
slot's query sees, K and V, once a layer, over peak HBM bandwidth
(``benchmarks/flops_eva.py``; 1 operation a byte, so bytes bind) -- over the
device time of the decode program under ``hvd_eva_attn`` (the score product,
the mask, the softmax, the weighted sum), in percent.  The program reads a
slot's whole extent behind the mask, so it moves more than is counted."""

from benchmarks import flops_eva, serve_scopes
from benchmarks.metrics import eva_decode_ms


def read(run):
    if not hasattr(run, "records") or run.peaks is None:
        return None
    from horovod_tpu.utils import profiling
    scope = getattr(profiling, "EVA_ATTN", None)    # a program before PR 44
    took = scope and eva_decode_ms.seconds(run, "decode", scope)
    steps = serve_scopes.traced(run, "decode")
    if not took or any(len(e) < 6 for e in steps):
        return None
    least = flops_eva.decode_attention_bytes(
        run.config, [e[5]["lengths"] for e in steps]) \
        / run.peaks["hbm_bytes_per_s"]
    print(f"eva_decode_attn_roofline: bound_by=bytes "
          f"least_ms={1e3 * least:.3f} took_ms={1e3 * took:.3f} "
          f"decode_calls={len(steps)}")
    return 100.0 * least / took
