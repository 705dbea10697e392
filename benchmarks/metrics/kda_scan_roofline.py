"""Models (``ops/kda_scan.py``), served: the least time the chip could take
for the delta rule's recurrence over the traced prefills' prompts at their
own lengths -- the larger of its operations over the MXU's peak and of the
bytes it cannot avoid over peak HBM bandwidth, every KDA layer
(``benchmarks/flops_kda.py``: needed work, whatever implements it) -- over
the device time of the prefill programs under ``hvd_kda_scan``, in percent.
The chunked form does more operations a position than are counted and works
whole chunks of a padded block."""

from benchmarks import flops_kda, serve_scopes
from benchmarks.metrics import kda_decode_ms


def read(run):
    if not hasattr(run, "records") or run.peaks is None:
        return None
    from horovod_tpu.utils import profiling
    scope = getattr(profiling, "KDA_SCAN", None)    # a program before PR 49
    took = scope and kda_decode_ms.seconds(run, "prefill", scope)
    prefills = serve_scopes.traced(run, "prefill")
    if not took or not prefills:
        return None
    lengths = [e[4] for e in prefills]
    by_flops = flops_kda.scan_flops(run.config, sum(lengths)) \
        / run.peaks["bf16_flops_per_s"]
    by_bytes = flops_kda.scan_bytes(run.config, lengths) \
        / run.peaks["hbm_bytes_per_s"]
    least = max(by_flops, by_bytes)
    print(f"kda_scan_roofline: bound_by="
          f"{'bytes' if by_bytes >= by_flops else 'flops'} "
          f"least_ms={1e3 * least:.3f} took_ms={1e3 * took:.3f} "
          f"prefill_calls={len(prefills)}")
    return 100.0 * least / took
