"""Models (``models/moe.py``), served, a block-diffusion model's passes with
every expert held: the least time the chip could take to read the weights
the traced passes' picks needed -- each expert some live position picked,
its three matrices, once a pass and layer (``benchmarks/flops_sdar.py``;
``experts_touched`` is the program's own count on each ``hvd_srv_decode``
span) -- over peak HBM bandwidth, over the device time of the decode program
under ``hvd_moe_experts`` (the three grouped products and the activation, in
whichever form ``models/moe.py`` picks for the pass's pairs), in percent.
Bound by bytes: a dozen rows an expert."""

from benchmarks import flops_sdar, serve_scopes
from benchmarks.metrics import moe_experts_touched_share


def read(run):
    j = serve_scopes.of(run)
    if j is None or run.peaks is None or not j.calls["decode"]:
        return None
    from horovod_tpu.utils import profiling
    took = j.under("decode", profiling.MOE_EXPERTS, profiling.MOE_EXPERTS)
    # the stretch under the profiler comes after the drain
    got = moe_experts_touched_share.touched(
        run, lambda start: start >= run.end_t)
    if not took or got is None or got[1] != j.calls["decode"]:
        return None
    least = flops_sdar.block_decode_bytes(run.config, got[0]) \
        / run.peaks["hbm_bytes_per_s"]
    print(f"moe_block_decode_roofline: bound_by=bytes "
          f"least_ms={1e3 * least:.3f} took_ms={1e3 * took:.3f} "
          f"experts_touched={got[0]} decode_calls={got[1]}")
    return 100.0 * least / took
