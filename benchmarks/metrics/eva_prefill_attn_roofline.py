"""Kernels (``ops/flash_attention``), served EVA attention: the least time
the chip could take for the two products of attention over the traced
prefills' prompts at their own lengths -- every query against the exact keys
of its own window and the summaries of the windows behind it, 4 x heads x
head size operations a (query, seen row) pair a layer
(``benchmarks/flops_eva.py``) -- at the MXU's peak, over the device time
under ``hvd_eva_attn`` in the prefill programs, the flash forward kernels it
launches included, in percent.  Bound by FLOPs.  The kernels work whole tiles
of a padded bucket and, for the summaries, the whole rectangle behind a
mask, so they execute more than is counted."""

from benchmarks import flops_eva, serve_scopes
from benchmarks.metrics import eva_decode_ms


def read(run):
    if serve_scopes.of(run) is None or run.peaks is None:
        return None
    from horovod_tpu.utils import profiling
    scope = getattr(profiling, "EVA_ATTN", None)    # a program before PR 44
    took = scope and eva_decode_ms.seconds(run, "prefill", scope,
                                           kernel=profiling.FLASH_FWD)
    prefills = serve_scopes.traced(run, "prefill")
    if not took or not prefills:
        return None
    least = flops_eva.prefill_attention_flops(
        run.config, [e[4] for e in prefills]) / run.peaks["bf16_flops_per_s"]
    print(f"eva_prefill_attn_roofline: bound_by=flops "
          f"least_ms={1e3 * least:.3f} took_ms={1e3 * took:.3f} "
          f"prefill_calls={len(prefills)}")
    return 100.0 * least / took
