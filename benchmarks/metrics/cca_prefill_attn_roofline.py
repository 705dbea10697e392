"""Kernels (``ops/flash_attention``), served attention inside CCA's latent:
the least time the chip could take for the two products of causal attention
over the traced prefills' prompts at their OWN lengths -- 8 query over 2 key
heads of 128, a causal triangle in every layer
(``benchmarks/flops_cca.py``) -- at the MXU's peak, over the device time of
the forward kernel (``hvd_flash_fwd``) in the prefill programs, in percent.
The prefills of a bucket that runs dense attention launch no kernel and are
counted on neither side.  Bound by FLOPs.  The kernel works whole tiles."""

from benchmarks import flops_cca, serve_scopes


def read(run):
    j = serve_scopes.of(run)
    if j is None or run.peaks is None:
        return None
    from horovod_tpu.utils import profiling
    took = j.kernel_s["prefill"].get(profiling.FLASH_FWD, 0.0)
    form = run.built.engine.backend.prefill_attention
    prefills = [e for e in serve_scopes.traced(run, "prefill")
                if form(e[3]) == "flash"]
    if not took or not prefills:
        return None
    least = flops_cca.prefill_attention_flops(
        run.config, [e[4] for e in prefills]) / run.peaks["bf16_flops_per_s"]
    print(f"cca_prefill_attn_roofline: bound_by=flops "
          f"least_ms={1e3 * least:.3f} took_ms={1e3 * took:.3f} "
          f"prefill_calls={len(prefills)}")
    return 100.0 * least / took
