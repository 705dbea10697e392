"""Kernels (``ops/flash_attention``), served latent attention beside KDA
layers: the least time the chip could take for the two products of expanded
attention over the traced prefills' prompts at their own lengths -- keys of
nope + rope, values of v_head_dim, a causal triangle in the ONE layer in six
that is latent attention (``benchmarks/flops_kda.py``) -- at the MXU's peak,
over the device time of the forward kernel (``hvd_flash_fwd``) in the prefill
programs, in percent.  Bound by FLOPs.  The kernel works whole tiles."""

from benchmarks import flops_kda, serve_scopes


def read(run):
    j = serve_scopes.of(run)
    if j is None or run.peaks is None:
        return None
    from horovod_tpu.utils import profiling
    took = j.kernel_s["prefill"].get(profiling.FLASH_FWD, 0.0)
    prefills = serve_scopes.traced(run, "prefill")
    if not took or not prefills:
        return None
    least = flops_kda.latent_prefill_flops(
        run.config, [e[4] for e in prefills]) / run.peaks["bf16_flops_per_s"]
    print(f"kdamla_prefill_attn_roofline: bound_by=flops "
          f"least_ms={1e3 * least:.3f} took_ms={1e3 * took:.3f} "
          f"prefill_calls={len(prefills)}")
    return 100.0 * least / took
