"""Kernels (``ops/flash_attention``), served, under the block-causal mask:
the least time the chip could take for the two products of attention over
the traced prefills' cached positions (the prompts' whole blocks) -- each
position sees up to its own block's end, the block-causal triangle
(``benchmarks/flops_sdar.py``) -- at the MXU's peak, over the device time of
the forward kernel (``hvd_flash_fwd``) in the prefill programs, in percent.
Bound by FLOPs.  The kernel works whole tiles of a padded bucket, so it
executes more than is counted; a bucket that prefills densely runs no
kernel, and its prompts are counted on neither side."""

from benchmarks import flops_sdar, serve_scopes


def read(run):
    j = serve_scopes.of(run)
    if j is None or run.peaks is None \
            or "generation" not in run.config:
        return None
    from horovod_tpu.utils import profiling
    took = j.kernel_s["prefill"].get(profiling.FLASH_FWD, 0.0)
    # the prefills whose bucket takes the kernel: the others ran none
    backend = run.built.engine.backend
    prefills = [e for e in serve_scopes.traced(run, "prefill")
                if backend.prefill_attention(e[3]) == "flash"]
    if not took or not prefills:
        return None
    least = flops_sdar.prefill_attention_flops(
        run.config, [e[4] for e in prefills]) \
        / run.peaks["bf16_flops_per_s"]
    print(f"sdar_prefill_attn_roofline: bound_by=flops "
          f"least_ms={1e3 * least:.3f} took_ms={1e3 * took:.3f} "
          f"prefill_calls={len(prefills)}")
    return 100.0 * least / took
