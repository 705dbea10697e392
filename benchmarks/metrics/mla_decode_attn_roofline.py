"""Serving backend (``absorbed_decode_attention`` over the latent pool):
the least time the chip could take for the absorbed attention of the traced
decode steps -- the larger of the bytes of the latents and rotary keys each
live slot holds, once a layer, over peak HBM bandwidth, and the products'
operations over the MXU's peak (``benchmarks/flops_mla.py``; 121 operations
a byte against the chip's 240, so bytes bind) -- over the device time of the
decode program under ``hvd_mla_attn`` (the two score products, the softmax,
the weighted sum), in percent."""

from benchmarks import flops_mla, serve_scopes
from benchmarks.metrics import mla_decode_ms


def read(run):
    if not hasattr(run, "records") or run.peaks is None:
        return None
    from horovod_tpu.utils import profiling
    scope = getattr(profiling, "MLA_ATTN", None)    # a program before PR 42
    took = scope and mla_decode_ms.seconds(run, "decode", scope)
    steps = serve_scopes.traced(run, "decode")
    if not took or any(len(e) < 6 for e in steps):
        return None
    lengths = [e[5]["lengths"] for e in steps]
    by_bytes = flops_mla.decode_attention_bytes(run.config, lengths) \
        / run.peaks["hbm_bytes_per_s"]
    by_flops = flops_mla.decode_attention_flops(run.config, lengths) \
        / run.peaks["bf16_flops_per_s"]
    least = max(by_bytes, by_flops)
    print(f"mla_decode_attn_roofline: bound_by="
          f"{'bytes' if by_bytes >= by_flops else 'flops'} "
          f"least_ms={1e3 * least:.3f} took_ms={1e3 * took:.3f} "
          f"decode_calls={len(steps)}")
    return 100.0 * least / took
