"""Serving backend (``cached_decode_attention`` with a window in its mask):
``decode_attn_roofline`` for a model whose sliding layers need the last
``sliding_window`` cached positions alone: the least time the chip could
take to read the keys and values the traced decode steps needed, min(length,
window) of them in a sliding layer and all in a full one
(``benchmarks/flops_cohere2.py``), over peak HBM bandwidth, over the device
time of the decode program's operations under the layers' ``attn`` modules
outside their projections, in percent.  Bound by bytes."""

from benchmarks import flops_cohere2, serve_scopes


def read(run):
    if not hasattr(run, "records"):      # a training run: not this metric's
        return None
    t = run.trace
    if t is None or not t.decode_attn_s or run.peaks is None:
        return None
    steps = serve_scopes.traced(run, "decode")
    if len(steps) != t.program_calls["decode"] \
            or any(len(e) < 6 for e in steps):
        return None
    least = flops_cohere2.decode_attention_window_bytes(
        run.config, [e[5]["lengths"] for e in steps]) \
        / run.peaks["hbm_bytes_per_s"]
    print(f"decode_attn_window_roofline: bound_by=bytes "
          f"least_ms={1e3 * least:.3f} took_ms={1e3 * t.decode_attn_s:.3f} "
          f"decode_calls={len(steps)}")
    return 100.0 * least / t.decode_attn_s
