"""Models (``models/moe.py``), served: the least time the chip could take to
read what the expert layers of the traced decode steps needed -- each
layer's router and shared experts, and each held expert some token of the
step picked (``benchmarks/flops_cohere2.py``; the picks are the program's
own count, a step) -- over peak HBM bandwidth, over the device time the
trace shows in those layers (``moe_decode_ms``), in percent.  Bound by
bytes: a handful of tokens do 2 to 16 operations a byte of weight."""

from benchmarks import flops_cohere2, serve_scopes
from benchmarks.metrics import moe_decode_ms


def read(run):
    took = moe_decode_ms.seconds(run, "decode")
    if not took or run.peaks is None:
        return None
    steps = serve_scopes.traced(run, "decode")
    if any(len(e) < 6 for e in steps):      # a family that counts no pairs
        return None
    least = flops_cohere2.moe_decode_bytes(
        run.config, [e[5]["pairs"] for e in steps]) \
        / run.peaks["hbm_bytes_per_s"]
    print(f"moe_decode_roofline: bound_by=bytes least_ms={1e3 * least:.3f} "
          f"took_ms={1e3 * took:.3f} decode_calls={len(steps)}")
    return 100.0 * least / took
