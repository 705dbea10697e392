"""Kernels (``ops/flash_attention``): the least time the chip could take
for a step's attention -- the larger of operations over peak FLOP/s and
bytes over peak HBM bandwidth, from shapes (benchmarks/flops.py) and the
peaks table -- over the time the trace shows in the kernels, in percent."""

from benchmarks import flops


def bound(run):
    """(least seconds a step, which peak sets it)."""
    calls = run.built.flash_calls
    by_flops = sum(flops.flash_train_flops(**c) for c in calls) \
        / run.peaks["bf16_flops_per_s"]
    by_bytes = sum(flops.flash_train_bytes(c["b"], c["h"], c["s"], c["d"])
                   for c in calls) / run.peaks["hbm_bytes_per_s"]
    return max(by_flops, by_bytes), "flops" if by_flops >= by_bytes else "bytes"


def read(run):
    t = run.trace
    if t is None or not run.built.flash_calls or not t.kind_s.get("flash"):
        return None
    least, which = bound(run)
    took = t.kind_s["flash"] / run.traced_steps
    print(f"flash_roofline: bound_by={which} least_ms={1e3 * least:.3f} "
          f"took_ms={1e3 * took:.3f}")
    return 100.0 * least / took
