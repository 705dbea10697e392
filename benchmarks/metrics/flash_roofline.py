"""Kernels (``ops/flash_attention``): the least time the chip could take
for a step's attention -- the larger of operations over peak FLOP/s and
bytes over peak HBM bandwidth, from shapes (benchmarks/flops.py) and the
peaks table -- over the time the trace shows in the flash kernels
(``flash_ms``), in percent.  The count is of needed work: where a layer is
rematerialised its forward kernel runs twice and is counted once, so the
reading lies under the kernels' share of peak for the work they executed."""

from benchmarks import flops, scopes


def bound(run):
    """(least seconds a step, which peak sets it)."""
    calls = run.built.flash_calls
    by_flops = sum(flops.flash_train_flops(**c) for c in calls) \
        / run.peaks["bf16_flops_per_s"]
    by_bytes = sum(flops.flash_train_bytes(c["b"], c["h"], c["s"], c["d"])
                   for c in calls) / run.peaks["hbm_bytes_per_s"]
    return max(by_flops, by_bytes), "flops" if by_flops >= by_bytes else "bytes"


def read(run):
    took_ms = scopes.flash_ms(run)
    if not took_ms or run.peaks is None:
        return None
    least, which = bound(run)
    print(f"flash_roofline: bound_by={which} least_ms={1e3 * least:.3f} "
          f"took_ms={took_ms:.3f}")
    return 100.0 * 1e3 * least / took_ms
