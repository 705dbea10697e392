"""Models (``models/mamba.py``): the least time the chip could take for a
step's state-space scans -- the larger of operations over peak FLOP/s and
bytes over peak HBM bandwidth, from shapes (benchmarks/flops_ssm.py, which the
family puts in ``built.notes``: forward, recompute and backward counted as a
step executes them) and the peaks table -- over the time the trace shows
under ``hvd_ssm_scan``, in percent."""

from benchmarks.metrics import ssm_ms


def bound(run):
    """(least seconds a step, which peak sets it)."""
    notes = run.built.notes
    by_flops = notes["ssd_scan_flops_per_step_a_chip"] \
        / run.peaks["bf16_flops_per_s"]
    by_bytes = notes["ssd_scan_bytes_per_step_a_chip"] \
        / run.peaks["hbm_bytes_per_s"]
    return max(by_flops, by_bytes), "flops" if by_flops >= by_bytes else "bytes"


def read(run):
    p = ssm_ms.parts(run)
    if p is None or not p["scan"] or run.peaks is None:
        return None
    least, which = bound(run)
    print(f"ssm_scan_roofline: bound_by={which} "
          f"least_ms={1e3 * least:.3f} took_ms={p['scan']:.3f}")
    return 100.0 * 1e3 * least / p["scan"]
