"""Models (``models/moe.py``): the least time the chip could take for a
step's grouped matmuls -- the larger of operations over peak FLOP/s and
bytes over peak HBM bandwidth, from shapes (benchmarks/flops_moe.py) and the
peaks table -- over the time the trace shows under ``hvd_moe_experts``, in
percent."""

from benchmarks import flops_moe
from benchmarks.metrics import moe_ms


def bound(run):
    """(least seconds a step, which peak sets it)."""
    cfg = run.config
    pairs = run.built.notes["pairs_per_step_a_chip"]
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    layers = cfg["num_hidden_layers"]
    by_flops = layers * flops_moe.grouped_matmul_train_flops(pairs, d, f) \
        / run.peaks["bf16_flops_per_s"]
    by_bytes = layers * flops_moe.grouped_matmul_train_bytes(
        pairs, cfg["num_experts"], d, f) / run.peaks["hbm_bytes_per_s"]
    return max(by_flops, by_bytes), "flops" if by_flops >= by_bytes else "bytes"


def read(run):
    p = moe_ms.parts(run)
    if p is None or not p["experts"] or run.peaks is None:
        return None
    least, which = bound(run)
    print(f"moe_experts_roofline: bound_by={which} "
          f"least_ms={1e3 * least:.3f} took_ms={p['experts']:.3f}")
    return 100.0 * 1e3 * least / p["experts"]
