"""Models: the rate times the operations forward and backward need a token
or image (nothing recomputed; benchmarks/flops.py) over chips x the
``device_kind``'s bf16 peak, in percent."""


def read(run):
    if run.peaks is None:
        return None
    return 100.0 * run.rate * run.built.flops_per_unit / (
        run.chips * run.peaks["bf16_flops_per_s"])
