"""Models (``models/moe.py``), served, top-1 with every expert held: of the
experts a decode step could read -- ``num_experts`` in each layer -- the
share some live slot picked, over the window's decode steps, in percent.
The program's own count: ``experts_touched`` on each ``hvd_srv_decode``
span (distinct experts picked, summed over the layers).  What a step's
grouped products must read of the experts' weights follows it."""

from horovod_tpu.utils import profiling


def touched(run, inside) -> tuple[int, int] | None:
    """(experts touched, decode steps) over the ``hvd_srv_decode`` spans
    whose start ``inside`` admits, or None where the program counts none."""
    spans = getattr(profiling, "spans", None)
    if spans is None or not hasattr(run, "records"):
        return None
    counts = [r.fields["experts_touched"] for r in spans()
              if r.name == profiling.SRV_DECODE and inside(r.start)
              and "experts_touched" in r.fields]
    return (sum(counts), len(counts)) if counts else None


def read(run):
    if not hasattr(run, "records"):     # a training run: not this metric's
        return None
    got = touched(run, run.inside)
    if got is None:
        return None
    cfg = run.config
    return 100.0 * got[0] / (cfg["num_experts"] * cfg["num_hidden_layers"]
                             * got[1])
