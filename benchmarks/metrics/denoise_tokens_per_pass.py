"""Scheduler (``serving/engine.py``), a block-diffusion model: the tokens
the window's passes made final, over its live slot-passes (a slot that held
a request through one pass of the decode program, denoising or commit).
The program's own counts: ``slots`` on each ``hvd_srv_decode`` span that has
a ``block``, ``made_final`` on the ``hvd_srv_step`` span the pass ran in.
0.8 by construction under the static rule at a block of 4 in 4 steps (four
denoising passes and one commit a block: the commit passes' share of the
live slot-passes is this number's rest, 1 - it over the block's steps a
token); what a commit fused into the next block's first pass, or the
dynamic rule on trained weights, would raise."""

from horovod_tpu.utils import profiling


def passes(run) -> dict | None:
    """The window's passes summed: ``{"live", "commits", "made_final"}``
    over the spans that began inside it, or None where the program writes
    none with a block's fields (an older checkout, a model that makes a
    token a step, a training run)."""
    spans = getattr(profiling, "spans", None)
    if spans is None or not hasattr(run, "records"):
        return None
    inside = [r for r in spans() if run.inside(r.start)]
    calls = [r.fields for r in inside
             if r.name == profiling.SRV_DECODE and "block" in r.fields]
    steps = [r.fields for r in inside
             if r.name == profiling.SRV_STEP and "made_final" in r.fields]
    if not calls or not steps:
        return None
    return {"live": sum(f["slots"] for f in calls),
            "commits": sum(f["commits"] for f in calls),
            "made_final": sum(f["made_final"] for f in steps)}


def read(run):
    got = passes(run)
    return got["made_final"] / got["live"] if got and got["live"] else None
