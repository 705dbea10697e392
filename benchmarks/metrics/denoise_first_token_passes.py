"""Scheduler (``serving/engine.py``), a block-diffusion model: the passes a
request's slot went through from its admission to the first token handed
over (tokens become final out of order and a block is handed over when it
is whole, so the first waits for the masks of the request's first block: 1
to 4 under the static rule at a block of 4, by the prompt's length alone),
the mean over the requests that fell due inside the window.  The program's own count:
``first_token_passes`` on each ``hvd_srv_request`` span as it closes."""

import statistics

from horovod_tpu.utils import profiling


def read(run):
    spans = getattr(profiling, "spans", None)
    if spans is None or not hasattr(run, "records"):
        return None
    counted = {r.request.rid for r in run.counted}
    passes = [r.fields["first_token_passes"] for r in spans()
              if r.name == profiling.SRV_REQUEST and r.rid in counted
              and r.fields.get("first_token_passes") is not None]
    return statistics.mean(passes) if passes else None
