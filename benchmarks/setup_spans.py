"""What the program says of its own start: the ``hvd_setup_*`` spans and the
compile ledger (``hvd_compile_trace`` / ``_lower`` / ``_backend``) that
``horovod_tpu/utils/profiling.py`` keeps, read for the per-layer metrics
that give ``setup_s`` its parts.  ``run.py`` times set-up from outside, one
number; these readers ask the program where it went.

One source: ``profiling.spans()``, read once a run, and of it the records
that ENDED between the process's start and the stamp that opens the window
(``run.stamps[0]`` of a training run, ``run.open_t`` of a served one, and
``run.setup_s`` before it), added up by ``profiling.startup_summary``.  The
parts nest (a jit traced inside a jit, a compile inside ``hvd.init`` or
inside a warm-up call), so each is the UNION of its records' intervals and
they do not sum: ``named_s`` on the ``setup:`` line is the union of all of
them, and ``setup_unnamed_s`` is ``setup_s`` less that: the interpreter's
start, ``import jax``, the TPU runtime's attach, the family's host-side
work, eager dispatches, a training cell's warm-up calls, a served cell's
waits for arrivals in its lead-in.

A program without the records (an older checkout under these files) reads
as nothing: :func:`of` returns None and every metric is left out of the line.
"""

from __future__ import annotations

import json

from horovod_tpu.utils import profiling

_UNREAD = object()
# metric -> (key of startup_summary, the name in profiling's vocabulary of
# the span whose absence means "not read")
PARTS = {
    "setup_import_s": ("import_s", "SETUP_IMPORT"),
    "setup_init_s": ("init_s", "SETUP_INIT"),
    "setup_engine_s": ("engine_s", "SETUP_ENGINE"),
    "setup_pool_s": ("pool_s", "SETUP_POOL"),
    "setup_trace_s": ("trace_s", None),
    "setup_traces": ("traces", None),
    "setup_lower_s": ("lower_s", None),
    "setup_backend_compile_s": ("backend_compile_s", None),
    "setup_cache_retrieval_s": ("cache_retrieval_s", None),
    "setup_cache_misses": ("cache_misses", None),
    "setup_programs": ("programs", None),
    "setup_warm_s": ("warm_s", "SRV_PREFILL"),
}


def opening_stamp(run) -> float:
    """The stamp that opens the window, on ``time.perf_counter``."""
    return run.open_t if hasattr(run, "open_t") else run.stamps[0]


def of(run) -> dict | None:
    """The parts of one run's set-up, metric name -> value, read once; the
    first reading of a traced run prints the ``setup:`` line.  None for a
    program that keeps no compile ledger."""
    if not hasattr(profiling, "startup_summary"):
        return None
    got = vars(run).get("_setup_parts", _UNREAD)
    if got is _UNREAD:
        open_t = opening_stamp(run)
        got = run._setup_parts = parts(profiling.spans(),
                                       open_t - run.setup_s, open_t)
        if got is not None:
            line = got.pop("line")
            if run.trace_dir is not None:
                print(f"setup: {json.dumps(line)}", flush=True)
    return got


def metric(run, name: str):
    got = of(run)
    return None if got is None else got.get(name)


def parts(records, start_t: float, open_t: float) -> dict | None:
    """``records`` (``profiling.spans()``) between a process's start and its
    window's opening stamp, as the metrics and the line."""
    said = profiling.startup_summary(records, since=start_t, until=open_t)
    if not said["programs"] and not said["spans"]:
        return None     # no listener heard a compile and no span was written
    out = {name: said[key] for name, (key, needs) in PARTS.items()
           if needs is None or getattr(profiling, needs) in said["spans"]}
    setup_s = open_t - start_t
    out["setup_unnamed_s"] = setup_s - said["named_s"]
    line = {k: round(v, 4) if isinstance(v, float) else v
            for k, v in out.items()}
    out["line"] = {
        "setup_s": round(setup_s, 4), "named_s": round(said["named_s"], 4),
        **line, "spans": said["spans"],
        "longest_backend": [{**p, "seconds": round(p["seconds"], 3)}
                            for p in said["longest"]],
        "longest_unnamed": [{**g, "seconds": round(g["seconds"], 3),
                             "at_s": round(g["at_s"], 3)}
                            for g in said["unnamed"]]}
    return out

