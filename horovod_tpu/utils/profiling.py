"""Device-side profiling — the XLA half of the timeline story.

The native timeline (core/src/timeline.cc, HOROVOD_TIMELINE) covers the
coordination plane; device compute/collective timing belongs to the XLA
profiler (docs/timeline.md, "Compiled steps").  This module is the
compiled path's whole tracing: it opens the profiler session, it holds the
names the program writes into its compiled steps and onto the host's
timeline, and it reads those names back out of a compiled program.

    with hvd.profiling.trace("/tmp/jax-trace"):
        for _ in range(10):
            state = train_step(state, batch)
    table = hvd.profiling.scope_table(train_step.lower(state, batch).compile())

View in XProf / TensorBoard (`tensorboard --logdir /tmp/jax-trace`) or
Perfetto.  Rank-gated like every reference observability feature (only
rank 0 traces by default).

There is nothing to switch on.  A scope (``jax.named_scope``) is metadata
of the traced program: it reaches the ``op_name`` of every instruction made
under it and costs nothing on the device.  A span (:func:`annotate`) is a
``TraceAnnotation``: with no profiler session open it is a branch on an
atomic.  "On" is a profiler session.

The serving path's spans (:func:`span`, the ``hvd_srv_*`` names) are the
same annotation and, besides, one record each in a bounded ring this module
keeps (:func:`spans`): an untraced run can then be read too, by the
benchmark (``benchmarks/serve_spans.py``) and by an operator
(``ServingEngine.span_summary()``).  The ring is always there, holds
:data:`SPAN_CAPACITY` records and drops the oldest; importing this module,
or writing a span, never imports jax.

Start-up is read the same way.  The ``hvd_setup_*`` spans are written where
the work happens (the package's import, ``basics.init``, the native
engine's build and load, a serving backend's pool, ``chip.require_tpu``'s
ask of the backend), and :func:`listen`
registers ONE listener of ``jax.monitoring`` that writes a record a compile
stage (``hvd_compile_trace`` / ``_lower`` / ``_backend``: the function's
name, the persistent cache's hit or miss, the span that caused it).  Both
kinds go to a store of their own beside the ring, of at most
:data:`STARTUP_CAPACITY` records, so that an hour of ``hvd_srv_*`` spans
does not push a process's start out of :func:`spans`;
:func:`startup_summary` adds them up.
"""

from __future__ import annotations

import collections
import contextlib
import dataclasses
import itertools
import math
import re
import sys
import threading
import time
import typing

from horovod_tpu import basics

# -- The vocabulary ---------------------------------------------------------
# Every name the program writes, once.  Scopes reach the compiled step's
# instructions (``op_name``), spans the host lines of a profiler trace.
ALLREDUCE = "hvd_allreduce"     # DistributedOptimizer: the gradient reduction
OPTIMIZER = "hvd_optimizer"     # DistributedOptimizer: the inner optax update
BUCKET = "hvd_bucket_"          # + chain position 0.., or BUCKET_ALL
BUCKET_ALL = "all"              # the free-combining path: one bucket
FLASH_FWD = "hvd_flash_fwd"     # ops/flash_attention: forward kernel
FLASH_BWD = "hvd_flash_bwd"     # ... backward: dq, dk and dv in one pass
FLASH_DQ = "hvd_flash_dq"       # the two passes the backward was before
FLASH_DKV = "hvd_flash_dkv"     # that: no kernel has these names now, a
                                # trace of an older program and the
                                # benchmark's readers of it do
LOSS = "hvd_loss"               # ops/losses.softmax_cross_entropy, both ways
MOE_ROUTE = "hvd_moe_route"     # models/moe: router matmul, softmax, top-k,
                                # the sort by expert, the counts, the losses
MOE_DISPATCH = "hvd_moe_dispatch"   # ... tokens gathered into expert order
MOE_EXPERTS = "hvd_moe_experts"     # ... the grouped matmuls, the activation
MOE_COMBINE = "hvd_moe_combine"     # ... back to token order, gate-weighted sum
MOE_SHARED = "hvd_moe_shared"       # ... the experts every token visits
TOKEN_SUM = "hvd_token_sum"     # ops/token_sum: the kernel that adds a walked
                                # share's rows to their tokens, launched
                                # under MOE_COMBINE
MOE_GROUPED = "hvd_moe_grouped"     # ops/grouped_matmul: the walked share's
                                    # grouped matmuls over a work list of
                                    # small row tiles, launched under
                                    # MOE_EXPERTS
MOE_ROWS = "hvd_moe_rows"   # ops/moe_rows: the kernels that move a training
                            # layer's rows between token order and expert
                            # order, launched under MOE_DISPATCH and
                            # MOE_COMBINE, forward and backward
MLA_DOWN = "hvd_mla_down"       # models/transformer.LatentAttention: W_DQ,
                                # W_DKV, the two norms, the rotary key, the
                                # cache's write
MLA_UP = "hvd_mla_up"           # ... W_UQ and its rotation; expanded: W_UKV
MLA_ABSORB = "hvd_mla_absorb"   # ... absorbed: q W_UK^T and o_lat W_UV
MLA_ATTN = "hvd_mla_attn"       # ... absorbed: the products and the softmax
EVA_SUMMARY = "hvd_eva_summary"     # models/transformer.EvaAttention: a
                                # chunk's weighted mean of keys and of values
EVA_ATTN = "hvd_eva_attn"       # ... the window's exact keys and the
                                # summaries under one softmax (merged form:
                                # the flash forward kernels it launches)
                                # over the pool (expanded: the attention
                                # function's own names, FLASH_FWD)
SSM_PROJ = "hvd_ssm_proj"       # models/mamba: the in and out projections
SSM_CONV = "hvd_ssm_conv"       # ... causal depthwise conv, bias, silu
SSM_SCAN = "hvd_ssm_scan"       # ... ops/ssd_scan: softplus, the decays' sums,
                                # and the scan's two kernels (or, at shapes
                                # outside their tiling rule, XLA's products)
SSM_GATE = "hvd_ssm_gate"       # ... y * silu(z) and the gated RMSNorm
SSD_FWD = "hvd_ssd_fwd"         # ops/ssd_scan: forward kernel, launched under
SSD_BWD = "hvd_ssd_bwd"         # SSM_SCAN; ... backward kernel (no flash pass)
CAUSAL_CONV_FWD = "hvd_causal_conv_fwd"     # ops/causal_conv: the depthwise
CAUSAL_CONV_BWD = "hvd_causal_conv_bwd"     # conv, bias and silu of a run of
                                # a stream's columns, forward and backward
                                # kernel, a call a part (mamba: x, B, C),
                                # launched under SSM_CONV
KDA_PROJ = "hvd_kda_proj"       # models/kda: the six projections in (q, k, v,
                                # decay, step size, output gate), the one out
KDA_CONV = "hvd_kda_conv"       # ... causal depthwise conv of q, k, v over
                                # the carried tail, silu, the l2 norms
KDA_GATE = "hvd_kda_gate"       # ... the decay a key channel, the step size
KDA_SCAN = "hvd_kda_scan"       # ... ops/kda_scan: the delta rule, chunked
                                # (a pass without a cache) or one step (a
                                # cache call), and the state's write
KDA_OUT = "hvd_kda_out"         # ... the norm a head and the output gate
KDA_CHUNK = "hvd_kda_chunk"     # ops/kda_scan: the chunked form's kernel,
                                # launched under KDA_SCAN (no flash pass)
CCA_PROJ = "hvd_cca_proj"       # models/cca: q and k into the latent, the two
                                # value halves
CCA_CONV = "hvd_cca_conv"       # ... both causal convolutions over the
                                # carried tail, the q-k mean, the value
                                # shift, the tail's write
CCA_ATTN = "hvd_cca_attn"       # ... a head's q and k to length, rotary,
                                # the rows' write, the attention itself
                                # (the attention function's own names
                                # inside it: FLASH_FWD)
CCA_OUT = "hvd_cca_out"         # ... the output projection
MHC_COEF = "hvd_mhc_coef"       # models/hyper: a sublayer's coefficients, the
                                # flat norm of the stream's rows, the one
                                # projection, the sigmoids
MHC_SINKHORN = "hvd_mhc_sinkhorn"   # ... exp, then columns and rows divided
                                # by their sums hyper_sinkhorn_iters times
MHC_PRE = "hvd_mhc_pre"         # ... h, the mix of rows a sublayer reads
MHC_POST = "hvd_mhc_post"       # ... X', the rows mixed and the sublayer's
                                # output written into them
LOADER_WAIT = "hvd_loader_wait"         # data.BackgroundLoader: q.get()
LOADER_PRODUCE = "hvd_loader_produce"   # ... next(source), producer thread
H2D_PUT = "hvd_h2d_put"         # data.prefetch_to_device: the device_put
# (``hvd_chain_gate`` is collective_ops.CHAIN_GATE_SCOPE, older than this
# table; examples/overlap_audit.py counts it.)
# The serving path's spans (serving/engine.py), written through :func:`span`:
SRV_REQUEST = "hvd_srv_request"     # ServingEngine: submit() -> eviction
SRV_QUEUED = "hvd_srv_queued"       # ... submit() -> its prefill call's start
SRV_STEP = "hvd_srv_step"           # ... one step(): admit, decode, evict
SRV_PREFILL = "hvd_srv_prefill"     # ... around backend.prefill[_prefixed]
SRV_DECODE = "hvd_srv_decode"       # ... around backend.decode
SRV_VERIFY = "hvd_srv_verify"       # ... around backend.verify (speculation)
SRV_H2D = "hvd_srv_h2d"             # a backend call: the copies in
SRV_DISPATCH = "hvd_srv_dispatch"   # ... the jitted call, until enqueued
SRV_WAIT = "hvd_srv_wait"           # ... until the tokens are on the host
SRV_FETCH = "hvd_srv_fetch"         # ... logits and pair counts to the host
# Start-up, each where the work happens, written through :func:`span`:
SETUP_IMPORT = "hvd_setup_import"   # horovod_tpu/__init__.py, first line to
                                    # last; ``jax_loaded``: jax was imported
                                    # before, so its import is the caller's
SETUP_INIT = "hvd_setup_init"       # basics.init, the call that does the work
SETUP_ENGINE = "hvd_setup_engine"   # core/engine.lib: ``make`` and the load;
                                    # ``built``: make produced a new library
SETUP_POOL = "hvd_setup_pool"       # serving backend: the pool made on the
                                    # device; ``bytes``, ``pool_form``
SETUP_BACKEND = "hvd_setup_backend"     # chip.require_tpu asking jax for its
                                    # backend: the TPU runtime's attach where
                                    # nobody asked before; after the caller's
                                    # own ``jax.devices()`` a mark of its end
# The compile ledger, written by :func:`listen`'s listener, records only:
COMPILE_TRACE = "hvd_compile_trace"     # a function traced to a jaxpr
COMPILE_LOWER = "hvd_compile_lower"     # a jaxpr lowered to an MLIR module
COMPILE_BACKEND = "hvd_compile_backend"     # XLA / Mosaic compiling it, or
                                    # the persistent cache's load; ``cache``
                                    # ("hit", "miss", "off"), ``retrieval_s``,
                                    # ``saved_s``.  All three: ``fun_name``

FLASH_PASSES = (FLASH_FWD, FLASH_DQ, FLASH_DKV, FLASH_BWD)
MOE_SCOPES = (MOE_ROUTE, MOE_DISPATCH, MOE_EXPERTS, MOE_COMBINE)
MLA_SCOPES = (MLA_DOWN, MLA_UP, MLA_ABSORB, MLA_ATTN)
SSM_SCOPES = (SSM_PROJ, SSM_CONV, SSM_SCAN, SSM_GATE)
SSD_PASSES = (SSD_FWD, SSD_BWD)
CAUSAL_CONV_PASSES = (CAUSAL_CONV_FWD, CAUSAL_CONV_BWD)
KDA_SCOPES = (KDA_PROJ, KDA_CONV, KDA_GATE, KDA_SCAN, KDA_OUT)
CCA_SCOPES = (CCA_PROJ, CCA_CONV, CCA_ATTN, CCA_OUT)
MHC_SCOPES = (MHC_COEF, MHC_SINKHORN, MHC_PRE, MHC_POST)
SRV_CALLS = (SRV_PREFILL, SRV_DECODE, SRV_VERIFY)   # the backend's calls
SRV_LEAVES = (SRV_H2D, SRV_DISPATCH, SRV_WAIT, SRV_FETCH)   # in call order
SETUP_SPANS = (SETUP_IMPORT, SETUP_INIT, SETUP_ENGINE, SETUP_POOL,
               SETUP_BACKEND)
COMPILE_STAGES = (COMPILE_TRACE, COMPILE_LOWER, COMPILE_BACKEND)
# records the span ring holds before it drops the oldest: a 35 s serving
# run writes about 12 000
SPAN_CAPACITY = 65536
# ``hvd_setup_*`` and ``hvd_compile_*`` records kept beside the ring, the
# EARLIEST of the process: a benchmark cell's start writes 1 600 (a sparse
# training step) to 35 000 (four prefill programs of a 40-layer served model:
# every inner ``jax.jit`` is a trace record once a signature).  Past the
# bound a later one goes into the ring like any other record, so a process
# that recompiles for ever holds at most the two bounds together.
STARTUP_CAPACITY = 65536
# XLA:TPU replaces ``lax.ragged_dot`` with Mosaic kernels of its own and
# names them afresh (``op_name="ragged-dot-none"``, and
# ``"ragged-dot-metadata"`` for the tile table they share): the scope the
# program wrote is gone from them.  The program's one grouped matmul is the
# expert layer's, so :func:`scope_table` gives such a kernel that name back.
_RAGGED_DOT_KERNEL = "ragged-dot"
# jax's own markers on the name stack, which the phase rule reads
_REMAT = "rematted_computation"     # nn.remat / jax.checkpoint's re-run
PHASES = ("forward", "backward", "recompute", "optimizer", "collective",
          "unscoped")
_CONTRACTIONS = ("convolution", "dot")
_FORWARD_OWN = "forward contraction"    # marks a fusion's inner set, no phase


@contextlib.contextmanager
def trace(path: str, *, all_ranks: bool = False):
    """Capture an XLA profiler trace around the block (rank 0 only unless
    ``all_ranks``)."""
    import jax

    enabled = all_ranks or not basics.is_initialized() or basics.rank() == 0
    if not enabled:
        yield
        return
    with jax.profiler.trace(path):
        yield


def annotate(name: str):
    """Named span inside a trace (shows as a range in XProf)."""
    import jax

    return jax.profiler.TraceAnnotation(name)


# -- Spans that are also records ---------------------------------------------

_ring: collections.deque = collections.deque(maxlen=SPAN_CAPACITY)
_startup: list = []             # at most STARTUP_CAPACITY, the earliest
_startup_lock = threading.Lock()
_STARTUP_NAMES = frozenset(SETUP_SPANS + COMPILE_STAGES)
_ids = itertools.count(1)
_open = threading.local()       # .stack: this thread's open ``with`` spans


class Record(typing.NamedTuple):
    """One record of the ring, as :func:`spans` hands it out: ``start`` and
    ``end`` are ``time.perf_counter`` seconds, ``cause`` the id of the span
    that caused it (the enclosing one unless the writer named another, 0
    for none), ``rid`` the request it belongs to if it belongs to one, and
    ``fields`` the counts of the boundary (``bucket``, ``length``,
    ``slots``, ``live_tokens``, ``rids``, ``bytes``; of a sparse model's
    prefill or decode call ``moe_rows`` and ``moe_held``: the rows its
    expert layers visited and the held pairs they visited them for, and
    where they walked ``moe_tile_rows``: the rows of the row tiles the
    grouped matmul worked; of a
    prefill whose attention is a kernel ``attn_rows``: the query rows the
    kernel was asked to work, whole q blocks up to the prompt's end)."""
    name: str
    start: float
    end: float
    id: int
    cause: int
    rid: int | None
    fields: dict

    @property
    def seconds(self) -> float:
        return self.end - self.start


class Span:
    """A span while it is open; closing it writes its :class:`Record`.

    ``with span(name, ...) as s:`` is the ``TraceAnnotation``
    :func:`annotate` opens (if jax is loaded: on the XLA profiler's host
    plane, on its clock, beside the device plane; ``rid`` and ``fields``
    become the event's stats) and one record in the ring, written when the
    block ends; its ``cause`` is the enclosing span unless one is named, and
    ``s.fields`` may be added to inside the block.  A span that is no
    ``with`` block on one thread is :func:`open_span`'s."""

    __slots__ = ("name", "start", "end", "id", "cause", "rid", "fields",
                 "_annotation")

    def __init__(self, name: str, *, cause: int | None = None,
                 rid: int | None = None, **fields):
        self.name, self.start, self.end = name, None, None
        self.id, self.cause, self.rid = next(_ids), cause, rid
        self.fields = fields
        self._annotation = None

    def close(self, end: float | None = None, **fields) -> None:
        """Write the record; ``end`` defaults to now.  What the ring keeps
        is a plain tuple of numbers, strings and tuples of them, which the
        garbage collector stops tracking at its first look: a ring of
        objects grew the old generation by some 8 000 a 30 s window, enough
        to move a full collection, an 88 ms pause of the serving loop, into
        every window of the benchmark (PERF.md, PR 39)."""
        self.end = time.perf_counter() if end is None else end
        self.fields.update(fields)
        _keep((self.name, self.start, self.end, self.id, self.cause,
               self.rid, tuple(self.fields.items())))

    def __enter__(self) -> "Span":
        stack = getattr(_open, "stack", None)
        if stack is None:
            stack = _open.stack = []
        if self.cause is None:
            self.cause = stack[-1].id if stack else 0
        stack.append(self)
        jax = sys.modules.get("jax")    # never imported from here
        if jax is not None:
            stats = self.fields if self.rid is None \
                else {**self.fields, "rid": self.rid}
            self._annotation = jax.profiler.TraceAnnotation(self.name,
                                                            **stats)
            self._annotation.__enter__()
        self.start = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        self.close()
        if self._annotation is not None:
            self._annotation.__exit__(*exc)
            self._annotation = None
        _open.stack.pop()


span = Span     # ``with profiling.span(SRV_STEP, queued=3):``


def _keep(kept: tuple) -> None:
    """A closed span's tuple into the ring, or, a start-up record while
    there is room, into the store the ring's turnover does not reach."""
    if kept[0] in _STARTUP_NAMES and len(_startup) < STARTUP_CAPACITY:
        with _startup_lock:
            if len(_startup) < STARTUP_CAPACITY:
                _startup.append(kept)
                return
    _ring.append(kept)


def current_span() -> Span | None:
    """This thread's innermost open ``with`` span, for what is called
    inside one to add to its ``fields``."""
    stack = getattr(_open, "stack", None)
    return stack[-1] if stack else None


def open_span(name: str, *, start: float | None = None, cause: int = 0,
              rid: int | None = None, **fields) -> Span:
    """A span that is no ``with`` block on one thread (a request from
    ``submit()`` to its eviction): a record only, no annotation, written
    when its ``close()`` is called.  Its ``id`` is there from the start, to
    be the ``cause`` of what it brings about."""
    record = Span(name, cause=cause, rid=rid, **fields)
    record.start = time.perf_counter() if start is None else start
    return record


def spans() -> list[Record]:
    """The records held, in one list by the time they ended: the start-up
    store's (at most :data:`STARTUP_CAPACITY`, the process's earliest
    ``hvd_setup_*`` and ``hvd_compile_*``) and the ring's (the last
    :data:`SPAN_CAPACITY` of every other name)."""
    held = sorted(list(_startup) + list(_ring), key=lambda kept: kept[2])
    return [Record(*kept[:6], dict(kept[6])) for kept in held]


# -- Start-up and the compile ledger ------------------------------------------

# jax.monitoring's names (jax 0.9): the three stages of a compile, each a
# duration with ``fun_name``; the persistent cache's account of a backend
# compile, which the compiling thread emits INSIDE that compile's duration
_STAGE_OF = {
    "/jax/core/compile/jaxpr_trace_duration": COMPILE_TRACE,
    "/jax/core/compile/jaxpr_to_mlir_module_duration": COMPILE_LOWER,
    "/jax/core/compile/backend_compile_duration": COMPILE_BACKEND}
_CACHE_SECONDS = {
    "/jax/compilation_cache/cache_retrieval_time_sec": "retrieval_s",
    "/jax/compilation_cache/compile_time_saved_sec": "saved_s"}
_CACHE_ASKED = "/jax/compilation_cache/compile_requests_use_cache"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"
_compiling = threading.local()  # .cache: what this thread's open backend
                                # compile has heard from the cache so far
_listening = False


def _on_duration(event: str, seconds: float, **kw) -> None:
    """One record a compile stage: it ends now, on the ring's clock, and
    began ``seconds`` ago; its cause is the span this thread is inside."""
    name = _STAGE_OF.get(event)
    if name is None:
        key = _CACHE_SECONDS.get(event)
        if key is not None:
            heard = getattr(_compiling, "cache", None)
            if heard is not None:
                heard[key] = seconds
        return
    end = time.perf_counter()
    fields = {"fun_name": str(kw.get("fun_name", ""))}
    if name == COMPILE_BACKEND:
        heard = getattr(_compiling, "cache", None)
        fields["cache"] = "off"
        # (jax asks its cache layer with no directory set too: no cache)
        if heard is not None and \
                sys.modules["jax"].config.jax_compilation_cache_dir:
            fields.update(heard)
        _compiling.cache = None
    stack = getattr(_open, "stack", None)
    _keep((name, end - seconds, end, next(_ids),
           stack[-1].id if stack else 0, None, tuple(fields.items())))


def _on_event(event: str, **kw) -> None:
    if event == _CACHE_ASKED:       # a compile that uses the cache begins:
        _compiling.cache = {"cache": "miss"}    # a miss unless a hit follows
    elif event == _CACHE_HIT:
        heard = getattr(_compiling, "cache", None)
        if heard is not None:
            heard["cache"] = "hit"


def listen() -> bool:
    """Register the compile ledger's listener with ``jax.monitoring``, once
    however often it is called; False, and nothing done, in a process that
    has not imported jax (this never does).  Called by the program's own
    code that runs before a first compile: ``chip.enable_compile_cache``,
    ``basics.init``, ``ServingEngine.__init__``."""
    global _listening
    jax = sys.modules.get("jax")
    if jax is None:
        return False
    with _startup_lock:
        if not _listening:
            jax.monitoring.register_event_duration_secs_listener(_on_duration)
            jax.monitoring.register_event_listener(_on_event)
            _listening = True
    return True


def _union(intervals) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for lo, hi in sorted(intervals):
        if out and lo <= out[-1][1]:
            out[-1][1] = max(out[-1][1], hi)
        elif hi > lo:
            out.append([lo, hi])
    return [(lo, hi) for lo, hi in out]


def _seconds(union) -> float:
    return sum(hi - lo for lo, hi in union)


def _shared(a: list, b: list) -> list[tuple[float, float]]:
    """The parts two unions of intervals share."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if lo < hi:
            out.append((lo, hi))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


_CAUSES = SETUP_SPANS + SRV_CALLS   # the spans a compile is said to be under


def under(record: Record, by_id: dict) -> dict:
    """Who caused ``record``: up its ``cause`` chain, the first span that is
    a start-up span or a backend call (``span``), and the first ``bucket``
    on the way (a compile inside ``hvd_srv_dispatch`` inside
    ``hvd_srv_prefill{bucket=8192}`` belongs to that bucket).  Empty for a
    record nobody's span caused: the caller's own code."""
    out: dict = {}
    seen = set()
    while record.cause and record.cause not in seen:
        seen.add(record.cause)
        record = by_id.get(record.cause)
        if record is None:
            break
        if "bucket" in record.fields:
            out.setdefault("bucket", record.fields["bucket"])
        if record.name in _CAUSES:
            out.setdefault("span", record.name)
            break
    return out


def _programs(compiles: list, records) -> list[dict]:
    """``hvd_compile_backend`` records as a reader wants them: ``fun_name``,
    ``cache``, ``seconds`` and who caused each (:func:`under`)."""
    by_id = {r.id: r for r in records}
    return [{"fun_name": r.fields.get("fun_name", ""),
             "cache": r.fields.get("cache", "off"),
             "seconds": r.seconds, **under(r, by_id)} for r in compiles]


def compiles_after(records, t: float, keep: int = 32) -> dict:
    """The backend compiles among ``records`` that ended after ``t``: their
    ``count`` and the last ``keep`` of them (:func:`_programs`)."""
    late = [r for r in records if r.name == COMPILE_BACKEND and r.end > t]
    return {"count": len(late), "programs": _programs(late[-keep:], records)}


def _stretches_between(named: list, held: list, since, until,
                       keep: int) -> list[dict]:
    """The longest stretches between ``named`` (a union of intervals), with
    the record of ``held`` each begins and ends at."""
    if not named:
        return []
    lo = named[0][0] if since is None else since
    edges = [lo] + [t for iv in named for t in iv] \
        + [named[-1][1] if until is None else until]
    label = lambda r: " ".join(  # noqa: E731
        [r.name, r.fields["fun_name"]] if "fun_name" in r.fields
        else [r.name])
    ended = {r.end: label(r) for r in held}
    began = {max(r.start, lo): label(r) for r in reversed(held)}
    gaps = sorted(((b - a, a, b) for a, b in zip(edges[::2], edges[1::2])
                   if b > a), reverse=True)[:keep]
    return [{"seconds": took, "at_s": a - lo, "after": ended.get(a),
             "before": began.get(b)} for took, a, b in gaps]


def startup_summary(records=None, *, since: float | None = None,
                    until: float | None = None, longest: int = 5) -> dict:
    """Where a process's start went, from its own records (:func:`spans`
    unless ``records`` is given): those that ENDED by ``until`` (default:
    all), cut at ``since``.  Seconds are of the union of a name's
    intervals, since the parts nest (a jit traced inside a jit lies inside
    its caller's record, a compile inside ``hvd_setup_init`` or inside a
    warm-up call):

    ``import_s`` / ``init_s`` / ``engine_s`` / ``pool_s`` / ``backend_s``
    the five ``hvd_setup_*`` spans; ``trace_s`` / ``lower_s`` / ``backend_compile_s``
    the three stages of the compile ledger, with ``traces`` and ``programs``
    the counts of ``hvd_compile_trace`` and ``hvd_compile_backend`` records,
    ``cache_retrieval_s`` the sum of ``retrieval_s`` (inside
    ``backend_compile_s``) and ``cache_misses`` the backend compiles the
    persistent cache did not hold (0 on a warm start; a miss on a machine
    that has run the program before: docs/timeline.md); ``warm_s`` the
    serving backend's calls (``SRV_CALLS``) less the compile records inside
    them: warm-up requests being served; ``named_s`` the union of every
    record above: what of the process's start has a name; ``longest`` the
    longest backend compiles (:func:`_programs`) and ``unnamed`` the
    longest stretches no record covers (from ``since`` and up to ``until``
    where given), each with its ``seconds``, where it began (``at_s``) and
    the records it lies between (``after`` / ``before``: a name, and the
    ``fun_name`` of a compile stage); ``spans`` how many of
    each ``hvd_setup_*`` span and backend call there were (an absent one
    was not written: its seconds are no reading of 0)."""
    if records is None:
        records = spans()
    held = [r for r in records if (until is None or r.end <= until)
            and (since is None or r.end > since)]
    floor = -math.inf if since is None else since
    by_name: dict[str, list] = {}
    for r in held:
        by_name.setdefault(r.name, []).append((max(r.start, floor), r.end))
    cover = lambda *names: _union(  # noqa: E731
        [iv for n in names for iv in by_name.get(n, ())])
    backend = [r for r in held if r.name == COMPILE_BACKEND]
    took = lambda *names: _seconds(cover(*names))  # noqa: E731
    calls, compiles = cover(*SRV_CALLS), cover(*COMPILE_STAGES)
    named = cover(*SETUP_SPANS, *COMPILE_STAGES, *SRV_CALLS)
    return {
        "import_s": took(SETUP_IMPORT), "init_s": took(SETUP_INIT),
        "engine_s": took(SETUP_ENGINE), "pool_s": took(SETUP_POOL),
        "backend_s": took(SETUP_BACKEND),
        "trace_s": took(COMPILE_TRACE),
        "traces": len(by_name.get(COMPILE_TRACE, ())),
        "lower_s": took(COMPILE_LOWER),
        "backend_compile_s": took(COMPILE_BACKEND),
        "cache_retrieval_s": sum(r.fields.get("retrieval_s", 0.0)
                                 for r in backend),
        "cache_misses": sum(r.fields.get("cache") == "miss"
                            for r in backend),
        "programs": len(backend),
        "warm_s": _seconds(calls) - _seconds(_shared(calls, compiles)),
        "named_s": _seconds(named),
        "unnamed": _stretches_between(named, held, since, until, longest),
        "longest": _programs(sorted(
            backend, key=lambda r: -r.seconds)[:longest], records),
        "spans": {n: len(by_name[n]) for n in _CAUSES if n in by_name}}


def summarize(records) -> dict[str, dict]:
    """Per span name: ``count``, ``total_s`` and the nearest-rank
    ``p50_ms`` / ``p95_ms`` / ``max_ms`` of the durations."""
    took: dict[str, list] = {}
    for r in records:
        took.setdefault(r.name, []).append(r.end - r.start)
    out = {}
    for name, xs in took.items():
        xs.sort()
        rank = lambda q: (  # noqa: E731
            1e3 * xs[min(len(xs) - 1, int(q * len(xs)))])
        out[name] = {"count": len(xs), "total_s": sum(xs),
                     "p50_ms": rank(0.50), "p95_ms": rank(0.95),
                     "max_ms": 1e3 * xs[-1]}
    return out


def bucket_scope(k) -> str:
    """The scope of chain bucket ``k`` (``hvd_bucket_0``...), or of the one
    free-combining bucket (``BUCKET_ALL``)."""
    return f"{BUCKET}{k}"


# -- Reading the names back -------------------------------------------------

_COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all)"
    r"(-start|-done)?$")
_INSTRUCTION = re.compile(r"^\s+(?:ROOT )?%?([^\s=]+) = (.*)$")
_COMPUTATION = re.compile(r"^(?:ENTRY )?%?([^\s(]+) \(.*\{\s*$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"\bcalls=%?([\w.\-]+)")
_BUCKET = re.compile(re.escape(BUCKET) + r"(\w+)")
_ARRAY = re.compile(r"\b(pred|[a-z]+?(\d+)\w*)\[([\d,]*)\]")
# path components jax put there: a transform around a name, and no module
_TRANSFORM = re.compile(r"^(?:jvp|transpose|vmap)\((.*)\)$")
_NO_MODULE = re.compile(r"^(?:p?jit\(.*\)|shard_map|checkpoint|remat\d*|"
                        r"while|body|cond|branch_\d+_fun|closed_call|"
                        + _REMAT + r")$")


def phase_of(op_name: str, opcode: str = "") -> str:
    """The phase of ONE instruction, from its ``op_name`` and opcode.

    In order: a collective opcode is ``collective``; under ``hvd_optimizer``
    is ``optimizer``; under jax's ``rematted_computation`` (the forward that
    ``nn.remat`` / ``jax.checkpoint`` re-runs inside backward) is
    ``recompute``; under ``transpose(`` is ``backward``; under ``jvp(`` is
    ``forward``; anything else is ``unscoped``."""
    if _COLLECTIVE.match(opcode):
        return "collective"
    if OPTIMIZER in op_name:
        return "optimizer"
    if _REMAT in op_name:
        return "recompute"
    if "transpose(" in op_name:
        return "backward"
    if "jvp(" in op_name:
        return "forward"
    return "unscoped"


def module_of(op_name: str) -> str:
    """The module path inside ``op_name`` with indices folded:
    ``jit(step)/shard_map/transpose(jvp(Transformer))/layer_1/mlp/up/dot_general``
    is ``Transformer/layer_N/mlp/up``.  A transform leaves the name it
    wraps; jax's own components (``jit(..)``, ``shard_map``, remat's, a
    loop's ``while/body``) and the closing primitive are dropped; an
    instruction outside every module keeps our own scope if it has one
    (``hvd_optimizer``), else ``""``."""
    parts = []
    for part in op_name.split(";")[0].split("/")[:-1]:
        while (m := _TRANSFORM.match(part)):
            part = m.group(1)
        # (remat's backward is transpose(jvp(M))/jvp(M)/..: M once)
        if part and not _NO_MODULE.match(part) and parts[-1:] != [part]:
            parts.append(re.sub(r"_\d+$", "_N", part))
    return "/".join(parts)


@dataclasses.dataclass(frozen=True)
class Scope:
    """What the compiled program says of one instruction."""
    opcode: str
    op_name: str                # the instruction's own; "" if it has none
    phases: tuple               # one phase; two or more for a mixed fusion
    module: str                 # module_of(op_name)
    bucket: str | None = None   # "0".., "all": a collective under hvd_bucket_
    kernel: str | None = None   # a kernel's name: a FLASH_PASSES or
                                # SSD_PASSES or CAUSAL_CONV_PASSES pass,
                                # TOKEN_SUM, KDA_CHUNK,
                                # MOE_GROUPED, MOE_ROWS, or MOE_EXPERTS (XLA's own
                                # grouped matmul)
    bytes: int = 0              # of the result, from its shape

    @property
    def phase(self) -> str:
        """``forward`` ... ``unscoped``, or ``mixed`` (see ``label``)."""
        return self.phases[0] if len(self.phases) == 1 else "mixed"

    @property
    def label(self) -> str:
        """The phase, a mixed fusion's as its pair: ``backward+optimizer``."""
        return "+".join(self.phases)


def scope_table(compiled) -> dict[str, Scope]:
    """``{instruction name: Scope}`` for every instruction of a compiled
    program (``jit(f).lower(...).compile()``, or its ``as_text()``), parsed
    from the optimized HLO text -- the names a device trace's ``XLA Ops``
    events carry, so a trace joins to the program's own vocabulary by name.

    The rule.  An instruction's phase is :func:`phase_of` its own
    ``op_name``.  A fusion (any instruction that ``calls=`` a computation)
    is labelled by the instructions inside it, since XLA fuses across
    phases: the set of phases of its scoped inner instructions, in
    ``PHASES`` order.  One phase: the fusion has it, and ``unscoped`` riders
    inside (compiler-made copies, converts that lost their metadata) take
    the fusion's label.  Two or more: the fusion is ``mixed`` and keeps the
    pair (``backward+optimizer``: a weight-gradient matmul with adamw in
    its epilogue); nobody guesses how its time divides.  None: the
    fusion's own ``op_name`` decides.  One exception, by data dependence:
    ``forward`` instructions in a fusion that also holds ``backward`` ones
    run in the backward pass -- the compiler re-does cheap forward
    arithmetic (a weight's cast, silu, a norm's rsqrt) where backward
    consumes it rather than keep the result -- so they add no phase, unless
    one of them is a contraction (``convolution``, ``dot``), which the
    compiler never duplicates: then the fusion holds both passes' own work
    (the head's matmul with the loss and its gradient) and is
    ``forward+backward``.  A collective is ``collective`` by
    opcode whatever its scope, and carries its ``hvd_bucket_<k>``; a kernel
    (custom call) under ``hvd_flash_*``, ``hvd_ssd_*``,
    ``hvd_causal_conv_*``, ``hvd_token_sum``,
    ``hvd_kda_chunk`` or ``hvd_moe_grouped`` carries that name, and one that
    XLA:TPU made of a ``ragged_dot`` carries ``hvd_moe_experts``.  ``while`` and
    ``conditional`` bodies are computations like the entry: their
    instructions are in the table under their own names.
    """
    computations = _computations(
        compiled if isinstance(compiled, str) else compiled.as_text())
    inner_memo: dict[str, tuple] = {}

    def inner(computation: str) -> tuple[frozenset, str]:
        """(phases of a computation's scoped instructions, the first
        module among them)."""
        if computation not in inner_memo:
            inner_memo[computation] = (frozenset(), "")  # a cycle cannot be
            found, module = set(), ""
            for _, opcode, op_name, calls, _ in computations.get(
                    computation, ()):
                if calls:
                    deeper, inside = inner(calls)
                    found |= deeper
                    module = module or inside
                elif op_name:
                    phase = phase_of(op_name, opcode)
                    found.add(phase)
                    if phase == "forward" and opcode in _CONTRACTIONS:
                        found.add(_FORWARD_OWN)
                    module = module or module_of(op_name)
            inner_memo[computation] = (frozenset(found - {"unscoped"}),
                                       module)
        return inner_memo[computation]

    table: dict[str, Scope] = {}
    for instructions in computations.values():
        for name, opcode, op_name, calls, shape in instructions:
            own = phase_of(op_name, opcode)
            phases = (own,)
            module = module_of(op_name)
            if calls and own != "collective":
                inside, inner_module = inner(calls)
                if "backward" in inside and _FORWARD_OWN not in inside:
                    inside = inside - {"forward"}
                if inside:
                    phases = tuple(p for p in PHASES if p in inside)
                # a fusion whose own name holds no module (its root is the
                # user's apply_updates, or it has no name) goes under the
                # first module named inside it
                module = module or inner_module
            bucket = _BUCKET.search(op_name) if own == "collective" else None
            kernel = None
            if opcode == "custom-call":
                kernel = next((k for k in FLASH_PASSES + SSD_PASSES
                               + CAUSAL_CONV_PASSES
                               + (TOKEN_SUM, KDA_CHUNK, MOE_GROUPED,
                                  MOE_ROWS)
                               if k in op_name),
                              MOE_EXPERTS
                              if op_name.startswith(_RAGGED_DOT_KERNEL)
                              else None)
            table[name] = Scope(
                opcode=opcode, op_name=op_name, phases=phases, module=module,
                bucket=bucket.group(1) if bucket else None, kernel=kernel,
                bytes=_shape_bytes(shape))
    return table


def _computations(text: str) -> dict[str, list]:
    """Optimized HLO text as ``{computation: [(instruction name, opcode,
    op_name, the computation it calls or None, result shape), ...]}``."""
    computations: dict[str, list] = {}
    current = None
    for line in text.splitlines():
        if current is None:
            m = _COMPUTATION.match(line)
            if m:
                current = computations.setdefault(m.group(1), [])
            continue
        if line.startswith("}"):
            current = None
            continue
        m = _INSTRUCTION.match(line)
        if not m:
            continue
        rest = m.group(2)
        op = _OP_NAME.search(rest)
        calls = _CALLS.search(rest)
        shape, opcode = _shape_and_opcode(rest)
        current.append((m.group(1), opcode, op.group(1) if op else "",
                        calls.group(1) if calls else None, shape))
    return computations


def _shape_and_opcode(rest: str) -> tuple[str, str]:
    """An instruction's text after ``%name = `` split into its result
    shape, which for a tuple is a parenthesised list, and its opcode."""
    if rest.startswith("("):
        depth = 0
        for i, ch in enumerate(rest):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                break
        shape, rest = rest[:i + 1], rest[i + 1:].lstrip()
    else:
        shape, _, rest = rest.partition(" ")
    return shape, re.match(r"[\w\-]*", rest).group(0)


def _shape_bytes(shape: str) -> int:
    """Bytes of every array in a shape's text (``f32[2048,32256]{1,0}``;
    a tuple's arrays summed; ``pred`` and sub-byte types count a byte)."""
    return sum(
        math.prod(int(d) for d in m.group(3).split(",") if d)
        * max(int(m.group(2) or 8) // 8, 1)
        for m in _ARRAY.finditer(shape))


def expert_load(pairs) -> dict:
    """What one call of an expert layer did to its experts, from the
    per-expert counts of (token, expert) pairs the layer sows (``[E]``
    ints; ``models/moe.py``, collection ``moe_stats``): how many pairs
    there were, the fullest expert's over the mean, and how many experts
    got none."""
    counts = [int(c) for c in pairs]
    total = sum(counts)
    return {"pairs": total,
            "max_over_mean": (max(counts) * len(counts) / total
                              if total else 0.0),
            "empty_experts": sum(c == 0 for c in counts)}
