"""The serving loop: what ``run.py`` drives when a cell's family defines
``serve(config, traffic, chips, seed) -> Served`` (the training loop is
``run.train``, for a family that defines ``build``).

An open loop.  ``benchmarks/arrivals.py`` gives every request a due time;
the loop hands a request to ``engine.submit`` when the host clock passes its
due time and calls ``engine.step()`` otherwise, as ``python -m
horovod_tpu.serving`` does with its queue.  One thread, one process.  A
request's latencies are timed FROM ITS DUE TIME, not from ``submit()``: a
step that held the loop while a request fell due is a wait its user had,
and how late each request was handed over is reported beside the latencies
(``arrival_late_ms_p95``).

A run is: set-up (the family builds and warms every program), a lead-in of
``lead_in_s`` of the same arrival process that is served and not counted,
the window of ``--seconds``, and then, where the mix's ``drain_s`` is above
0, a drain in which nothing new is sent and the requests that fell due in
the window are served to their end (a late answer is late, not missing).
The counted requests are those due inside the window; the rate is every
token stamped inside it after the first over the time from that first
stamp to the last (``ServeRun.rate``).

Every token is stamped on the host clock by the engine itself (its
``clock`` is ``time.perf_counter``); every call into the backend is timed
by :class:`Timed`, the harness's span around the layer below the scheduler.
"""

from __future__ import annotations

import bisect
import dataclasses
import json
import os
import time

import jax
import numpy as np

from benchmarks import arrivals

clock = time.perf_counter
TRACE_SECONDS = 4.0         # of the same traffic, under the profiler


def _annotate(name: str):
    return jax.profiler.TraceAnnotation(name)


class Timed:
    """The backend with the host clock around its two calls.  ``log`` holds
    ``(kind, start, end, n, tokens)``: for a prefill the bucket and the
    prompt's own length, for a decode the slots in use and the sum of their
    live lengths (the keys and values the step has to read)."""

    def __init__(self, backend):
        self.inner = backend
        self.log: list[tuple] = []

    def prefill(self, padded, length, slot):
        t = clock()
        with _annotate("prefill"):
            out = self.inner.prefill(padded, length, slot)
        self.log.append(("prefill", t, clock(), int(padded.shape[1]),
                         int(length)))
        return out

    def decode(self, last_tokens, lengths):
        live = lengths[lengths > 0]
        t = clock()
        with _annotate("decode"):
            out = self.inner.decode(last_tokens, lengths)
        self.log.append(("decode", t, clock(), int(live.size),
                         int(live.sum())))
        return out

    def __getattr__(self, name):
        return getattr(self.inner, name)


@dataclasses.dataclass
class Record:
    """One request as the loop saw it; times on the host clock."""
    index: int                  # in the schedule
    due: float
    submitted: float
    request: object             # the engine's Request
    admitted: float | None = None       # its prefill call began

    @property
    def prompt_len(self) -> int:
        return len(self.request.prompt)

    @property
    def stamps(self) -> list[float]:
        """When each of its tokens was emitted."""
        r = self.request
        if r.ttft_s is None:
            return []
        return list(np.cumsum([r.submitted_t + r.ttft_s] + r.token_lat_s))

    @property
    def done(self) -> bool:
        return self.request.state == "DONE"


def drive(engine, sched, prompt_ids, *, first: int, offset_s: float,
          close_s: float, drain_s: float = 0.0, count_from_s: float = 0.0):
    """Serve requests ``first..`` of the schedule, request i due at
    ``t0 + sched.due_s[i] - offset_s``, until ``close_s`` after ``t0``; then
    up to ``drain_s`` more, sending nothing, until every request that fell
    due ``count_from_s`` after ``t0`` or later is done.
    Returns (t0, records, the next request's index, the clock at the end)."""
    t0 = clock()
    close = t0 + close_s
    records: list[Record] = []
    i, n = first, len(sched)
    busy = lambda: bool(engine.queue) or any(  # noqa: E731
        r is not None for r in engine.slots)

    def due(k: int) -> float:
        return t0 + float(sched.due_s[k]) - offset_s

    while True:
        now = clock()
        if now >= close:
            break
        while i < n and due(i) <= now:
            with _annotate("submit"):
                req = engine.submit(prompt_ids[i], int(sched.output_len[i]))
            records.append(Record(i, due(i), req.submitted_t, req))
            i += 1
        if busy():
            with _annotate("engine_step"):
                engine.step()
        else:
            nxt = min(due(i), close) if i < n else close
            with _annotate("wait_arrival"):
                time.sleep(max(0.0, nxt - clock()))
    owed = [r for r in records if r.due >= t0 + count_from_s]
    while drain_s and clock() < close + drain_s \
            and not all(r.done for r in owed):
        with _annotate("engine_step"):
            engine.step()
    return t0, records, i, clock()


def stamp_admissions(records: list[Record], log: list[tuple]) -> None:
    """A request was admitted when its prefill call began: the engine reads
    its clock for the first token as that call returns."""
    prefills = [e for e in log if e[0] == "prefill"]
    ends = [e[2] for e in prefills]
    for r in records:
        s = r.stamps
        if s:
            k = bisect.bisect_right(ends, s[0]) - 1
            r.admitted = prefills[k][1] if k >= 0 else None


def percentile(values, q: float) -> float | None:
    return float(np.percentile(values, q)) if len(values) else None


@dataclasses.dataclass
class ServeRun:
    """What a serving metric's reader is given: the fields the device's and
    the set-up's readers share with ``run.Run``, and the loop's own."""
    cell: dict
    config: dict
    traffic: dict
    built: object               # the family's Served
    chips: int
    peaks: dict | None
    setup_s: float
    compiles_in_window: int
    memory: dict | None
    open_t: float               # the window, on the host clock
    close_t: float
    end_t: float                # after the drain
    records: list               # every request sent, lead-in and all
    steps: list                 # Timed.log over the same time
    trace: object = None        # benchmarks.serve_trace.Summary
    trace_dir: str | None = None
    traced_steps_log: list = dataclasses.field(default_factory=list)

    @property
    def peak_bytes(self) -> int:
        m = self.memory
        return m["peak_bytes_in_use"] + m["peak_bytes_reserved"] if m else 0

    @property
    def seconds(self) -> float:
        return self.close_t - self.open_t

    @property
    def counted(self) -> list[Record]:
        """The requests that fell due inside the window."""
        return [r for r in self.records
                if self.open_t <= r.due < self.close_t]

    def inside(self, t: float) -> bool:
        return self.open_t <= t < self.close_t

    @property
    def tokens_in_window(self) -> int:
        return sum(self.inside(t) for r in self.records for t in r.stamps)

    @property
    def rate(self) -> float:
        """Every token emitted in the window over the window's whole time,
        the window taken from the first token stamp inside it to the last
        (as ``rates.whole_window_rate`` takes a training window from its
        first stamp to its last): eight slots' tokens share a decode step's
        stamp, and a window cut at fixed instants would gain or lose a
        whole step's tokens at either edge, 0.2% of the rate each."""
        stamps = sorted(t for r in self.records for t in r.stamps
                        if self.inside(t))
        if len(stamps) < 2 or stamps[-1] == stamps[0]:
            return 0.0
        return sum(t > stamps[0] for t in stamps) / (stamps[-1] - stamps[0])

    def steps_in_window(self, kind: str) -> list[tuple]:
        return [e for e in self.steps if e[0] == kind and self.inside(e[1])]

    def ttft_ms(self) -> list[float]:
        """Due time to first token of every counted request; one that has
        no token yet has waited until the run's end at least."""
        return [1e3 * ((r.stamps[0] if r.stamps else self.end_t) - r.due)
                for r in self.counted]

    def kv_live_tokens(self) -> tuple[float, int] | None:
        """Cached tokens that slots in use held over the window's decode
        steps: the mean, each step weighted by its host time, and the peak.
        The pool reserves ``num_slots * max_seq_len`` whatever is live."""
        steps = self.steps_in_window("decode")
        took = sum(e[2] - e[1] for e in steps)
        if not took:
            return None
        return (sum(e[4] * (e[2] - e[1]) for e in steps) / took,
                max(e[4] for e in steps))

    def token_gaps_ms(self) -> list[float]:
        """Between successive tokens of one request, the later one emitted
        inside the window: a prefill that held the decoding slots is in."""
        return [1e3 * (b - a) for r in self.records
                for a, b in zip(r.stamps, r.stamps[1:]) if self.inside(b)]


def measure(h) -> "run.Outcome":
    """One run of a serving cell; ``h`` is ``run.Harness``."""
    from benchmarks import run as harness, serve_trace, trace

    args, cell, config, traffic = h.args, h.cell, h.config, h.traffic
    t = clock()
    served = h.family.serve(config, traffic, h.chips, args.seed)
    lead_in, drain_s = float(traffic["lead_in_s"]), float(traffic["drain_s"])
    horizon = lead_in + args.seconds + (TRACE_SECONDS if args.trace else 0.0)
    sched = arrivals.schedule(traffic, horizon)
    prompt_ids = arrivals.prompts(traffic, sched, args.seed, served.vocab_size)
    # of the lead-in and the window, which every run of the cell sends
    print(f"arrivals: rate_per_s={traffic['rate']} " + json.dumps(
        arrivals.describe(arrivals.schedule(traffic,
                                            lead_in + args.seconds))))
    served.warm()
    print(f"build: family={config['family']} "
          f"parameters={served.parameters / 1e6:.1f}M "
          f"seconds={clock() - t:.1f} notes={json.dumps(served.notes)}")
    engine, timed = served.engine, served.engine.backend
    del timed.log[:]

    # the lead-in's end opens the window, and set-up ends there
    heartbeat = harness.Heartbeat()
    heartbeat.start()
    t0, records, nxt, end_t = drive(
        engine, sched, prompt_ids, first=0, offset_s=0.0,
        close_s=lead_in + args.seconds, drain_s=drain_s,
        count_from_s=lead_in)
    heartbeat.stop()
    open_t, close_t = t0 + lead_in, t0 + lead_in + args.seconds
    setup_s = open_t - harness.PROCESS_START
    compiles = sum(open_t <= c <= end_t for c in h.compile_events)
    memory = harness.fullest_chip(jax)
    run = ServeRun(cell=cell, config=config, traffic=traffic, built=served,
                   chips=h.chips, peaks=h.peaks, setup_s=setup_s,
                   compiles_in_window=compiles, memory=memory, open_t=open_t,
                   close_t=close_t, end_t=end_t, records=records,
                   steps=list(timed.log))
    stamp_admissions(records, run.steps)
    print(f"memory: fullest_chip={json.dumps(memory)}")

    out_dir = os.path.join(args.out, cell["name"])
    os.makedirs(out_dir, exist_ok=True)
    tag = f"seed{args.seed}.trace{args.trace}"
    device = {"platform": h.dev.platform, "kind": h.dev.device_kind,
              "count": len(h.devices), "memory_peak_bytes": run.peak_bytes}
    breakdown = None
    if args.trace:
        run.trace_dir = os.path.join(out_dir, f"{tag}.profile")
        mark = len(timed.log)
        with trace.record(run.trace_dir):
            drive(engine, sched, prompt_ids, first=nxt,
                  offset_s=float(sched.due_s[nxt]) if nxt < len(sched)
                  else 0.0, close_s=TRACE_SECONDS)
        run.traced_steps_log = list(timed.log[mark:])
        run.trace = serve_trace.reduce(
            trace.load(run.trace_dir), served.program_names,
            served.decode_scopes() if h.peaks else None)
        if run.trace is not None:
            device["busy_s"] = run.trace.busy_s
            device["window_s"] = run.trace.window_s
            breakdown = {"device_ops": run.trace.device_ops,
                         "idle_gaps": run.trace.idle_gaps}
            print(f"serve_scopes: {json.dumps(run.trace.describe())}")

    counted = run.counted
    rejected = [r for r in records if r.request.finish_reason == "rejected"]
    unfinished = [r for r in counted if not r.done] if drain_s else []
    gaps = run.token_gaps_ms()
    decodes = run.steps_in_window("decode")
    prefills = run.steps_in_window("prefill")
    print(f"window: seconds={run.seconds:.3f} sent={len(records)} "
          f"counted={len(counted)} finished="
          f"{sum(r.done for r in counted)} rejected={len(rejected)} "
          f"tokens_in_window={run.tokens_in_window} "
          f"tokens_per_s={run.rate:.2f} "
          f"ttft_ms_p50={percentile(run.ttft_ms(), 50):.2f} "
          f"ttft_ms_p90={percentile(run.ttft_ms(), 90):.2f} "
          f"ttft_ms_p95={percentile(run.ttft_ms(), 95):.2f} "
          f"ttft_ms_mean={float(np.mean(run.ttft_ms())):.2f} "
          f"tpot_ms_p50={percentile(gaps, 50) or 0:.2f} "
          f"decode_steps={len(decodes)} prefills={len(prefills)} "
          f"queue_at_close={queue_depth(records, close_t)} "
          f"drain_s={end_t - close_t:.3f} "
          f"host_gap_s={heartbeat.longest_gap(open_t, close_t):.5f} "
          f"compiles_in_window={compiles}")
    live = run.kv_live_tokens()
    if live is not None:
        per_token = served.kv_bytes_per_token
        pool = served.num_slots * int(traffic["max_seq_len"])
        print(f"kv: bytes_per_token={per_token} "
              f"pool_gb={pool * per_token / 1e9:.3f} "
              f"live_mean_gb={live[0] * per_token / 1e9:.3f} "
              f"live_peak_gb={live[1] * per_token / 1e9:.3f} "
              f"live_mean_share={100 * live[0] / pool:.2f}% "
              f"live_peak_share={100 * live[1] / pool:.2f}%")
    with open(harness.free_name(out_dir, tag, "requests.json"), "w") as f:
        json.dump({"workload": cell["name"], "seed": args.seed,
                   "open_t": open_t, "close_t": close_t, "setup_s": setup_s,
                   "requests": [{"index": r.index, "due": r.due,
                                 "submitted": r.submitted,
                                 "admitted": r.admitted,
                                 "prompt_len": r.prompt_len,
                                 "stamps": r.stamps,
                                 "finish": r.request.finish_reason}
                                for r in records],
                   "steps": run.steps}, f)

    # The comparison runs last, with the cache and the program's weights
    # gone from the chip: it neither sets the peak nor shares the memory.
    finished = [r for r in (counted if drain_s else records)
                if r.done and r.request.finish_reason != "rejected"
                and (drain_s or r.stamps[-1] < close_t)]
    served.release()
    t = clock()
    checks = served.compare(
        [(np.asarray(r.request.prompt), np.asarray(r.request.tokens))
         for r in finished], args.seed)
    print(f"reference: seconds={clock() - t:.1f} "
          f"checks={json.dumps(checks)}")
    compared = {c["name"]: [c["error"], c["tolerance"]] for c in checks}
    compared["compiles_in_window"] = [compiles, 0]
    compared["rejected"] = [len(rejected), 0]
    if drain_s:
        compared["unfinished_after_drain"] = [len(unfinished), 0]
    correct = (all(c["ok"] for c in checks) and compiles == 0
               and not rejected and not unfinished)
    return harness.Outcome(
        run=run, correct=correct, attempted=len(counted),
        failed=len({r.index for r in rejected + unfinished}),
        compared=compared, device=device, breakdown=breakdown)


def open_cell(manifest: str, workload: str, tool: str):
    """What the tools beside the benchmark (``sweep.py``, ``control.py``)
    start from, as ``run.main`` starts a run: the cell's files, a chip or no
    go, the compile cache.  Returns (cell, config, traffic, family)."""
    from horovod_tpu.utils import chip

    from benchmarks import run

    _, cell, config, traffic = run.load_cell(manifest, workload)
    chip.enable_compile_cache()
    chip.require_tpu(tool)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return cell, config, traffic, run.load_module("families",
                                                  config["family"])


def queue_depth(records: list[Record], t: float) -> int:
    """Requests due by ``t`` that no prefill had begun for."""
    return sum(r.due <= t and (r.admitted is None or r.admitted > t)
               for r in records)
