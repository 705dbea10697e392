"""Run one cell of BENCHMARK.json once and print its result line.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell names a configuration and a traffic mix.  Everything that belongs to
one of them is a file of its own that this harness finds by name and never
lists (PERF.md, "How the harness finds a cell's files"):

    configs/<config>.json     sizes as run, with "family"      (manifest "file")
    traffic/<traffic>.json    batch, optimizer, data stream ("extends": another)
    families/<family>.py      build(config, traffic, chips, seed) -> Built, or
                              serve(config, traffic, chips, seed) -> Served
    reference/<family>.py     the plain reference the family compares with
    metrics/<stem>.py         read(run) -> value or None; <stem> is the
                              metric's name up to its first "."

Which loop runs follows from what the family defines, not from a flag: a
family with ``build`` is trained by :func:`train`, one with ``serve`` is sent
requests by ``benchmarks/serving.py``; what is printed of either is
:func:`report`'s.

With ``--trace 0`` the metrics are the cell's end-to-end metrics; with
``--trace 1`` its per-layer metrics, a few more steps (or seconds of the same
traffic) being run under the profiler after the window.  The last line of
stdout is the result.
"""

from __future__ import annotations

import time

PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import importlib.util  # noqa: E402
import itertools  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)

WARMUP_CALLS = 3
TRACED_CALLS = 6      # the trace's start and stop cut the outer two


@dataclasses.dataclass
class Run:
    """What a metric reader is given."""
    cell: dict
    config: dict
    traffic: dict
    built: object
    chips: int
    peaks: dict | None          # None off the TPU (a rehearsal)
    setup_s: float
    stamps: list                # stamps[0] opens the window
    losses: list                # one per call of the window
    input_wait_s: list          # host seconds waiting for each call's batch
    dispatch_s: list            # host seconds enqueueing each call
    fetch_s: list               # host seconds blocked on each call's loss
    compiles_in_window: int
    memory: dict | None         # the fullest chip's memory_stats() counters
    trace: object = None        # benchmarks.trace.Summary, traced runs only
    compiled: object = None     # the timed step as compiled: its text holds
                                # the program's names (benchmarks/scopes.py)
    trace_dir: str | None = None    # where a traced run's profile lies

    @property
    def calls(self) -> int:
        return len(self.stamps) - 1

    @property
    def steps(self) -> int:
        return self.calls * self.built.steps_per_call

    @property
    def traced_steps(self) -> int:
        return self.trace.calls * self.built.steps_per_call

    @property
    def rate(self) -> float:
        """All the window's work over all its time."""
        from benchmarks import rates
        return rates.whole_window_rate(self.stamps,
                                       self.built.units_per_call)

    @property
    def peak_bytes(self) -> int:
        """Buffers at their peak plus the scratch the loaded programs
        reserve.  On this runtime a program's temporaries are a reservation
        and not part of ``peak_bytes_in_use`` (my chip run, PR 23: ResNet
        0.60 GB in use beside 4.52 GB reserved, the step's
        ``temp_size_in_bytes`` being 4.56); the ``memory:`` line sets the
        step's ``memory_analysis()`` beside the sum."""
        m = self.memory
        return m["peak_bytes_in_use"] + m["peak_bytes_reserved"] if m else 0


@dataclasses.dataclass
class Harness:
    """What ``main`` hands the loop it chose."""
    args: argparse.Namespace
    manifest: dict
    cell: dict
    config: dict
    traffic: dict
    chips: int
    family: object              # the module families/<family>.py
    dev: object                 # jax.devices()[0]
    devices: list
    peaks: dict | None
    compile_events: list        # host-clock stamps of backend compilations


@dataclasses.dataclass
class Outcome:
    """What a loop hands back for :func:`report` to print."""
    run: object                 # what the metric readers are given
    correct: bool
    attempted: int
    failed: int
    compared: dict              # name -> [number, limit]
    device: dict
    breakdown: dict | None
    finish: object = None       # called once the readers have read


class Heartbeat(threading.Thread):
    """Stamps the host clock every 50 ms and does nothing else.  A gap
    between its stamps is time in which this process did not run at all:
    it tells a stall of the machine from a step the device was late with."""

    def __init__(self):
        super().__init__(daemon=True)
        self.beats: list[float] = []
        self._stop_it = threading.Event()

    def run(self):
        while not self._stop_it.wait(0.05):
            self.beats.append(time.perf_counter())

    def stop(self):
        self._stop_it.set()
        self.join()

    def longest_gap(self, start: float, end: float) -> float:
        inside = [start] + [b for b in self.beats if start < b < end] + [end]
        return max(b - a for a, b in zip(inside, inside[1:]))


def load_module(kind: str, name: str):
    path = os.path.join(HERE, kind, f"{name}.py")
    if not os.path.exists(path):
        raise SystemExit(f"run.py: no {kind}/{name}.py under benchmarks/")
    spec = importlib.util.spec_from_file_location(
        f"benchmarks.{kind}.{name}", path)
    module = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = module
    spec.loader.exec_module(module)
    return module


def load_cell(manifest_path: str, workload: str):
    with open(manifest_path) as f:
        manifest = json.load(f)
    base = os.path.dirname(os.path.abspath(manifest_path))
    cells = {c["name"]: c for c in manifest["workloads"]}
    if workload not in cells:
        raise SystemExit(f"run.py: no workload {workload!r} in "
                         f"{manifest_path}; it has {sorted(cells)}")
    cell = cells[workload]
    entry = next(c for c in manifest["configs"] if c["name"] == cell["config"])
    with open(os.path.join(base, entry["file"])) as f:
        config = json.load(f)
    def traffic_file(name: str) -> dict:
        with open(os.path.join(base, manifest["paths"][0], "traffic",
                               f"{name}.json")) as f:
            mix = json.load(f)
        # "extends" names the mix this one takes every other key from, so
        # that two cells meant to see one traffic cannot drift apart
        return {**traffic_file(mix.pop("extends")), **mix} \
            if "extends" in mix else mix

    return manifest, cell, config, traffic_file(cell["traffic"])


def metrics_of(manifest: dict, group: str, workload: str) -> list[dict]:
    return [m for m in manifest[group]
            if "workloads" not in m or workload in m["workloads"]]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    ap.add_argument("--manifest", default=os.path.join(ROOT, "BENCHMARK.json"))
    ap.add_argument("--out", default=os.path.join(HERE, "out"),
                    help="per-step files and traces go here")
    ap.add_argument("--rehearse-on-cpu", action="store_true",
                    help="walk the code on whatever backend is there; the "
                         "line it prints is marked and is no result")
    args = ap.parse_args()
    manifest, cell, config, traffic = load_cell(args.manifest, args.workload)
    chips = int(cell["chips"])

    import jax

    from horovod_tpu.utils import chip

    from benchmarks import peaks as peak_table

    cache_dir = chip.enable_compile_cache()
    # keep the small programs (init, comparison) too: every run is a new
    # process and pays for whatever the cache does not hold
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    devices = jax.devices()
    dev = devices[0]
    if not args.rehearse_on_cpu:
        try:
            chip.require_tpu("benchmarks/run.py")
        except RuntimeError as e:
            print(f"run.py: {e}", file=sys.stderr)
            return 2
    if len(devices) != chips:
        print(f"run.py: cell {cell['name']!r} is defined on {chips} chip(s) "
              f"and JAX reports {len(devices)} device(s)", file=sys.stderr)
        return 2
    peaks = None if dev.platform != "tpu" else peak_table.peaks(dev.device_kind)
    print(f"device: platform={dev.platform} kind={dev.device_kind!r} "
          f"count={len(devices)} jax={jax.__version__} cache={cache_dir}")

    compile_events: list[float] = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda name, _secs, **_kw: compile_events.append(time.perf_counter())
        if name.endswith("backend_compile_duration") else None)

    family = load_module("families", config["family"])
    h = Harness(args=args, manifest=manifest, cell=cell, config=config,
                traffic=traffic, chips=chips, family=family, dev=dev,
                devices=devices, peaks=peaks, compile_events=compile_events)
    if hasattr(family, "serve"):
        from benchmarks import serving

        return report(h, serving.measure(h))
    return report(h, train(h))


def train(h: Harness) -> Outcome:
    """One run of a training cell: the family's step, one call in flight."""
    import jax
    import numpy as np

    import horovod_tpu as hvd

    from benchmarks import rates, trace

    args, cell, config, traffic = h.args, h.cell, h.config, h.traffic
    chips, peaks, dev, devices = h.chips, h.peaks, h.dev, h.devices
    compile_events = h.compile_events
    hvd.init()
    t = time.perf_counter()
    built = h.family.build(config, traffic, chips, args.seed)
    model_state = built.init_model()
    jax.block_until_ready(model_state)
    n_params = sum(int(np.prod(x.shape))
                   for x in jax.tree.leaves(model_state))
    print(f"build: family={config['family']} parameters={n_params / 1e6:.1f}M "
          f"pool_batches={len(built.pool)} seconds={time.perf_counter() - t:.1f}"
          f" notes={json.dumps(built.notes)}")

    state = built.init_train(model_state)
    host_batches = itertools.cycle(built.pool)
    loader = iter(hvd.data.BackgroundLoader(host_batches, depth=2))
    batches = hvd.data.prefetch_to_device(loader, size=2,
                                          sharding=built.batch_shardings)
    first = next(batches)
    # The step is the first thing traced, as a user's is, with the whole
    # train state on the device: the planner probes the memory left at the
    # process's first trace and keeps the answer.
    t0 = time.perf_counter()
    lowered = built.step.lower(state, *first)
    t1 = time.perf_counter()
    step = lowered.compile()
    print(f"compile: trace_and_lower_s={t1 - t0:.1f} "
          f"backend_or_cache_s={time.perf_counter() - t1:.1f}")
    print(f"plan: overlap_plan={json.dumps(hvd.overlap_plan())}")
    analysis = step.memory_analysis()
    del model_state

    annotate = jax.profiler.TraceAnnotation
    stamps: list[float] = []
    losses: list[float] = []
    waits: list[float] = []
    dispatches: list[float] = []
    fetches: list[float] = []

    def dispatch(batch=None):
        nonlocal state
        a = time.perf_counter()
        if batch is None:
            with annotate("input_wait"):
                batch = next(batches)
        b = time.perf_counter()
        with annotate("dispatch"):
            state, loss = step(state, *batch)
        return loss, b - a, time.perf_counter() - b

    def calls_until(done) -> None:
        """Keep one call in flight; stamp each loss as it arrives."""
        nonlocal pending
        while not done():
            following = dispatch()
            a = time.perf_counter()
            with annotate("loss_fetch"):
                value = float(pending[0])
            stamps.append(time.perf_counter())
            losses.append(value)
            waits.append(pending[1])
            dispatches.append(pending[2])
            fetches.append(stamps[-1] - a)
            pending = following

    pending = dispatch(first)
    calls_until(lambda: len(stamps) >= WARMUP_CALLS)
    del stamps[:-1], losses[:], waits[:], dispatches[:], fetches[:]
    window_open = stamps[0]
    setup_s = window_open - PROCESS_START
    heartbeat = Heartbeat()
    heartbeat.start()
    calls_until(lambda: stamps[-1] - window_open >= args.seconds)
    heartbeat.stop()
    compiles = sum(window_open <= c <= stamps[-1] for c in compile_events)
    memory = fullest_chip(jax)

    run = Run(cell=cell, config=config, traffic=traffic, built=built,
              chips=chips, peaks=peaks,
              setup_s=setup_s, stamps=list(stamps),
              losses=list(losses), input_wait_s=list(waits),
              dispatch_s=list(dispatches), fetch_s=list(fetches),
              compiles_in_window=compiles, memory=memory, compiled=step)
    print(f"memory: fullest_chip={json.dumps(memory)} "
          f"step_memory_analysis={json.dumps(program_bytes(analysis))}")

    out_dir = os.path.join(args.out, cell["name"])
    os.makedirs(out_dir, exist_ok=True)
    tag = f"seed{args.seed}.trace{args.trace}"
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": run.peak_bytes}
    breakdown = None
    if args.trace:
        run.trace_dir = os.path.join(out_dir, f"{tag}.profile")
        with trace.record(run.trace_dir):
            target = len(stamps) + TRACED_CALLS
            calls_until(lambda: len(stamps) >= target)
        run.trace = trace.reduce(trace.load(run.trace_dir))
        if run.trace is not None:
            device["busy_s"] = run.trace.busy_s
            device["window_s"] = run.trace.window_s
            breakdown = {"device_ops": run.trace.device_ops,
                         "idle_gaps": run.trace.idle_gaps}
    float(pending[0])                   # the call still in flight
    batches.close()
    loader.close()

    # The reference comparison runs last, on the seeded parameters made
    # anew, with the train state gone: it then neither shares the chip's
    # memory with the step nor sets the peak that peak_hbm reports.
    del state, pending
    t = time.perf_counter()
    checks = built.compare(built.init_model())
    print(f"reference: seconds={time.perf_counter() - t:.1f} "
          f"checks={json.dumps(checks)}")

    periods = rates.periods(run.stamps)
    seg = rates.segment_rates(run.stamps, built.units_per_call)
    unit = traffic["unit"]
    # The longest period, and what the host was doing in it: the batch and
    # the dispatch of the call that followed were made inside it, then the
    # loop blocked on this call's loss.  host_gap_s is the heartbeat's.
    k = max(range(len(periods)), key=periods.__getitem__)
    nxt = k + 1 if k + 1 < len(periods) else None   # made in the period
    host_gap_s = heartbeat.longest_gap(run.stamps[0], run.stamps[-1])
    print(f"window: calls={run.calls} steps={run.steps} "
          f"seconds={run.stamps[-1] - run.stamps[0]:.3f} "
          f"whole_window_{unit}_per_s={run.rate:.2f} "
          f"segment_{unit}_per_s={[round(r, 2) for r in seg]} "
          f"stall_share_pct={100 * rates.stall_share(run.stamps):.4f} "
          f"median_period_s={statistics.median(periods):.5f} "
          f"longest_period_s={max(periods):.5f} at_call={k} "
          f"its_input_wait_s="
          f"{run.input_wait_s[nxt] if nxt else math.nan:.5f} "
          f"its_dispatch_s={run.dispatch_s[nxt] if nxt else math.nan:.5f} "
          f"its_loss_fetch_s={run.fetch_s[k]:.5f} "
          f"host_gap_s={host_gap_s:.5f} "
          f"compiles_in_window={compiles}")
    with open(free_name(out_dir, tag, "steps.json"), "w") as f:
        json.dump({"workload": cell["name"], "seed": args.seed,
                   "unit": unit, "units_per_call": built.units_per_call,
                   "steps_per_call": built.steps_per_call,
                   "periods_s": periods, "losses": run.losses,
                   "input_wait_s": run.input_wait_s,
                   "dispatch_s": run.dispatch_s, "fetch_s": run.fetch_s,
                   "host_gap_s": host_gap_s, "segment_rates": seg,
                   "whole_window_rate": run.rate, "setup_s": setup_s}, f)

    finite = [math.isfinite(v) for v in run.losses]
    loss_means = rates.segment_means(run.losses)
    learnt = (not traffic["expect_loss_to_fall"]
              or loss_means[-1] < loss_means[0])
    print(f"loss: first_segment={loss_means[0]:.4f} "
          f"last_segment={loss_means[-1]:.4f} fell={learnt} "
          f"finite={all(finite)}")
    correct = (all(c["ok"] for c in checks) and compiles == 0
               and all(finite) and learnt)

    # every number ``correct`` was decided from, beside its limit
    compared = {c["name"]: [c["error"], c["tolerance"]] for c in checks}
    compared["compiles_in_window"] = [compiles, 0]
    compared["non_finite_losses"] = [finite.count(False), 0]
    if traffic["expect_loss_to_fall"]:
        compared["last_segment_loss_less_first"] = [
            loss_means[-1] - loss_means[0], 0.0]
    return Outcome(run=run, correct=correct, attempted=run.steps,
                   failed=finite.count(False) * built.steps_per_call,
                   compared=compared, device=device, breakdown=breakdown,
                   finish=hvd.shutdown)


def report(h: Harness, o: Outcome) -> int:
    """Read the cell's metrics off the run and print the result line."""
    args, cell, dev = h.args, h.cell, h.dev
    group = "per_layer" if args.trace else "end_to_end"
    metrics = {}
    for entry in metrics_of(h.manifest, group, cell["name"]):
        value = load_module("metrics", entry["name"].split(".")[0]).read(o.run)
        if value is not None:
            metrics[entry["name"]] = {"value": value, "unit": entry["unit"]}
    result = {"correct": bool(o.correct), "attempted": o.attempted,
              "failed": o.failed, "metrics": metrics, "device": o.device}
    if o.breakdown is not None:
        result["breakdown"] = o.breakdown
    # every number ``correct`` was decided from, beside its limit: the last
    # lines of stderr and the last key of the line (what is kept of a run
    # that was not correct)
    result["compared"] = o.compared
    if o.finish is not None:
        o.finish()
    for name, (value, limit) in o.compared.items():
        print(f"compared: {name}={value!r} limit={limit!r}", file=sys.stderr)
    line = json.dumps(result)
    if dev.platform != "tpu":
        line = "REHEARSAL on " + dev.platform + ", no result: " + line
    print(line, flush=True)
    return 0


MEMORY_COUNTERS = ("peak_bytes_in_use", "peak_bytes_reserved",
                   "bytes_in_use", "bytes_reserved")


def fullest_chip(jax) -> dict | None:
    """The memory counters of the device whose two peaks sum highest; the
    current readings beside the peaks say whether the peaks are the
    window's (they are where the two agree).  None where the backend keeps
    no counters (a rehearsal on the CPU)."""
    stats = [{k: int(s.get(k, 0)) for k in MEMORY_COUNTERS}
             for s in (d.memory_stats() for d in jax.local_devices()) if s]
    return max(stats, key=lambda s: s["peak_bytes_in_use"]
               + s["peak_bytes_reserved"], default=None)


def program_bytes(analysis) -> dict | None:
    """A compiled program's own account of one chip's memory."""
    if analysis is None:
        return None
    out = {k: int(getattr(analysis, f"{k}_size_in_bytes"))
           for k in ("argument", "output", "alias", "temp")}
    out["total"] = (out["argument"] + out["output"] - out["alias"]
                    + out["temp"])
    return out


def free_name(directory: str, tag: str, suffix: str) -> str:
    """``<tag>.<suffix>``, or ``<tag>.<k>.<suffix>`` with the first free k:
    a second run of one seed never overwrites the first's record."""
    path, k = os.path.join(directory, f"{tag}.{suffix}"), 1
    while os.path.exists(path):
        k += 1
        path = os.path.join(directory, f"{tag}.{k}.{suffix}")
    return path


if __name__ == "__main__":
    # a loop that says ``from benchmarks import run`` gets this module, not
    # a second copy with a later PROCESS_START
    sys.modules["benchmarks.run"] = sys.modules["__main__"]
    sys.exit(main())
