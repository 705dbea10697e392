"""Find what a serving cell's replica sustains, once, when the cell is
defined.  No run of the benchmark calls this.

    python3 benchmarks/sweep.py --workload <cell> --rates 8 --seconds 120 \\
        --mark 20 --drain 0                  # capacity, from an overload
    python3 benchmarks/sweep.py --workload <cell> --rates 2.0,2.4,... \\
        [--seconds 90] [--mark 30]           # how the tails grow below it

One process, one engine, the cell's own requests (``arrivals.schedule`` at
each rate in turn: the same prompts and outputs, closer together).  Each
rate is served for ``--seconds`` from an empty engine and then drained for
at most ``--drain`` seconds (0: not at all, and then one rate only).

CAPACITY is read from an overload: offered several times what the replica
can serve, every slot is full and the queue never empties, and the requests
finished a second between ``--mark`` and the end are what it sustains
(``finished_per_s``), over some hundreds of requests and not one window's
bursts.  The traffic file holds 0.8 times it under ``rate``.
The line also gives what the capacity is made of: the median host time of a
prefill call by bucket and of a decode call, from which the long-run mean of
the mix (``arrivals.long_run``) gives the same number another way.  One
JSON line a rate, for the traffic file's ``knee`` group.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=90.0)
    ap.add_argument("--mark", type=float, default=30.0)
    ap.add_argument("--drain", type=float, default=600.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--manifest",
                    default=os.path.join(os.path.dirname(HERE),
                                         "BENCHMARK.json"))
    args = ap.parse_args()

    import statistics

    import numpy as np

    from benchmarks import arrivals, serving

    cell, config, traffic, family = serving.open_cell(
        args.manifest, args.workload, "benchmarks/sweep.py")
    rates = [float(r) for r in args.rates.split(",")]
    if not args.drain and len(rates) > 1:
        raise SystemExit("sweep.py: --drain 0 leaves the engine full: "
                         "one rate a process")
    served = family.serve(config, traffic, int(cell["chips"]), args.seed)
    served.warm()
    print(f"unloaded: {json.dumps(served.notes)}", flush=True)
    print(f"long_run: {json.dumps(arrivals.long_run(traffic))}", flush=True)
    timed = served.engine.backend
    for rate in rates:
        sched = arrivals.schedule(traffic, args.seconds, rate)
        ids = arrivals.prompts(traffic, sched, args.seed, served.vocab_size)
        del timed.log[:]
        t0, records, _, end = serving.drive(
            served.engine, sched, ids, first=0, offset_s=0.0,
            close_s=args.seconds, drain_s=args.drain)
        serving.stamp_admissions(records, timed.log)
        lo, hi = t0 + args.mark, t0 + args.seconds

        def depth(at_s: float) -> int:
            return serving.queue_depth(records, t0 + at_s)

        def mean_depth(a: float, b: float) -> float:
            return float(np.mean([depth(x) for x in np.arange(a, b, 0.25)]))

        # judged as a window of the benchmark is: requests due after the mark
        ttft = [1e3 * (r.stamps[0] - r.due) for r in records
                if r.stamps and r.due >= lo]
        tokens = sum(lo <= s < hi for r in records for s in r.stamps)
        done = [r for r in records if r.done and lo <= r.stamps[-1] < hi]
        prefill_ms: dict = {}
        for e in timed.log:
            if e[0] == "prefill":
                prefill_ms.setdefault(e[3], []).append(1e3 * (e[2] - e[1]))
        print("sweep: " + json.dumps({
            "rate_per_s": rate, "sent": len(records),
            "counted_from_mark": len(ttft),
            "queue_at_mark": depth(args.mark),
            "queue_at_end": depth(args.seconds),
            "mean_queue_mark_to_end": mean_depth(args.mark, args.seconds),
            "finished_per_s": len(done) / (args.seconds - args.mark),
            "tokens_per_s": tokens / (args.seconds - args.mark),
            "finished_mean_prompt_tokens": float(np.mean(
                [r.prompt_len for r in done])) if done else None,
            "finished_mean_output_tokens": float(np.mean(
                [len(r.stamps) for r in done])) if done else None,
            "ttft_ms_p50": serving.percentile(ttft, 50),
            "ttft_ms_p90": serving.percentile(ttft, 90),
            "ttft_ms_p95": serving.percentile(ttft, 95),
            "ttft_ms_mean": float(np.mean(ttft)) if ttft else None,
            "prefill_ms_by_bucket": {b: statistics.median(v) for b, v
                                     in sorted(prefill_ms.items())},
            "decode_ms": statistics.median(
                1e3 * (e[2] - e[1]) for e in timed.log if e[0] == "decode"),
            "drain_s": end - t0 - args.seconds}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
