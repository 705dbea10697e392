"""Read the comparison's two ends on the chip, at a cell's own size: what
sound runs of the program give (the lower reading) and what the control
gives (the upper one).  No run of the benchmark calls this.

    python3 benchmarks/control.py --workload <cell> --seeds 1,2,3 \\
        [--seconds 12]

For each seed, in one process: the family builds and warms the cell's
engine on that seed's weights, the cell's own schedule is served for the
lead-in and ``--seconds`` at the cell's rate (and drained where the cell
drains), and the comparison of a run is made on a run's sample of the
finished requests.  Then the control goes through that same comparison
(``families/<family>.compare_served(..., control=float8_e4m3fn)``): the
reference with float8_e4m3fn operands, the step below the configuration's
bfloat16, stands in the program's place; at each position of the same
prompts and tokens, the token it puts first is judged as a served token is,
and ``control_correct`` has to come out false.  One JSON line a seed.
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
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=12.0)
    ap.add_argument("--manifest",
                    default=os.path.join(os.path.dirname(HERE),
                                         "BENCHMARK.json"))
    args = ap.parse_args()

    import jax.numpy as jnp
    import numpy as np

    from benchmarks import arrivals, serving

    cell, config, traffic, family = serving.open_cell(
        args.manifest, args.workload, "benchmarks/control.py")
    lead_in, drain_s = float(traffic["lead_in_s"]), float(traffic["drain_s"])
    sched = arrivals.schedule(traffic, lead_in + args.seconds)
    for seed in (int(s) for s in args.seeds.split(",")):
        served = family.serve(config, traffic, int(cell["chips"]), seed)
        served.warm()
        ids = arrivals.prompts(traffic, sched, seed, served.vocab_size)
        _, records, _, _ = serving.drive(
            served.engine, sched, ids, first=0, offset_s=0.0,
            close_s=lead_in + args.seconds, drain_s=drain_s)
        finished = [(np.asarray(r.request.prompt),
                     np.asarray(r.request.tokens)) for r in records
                    if r.done and r.request.finish_reason != "rejected"]
        served.release()
        del served
        program, = family.compare_served(config, traffic, finished, seed)
        control, = family.compare_served(config, traffic, finished, seed,
                                         control=jnp.float8_e4m3fn)
        print("control: " + json.dumps({
            "seed": seed, "finished": len(finished),
            "requests": program["requests"], "tokens": program["tokens"],
            "program_gap": program["error"], "program_correct": program["ok"],
            "control_gap_fp8": control["error"],
            "control_correct": control["ok"],
            "limit": program["tolerance"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
