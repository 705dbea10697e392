"""Control-plane scaling microbench (VERDICT r3 item 7).

The eager engine's coordinator is a rank-0 TCP star: every tick gathers
one frame per worker and broadcasts responses sequentially
(core/src/controller.cc Gather/Bcast loops).  The reference used
MPI_Gather/Bcast, whose implementations tree these (log P); the question
is where the sequential star's ceiling is.  This harness measures, at a
given ``-np``:

* **rendezvous_s** — wall time of ``hvd.init()`` (socket accept quorum);
* **per_op_ms** — latency of a lone tiny allreduce (one negotiation
  round trip + the device dispatch floor);
* **names_per_s** — throughput when SATURATED with many outstanding
  tiny tensors (100 async enqueues per round): the negotiation batching
  amortizes ticks, so this isolates the coordinator's frame-handling
  rate from the cycle time.

Run under the launcher at increasing widths and compare:

    python -m horovod_tpu.run -np 4 -- \
        python examples/control_plane_benchmark.py

``--star P1,P2,...`` instead runs the ISOLATED star harness
(core/src/star_bench.cc — the real TcpControlPlane::Gather/Broadcast on
loopback threads, no JAX): one JSON line per width with the tick cost.
This is the measurement behind the round-5 poll()-interleaved Gather and
the 512-worker table in docs/benchmarks.md (the reference's demonstrated
scale, reference README.md:45-51).

    python examples/control_plane_benchmark.py --star 63,128,256,512

Numbers recorded in docs/benchmarks.md "Control-plane scaling: the rank-0
star".
"""

from __future__ import annotations

import argparse
import json
import time

import numpy as np


def run_star(widths: str, ticks: int, names: int) -> None:
    """Build (if needed) and run the C++ star benchmark per width."""
    import os
    import subprocess

    core = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "horovod_tpu", "core")
    exe = os.path.join(core, "star_bench")
    build = subprocess.run(["make", "-C", core, "star_bench"],
                           capture_output=True, text=True)
    if build.returncode != 0:
        raise RuntimeError(f"star_bench build failed:\n{build.stderr}")
    for p in widths.split(","):
        out = subprocess.run([exe, p.strip(), str(ticks), str(names)],
                             capture_output=True, text=True, check=True)
        print(out.stdout.strip(), flush=True)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--rounds", type=int, default=30)
    ap.add_argument("--burst", type=int, default=100,
                    help="outstanding async tensors per saturated round")
    ap.add_argument("--warmup", type=int, default=5)
    ap.add_argument("--star", default=None,
                    help="comma-separated widths for the isolated star "
                    "harness (no JAX; e.g. 63,128,256,512)")
    ap.add_argument("--star-ticks", type=int, default=200)
    ap.add_argument("--star-names", type=int, default=1,
                    help="negotiation names per worker frame")
    args = ap.parse_args()

    if args.star:
        run_star(args.star, args.star_ticks, args.star_names)
        return

    import horovod_tpu as hvd  # noqa: F811 — heavy import, star path skips it

    t0 = time.perf_counter()
    hvd.init()
    rendezvous_s = time.perf_counter() - t0

    x = np.ones(4, np.float32)

    # Warmup (engine start, first negotiation).
    for i in range(args.warmup):
        hvd.allreduce(x, name=f"warm.{i}")

    # Lone-op latency: one tensor in flight — a full negotiate+dispatch
    # round trip per call.
    t0 = time.perf_counter()
    for i in range(args.rounds):
        hvd.allreduce(x, name=f"lone.{i}")
    per_op_ms = (time.perf_counter() - t0) / args.rounds * 1e3

    # Saturated: burst of async enqueues, then synchronize all — the
    # coordinator sees many names per tick and batches them.
    t0 = time.perf_counter()
    for r in range(args.rounds):
        handles = [hvd.allreduce_async(x, name=f"burst.{r}.{i}")
                   for i in range(args.burst)]
        for h in handles:
            hvd.synchronize(h)
    dt = time.perf_counter() - t0
    names_per_s = args.rounds * args.burst / dt

    if hvd.rank() == 0:
        print(json.dumps({
            "np": hvd.size(),
            "rendezvous_s": round(rendezvous_s, 3),
            "per_op_ms": round(per_op_ms, 3),
            "names_per_s": round(names_per_s, 1),
        }), flush=True)
    hvd.shutdown()


if __name__ == "__main__":
    main()
