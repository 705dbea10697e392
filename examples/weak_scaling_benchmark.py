"""Weak-scaling harness for the eager (engine) data plane.

Ingredient (b) of the scaling-efficiency story: run
the same per-rank work at -np 1/2/4/8 under the launcher and watch per-rank
throughput — with a bandwidth-optimal allreduce the communication term per
rank is ~2n bytes REGARDLESS of rank count (core/device_reduce.py), so
per-rank rate should stay flat, which is exactly what >=90% weak scaling
means.  CPU processes stand in for hosts: the TREND (flat vs collapsing
with P) is what this harness certifies; absolute rates are CPU numbers.

Each step: fixed local compute (matmul loop) + one fused engine allreduce
of a configurable gradient-sized buffer, i.e. the DistributedOptimizer
cadence stripped to its two terms.

Run:  python -m horovod_tpu.run -np 4 -- \
          python examples/weak_scaling_benchmark.py --grad-mb 16
"""

from __future__ import annotations

import argparse
import json
import time

import numpy as np

import horovod_tpu as hvd


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--grad-mb", type=float, default=16.0,
                    help="allreduced bytes per step (ResNet-50 bf16 wire "
                         "~51 MB; default small for CI)")
    ap.add_argument("--compute-dim", type=int, default=384,
                    help="square matmul dim for the fixed local compute")
    ap.add_argument("--compute-reps", type=int, default=8)
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--warmup", type=int, default=2)
    args = ap.parse_args()

    hvd.init()
    elems = int(args.grad_mb * 1e6 / 4)
    rng = np.random.RandomState(hvd.rank())
    grad = rng.rand(elems).astype(np.float32)
    a = rng.rand(args.compute_dim, args.compute_dim).astype(np.float32)

    def step(i):
        acc = a
        for _ in range(args.compute_reps):     # fixed local "backward"
            acc = acc @ a
        h = hvd.allreduce_async(grad, average=True, name=f"ws.{i}")
        out = hvd.synchronize(h)
        return float(acc[0, 0]) + float(out[0])

    for i in range(args.warmup):
        step(-1 - i)
    hvd.barrier(name="ws.start")
    t0 = time.perf_counter()
    for i in range(args.steps):
        step(i)
    dt = time.perf_counter() - t0
    hvd.barrier(name="ws.done")

    rate = args.steps / dt
    print(json.dumps({
        "rank": hvd.rank(), "workers": hvd.size(),
        "steps_per_s_per_rank": round(rate, 3),
        "grad_mb": args.grad_mb,
        "wire_model_mb_per_rank_per_step": round(
            2 * (hvd.size() - 1) / max(hvd.size(), 1) * args.grad_mb, 2),
    }), flush=True)


if __name__ == "__main__":
    main()
