"""Collective microbench — psum / all_gather achieved bytes-per-second vs
message size over the global mesh, plus the dispatch floor.

This is ingredient (a) of the scaling-efficiency story (BASELINE.md: >=90%
ResNet-50 scaling on v5e-64, matching reference README.md:45-51): measure
what the collectives actually sustain, then project step-time dilution from
gradient bytes.

On one real chip the data axis has width 1, so psum lowers to a no-op:
what the harness records there is the DISPATCH floor (per-call latency of
a jitted collective through the runtime), the term that bounds how finely
fusion may slice gradient buckets.  On a multi-chip mesh (or the 8-device
CPU simulation) the same harness times real AllReduce/AllGather HLOs;
bytes/s is reported under the ring model (wire bytes per chip =
2*(n-1)/n * size for psum, (n-1)/n * size for all_gather).

Run:  python examples/collective_microbench.py [--sizes-mb 1,4,16,64]
"""

from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

import horovod_tpu as hvd
from horovod_tpu import mesh as hmesh


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--sizes-mb", default="1,4,16,64,256")
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--warmup", type=int, default=3)
    args = ap.parse_args()

    hvd.init()
    n = 1
    for a in hmesh.data_axes():
        n *= hmesh.global_mesh().shape[a]

    def timed(fn, x):
        for _ in range(args.warmup):
            out = fn(x)
        jax.block_until_ready(out)
        t0 = time.perf_counter()
        for _ in range(args.iters):
            out = fn(x)
        jax.block_until_ready(out)
        return (time.perf_counter() - t0) / args.iters

    results = []
    for mb in [float(s) for s in args.sizes_mb.split(",")]:
        elems = int(mb * 1e6 / 4)
        x = jnp.zeros((elems,), jnp.float32) + hvd.rank()

        psum = jax.jit(hvd.shard(lambda v: lax.psum(v, hmesh.data_axes()),
                                 in_specs=hvd.batch_spec(1),
                                 out_specs=hvd.batch_spec(1)))
        ag = jax.jit(hvd.shard(
            lambda v: lax.all_gather(
                v, hmesh.data_axes() if len(hmesh.data_axes()) > 1
                else hmesh.data_axes()[0], tiled=True),
            in_specs=hvd.batch_spec(1), out_specs=hvd.batch_spec(1)))

        t_psum = timed(psum, x)
        t_ag = timed(ag, x)
        size_b = elems * 4
        results.append({
            "size_mb": mb, "workers": n,
            "psum_ms": round(t_psum * 1e3, 3),
            "all_gather_ms": round(t_ag * 1e3, 3),
            # ring-model wire bytes per chip / time
            "psum_ring_GBps": round(
                2 * (n - 1) / max(n, 1) * size_b / t_psum / 1e9, 2),
            "all_gather_ring_GBps": round(
                (n - 1) / max(n, 1) * size_b / t_ag / 1e9, 2),
        })
        if hvd.rank() == 0:
            print(json.dumps(results[-1]), flush=True)

    # Dispatch floor: smallest useful collective, timed alone.
    tiny = jnp.zeros((128,), jnp.float32)
    psum1 = jax.jit(hvd.shard(lambda v: lax.psum(v, hmesh.data_axes()),
                              in_specs=hvd.batch_spec(1),
                              out_specs=hvd.batch_spec(1)))
    t = timed(psum1, tiny)
    if hvd.rank() == 0:
        print(json.dumps({"dispatch_floor_ms": round(t * 1e3, 3),
                          "workers": n}), flush=True)


if __name__ == "__main__":
    main()
