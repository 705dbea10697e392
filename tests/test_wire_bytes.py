"""Wire-byte accounting for the eager data plane (VERDICT r2 item 1).

The reference's eager allreduce inherits MPI's ring economics: ~2n wire
bytes per rank regardless of job size (reference operations.cc:1242-1268).
Round 2's allgather+host-sum moved (P-1)*n per rank instead.  This
microbench measures REAL loopback traffic (/proc/net/dev) for a 4-process
job in both modes and asserts the device reduce-scatter route
(core/device_reduce.py) cuts wire bytes by ~P/2 = 2x, for the dense f32
wire and the int8 wire alike.

Accounting model (total rx across all ranks, K iterations of n bytes):
  gather:  P*(P-1)*n*K      device:  2*(P-1)*n*K      ratio: P/2
"""

import json
import os
import subprocess
import sys

import pytest

from _timing import scaled
from test_multiprocess import PRELUDE, _run_workers_once

NPROCS = 4
ELEMS = 1 << 21          # 2 Mi f32 elements = 8 MiB dense, 2 MiB int8 wire
ITERS = 4

WIRE_WORKER = PRELUDE + """
import numpy as np
mode = os.environ["WB_MODE"]
N = int(os.environ["WB_ELEMS"])
K = int(os.environ["WB_ITERS"])
x = (np.random.RandomState(rank).rand(N).astype(np.float32) - 0.5)
if mode == "dense":
    for k in range(K):
        h = hvd.allreduce_async(x, average=False, name=f"wb.{k}")
        hvd.synchronize(h)
elif mode == "int8":
    for k in range(K):
        h = hvd.allreduce_async(x, average=False, name=f"wbq.{k}",
                                compression=hvd.Compression.int8)
        hvd.synchronize(h)
elif mode == "idle":
    pass
else:
    raise AssertionError(mode)
# Rendezvous before exit: a rank that exits early tears down the control
# plane and aborts peers still inside their last synchronize.
hvd.barrier(name="wb.done")
print(f"RANK{rank} OK", flush=True)
"""


def _lo_rx_bytes() -> int:
    with open("/proc/net/dev") as f:
        for line in f:
            line = line.strip()
            if line.startswith("lo:"):
                return int(line.split(":")[1].split()[0])
    raise AssertionError("no loopback interface in /proc/net/dev")


def _own_loopback() -> str:
    """Move this process into a network namespace of its own, with its
    loopback up: /proc/net/dev then counts this process's children and
    nothing else.  Where the kernel will not allow it the process stays
    where it is, and reads the machine's counter as before.  Returns which
    counter it is, for the job's line: a ratio read off the shared one
    beside other workers' jobs names its cause."""
    import fcntl
    import socket
    import struct

    try:
        os.unshare(os.CLONE_NEWNET)
        with socket.socket(socket.AF_INET, socket.SOCK_DGRAM) as s:
            SIOCGIFFLAGS, SIOCSIFFLAGS, IFF_UP = 0x8913, 0x8914, 1
            flags = struct.unpack("16sH", fcntl.ioctl(
                s, SIOCGIFFLAGS, struct.pack("16sH", b"lo", 0)))[1]
            fcntl.ioctl(s, SIOCSIFFLAGS,
                        struct.pack("16sH", b"lo", flags | IFF_UP))
    except (AttributeError, OSError) as refused:
        return f"the machine's shared loopback ({refused!r})"
    return "a loopback of its own"


def _one_job(worker: str, env: dict) -> dict:
    counter = _own_loopback()
    before = _lo_rx_bytes()
    try:
        outs = _run_workers_once(worker, NPROCS, scaled(300), env)
    except subprocess.TimeoutExpired:
        return {"bytes": None, "err": "job timeout"}
    ok = all(f"RANK{r} OK" in out for r, (out, _, _) in enumerate(outs))
    return {"bytes": _lo_rx_bytes() - before if ok else None,
            "counter": counter,
            "err": "\n".join(err[-2000:] for _, err, _ in outs)}


def _job_bytes(mode: str, algo: str | None = None,
               worker: str = WIRE_WORKER) -> int:
    """Loopback rx bytes for one 4-process job, counted where no other
    process's traffic is: the job runs under a child of this process that
    has left for a network namespace of its own (the interface's counter is
    the machine's, and five other workers' jobs speak over it meanwhile;
    where the kernel refuses a namespace the shared counter is read as
    before).  Retries infra noise with a FRESH counter read — a silent
    whole-job retry under one measurement would double-count traffic and
    corrupt the ratio assertions."""
    env = {"WB_MODE": mode, "WB_ELEMS": str(ELEMS), "WB_ITERS": str(ITERS)}
    if algo is not None:
        env["HVD_TPU_EAGER_REDUCE"] = algo
    last_err = ""
    for _attempt in range(2):
        res = subprocess.run(
            [sys.executable, "-c",
             "import json, sys, test_wire_bytes as t\n"
             "worker, env = json.load(sys.stdin)\n"
             "print('JOB=' + json.dumps(t._one_job(worker, env)))"],
            input=json.dumps([worker, env]), capture_output=True, text=True,
            cwd=os.path.dirname(os.path.abspath(__file__)))
        said = [ln for ln in res.stdout.splitlines() if ln.startswith("JOB=")]
        if not said:
            last_err = res.stderr[-2000:]
            continue
        job = json.loads(said[-1][4:])
        if job["bytes"] is not None:
            print(f"job {mode}/{algo}: {job['bytes']} bytes over "
                  f"{job['counter']}")
            return job["bytes"]
        last_err = job["err"]
    raise AssertionError(f"wire-byte job {mode}/{algo} failed twice:\n"
                         f"{last_err}")


@pytest.mark.skipif(not os.path.exists("/proc/net/dev"),
                    reason="needs /proc/net/dev")
def test_device_reduce_halves_wire_bytes():
    # Boot/rendezvous overhead measured once and subtracted from each job.
    overhead = _job_bytes("idle", "device")
    payload = ELEMS * 4 * ITERS
    results = {}
    for mode in ("dense", "int8"):
        for algo in ("gather", "device"):
            raw = _job_bytes(mode, algo)
            results[(mode, algo)] = max(raw - overhead, 1)
    n_dense, n_int8 = payload, payload // 4
    expect = {
        ("dense", "gather"): NPROCS * (NPROCS - 1) * n_dense,
        ("dense", "device"): 2 * (NPROCS - 1) * n_dense,
        ("int8", "gather"): NPROCS * (NPROCS - 1) * n_int8,
        ("int8", "device"): 2 * (NPROCS - 1) * n_int8,
    }
    for key, got in results.items():
        print(f"{key}: measured {got/1e6:.1f} MB, model {expect[key]/1e6:.1f}"
              f" MB ({got/expect[key]:.2f}x of model)")

    dense_ratio = results[("dense", "gather")] / results[("dense", "device")]
    int8_ratio = results[("int8", "gather")] / results[("int8", "device")]
    # Model says P/2 = 2.0; margin for gloo framing + control plane noise.
    assert dense_ratio >= 1.7, f"dense wire reduction only {dense_ratio:.2f}x"
    assert int8_ratio >= 1.7, f"int8 wire reduction only {int8_ratio:.2f}x"
    # int8 wire is ~4x leaner than the dense wire on the same route.
    comp_ratio = results[("dense", "device")] / results[("int8", "device")]
    assert comp_ratio >= 2.5, f"int8 compression only {comp_ratio:.2f}x"


OPT_WORKER = PRELUDE + """
import jax.numpy as jnp
import numpy as np
import optax
N = int(os.environ["WB_ELEMS"])
K = int(os.environ["WB_ITERS"])
# A full DistributedOptimizer training step on the eager path: many
# leaves of mixed sizes totalling N f32 elements, so the wire carries
# the production (bucketed) gradient payload, not one raw collective.
sizes = [N // 2, N // 4, N // 8, N - (N // 2 + N // 4 + N // 8)]
rng = np.random.RandomState(rank)
params = {f"p{i}": jnp.asarray(rng.rand(s).astype(np.float32))
          for i, s in enumerate(sizes)}
opt = hvd.DistributedOptimizer(optax.sgd(0.01))
state = opt.init(params)
for k in range(K):
    grads = {f"p{i}": jnp.asarray(rng.rand(s).astype(np.float32) - 0.5)
             for i, s in enumerate(sizes)}
    updates, state = opt.update(grads, state, params)
    params = optax.apply_updates(params, updates)
hvd.barrier(name="wbopt.done")
print(f"RANK{rank} OK", flush=True)
"""


@pytest.mark.skipif(not os.path.exists("/proc/net/dev"),
                    reason="needs /proc/net/dev")
def test_distributed_optimizer_step_matches_ring_model():
    """The scaling projection's wire model, asserted for the FULL
    DistributedOptimizer step (not just raw collectives): K eager steps
    over V bytes of gradients at P ranks must move ≈ 2·(P−1)·V·K total
    loopback bytes (ring reduce-scatter → allgather), within framing
    margins.  VERDICT r3 weak-item 5."""
    overhead = _job_bytes("idle")
    measured = _job_bytes("opt", worker=OPT_WORKER) - overhead
    model = 2 * (NPROCS - 1) * ELEMS * 4 * ITERS
    ratio = measured / model
    print(f"optimizer step: measured {measured/1e6:.1f} MB, ring model "
          f"{model/1e6:.1f} MB ({ratio:.2f}x)")
    # Ring-optimal within framing/control noise; far below the P-1=3x of
    # a naive gather transport.
    assert 0.8 <= ratio <= 1.6, f"optimizer wire {ratio:.2f}x of ring model"
