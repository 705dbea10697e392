"""Host-side phases: what the control plane and the recovery paths cost.

Nothing here times the accelerator.  Training rates and served latencies are
``benchmarks/run.py``'s (``BENCHMARK.json``; the driver's readings are in
``PERF_LEDGER.jsonl``, their meaning in ``PERF.md``).  These phases run the
native engine, the checkpoint manager, the bulk data plane and the
coordinator tree on the host's CPU, one JSON line each, and every line says
so: ``"platform": "host"`` for a phase that never imports jax,
``jax.default_backend()`` for the one that does (checkpoint).
``docs/benchmarks.md`` records a reading of each.

``python bench.py`` runs four, each skipped by its ``BENCH_SKIP_*=1``:

1. **Eager small-tensor latency** (``BENCH_SKIP_EAGER``) — 256 x 4 KiB
   engine allreduces with a warm response cache against the same run under
   ``HOROVOD_CACHE_CAPACITY=0`` (docs/response_cache.md)::

       {"metric": "eager_allreduce_p50_us", "value": N, "unit": "us",
        "vs_baseline": <cold_p50 / warm_p50>, "cold_p50_us": M}

   ``BENCH_EAGER_OPS`` / ``BENCH_EAGER_ELEMS`` resize it.

2. **Checkpoint snapshot stall** (``BENCH_SKIP_CKPT``) — what the train
   loop pays per checkpoint under the async persist split
   (``HVD_TPU_CKPT_ASYNC=1``, checkpoint.CheckpointManager: snapshot at the
   step barrier, commit on the persist thread) against the synchronous save
   of the same state::

       {"metric": "checkpoint_stall_ms", "value": N, "unit": "ms",
        "vs_baseline": <sync_ms / stall_ms>, "checkpoint_sync_ms": M,
        "state_bytes": B}

   ``BENCH_CKPT_BYTES`` sizes the state (default 64 MiB),
   ``BENCH_CKPT_STEPS`` the saves.

3. **Replication data plane** (``BENCH_SKIP_DATAPLANE``) — engine-only jobs
   of 2 then 4 ranks replicate ``BENCH_DP_BYTES`` a step
   (``BENCH_DP_STEPS`` steps) over the rank-to-rank bulk data plane
   (dataplane.py, ZeRO-sharded replication.py) and report what ONE rank
   ships a snapshot::

       {"metric": "dataplane_replication_bytes_per_rank", "value": N,
        "unit": "bytes", "vs_baseline": <whole_replica_bytes / value>,
        "bytes_per_rank_n2": M, "relay_bytes": 0, "bandwidth_mb_s": B}

   Asserted, not only reported: the ~1/N scaling from 2 to 4 ranks, and
   zero payload bytes through the coordinator star in steady state.

4. **Control-plane scaling** (``BENCH_SKIP_CONTROL_PLANE``) — the deviceless
   fleet simulator (core/src/fleet_sim.cc: the real root/relay protocol
   code, scripted members, thread-CPU busy accounting) gives the negotiated
   coordination tick of the tree at 4096 protocol-only ranks against the
   rank-0 star at 512::

       {"metric": "control_plane_tick_us", "value": N, "unit": "us",
        "vs_baseline": <star_512_tick_us / value>, "p": 4096,
        "topology": "tree", "fanout": F, "num_groups": G, "depth": 2,
        "star_512_tick_us": M, "agg_frames_per_tick": G}

   The bar is value < 5000 (one HOROVOD_CYCLE_TIME) at depth >= 2 while the
   512-star already exceeds it (docs/benchmarks.md "Control-plane
   scaling").  ``BENCH_CP_RANKS`` / ``BENCH_CP_FANOUT`` / ``BENCH_CP_TICKS``
   resize the run.

``python bench.py --fault`` — **failure-detection MTTR**: a two-process
engine job; rank 1 is SIGKILLed at steady state and the survivor's
peer-failure abort (heartbeats + hardened frames, docs/fault_tolerance.md)
is timed end to end::

    {"metric": "failure_detection_ms", "value": N, "unit": "ms",
     "vs_baseline": <60 s stall window / value>,
     "wire_drop_silence_ms": <heartbeat-timeout path>}

``python bench.py --fault --elastic`` — **elastic recovery**: a
three-process job under ``HVD_TPU_ELASTIC=1``; a rank is SIGKILLed at steady
state and the survivors' in-place recovery is timed kill -> training again,
beside the full restart from a checkpoint on the same scenario.  Rank 2
(plain shrink) and rank 0 (standby promotion, succession-port re-bind,
re-rendezvous; docs/fault_tolerance.md "Coordinator failover")::

    {"metric": "elastic_recovery_ms", "value": N, "unit": "ms",
     "vs_baseline": <full_restart_recovery_ms / value>,
     "full_restart_recovery_ms": M}
    {"metric": "coordinator_failover_ms", ...same keys...}

The bar is >= 5x over the full restart for both.
"""

from __future__ import annotations

import json
import os
import signal
import socket
import subprocess
import sys
import textwrap
import time

# Every worker this file spawns is engine-only (NativeEngine + numpy; none
# imports jax — tests/test_chip_bringup.py holds them to it).  A chip belongs
# to one process and this parent may hold it, so the children are pinned to
# the CPU platform: a later edit that pulls jax into a worker then gets a CPU
# backend instead of hanging on a TPU it cannot have.
CHILD_ENV = {"JAX_PLATFORMS": "cpu"}


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def eager_microbench() -> None:
    """Per-op eager allreduce latency, warm response cache vs cache off.

    Single-process engine + local executor: the numbers isolate the CONTROL
    plane (negotiation + cycle pacing), which is exactly what the response
    cache and the event-driven wake-up change.  4 KiB tensors are the
    small-gradient regime where per-op overhead dominates the wire time.
    """
    import numpy as np

    from horovod_tpu.core.engine import OP_ALLREDUCE, NativeEngine
    from horovod_tpu.core.executors import local_executor

    ops = int(os.environ.get("BENCH_EAGER_OPS", "256"))
    elems = int(os.environ.get("BENCH_EAGER_ELEMS", "1024"))  # 4 KiB f32
    x = np.ones(elems, np.float32)

    def run(cache_capacity: int) -> float:
        eng = NativeEngine(0, 1, executor=local_executor,
                           cache_capacity=cache_capacity)
        try:
            for _ in range(8):  # warm-up: populates the cache when enabled
                eng.synchronize(eng.enqueue("bench.eager", x, OP_ALLREDUCE))
            lat = []
            for _ in range(ops):
                t0 = time.perf_counter()
                eng.synchronize(eng.enqueue("bench.eager", x, OP_ALLREDUCE))
                lat.append(time.perf_counter() - t0)
        finally:
            eng.shutdown()
        return sorted(lat)[len(lat) // 2] * 1e6  # p50, microseconds

    warm_p50 = run(cache_capacity=1024)
    cold_p50 = run(cache_capacity=0)
    print(json.dumps({
        "metric": "eager_allreduce_p50_us",
        "value": round(warm_p50, 1),
        "unit": "us",
        "platform": "host",
        "vs_baseline": round(cold_p50 / warm_p50, 3),
        "cold_p50_us": round(cold_p50, 1),
    }))


_FAULT_WORKER = textwrap.dedent("""
    import sys, time
    import numpy as np
    from horovod_tpu.core.engine import NativeEngine, OP_ALLREDUCE, \\
        CollectiveError
    from horovod_tpu.core.executors import local_executor

    rank, port = int(sys.argv[1]), int(sys.argv[2])
    eng = NativeEngine(rank, 2, executor=local_executor,
                       coordinator_host="127.0.0.1", coordinator_port=port,
                       cycle_time_ms=2.0)
    i = 0
    try:
        while True:
            h = eng.enqueue(f"b{i}", np.ones(1024, np.float32), OP_ALLREDUCE)
            eng.synchronize(h, timeout_s=120.0)
            i += 1
            if i == 20:
                print("STEADY", flush=True)
    except CollectiveError:
        print(f"REPORT={eng.failure_report()!r}", flush=True)
        time.sleep(30)  # the abort grace exits 75
""")


def fault_bench() -> None:
    """MTTR of the failure-detection layer (docs/fault_tolerance.md): wall
    time from SIGKILLing a rank to the survivor's structured exit-75 abort
    (EOF path), plus the heartbeat-timeout path's silence-to-detection
    from a wire-DROP run's failure_report."""
    here = os.path.dirname(os.path.abspath(__file__))

    def run(extra_env):
        port = _free_port()
        env = {**os.environ, **CHILD_ENV, "PYTHONPATH": here,
               "HVD_TPU_HEARTBEAT_MS": "50",
               "HVD_TPU_HEARTBEAT_TIMEOUT_MS": "1000",
               "HVD_TPU_ABORT_GRACE_MS": "100", **extra_env}
        procs = [subprocess.Popen(
            [sys.executable, "-c", _FAULT_WORKER, str(r), str(port)],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
            env=env, cwd=here) for r in (0, 1)]
        return procs

    # EOF path: SIGKILL rank 1 at steady state, time the survivor's abort.
    procs = run({})
    for line in procs[0].stdout:
        if "STEADY" in line:
            break
    procs[1].send_signal(signal.SIGKILL)
    t_kill = time.perf_counter()
    out0, _ = procs[0].communicate(timeout=120)
    detect_ms = (time.perf_counter() - t_kill) * 1e3
    procs[1].wait()
    assert procs[0].returncode == 75, (procs[0].returncode, out0[-1000:])

    # Heartbeat-timeout path: rank 1 silently DROPs all frames; the
    # survivor's report records how long the silence lasted at detection.
    procs = run({"HVD_TPU_FAULT_WIRE_DROP": "1:400"})
    out0, _ = procs[0].communicate(timeout=120)
    procs[1].communicate(timeout=120)
    silence_ms = -1.0
    if "'last_heard_ms': " in out0:
        silence_ms = float(
            out0.split("'last_heard_ms': ", 1)[1].split(",", 1)[0])

    stall_window_ms = 60_000.0  # the pre-heartbeat detection floor
    print(json.dumps({
        "metric": "failure_detection_ms",
        "value": round(detect_ms, 1),
        "unit": "ms",
        "platform": "host",
        "vs_baseline": round(stall_window_ms / max(detect_ms, 1e-9), 1),
        "wire_drop_silence_ms": round(silence_ms, 1),
    }))


_ELASTIC_WORKER = textwrap.dedent("""
    import sys, time
    import numpy as np
    from horovod_tpu.core.engine import NativeEngine, OP_ALLREDUCE, \\
        MembershipChanged, CollectiveError
    from horovod_tpu.core import engine as em
    from horovod_tpu.core.executors import local_executor
    from horovod_tpu import elastic

    rank, port, n = int(sys.argv[1]), int(sys.argv[2]), int(sys.argv[3])
    eng = NativeEngine(rank, n, executor=local_executor,
                       coordinator_host="127.0.0.1", coordinator_port=port,
                       cycle_time_ms=2.0)
    elastic.attach(eng)
    i, done, resumed = 0, 0, False
    while done < 5000:
        try:
            h = eng.enqueue(f"b{i}", np.ones(1024, np.float32),
                            OP_ALLREDUCE)
            eng.synchronize(h, timeout_s=120.0)
            done += 1
            i += 1
            if done == 20:
                print("STEADY", flush=True)
            if resumed:
                # First collective COMPLETED under the shrunken
                # membership: the survivors are training again.
                print(f"RESUMED ts={time.time():.6f}", flush=True)
                break
        except MembershipChanged:
            ev = elastic.reconfigure()
            eng = em.peek_engine()
            i = ev.epoch * 100000
            resumed = True
        except CollectiveError:
            time.sleep(10)
            sys.exit(3)
""")


# Launcher child for the full-restart comparison: same 3-proc kill, but
# recovery = teardown + relaunch + re-rendezvous (PR-1 supervision).
_RESTART_WORKER = textwrap.dedent("""
    import os, sys, time
    import numpy as np
    from horovod_tpu.core.engine import NativeEngine, OP_ALLREDUCE, \\
        CollectiveError
    from horovod_tpu.core.executors import local_executor
    from horovod_tpu import faults

    rank = int(os.environ["JAX_PROCESS_ID"])
    n = int(os.environ["JAX_NUM_PROCESSES"])
    port = int(os.environ["HVD_TPU_COORDINATOR_PORT"])
    attempt = int(os.environ.get("HVD_TPU_RESTART_ATTEMPT", "0"))
    eng = NativeEngine(rank, n, executor=local_executor,
                       coordinator_host="127.0.0.1", coordinator_port=port,
                       cycle_time_ms=2.0)
    try:
        for i in range(40):
            if rank == 2 and attempt == 0 and i == 25:
                print(f"KILLNOW ts={time.time():.6f}", flush=True)
            faults.step(i, rank=rank)
            h = eng.enqueue(f"g{i}", np.ones(1024, np.float32),
                            OP_ALLREDUCE)
            eng.synchronize(h, timeout_s=120.0)
            if i == 0 and attempt > 0:
                # First collective of the relaunched attempt completed:
                # the job is training again after the full restart.
                print(f"TRAINING ts={time.time():.6f}", flush=True)
        eng.shutdown()
    except CollectiveError:
        time.sleep(30)  # the abort grace exits 75; supervisor relaunches
""")


def elastic_bench() -> None:
    """Kill → survivors-training-again MTTR of in-place elastic recovery,
    vs the full teardown+relaunch path on the same 3-process scenario.
    Measured twice: a WORKER death (plain shrink, ``elastic_recovery_ms``)
    and the COORDINATOR's death (standby promotion + port re-bind + every
    survivor's re-rendezvous, ``coordinator_failover_ms``) — the failover
    path does strictly more work, so it gets its own number."""
    here = os.path.dirname(os.path.abspath(__file__))
    base_env = {**os.environ, **CHILD_ENV, "PYTHONPATH": here,
                "HVD_TPU_HEARTBEAT_MS": "50",
                "HVD_TPU_HEARTBEAT_TIMEOUT_MS": "1000",
                "HVD_TPU_ABORT_GRACE_MS": "100",
                "HVD_TPU_CONNECT_TIMEOUT": "60"}

    def in_place_mttr(kill_rank: int, watch_rank: int) -> float:
        """SIGKILL ``kill_rank`` at steady state; wall-clock ms until
        ``watch_rank``'s first post-shrink collective completes."""
        env = {**base_env, "HVD_TPU_ELASTIC": "1",
               "HVD_TPU_RECONFIG_TIMEOUT_MS": "20000"}
        p0_port = _free_port()
        procs = [subprocess.Popen(
            [sys.executable, "-c", _ELASTIC_WORKER, str(r), str(p0_port),
             "3"],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
            env=env, cwd=here) for r in range(3)]
        for line in procs[watch_rank].stdout:
            if "STEADY" in line:
                break
        procs[kill_rank].send_signal(signal.SIGKILL)
        t_kill = time.time()
        out, _ = procs[watch_rank].communicate(timeout=120)
        for r, p in enumerate(procs):
            if r != watch_rank:
                p.kill()
                p.wait()
        resumed_ts = float(out.split("RESUMED ts=", 1)[1].split()[0])
        return (resumed_ts - t_kill) * 1e3

    # In-place shrink: kill rank 2, read a survivor's RESUMED stamp.
    elastic_ms = in_place_mttr(kill_rank=2, watch_rank=0)
    # Coordinator failover: kill rank 0, read the promoted standby's stamp.
    failover_ms = in_place_mttr(kill_rank=0, watch_rank=1)

    # Full restart on the same scenario: launcher supervision, injected
    # SIGKILL of rank 2, recovery ends at the relaunched attempt's first
    # completed collective.
    env = {**base_env, "HVD_TPU_RESTART_BACKOFF": "0.1",
           "HVD_TPU_FAULT_KILL_RANK": "2", "HVD_TPU_FAULT_KILL_STEP": "25"}
    env.pop("HVD_TPU_ELASTIC", None)
    res = subprocess.run(
        [sys.executable, "-m", "horovod_tpu.run", "-np", "3",
         "--max-restarts", "2", "--",
         sys.executable, "-c", _RESTART_WORKER],
        cwd=here, capture_output=True, text=True, timeout=300, env=env)
    kill_ts = float(res.stdout.split("KILLNOW ts=", 1)[1].split()[0])
    train_ts = min(float(c.split()[0])
                   for c in res.stdout.split("TRAINING ts=")[1:])
    restart_ms = (train_ts - kill_ts) * 1e3

    print(json.dumps({
        "metric": "elastic_recovery_ms",
        "value": round(elastic_ms, 1),
        "unit": "ms",
        "platform": "host",
        "vs_baseline": round(restart_ms / max(elastic_ms, 1e-9), 1),
        "full_restart_recovery_ms": round(restart_ms, 1),
    }))
    print(json.dumps({
        "metric": "coordinator_failover_ms",
        "value": round(failover_ms, 1),
        "unit": "ms",
        "platform": "host",
        "vs_baseline": round(restart_ms / max(failover_ms, 1e-9), 1),
        "full_restart_recovery_ms": round(restart_ms, 1),
    }))


def checkpoint_bench() -> None:
    """Snapshot-stall of the async persist split vs the synchronous save.

    One process, one state dict of ``BENCH_CKPT_BYTES`` of float32: the
    sync manager's ``save()`` (payload write + ``_COMMIT`` inline) is the
    baseline; the async manager's ``save()`` returns after the snapshot
    (orbax async kick + persist-thread enqueue), so its call time IS the
    per-checkpoint train-loop stall the tentpole exists to shrink.
    Median of ``BENCH_CKPT_STEPS`` saves each, same state both times."""
    import shutil
    import tempfile

    import jax
    import numpy as np

    from horovod_tpu import checkpoint as hvd_checkpoint

    nbytes = int(os.environ.get("BENCH_CKPT_BYTES", str(64 << 20)))
    steps = int(os.environ.get("BENCH_CKPT_STEPS", "5"))
    state = {"params": np.random.default_rng(0)
             .standard_normal(max(1, nbytes // 4)).astype(np.float32)}

    def run(async_mode: bool) -> float:
        root = tempfile.mkdtemp(prefix="bench-ckpt-")
        saved = os.environ.get("HVD_TPU_CKPT_ASYNC")
        os.environ["HVD_TPU_CKPT_ASYNC"] = "1" if async_mode else "0"
        try:
            mgr = hvd_checkpoint.CheckpointManager(
                root, max_to_keep=2, rank=0, size=1)
            lat = []
            for s in range(steps):
                t0 = time.perf_counter()
                mgr.save(s, state, metadata={"step": s})
                lat.append(time.perf_counter() - t0)
                # Let the background persist land OUTSIDE the timed
                # window: real checkpoints are steps apart, so the stall
                # the loop pays is the snapshot, not the previous write
                # (back-to-back saves would serialize on it and measure
                # the disk, not the split).
                mgr.drain()
        finally:
            if saved is None:
                os.environ.pop("HVD_TPU_CKPT_ASYNC", None)
            else:
                os.environ["HVD_TPU_CKPT_ASYNC"] = saved
            shutil.rmtree(root, ignore_errors=True)
        return sorted(lat)[len(lat) // 2] * 1e3  # median, ms

    sync_ms = run(async_mode=False)
    stall_ms = run(async_mode=True)
    print(json.dumps({
        "metric": "checkpoint_stall_ms",
        "value": round(stall_ms, 1),
        "unit": "ms",
        "platform": jax.default_backend(),
        "vs_baseline": round(sync_ms / max(stall_ms, 1e-9), 1),
        "checkpoint_sync_ms": round(sync_ms, 1),
        "state_bytes": nbytes,
    }))


DATAPLANE_WORKER = textwrap.dedent("""
    import os, sys, time
    import numpy as np
    from horovod_tpu import dataplane, replication
    from horovod_tpu.core import engine as ce
    from horovod_tpu.core.engine import NativeEngine
    from horovod_tpu.core.executors import local_executor

    rank, port, n = int(sys.argv[1]), int(sys.argv[2]), int(sys.argv[3])
    nbytes = int(os.environ.get("BENCH_DP_BYTES", str(8 << 20)))
    steps = int(os.environ.get("BENCH_DP_STEPS", "3"))
    bp = dataplane.ensure_listener()
    eng = NativeEngine(rank, n, executor=local_executor,
                       coordinator_host="127.0.0.1", coordinator_port=port,
                       cycle_time_ms=2.0, bulk_port=bp)
    ce.replace_engine(None, eng)
    state = {"w": np.zeros(max(1, nbytes // 4), np.float32)}
    blob_len = len(replication.encode_snapshot(0, state))
    for step in range(1, steps + 1):
        replication.put(step, state, eng=eng)
    # Steady state: this rank holds its OWN shard of the newest step plus
    # its ring predecessor's (2 holders per shard; full reassembly at
    # N > 2 is the restore path's transfer plan, not steady state).
    want = {rank, (rank - 1) % n}
    deadline = time.time() + 60
    done = False
    while time.time() < deadline:
        replication.drain(eng)
        done = want <= set(replication.have_shards(steps, eng.epoch))
        if done:
            break
        time.sleep(0.02)
    s = replication.replication_stats()
    s["blob_len"] = blob_len
    s["replicated"] = done
    print(f"RANK{rank} STATS={s!r}", flush=True)
    time.sleep(0.5)
    eng.shutdown()
""")


def dataplane_bench() -> None:
    """Per-rank replication traffic of the ZeRO-sharded bulk data plane.

    Two engine-only jobs (N=2, N=4) replicate the same state; each rank
    ships exactly its own 1/N shard per snapshot, rank-to-rank.  Asserted
    here, not just reported: bytes per rank halve from N=2 to N=4, and
    the coordinator relayed ZERO payload bytes in steady state."""
    def run(n: int) -> list[dict]:
        cp = _free_port()
        env = {**os.environ, **CHILD_ENV, "PYTHONPATH": os.path.dirname(
            os.path.abspath(__file__))}
        procs = [subprocess.Popen(
            [sys.executable, "-c", DATAPLANE_WORKER, str(r), str(cp), str(n)],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
            env=env)
            for r in range(n)]
        stats = []
        for p in procs:
            out, _ = p.communicate(timeout=180)
            assert p.returncode == 0, out[-2000:]
            line = next(ln for ln in out.splitlines() if "STATS=" in ln)
            stats.append(eval(line.split("STATS=", 1)[1]))
        return stats

    steps = int(os.environ.get("BENCH_DP_STEPS", "3"))
    s2, s4 = run(2), run(4)
    for stats in (s2, s4):
        for s in stats:
            assert s["replicated"], s
            assert s["bytes_shipped_relay"] == 0, s  # zero coordinator bytes
    per_rank2 = max(s["bytes_shipped_direct"] for s in s2) / steps
    per_rank4 = max(s["bytes_shipped_direct"] for s in s4) / steps
    assert 0.35 <= per_rank4 / per_rank2 <= 0.65, (per_rank2, per_rank4)
    whole = s4[0]["blob_len"]  # what the pre-shard design shipped per rank
    bw = max(s["bandwidth_bytes_per_s"] for s in s4)
    print(json.dumps({
        "metric": "dataplane_replication_bytes_per_rank",
        "value": int(per_rank4),
        "unit": "bytes",
        "platform": "host",
        "vs_baseline": round(whole / max(per_rank4, 1), 2),
        "bytes_per_rank_n2": int(per_rank2),
        "relay_bytes": 0,
        "bandwidth_mb_s": round(bw / 1e6, 1),
    }))


def control_plane_bench() -> None:
    """Tree-vs-star coordination-tick scaling via the fleet simulator.

    Runs core/fleet_sim twice — the tree at ``BENCH_CP_RANKS`` (default
    4096) protocol-only ranks and the star at 512, the reference's
    demonstrated scale — and reports the tree's modeled per-tick busy
    time with the star baseline as ``vs_baseline``.  The simulator runs
    the REAL TreeRootPlane/Coordinator/relay code; only the members are
    scripted, and busy time is thread CPU so one oversubscribed host
    can stand in for a fleet (methodology disclosed in fleet_sim.cc and
    docs/benchmarks.md)."""
    here = os.path.dirname(os.path.abspath(__file__))
    core = os.path.join(here, "horovod_tpu", "core")
    binary = os.path.join(core, "fleet_sim")
    # Always through make, like the library (core/engine.py): it no-ops when
    # the binary is current and rebuilds one left over from older sources.
    subprocess.run(["make", "-C", core, "fleet_sim"], check=True,
                   capture_output=True)

    def run(argv: list[str]) -> dict:
        res = subprocess.run([binary] + argv, capture_output=True,
                             text=True, timeout=900, check=True)
        line = next(ln for ln in reversed(res.stdout.splitlines())
                    if "modeled_tick_us" in ln)
        return json.loads(line)

    ranks = int(os.environ.get("BENCH_CP_RANKS", "4096"))
    fanout = int(os.environ.get("BENCH_CP_FANOUT", "128"))
    ticks = os.environ.get("BENCH_CP_TICKS", "12")
    tree = run(["--p", str(ranks), "--fanout", str(fanout),
                "--ticks", ticks])
    star = run(["--p", "512", "--topology", "star", "--ticks", ticks])
    assert tree["ok"] and star["ok"], (tree, star)
    print(json.dumps({
        "metric": "control_plane_tick_us",
        "value": round(tree["modeled_tick_us"], 1),
        "unit": "us",
        "platform": "host",
        "vs_baseline": round(star["modeled_tick_us"]
                             / max(tree["modeled_tick_us"], 1e-9), 2),
        "p": ranks,
        "topology": "tree",
        "fanout": fanout,
        "num_groups": tree["num_groups"],
        "depth": tree["depth"],
        "star_512_tick_us": round(star["modeled_tick_us"], 1),
        "agg_frames_per_tick": tree["agg_frames_per_tick"],
    }))


def main() -> None:
    if "--fault" in sys.argv:
        if "--elastic" in sys.argv:
            elastic_bench()
        else:
            fault_bench()
        return
    if os.environ.get("BENCH_SKIP_EAGER") != "1":
        eager_microbench()
    if os.environ.get("BENCH_SKIP_CKPT") != "1":
        checkpoint_bench()
    if os.environ.get("BENCH_SKIP_DATAPLANE") != "1":
        dataplane_bench()
    if os.environ.get("BENCH_SKIP_CONTROL_PLANE") != "1":
        control_plane_bench()


if __name__ == "__main__":
    sys.exit(main())
