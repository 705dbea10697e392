"""In-place elastic recovery: shrink-to-survive membership reconfiguration
and rank rejoin (docs/fault_tolerance.md "In-place recovery").

PR 4 made peer-death *detection* ~100 ms; these tests cover the *recovery*
half: with ``HVD_TPU_ELASTIC=1`` the survivors of a non-coordinator death
shrink in place — RECONFIG broadcast, epoch bump, same-process engine
re-form — instead of exiting 75 for a full relaunch.  Children are
engine-only where possible (numpy + ctypes) so scenarios stay cheap; the
checkpoint-resume tests pay the jax import because they drive the REAL
``training.elastic_loop`` + ``CheckpointManager`` path.

Coordinator failover (docs/fault_tolerance.md "Coordinator failover") is
covered here too: rank 0's death promotes the pre-announced standby —
every survivor synthesizes the identical succession verdict locally, the
standby re-binds its advertised port as the new rank 0, and the job
shrinks in place exactly like a worker death.  The chaos soak points the
PR-4 wire injectors at the coordinator itself (KILL / DROP / PARTITION /
HALFCLOSE / CORRUPT): every scenario must end in a clean shrink or a
structured bounded abort — never a hang.
"""

import os
import signal
import socket
import subprocess
import sys
import textwrap
import time

import pytest

from _timing import scaled
from _tsan import tsan_runtime

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

FAST_HB = {
    "HVD_TPU_HEARTBEAT_MS": "50",
    "HVD_TPU_HEARTBEAT_TIMEOUT_MS": str(int(scaled(800))),
    "HVD_TPU_ABORT_GRACE_MS": "300",
    "HVD_TPU_CONNECT_TIMEOUT": str(scaled(60)),
    "HVD_TPU_RECONFIG_TIMEOUT_MS": str(int(scaled(20000))),
    "HVD_TPU_ELASTIC": "1",
}


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _spawn(script, nprocs, extra_env, port=None, args=()):
    port = port or _free_port()
    env = {**os.environ, "PYTHONPATH": REPO, **FAST_HB, **extra_env}
    procs = [
        subprocess.Popen(
            [sys.executable, "-c", script, str(r), str(port), str(nprocs),
             *[str(a) for a in args]],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
            env=env, cwd=REPO)
        for r in range(nprocs)
    ]
    return procs, port


def _drain(procs, timeout):
    outs = []
    for p in procs:
        try:
            out, _ = p.communicate(timeout=timeout)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            raise
        outs.append(out or "")
    return outs


# Engine-only elastic worker: streams allreduces; on MembershipChanged it
# reconfigures in place and resynchronizes its name counter through the
# shared epoch (real training resynchronizes through the checkpoint step —
# see the elastic_loop test below).  argv: rank port nprocs [total [epoch]]
# With an epoch the stream also runs on until the membership has changed
# that often (or the job has aborted): a test that kills a rank once the
# job is STEADY then finds it still running, however late the test's own
# process is given the CPU; 25 steps of 2 ms are no time to count on.
ELASTIC_WORKER = textwrap.dedent("""
    import os, sys, time
    import numpy as np
    from horovod_tpu.core.engine import NativeEngine, OP_ALLREDUCE, \\
        CollectiveError, MembershipChanged
    from horovod_tpu.core import engine as em
    from horovod_tpu.core.executors import local_executor
    from horovod_tpu import elastic

    rank, port, n = int(sys.argv[1]), int(sys.argv[2]), int(sys.argv[3])
    total = int(sys.argv[4]) if len(sys.argv) > 4 else 30
    until_epoch = int(sys.argv[5]) if len(sys.argv) > 5 else 0
    eng = NativeEngine(rank, n, executor=local_executor,
                       coordinator_host="127.0.0.1", coordinator_port=port,
                       cycle_time_ms=2.0)
    elastic.attach(eng)
    pid = os.getpid()
    i, done = 0, 0
    while done < total or eng.epoch < until_epoch:
        try:
            h = eng.enqueue(f"s{i}", np.ones(8, np.float32), OP_ALLREDUCE)
            eng.synchronize(h, timeout_s=120.0)
            done += 1
            i += 1
            if done == 5:
                print(f"RANK{rank} STEADY pid={pid}", flush=True)
        except MembershipChanged:
            ev = elastic.reconfigure()
            eng = em.peek_engine()
            i = ev.epoch * 1000
            print(f"RANK{rank} RECONFIGURED epoch={ev.epoch} "
                  f"new_rank={ev.new_rank} new_size={ev.new_size} "
                  f"failed={ev.failed_rank} pid={os.getpid()}", flush=True)
        except CollectiveError as e:
            print(f"RANK{rank} ABORTED {e}", flush=True)
            time.sleep(30)  # the abort grace exits 75
            sys.exit(3)
    print(f"RANK{rank} DONE rank={eng.rank} size={eng.size} "
          f"epoch={eng.epoch} pid={os.getpid()}", flush=True)
    eng.shutdown()
""")


def _wait_steady(proc, deadline):
    lines = []
    for line in proc.stdout:
        lines.append(line)
        if "STEADY" in line:
            return lines
        assert time.monotonic() < deadline, "".join(lines[-30:])
    raise AssertionError("stream ended early:\n" + "".join(lines[-30:]))


def test_shrink_in_place_reassigns_ranks_no_process_restart():
    """Kill the MIDDLE rank of 3: survivors shrink to size 2 with
    contiguous re-assigned ranks (old rank 2 -> new rank 1), the epoch
    bumps to 1, collectives resume, and — the point of the PR — both
    survivors finish in the SAME process (pid unchanged, exit 0)."""
    procs, _ = _spawn(ELASTIC_WORKER, 3, {}, args=(30, 1))
    try:
        deadline = time.monotonic() + scaled(60)
        heads = [_wait_steady(p, deadline) for p in procs]
        procs[1].kill()
        outs = _drain(procs, timeout=scaled(60))
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
    full = ["".join(h) + o for h, o in zip(heads, outs)]
    assert procs[0].returncode == 0, (procs[0].returncode, full[0][-2000:])
    assert procs[2].returncode == 0, (procs[2].returncode, full[2][-2000:])
    # Rank 0 stays rank 0; old rank 2 is contiguously re-assigned rank 1.
    assert "RANK0 RECONFIGURED epoch=1 new_rank=0 new_size=2 failed=1" \
        in full[0], full[0][-2000:]
    assert "RANK2 RECONFIGURED epoch=1 new_rank=1 new_size=2 failed=1" \
        in full[2], full[2][-2000:]
    assert "RANK0 DONE rank=0 size=2 epoch=1" in full[0], full[0][-2000:]
    assert "RANK2 DONE rank=1 size=2 epoch=1" in full[2], full[2][-2000:]
    # No process restart: the pid before the kill equals the pid after.
    for r in (0, 2):
        pre = full[r].split("STEADY pid=", 1)[1].split()[0]
        post = full[r].split("DONE", 1)[1].split("pid=", 1)[1].split()[0]
        assert pre == post, (r, pre, post)


def test_coordinator_death_promotes_standby_in_place():
    """The tentpole scenario: kill rank 0 of 3.  Every survivor detects
    the coordinator death independently and synthesizes the same
    succession verdict — the default standby (rank 1) re-binds its
    pre-announced port as the NEW rank 0, old rank 2 renumbers to 1, the
    epoch bumps, and both survivors finish in the SAME process."""
    procs, _ = _spawn(ELASTIC_WORKER, 3, {}, args=(30, 1))
    try:
        deadline = time.monotonic() + scaled(60)
        heads = [_wait_steady(p, deadline) for p in procs]
        procs[0].kill()
        outs = _drain(procs, timeout=scaled(90))
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
    full = ["".join(h) + o for h, o in zip(heads, outs)]
    assert procs[1].returncode == 0, (procs[1].returncode, full[1][-2500:])
    assert procs[2].returncode == 0, (procs[2].returncode, full[2][-2500:])
    # The promotion is announced with the succession endpoint...
    assert "promoting standby rank 1" in full[1], full[1][-2500:]
    # ...the standby takes the coordinator seat, the other survivor
    # renumbers contiguously, and failed=0 names the dead coordinator.
    assert "RANK1 RECONFIGURED epoch=1 new_rank=0 new_size=2 failed=0" \
        in full[1], full[1][-2500:]
    assert "RANK2 RECONFIGURED epoch=1 new_rank=1 new_size=2 failed=0" \
        in full[2], full[2][-2500:]
    assert "RANK1 DONE rank=0 size=2 epoch=1" in full[1], full[1][-2500:]
    assert "RANK2 DONE rank=1 size=2 epoch=1" in full[2], full[2][-2500:]
    # In place: the engine moved, the processes did not.
    for r in (1, 2):
        pre = full[r].split("STEADY pid=", 1)[1].split()[0]
        post = full[r].split("DONE", 1)[1].split("pid=", 1)[1].split()[0]
        assert pre == post, (r, pre, post)


def test_standby_env_override_promotes_named_rank():
    """HVD_TPU_STANDBY=2 pins the succession: rank 2 (not the default
    lowest rank 1) is promoted to coordinator; rank 1 fills new rank 1 by
    the deterministic old-rank-order remap."""
    procs, _ = _spawn(ELASTIC_WORKER, 3, {"HVD_TPU_STANDBY": "2"},
                      args=(30, 1))
    try:
        deadline = time.monotonic() + scaled(60)
        heads = [_wait_steady(p, deadline) for p in procs]
        procs[0].kill()
        outs = _drain(procs, timeout=scaled(90))
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
    full = ["".join(h) + o for h, o in zip(heads, outs)]
    assert procs[1].returncode == 0, (procs[1].returncode, full[1][-2500:])
    assert procs[2].returncode == 0, (procs[2].returncode, full[2][-2500:])
    assert "promoting standby rank 2" in full[2], full[2][-2500:]
    assert "RANK2 RECONFIGURED epoch=1 new_rank=0 new_size=2 failed=0" \
        in full[2], full[2][-2500:]
    assert "RANK1 RECONFIGURED epoch=1 new_rank=1 new_size=2 failed=0" \
        in full[1], full[1][-2500:]
    assert "RANK2 DONE rank=0 size=2 epoch=1" in full[2], full[2][-2500:]
    assert "RANK1 DONE rank=1 size=2 epoch=1" in full[1], full[1][-2500:]


# Replication probe: the standby reports the streamed coordinator state;
# a plain worker reports nothing.  argv: rank port nprocs
COORD_STATE_PROBE = textwrap.dedent("""
    import sys, time
    import numpy as np
    from horovod_tpu.core.engine import NativeEngine, OP_ALLREDUCE
    from horovod_tpu.core import engine as em
    from horovod_tpu.core.executors import local_executor

    rank, port, n = int(sys.argv[1]), int(sys.argv[2]), int(sys.argv[3])
    eng = NativeEngine(rank, n, executor=local_executor,
                       coordinator_host="127.0.0.1", coordinator_port=port,
                       cycle_time_ms=2.0)
    # STATE frames land on the standby's ACTIVE read path (idle bytes stay
    # unread so the heartbeat starvation probe works), so keep exchanging
    # while polling — like a real training loop does.  Every rank runs the
    # same fixed schedule (collectives need all participants); 60 steps at
    # 20 ms spans dozens of 50 ms monitor ticks.
    state = None
    for i in range(60):
        h = eng.enqueue(f"p{i}", np.ones(8, np.float32), OP_ALLREDUCE)
        eng.synchronize(h, timeout_s=60.0)
        state = eng.coord_state() or state
        time.sleep(0.02)
    print(f"RANK{rank} STATE={state!r}", flush=True)
    eng.shutdown()
""")


def test_coordinator_state_replicates_to_standby_only():
    """The coordinator streams its authoritative state to the standby in
    STATE frames each monitor tick: the standby (rank 1) observes a
    snapshot with the live epoch and the response-cache LRU order; a
    non-standby worker (rank 2) observes nothing."""
    procs, _ = _spawn(COORD_STATE_PROBE, 3, {})
    outs = _drain(procs, timeout=scaled(90))
    assert all(p.returncode == 0 for p in procs), \
        [(p.returncode, o[-1500:]) for p, o in zip(procs, outs)]
    by_rank = {r: outs[r] for r in range(3)}
    assert "RANK2 STATE=None" in by_rank[2], by_rank[2][-1500:]
    line = [ln for ln in by_rank[1].splitlines() if "STATE=" in ln][0]
    assert "'epoch': 0" in line, line
    # The LRU order replicates the coordinator's slot decisions: each
    # coordinated collective occupies a cache entry, newest first.
    assert "'lru_order':" in line, line
    state = eval(line.split("STATE=", 1)[1])  # repr of a plain dict
    assert 1 <= len(state["lru_order"]) <= 60, state
    assert state["verify_tick"] >= 0 and state["joins_admitted"] == 0, state


def test_min_size_floor_keeps_legacy_full_restart_path():
    """HVD_TPU_MIN_SIZE=2 with 2 processes: the shrink to 1 would cross
    the floor, so the legacy coordinated abort applies — survivor exits 75
    with a failure report naming the dead rank, and no RECONFIG fires."""
    procs, _ = _spawn(ELASTIC_WORKER, 2, {"HVD_TPU_MIN_SIZE": "2"},
                      args=(30, 1))
    try:
        deadline = time.monotonic() + scaled(60)
        heads = [_wait_steady(p, deadline) for p in procs]
        procs[1].kill()
        outs = _drain(procs, timeout=scaled(60))
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
    full = "".join(heads[0]) + outs[0]
    assert procs[0].returncode == 75, (procs[0].returncode, full[-2000:])
    assert "RECONFIGURED" not in full, full[-2000:]
    assert "ABORTED" in full, full[-2000:]


# The REAL recovery path: training.elastic_loop + CheckpointManager.
# argv: rank port nprocs ckpt_dir steps
ELASTIC_TRAIN = textwrap.dedent("""
    import os, sys
    import numpy as np
    from horovod_tpu.core.engine import NativeEngine, OP_ALLREDUCE
    from horovod_tpu.core import engine as em
    from horovod_tpu.core.executors import local_executor
    from horovod_tpu import checkpoint, elastic, training

    rank, port, n = int(sys.argv[1]), int(sys.argv[2]), int(sys.argv[3])
    ckpt_dir, steps = sys.argv[4], int(sys.argv[5])
    eng = NativeEngine(rank, n, executor=local_executor,
                       coordinator_host="127.0.0.1", coordinator_port=port,
                       cycle_time_ms=2.0)
    elastic.attach(eng)
    pid = os.getpid()
    # rank= gates writes to the actual rank 0; size=1 restores from the
    # shared directory directly (engine-only job: no broadcast plane).
    mgr = checkpoint.CheckpointManager(ckpt_dir, max_to_keep=2, rank=rank,
                                       size=1)

    def step_fn(step, state):
        e = em.peek_engine()   # the engine can be re-formed between steps
        grad = np.full(4, float(step + 1), np.float32)
        h = e.enqueue(f"el.g{step}", grad, OP_ALLREDUCE)
        g = e.synchronize(h, timeout_s=120.0)
        print(f"STEP {step} rank={rank}", flush=True)
        return {"params": state["params"] + g}

    state = {"params": np.zeros(4, np.float32)}
    state = training.elastic_loop(step_fn, state, num_steps=steps,
                                  manager=mgr, checkpoint_every=1)
    print(f"[rank {rank}] FINAL={state['params'].tolist()} pid={pid} "
          f"now={os.getpid()} size={em.peek_engine().size} "
          f"reads={checkpoint.disk_read_count()}", flush=True)
    em.peek_engine().shutdown()  # coordinated teardown, no EOF-side effects
""")


def _finals(outs):
    res = {}
    for out in outs:
        for line in out.splitlines():
            if "FINAL=" in line:
                r = int(line.split("[rank ", 1)[1].split("]")[0])
                res[r] = line.split("FINAL=", 1)[1].split(" pid=")[0]
    return res


def test_elastic_loop_shrinks_and_resumes_bit_exact_from_checkpoint(
        tmp_path):
    """The acceptance scenario: 3 ranks in training.elastic_loop with
    manifest-committed checkpoints; rank 2 is SIGKILLed at step 3.  The
    survivors shrink to size 2 and resume from the step-2 checkpoint
    WITHOUT process restart — final parameters are bit-identical to an
    uninterrupted run's, and each survivor's pid is unchanged."""
    steps = 6
    expected = str([float(sum(s + 1 for s in range(steps)))] * 4)

    def run(tag, extra_env, kill=False):
        ckpt = tmp_path / tag
        ckpt.mkdir()
        env = {**extra_env}
        procs, _ = _spawn(ELASTIC_TRAIN, 3, env,
                          args=(ckpt, steps))
        outs = _drain(procs, timeout=scaled(240))
        return procs, outs

    # Uninterrupted reference run.
    clean_procs, clean_outs = run("clean", {})
    assert all(p.returncode == 0 for p in clean_procs), \
        [o[-1500:] for o in clean_outs]
    clean_finals = _finals(clean_outs)
    assert set(clean_finals) == {0, 1, 2}
    assert clean_finals[0] == expected, clean_finals

    # Faulted run: deterministic SIGKILL of rank 2 at step 3 (faults.py,
    # rank from JAX_PROCESS_ID in each child).
    ckpt = tmp_path / "faulted"
    ckpt.mkdir()
    port = _free_port()
    procs = []
    for r in range(3):
        env = {**os.environ, "PYTHONPATH": REPO, **FAST_HB,
               "JAX_PROCESS_ID": str(r),
               "HVD_TPU_FAULT_KILL_RANK": "2",
               "HVD_TPU_FAULT_KILL_STEP": "3"}
        procs.append(subprocess.Popen(
            [sys.executable, "-c", ELASTIC_TRAIN, str(r), str(port), "3",
             str(ckpt), str(steps)],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
            env=env, cwd=REPO))
    outs = _drain(procs, timeout=scaled(240))
    assert procs[0].returncode == 0, outs[0][-2500:]
    assert procs[1].returncode == 0, outs[1][-2500:]
    assert procs[2].returncode != 0  # the killed rank
    finals = _finals(outs)
    assert set(finals) == {0, 1}, outs[0][-1500:]
    # Bit-identical to the uninterrupted run.
    assert finals[0] == expected, (finals, expected)
    assert finals[1] == expected
    # In place: same pid before and after, shrunken engine size 2.
    for r in (0, 1):
        line = [ln for ln in outs[r].splitlines() if "FINAL=" in ln][0]
        pid = line.split("pid=", 1)[1].split()[0]
        now = line.split("now=", 1)[1].split()[0]
        assert pid == now, line
        assert "size=2" in line, line
    # The job genuinely rewound to the checkpoint: the pre-kill step-3
    # attempt aborted (no completion print), and step 3 completed exactly
    # once, AFTER the reconfiguration notice.
    assert outs[0].count("STEP 3 rank=0") == 1, outs[0][-2500:]
    assert outs[0].index("Membership changed") \
        < outs[0].index("STEP 3 rank=0"), outs[0][-2500:]


def test_elastic_loop_peer_restore_zero_disk_reads_bit_exact(tmp_path):
    """The PR-10 tentpole acceptance scenario: ``HVD_TPU_CKPT_REPLICATE=1``
    (+ async persist) ships every rank's snapshot to its ring neighbor's
    host memory as SHARD_PUT frames; when rank 2 dies at step 3 the
    survivors reconfigure and restore the step-2 state FROM THE REPLICA —
    ``checkpoint.disk_read_count()`` stays 0 on both survivors — with
    final parameters bit-identical to the disk-restore run of the exact
    same scenario (test_elastic_loop_shrinks_and_resumes_bit_exact...).
    Epoch-stale rejection is pinned at the unit level
    (tests/test_replication.py): here the reconfigure path re-stamps the
    survivors' replicas to epoch 1, which is what makes them eligible."""
    steps = 6
    expected = str([float(sum(s + 1 for s in range(steps)))] * 4)
    ckpt = tmp_path / "peer"
    ckpt.mkdir()
    port = _free_port()
    procs = []
    for r in range(3):
        env = {**os.environ, "PYTHONPATH": REPO, **FAST_HB,
               "JAX_PROCESS_ID": str(r),
               "HVD_TPU_CKPT_REPLICATE": "1",
               "HVD_TPU_CKPT_ASYNC": "1",
               "HVD_TPU_FAULT_KILL_RANK": "2",
               "HVD_TPU_FAULT_KILL_STEP": "3"}
        procs.append(subprocess.Popen(
            [sys.executable, "-c", ELASTIC_TRAIN, str(r), str(port), "3",
             str(ckpt), str(steps)],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
            env=env, cwd=REPO))
    outs = _drain(procs, timeout=scaled(240))
    assert procs[0].returncode == 0, outs[0][-2500:]
    assert procs[1].returncode == 0, outs[1][-2500:]
    assert procs[2].returncode != 0  # the killed rank
    finals = _finals(outs)
    assert set(finals) == {0, 1}, outs[0][-1500:]
    # Bit-identical to the uninterrupted (and disk-restore) runs.
    assert finals[0] == expected, (finals, expected)
    assert finals[1] == expected
    for r in (0, 1):
        line = [ln for ln in outs[r].splitlines() if "FINAL=" in ln][0]
        # The whole recovery was disk-free: zero payload reads.
        assert "reads=0" in line, line
        assert "size=2" in line, line
    # The job really rewound through the replica: the post-reconfig step 3
    # completed exactly once, after the membership-change notice.
    assert outs[0].count("STEP 3 rank=0") == 1, outs[0][-2500:]
    assert outs[0].index("Membership changed") \
        < outs[0].index("STEP 3 rank=0"), outs[0][-2500:]


def test_elastic_loop_survives_coordinator_kill_bit_exact(tmp_path):
    """The PR-7 acceptance scenario: 3 ranks in ``training.elastic_loop``
    with manifest-committed checkpoints; the COORDINATOR (rank 0) is
    SIGKILLed at step 3.  The standby (rank 1) promotes itself to rank 0
    on its pre-announced port, the survivors shrink to size 2 in place —
    same pids, no process restart — and resume from the step-2 checkpoint
    with final parameters bit-identical to an uninterrupted run's."""
    steps = 6
    expected = str([float(sum(s + 1 for s in range(steps)))] * 4)
    ckpt = tmp_path / "coord_kill"
    ckpt.mkdir()
    port = _free_port()
    procs = []
    for r in range(3):
        env = {**os.environ, "PYTHONPATH": REPO, **FAST_HB,
               "JAX_PROCESS_ID": str(r),
               "HVD_TPU_FAULT_KILL_RANK": "0",
               "HVD_TPU_FAULT_KILL_STEP": "3"}
        procs.append(subprocess.Popen(
            [sys.executable, "-c", ELASTIC_TRAIN, str(r), str(port), "3",
             str(ckpt), str(steps)],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
            env=env, cwd=REPO))
    outs = _drain(procs, timeout=scaled(240))
    assert procs[0].returncode != 0  # the killed coordinator
    assert procs[1].returncode == 0, outs[1][-2500:]
    assert procs[2].returncode == 0, outs[2][-2500:]
    finals = _finals(outs)
    assert set(finals) == {1, 2}, outs[1][-1500:]
    # Bit-identical resumption from the step-2 checkpoint.
    assert finals[1] == expected, (finals, expected)
    assert finals[2] == expected
    # The standby really was promoted (not a full restart): succession
    # notice on both survivors, same pid before/after, shrunken size 2.
    for r in (1, 2):
        assert "promoting standby rank 1" in outs[r], outs[r][-2500:]
        line = [ln for ln in outs[r].splitlines() if "FINAL=" in ln][0]
        pid = line.split("pid=", 1)[1].split()[0]
        now = line.split("now=", 1)[1].split()[0]
        assert pid == now, line
        assert "size=2" in line, line
    # The job rewound to the checkpoint: step 3 completed exactly once on
    # each survivor, AFTER the membership-change notice.
    for r in (1, 2):
        assert outs[r].count(f"STEP 3 rank={r}") == 1, outs[r][-2500:]
        assert outs[r].index("Membership changed") \
            < outs[r].index(f"STEP 3 rank={r}"), outs[r][-2500:]


# Rejoin end to end through the launcher: engine-only children, injected
# SIGKILL, single-rank relaunch with HVD_TPU_ELASTIC_JOIN=1.
LAUNCHED_ELASTIC = textwrap.dedent("""
    import os, sys, time
    import numpy as np
    from horovod_tpu.core.engine import NativeEngine, OP_ALLREDUCE, \\
        CollectiveError, MembershipChanged
    from horovod_tpu.core import engine as em
    from horovod_tpu.core.executors import local_executor
    from horovod_tpu import elastic, faults

    rank = int(os.environ["JAX_PROCESS_ID"])
    n = int(os.environ["JAX_NUM_PROCESSES"])
    port = int(os.environ["HVD_TPU_COORDINATOR_PORT"])
    if os.environ.get("HVD_TPU_ELASTIC_JOIN") == "1":
        t = elastic.join("127.0.0.1", port, old_rank=rank,
                         timeout_s=float(os.environ.get(
                             "HVD_TPU_CONNECT_TIMEOUT", "60")))
        print(f"RANK{rank} TICKET epoch={t.epoch} size={t.new_size} "
              f"as={t.assigned_rank}", flush=True)
        # The coordinator may have MOVED (standby promotion) since this
        # seat died: rendezvous at the published endpoint, not the env's.
        host, cport = elastic.coordinator_endpoint("127.0.0.1", port)
        eng = NativeEngine(t.assigned_rank, t.new_size,
                           executor=local_executor,
                           coordinator_host=host,
                           coordinator_port=cport, cycle_time_ms=2.0,
                           epoch=t.epoch)
        i = t.epoch * 1000
    else:
        eng = NativeEngine(rank, n, executor=local_executor,
                           coordinator_host="127.0.0.1",
                           coordinator_port=port, cycle_time_ms=2.0)
        i = 0
    elastic.attach(eng)
    # Run until the whole job is back at full size AND a common milestone
    # is reached — the epoch resynchronizes the name counter after every
    # reconfiguration, so all members count in lockstep.
    while True:
        try:
            faults.step(i, rank=eng.rank if eng.size == n else -1)
            h = eng.enqueue(f"s{i}", np.ones(8, np.float32), OP_ALLREDUCE)
            eng.synchronize(h, timeout_s=120.0)
            i += 1
            if eng.size == n and eng.epoch >= 2 and i >= eng.epoch * 1000 + 20:
                print(f"RANK{rank} DONE size={eng.size} as={eng.rank} "
                      f"epoch={eng.epoch}", flush=True)
                break
            time.sleep(0.05)
        except MembershipChanged:
            ev = elastic.reconfigure()
            eng = em.peek_engine()
            i = ev.epoch * 1000
            print(f"RANK{rank} RECONFIGURED epoch={ev.epoch} "
                  f"size={ev.new_size}", flush=True)
        except CollectiveError as e:
            print(f"RANK{rank} ABORTED {e}", flush=True)
            time.sleep(30)
            sys.exit(3)
    eng.shutdown()
""")


def test_launcher_relaunches_single_rank_which_rejoins():
    """Grow path end to end: ``--elastic`` supervision SIGKILLs rank 2 via
    the fault injector, relaunches ONLY rank 2 (survivors keep running,
    shrunk), the relaunch JOINs and the job returns to size 3 — exit 0,
    with the rejoin accounted separately from full restarts in the
    supervisor summary."""
    env = {**os.environ, "PYTHONPATH": REPO, **FAST_HB,
           "HVD_TPU_RESTART_BACKOFF": "0.1",
           "HVD_TPU_FAULT_KILL_RANK": "2",
           "HVD_TPU_FAULT_KILL_STEP": "10"}
    res = subprocess.run(
        [sys.executable, "-m", "horovod_tpu.run", "-np", "3", "--elastic",
         "--platform", "", "--max-restarts", "2", "--",
         sys.executable, "-c", LAUNCHED_ELASTIC],
        cwd=REPO, capture_output=True, text=True, timeout=scaled(180),
        env=env)
    assert res.returncode == 0, res.stdout[-4000:] + res.stderr[-2000:]
    assert "killing rank 2 at step 10" in res.stdout, res.stdout[-4000:]
    # Survivors shrank in place (no full-job teardown)...
    assert "RANK0 RECONFIGURED epoch=1 size=2" in res.stdout, \
        res.stdout[-4000:]
    assert "relaunching only rank 2" in res.stderr, res.stderr[-2000:]
    # ... the relaunched rank was admitted with a JOIN ticket ...
    assert "RANK2 TICKET epoch=2 size=3 as=2" in res.stdout, \
        res.stdout[-4000:]
    # ... and every member finished at full size.
    for r in range(3):
        assert f"RANK{r} DONE size=3" in res.stdout, res.stdout[-4000:]
    # Accounting: one single-rank relaunch, zero full-job restarts.
    assert "supervisor summary: full_restarts=0 single_rank_relaunches=1" \
        in res.stderr, res.stderr[-2000:]
    assert "restarting (attempt" not in res.stderr, res.stderr[-2000:]


def test_launcher_relaunches_coordinator_seat_after_failover():
    """Coordinator failover end to end through the launcher: the fault
    injector SIGKILLs rank 0; the standby promotes in-job (survivors keep
    running, shrunk); the launcher relaunches ONLY the dead seat, which
    JOINs the promoted coordinator via the HVD_TPU_COORD_FILE endpoint —
    the job returns to full size without a full restart."""
    env = {**os.environ, "PYTHONPATH": REPO, **FAST_HB,
           "HVD_TPU_RESTART_BACKOFF": "0.1",
           "HVD_TPU_FAULT_KILL_RANK": "0",
           "HVD_TPU_FAULT_KILL_STEP": "10"}
    res = subprocess.run(
        [sys.executable, "-m", "horovod_tpu.run", "-np", "3", "--elastic",
         "--platform", "", "--max-restarts", "2", "--",
         sys.executable, "-c", LAUNCHED_ELASTIC],
        cwd=REPO, capture_output=True, text=True, timeout=scaled(180),
        env=env)
    assert res.returncode == 0, res.stdout[-4000:] + res.stderr[-2000:]
    assert "killing rank 0 at step 10" in res.stdout, res.stdout[-4000:]
    # The standby promoted and the survivors shrank in place...
    assert "promoting standby rank 1" in res.stdout, res.stdout[-4000:]
    assert "RANK1 RECONFIGURED epoch=1 size=2" in res.stdout, \
        res.stdout[-4000:]
    assert "relaunching only rank 0" in res.stderr, res.stderr[-2000:]
    # ... the dead seat was admitted by the PROMOTED coordinator ...
    assert "RANK0 TICKET epoch=2 size=3 as=2" in res.stdout, \
        res.stdout[-4000:]
    # ... and every member finished at full size.
    for r in range(3):
        assert f"RANK{r} DONE size=3" in res.stdout, res.stdout[-4000:]
    assert "supervisor summary: full_restarts=0 single_rank_relaunches=1" \
        in res.stderr, res.stderr[-2000:]
    assert "restarting (attempt" not in res.stderr, res.stderr[-2000:]


# Two-stage succession: a worker death (epoch 1) followed by the
# coordinator's death (epoch 2) under the SAME processes, plus a raw
# stale-straggler probe against the promoted coordinator's listener.
# argv: rank port nprocs
SUCCESSION_WORKER = textwrap.dedent("""
    import os, socket, struct, sys, time, zlib
    import numpy as np
    from horovod_tpu.core.engine import NativeEngine, OP_ALLREDUCE, \\
        CollectiveError, MembershipChanged
    from horovod_tpu.core import engine as em
    from horovod_tpu.core.executors import local_executor
    from horovod_tpu import elastic

    rank, port, n = int(sys.argv[1]), int(sys.argv[2]), int(sys.argv[3])
    eng = NativeEngine(rank, n, executor=local_executor,
                       coordinator_host="127.0.0.1", coordinator_port=port,
                       cycle_time_ms=2.0)
    elastic.attach(eng)
    i = 0
    while True:
        try:
            h = eng.enqueue(f"s{i}", np.ones(8, np.float32), OP_ALLREDUCE)
            eng.synchronize(h, timeout_s=120.0)
            i += 1
            if i == 5:
                print(f"RANK{rank} STEADY pid={os.getpid()}", flush=True)
            if eng.epoch >= 2 and i >= 2005:
                print(f"RANK{rank} DONE rank={eng.rank} size={eng.size} "
                      f"epoch={eng.epoch}", flush=True)
                break
            time.sleep(0.02)
        except MembershipChanged:
            ev = elastic.reconfigure()
            eng = em.peek_engine()
            i = ev.epoch * 1000
            print(f"RANK{rank} RECONFIGURED epoch={ev.epoch} "
                  f"new_rank={ev.new_rank} new_size={ev.new_size}",
                  flush=True)
            if ev.new_rank == 0 and ev.epoch >= 2 and ev.new_coord_port:
                # Stale-straggler probe: replay an epoch-0 HELLO (the frame
                # a pre-succession worker would send) at the PROMOTED
                # coordinator's endpoint.  The join listener must drop the
                # connection — EOF, no ticket, no wedge — and the epoch-2
                # plane below must keep working.
                payload = struct.pack("<ii", 5, 0)
                hdr = struct.pack("<IBBHII", 0x48564446, 1, 1, 0,
                                  len(payload),
                                  zlib.crc32(payload) & 0xFFFFFFFF)
                s = socket.create_connection(
                    ("127.0.0.1", ev.new_coord_port), timeout=10.0)
                s.sendall(hdr + payload)
                s.settimeout(10.0)
                try:
                    data = s.recv(64)
                except socket.timeout:
                    data = b"TIMEOUT"
                except OSError:
                    data = b""  # RST: dropped even more emphatically
                s.close()
                print(f"RANK{rank} STALE_PROBE dropped="
                      f"{data == b''}", flush=True)
        except CollectiveError as e:
            print(f"RANK{rank} ABORTED {e}", flush=True)
            time.sleep(30)
            sys.exit(3)
    eng.shutdown()
""")


def _read_until(proc, needle, deadline):
    lines = []
    for line in proc.stdout:
        lines.append(line)
        if needle in line:
            return lines
        assert time.monotonic() < deadline, "".join(lines[-30:])
    raise AssertionError("stream ended early:\n" + "".join(lines[-30:]))


def test_succession_epochs_are_monotonic_and_stale_frames_rejected():
    """Two successive failures under the SAME 4 processes: a worker death
    bumps the epoch to 1, then the coordinator's death bumps it to 2 with
    a standby promotion — proving the epoch is monotonic ACROSS a
    succession, every frame re-stamps, and a straggler replaying its
    epoch-0 HELLO at the promoted endpoint is dropped on the floor while
    the epoch-2 plane keeps running."""
    procs, _ = _spawn(SUCCESSION_WORKER, 4, {})
    try:
        deadline = time.monotonic() + scaled(120)
        heads = [_wait_steady(p, deadline) for p in procs]
        procs[3].kill()  # stage 1: tail worker dies -> plain shrink
        mid = _read_until(procs[1], "RECONFIGURED epoch=1", deadline)
        time.sleep(scaled(1.0))  # let the epoch-1 plane settle everywhere
        procs[0].kill()  # stage 2: the coordinator dies -> promotion
        outs = _drain(procs, timeout=scaled(120))
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
    full = ["".join(h) + o for h, o in zip(heads, outs)]
    full[1] = "".join(heads[1]) + "".join(mid) + outs[1]
    assert procs[1].returncode == 0, (procs[1].returncode, full[1][-2500:])
    assert procs[2].returncode == 0, (procs[2].returncode, full[2][-2500:])
    # Stage 1: identity remap (the dead rank was the tail), size 3.
    assert "RANK1 RECONFIGURED epoch=1 new_rank=1 new_size=3" in full[1], \
        full[1][-2500:]
    assert "RANK2 RECONFIGURED epoch=1 new_rank=2 new_size=3" in full[2], \
        full[2][-2500:]
    # Stage 2: the epoch-1 standby (rank 1) takes the coordinator seat.
    assert "promoting standby rank 1" in full[1], full[1][-2500:]
    assert "RANK1 RECONFIGURED epoch=2 new_rank=0 new_size=2" in full[1], \
        full[1][-2500:]
    assert "RANK2 RECONFIGURED epoch=2 new_rank=1 new_size=2" in full[2], \
        full[2][-2500:]
    # The straggler's stale HELLO was dropped (EOF, no ticket)...
    assert "RANK1 STALE_PROBE dropped=True" in full[1], full[1][-2500:]
    # ...and did not disturb the promoted plane: DONE comes after it.
    assert "RANK1 DONE rank=0 size=2 epoch=2" in full[1], full[1][-2500:]
    assert "RANK2 DONE rank=1 size=2 epoch=2" in full[2], full[2][-2500:]
    assert full[1].index("RECONFIGURED epoch=1") \
        < full[1].index("RECONFIGURED epoch=2") \
        < full[1].index("STALE_PROBE") < full[1].index("DONE"), \
        full[1][-2500:]


@pytest.mark.slow
@pytest.mark.parametrize(
    "fault", ["KILL", "DROP", "PARTITION", "HALFCLOSE", "CORRUPT"])
def test_coordinator_chaos_soak_shrinks_or_aborts_never_hangs(fault):
    """Chaos soak, coordinator-targeted: every PR-4 wire injector (plus
    SIGKILL) aimed at rank 0 of 3 with HVD_TPU_MIN_SIZE=2.  Outcome matrix
    (faults.py "Coordinator-targeted plans"): at least two processes
    promote/shrink to a working size-2 job and exit 0; a split-brain loser
    (the isolated ex-coordinator, or the one worker a CORRUPT verdict
    stranded) takes a structured nonzero exit bounded by the reconfig
    budget.  Nobody EVER hangs — the drain deadline is the assertion.
    Stress-loop with HVD_TPU_SOAK_REPS>1 (make ci runs 3)."""
    reps = int(os.environ.get("HVD_TPU_SOAK_REPS", "1"))
    for rep in range(reps):
        extra = {"HVD_TPU_MIN_SIZE": "2",
                 # Bound the split-brain loser's doomed re-form attempt.
                 "HVD_TPU_RECONFIG_TIMEOUT_MS": str(int(scaled(8000)))}
        if fault != "KILL":
            extra[f"HVD_TPU_FAULT_WIRE_{fault}"] = "0:30"
        procs, _ = _spawn(ELASTIC_WORKER, 3, extra, args=(60,))
        heads = [[] for _ in procs]
        try:
            if fault == "KILL":
                deadline = time.monotonic() + scaled(60)
                heads = [_wait_steady(p, deadline) for p in procs]
                procs[0].kill()
            outs = _drain(procs, timeout=scaled(90))  # never-hang bound
        finally:
            for p in procs:
                if p.poll() is None:
                    p.kill()
        full = ["".join(h) + o for h, o in zip(heads, outs)]
        winners = [r for r in range(3)
                   if procs[r].returncode == 0 and f"RANK{r} DONE" in full[r]]
        assert len(winners) >= 2, (
            fault, rep, [(p.returncode, f[-1200:])
                         for p, f in zip(procs, full)])
        for r in winners:
            # Winners finished on a real post-shrink plane of exactly the
            # two survivors (MIN_SIZE floor respected).
            assert "size=2" in full[r].split(f"RANK{r} DONE", 1)[1], full[r]
            assert f"RANK{r} RECONFIGURED epoch=1" in full[r], \
                full[r][-1200:]
        # The loser (if any) exited too — with a code, not a hang.
        for r in range(3):
            assert procs[r].returncode is not None


# TSAN: reconfiguration racing client threads and shutdown.
TSAN_ELASTIC = textwrap.dedent("""
    import sys, threading, time
    import numpy as np
    from horovod_tpu.core.engine import NativeEngine, OP_ALLREDUCE, \\
        CollectiveError, MembershipChanged
    from horovod_tpu.core import engine as em
    from horovod_tpu.core.executors import local_executor
    from horovod_tpu import elastic

    rank, port, n = int(sys.argv[1]), int(sys.argv[2]), int(sys.argv[3])
    eng = NativeEngine(rank, n, executor=local_executor,
                       coordinator_host="127.0.0.1", coordinator_port=port,
                       cycle_time_ms=1.0)
    elastic.attach(eng)
    resized = threading.Event()
    stop = threading.Event()

    def pound(tid):
        i = 0
        while not stop.is_set() and i < 200:
            try:
                e = em.peek_engine()
                h = e.enqueue(f"t{tid}.{i}", np.ones(16, np.float32),
                              OP_ALLREDUCE)
                e.synchronize(h, timeout_s=60.0)
            except MembershipChanged:
                resized.set()
                return
            except (CollectiveError, RuntimeError, TimeoutError):
                stop.set()
                return
            i += 1

    threads = [threading.Thread(target=pound, args=(t,)) for t in range(2)]
    for t in threads: t.start()
    if rank == 1:
        time.sleep(0.5)
        import os, signal
        os.kill(os.getpid(), signal.SIGKILL)
    # Rank 0: wait for the resize signal, reconfigure (to size 1 —
    # loopback) while the pound threads drain, then immediately shut the
    # fresh engine down: reconfigure vs client threads vs teardown.
    assert resized.wait(timeout=120), "no resize observed"
    ev = elastic.reconfigure()
    stop.set()
    for t in threads: t.join()
    e = em.peek_engine()
    h = e.enqueue("post.reconfig", np.ones(4, np.float32), OP_ALLREDUCE)
    e.synchronize(h, timeout_s=60.0)
    e.shutdown()
    print(f"RANK{rank} OK epoch={ev.epoch}", flush=True)
""")


@pytest.mark.tsan
@pytest.mark.slow
def test_concurrent_reconfigure_and_shutdown_under_tsan():
    """ThreadSanitizer leg (make check): a real peer death triggering the
    elastic RECONFIG path while client threads pound enqueues, followed by
    an immediate post-reconfigure collective and teardown.  No data-race
    report may implicate libhvdcore."""
    core = os.path.join(REPO, "horovod_tpu", "core")
    rc = subprocess.run(["make", "-C", core, "tsan", "-j4"],
                        capture_output=True)
    if rc.returncode != 0 and not os.path.exists(
            os.path.join(core, "libhvdcore_tsan.so")):
        pytest.skip("tsan build unavailable")
    runtime = tsan_runtime()
    if runtime is None:
        pytest.skip("libtsan runtime not installed")
    port = _free_port()
    env = {**os.environ, "PYTHONPATH": REPO, **FAST_HB,
           # TSAN is ~10x slower: only injected deaths may fire, and the
           # reconfig hand-off needs real slack.
           "HVD_TPU_HEARTBEAT_TIMEOUT_MS": str(int(scaled(8000))),
           "HVD_TPU_ABORT_GRACE_MS": "5000",
           "HVD_TPU_RECONFIG_TIMEOUT_MS": str(int(scaled(60000))),
           "HVD_CORE_LIB": "libhvdcore_tsan.so",
           "LD_PRELOAD": runtime,
           "TSAN_OPTIONS": "report_bugs=1 halt_on_error=0 exitcode=0"}
    procs = [
        subprocess.Popen(
            [sys.executable, "-c", TSAN_ELASTIC, str(r), str(port), "2"],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
            env=env, cwd=REPO)
        for r in range(2)
    ]
    outs = []
    for p in procs:
        try:
            outs.append(p.communicate(timeout=scaled(240)))
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            raise
    assert "RANK0 OK epoch=1" in outs[0][0], (outs[0][0][-2000:],
                                              outs[0][1][-3000:])
    for r, (out, err) in enumerate(outs):
        for chunk in err.split("WARNING: ThreadSanitizer")[1:]:
            assert "hvdcore" not in chunk.split("=" * 18)[0], (
                f"tsan race in libhvdcore on rank {r}:\n{chunk[:4000]}")


# TSAN: standby PROMOTION racing client threads and immediate teardown.
# The promotion path is the racy part of failover — CloseListener, the
# standby port re-bind, the monitor thread's verdict synthesis, and the
# replicated-state swap all overlap with application enqueues.
TSAN_FAILOVER = textwrap.dedent("""
    import sys, threading, time
    import numpy as np
    from horovod_tpu.core.engine import NativeEngine, OP_ALLREDUCE, \\
        CollectiveError, MembershipChanged
    from horovod_tpu.core import engine as em
    from horovod_tpu.core.executors import local_executor
    from horovod_tpu import elastic

    rank, port, n = int(sys.argv[1]), int(sys.argv[2]), int(sys.argv[3])
    eng = NativeEngine(rank, n, executor=local_executor,
                       coordinator_host="127.0.0.1", coordinator_port=port,
                       cycle_time_ms=1.0)
    elastic.attach(eng)
    resized = threading.Event()
    stop = threading.Event()

    def pound(tid):
        i = 0
        while not stop.is_set() and i < 200:
            try:
                e = em.peek_engine()
                h = e.enqueue(f"t{tid}.{i}", np.ones(16, np.float32),
                              OP_ALLREDUCE)
                e.synchronize(h, timeout_s=60.0)
            except MembershipChanged:
                resized.set()
                return
            except (CollectiveError, RuntimeError, TimeoutError):
                stop.set()
                return
            i += 1

    threads = [threading.Thread(target=pound, args=(t,)) for t in range(2)]
    for t in threads: t.start()
    if rank == 0:
        time.sleep(0.5)
        import os, signal
        os.kill(os.getpid(), signal.SIGKILL)
    # Survivors: the standby (rank 1) PROMOTES while its pound threads are
    # still draining against the dead plane, then tears the fresh engine
    # down right after one proving collective — promotion vs clients vs
    # shutdown, the three-way race the succession path must survive.
    assert resized.wait(timeout=120), "no resize observed"
    ev = elastic.reconfigure()
    stop.set()
    for t in threads: t.join()
    e = em.peek_engine()
    h = e.enqueue("post.promote", np.ones(4, np.float32), OP_ALLREDUCE)
    e.synchronize(h, timeout_s=60.0)
    e.shutdown()
    print(f"RANK{rank} OK epoch={ev.epoch} as={ev.new_rank}", flush=True)
""")


@pytest.mark.tsan
@pytest.mark.slow
def test_concurrent_promotion_and_shutdown_under_tsan():
    """ThreadSanitizer leg (make check): the COORDINATOR dies while client
    threads pound enqueues on both survivors; the standby promotes itself
    (port re-bind + verdict synthesis + replicated-state swap) racing
    those threads, runs one post-promotion collective, and shuts down
    immediately.  No data-race report may implicate libhvdcore."""
    core = os.path.join(REPO, "horovod_tpu", "core")
    rc = subprocess.run(["make", "-C", core, "tsan", "-j4"],
                        capture_output=True)
    if rc.returncode != 0 and not os.path.exists(
            os.path.join(core, "libhvdcore_tsan.so")):
        pytest.skip("tsan build unavailable")
    runtime = tsan_runtime()
    if runtime is None:
        pytest.skip("libtsan runtime not installed")
    port = _free_port()
    env = {**os.environ, "PYTHONPATH": REPO, **FAST_HB,
           "HVD_TPU_HEARTBEAT_TIMEOUT_MS": str(int(scaled(8000))),
           "HVD_TPU_ABORT_GRACE_MS": "5000",
           "HVD_TPU_RECONFIG_TIMEOUT_MS": str(int(scaled(60000))),
           "HVD_CORE_LIB": "libhvdcore_tsan.so",
           "LD_PRELOAD": runtime,
           "TSAN_OPTIONS": "report_bugs=1 halt_on_error=0 exitcode=0"}
    procs = [
        subprocess.Popen(
            [sys.executable, "-c", TSAN_FAILOVER, str(r), str(port), "3"],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
            env=env, cwd=REPO)
        for r in range(3)
    ]
    outs = []
    for p in procs:
        try:
            outs.append(p.communicate(timeout=scaled(300)))
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            raise
    assert "RANK1 OK epoch=1 as=0" in outs[1][0], (outs[1][0][-2000:],
                                                   outs[1][1][-3000:])
    assert "RANK2 OK epoch=1 as=1" in outs[2][0], (outs[2][0][-2000:],
                                                   outs[2][1][-3000:])
    for r, (out, err) in enumerate(outs):
        for chunk in err.split("WARNING: ThreadSanitizer")[1:]:
            assert "hvdcore" not in chunk.split("=" * 18)[0], (
                f"tsan race in libhvdcore on rank {r}:\n{chunk[:4000]}")


# ---------------------------------------------------------------------------
# Checkpoint chaos soak: the persist-path injectors (torn manifest, ENOSPC,
# slow disk) and the two kill drills, each driven through the REAL
# training.elastic_loop with async persist + peer replication + the
# bounded-staleness backpressure knob all on at once.


CKPT_SOAK_TRAIN = textwrap.dedent("""
    import os, sys
    import numpy as np
    from horovod_tpu.core.engine import NativeEngine, OP_ALLREDUCE
    from horovod_tpu.core import engine as em
    from horovod_tpu.core.executors import local_executor
    from horovod_tpu import checkpoint, elastic, training
    from horovod_tpu.utils import manifest

    rank, port, n = int(sys.argv[1]), int(sys.argv[2]), int(sys.argv[3])
    ckpt_dir, steps = sys.argv[4], int(sys.argv[5])
    eng = NativeEngine(rank, n, executor=local_executor,
                       coordinator_host="127.0.0.1", coordinator_port=port,
                       cycle_time_ms=2.0)
    elastic.attach(eng)
    mgr = checkpoint.CheckpointManager(ckpt_dir, max_to_keep=2, rank=rank,
                                       size=1)

    @elastic.on_reconfigure
    def _regate(ev):
        # The disk-writer seat follows ENGINE rank 0 across failovers:
        # after a coordinator death the promoted standby must take over
        # persist duty or the job silently stops checkpointing.
        mgr._rank_override = ev.new_rank

    def step_fn(step, state):
        e = em.peek_engine()
        h = e.enqueue(f"soak.g{step}",
                      np.full(4, float(step + 1), np.float32), OP_ALLREDUCE)
        g = e.synchronize(h, timeout_s=120.0)
        return {"params": state["params"] + g}

    state = {"params": np.zeros(4, np.float32)}
    state = training.elastic_loop(step_fn, state, num_steps=steps,
                                  manager=mgr, checkpoint_every=1)
    err = mgr.persist_error()
    complete = manifest.complete_steps(ckpt_dir)
    print(f"[rank {rank}] SOAK FINAL={state['params'].tolist()} "
          f"newest={max(complete) if complete else -1} "
          f"size={em.peek_engine().size} "
          f"perr={type(err).__name__ if err else 'None'}", flush=True)
    em.peek_engine().shutdown()
""")


_SOAK_MODES = [
    ("torn-manifest", {"HVD_TPU_FAULT_TORN_MANIFEST_STEP": "2"}),
    ("enospc", {"HVD_TPU_FAULT_ENOSPC_STEP": "2"}),
    ("slow-disk", {"HVD_TPU_FAULT_SLOW_DISK_MS": "200"}),
    ("kill-worker", {"HVD_TPU_FAULT_KILL_RANK": "2",
                     "HVD_TPU_FAULT_KILL_STEP": "3"}),
    ("kill-coordinator", {"HVD_TPU_FAULT_KILL_RANK": "0",
                          "HVD_TPU_FAULT_KILL_STEP": "3"}),
]


@pytest.mark.slow
def test_checkpoint_chaos_soak_bounded_staleness_never_hangs(tmp_path):
    """The persist path under fire (HVD_TPU_SOAK_REPS rounds of torn
    manifest / ENOSPC / slow disk / worker kill / coordinator kill), all
    with async persist + peer replication + HVD_TPU_CKPT_STALENESS_STEPS
    backpressure on.  Three invariants, per ISSUE acceptance:

    * never hangs — _drain's timeout kills and fails the round;
    * survivors always finish rc=0 with the bit-exact uninterrupted
      final state (kill rounds rewind through the replica and replay);
    * the newest COMPLETE checkpoint is never more than the staleness
      bound behind the last trained step — a torn or ENOSPC'd commit
      leaves that one step invisible, it never poisons the ones after.
    """
    reps = int(os.environ.get("HVD_TPU_SOAK_REPS", "1"))
    steps, bound = 6, 2
    expected = str([float(sum(s + 1 for s in range(steps)))] * 4)
    for rep in range(reps):
        for name, fault in _SOAK_MODES:
            ckpt = tmp_path / f"{name}-{rep}"
            ckpt.mkdir()
            port = _free_port()
            procs = []
            for r in range(3):
                env = {**os.environ, "PYTHONPATH": REPO, **FAST_HB,
                       "JAX_PROCESS_ID": str(r),
                       "HVD_TPU_CKPT_REPLICATE": "1",
                       "HVD_TPU_CKPT_ASYNC": "1",
                       "HVD_TPU_CKPT_STALENESS_STEPS": str(bound),
                       **fault}
                procs.append(subprocess.Popen(
                    [sys.executable, "-c", CKPT_SOAK_TRAIN, str(r),
                     str(port), "3", str(ckpt), str(steps)],
                    stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
                    text=True, env=env, cwd=REPO))
            outs = _drain(procs, timeout=scaled(240))
            killed = int(fault.get("HVD_TPU_FAULT_KILL_RANK", "-1"))
            for r in range(3):
                ctx = (name, rep, r, outs[r][-2500:])
                if r == killed:
                    assert procs[r].returncode != 0, ctx
                    continue
                assert procs[r].returncode == 0, ctx
                line = [ln for ln in outs[r].splitlines()
                        if "SOAK FINAL=" in ln][0]
                assert f"FINAL={expected}" in line, ctx
                newest = int(line.split("newest=")[1].split()[0])
                assert newest >= steps - 1 - bound, ctx


# ---------------------------------------------------------------------------
# Peer-replication concurrency under ThreadSanitizer: a dedicated thread
# hammers the SHARD_PUT path while the main thread runs collectives and
# drains the shard inbox — the exact contention the async persist thread
# creates in production.


TSAN_SHARD = textwrap.dedent("""
    import sys, threading
    import numpy as np
    from horovod_tpu.core.engine import NativeEngine, OP_ALLREDUCE
    from horovod_tpu.core.executors import local_executor
    from horovod_tpu import replication

    rank, port, n = int(sys.argv[1]), int(sys.argv[2]), int(sys.argv[3])
    eng = NativeEngine(rank, n, executor=local_executor,
                       coordinator_host="127.0.0.1", coordinator_port=port,
                       cycle_time_ms=2.0)
    stop = threading.Event()

    def putter():
        step = 0
        while not stop.is_set() and step < 400:
            replication.put(step, {"w": np.full(64, float(step),
                                                np.float32)}, {}, eng=eng)
            step += 1

    t = threading.Thread(target=putter, daemon=True)
    t.start()
    for i in range(40):
        h = eng.enqueue(f"ts.{i}", np.ones(32, np.float32), OP_ALLREDUCE)
        eng.synchronize(h, timeout_s=120.0)
        replication.drain(eng)
    stop.set()
    t.join()
    replication.drain(eng)
    s = replication.stats()
    assert s["puts"] > 0 and s["drained"] > 0, s
    print(f"RANK{rank} SHARD OK puts={s['puts']} "
          f"drained={s['drained']}", flush=True)
    eng.shutdown()
""")


@pytest.mark.tsan
@pytest.mark.slow
def test_shard_replication_concurrency_under_tsan():
    """SHARD_PUT/SHARD_ACK under ThreadSanitizer: the replication putter
    thread races the collective cycle thread and the drain loop on the
    native shard inbox.  No data-race report may implicate libhvdcore."""
    core = os.path.join(REPO, "horovod_tpu", "core")
    rc = subprocess.run(["make", "-C", core, "tsan", "-j4"],
                        capture_output=True)
    if rc.returncode != 0 and not os.path.exists(
            os.path.join(core, "libhvdcore_tsan.so")):
        pytest.skip("tsan build unavailable")
    runtime = tsan_runtime()
    if runtime is None:
        pytest.skip("libtsan runtime not installed")
    port = _free_port()
    env = {**os.environ, "PYTHONPATH": REPO, **FAST_HB,
           "HVD_TPU_HEARTBEAT_TIMEOUT_MS": str(int(scaled(8000))),
           "HVD_TPU_ABORT_GRACE_MS": "5000",
           "HVD_CORE_LIB": "libhvdcore_tsan.so",
           "LD_PRELOAD": runtime,
           "TSAN_OPTIONS": "report_bugs=1 halt_on_error=0 exitcode=0"}
    procs = [
        subprocess.Popen(
            [sys.executable, "-c", TSAN_SHARD, str(r), str(port), "2"],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
            env=env, cwd=REPO)
        for r in range(2)
    ]
    outs = []
    for p in procs:
        try:
            outs.append(p.communicate(timeout=scaled(300)))
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            raise
    for r, (out, err) in enumerate(outs):
        assert f"RANK{r} SHARD OK" in out, (out[-2000:], err[-3000:])
        for chunk in err.split("WARNING: ThreadSanitizer")[1:]:
            assert "hvdcore" not in chunk.split("=" * 18)[0], (
                f"tsan race in libhvdcore on rank {r}:\n{chunk[:4000]}")
