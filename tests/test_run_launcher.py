"""The ``python -m horovod_tpu.run`` launcher (mpirun -np analog).

Covers the two contracts mpirun gives the reference's users (reference
README.md:148-180): (1) N ranks come up wired together — a cross-process
eager allreduce produces the job-wide sum on every rank; (2) the first
abnormal rank exit aborts the whole job with that exit code instead of
leaving surviving ranks hung.
"""

import os
import subprocess
import sys
import textwrap

import pytest

from _timing import scaled

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

OK_SCRIPT = textwrap.dedent("""
    import numpy as np
    import horovod_tpu as hvd
    hvd.init()
    h = hvd.allreduce_async(np.full(3, float(hvd.rank() + 1), np.float32),
                            average=False, name="launch.ar")
    out = hvd.synchronize(h)
    expect = hvd.size() * (hvd.size() + 1) / 2
    np.testing.assert_allclose(out, np.full(3, expect))
    print(f"RANK{hvd.rank()} SUM={out[0]:.0f}", flush=True)
""")

CRASH_SCRIPT = textwrap.dedent("""
    import sys, time
    import horovod_tpu as hvd
    hvd.init()
    if hvd.rank() == 1:
        sys.exit(7)
    time.sleep(600)   # must be terminated by the launcher, not run out
""")

# Rank 1 leaves on an uncaught exception, its last words in a logging
# handler that holds them until it is flushed.
RAISE_SCRIPT = textwrap.dedent("""
    import logging, logging.handlers, sys, time
    import horovod_tpu as hvd
    hvd.init()
    if hvd.rank() == 1:
        held = logging.handlers.MemoryHandler(
            100, target=logging.StreamHandler(sys.stdout))
        logging.getLogger("rank").addHandler(held)
        logging.getLogger("rank").warning("LAST WORDS")
        raise RuntimeError("rank 1 is gone")
    time.sleep(600)   # must be terminated by the launcher, not run out
""")

# A ``sys.exit`` that was caught (a CLI's, argparse's), here and in a
# thread, is no exit: both ranks then end cleanly, rank 1 at once and rank 0
# two seconds later, and rank 1 waits for it at the job's shutdown barrier
# (jax's exit handler, registered after this script's: it has run when
# LEFT is printed).
CLEAN_SCRIPT = textwrap.dedent("""
    import atexit, sys, threading, time
    atexit.register(lambda: print(f"LEFT {time.time()}", flush=True))
    import horovod_tpu as hvd
    hvd.init()
    try:
        sys.exit(3)
    except SystemExit:
        pass
    worker = threading.Thread(target=sys.exit, args=(5,))
    worker.start()
    worker.join()
    if hvd.rank() == 0:
        time.sleep(2)
    print(f"DONE {time.time()}", flush=True)
""")


def _launch(np_, script, timeout):
    return subprocess.run(
        [sys.executable, "-m", "horovod_tpu.run", "-np", str(np_),
         sys.executable, "-c", script],
        cwd=REPO, capture_output=True, text=True, timeout=timeout)


def test_two_ranks_allreduce_with_tagged_output():
    res = _launch(2, OK_SCRIPT, timeout=scaled(180))
    assert res.returncode == 0, res.stdout + res.stderr
    for rank in (0, 1):
        assert f"[{rank}]: RANK{rank} SUM=3" in res.stdout, res.stdout


def test_crashed_rank_aborts_job_with_its_exit_code():
    res = _launch(2, CRASH_SCRIPT, timeout=scaled(180))
    assert res.returncode == 7, res.stdout + res.stderr
    assert "rank 1 exited with code 7" in res.stderr


def test_a_rank_s_uncaught_exception_aborts_the_job_with_its_last_words():
    res = _launch(2, RAISE_SCRIPT, timeout=scaled(180))
    assert res.returncode == 1, res.stdout + res.stderr
    assert "rank 1 exited with code 1" in res.stderr
    assert "[1]: RuntimeError: rank 1 is gone" in res.stdout, res.stdout
    assert "[1]: LAST WORDS" in res.stdout, res.stdout


def test_a_caught_exit_is_none_and_a_clean_rank_waits_for_its_peer():
    res = _launch(2, CLEAN_SCRIPT, timeout=scaled(180))
    assert res.returncode == 0, res.stdout + res.stderr
    said = {(rank, word): float(line.split()[2])
            for line in res.stdout.splitlines()
            for rank, word in [(line[1], line.split()[1])]
            if word in ("DONE", "LEFT")}
    assert set(said) == {(r, w) for r in "01" for w in ("DONE", "LEFT")}
    assert said["0", "DONE"] >= said["1", "DONE"] + 1.5
    # rank 1 was still there when rank 0 came to the barrier
    assert said["1", "LEFT"] >= said["0", "DONE"]


def test_the_exit_wrapper_goes_on_once_and_a_caught_exit_notes_nothing(
        monkeypatch):
    import gc

    from horovod_tpu import basics

    plain = sys.exit
    monkeypatch.setattr(sys, "exit", plain)
    monkeypatch.setattr(basics, "_sys_exit", None)
    monkeypatch.setattr(basics, "_exit_status", None)
    for name in ("last_exc", "last_value"):     # a failed test's, earlier
        monkeypatch.delattr(sys, name, raising=False)
    basics._note_exit_status()
    once = sys.exit
    basics._note_exit_status()      # init -> shutdown -> init
    assert sys.exit is once is not plain and basics._sys_exit is plain
    with pytest.raises(SystemExit) as caught:
        sys.exit(3)
    assert caught.value.code == 3
    del caught
    gc.collect()
    assert basics._exit_status is None and basics._crash_code() == 0


def test_rejects_hosts_flag():
    res = subprocess.run(
        [sys.executable, "-m", "horovod_tpu.run", "-np", "2", "-H",
         "a:1,b:1", "true"],
        cwd=REPO, capture_output=True, text=True, timeout=scaled(60))
    assert res.returncode != 0
    assert "pod runtime" in res.stderr


# ---------------------------------------------------------------------------
# Supervision: restarts, crash-loop breaker, process-group signal forwarding
# (docs/fault_tolerance.md).  Children are jax-free so these stay cheap.
# ---------------------------------------------------------------------------

# Fails on the first attempt, succeeds after the supervisor relaunches —
# HVD_TPU_RESTART_ATTEMPT is the launcher-exported attempt counter.
FLAKY_SCRIPT = textwrap.dedent("""
    import os, sys
    attempt = int(os.environ.get("HVD_TPU_RESTART_ATTEMPT", "0"))
    print(f"ATTEMPT={attempt}", flush=True)
    sys.exit(7 if attempt == 0 else 0)
""")

ALWAYS_FAIL_SCRIPT = "import sys; sys.exit(9)"

# Spawns a grandchild, reports its pid, then lingers: SIGTERM to the
# launcher must reap the WHOLE process group, grandchild included.
GRANDCHILD_SCRIPT = textwrap.dedent("""
    import subprocess, sys, time
    p = subprocess.Popen([sys.executable, "-c", "import time; time.sleep(300)"])
    print(f"GRANDCHILD={p.pid}", flush=True)
    for _ in range(1200):
        time.sleep(0.25)
""")


def _supervised(np_, script, *flags, timeout):
    env = {**os.environ, "HVD_TPU_RESTART_BACKOFF": "0.05"}
    return subprocess.run(
        [sys.executable, "-m", "horovod_tpu.run", "-np", str(np_), *flags,
         "--", sys.executable, "-c", script],
        cwd=REPO, capture_output=True, text=True, timeout=timeout, env=env)


# Rank 1 is the originator (exit 7); every other rank lingers and is
# SIGTERM'd by the launcher's job-abort (rc -15 → 143).  Secondary exits
# must never mask the originator in supervision accounting.
ORIGINATOR_SCRIPT = textwrap.dedent("""
    import os, sys, time
    rank = int(os.environ["JAX_PROCESS_ID"])
    attempt = int(os.environ.get("HVD_TPU_RESTART_ATTEMPT", "0"))
    if rank == 1 and attempt == 0:
        time.sleep(0.3)   # let the peers reach their sleep first
        sys.exit(7)
    if attempt > 0:
        sys.exit(0)       # relaunched job runs clean
    time.sleep(120)       # terminated by the launcher, not run out
""")


def test_secondary_sigterm_exits_never_mask_originator():
    """Supervision/restart accounting keys off the ORIGINATING abnormal
    exit: ranks the launcher SIGTERMs afterwards (rc -15 → 143) ride along
    in the same teardown and must not become the recorded job exit code —
    neither in the restart log line nor in the budget-exhausted final
    code."""
    res = _supervised(3, ORIGINATOR_SCRIPT, "--max-restarts", "1",
                      timeout=scaled(60))
    assert res.returncode == 0, res.stdout + res.stderr
    assert "rank 1 exited with code 7" in res.stderr, res.stderr
    # The restart accounting recorded the originator's 7, not a
    # secondary's 143.
    assert "job failed with exit code 7" in res.stderr, res.stderr
    assert "exit code 143" not in res.stderr, res.stderr

    # Without restart budget the job's own exit code is the originator's.
    res = _supervised(3, ORIGINATOR_SCRIPT, timeout=scaled(60))
    assert res.returncode == 7, res.stdout + res.stderr


def test_restart_recovers_flaky_job():
    res = _supervised(2, FLAKY_SCRIPT, "--max-restarts", "2",
                      timeout=scaled(60))
    assert res.returncode == 0, res.stdout + res.stderr
    assert "ATTEMPT=0" in res.stdout and "ATTEMPT=1" in res.stdout
    assert "restarting (attempt 1" in res.stderr, res.stderr


def test_restart_budget_exhausts_with_original_code():
    res = _supervised(1, ALWAYS_FAIL_SCRIPT, "--max-restarts", "1",
                      timeout=scaled(60))
    assert res.returncode == 9, res.stdout + res.stderr
    assert "restart budget exhausted" in res.stderr, res.stderr
    # Exactly one restart was attempted before giving up.
    assert res.stderr.count("restarting (attempt") == 1, res.stderr


def test_no_restart_by_default():
    res = _supervised(1, ALWAYS_FAIL_SCRIPT, timeout=scaled(60))
    assert res.returncode == 9
    assert "restarting" not in res.stderr


# Elastic supervision accounting (docs/fault_tolerance.md "In-place
# recovery"): rank 1 dies on its founding launch but succeeds as a JOIN
# relaunch; the other ranks linger long enough to stay "alive" while the
# single-rank relaunch happens, then exit clean.
ELASTIC_ACCOUNTING_SCRIPT = textwrap.dedent("""
    import os, sys, time
    rank = int(os.environ["JAX_PROCESS_ID"])
    joined = os.environ.get("HVD_TPU_ELASTIC_JOIN") == "1"
    if rank == 1 and not joined:
        time.sleep(0.3)
        sys.exit(75)          # the expelled/aborted-rank exit
    if rank == 1 and joined:
        print("REJOINED attempt="
              + os.environ.get("HVD_TPU_RESTART_ATTEMPT", "?"), flush=True)
        sys.exit(0)
    time.sleep(2.0)           # survivors keep running through the rejoin
    sys.exit(0)
""")


def test_elastic_single_rank_relaunch_accounting_and_breaker_reset():
    """--elastic supervision: a dead non-coordinator rank is relaunched
    ALONE with HVD_TPU_ELASTIC_JOIN=1 (survivors keep running — no job
    teardown, no full restart), the relaunch gets a fresh attempt counter
    so step-keyed injectors stay disarmed, and the supervisor summary
    accounts it separately from full-job restarts."""
    res = _supervised(3, ELASTIC_ACCOUNTING_SCRIPT, "--elastic",
                      "--max-restarts", "1", timeout=scaled(60))
    assert res.returncode == 0, res.stdout + res.stderr
    assert "elastic mode: relaunching only rank 1" in res.stderr, res.stderr
    # The relaunched incarnation carries a bumped attempt counter (faults
    # keyed to attempt 0 must not re-fire inside the rejoin).
    assert "REJOINED attempt=1" in res.stdout, res.stdout
    # Separate accounting: one single-rank relaunch, zero full restarts —
    # and no mpirun-style job abort was triggered.
    assert "supervisor summary: full_restarts=0 single_rank_relaunches=1" \
        in res.stderr, res.stderr
    assert "terminating remaining ranks" not in res.stderr, res.stderr
    assert "restarting (attempt" not in res.stderr, res.stderr


def test_elastic_rank0_death_still_aborts_job():
    """Coordinator failover (PR 7): rank 0 dying under --elastic no longer
    aborts the job — the standby promotes, the dead seat is relaunched
    alone as a joiner, and the supervisor accounts it as a single-rank
    relaunch rather than an mpirun-style full restart.  (Pre-PR-7 this
    test asserted the job-abort + full-restart contract.)"""
    script = textwrap.dedent("""
        import os, sys, time
        rank = int(os.environ["JAX_PROCESS_ID"])
        joined = os.environ.get("HVD_TPU_ELASTIC_JOIN") == "1"
        if rank == 0 and not joined:
            time.sleep(0.3)
            sys.exit(75)
        if rank == 0 and joined:
            print("COORD_SEAT_REJOINED attempt="
                  + os.environ.get("HVD_TPU_RESTART_ATTEMPT", "?"), flush=True)
            sys.exit(0)
        time.sleep(2.0)           # survivor keeps running through failover
        sys.exit(0)
    """)
    res = _supervised(2, script, "--elastic", "--max-restarts", "1",
                      timeout=scaled(60))
    assert res.returncode == 0, res.stdout + res.stderr
    # The job survives: rank 0's seat comes back alone, no job teardown.
    assert "elastic mode: relaunching only rank 0" in res.stderr, res.stderr
    assert "COORD_SEAT_REJOINED attempt=1" in res.stdout, res.stdout
    assert "supervisor summary: full_restarts=0 single_rank_relaunches=1" \
        in res.stderr, res.stderr
    assert "restarting (attempt" not in res.stderr, res.stderr


def test_sigterm_reaps_grandchildren():
    import signal
    import time

    env = {**os.environ}
    p = subprocess.Popen(
        [sys.executable, "-m", "horovod_tpu.run", "-np", "1", "--",
         sys.executable, "-c", GRANDCHILD_SCRIPT],
        cwd=REPO, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        text=True, env=env)
    try:
        gpid = None
        deadline = time.monotonic() + scaled(30)
        for line in p.stdout:
            if "GRANDCHILD=" in line:
                gpid = int(line.rsplit("=", 1)[1])
                break
            assert time.monotonic() < deadline, "no grandchild line"
        assert gpid is not None
        p.send_signal(signal.SIGTERM)
        p.wait(timeout=scaled(30))
        # The grandchild must be gone: SIGTERM was forwarded to the whole
        # process group (os.killpg), so a preempted supervisor cannot
        # orphan worker subprocesses.
        deadline = time.monotonic() + scaled(10)
        while time.monotonic() < deadline:
            try:
                os.kill(gpid, 0)
            except ProcessLookupError:
                break
            time.sleep(0.1)
        else:
            os.kill(gpid, 9)
            raise AssertionError(f"grandchild {gpid} survived the drain")
    finally:
        if p.poll() is None:
            p.kill()
