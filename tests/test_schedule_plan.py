"""Unit + lowering tests for the trace-time overlap schedule planner
(ops/schedule_plan.py).

The planner's contract, pinned here:

* width-1 bypass (chaining where psum is identity only constrains the
  scheduler);
* headroom-deficit degradation — the 468M config's 79 MB OOM must turn
  into a shallower chain (or free-combining fallback);
* one owner: no environment name changes the plan, and ``planner=`` is the
  seam through which a test forces a depth;
* plan stability: the same manifest/width/headroom always produces the
  same plan, across repeated traces.
"""

import numpy as np
import pytest

from horovod_tpu.ops import schedule_plan as sp
from horovod_tpu.utils import env


@pytest.fixture(autouse=True)
def _fresh_planner_state(monkeypatch):
    # The headroom must come from THIS test's env, not the shell's;
    # the probe cache and dedup log reset so tests stay order-independent.
    monkeypatch.delenv("HOROVOD_DEVICE_HEADROOM_MB", raising=False)
    monkeypatch.delenv("HVD_TPU_DEVICE_HEADROOM_MB", raising=False)
    sp._reset_for_tests()
    yield
    sp._reset_for_tests()


def manifest(count=18, bytes_per=2 * 1024 * 1024):
    return sp.GradientManifest(nbytes=(bytes_per,) * count,
                               dtypes=("float32",) * count)


# ---------------------------------------------------------------------------
# AdaptivePlanner policy
# ---------------------------------------------------------------------------

def test_width1_bypasses_chain():
    plan = sp.AdaptivePlanner().plan(manifest(), width=1, headroom_mb=None)
    assert plan.chain_depth == 0 and not plan.chained
    assert "width-1" in plan.reason
    # Bypass even with infinite headroom — width, not memory, is the
    # reason there is nothing to overlap.
    plan = sp.AdaptivePlanner().plan(manifest(), width=1, headroom_mb=1e9)
    assert not plan.chained


def test_real_width_slack_headroom_keeps_default_depth():
    plan = sp.AdaptivePlanner().plan(manifest(), width=8,
                                     headroom_mb=8000.0)
    assert plan.chain_depth == sp.DEFAULT_CHAIN_DEPTH and plan.chained


def test_unknown_headroom_keeps_default_depth():
    plan = sp.AdaptivePlanner().plan(manifest(), width=8, headroom_mb=None)
    assert plan.chain_depth == sp.DEFAULT_CHAIN_DEPTH and plan.chained


def test_headroom_deficit_degrades_depth_then_bypasses():
    # The 468M shape: ~936 MB of bf16 gradients.  The depth-4 chain's
    # estimated extra live-range (~88 MB — calibrated to the measured
    # 79 MB OOM, see CHAIN_LIVE_FRACTION) exceeds an 80 MB headroom, so
    # the planner halves the depth; a tiny headroom kills the chain.
    m = sp.GradientManifest(
        nbytes=(936 * 1024 * 1024 // 20,) * 20, dtypes=("bfloat16",) * 20)
    assert sp.chain_extra_bytes(m.total_bytes, 4) > 80 * 1024 * 1024
    degraded = sp.AdaptivePlanner().plan(m, width=16, headroom_mb=80.0)
    assert 1 < degraded.chain_depth < sp.DEFAULT_CHAIN_DEPTH
    assert sp.chain_extra_bytes(m.total_bytes, degraded.chain_depth) \
        <= 80 * 1024 * 1024
    assert "degraded" in degraded.reason
    dead = sp.AdaptivePlanner().plan(m, width=16, headroom_mb=10.0)
    assert dead.chain_depth == 0 and not dead.chained
    assert "free-combining" in dead.reason


def test_chain_extra_bytes_monotone_and_zero_without_chain():
    total = 936 * 1024 * 1024
    estimates = [sp.chain_extra_bytes(total, d) for d in (8, 4, 2, 1, 0)]
    assert estimates == sorted(estimates, reverse=True)
    assert estimates[-2:] == [0, 0]  # depth <= 1: no chain, no bill


def test_single_tensor_never_chains():
    plan = sp.AdaptivePlanner().plan(manifest(count=1), width=8,
                                     headroom_mb=None)
    assert not plan.chained and plan.chain_depth == 0


# ---------------------------------------------------------------------------
# Width 1: which gradients are materialised before the update
# ---------------------------------------------------------------------------

def decoder_manifest(layers=6, hidden=2048, mlp=5504, vocab=32256):
    """The benchmark's decoder in tree order: f32 matrices of 16.8-264 MB
    and norm scales of 8 KB (benchmarks/configs/deepseek-coder-1.3b.json)."""
    mat, scale = lambda a, b: a * b * 4, hidden * 4
    layer = [scale, mat(hidden, hidden), mat(hidden, hidden),
             mat(hidden, hidden), mat(hidden, hidden), scale,
             mat(hidden, mlp), mat(hidden, mlp), mat(mlp, hidden)]
    nbytes = [mat(vocab, hidden), scale] + layer * layers \
        + [mat(hidden, vocab)]
    return sp.GradientManifest(nbytes=tuple(nbytes),
                               dtypes=("float32",) * len(nbytes))


# ResNet-50 v1.5's 161 f32 leaves as {bytes: how many}
# (models/resnet.ResNet50, 1000 classes): 102.2 MB, none over 9.5 MB.
RESNET50_LEAVES = {
    256: 14, 512: 16, 1024: 32, 2048: 22, 4000: 1, 4096: 14, 8192: 8,
    16384: 1, 37632: 1, 65536: 6, 131072: 1, 147456: 3, 262144: 7,
    524288: 2, 589824: 4, 1048576: 11, 2097152: 2, 2359296: 6, 4194304: 5,
    8192000: 1, 8388608: 1, 9437184: 3}


def resnet_manifest():
    nbytes = [n for n, k in RESNET50_LEAVES.items() for _ in range(k)]
    assert len(nbytes) == 161 and sum(nbytes) == 102228128
    return sp.GradientManifest(nbytes=tuple(nbytes),
                               dtypes=("float32",) * len(nbytes))


def test_width1_materialises_the_decoders_matrices_not_its_norm_scales():
    m = decoder_manifest()
    plan = sp.AdaptivePlanner().plan(m, width=1, headroom_mb=None)
    matrices = tuple(i for i, n in enumerate(m.nbytes) if n > 8192)
    assert len(matrices) == 44 and m.count == 57
    assert plan.materialized == matrices
    assert plan.materialized_bytes == sum(m.nbytes[i] for i in matrices)
    assert plan.chain_depth == 0 and not plan.chained
    assert "width-1" in plan.reason and "44 of 57" in plan.reason


def test_width1_materialises_no_resnet_leaf():
    # the measured rule (PERF.md, PR 25): below MATERIALIZE_MIN_BYTES the
    # fused update is the cheaper form -- every ResNet-50 leaf read slower
    # materialised on the chip -- so the plan is the bare bypass, as before
    m = resnet_manifest()
    assert max(m.nbytes) < sp.MATERIALIZE_MIN_BYTES
    plan = sp.AdaptivePlanner().plan(m, width=1, headroom_mb=None)
    assert plan.materialized == () and plan.materialized_bytes == 0
    assert plan.chain_depth == 0 and "width-1" in plan.reason
    assert "0 of 161" in plan.reason


def test_width1_floor_is_inclusive_and_reads_bytes_alone():
    floor = sp.MATERIALIZE_MIN_BYTES
    m = sp.GradientManifest(nbytes=(floor - 1, floor, floor + 1, 0),
                            dtypes=("float32", "bfloat16", "float32",
                                    "int32"))
    assert sp.materialized_leaves(m) == (1, 2)


def test_width1_plan_carries_the_counts_in_its_record():
    m = decoder_manifest()
    d = sp.AdaptivePlanner().plan(m, width=1, headroom_mb=None).as_dict()
    assert d["materialized_leaves"] == 44
    assert d["materialized_bytes"] == m.total_bytes - 13 * 8192
    assert "materialized" not in d      # the count, not 44 indices a line


def test_real_width_materialises_nothing():
    # the all-reduce already stands between a gradient and its update
    for m in (decoder_manifest(), resnet_manifest()):
        for headroom in (None, 8000.0, 10.0):
            plan = sp.AdaptivePlanner().plan(m, width=4,
                                             headroom_mb=headroom)
            assert plan.materialized == () and plan.materialized_bytes == 0
            assert plan.as_dict()["materialized_leaves"] == 0


def test_materialised_set_is_deterministic():
    for m in (decoder_manifest(), resnet_manifest()):
        plans = [sp.AdaptivePlanner().plan(m, width=1, headroom_mb=h)
                 for h in (None, 1e9, 0.0)]
        assert len({p.materialized for p in plans}) == 1
        assert sp.materialized_leaves(m) == plans[0].materialized


# ---------------------------------------------------------------------------
# One owner: the environment cannot change the plan; planner= is the seam
# ---------------------------------------------------------------------------

# The two names that, until PR 29, swapped the planner for a static one.
RETIRED_NAMES = ["HOROVOD_OVERLAP_BUCKETS", "HVD_TPU_OVERLAP_BUCKETS"]


@pytest.mark.parametrize("value", ["0", "4", "junk"])
@pytest.mark.parametrize("name", RETIRED_NAMES)
def test_bucket_names_in_the_environment_are_read_by_nothing(
        monkeypatch, name, value):
    # Set to anything, either name made the plan static: chained at width
    # 1, materialised nothing.  Now they are two strings nobody reads: the
    # same plan as with nothing set, and no warning.
    import warnings

    t = [np.zeros((8, 8), np.float32)] * 12
    unset = {w: sp.plan_overlap(t, width=w) for w in (1, 8)}
    monkeypatch.setenv(name, value)
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        for w in (1, 8):
            plan = sp.plan_overlap(t, width=w)
            assert plan == unset[w] and plan.planner == "adaptive"
    assert not unset[1].chained
    assert unset[8].chain_depth == sp.DEFAULT_CHAIN_DEPTH


@pytest.mark.parametrize("name", RETIRED_NAMES)
def test_width1_step_lowers_the_same_with_a_bucket_name_set(
        monkeypatch, name):
    # A width-1 DistributedOptimizer step with one gradient large enough to
    # be materialised: the lowered program and the plan's record are the
    # same with the name set to 0 (at the parent: static planner, no
    # barrier, another program).
    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import Mesh, PartitionSpec as P

    import horovod_tpu as hvd

    hvd.init()
    opt = hvd.DistributedOptimizer(optax.adamw(1e-3))

    def step(params, opt_state, x):
        def loss(p):
            return jnp.mean((jnp.tanh(x @ p["big"]) @ p["small"]) ** 2)

        u, opt_state = opt.update(jax.grad(loss)(params), opt_state, params)
        return optax.apply_updates(params, u), opt_state

    params = {"big": jax.ShapeDtypeStruct((2048, 2048), jnp.float32),
              "small": jax.ShapeDtypeStruct((2048, 8), jnp.float32)}
    assert 2048 * 2048 * 4 >= sp.MATERIALIZE_MIN_BYTES
    mesh = Mesh(np.asarray(jax.devices()[:1]), ("hvd",))
    sharded = jax.jit(jax.shard_map(
        step, mesh=mesh, in_specs=(P(), P(), P("hvd")),
        out_specs=(P(), P()), check_vma=False))
    args = (params, jax.eval_shape(opt.init, params),
            jax.ShapeDtypeStruct((4, 2048), jnp.float32))

    def lowered():
        text = sharded.lower(*args).as_text()
        return text, hvd.overlap_plan()

    text, plan = lowered()
    monkeypatch.setenv(name, "0")
    text_set, plan_set = lowered()
    assert text_set == text
    assert plan["planner"] == plan_set["planner"] == "adaptive"
    assert plan["materialized_leaves"] == plan_set["materialized_leaves"] == 1
    assert "optimization_barrier" in text


@pytest.mark.parametrize("depth,chain,gates", [
    (0, 0, 0), (1, 0, 0), (2, 2, 1), (3, 3, 2), (8, 8, 7)])
def test_default_depth_through_the_one_planner(depth, chain, gates):
    # A depth of N forced through ``planner=``: at real
    # width with the headroom unknown, ``AdaptivePlanner(default_depth=N)``
    # chains N buckets (none for N <= 1), and the lowered program carries
    # one gate between consecutive buckets.
    import horovod_tpu as hvd

    hvd.init()
    from examples.overlap_audit import audit_cpu_sim

    audit = audit_cpu_sim(planner=sp.AdaptivePlanner(default_depth=depth))
    plan = audit["plan"]
    assert plan["planner"] == "adaptive" and plan["width"] == 8, plan
    assert plan["headroom_mb"] is None and plan["tensor_count"] >= 9, plan
    assert plan["chain_depth"] == chain, plan
    assert audit["gate_is_finite_ops"] == gates, audit


def test_custom_planner_instance_wins():
    # the seam is duck-typed: anything with plan(manifest, width, headroom)
    class Fixed3:
        def plan(self, m, width, headroom_mb):
            return sp.BucketPlan(
                planner="fixed3", chain_depth=3, width=width,
                tensor_count=m.count, total_bytes=m.total_bytes,
                headroom_mb=headroom_mb, chain_extra_bytes=0,
                reason="test planner")

    t = [np.zeros((8, 8), np.float32)] * 4
    plan = sp.plan_overlap(t, width=8, planner=Fixed3())
    assert plan.planner == "fixed3" and plan.chain_depth == 3


# ---------------------------------------------------------------------------
# Stability + observability
# ---------------------------------------------------------------------------

def test_plan_stable_across_repeated_traces():
    t = [np.zeros((64, 64), np.float32)] * 8
    plans = [sp.plan_overlap(t, width=8) for _ in range(3)]
    assert plans[0] == plans[1] == plans[2]
    import horovod_tpu as hvd

    last = hvd.overlap_plan()
    assert last == plans[-1].as_dict()
    assert last["chained"] and last["planner"] == "adaptive"


def test_overlap_plan_none_before_any_decision():
    import horovod_tpu as hvd

    assert hvd.overlap_plan() is None


def test_headroom_env_override_wins_and_is_deterministic(monkeypatch):
    monkeypatch.setenv("HVD_TPU_DEVICE_HEADROOM_MB", "50")
    assert sp.probe_headroom_mb() == 50.0
    assert env.device_headroom_mb() == 50.0
    monkeypatch.setenv("HVD_TPU_DEVICE_HEADROOM_MB", "-5")
    assert sp.probe_headroom_mb() == 0.0  # negative clamps to "none left"


def test_headroom_env_malformed_warns_and_probes(monkeypatch):
    import warnings

    monkeypatch.setenv("HVD_TPU_DEVICE_HEADROOM_MB", "lots")
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        assert env.device_headroom_mb() is None
    assert any("HVD_TPU_DEVICE_HEADROOM_MB" in str(w.message)
               for w in caught)


def test_probe_result_is_cached_per_process(monkeypatch):
    # Plan stability across retraces requires one probe answer per
    # process — not a live value that drifts as buffers come and go.
    first = sp.probe_headroom_mb()
    assert sp.probe_headroom_mb() == first
    assert sp._probe_cache == [first]


# ---------------------------------------------------------------------------
# Lowering integration: headroom deficit reshapes the compiled program
# ---------------------------------------------------------------------------

def test_simulated_headroom_deficit_degrades_lowered_chain(monkeypatch):
    # A simulated deficit (HVD_TPU_DEVICE_HEADROOM_MB) makes the planner
    # degrade chain depth in the ACTUAL lowered program.  The audit model
    # carries ~33.6 MB of gradients -> depth-4 chain bill ≈ 3.01 MB,
    # depth-2 ≈ 2.0 MB: a 3 MB headroom forces exactly one halving.
    import horovod_tpu as hvd

    hvd.init()
    monkeypatch.setenv("HVD_TPU_DEVICE_HEADROOM_MB", "3")
    from examples.overlap_audit import audit_cpu_sim

    audit = audit_cpu_sim()
    plan = audit["plan"]
    assert plan["planner"] == "adaptive", plan
    assert plan["chain_depth"] == 2, plan
    assert plan["headroom_mb"] == 3.0, plan
    # depth 2 -> exactly one inter-bucket gate survives in the stablehlo.
    assert audit["gate_is_finite_ops"] == 1, audit


def test_distributed_optimizer_planner_kwarg_rejected_with_zero1():
    import optax

    import horovod_tpu as hvd

    with pytest.raises(ValueError, match="planner"):
        hvd.DistributedOptimizer(optax.sgd(0.01), sharded_state=True,
                                 planner=sp.AdaptivePlanner())
