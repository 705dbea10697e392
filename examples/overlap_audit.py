"""Audit: does XLA overlap the gradient AllReduce with backward compute?

An early scaling projection listed comm/compute
overlap inside the jitted step as a structural reason realized efficiency
lands above the zero-overlap column.  This harness MEASURES that claim
instead of assuming it, by compiling a real ``DistributedOptimizer`` step
for a multi-chip target and inspecting the scheduled HLO:

* per-bucket ``psum`` calls are issued in backward order (the reference's
  hook-in-backward motivation, reference torch/__init__.py:83-112);
* we then count what survives compilation: how many all-reduce ops the
  backend's combiner left, whether any are async pairs
  (``all-reduce-start``/``all-reduce-done``), and where they sit relative
  to backward compute in the schedule.

Run on a machine with the TPU plugin for the deviceless v5e:2x4 AOT audit
(no chips needed — topology compile only), anywhere for the CPU-sim mesh:

    python examples/overlap_audit.py            # both targets if available

Measured results:

* Round 4 (free-combining psums, default flags): the combiner merges
  every gradient bucket into ONE synchronous tuple all-reduce scheduled
  after all backward compute — zero HLO-level overlap, on both the TPU
  (v5e:2x4, RotatedPincer ring emitter) and CPU backends.
* Round 5 (this harness): chaining the
  bucket psums (collective_ops._chained_allreduce, now the
  DistributedOptimizer default) makes them uncombinable, and the
  schedule interleaves them with backward — 16 of 17 surviving
  all-reduces sit BEFORE the last backward fusion at default flags;
  ``hvd.overlap_compiler_options()`` adds explicit async start/done
  pairs and continuation fusions on top.  The flag-only
  and chain-only cells of the matrix do NOT overlap (flags alone leave
  one post-backward AR; the chain alone stays synchronous), and
  ``optimization_barrier`` chaining is stripped by the TPU pipeline —
  the arithmetic gate is load-bearing.  The scaling projection keeps its
  zero-overlap column as the conservative floor.
* Round 9: the chain is no longer unconditional — a trace-time schedule
  planner (ops/schedule_plan.py) decides per program.  This harness now
  audits BOTH planner branches: :func:`audit_cpu_sim` lowers at the sim
  mesh's real width (the chain engages, ``gate_is_finite_ops`` > 0) and
  :func:`audit_cpu_sim_width1` lowers the same step on a 1-device mesh
  (the adaptive planner bypasses the chain — zero gates, the round-4
  free-combining structure).  ``--assert-planner`` runs both and exits
  nonzero on any regression (wired into ``make ci``).

Each audit dict carries ``plan`` — the ``hvd.overlap_plan()`` decision
recorded while the step traced — and ``gate_is_finite_ops``, the count of
the chain's own ``is_finite`` gate ops in the lowered stablehlo.  The gate
is traced under the ``CHAIN_GATE_SCOPE`` name scope, and only ops whose
location carries that scope are counted: the loss's logsumexp emits an
``is_finite`` too, which a plain text count mistook for a gate.
"""

from __future__ import annotations

import json
import re
import sys


def build_step(planner=None):
    """``planner`` forces a chain depth for a comparison
    (``AdaptivePlanner(default_depth=0)`` is the unchained program); None
    audits what ships."""
    import jax
    import jax.numpy as jnp
    import optax
    import flax.linen as nn

    import horovod_tpu as hvd

    class MLP(nn.Module):
        @nn.compact
        def __call__(self, x):
            for i in range(8):
                x = nn.Dense(1024, name=f"d{i}", dtype=jnp.bfloat16)(x)
                x = nn.relu(x)
            return nn.Dense(10, name="out", dtype=jnp.bfloat16)(x)

    model = MLP()
    # In-mesh the optimizer emits one psum per gradient tensor (XLA's
    # combiner owns batching), each issued as soon as its gradient exists
    # (backward order) — the structure that WOULD overlap if the backend
    # kept the collectives separate.
    opt = hvd.DistributedOptimizer(optax.sgd(0.01), planner=planner)

    def step(params, opt_state, x, y):
        def loss_fn(p):
            logits = model.apply(p, x).astype(jnp.float32)
            return optax.softmax_cross_entropy_with_integer_labels(
                logits, y).mean()

        g = jax.grad(loss_fn)(params)
        u, opt_state = opt.update(g, opt_state, params)
        return optax.apply_updates(params, u), opt_state

    return model, opt, step


def lowered_stats(lowered) -> dict:
    """Structural counts read off the lowered stablehlo: all-reduces, and
    the ``is_finite`` ops traced inside the bucket chain's gate scope."""
    from horovod_tpu.ops.collective_ops import CHAIN_GATE_SCOPE

    txt = lowered.as_text(debug_info=True)
    gate_locs = set(re.findall(
        rf'^(#loc\d+) = loc\("(?:[^"]*/)?{CHAIN_GATE_SCOPE}/is_finite"',
        txt, re.M))
    gates = sum(1 for m in re.finditer(
        r"stablehlo\.is_finite .* loc\((#loc\d+)\)", txt)
        if m.group(1) in gate_locs)
    return {"stablehlo_all_reduces": txt.count("stablehlo.all_reduce"),
            "gate_is_finite_ops": gates}


def audit_text(txt: str) -> dict:
    lines = txt.splitlines()
    ar = [i for i, l in enumerate(lines)
          if re.search(r"= .*all-reduce(\.|\()", l)]
    ar_start = [i for i, l in enumerate(lines) if "all-reduce-start" in l]
    bwd = [i for i, l in enumerate(lines) if "transpose(jvp" in l]
    return {
        "all_reduce_ops": len(ar),
        "async_pairs": len(ar_start),
        "first_all_reduce_line": ar[0] if ar else None,
        "last_backward_line": max(bwd) if bwd else None,
        "all_reduces_before_last_backward":
            sum(1 for i in ar if bwd and i < max(bwd)),
    }


def audit_cpu_sim(planner=None) -> dict:
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    import horovod_tpu as hvd

    hvd.init()
    model, opt, step = build_step(planner)
    x = jnp.zeros((16, 1024))
    y = jnp.zeros((16,), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), x)
    opt_state = opt.init(params)
    sharded = hvd.shard(step,
                        in_specs=(P(), P(), hvd.batch_spec(2),
                                  hvd.batch_spec(1)),
                        out_specs=(P(), P()))
    lowered = jax.jit(sharded).lower(params, opt_state, x, y)
    out = audit_text(lowered.compile().as_text())
    out.update(lowered_stats(lowered))
    out["plan"] = hvd.overlap_plan()
    return out


def audit_cpu_sim_width1() -> dict:
    """The same step lowered over a ONE-device mesh: data width 1, where
    ``psum`` is identity — the adaptive planner must bypass the chain
    (zero ``is_finite`` gates, the round-4 free-combining structure) so
    single-chip runs stop paying for overlap that cannot exist (the r5
    −4.3% ResNet headline regression this planner retires)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, PartitionSpec as P

    import horovod_tpu as hvd

    hvd.init()
    model, opt, step = build_step()
    mesh = Mesh(np.asarray(jax.devices()[:1]), ("hvd",))
    sharded = jax.shard_map(step, mesh=mesh,
                            in_specs=(P(), P(), P("hvd"), P("hvd")),
                            out_specs=(P(), P()), check_vma=False)
    x = jnp.zeros((16, 1024))
    y = jnp.zeros((16,), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), x)
    opt_state = opt.init(params)
    lowered = jax.jit(sharded).lower(params, opt_state, x, y)
    out = audit_text(lowered.compile().as_text())
    out.update(lowered_stats(lowered))
    out["plan"] = hvd.overlap_plan()
    return out


def audit_tpu_topology(topology: str = "v5e:2x4",
                       compiler_options: dict | None = None) -> dict:
    """Deviceless AOT compile for a multi-chip TPU topology — inspects the
    REAL TPU backend's scheduled module without needing the chips."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name=topology)
    mesh = Mesh(topo.devices, ("hvd",))
    model, opt, step = build_step()

    sharded = jax.shard_map(step, mesh=mesh,
                            in_specs=(P(), P(), P("hvd"), P("hvd")),
                            out_specs=(P(), P()), check_vma=False)

    pv = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                        jnp.zeros((1, 1024)))

    def repl(t):
        return jax.ShapeDtypeStruct(t.shape, t.dtype,
                                    sharding=NamedSharding(mesh, P()))

    ps = jax.tree.map(repl, pv)
    os_ = jax.tree.map(repl, jax.eval_shape(opt.init, pv))
    xs = jax.ShapeDtypeStruct((64, 1024), jnp.float32,
                              sharding=NamedSharding(mesh, P("hvd")))
    ys = jax.ShapeDtypeStruct((64,), jnp.int32,
                              sharding=NamedSharding(mesh, P("hvd")))
    lowered = jax.jit(sharded).lower(ps, os_, xs, ys)
    out = audit_text(lowered.compile().as_text()
                     if compiler_options is None else
                     lowered.compile(compiler_options=compiler_options)
                     .as_text())
    out.update(lowered_stats(lowered))
    import horovod_tpu as hvd

    out["plan"] = hvd.overlap_plan()
    out["topology"] = topology
    return out


def assert_planner() -> int:
    """CI gate (``make ci`` overlap-audit leg): lower BOTH planner
    branches on the CPU sim and fail loudly on any regression —

    * at the sim mesh's real width the adaptive default must keep the
      depth-4 chain (gates present, >= DEFAULT_CHAIN_DEPTH surviving
      all-reduces);
    * at width 1 it must bypass the chain entirely (zero gates — the
      free-combining structure, so single-chip runs never pay for it).

    Runs deviceless.
    """
    from horovod_tpu.ops.schedule_plan import DEFAULT_CHAIN_DEPTH

    wide = audit_cpu_sim()
    w1 = audit_cpu_sim_width1()
    failures = []
    plan_wide, plan_w1 = wide["plan"], w1["plan"]
    if not (plan_wide and plan_wide["chained"]
            and plan_wide["chain_depth"] == DEFAULT_CHAIN_DEPTH
            and plan_wide["planner"] == "adaptive"):
        failures.append(f"width>1 plan lost the default chain: {plan_wide}")
    if wide["gate_is_finite_ops"] == 0:
        failures.append("width>1 lowering carries no chain gates")
    if wide["all_reduce_ops"] < DEFAULT_CHAIN_DEPTH:
        failures.append(
            f"chained all-reduces merged: {wide['all_reduce_ops']} survive")
    if not (plan_w1 and not plan_w1["chained"]
            and plan_w1["chain_depth"] == 0
            and plan_w1["planner"] == "adaptive"):
        failures.append(f"width-1 plan failed to bypass the chain: {plan_w1}")
    if w1["gate_is_finite_ops"] != 0:
        failures.append(
            f"width-1 lowering still carries {w1['gate_is_finite_ops']} "
            f"chain gates — the r5 regression structure")
    print(json.dumps({"cpu_sim": wide, "cpu_sim_width1": w1,
                      "failures": failures}, indent=1))
    return 1 if failures else 0


def main():
    import os

    if "jax" not in sys.modules and "xla_force_host_platform_device_count" \
            not in os.environ.get("XLA_FLAGS", ""):
        # Standalone-script runs (the make ci overlap-audit leg) need a
        # multi-device CPU sim for the width>1 branch; under pytest the
        # conftest forces the same 8-device count.
        os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                                   + " --xla_force_host_platform_device_count=8")
    import jax

    if "--assert-planner" in sys.argv:
        return assert_planner()
    results = {}
    platform = jax.default_backend()
    if platform == "cpu":
        results["cpu_sim"] = audit_cpu_sim()
        results["cpu_sim_width1"] = audit_cpu_sim_width1()
    else:
        # The constant, not overlap_compiler_options(): the deviceless AOT
        # compile targets TPU regardless of this host's default backend,
        # and the audit must always measure the SHIPPED flag set.
        from horovod_tpu.ops.collective_ops import OVERLAP_XLA_OPTIONS

        try:
            results["tpu_topology"] = audit_tpu_topology()
            results["tpu_topology_async"] = audit_tpu_topology(
                compiler_options=dict(OVERLAP_XLA_OPTIONS))
        except Exception as e:  # topology compile unsupported here
            results["tpu_topology_error"] = f"{type(e).__name__}: {e}"
        results["cpu_sim"] = "run under JAX_PLATFORMS=cpu for the sim audit"
    print(json.dumps(results, indent=1))


if __name__ == "__main__":
    import os as _os

    # Script entry (make ci runs `python examples/overlap_audit.py`): put
    # the repo root ahead of the script dir so `import horovod_tpu` works
    # without an install.
    sys.path.insert(0, _os.path.dirname(_os.path.dirname(
        _os.path.abspath(__file__))))
    sys.exit(main())
