"""Audit: is the ring K/V rotation issued before the step's kernel?

The long-context round claimed the ring attention
steps hide their ICI transfer behind the flash kernel: each scan step
issues the ``ppermute`` for the NEXT step's K/V shard before calling this
step's kernel, so the transfer and the compute can run concurrently.  On
CPU sim meshes we cannot time that — instead this harness verifies the
STRUCTURE the claim depends on, straight from the traced jaxpr:

* **overlap** — every ring scan body (plain + zigzag, forward + backward)
  contains >= 2 ``ppermute`` eqns (K and V) that sit BEFORE the first
  kernel eqn and are not transitively data-dependent on any kernel output
  in the same step.  A serial implementation (kernel, then rotate what
  the kernel consumed) fails both conditions; a scheduler can only
  overlap what the dataflow leaves independent.  The backward scans also
  rotate dk/dv — those legitimately depend on the kernel and are NOT
  counted.  Each audited scan must run exactly ``ring_size - 1`` steps
  (the final step is unrolled outside the scan: its K/V needs no
  forwarding, so the n-th rotation the serial loop paid is gone).
* **step skipping** — on the plain causal layout, ring steps whose whole
  K block sits in the masked future are skipped exactly (the lse-merge
  identity): executed steps per rank must be ``rank + 1``, i.e. every
  rank but the last runs strictly fewer steps than the ring size.
* **planner** — ``plan_context`` (ops/schedule_plan.py) must pick zigzag
  for causal multi-shard work, keep its VMEM estimate inside the flash
  budget at S=8K *and* S=32K, and clamp a hand-pinned ``block_k=4096``
  (the tile that wins at S=8K but VMEM-OOMs at S=32K) back into budget.

``--assert-planner`` runs all three and exits nonzero on any regression
(the ``make ci`` longctx leg); the default mode prints the full JSON.
"""

from __future__ import annotations

import json
import sys


# --------------------------------------------------------------------------
# jaxpr traversal helpers


def _subjaxprs(eqn):
    """Yield every sub-jaxpr stored in an eqn's params (scan/cond/shard_map/
    custom_vjp/pallas all stash theirs under different keys and shapes)."""
    for v in eqn.params.values():
        for item in (v if isinstance(v, (tuple, list)) else (v,)):
            j = getattr(item, "jaxpr", item)
            if hasattr(j, "eqns"):
                yield j


def _find_scans(jaxpr, out):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "scan":
            out.append(eqn)
        for sub in _subjaxprs(eqn):
            _find_scans(sub, out)


def _contains_pallas(jaxpr) -> bool:
    for eqn in jaxpr.eqns:
        if "pallas" in eqn.primitive.name:
            return True
        if any(_contains_pallas(sub) for sub in _subjaxprs(eqn)):
            return True
    return False


def _is_kernel_eqn(eqn) -> bool:
    """The attention kernel shows up as a pallas_call — possibly wrapped in
    the causal-skip ``cond`` or a custom_vjp call — so: any eqn that
    transitively contains one."""
    if "pallas" in eqn.primitive.name:
        return True
    return any(_contains_pallas(sub) for sub in _subjaxprs(eqn))


def _depends_on_kernel(start_eqn, body) -> bool:
    """Is ``start_eqn`` transitively data-dependent on a kernel eqn's
    output within this scan body?  (BFS over invars -> producing eqns.)"""
    producer = {}
    for e in body.eqns:
        for ov in e.outvars:
            producer[id(ov)] = e
    seen = set()
    stack = list(start_eqn.invars)
    while stack:
        v = stack.pop()
        if hasattr(v, "val"):  # Literal
            continue
        e = producer.get(id(v))
        if e is None or id(e) in seen:
            continue
        seen.add(id(e))
        if _is_kernel_eqn(e):
            return True
        stack.extend(e.invars)
    return False


def _audit_scan(scan_eqn) -> dict | None:
    body = scan_eqn.params["jaxpr"].jaxpr
    kernel_idx = [i for i, e in enumerate(body.eqns) if _is_kernel_eqn(e)]
    pp_idx = [i for i, e in enumerate(body.eqns)
              if e.primitive.name == "ppermute"]
    if not kernel_idx or not pp_idx:
        return None  # not a ring scan (e.g. a training-loop scan)
    first_kernel = min(kernel_idx)
    prefetch = [i for i in pp_idx
                if i < first_kernel
                and not _depends_on_kernel(body.eqns[i], body)]
    return {
        "length": scan_eqn.params.get("length"),
        "ppermutes": len(pp_idx),
        "kernel_eqns": len(kernel_idx),
        "prefetch_ppermutes": len(prefetch),
    }


def _audit_traced(fn, *args) -> list[dict]:
    import jax

    scans: list = []
    _find_scans(jax.make_jaxpr(fn)(*args).jaxpr, scans)
    return [a for a in (map(_audit_scan, scans)) if a is not None]


# --------------------------------------------------------------------------
# the three audits


def audit_overlap() -> dict:
    """Trace plain + zigzag ring attention (forward and grad) over the sim
    mesh and audit every ring scan's body for the double-buffer structure.
    Kernel tiles come from the planner — nothing here is hand-set except
    the plain-causal layout the step-skip path needs pinned."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, PartitionSpec as P

    from horovod_tpu.parallel import (
        plan_long_context,
        ring_flash_attention,
        zigzag_ring_flash_attention,
    )

    n = jax.device_count()
    mesh = Mesh(np.array(jax.devices()), ("sp",))
    B, H, D = 1, 2, 8
    S = 16 * n
    zplan = plan_long_context(seq_len=S, num_heads=H, head_dim=D, width=n)
    pplan = plan_long_context(seq_len=S, num_heads=H, head_dim=D, width=n,
                              layout="plain")

    def plain(q, k, v):
        # The audit pins the plain causal layout on purpose: the step-skip
        # contract below is specific to it.  Production call sites go
        # through plan_context, which routes causal work to zigzag.
        return ring_flash_attention(  # hvd-lint: disable=HVD108
            q, k, v, "sp", True, pplan.block_q, pplan.block_k)

    def zigzag(q, k, v):
        return zigzag_ring_flash_attention(q, k, v, "sp", True,
                                           zplan.block_q, zplan.block_k)

    def sharded(f):
        return jax.shard_map(f, mesh=mesh, in_specs=(P(None, "sp"),) * 3,
                             out_specs=P(None, "sp"), check_vma=False)

    def grad_of(f):
        sm = sharded(f)
        return jax.grad(lambda q, k, v: sm(q, k, v).sum())

    rng = np.random.RandomState(0)
    q, k, v = (jnp.asarray(rng.randn(B, S, H, D), jnp.float32)
               for _ in range(3))
    out = {"ring_size": n}
    for name, fn in (("plain_fwd", sharded(plain)),
                     ("plain_grad", grad_of(plain)),
                     ("zigzag_fwd", sharded(zigzag)),
                     ("zigzag_grad", grad_of(zigzag))):
        out[name] = _audit_traced(fn, q, k, v)
    return out


def audit_step_skip() -> dict:
    """Run (not just trace) the plain causal ring on the sim mesh and read
    back the per-rank executed-step counters: rank r attends shards
    0..r only, so counts must be [1, 2, ..., n]."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, PartitionSpec as P

    from horovod_tpu.parallel import ring_flash_attention_stats

    n = jax.device_count()
    mesh = Mesh(np.array(jax.devices()), ("sp",))
    B, H, D = 1, 2, 8
    S = 8 * n
    rng = np.random.RandomState(0)
    q, k, v = (jnp.asarray(rng.randn(B, S, H, D), jnp.float32)
               for _ in range(3))

    def f(q, k, v):
        _, steps = ring_flash_attention_stats(q, k, v, "sp", causal=True,
                                              block_q=4, block_k=4)
        return steps[None]

    steps = jax.shard_map(f, mesh=mesh, in_specs=(P(None, "sp"),) * 3,
                          out_specs=P("sp"), check_vma=False)(q, k, v)
    per_rank = [int(s) for s in np.asarray(steps)]
    return {
        "ring_size": n,
        "steps_per_rank": per_rank,
        "expected": list(range(1, n + 1)),
        "exact": per_rank == list(range(1, n + 1)),
        "interior_ranks_skip": all(s < n for s in per_rank[:-1]),
    }


def audit_planner() -> dict:
    """plan_context decisions at the sizes the round cares about, checked
    against the flash kernel's own VMEM budget."""
    from horovod_tpu.ops.flash_attention import VMEM_FIT_BUDGET_MB
    from horovod_tpu.ops.schedule_plan import ContextWorkload, plan_context

    budget_kb = VMEM_FIT_BUDGET_MB * 1024
    out = {}
    for s in (8192, 32768):
        wl = ContextWorkload(seq_len=s, num_heads=16, head_dim=128)
        out[f"s{s}"] = plan_context(wl, 8).as_dict()
    pinned = plan_context(
        ContextWorkload(seq_len=32768, num_heads=16, head_dim=128), 8,
        block_k=4096)
    out["s32768_pinned_bk4096"] = pinned.as_dict()
    out["checks"] = {
        "zigzag_default_for_causal": all(
            out[f"s{s}"]["layout"] == "zigzag" for s in (8192, 32768)),
        "vmem_fits_all": all(
            out[key]["est_vmem_kb"] <= budget_kb
            for key in ("s8192", "s32768", "s32768_pinned_bk4096")),
        "pinned_bk4096_clamped": pinned.block_k < 4096,
    }
    return out


def assert_planner() -> int:
    """CI gate (``make ci`` longctx leg): all three audits, exit 1 on any
    regression.  Ambient HVD_TPU_CTX_* overrides are stripped first — the
    gate audits the SHIPPED defaults, not the local shell."""
    import os

    for v in list(os.environ):
        if v.startswith(("HVD_TPU_CTX_", "HOROVOD_CTX_")):
            os.environ.pop(v)

    import jax

    n = jax.device_count()
    failures = []
    overlap = audit_overlap()
    for name in ("plain_fwd", "plain_grad", "zigzag_fwd", "zigzag_grad"):
        scans = overlap[name]
        if not scans:
            failures.append(f"{name}: no ring scan found in the jaxpr")
        for a in scans:
            if a["prefetch_ppermutes"] < 2:
                failures.append(
                    f"{name}: only {a['prefetch_ppermutes']} kernel-"
                    f"independent ppermutes before the kernel — the K/V "
                    f"rotation is serialized behind the attention step")
            if a["length"] != n - 1:
                failures.append(
                    f"{name}: ring scan runs {a['length']} steps, expected "
                    f"{n - 1} (final step should be unrolled, no rotation)")
    skip = audit_step_skip()
    if not skip["exact"]:
        failures.append(
            f"causal plain steps {skip['steps_per_rank']} != "
            f"{skip['expected']} — masked ring steps are not being skipped")
    planner = audit_planner()
    for check, ok in planner["checks"].items():
        if not ok:
            failures.append(f"planner: {check} failed")
    print(json.dumps({"overlap": overlap, "step_skip": skip,
                      "planner": planner, "failures": failures}, indent=1))
    return 1 if failures else 0


def main():
    import os

    if "jax" not in sys.modules and "xla_force_host_platform_device_count" \
            not in os.environ.get("XLA_FLAGS", ""):
        # Standalone-script runs (the make ci longctx leg) need a
        # multi-device CPU sim ring; under pytest the conftest forces the
        # same 8-device count.
        os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                                   + " --xla_force_host_platform_device_count=8")

    if "--assert-planner" in sys.argv:
        return assert_planner()
    print(json.dumps({"overlap": audit_overlap(),
                      "step_skip": audit_step_skip(),
                      "planner": audit_planner()}, indent=1))


if __name__ == "__main__":
    import os as _os

    # Script entry (make ci runs `python examples/longctx_audit.py`): put
    # the repo root ahead of the script dir so `import horovod_tpu` works
    # without an install.
    sys.path.insert(0, _os.path.dirname(_os.path.dirname(
        _os.path.abspath(__file__))))
    sys.exit(main())
