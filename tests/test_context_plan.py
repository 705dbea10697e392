"""ContextPlan: the long-context planner's decisions, and bit-level parity
of the attention paths it wires — with NO hand-set kernel params anywhere
(every block_q/block_k below is a plan field, the HVD108 contract).

The parity strategy follows the reference's collectives-equal-local-math
pattern (reference test_tensorflow.py:56-247): the planner-chosen sharded
ring/zigzag flash path must reproduce single-device dense attention within
fp32 tolerance, forward and backward, across several (S, block) shapes the
planner itself picks.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from _one_program import shard_map

from horovod_tpu.models.transformer import dense_causal_attention
from horovod_tpu.ops.schedule_plan import ContextWorkload, plan_context
from horovod_tpu.parallel import (
    context_attention_fn,
    plan_long_context,
    ring_flash_attention_stats,
    shard_sequence,
    unshard_sequence,
)


def _qkv(b=1, s=128, h=2, d=8, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    return tuple(jax.random.normal(k, (b, s, h, d), dtype) for k in ks)


def _wl(s, h=16, d=128, **kw):
    return ContextWorkload(seq_len=s, num_heads=h, head_dim=d, **kw)


# ---------------------------------------------------------------------------
# planner decisions
# ---------------------------------------------------------------------------

def test_plan_zigzag_default_for_causal_multishard():
    plan = plan_context(_wl(32768), 8)
    assert plan.layout == "zigzag"
    assert plan.seq_local == 4096
    assert "zigzag" in plan.reason


def test_plan_plain_for_width1_and_noncausal():
    assert plan_context(_wl(8192), 1).layout == "plain"
    assert plan_context(_wl(8192, causal=False), 8).layout == "plain"
    # Causal but not divisible by 2*width (odd local shard): plain, with
    # step skipping noted.
    odd = plan_context(_wl(8 * 13, h=2, d=8), 8)
    assert odd.layout == "plain"
    assert "step skipping" in odd.reason


def test_plan_clamps_pinned_block_k_to_vmem():
    # The r5 failure mode: block_k=4096 wins at S=8192 but VMEM-OOMs at
    # S=32768.  A pinned tile must come back clamped into budget.
    from horovod_tpu.ops.flash_attention import (
        VMEM_FIT_BUDGET_MB,
        _vmem_estimate_bytes,
    )

    budget = VMEM_FIT_BUDGET_MB * 2 ** 20
    # Zigzag splits the shard in two, so the chunk bound already pulls the
    # pinned tile in; the plain layout's chunk admits 4096, so only the
    # VMEM model stops it there.
    for layout in ("zigzag", "plain"):
        plan = plan_context(_wl(32768), 8, layout=layout, block_k=4096)
        assert plan.block_k < 4096
        assert _vmem_estimate_bytes(plan.block_q, plan.block_k, 128) <= \
            budget
    assert "VMEM fit" in plan.reason  # the plain case hits the model


@pytest.mark.parametrize("s_q", [2048, 16384, 32768])
def test_backward_vmem_limit_covers_its_estimate(s_q):
    """The one backward pass holds the head's whole f32 dq accumulator
    (S_q·d·4 bytes) and, beside it, the dq output block in the gradients'
    own dtype (S_q·d·2 in bf16; ·4 for a caller that asks for float32), so
    it has an estimate of its own and asks Mosaic for a limit from it.  At
    the default tiles (d=128, bf16) the limit covers the estimate, both
    terms are in the estimate, and the ask stays under the chip's VMEM, in
    one call; what the forward and the plan read (``_vmem_estimate_bytes``,
    no ``s_q``) is untouched by it."""
    from horovod_tpu.ops.flash_attention import (
        _BWD_VMEM_ASK_MAX_BYTES,
        VMEM_PHYSICAL_MB,
        _bwd_q_rows_per_call,
        _bwd_vmem_estimate_bytes,
        _bwd_vmem_limit_bytes,
    )

    geometry = (1024, 1024, 128, s_q)
    est = _bwd_vmem_estimate_bytes(*geometry)
    limit = _bwd_vmem_limit_bytes(*geometry)
    assert est - _bwd_vmem_estimate_bytes(1024, 1024, 128, 0) \
        == s_q * 128 * (4 + 2)
    assert _bwd_vmem_estimate_bytes(*geometry, out_itemsize=4) - est \
        == (s_q + 2 * 2 * 1024) * 128 * 2       # dq's block, dk's and dv's
    assert est <= limit <= _BWD_VMEM_ASK_MAX_BYTES \
        < VMEM_PHYSICAL_MB * 2 ** 20
    assert limit >= 16 * 2 ** 20        # never under Mosaic's default
    assert _bwd_q_rows_per_call(*geometry) == s_q


def test_backward_rows_per_call_on_each_side_of_the_bound():
    from horovod_tpu.ops.flash_attention import (
        _BWD_VMEM_ASK_MAX_BYTES,
        _bwd_q_rows_per_call,
        _bwd_vmem_limit_bytes,
    )

    # 65536 rows are the most one call takes at d=128: twice that is two
    # equal calls, and a length that does not divide is cut evenly
    assert _bwd_q_rows_per_call(1024, 1024, 128, 65536) == 65536
    assert _bwd_q_rows_per_call(1024, 1024, 128, 131072) == 65536
    rows = _bwd_q_rows_per_call(1024, 1024, 128, 262144)
    assert rows == 64 * 1024
    assert _bwd_vmem_limit_bytes(1024, 1024, 128, rows) \
        <= _BWD_VMEM_ASK_MAX_BYTES
    # several calls hand back float32 parts (dk and dv are summed over
    # them before the cast), and are priced with float32 output blocks
    rows = _bwd_q_rows_per_call(1024, 1024, 128, 262144, out_itemsize=4)
    assert rows == 52 * 1024
    assert _bwd_vmem_limit_bytes(1024, 1024, 128, rows, out_itemsize=4) \
        <= _BWD_VMEM_ASK_MAX_BYTES


def test_plan_remat_follows_headroom_and_width():
    wl = _wl(131072, h=16, d=128, embed_dim=2048, mlp_dim=8192,
             num_layers=16)
    tight = plan_context(wl, 8, headroom_mb=64.0)
    roomy = plan_context(wl, 8, headroom_mb=65536.0)
    assert tight.remat and not roomy.remat
    # Ring sharding shrinks per-chip activations 1/width: the same
    # workload that needs remat solo fits without it across 8 chips.
    assert wl.activation_mb(8) == pytest.approx(wl.activation_mb(1) / 8)
    solo = plan_context(wl, 1, headroom_mb=wl.activation_mb(4))
    wide = plan_context(wl, 8, headroom_mb=wl.activation_mb(4))
    assert solo.remat and not wide.remat


def test_plan_env_override_below_code_kwarg(monkeypatch):
    monkeypatch.setenv("HVD_TPU_CTX_LAYOUT", "plain")
    assert plan_context(_wl(8192), 8).layout == "plain"
    # A keyword argument in code outranks the env knob.
    assert plan_context(_wl(8192), 8, layout="zigzag").layout == "zigzag"


def test_plan_rejects_indivisible_width():
    with pytest.raises(ValueError, match="divisible"):
        plan_context(_wl(8192), 3)


# ---------------------------------------------------------------------------
# parity on the planner-chosen path (>= 3 (S, block) configs, no literals)
# ---------------------------------------------------------------------------

PARITY_CONFIGS = [(128, 2, 8), (256, 2, 8), (512, 4, 16)]


def _plan_path_out(plan, q, k, v, causal=True):
    mesh = Mesh(np.array(jax.devices()[:plan.width]), ("sp",))
    attn = context_attention_fn("sp", plan)
    qp, kp, vp = (shard_sequence(x, plan) for x in (q, k, v))
    out = shard_map(
        lambda q, k, v: attn(q, k, v, causal=causal), mesh=mesh,
        in_specs=P(None, "sp"), out_specs=P(None, "sp"),
        check_vma=False)(qp, kp, vp)
    return unshard_sequence(out, plan)


@pytest.mark.parametrize("s,h,d", PARITY_CONFIGS)
def test_plan_chosen_attention_matches_dense(hvd, s, h, d):
    plan = plan_long_context(seq_len=s, num_heads=h, head_dim=d, width=8)
    assert plan.layout == "zigzag"  # causal multi-shard default
    q, k, v = _qkv(s=s, h=h, d=d)
    out = _plan_path_out(plan, q, k, v)
    ref = dense_causal_attention(q, k, v, causal=True)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)
    # Distinct configs must exercise distinct planner-chosen tiles.
    assert (plan.block_q, plan.block_k) == (s // 16, s // 16)


@pytest.mark.parametrize("s,h,d", PARITY_CONFIGS[:2])
def test_plan_chosen_attention_grads_match(hvd, s, h, d):
    plan = plan_long_context(seq_len=s, num_heads=h, head_dim=d, width=8)
    q, k, v = _qkv(s=s, h=h, d=d)

    def loss_plan(q, k, v):
        # sum-of-squares is permutation invariant, so the zigzag-layout
        # output compares against the natural-order reference directly.
        return (_plan_path_out(plan, q, k, v).astype(jnp.float32) ** 2).sum()

    def loss_ref(q, k, v):
        out = dense_causal_attention(q, k, v, causal=True)
        return (out.astype(jnp.float32) ** 2).sum()

    g_plan = jax.grad(loss_plan, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for gp, gr in zip(g_plan, g_ref):
        np.testing.assert_allclose(gp, gr, atol=5e-4, rtol=5e-4)


def test_plan_noncausal_plain_parity(hvd):
    plan = plan_long_context(seq_len=128, num_heads=2, head_dim=8, width=8,
                             causal=False)
    assert plan.layout == "plain"
    q, k, v = _qkv(s=128)
    out = _plan_path_out(plan, q, k, v, causal=False)
    ref = dense_causal_attention(q, k, v, causal=False)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


# ---------------------------------------------------------------------------
# causal step skipping on the plain layout (exact, not approximate)
# ---------------------------------------------------------------------------

def test_plain_causal_skips_masked_steps_exactly(hvd):
    n = jax.device_count()
    s = 16 * n
    plan = plan_long_context(seq_len=s, num_heads=2, head_dim=8, width=n,
                             layout="plain")
    q, k, v = _qkv(s=s)
    mesh = Mesh(np.array(jax.devices()), ("sp",))

    def f(q, k, v):
        out, steps = ring_flash_attention_stats(
            q, k, v, "sp", causal=True,
            block_q=plan.block_q, block_k=plan.block_k)
        return out, steps[None]

    out, steps = shard_map(
        f, mesh=mesh, in_specs=(P(None, "sp"),) * 3,
        out_specs=(P(None, "sp"), P("sp")), check_vma=False)(q, k, v)
    # Rank r attends K shards 0..r only: r+1 kernels, never the full ring.
    assert [int(x) for x in steps] == list(range(1, n + 1))
    # Skipping is exact — the lse-merge identity, not an approximation.
    ref = dense_causal_attention(q, k, v, causal=True)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


# ---------------------------------------------------------------------------
# elastic width change: re-plan, stay correct on the surviving mesh
# ---------------------------------------------------------------------------

def test_replan_after_elastic_width_change(hvd):
    s, h, d = 256, 2, 8
    plan8 = plan_long_context(seq_len=s, num_heads=h, head_dim=d, width=8)
    plan4 = plan_long_context(seq_len=s, num_heads=h, head_dim=d, width=4)
    # Same workload, half the ring: shard doubles, tiles re-fit.
    assert plan4.seq_local == 2 * plan8.seq_local
    assert plan4.layout == "zigzag"
    q, k, v = _qkv(s=s, h=h, d=d)
    out = _plan_path_out(plan4, q, k, v)
    ref = dense_causal_attention(q, k, v, causal=True)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)
