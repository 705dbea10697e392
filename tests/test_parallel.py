"""Sequence-parallel attention + hierarchical allreduce correctness.

No reference analog exists (reference has no attention, SURVEY §2.9); the
test strategy follows the reference's pattern of asserting collectives equal
local math (reference test_tensorflow.py:56-247): sharded attention must
reproduce dense single-device attention bit-for-tolerance, and hierarchical
allreduce must equal a flat psum.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from _one_program import shard_map

from horovod_tpu.models.transformer import dense_causal_attention
from horovod_tpu.parallel import (
    hierarchical_allreduce,
    ring_attention,
    ulysses_attention,
)


def _qkv(b=2, s=32, h=4, d=8, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    shape = (b, s, h, d)
    return tuple(jax.random.normal(k, shape, dtype) for k in ks)


@pytest.mark.parametrize("causal", [True, False])
def test_ring_attention_matches_dense(hvd, causal):
    q, k, v = _qkv()
    mesh = jax.sharding.Mesh(np.array(jax.devices()), ("sp",))
    sharded = shard_map(
        lambda q, k, v: ring_attention(q, k, v, "sp", causal=causal),
        mesh=mesh, in_specs=P(None, "sp"), out_specs=P(None, "sp"))
    out = sharded(q, k, v)
    ref = dense_causal_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("causal", [True, False])
def test_ulysses_attention_matches_dense(hvd, causal):
    q, k, v = _qkv(h=8)
    mesh = jax.sharding.Mesh(np.array(jax.devices()), ("sp",))
    sharded = shard_map(
        lambda q, k, v: ulysses_attention(q, k, v, "sp", causal=causal),
        mesh=mesh, in_specs=P(None, "sp"), out_specs=P(None, "sp"))
    out = sharded(q, k, v)
    ref = dense_causal_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


def test_ulysses_rejects_indivisible_heads(hvd):
    q, k, v = _qkv(h=3)
    mesh = jax.sharding.Mesh(np.array(jax.devices()), ("sp",))
    with pytest.raises(ValueError, match="divisible"):
        shard_map(
            lambda q, k, v: ulysses_attention(q, k, v, "sp"),
            mesh=mesh, in_specs=P(None, "sp"), out_specs=P(None, "sp"))(q, k, v)


def test_ring_attention_bf16(hvd):
    q, k, v = _qkv(dtype=jnp.bfloat16)
    mesh = jax.sharding.Mesh(np.array(jax.devices()), ("sp",))
    out = shard_map(
        lambda q, k, v: ring_attention(q, k, v, "sp", causal=True),
        mesh=mesh, in_specs=P(None, "sp"), out_specs=P(None, "sp"))(q, k, v)
    ref = dense_causal_attention(q, k, v, causal=True)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(out.astype(np.float32), ref.astype(np.float32),
                               atol=3e-2, rtol=3e-2)


def test_hierarchical_allreduce_matches_flat_psum(hvd):
    devs = np.array(jax.devices()).reshape(2, 4)
    mesh = Mesh(devs, ("dcn", "ici"))
    x = jax.random.normal(jax.random.PRNGKey(1), (8, 256))

    flat = shard_map(lambda t: jax.lax.psum(t, ("dcn", "ici")),
                         mesh=mesh, in_specs=P(("dcn", "ici")), out_specs=P())
    # check_vma=False: the closing ici all_gather leaves values equal across
    # the axis but the vma system cannot prove it (hvd.shard defaults this).
    hier = shard_map(
        lambda t: hierarchical_allreduce(t.reshape(-1),
                                         ("dcn", "ici")).reshape(t.shape),
        mesh=mesh, in_specs=P(("dcn", "ici")), out_specs=P(), check_vma=False)
    np.testing.assert_allclose(hier(x), flat(x), rtol=1e-5, atol=1e-5)


def test_hierarchical_allreduce_ragged_length(hvd):
    # Length not divisible by the ici axis exercises the padding path
    # (reference padding semantics, operations.cc:1033-1039).
    devs = np.array(jax.devices()).reshape(2, 4)
    mesh = Mesh(devs, ("dcn", "ici"))
    x = jax.random.normal(jax.random.PRNGKey(2), (13,))
    flat = shard_map(lambda t: jax.lax.psum(t, ("dcn", "ici")),
                         mesh=mesh, in_specs=P(), out_specs=P())
    hier = shard_map(lambda t: hierarchical_allreduce(t, ("dcn", "ici")),
                         mesh=mesh, in_specs=P(), out_specs=P(),
                         check_vma=False)
    np.testing.assert_allclose(hier(x), flat(x), rtol=1e-5, atol=1e-5)


def test_transformer_with_ring_attention(hvd):
    """End-to-end: sequence-sharded transformer == dense transformer."""
    from horovod_tpu.models import Transformer, TransformerConfig
    from horovod_tpu.parallel import make_ring_attention

    mesh = jax.sharding.Mesh(np.array(jax.devices()), ("sp",))
    n = len(jax.devices())
    cfg = dict(vocab_size=64, num_layers=2, num_heads=4, head_dim=8,
               embed_dim=32, mlp_dim=64, dtype=jnp.float32)
    dense_model = Transformer(TransformerConfig(**cfg))
    ring_model = Transformer(TransformerConfig(
        **cfg, attention_fn=make_ring_attention("sp")))

    tokens = jax.random.randint(jax.random.PRNGKey(3), (2, 32), 0, 64)
    params = dense_model.init(jax.random.PRNGKey(0), tokens)
    ref = dense_model.apply(params, tokens)

    s_local = tokens.shape[1] // n

    def fwd(params, toks):
        offset = jax.lax.axis_index("sp") * s_local
        return ring_model.apply(params, toks, position_offset=offset)

    out = shard_map(fwd, mesh=mesh, in_specs=(P(), P(None, "sp")),
                        out_specs=P(None, "sp"))(params, tokens)
    np.testing.assert_allclose(out, ref, atol=2e-4, rtol=2e-4)


@pytest.mark.parametrize("causal", [True, False])
def test_ring_flash_attention_matches_dense(hvd, causal):
    from horovod_tpu.parallel import ring_flash_attention

    q, k, v = _qkv()
    mesh = jax.sharding.Mesh(np.array(jax.devices()), ("sp",))
    # check_vma=False: pallas_call outputs carry no vma info (hvd.shard's
    # default); required whenever the flash kernel runs inside shard_map.
    out = shard_map(
        lambda q, k, v: ring_flash_attention(  # hvd-lint: disable=HVD108
            q, k, v, "sp", causal, block_q=4, block_k=4),
        mesh=mesh, in_specs=P(None, "sp"), out_specs=P(None, "sp"),
        check_vma=False)(q, k, v)
    ref = dense_causal_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


def test_ring_flash_attention_grads_match(hvd):
    from horovod_tpu.parallel import ring_flash_attention

    q, k, v = _qkv(s=16)
    mesh = jax.sharding.Mesh(np.array(jax.devices()), ("sp",))

    def loss_flash(q, k, v):
        out = shard_map(
            lambda q, k, v: ring_flash_attention(  # hvd-lint: disable=HVD108
                q, k, v, "sp", True, block_q=2, block_k=2),
            mesh=mesh, in_specs=P(None, "sp"),
            out_specs=P(None, "sp"), check_vma=False)(q, k, v)
        return (out.astype(jnp.float32) ** 2).sum()

    def loss_dense(q, k, v):
        return (dense_causal_attention(q, k, v, causal=True)
                .astype(jnp.float32) ** 2).sum()

    g1 = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(a, b, atol=5e-4, rtol=5e-4)


@pytest.mark.parametrize("causal", [True, False])
def test_ulysses_flash_matches_dense(hvd, causal):
    """Ulysses with the fused flash kernel as local attention — forward
    and gradients must match dense attention."""
    from horovod_tpu.parallel import make_ulysses_flash_attention

    q, k, v = _qkv(h=8)
    mesh = jax.sharding.Mesh(np.array(jax.devices()), ("sp",))
    attn = make_ulysses_flash_attention("sp", block_q=8, block_k=8)
    sharded = shard_map(
        lambda q, k, v: attn(q, k, v, causal=causal),
        mesh=mesh, in_specs=P(None, "sp"), out_specs=P(None, "sp"),
        check_vma=False)  # pallas_call outputs carry no vma metadata
    out = sharded(q, k, v)
    ref = dense_causal_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)

    # gradients through the alltoall + flash vjp
    def loss_sharded(q, k, v):
        return jnp.sum(sharded(q, k, v) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(dense_causal_attention(q, k, v, causal=causal) ** 2)

    g_sh = jax.grad(loss_sharded, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_sh, g_ref):
        np.testing.assert_allclose(a, b, atol=5e-4, rtol=5e-4)


def test_three_axis_dp_hierarchical_sp_composition(hvd):
    """The docs/parallelism.md Composing claim, tested literally: a 3-D
    ("dcn", "ici", "sp") mesh — multi-slice hierarchical data parallelism
    composed with in-slice ring-attention sequence parallelism — must
    reproduce dense single-device training math.  Exercises, in ONE step:
    hierarchical allreduce over two data axes (DistributedOptimizer's
    in-mesh detection of the (dcn, ici) pair), ring attention's ppermute
    collectives over "sp", and their non-interference."""
    import optax

    from horovod_tpu.models import Transformer, TransformerConfig
    from horovod_tpu.parallel import make_ring_attention

    devs = np.array(jax.devices()[:8]).reshape(2, 2, 2)
    mesh = Mesh(devs, ("dcn", "ici", "sp"))

    base = dict(vocab_size=32, num_layers=1, num_heads=2, head_dim=8,
                embed_dim=16, mlp_dim=32, dtype=jnp.float32)
    sp_model = Transformer(TransformerConfig(
        **base, attention_fn=make_ring_attention("sp")))
    dense_model = Transformer(TransformerConfig(**base))

    B, S = 4, 8  # B split 2x2 over (dcn, ici); S split 2 over sp
    s_local = S // 2
    tokens = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0, 32)
    params = dense_model.init(jax.random.PRNGKey(2), tokens[:1, :s_local])
    opt = hvd.DistributedOptimizer(optax.sgd(0.1))
    opt_state = opt.init(params)

    def step(params, opt_state, toks):
        def loss_fn(p):
            offset = jax.lax.axis_index("sp") * s_local
            logits = sp_model.apply(p, toks, position_offset=offset)
            # Position-uniform loss (mean of squared logits): exact under
            # sequence sharding via pmean — no cross-shard target shift.
            return jax.lax.pmean(jnp.mean(logits.astype(jnp.float32) ** 2),
                                 "sp")

        loss, grads = jax.value_and_grad(loss_fn)(params)
        grads = jax.tree.map(lambda g: jax.lax.pmean(g, "sp"), grads)
        updates, opt_state = opt.update(grads, opt_state, params)
        # Reporting only: the per-shard loss covers the local batch rows;
        # average over the data axes to compare with the full-batch ref
        # (gradients are averaged by DistributedOptimizer, not here).
        loss = jax.lax.pmean(loss, ("dcn", "ici"))
        return optax.apply_updates(params, updates), opt_state, loss

    stepped = jax.jit(jax.shard_map(
        step, mesh=mesh,
        in_specs=(P(), P(), P(("dcn", "ici"), "sp")),
        out_specs=(P(), P(), P()), check_vma=False))
    new_params, _, loss = stepped(params, opt_state, tokens)

    # Dense single-device reference on the full batch and sequence.
    def ref_loss(p):
        return jnp.mean(dense_model.apply(p, tokens).astype(jnp.float32)
                        ** 2)

    ref_l, ref_g = jax.value_and_grad(ref_loss)(params)
    ref_opt = optax.sgd(0.1)
    ref_params = optax.apply_updates(
        params, ref_opt.update(ref_g, ref_opt.init(params), params)[0])

    np.testing.assert_allclose(float(loss), float(ref_l), rtol=1e-5)
    for got, want in zip(jax.tree.leaves(new_params),
                         jax.tree.leaves(ref_params)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-4, atol=2e-5)
