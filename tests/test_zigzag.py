"""Zigzag ring attention: load-balanced causal sequence parallelism.

No reference analog (the reference has no attention, SURVEY §2.9); the test
contract follows the suite's rule: sharded attention must reproduce dense
single-device attention, including gradients, with the zigzag layout's
permutation round-tripping exactly.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from _one_program import shard_map

from horovod_tpu.models.transformer import dense_causal_attention
from horovod_tpu.parallel import (
    zigzag_inverse_permutation,
    zigzag_permutation,
    zigzag_positions,
    zigzag_ring_flash_attention,
)

N = 8  # virtual chips (conftest)


def _qkv(b=2, s=32, h=4, d=8, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    return tuple(jax.random.normal(k, (b, s, h, d), dtype) for k in ks)


def _sharded_zigzag(causal, s, block=2):
    mesh = Mesh(np.array(jax.devices()), ("sp",))
    perm = zigzag_permutation(s, N)
    inv = zigzag_inverse_permutation(s, N)

    sharded = shard_map(
        lambda q, k, v: zigzag_ring_flash_attention(
            q, k, v, "sp", causal, block, block),
        mesh=mesh, in_specs=P(None, "sp"), out_specs=P(None, "sp"),
        check_vma=False)
    return lambda q, k, v: sharded(q[:, perm], k[:, perm], v[:, perm])[:, inv]


def test_permutation_round_trips():
    perm = zigzag_permutation(32, N)
    inv = zigzag_inverse_permutation(32, N)
    np.testing.assert_array_equal(perm[inv], np.arange(32))
    # rank r's shard = chunks (r, 2n-1-r): first shard is [c0 | c15]
    c = 32 // (2 * N)
    np.testing.assert_array_equal(perm[: 2 * c], [0, 1, 30, 31])


def test_positions_match_permutation(hvd):
    mesh = Mesh(np.array(jax.devices()), ("sp",))
    pos = jax.shard_map(lambda: zigzag_positions(4, "sp"), mesh=mesh,
                        in_specs=(), out_specs=P("sp"), check_vma=False)()
    np.testing.assert_array_equal(np.asarray(pos), zigzag_permutation(32, N))


@pytest.mark.parametrize("causal", [True, False])
def test_zigzag_matches_dense(hvd, causal):
    q, k, v = _qkv()
    out = _sharded_zigzag(causal, 32)(q, k, v)
    ref = dense_causal_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


def test_zigzag_grads_match_dense(hvd):
    q, k, v = _qkv(s=16)
    run = _sharded_zigzag(True, 16)

    def loss_zz(q, k, v):
        return (run(q, k, v).astype(jnp.float32) ** 2).sum()

    def loss_dense(q, k, v):
        return (dense_causal_attention(q, k, v, causal=True)
                .astype(jnp.float32) ** 2).sum()

    g1 = jax.jit(jax.grad(loss_zz, argnums=(0, 1, 2)))(q, k, v)
    g2 = jax.jit(jax.grad(loss_dense, argnums=(0, 1, 2)))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(a, b, atol=5e-4, rtol=5e-4)


def test_zigzag_rejects_indivisible(hvd):
    with pytest.raises(ValueError, match="divisible"):
        zigzag_permutation(12, N)


def test_transformer_with_zigzag_attention(hvd):
    """LM logits through zigzag layout == dense transformer, token-exact."""
    from horovod_tpu.models import Transformer, TransformerConfig
    from horovod_tpu.parallel import make_zigzag_ring_flash_attention

    cfg = dict(vocab_size=64, num_layers=2, num_heads=2, head_dim=8,
               embed_dim=16, mlp_dim=32, dtype=jnp.float32)
    s = 32
    tokens = jax.random.randint(jax.random.PRNGKey(3), (2, s), 0, 64)
    dense_model = Transformer(TransformerConfig(**cfg))
    params = dense_model.init(jax.random.PRNGKey(0), tokens)
    ref = dense_model.apply(params, tokens)

    zz_model = Transformer(TransformerConfig(
        **cfg, attention_fn=make_zigzag_ring_flash_attention(  # hvd-lint: disable=HVD108
            "sp", block_q=2, block_k=2)))
    mesh = Mesh(np.array(jax.devices()), ("sp",))
    perm = zigzag_permutation(s, N)
    inv = zigzag_inverse_permutation(s, N)
    s_local = s // N

    def fwd(params, toks):
        return zz_model.apply(params, toks,
                              positions=zigzag_positions(s_local, "sp"))

    out = shard_map(
        fwd, mesh=mesh, in_specs=(P(), P(None, "sp")),
        out_specs=P(None, "sp"), check_vma=False)(params, tokens[:, perm])
    np.testing.assert_allclose(out[:, inv], ref, atol=2e-4, rtol=2e-4)
