"""Flash-attention kernel vs dense reference (interpret mode on CPU)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.models.transformer import dense_causal_attention
from horovod_tpu.ops.flash_attention import flash_attention


def _qkv(b=2, s=64, h=2, d=16, dtype=jnp.float32, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return tuple(jax.random.normal(k, (b, s, h, d), dtype) for k in ks)


@pytest.mark.parametrize("causal", [True, False])
def test_matches_dense(hvd, causal):
    q, k, v = _qkv()
    out = flash_attention(q, k, v, causal=causal, block_q=16, block_k=16)
    ref = dense_causal_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


def test_unaligned_lengths(hvd):
    # S not divisible by block sizes exercises the padding mask.
    q, k, v = _qkv(s=50)
    out = flash_attention(q, k, v, causal=True, block_q=16, block_k=16)
    ref = dense_causal_attention(q, k, v, causal=True)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


def test_offsets_match_shifted_positions(hvd):
    # With q_offset = S_k and causal, every query sees all keys.
    q, k, v = _qkv(s=32)
    out = flash_attention(q, k, v, causal=True, q_offset=32, k_offset=0,
                          block_q=16, block_k=16)
    ref = dense_causal_attention(q, k, v, causal=False)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


def test_gradients_match_dense(hvd):
    q, k, v = _qkv(s=32)

    def f_flash(q, k, v):
        return (flash_attention(q, k, v, block_q=16, block_k=16) ** 2).sum()

    def f_dense(q, k, v):
        return (dense_causal_attention(q, k, v) ** 2).sum()

    g1 = jax.grad(f_flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(f_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(a, b, atol=5e-4, rtol=5e-4)


def test_bf16(hvd):
    q, k, v = _qkv(dtype=jnp.bfloat16)
    out = flash_attention(q, k, v, block_q=16, block_k=16)
    assert out.dtype == jnp.bfloat16
    ref = dense_causal_attention(q, k, v)
    np.testing.assert_allclose(out.astype(np.float32),
                               ref.astype(np.float32), atol=3e-2, rtol=3e-2)


def test_default_block_k(hvd):
    """block_k=None resolves to min(S, 2048) at d≤128 — the largest
    streaming tile that compiles on every shipped long-context config
    (4096 VMEM-overflows the S=32768 remat backward) — and stays at the
    proven 1024 for d>128 where K/V tile bytes scale with d."""
    from horovod_tpu.ops.flash_attention import _default_block_k

    assert _default_block_k(1024, 128) == 1024   # clamps to S
    assert _default_block_k(8192, 128) == 2048   # the measured default
    assert _default_block_k(32768, 128) == 2048  # capped (VMEM)
    assert _default_block_k(8192, 256) == 1024   # d>128 safety branch
    assert _default_block_k(0, 128) == 1         # degenerate floor


@pytest.mark.parametrize("s", [64, 50])
def test_subtiled_kernels_match_dense(hvd, s):
    """nsub > 1 (sub < block): the statically-unrolled sub-tile loop
    (round 5) with its pl.when interior/boundary guards must match dense
    numerics in fwd AND backward, including the padded-length case."""
    q, k, v = _qkv(s=s)

    def f_flash(q, k, v):
        return (flash_attention(q, k, v, block_q=32, block_k=32,
                                sub=8) ** 2).sum()

    def f_dense(q, k, v):
        return (dense_causal_attention(q, k, v) ** 2).sum()

    out = flash_attention(q, k, v, block_q=32, block_k=32, sub=8)
    ref = dense_causal_attention(q, k, v)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)
    g1 = jax.grad(f_flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(f_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(a, b, atol=5e-4, rtol=5e-4)


def test_deep_sub_tile_unroll_warns(hvd):
    """The sub-tile sweep is statically unrolled — each sub-tile emits two
    guarded matmul bodies — so geometry past MAX_SUB_TILES (8) must warn,
    naming the block/sub/nsub numbers, instead of silently bloating the
    compile.  Numerics stay correct either way."""
    import warnings

    from horovod_tpu.ops.flash_attention import MAX_SUB_TILES, _sub_fit

    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        assert _sub_fit(1024, 64) == (1024, 64)  # nsub = 16 > 8
    assert len(caught) == 1
    msg = str(caught[0].message)
    assert "16 sub-tiles" in msg and "32 guarded" in msg
    assert f"<= {MAX_SUB_TILES}" in msg

    # At or under the bound: silent (the shipped defaults stay nsub <= 2).
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        _sub_fit(1024, 128)   # nsub = 8: the documented edge, no warning
        _sub_fit(2048, 1024)  # the block_k=2048/sub=1024 shipped default
    assert caught == []

    # The public entry point routes its geometry through the same check.
    q, k, v = _qkv(s=64)
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        out = flash_attention(q, k, v, block_q=64, block_k=64, sub=4)
    assert any("sub-tiles" in str(w.message) for w in caught)
    ref = dense_causal_attention(q, k, v)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


def test_bf16_gradients(hvd):
    """bf16 end to end through the backward kernel: the input-dtype
    matmul path (round 5 — bf16 operands, f32 accumulation, scale-fold
    rounding shared by forward and backward) must stay near the f32 dense
    reference within bf16 tolerance."""
    q, k, v = _qkv(s=32, dtype=jnp.bfloat16)

    def f_flash(q, k, v):
        return (flash_attention(q, k, v, block_q=16, block_k=16)
                .astype(jnp.float32) ** 2).sum()

    qf, kf, vf = (t.astype(jnp.float32) for t in (q, k, v))

    def f_dense(q, k, v):
        return (dense_causal_attention(q, k, v) ** 2).sum()

    g1 = jax.grad(f_flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(f_dense, argnums=(0, 1, 2))(qf, kf, vf)
    for a, b in zip(g1, g2):
        assert a.dtype == jnp.bfloat16
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   atol=5e-2, rtol=5e-2)


def test_transformer_with_flash_attention(hvd):
    from horovod_tpu.models import Transformer, TransformerConfig
    from horovod_tpu.ops.flash_attention import make_flash_attention

    base = dict(vocab_size=64, num_layers=2, num_heads=2, head_dim=8,
                embed_dim=16, mlp_dim=32, dtype=jnp.float32)
    tokens = jax.random.randint(jax.random.PRNGKey(0), (2, 32), 0, 64)
    dense = Transformer(TransformerConfig(**base))
    flash = Transformer(TransformerConfig(
        **base, attention_fn=make_flash_attention(block_q=16, block_k=16)))
    params = dense.init(jax.random.PRNGKey(1), tokens)
    np.testing.assert_allclose(flash.apply(params, tokens),
                               dense.apply(params, tokens),
                               atol=2e-4, rtol=2e-4)


def test_gradients_unaligned_lengths(hvd):
    # S not a multiple of the block size exercises the padded-row masking
    # (lse = +inf padding) in the fused backward kernel.
    q, k, v = _qkv(s=23)

    def f_flash(q, k, v):
        return (flash_attention(q, k, v, block_q=16, block_k=16) ** 2).sum()

    def f_dense(q, k, v):
        return (dense_causal_attention(q, k, v) ** 2).sum()

    g1 = jax.grad(f_flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(f_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(a, b, atol=5e-4, rtol=5e-4)


def test_gradients_noncausal(hvd):
    q, k, v = _qkv(s=32)

    def f_flash(q, k, v):
        return (flash_attention(q, k, v, causal=False,
                                block_q=16, block_k=16) ** 2).sum()

    def f_dense(q, k, v):
        import jax.numpy as jnp
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * q.shape[-1] ** -0.5
        p = jax.nn.softmax(s, axis=-1)
        return (jnp.einsum("bhqk,bkhd->bqhd", p, v) ** 2).sum()

    g1 = jax.grad(f_flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(f_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(a, b, atol=5e-4, rtol=5e-4)


def test_gradients_with_offsets(hvd):
    # Shifted global positions (the sequence-parallel shard case): grads of
    # the shard must match the corresponding slice of the dense grads.
    import jax.numpy as jnp
    q, k, v = _qkv(s=32)
    half = 16
    q2 = q[:, half:]  # shard holding the second half of the sequence

    def f_flash(q2, k, v):
        return (flash_attention(q2, k, v, q_offset=half, k_offset=0,
                                block_q=16, block_k=16) ** 2).sum()

    def f_dense(q, k, v):
        out = dense_causal_attention(q, k, v)
        return (out[:, half:] ** 2).sum()

    g1 = jax.grad(f_flash, argnums=(0, 1, 2))(q2, k, v)
    g2 = jax.grad(f_dense, argnums=(0, 1, 2))(q, k, v)
    np.testing.assert_allclose(g1[0], g2[0][:, half:], atol=5e-4, rtol=5e-4)
    np.testing.assert_allclose(g1[1], g2[1], atol=5e-4, rtol=5e-4)
    np.testing.assert_allclose(g1[2], g2[2], atol=5e-4, rtol=5e-4)


@pytest.mark.parametrize("causal", [True, False])
def test_subtiled_matches_dense(hvd, causal):
    """The nsub>1 path (sub < block_k: in-kernel fori over sub-tiles with
    split interior/masked bounds) — fwd AND the backward kernel, whose
    k tile is smaller than the forward's streaming super tile."""
    q, k, v = _qkv(s=96)
    out = flash_attention(q, k, v, causal=causal, block_q=16, block_k=64,
                          sub=16)
    ref = dense_causal_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)

    def f_flash(q, k, v):
        return (flash_attention(q, k, v, causal=causal, block_q=16,
                                block_k=64, sub=16) ** 2).sum()

    def f_dense(q, k, v):
        return (dense_causal_attention(q, k, v, causal=causal) ** 2).sum()

    g1 = jax.grad(f_flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(f_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(a, b, atol=5e-4, rtol=5e-4)


def test_subtiled_unaligned_gradients(hvd):
    """nsub>1 with a ragged sequence length (padding masks in the sub-tile
    loop's masked suffix)."""
    q, k, v = _qkv(s=72)

    def f_flash(q, k, v):
        return (flash_attention(q, k, v, causal=True, block_q=16,
                                block_k=48, sub=16) ** 2).sum()

    def f_dense(q, k, v):
        return (dense_causal_attention(q, k, v) ** 2).sum()

    g1 = jax.grad(f_flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(f_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(a, b, atol=5e-4, rtol=5e-4)


def _dense_backward(q, k, v, do, causal, q_offset, k_offset):
    """The backward written out densely in f32 from its definition: the
    positions' mask, p from the rows' own log-sum-exp, dv = pᵀ·dO,
    ds = p·(dO·vᵀ − Δ), dq = ds·k·scale, dk = dsᵀ·q·scale.  Returns
    (lse, Δ, dq, dk, dv); a row that may attend to nothing has
    lse = −1e30 and p = 0."""
    q, k, v, do = (t.astype(jnp.float32) for t in (q, k, v, do))
    scale = q.shape[-1] ** -0.5
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    mask = jnp.ones(s.shape[-2:], bool)
    if causal:
        mask = (q_offset + jnp.arange(q.shape[1]))[:, None] \
            >= (k_offset + jnp.arange(k.shape[1]))[None, :]
    s = jnp.where(mask, s, -jnp.inf)
    m = jnp.max(s, axis=-1, keepdims=True)
    attended = jnp.isfinite(m)
    m = jnp.where(attended, m, 0.0)
    l = jnp.sum(jnp.exp(s - m), axis=-1, keepdims=True)
    lse = jnp.where(attended, m + jnp.log(jnp.maximum(l, 1e-30)), -1e30)
    p = jnp.where(attended, jnp.exp(s - lse), 0.0)
    out = jnp.einsum("bhqk,bkhd->bqhd", p, v)
    delta = jnp.sum(do * out, axis=-1)                        # [B, S_q, H]
    dp = jnp.einsum("bqhd,bkhd->bhqk", do, v)
    ds = p * (dp - delta.transpose(0, 2, 1)[..., None])
    return (lse[..., 0].transpose(0, 2, 1), delta,
            jnp.einsum("bhqk,bkhd->bqhd", ds, k) * scale,
            jnp.einsum("bhqk,bqhd->bkhd", ds, q) * scale,
            jnp.einsum("bhqk,bqhd->bkhd", p, do))


def _backward_case(s_q, s_k, q_offset, k_offset, causal, block_q, block_k,
                   sub, dtype=jnp.float32):
    """(kernel's dq, dk, dv), (dense dq, dk, dv) for one direct call of the
    backward entry, the way ring attention drives it: its own lengths and
    offsets, lse and Δ handed in."""
    from horovod_tpu.ops.flash_attention import flash_attention_backward

    ks = jax.random.split(jax.random.PRNGKey(s_q + 7 * s_k), 4)
    q, do = (jax.random.normal(kk, (2, s_q, 2, 16), dtype) for kk in ks[:2])
    k, v = (jax.random.normal(kk, (2, s_k, 2, 16), dtype) for kk in ks[2:])
    lse, delta, *dense = _dense_backward(q, k, v, do, causal, q_offset,
                                         k_offset)
    got = flash_attention_backward(q, k, v, do, lse, delta, causal,
                                   q_offset, k_offset, block_q, block_k,
                                   True, sub=sub)
    return got, dense


@pytest.mark.parametrize("s_q,s_k,q_offset,k_offset,causal,bq,bk,sub", [
    # a late shard of q against a longer K: three k-blocks a head, so dq's
    # rows are revisited across grid steps; both lengths unaligned
    (40, 72, 32, 0, True, 16, 16, 1024),
    # K begins inside Q's range: the first rows attend to nothing
    (64, 64, 0, 32, True, 16, 32, 1024),
    (24, 56, 0, 0, False, 16, 16, 1024),
    # block / sub > 1 on the q side, where the fused kernel's sub-tiles are
    (64, 96, 32, 0, True, 32, 32, 8),
    (48, 80, 0, 0, False, 32, 16, 16),
    # one k-block, several q-blocks, and the reverse
    (64, 16, 48, 0, True, 16, 16, 1024),
    (16, 64, 48, 0, True, 16, 16, 1024),
])
def test_backward_entry_matches_dense(hvd, s_q, s_k, q_offset, k_offset,
                                      causal, bq, bk, sub):
    got, dense = _backward_case(s_q, s_k, q_offset, k_offset, causal, bq,
                                bk, sub)
    for a, b in zip(got, dense):
        np.testing.assert_allclose(a, b, atol=5e-4, rtol=5e-4)


@pytest.mark.parametrize("s_q,s_k,q_offset,k_offset", [
    (48, 80, 32, 0),      # a ring step: a late shard of q, a longer K
    (32, 32, 96, 32),     # a zigzag half pair: equal chunks, both offset
])
def test_backward_entry_returns_float32_partials(hvd, s_q, s_k, q_offset,
                                                 k_offset):
    """Ring and zigzag attention call this entry once per ring step and sum
    what it returns: bf16 inputs, float32 gradients (the kernel's f32
    accumulators, written as they are), rounded by the caller after its
    last sum."""
    got, dense = _backward_case(s_q, s_k, q_offset, k_offset, True, 16, 32,
                                1024, dtype=jnp.bfloat16)
    for a, b in zip(got, dense):
        assert a.dtype == jnp.float32
        np.testing.assert_allclose(a, b, atol=6e-2, rtol=6e-2)
    # and what it returns holds more than bf16 does: not yet rounded
    assert any(np.any(np.asarray(a) != np.asarray(a.astype(jnp.bfloat16),
                                                   np.float32)) for a in got)


@pytest.mark.parametrize("causal,s_q,s_k,q_offset,k_offset,bq,bk,sub", [
    (True, 64, 64, 0, 0, 16, 16, 1024),      # whole tiles
    (False, 64, 64, 0, 0, 16, 16, 1024),
    (True, 50, 50, 0, 0, 16, 16, 1024),      # S no multiple of the tile
    (False, 40, 72, 0, 0, 16, 16, 1024),
    (True, 40, 72, 32, 0, 16, 16, 1024),     # offsets, unequal lengths
    (True, 64, 64, 0, 32, 16, 32, 1024),     # rows that attend to nothing
    (True, 64, 96, 32, 0, 32, 32, 8),        # block / sub > 1
])
def test_compute_dtype_outputs_are_the_float32_outputs_cast(
        hvd, causal, s_q, s_k, q_offset, k_offset, bq, bk, sub):
    """The kernels write bf16 themselves, on the grid step that finishes a
    block, from the f32 accumulator a float32 output would hold: the same
    arithmetic and the same one rounding as a cast after the call, so the
    two agree to the bit -- forward and backward, and through
    ``flash_attention``'s own vjp (residuals kept in the kernels' layout)
    against the [B, S, H, D] entry ring attention calls."""
    import importlib
    fa = importlib.import_module("horovod_tpu.ops.flash_attention")

    bf16 = jnp.bfloat16
    ks = jax.random.split(jax.random.PRNGKey(s_q + 7 * s_k + q_offset), 4)
    q, do = (jax.random.normal(kk, (2, s_q, 2, 16), bf16) for kk in ks[:2])
    k, v = (jax.random.normal(kk, (2, s_k, 2, 16), bf16) for kk in ks[2:])
    forward = lambda dtype: fa._forward_bh(  # noqa: E731
        q, k, v, causal, q_offset, k_offset, bq, bk, True, sub, dtype)
    *_, o16, lse16 = forward(bf16)
    qb, kb, vb, o32, lse32 = forward(jnp.float32)
    assert (o16.dtype, o32.dtype) == (bf16, jnp.float32)
    np.testing.assert_array_equal(o16, o32.astype(bf16))
    np.testing.assert_array_equal(lse16, lse32)

    dob = fa._pad_to(fa._to_bh(do), 1, o16.shape[1])
    delta = jnp.sum(dob.astype(jnp.float32) * o16.astype(jnp.float32), -1)
    backward = lambda dtype: fa._backward_bh(  # noqa: E731
        qb, kb, vb, dob, lse16, delta, s_q, s_k, causal, q_offset, k_offset,
        bq, bk, True, sub, dtype)
    g16, g32 = backward(bf16), backward(jnp.float32)
    for a, b in zip(g16, g32):
        assert (a.dtype, b.dtype) == (bf16, jnp.float32)
        np.testing.assert_array_equal(a, b.astype(bf16))

    # the public path: [B, S, H, D] in and out, in the compute dtype
    out, vjp = jax.vjp(lambda q, k, v: flash_attention(
        q, k, v, causal=causal, q_offset=q_offset, k_offset=k_offset,
        block_q=bq, block_k=bk, sub=sub), q, k, v)
    o_ring, lse = fa.flash_attention_with_lse(
        q, k, v, causal=causal, q_offset=q_offset, k_offset=k_offset,
        block_q=bq, block_k=bk, sub=sub)
    assert (out.dtype, o_ring.dtype) == (bf16, jnp.float32)
    np.testing.assert_array_equal(out, o_ring.astype(bf16))
    ring = fa.flash_attention_backward(
        q, k, v, do, lse, jnp.sum(do.astype(jnp.float32)
                                  * out.astype(jnp.float32), -1),
        causal, q_offset, k_offset, bq, bk, True, sub=sub)
    for a, b in zip(vjp(do), ring):
        assert (a.dtype, b.dtype) == (bf16, jnp.float32)
        np.testing.assert_array_equal(a, b.astype(bf16))


@pytest.mark.parametrize("bq,sub", [(16, 1024), (32, 8)])
def test_backward_k_wholly_after_q_is_zero(hvd, bq, sub):
    """A ring step whose K lies wholly after its Q runs no tile: all three
    gradients are zeros (dq's accumulator is zeroed at the head's first
    grid step whether or not a tile follows), whatever lse says."""
    from horovod_tpu.ops.flash_attention import flash_attention_backward

    ks = jax.random.split(jax.random.PRNGKey(3), 4)
    q, do = (jax.random.normal(kk, (2, 32, 2, 16)) for kk in ks[:2])
    k, v = (jax.random.normal(kk, (2, 48, 2, 16)) for kk in ks[2:])
    stats = jax.random.normal(ks[0], (2, 32, 2))      # the ring's merged lse
    for g in flash_attention_backward(q, k, v, do, stats, stats, True, 0,
                                      64, bq, 16, True, sub=sub):
        assert not np.asarray(g).any()


def test_backward_long_q_is_cut_into_row_ranges(hvd, monkeypatch):
    """Past the VMEM the backward may ask for, q is cut into ranges of
    whole blocks, one call each, dk/dv summed in f32: the same gradients
    as the one call on the other side of the bound (dq to the bit)."""
    import importlib
    fa = importlib.import_module("horovod_tpu.ops.flash_attention")

    def calls(*a):
        return str(jax.make_jaxpr(
            lambda: fa.flash_attention_backward(*a))()).count("pallas_call")

    ks = jax.random.split(jax.random.PRNGKey(5), 4)
    q, do = (jax.random.normal(kk, (2, 72, 2, 16)) for kk in ks[:2])
    k, v = (jax.random.normal(kk, (2, 50, 2, 16)) for kk in ks[2:])
    lse, delta, *dense = _dense_backward(q, k, v, do, True, 16, 0)
    args = (q, k, v, do, lse, delta, True, 16, 0, 16, 16, True)
    assert calls(*args) == 1
    whole = fa.flash_attention_backward(*args)
    # the bound made small: no sequence fits, one q block (the least) a call
    monkeypatch.setattr(fa, "_BWD_VMEM_ASK_MAX_BYTES", 0)
    assert fa._bwd_q_rows_per_call(16, 16, 16, 80, 1024, 4) == 16
    assert calls(*args) == 5
    cut = fa.flash_attention_backward(*args)
    np.testing.assert_array_equal(cut[0], whole[0])
    for a, b, c in zip(cut, whole, dense):
        np.testing.assert_allclose(a, b, atol=1e-5, rtol=1e-5)
        np.testing.assert_allclose(a, c, atol=5e-4, rtol=5e-4)


# -- grouped-query attention, head size 64, a caller's scale (PR 33) ---------

def _grouped(b=1, s=48, h=8, kv=2, d=64, dtype=jnp.float32, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(ks[0], (b, s, h, d), dtype),
            jax.random.normal(ks[1], (b, s, kv, d), dtype),
            jax.random.normal(ks[2], (b, s, kv, d), dtype))


def _dense_by_hand(q, k, v, scale):
    """Query head j reads KV head j // group, written out per head."""
    group = q.shape[2] // k.shape[2]
    outs = []
    for j in range(q.shape[2]):
        kj, vj = k[:, :, j // group], v[:, :, j // group]
        logits = jnp.einsum("bqd,bkd->bqk", q[:, :, j], kj) * scale
        mask = jnp.tril(jnp.ones(logits.shape[-2:], bool))
        probs = jax.nn.softmax(jnp.where(mask, logits, -1e30), axis=-1)
        outs.append(jnp.einsum("bqk,bkd->bqd", probs, vj))
    return jnp.stack(outs, axis=2)


@pytest.mark.parametrize("scale", [None, 0.015625, 0.3])
def test_grouped_kv_heads_and_a_callers_scale_match_dense(hvd, scale):
    """d = 64, 4 query heads a KV head: forward and every gradient against
    dense attention written out per head, and against the program's own
    dense path."""
    q, k, v = _grouped()
    used = 64 ** -0.5 if scale is None else scale
    out = flash_attention(q, k, v, block_q=16, block_k=16, scale=scale)
    np.testing.assert_allclose(out, _dense_by_hand(q, k, v, used),
                               atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(
        out, dense_causal_attention(q, k, v, scale=scale),
        atol=2e-5, rtol=2e-5)
    weights = jax.random.normal(jax.random.PRNGKey(5), out.shape)
    g_flash = jax.grad(lambda *a: (flash_attention(
        *a, block_q=16, block_k=16, scale=scale) * weights).sum(),
        (0, 1, 2))(q, k, v)
    g_dense = jax.grad(lambda *a: (_dense_by_hand(*a, used) * weights).sum(),
                       (0, 1, 2))(q, k, v)
    for name, a, b in zip("qkv", g_flash, g_dense):
        assert a.shape == b.shape, name             # dk, dv at the KV heads
        np.testing.assert_allclose(a, b, atol=1e-4, rtol=1e-4, err_msg=name)


def test_grouped_kv_heads_in_bf16(hvd):
    q, k, v = _grouped(dtype=jnp.bfloat16)
    out = flash_attention(q, k, v, block_q=16, block_k=16, scale=0.015625)
    assert out.dtype == jnp.bfloat16
    ref = _dense_by_hand(*(a.astype(jnp.float32) for a in (q, k, v)),
                         0.015625)
    np.testing.assert_allclose(out.astype(jnp.float32), ref, atol=3e-2,
                               rtol=3e-2)
    grads = jax.grad(lambda *a: flash_attention(
        *a, block_q=16, block_k=16, scale=0.015625).astype(
        jnp.float32).sum(), (0, 1, 2))(q, k, v)
    assert [g.dtype for g in grads] == [jnp.bfloat16] * 3
    assert grads[1].shape == k.shape


def test_kv_heads_that_do_not_divide_are_refused(hvd):
    q, k, v = _grouped(h=8, kv=3)
    with pytest.raises(ValueError, match="do not divide"):
        flash_attention(q, k, v, block_q=16, block_k=16)


def test_the_default_scale_is_the_kernels_old_constant(hvd):
    """No scale and d ** -0.5 by name are one program: the argument adds no
    op, so every configuration that names none compiles to what it did."""
    q, k, v = _qkv(s=32)
    f = lambda scale: jax.jit(lambda *a: flash_attention(  # noqa: E731
        *a, block_q=16, block_k=16, scale=scale)).lower(q, k, v).as_text()
    strip = lambda t: "\n".join(  # noqa: E731
        line.split(" loc(")[0] for line in t.splitlines()
        if not line.startswith("#loc"))
    assert strip(f(None)) == strip(f(16 ** -0.5))
