"""The Mamba-2 mixer (``models/mamba.py``) and its chunked scan
(``ops/ssd_scan.py``): the scan against the recurrence itself, one position
after the other, and against the quadratic form over the whole sequence
(both written here, sharing nothing with the program's chunks), values and
the gradient of every input; the convolution's causality; and
``layer_types`` as the one switch between mixers in ``models.Transformer``."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.models import Transformer, TransformerConfig
from horovod_tpu.models.mamba import Mamba2Mixer, causal_conv, ssm_plan
from horovod_tpu.ops.ssd_scan import ssd_scan

H, P, G, N = 4, 8, 2, 16


def inputs(s, dtype, seed=0, batch=2):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    x = jax.random.normal(ks[0], (batch, s, H, P), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (batch, s, H)) - 2.0)
    a = -jnp.exp(jax.random.uniform(ks[2], (H,), minval=0.0, maxval=2.7))
    b = jax.random.normal(ks[3], (batch, s, G, N), jnp.float32)
    c = jax.random.normal(ks[4], (batch, s, G, N), jnp.float32)
    d = jax.random.normal(ks[5], (H,), jnp.float32)
    return (x.astype(dtype), dt, a, b.astype(dtype), c.astype(dtype), d)


def sequential(x, dt, a, b, c, d):
    """H_t = exp(dt_t a) H_{t-1} + dt_t x_t B_t';  y_t = H_t C_t + D x_t."""
    h, p = x.shape[2:]
    n = b.shape[-1]
    x, b, c = (v.astype(jnp.float32) for v in (x, b, c))
    b, c = (jnp.repeat(v, h // v.shape[2], axis=2) for v in (b, c))

    def one(x, dt, b, c):
        def step(state, t):
            x_t, dt_t, b_t, c_t = t
            state = state * jnp.exp(dt_t * a)[:, None, None] \
                + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :]
            return state, jnp.einsum("hpn,hn->hp", state, c_t)
        return jax.lax.scan(step, jnp.zeros((h, p, n)), (x, dt, b, c))[1]

    return jax.vmap(one)(x, dt, b, c) + d[None, None, :, None] * x


def quadratic(x, dt, a, b, c, d):
    """y_t = sum_{s<=t} exp(sum_{s<r<=t} dt_r a) (C_t . B_s) dt_s x_s + D x_t."""
    x, b, c = (v.astype(jnp.float32) for v in (x, b, c))
    b, c = (jnp.repeat(v, H // G, axis=2) for v in (b, c))
    s = x.shape[1]
    cum = jnp.cumsum(dt * a, axis=1)
    lower = jnp.tril(jnp.ones((s, s), bool))[None, :, :, None]
    seg = jnp.where(lower, cum[:, :, None] - cum[:, None, :], -jnp.inf)
    scores = jnp.einsum("bthn,bshn->btsh", c, b) * jnp.exp(seg)
    return jnp.einsum("btsh,bshp->bthp", scores, dt[..., None] * x) \
        + d[None, None, :, None] * x


def loss_of(fn, weights):
    return lambda *args: jnp.sum(fn(*args).astype(jnp.float32) * weights)


# length, chunk: one chunk, several whole chunks, a ragged last chunk, a
# sequence shorter than the chunk
SHAPES = [(16, 16), (64, 16), (50, 16), (10, 16), (33, 8)]


@pytest.mark.parametrize("other", [sequential, quadratic])
@pytest.mark.parametrize("s,chunk", SHAPES)
def test_scan_matches_the_recurrence_in_float32(s, chunk, other):
    args = inputs(s, jnp.float32)
    weights = jax.random.normal(jax.random.PRNGKey(9), (2, s, H, P))
    with jax.default_matmul_precision("highest"):
        got = ssd_scan(*args, chunk)
        want = other(*args)
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)
        every = tuple(range(6))
        g_got = jax.grad(loss_of(lambda *a: ssd_scan(*a, chunk), weights),
                         every)(*args)
        g_want = jax.grad(loss_of(other, weights), every)(*args)
    for name, g, w in zip("x dt a b c d".split(), g_got, g_want):
        assert np.all(np.isfinite(g)), name
        np.testing.assert_allclose(g, w, rtol=2e-3, atol=2e-3, err_msg=name)


@pytest.mark.parametrize("other", [sequential, quadratic])
@pytest.mark.parametrize("s,chunk", [(64, 16), (50, 16)])
def test_scan_in_bfloat16_stays_within_its_roundings(s, chunk, other):
    """The program's precision: bf16 operands, f32 accumulation, f32 decays.
    Against the f32 recurrence on the same (bf16-rounded) inputs the result
    and every gradient agree to a few bf16 roundings of their norm."""
    args = inputs(s, jnp.bfloat16)
    weights = jax.random.normal(jax.random.PRNGKey(9), (2, s, H, P))
    every = tuple(range(6))
    got = ssd_scan(*args, chunk)
    assert got.dtype == jnp.bfloat16
    g_got = jax.grad(loss_of(lambda *a: ssd_scan(*a, chunk), weights),
                     every)(*args)
    with jax.default_matmul_precision("highest"):
        want = other(*args)
        g_want = jax.grad(loss_of(other, weights), every)(*args)

    def rel(g, w):
        g, w = (np.asarray(v, np.float32) for v in (g, w))
        return np.linalg.norm(g - w) / np.linalg.norm(w)

    assert rel(got, want) < 0.02
    for name, g, w in zip("x dt a b c d".split(), g_got, g_want):
        assert rel(g, w) < 0.03, (name, rel(g, w))


def test_padding_neither_decays_nor_feeds_the_state():
    """A ragged length is padded inside the scan; the real positions read
    what they read in a longer sequence cut at the same place."""
    long = inputs(48, jnp.float32)
    cut = tuple(v[:, :37] if v.ndim > 1 else v for v in long)
    with jax.default_matmul_precision("highest"):
        np.testing.assert_allclose(ssd_scan(*cut, 16),
                                   ssd_scan(*long, 16)[:, :37],
                                   rtol=1e-5, atol=1e-5)


def test_groups_must_divide_heads():
    x, dt, a, b, c, d = inputs(16, jnp.float32)
    with pytest.raises(ValueError, match="do not divide"):
        ssd_scan(x, dt, a, b[:, :, :1].repeat(3, 2), c[:, :, :1].repeat(3, 2),
                 d, 16)


def test_conv_is_causal_and_has_its_bias():
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (1, 20, 6))
    kernel = jax.random.normal(jax.random.PRNGKey(1), (4, 6))
    bias = jax.random.normal(jax.random.PRNGKey(2), (6,))
    y = causal_conv(x, kernel, bias)
    moved = causal_conv(x.at[0, 11].add(1.0), kernel, bias)
    changed = np.abs(np.asarray(moved - y)).sum(-1)[0] > 0
    assert not changed[:11].any()          # nothing before t moves
    assert changed[11:15].all() and not changed[15:].any()     # 4 taps
    # position 0 sees zeros before it: the last tap and the bias alone
    np.testing.assert_allclose(y[0, 0], bias + kernel[3] * x[0, 0],
                               rtol=1e-6)
    np.testing.assert_allclose(
        y[0, 5], bias + sum(kernel[k] * x[0, 2 + k] for k in range(4)),
        rtol=1e-5)


BASE = dict(vocab_size=64, num_layers=3, num_heads=4, head_dim=8,
            embed_dim=32, mlp_dim=64, max_seq_len=64, dtype=jnp.float32)
HYBRID = dict(BASE, layer_types=("mamba", "attention", "mamba"),
              num_kv_heads=2, rotary=False, attention_scale=1 / 64,
              tie_embeddings=True, embedding_multiplier=12.0,
              residual_multiplier=0.22, logits_scaling=8.0, mamba_heads=8,
              mamba_head_dim=8, mamba_state_dim=16, mamba_chunk=8)


def test_all_attention_layer_types_is_the_model_it_was():
    """``layer_types`` of all ``attention`` (and every new field at its
    default) gives the parameter tree and the output of no ``layer_types``
    at all."""
    tokens = jax.random.randint(jax.random.PRNGKey(3), (2, 24), 0, 64)
    plain = Transformer(TransformerConfig(**BASE))
    named = Transformer(TransformerConfig(
        **BASE, layer_types=("attention",) * 3, num_kv_heads=4))
    params = plain.init(jax.random.PRNGKey(0), tokens)
    again = named.init(jax.random.PRNGKey(0), tokens)
    assert jax.tree.structure(params) == jax.tree.structure(again)
    assert set(params["params"]["layer_0"]) == {"attn", "attn_norm", "mlp",
                                                "mlp_norm"}
    assert "lm_head" in params["params"]
    jax.tree.map(np.testing.assert_array_equal, params, again)
    np.testing.assert_array_equal(plain.apply(params, tokens),
                                  named.apply(params, tokens))


def test_each_layer_type_owns_its_parameters():
    tokens = jnp.zeros((1, 16), jnp.int32)
    params = Transformer(TransformerConfig(**HYBRID)).init(
        jax.random.PRNGKey(0), tokens)["params"]
    assert "lm_head" not in params                       # tied
    assert set(params["layer_0"]) == {"mamba", "mamba_norm", "mlp",
                                      "mlp_norm"}
    assert set(params["layer_1"]) == {"attn", "attn_norm", "mlp", "mlp_norm"}
    assert set(params["layer_0"]["mamba"]) == {
        "in_proj", "conv_kernel", "conv_bias", "dt_bias", "A_log", "D",
        "norm", "out_proj"}
    # z | x | B | C | dt = 64 + 64 + 16 + 16 + 8
    assert params["layer_0"]["mamba"]["in_proj"]["kernel"].shape == (32, 168)
    assert params["layer_0"]["mamba"]["conv_kernel"].shape == (4, 96)
    assert params["layer_1"]["attn"]["k"]["kernel"].shape == (32, 2, 8)
    assert params["layer_1"]["attn"]["q"]["kernel"].shape == (32, 4, 8)


@pytest.mark.parametrize("remat", [False, True])
def test_hybrid_model_is_causal_and_trains(remat):
    cfg = TransformerConfig(**HYBRID, remat=remat)
    model = Transformer(cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(3), (2, 24), 0, 64)
    params = model.init(jax.random.PRNGKey(0), tokens)
    logits = model.apply(params, tokens)
    assert logits.shape == (2, 24, 64)
    later = model.apply(params, tokens.at[:, 17].set(5))
    np.testing.assert_allclose(logits[:, :17], later[:, :17], atol=1e-6)
    assert not np.allclose(logits[:, 17:], later[:, 17:])
    grads = jax.grad(lambda p: model.apply(p, tokens).sum())(params)
    assert all(np.all(np.isfinite(g)) and np.any(g != 0)
               for g in jax.tree.leaves(grads))


def test_a_recurrent_layer_refuses_a_cache():
    cfg = TransformerConfig(**HYBRID)
    model = Transformer(cfg)
    tokens = jnp.zeros((1, 8), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), tokens)
    with pytest.raises(NotImplementedError, match="recurrent layer"):
        model.apply(params, tokens, return_kv=True)
    from horovod_tpu.models.transformer import init_kv_cache

    k, v = init_kv_cache(cfg, 1)
    assert k.shape[3] == 2                      # KV heads, not query heads
    with pytest.raises(NotImplementedError, match="recurrent layer"):
        model.apply(params, tokens[:, :1], kv_cache=(k, v),
                    lengths=jnp.zeros((1,), jnp.int32))


def test_grouped_attention_decodes_through_its_cache():
    """Grouped-query attention without rotary embedding and with a caller's
    scale serves from a cache of KV heads: prefill then decode agrees with
    the full forward pass."""
    from horovod_tpu.models.transformer import init_kv_cache

    cfg = TransformerConfig(**dict(HYBRID, layer_types=None))
    model = Transformer(cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(3), (1, 9), 0, 64)
    params = model.init(jax.random.PRNGKey(0), tokens)
    full = model.apply(params, tokens)
    _, (k, v) = model.apply(params, tokens[:, :8], return_kv=True)
    assert k.shape == (3, 1, 8, 2, 8)
    kc, vc = init_kv_cache(cfg, 1, 16)
    kc, vc = kc.at[:, :, :8].set(k), vc.at[:, :, :8].set(v)
    step, _ = model.apply(params, tokens[:, 8:9], kv_cache=(kc, vc),
                          lengths=jnp.array([8]))
    np.testing.assert_allclose(step[0], full[0, 8], atol=1e-5, rtol=1e-5)


def test_unknown_layer_type_and_wrong_count_are_refused():
    tokens = jnp.zeros((1, 8), jnp.int32)
    with pytest.raises(ValueError, match="layer type 'linear'"):
        Transformer(TransformerConfig(**dict(
            HYBRID, layer_types=("mamba", "linear", "mamba")))).init(
            jax.random.PRNGKey(0), tokens)
    with pytest.raises(ValueError, match="names 2 layers"):
        Transformer(TransformerConfig(**dict(
            HYBRID, layer_types=("mamba", "mamba")))).init(
            jax.random.PRNGKey(0), tokens)


def test_mixer_alone_matches_its_equations():
    """``Mamba2Mixer`` against the equations written out with the sequential
    recurrence, in float32."""
    cfg = TransformerConfig(**HYBRID)
    mixer = Mamba2Mixer(cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 21, 32))
    params = mixer.init(jax.random.PRNGKey(0), x)
    p = params["params"]
    with jax.default_matmul_precision("highest"):
        got = mixer.apply(params, x)
        zxbcdt = x @ p["in_proj"]["kernel"]
        z, xbc, dt = jnp.split(zxbcdt, [64, 64 + 96], axis=-1)
        xbc = jax.nn.silu(causal_conv(xbc, p["conv_kernel"], p["conv_bias"]))
        xs, b, c = jnp.split(xbc, [64, 80], axis=-1)
        y = sequential(
            xs.reshape(2, 21, 8, 8), jax.nn.softplus(dt + p["dt_bias"]),
            -jnp.exp(p["A_log"]), b[:, :, None], c[:, :, None], p["D"])
        g = y.reshape(2, 21, 64) * jax.nn.silu(z)
        g = g * jax.lax.rsqrt(jnp.mean(g * g, -1, keepdims=True) + 1e-6) \
            * p["norm"]["scale"]
        want = g @ p["out_proj"]["kernel"]
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


def test_ssm_plan_counts_layers_chunks_and_state():
    cfg = dataclasses.replace(
        TransformerConfig(**HYBRID), mamba_heads=64, mamba_head_dim=64,
        mamba_state_dim=128, mamba_chunk=256)
    plan = ssm_plan(cfg, 8192)
    assert plan == {"layers": {"attention": 1, "mamba": 2}, "chunk": 256,
                    "chunks_per_sequence": 32,
                    "carried_state_bytes_per_layer_and_sequence": 2097152,
                    "scan": "xla"}
    assert ssm_plan(cfg, 1000)["chunks_per_sequence"] == 4


def test_a_scan_refuses_a_sequence_sharded_over_chips():
    cfg = TransformerConfig(**HYBRID, context_axis="cp")
    with pytest.raises(NotImplementedError, match="across a scan"):
        Transformer(cfg).init(jax.random.PRNGKey(0),
                              jnp.zeros((1, 8), jnp.int32))
