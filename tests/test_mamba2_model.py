"""The Mamba-2 mixer as a layer of ``models.Transformer``: ``layer_types`` as
the one switch between mixers, each layer type's parameters, a hybrid model
causal and trained in both forms of the scan, what a recurrent layer refuses,
and ``Mamba2Mixer`` alone against its equations written out with the
sequential recurrence of ``tests/test_mamba2.py``, where the scan and the
convolution themselves are held."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.models import Transformer, TransformerConfig
from horovod_tpu.models.mamba import Mamba2Mixer, ssm_plan
from horovod_tpu.ops.causal_conv import causal_conv

from test_mamba2 import sequential

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


# the widths meet the kernels' rule: two heads of 64, a state of 128
KERNEL_HYBRID = dict(HYBRID, layer_types=("mamba", "attention"), num_layers=2,
                     mamba_heads=2, mamba_head_dim=64, mamba_state_dim=128,
                     mamba_chunk=128, max_seq_len=256)


@pytest.mark.parametrize("widths,length,remat", [
    (HYBRID, 24, False), (HYBRID, 24, True), (KERNEL_HYBRID, 200, True)],
    ids=["xla", "xla-remat", "kernel-remat"])
def test_hybrid_model_is_causal_and_trains(widths, length, remat):
    cfg = TransformerConfig(**widths, remat=remat)
    assert ssm_plan(cfg, length)["scan"] == \
        ("kernel" if widths is KERNEL_HYBRID else "xla")
    model = Transformer(cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(3), (2, length), 0, 64)
    # each a program, as a training step holds them: eagerly every layer's
    # every operation is compiled and dispatched by itself
    params = jax.jit(model.init)(jax.random.PRNGKey(0), tokens)
    forward = jax.jit(model.apply)
    logits = forward(params, tokens)
    assert logits.shape == (2, length, 64)
    later = forward(params, tokens.at[:, 17].set(5))
    np.testing.assert_allclose(logits[:, :17], later[:, :17], atol=1e-6)
    assert not np.allclose(logits[:, 17:], later[:, 17:])
    grads = jax.jit(jax.grad(lambda p: model.apply(p, tokens).sum()))(params)
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


@pytest.mark.parametrize("widths,length,forms", [
    (HYBRID, 21, "xla"), (KERNEL_HYBRID, 256, "kernel")],
    ids=["xla", "kernel"])
def test_mixer_alone_matches_its_equations(widths, length, forms):
    """``Mamba2Mixer`` against the equations written out with the sequential
    recurrence, in float32; at widths that meet the kernels' rules the conv
    and the scan are both the kernels'."""
    cfg = TransformerConfig(**widths)
    plan = ssm_plan(cfg, length)
    assert (plan["conv"], plan["scan"]) == (forms, forms)
    h, p_, n = cfg.mamba_heads, cfg.mamba_head_dim, cfg.mamba_state_dim
    inner, e = h * p_, cfg.embed_dim
    mixer = Mamba2Mixer(cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, length, e))
    params = mixer.init(jax.random.PRNGKey(0), x)
    p = params["params"]
    with jax.default_matmul_precision("highest"):
        got = mixer.apply(params, x)
        zxbcdt = x @ p["in_proj"]["kernel"]
        z, xbc, dt = jnp.split(zxbcdt, [inner, 2 * inner + 2 * n], axis=-1)
        xbc = jax.nn.silu(causal_conv(xbc, p["conv_kernel"], p["conv_bias"]))
        xs, b, c = jnp.split(xbc, [inner, inner + n], axis=-1)
        y = sequential(
            xs.reshape(2, length, h, p_), jax.nn.softplus(dt + p["dt_bias"]),
            -jnp.exp(p["A_log"]), b[:, :, None], c[:, :, None], p["D"])
        g = y.reshape(2, length, inner) * jax.nn.silu(z)
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
                    "scan": "kernel", "conv": "kernel"}
    assert ssm_plan(cfg, 1000)["chunks_per_sequence"] == 4
    assert ssm_plan(cfg, 1000)["conv"] == "xla"     # no row tile divides it
    # the tiny widths of the tests, and of the benchmark's rehearsal
    tiny = ssm_plan(TransformerConfig(**HYBRID), 64)
    assert (tiny["scan"], tiny["conv"]) == ("xla", "xla")


def test_a_scan_refuses_a_sequence_sharded_over_chips():
    cfg = TransformerConfig(**HYBRID, context_axis="cp")
    with pytest.raises(NotImplementedError, match="across a scan"):
        Transformer(cfg).init(jax.random.PRNGKey(0),
                              jnp.zeros((1, 8), jnp.int32))
