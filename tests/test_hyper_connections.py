"""Manifold-constrained hyper-connections (``TransformerConfig.hyper_streams``,
``models/hyper.py``) on the program's normal path against the plain reference
(``benchmarks/reference/mhc_mla_moe_serve.py``) at n = 4, C = 64, two layers,
float32 on the CPU (PR 59): the coefficients and the two mixes line by line;
Sinkhorn's rows and columns; the dynamic part live; a prefill with
``lengths`` and three cache calls against one full forward through the
reference; the four refused combinations by name; ``hyper_streams`` 0 the
stream every other model has.

Tolerances.  Program and reference both compute in float32 here, in another
order (one fused projection divided by the norm after it, Sinkhorn with the
positions last, the mixes as scaled adds): the coefficients agree to 1e-6
and the bound is 1e-5; logits through two layers of latent attention and a
sparse feed-forward agree to 2e-6 of their size and the bound is 2e-4
(``tests/test_latent_attention.py``'s).  Dropping the dynamic part (phi = 0)
moves the logits by a tenth of their size.
"""

import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.run import load_module  # noqa: E402
from horovod_tpu.models import Transformer, hyper  # noqa: E402
from horovod_tpu.models.transformer import (  # noqa: E402
    HYPER_REFUSED, TransformerConfig, init_kv_cache)

TOL = 2e-4
CFG = {"family": "mhc_mla_moe_serve", "model_type": "xing4_0",
       "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 1,
       "hidden_act": "silu", "hidden_size": 64, "intermediate_size": 96,
       "kv_lora_rank": 8, "moe_intermediate_size": 16, "moe_layer_freq": 1,
       "n_group": 1, "n_routed_experts": 8, "n_shared_experts": 1,
       "norm_topk_prob": True, "num_attention_heads": 4,
       "num_experts_per_tok": 2, "num_hidden_layers": 2,
       "num_key_value_heads": 4, "q_lora_rank": 16, "hc_mult": 4,
       "hc_sinkhorn_iters": 20, "hc_eps": 1e-6, "mhc_h_res_clamp_min": -30,
       "mhc_h_res_clamp_max": 30, "qk_nope_head_dim": 8,
       "qk_rope_head_dim": 4, "rms_norm_eps": 1e-6, "rope_theta": 10000,
       "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 64,
                        "mscale": 1, "mscale_all_dim": 1,
                        "original_max_position_embeddings": 16,
                        "type": "yarn"},
       "routed_scaling_factor": 2, "scoring_func": "sigmoid",
       "tie_word_embeddings": False, "topk_group": 1,
       "topk_method": "noaux_tc", "v_head_dim": 6, "vocab_size": 128,
       "initializer_range": 0.2, "expert_bias_scale": 0.01}
TRAFFIC = {"max_seq_len": 64}


@pytest.fixture(scope="module")
def built():
    """(family, reference, float32 weights in the reference's layout, the
    program's parameters, the program's config in float32)."""
    family = load_module("families", "mhc_mla_moe_serve")
    reference = load_module("reference", "mhc_mla_moe_serve")
    weights = jax.tree.map(lambda x: x.astype(jnp.float32),
                           family.draw(CFG, family.seed_key(11)))
    mcfg = dataclasses.replace(family.model_config(CFG, TRAFFIC),
                               dtype=jnp.float32, param_dtype=jnp.float32)
    return family, reference, weights, family.to_program(weights, CFG), mcfg


def test_coefficients_and_mixes_follow_the_references_lines(built):
    family, reference, weights, params, _ = built
    w = weights["layers"][0]["attn_hc"]
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 5, 4, 64), jnp.float32)
    y = jax.random.normal(jax.random.PRNGKey(4), (2, 5, 64), jnp.float32)
    coef, err = jax.jit(hyper.HyperConnection(4).apply)(
        {"params": params["params"]["layer_0"]["attn_hc"]}, x)
    with jax.default_matmul_precision("highest"):
        mm = lambda a, b: a @ b.astype(jnp.float32)  # noqa: E731
        for b in range(2):
            pre, post, res = reference.hyper_coefficients(x[b], w, CFG, mm)
            np.testing.assert_allclose(coef[b, :, :4], pre, atol=1e-5)
            np.testing.assert_allclose(coef[b, :, 4:8], post, atol=1e-5)
            np.testing.assert_allclose(coef[b, :, 8:].reshape(5, 4, 4), res,
                                       atol=1e-5)
            np.testing.assert_allclose(
                hyper.pre_mix(coef, x)[b], reference.hyper_read(pre, x[b]),
                atol=1e-5)
            np.testing.assert_allclose(
                hyper.post_mix(coef, x, y)[b],
                reference.hyper_write(res, post, x[b], y[b]), atol=1e-5)
            # what the module hands back beside them: the columns' error
            assert float(err[b, 0]) == pytest.approx(float(jnp.max(jnp.abs(
                res.sum(axis=-2) - 1.0))), abs=1e-6)
    assert coef.shape == (2, 5, 24) and err.shape == (2, 1)


@pytest.mark.parametrize("iters, closed", [(20, True), (1, False)])
def test_sinkhorn_rows_and_columns(iters, closed):
    logits = jax.random.normal(jax.random.PRNGKey(0), (4, 4, 256))
    m = hyper.sinkhorn(logits, iters, 1e-6, (-30.0, 30.0))
    assert float(jnp.max(jnp.abs(m.sum(axis=1) - 1.0))) < 1e-3     # rows
    cols = float(jnp.max(jnp.abs(m.sum(axis=0) - 1.0)))
    assert (cols < 1e-3) == closed, cols
    assert float(jnp.max(hyper.column_error(m))) == pytest.approx(cols)
    # the clamp comes before exp: logits far outside it are finite
    far = hyper.sinkhorn(1e4 * logits, iters, 1e-6, (-30.0, 30.0))
    assert bool(jnp.isfinite(far).all())


def test_full_forward_prefill_and_cache_calls_against_the_reference(built):
    family, reference, weights, params, mcfg = built
    model = Transformer(mcfg)
    apply = jax.jit(model.apply, static_argnames=("return_kv",))
    tokens = np.asarray(jax.random.randint(jax.random.PRNGKey(5), (2, 12),
                                           0, CFG["vocab_size"]))
    rows = jax.jit(lambda w, row: reference.logits_of_rows(
        w, row, CFG, family.held(CFG), 0, 12)[0])
    want = np.stack([np.asarray(rows(weights, jnp.asarray(row)))
                     for row in tokens])
    scale = np.abs(want).max()
    full = apply(params, tokens, valid=jnp.ones((2, 12), bool))
    assert np.abs(np.asarray(full) - want).max() < TOL * scale
    # zeroing phi (the dynamic part) is another model
    static = jax.tree_util.tree_map_with_path(
        lambda path, x: jnp.zeros_like(x) if path[-1].key == "phi" else x,
        params)
    moved = apply(static, tokens, valid=jnp.ones((2, 12), bool))
    assert np.abs(np.asarray(moved) - want).max() > 0.05 * scale
    # a prefill that says where its prompts end, then three cache calls
    lengths = jnp.array([9, 9])
    logits, (k, v) = apply(
        params, tokens[:, :9], return_kv=True, lengths=lengths,
        valid=jnp.ones((2, 9), bool), logits_at=lengths - 1)
    assert np.abs(np.asarray(logits) - want[:, 8]).max() < TOL * scale
    kk, vv = init_kv_cache(mcfg, 2, 64)
    kk, vv = kk.at[:, :, :9].set(k), vv.at[:, :, :9].set(v)
    for t in range(9, 12):
        step, (kk, vv) = apply(
            params, tokens[:, t:t + 1], kv_cache=(kk, vv), lengths=lengths,
            valid=jnp.ones((2, 1), bool))
        assert step.shape == (2, CFG["vocab_size"])
        assert np.abs(np.asarray(step) - want[:, t]).max() < TOL * scale
        lengths = lengths + 1


@pytest.mark.parametrize("field, value", [
    ("parallel_block", True), ("residual_scaling", True),
    ("moe_router_dim", 8), ("attention_block", 4)])
def test_refused_beside_hyper_streams_by_name(field, value):
    assert field in HYPER_REFUSED
    cfg = TransformerConfig(vocab_size=32, num_layers=1, num_heads=2,
                            head_dim=8, embed_dim=16, mlp_dim=32,
                            hyper_streams=4, **{field: value})
    with pytest.raises(NotImplementedError,
                       match=f"hyper_streams beside {field}"):
        jax.eval_shape(Transformer(cfg).init, jax.random.PRNGKey(0),
                       jnp.zeros((1, 4), jnp.int32))


def test_parallel_block_refuses_the_sequential_blocks_fields_by_name():
    cfg = TransformerConfig(vocab_size=32, num_layers=1, num_heads=2,
                            head_dim=8, embed_dim=16, mlp_dim=32,
                            parallel_block=True, residual_scaling=True)
    with pytest.raises(NotImplementedError,
                       match="parallel_block beside moe_router_dim or "
                             "residual_scaling"):
        jax.eval_shape(Transformer(cfg).init, jax.random.PRNGKey(0),
                       jnp.zeros((1, 4), jnp.int32))


def test_no_streams_is_the_model_it_was():
    """``hyper_streams`` 0 adds no parameter and changes no output: the same
    tree's keys and the same logits, to the bit, as with the field left
    out; 4 adds two hyper-connections a layer and nothing else."""
    fields = dict(vocab_size=48, num_layers=1, num_heads=2, head_dim=8,
                  embed_dim=16, mlp_dim=32, dtype=jnp.float32)
    tokens = jnp.arange(10).reshape(2, 5)
    plain, zero = (Transformer(TransformerConfig(**fields, **more))
                   for more in ({}, {"hyper_streams": 0}))
    p = jax.jit(plain.init)(jax.random.PRNGKey(1), tokens)
    q = jax.jit(zero.init)(jax.random.PRNGKey(1), tokens)
    assert jax.tree.structure(p) == jax.tree.structure(q)
    assert bool((jax.jit(plain.apply)(p, tokens)
                 == jax.jit(zero.apply)(q, tokens)).all())
    four = Transformer(TransformerConfig(**fields, hyper_streams=4))
    r = jax.jit(four.init)(jax.random.PRNGKey(1), tokens)
    assert set(r) == {"params"}       # nothing is sown at init
    added = set(r["params"]["layer_0"]) - set(p["params"]["layer_0"])
    assert added == {"attn_hc", "mlp_hc"}
    assert {k: v.shape for k, v in r["params"]["layer_0"]["mlp_hc"].items()} \
        == {"phi": (4, 16, 24), "bias": (24,), "alpha": (3,)}
    out, sown = jax.jit(lambda r, t: four.apply(
        r, t, mutable=[hyper.MHC_STATS]))(r, tokens)
    assert out.shape == (2, 5, 48)
    errs = jax.tree.leaves(sown[hyper.MHC_STATS])
    assert len(errs) == 1 and all(0 <= float(e) < 1e-2 for e in errs)
