"""Compressed convolutional attention and the MLP router with a carried
state on the serving path (PR 52): the mixer and a whole model of "cca"
layers against the plain reference (``benchmarks/reference/
cca_moe_serve.py``), in float32 so that the tolerances are rounding's and
not bfloat16's -- without a cache (prefill then decode through
``ServingEngine`` and the one-loop prefill over row blocks against the
one-piece form are ``tests/test_cca_served.py``'s); the value shift and both convolutions at
position 0 and across a row-block boundary; the q-k mean at 8 over 2; the
router's state through three layers whole, by row blocks and a position a
step; top-1 with a pick bias that changes the pick and not the gate; and
what refuses the mixer by name."""

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

from benchmarks.families import cca_moe_serve as family  # noqa: E402
from benchmarks.reference import cca_moe_serve as reference  # noqa: E402
from horovod_tpu.models import Transformer  # noqa: E402
from horovod_tpu.models import transformer as T  # noqa: E402
from horovod_tpu.models.cca import CCAMixer, cca_sizes  # noqa: E402
from horovod_tpu.models.moe import MOE_STATS, MoEMLP  # noqa: E402
from horovod_tpu.serving.engine import (PagedTransformerBackend,  # noqa: E402
                                        TransformerBackend)

from test_bench_zaya import TINY, TRAFFIC  # noqa: E402

F32 = jnp.float32


def floated(tree):
    return jax.tree.map(lambda x: x.astype(F32), tree)


@pytest.fixture(scope="module")
def built():
    """The tiny configuration of tests/test_bench_zaya.py in float32: the
    family's draw in the reference's layout and in the program's."""
    cfg = dict(TINY)
    mcfg = dataclasses.replace(family.model_config(cfg, TRAFFIC), dtype=F32,
                               param_dtype=F32)
    weights = floated(family.draw(cfg, family.seed_key(3)))
    return cfg, mcfg, Transformer(mcfg), weights, family.to_program(
        weights, cfg)


def reference_logits(cfg, weights, tokens):
    return reference.logits_of_rows(
        weights, jnp.asarray(tokens, jnp.int32), cfg, 0, len(tokens))[0]


def mixer_of(built, layer=1):
    cfg, mcfg, _, weights, params = built
    return (CCAMixer(mcfg), {"params": params["params"][f"layer_{layer}"][
        "cca"]}, weights["layers"][layer]["cca"])


def reference_cca(cfg, w, h):
    with jax.default_matmul_precision("highest"):
        return reference.cca(h, w, cfg, lambda x, w: x @ w.astype(F32),
                             lambda x: x, None)


def test_the_pool_of_rows_and_a_tail(built):
    cfg, mcfg, *_ = built
    assert mcfg.layer_kinds == ("cca",) * 3 and not mcfg.latent
    assert mcfg.cache_layout == (("cca", 0), ("cca", 1), ("cca", 2))
    first, second = T.init_kv_cache(mcfg, 3, 128)
    # rows in the compute dtype, the tail float32 whatever it is
    bf = T.init_kv_cache(dataclasses.replace(mcfg, dtype=jnp.bfloat16), 3,
                         128)
    assert {k: (v.shape, v.dtype) for k, v in bf[0].items()} == {
        "cca": ((3, 3, 128, 16), jnp.bfloat16),
        "cca_tail": ((3, 3, 2, 48), jnp.float32)}
    assert {k: (v.shape, v.dtype) for k, v in bf[1].items()} == {
        "cca": ((3, 3, 128, 16), jnp.bfloat16),
        "cca_tail": ((3, 3, 1, 8), jnp.float32)}
    assert first["cca"].dtype == F32
    sizes = cca_sizes(dataclasses.replace(
        mcfg, num_heads=8, num_kv_heads=2, head_dim=128, dtype=jnp.bfloat16))
    # the published widths: 1024 bytes a position, 2688 values a tail
    assert sizes["bytes_per_token_and_layer"] == 1024
    assert sizes["tail_values_per_layer_and_slot"] == 2688
    assert (sizes["query_width"], sizes["key_width"], sizes["channels"],
            sizes["rotary_channels"]) == (1024, 256, 1280, 64)


def test_the_mixer_is_the_references_layer(built):
    """Without a cache, one layer's mixer alone: float32 against float32 at
    the highest precision, so the tolerance is the order of the sums."""
    cfg, *_ = built
    mixer, params, w = mixer_of(built)
    h = jax.random.normal(jax.random.PRNGKey(0), (1, 37, 32), F32)
    ours = mixer.apply(params, h, jnp.arange(37)[None])[0]
    np.testing.assert_allclose(ours, reference_cca(cfg, w, h[0]), atol=2e-5)


def test_a_forward_pass_is_the_references(built):
    cfg, mcfg, model, weights, params = built
    tokens = np.random.default_rng(0).integers(0, 256, 45)
    ours = jax.jit(model.apply)(params, jnp.asarray(tokens)[None])[0]
    np.testing.assert_allclose(ours, reference_logits(cfg, weights, tokens),
                               atol=2e-4)


def test_the_shift_and_both_convolutions_at_the_start_and_across_blocks(
        built):
    """Position 0 sees zeros before it (not the bias of the first
    convolution); and the mixer over [0, 1024) and then, from its tail, over
    [1024, 1100) is the mixer over [0, 1100): the row block's boundary is
    invisible."""
    cfg, mcfg, *_ = built
    mixer, params, w = mixer_of(built)
    h = jax.random.normal(jax.random.PRNGKey(1), (1, 1100, 32), F32)
    pos = jnp.arange(1100)[None]
    rows_of = jax.jit(lambda p, h, pos: mixer.apply(p, h, pos,
                                                    return_kv=True))
    whole, (k_all, v_all) = rows_of(params, h, pos)
    # position 0 alone is position 0 of the whole: nothing leaks backwards
    alone, (k_0, v_0) = rows_of(params, h[:, :1], pos[:, :1])
    np.testing.assert_allclose(k_0["cca"][0, 0], k_all["cca"][0, 0],
                               atol=1e-6)
    np.testing.assert_allclose(
        alone[0, 0], reference_cca(cfg, w, h[0, :1])[0], atol=2e-5)
    # ... and its shifted value half is exactly 0 (a row is its two KV
    # heads side by side, each [this position's half ; the one before's])
    by_head = np.asarray(v_0["cca"][0, 0]).reshape(2, 8)
    assert not by_head[:, 4:].any() and by_head[:, :4].all()
    # across the boundary: the first block's tail, then decode steps
    _, (k_first, v_first) = mixer.apply(
        params, h[:, :1024], pos[:, :1024], return_kv=True,
        lengths=jnp.array([1024]))
    np.testing.assert_array_equal(k_first["cca_tail"], jax.tree.map(
        lambda x: x, mixer.apply(params, h, pos, return_kv=True,
                                 lengths=jnp.array([1024]))[1][0]["cca_tail"]))
    rows = {"cca": jnp.zeros((1, 1, 1100, 16), F32).at[0, :, :1024].set(
        k_first["cca"])}
    first = {**rows, "cca_tail": k_first["cca_tail"][None]}
    second = {"cca": jnp.zeros((1, 1, 1100, 16), F32).at[0, :, :1024].set(
        v_first["cca"]), "cca_tail": v_first["cca_tail"][None]}
    step = jax.jit(lambda p, h, pos, first, second, t: mixer.apply(
        p, h, pos, cache=(first, second, t, 0)))
    for t in range(1024, 1030):
        out, (first, second) = step(params, h[:, t:t + 1], pos[:, t:t + 1],
                                    first, second, jnp.array([t]))
        np.testing.assert_allclose(out[0, 0], whole[0, t], atol=2e-5)
        np.testing.assert_allclose(first["cca"][0, 0, t], k_all["cca"][0, t],
                                   atol=1e-5)
        np.testing.assert_allclose(second["cca"][0, 0, t],
                                   v_all["cca"][0, t], atol=1e-5)


def test_the_qk_mean_at_eight_over_two():
    """The published grouping, 8 query over 2 key heads: with both
    convolutions silenced (taps and biases 0) a query head is the mean of
    its own plain projection and its KEY head's, a key head the mean of its
    four query heads' mean and its own: checked through the keys the mixer
    hands back, against the reference."""
    cfg = dict(TINY, num_attention_heads=8, num_key_value_heads=2)
    mcfg = dataclasses.replace(family.model_config(cfg, TRAFFIC), dtype=F32,
                               param_dtype=F32)
    w = floated(family.draw_layer(cfg, False, jax.random.PRNGKey(5)))["cca"]
    w = dict(w, conv0=w["conv0"] * 0, conv0_bias=w["conv0_bias"] * 0,
             conv1=w["conv1"] * 0, conv1_bias=w["conv1_bias"] * 0)
    params = {"params": family.layer_to_program(
        {**floated(family.draw_layer(cfg, False, jax.random.PRNGKey(5))),
         "cca": w}, cfg)["cca"]}
    h = jax.random.normal(jax.random.PRNGKey(2), (1, 9, 32), F32)
    out, (k, _) = jax.jit(lambda p, h: CCAMixer(mcfg).apply(
        p, h, jnp.arange(9)[None], return_kv=True))(params, h)
    np.testing.assert_allclose(out[0], reference_cca(cfg, w, h[0]),
                               atol=2e-5)
    q_plain = (h[0] @ w["q_proj"]).reshape(9, 2, 4, 8)
    k_plain = (h[0] @ w["k_proj"]).reshape(9, 2, 8)
    mean = (q_plain.mean(axis=2) + k_plain) / 2
    unit = mean * jax.lax.rsqrt(jnp.mean(mean * mean, -1, keepdims=True)
                                + 1e-5) * w["k_scale"][:, None]
    # the channels past the rotary share are not rotated
    np.testing.assert_allclose(k["cca"][0].reshape(9, 2, 8)[..., 4:],
                               unit[..., 4:], atol=1e-5)


def test_the_routers_state_whole_by_row_blocks_and_a_step_at_a_time(built):
    """Three layers of the MLP router alone, each handed the state of the
    one before: over the whole sequence, over its row blocks (``valid``
    given over more than two blocks: ``by_rows``) and one position a call
    give the same picks and, to float32's rounding, the same state; the
    first layer has no decay to learn."""
    cfg, mcfg, _, weights, params = built
    s = 3 * T.ROW_BLOCK
    moe = MoEMLP(embed_dim=32, mlp_dim=24, axis_name=None, dtype=F32,
                 num_experts=4, experts_per_token=1, expert_bias=True,
                 router_dim=16, norm_eps=1e-5, param_dtype=F32)
    x = jax.random.normal(jax.random.PRNGKey(3), (1, s, 32), F32)
    assert "router_decay" not in params["params"]["layer_0"]["moe_mlp"]

    def through(x, **told):
        state, picks = None, []
        for i in range(3):
            (_, state), sown = moe.apply(
                {"params": params["params"][f"layer_{i}"]["moe_mlp"]}, x,
                router_state=state, mutable=[MOE_STATS], **told)
            picks.append(sown[MOE_STATS]["picks"][0])
        return state, jnp.stack(picks)

    whole, picks = through(x)
    blocks, picks_b = through(x, valid=jnp.ones((1, s), bool))
    np.testing.assert_array_equal(picks, picks_b)
    np.testing.assert_allclose(whole, blocks, atol=1e-5)
    for t in (0, 1, T.ROW_BLOCK, s - 1):
        one, picks_t = through(x[:, t:t + 1])
        np.testing.assert_array_equal(picks_t[:, :, 0], picks[:, :, t])
        np.testing.assert_allclose(one[0, 0], whole[0, t], atol=1e-5)
    # ... and it is the reference's state and picks
    h, state, ref_picks = x[0], None, []
    for i in range(3):
        w = weights["layers"][i]
        pick, _, state = reference.route(h, state, w["router"],
                                         w["expert_bias"], cfg, lambda x: x)
        ref_picks.append(pick)
    np.testing.assert_allclose(whole[0], state, atol=1e-4)
    agree = (jnp.stack(ref_picks) == picks[:, 0, :, 0]).mean()
    assert agree > 0.995        # (a near-tie may fall either way)


def test_top1_with_a_pick_bias_that_changes_the_pick_and_not_the_gate(built):
    cfg, mcfg, _, weights, params = built
    layer = dict(params["params"]["layer_0"]["moe_mlp"])
    moe = MoEMLP(embed_dim=32, mlp_dim=24, axis_name=None, dtype=F32,
                 num_experts=4, experts_per_token=1, expert_bias=True,
                 router_dim=16, norm_eps=1e-5, param_dtype=F32)
    x = jax.random.normal(jax.random.PRNGKey(4), (1, 64, 32), F32)

    def run(bias):
        (out, _), sown = moe.apply(
            {"params": {**layer, "expert_bias": bias}}, x,
            mutable=[MOE_STATS])
        return out[0], sown[MOE_STATS]["picks"][0][0, :, 0]

    plain, picks = run(jnp.zeros((4,), F32))
    forced, picks_f = run(jnp.array([0.0, 0.0, 10.0, 0.0], F32))
    assert len(set(np.asarray(picks).tolist())) > 1
    assert (np.asarray(picks_f) == 2).all()     # the bias decides the pick
    # ... and never the weight: expert 2's output times p[2], the softmax's
    # own probability, which no bias entered
    w = weights["layers"][0]
    _, _, _ = reference.route(x[0], None, w["router"], w["expert_bias"], cfg,
                              lambda x: x)
    f = lambda name: w["router"][name]  # noqa: E731
    with jax.default_matmul_precision("highest"):
        r = x[0] @ f("down") + f("down_bias")
        y = reference.rms_norm(r, f("norm"), 1e-5)
        y = jax.nn.gelu(y @ f("w1") + f("b1"), approximate=False)
        y = jax.nn.gelu(y @ f("w2") + f("b2"), approximate=False)
        p = jax.nn.softmax(y @ f("w3"), axis=-1)
        ex = w["experts"]
        expert = (jax.nn.silu(x[0] @ ex["gate_proj"][2])
                  * (x[0] @ ex["up_proj"][2])) @ ex["down_proj"][2]
    np.testing.assert_allclose(forced, p[:, 2:3] * expert, atol=2e-5)
    same = np.asarray(picks) == 2
    np.testing.assert_allclose(forced[same], plain[same], atol=1e-6)


def test_what_refuses_the_mixer_by_name(built):
    """Pages, prefix snapshots and speculation's verify."""
    cfg, mcfg, model, weights, params = built
    with pytest.raises(NotImplementedError, match="cca layer's tail"):
        T.init_kv_pages(mcfg, 4, 16)
    with pytest.raises(NotImplementedError, match="prefix cache"):
        PagedTransformerBackend(model, params, mcfg, 2, 128, cache_pages=4)
    backend = TransformerBackend(model, params, mcfg, 2, 128)
    with pytest.raises(NotImplementedError,
                       match="a cca layer decodes one position"):
        backend.verify(np.zeros((2, 3), np.int32), np.ones((2,), np.int32))
    with pytest.raises(NotImplementedError, match="cca layers beside"):
        dataclasses.replace(mcfg, layer_types=(
            "cca", "eva_attention", "cca")).cache_layout
    with pytest.raises(ValueError, match="cca_taps of 2 or more"):
        Transformer(dataclasses.replace(mcfg, cca_taps=(1, 2))).init(
            jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    with pytest.raises(NotImplementedError, match="sequential block"):
        Transformer(dataclasses.replace(mcfg, parallel_block=True)).init(
            jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
