"""The every-expert-here layout of ``models/moe.py`` (``num_experts`` > 0:
top-k, dropless, sort + grouped matmul) and the model fields that came with
it (``qk_norm``, ``norm_eps``): the layer against a dense
every-expert-on-every-token sum, its auxiliary losses against their formulas,
its names in a compiled step, and the dense model left as it was."""

import dataclasses

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from horovod_tpu.models import (MOE_LOSSES, MOE_STATS, MoEMLP, Transformer,
                                TransformerConfig, moe_aux_loss)
from horovod_tpu.utils import profiling

D, F, E, K = 64, 32, 8, 2
TINY = TransformerConfig(
    vocab_size=97, num_layers=2, num_heads=4, head_dim=16, embed_dim=D,
    mlp_dim=F, max_seq_len=64, dtype=jnp.float32, num_experts=E,
    experts_per_token=K, qk_norm=True, norm_eps=1e-5)


def layer(**kw):
    return MoEMLP(embed_dim=D, mlp_dim=F, axis_name=None, dtype=jnp.float32,
                  num_experts=E, experts_per_token=K, **kw)


def dense_sum(params, x, norm_topk_prob=False):
    """Every expert on every token, masked by the top-k probabilities."""
    p = params["params"]
    t = x.reshape(-1, D)
    probs = jax.nn.softmax(t @ p["router"], axis=-1)
    _, picks = jax.lax.top_k(probs, K)
    weights = probs * jax.nn.one_hot(picks, E).sum(1)
    if norm_topk_prob:
        weights = weights / weights.sum(-1, keepdims=True)
    hidden = nn.silu(jnp.einsum("td,edf->etf", t, p["gate"])) \
        * jnp.einsum("td,edf->etf", t, p["up"])
    out = jnp.einsum("etf,efd->etd", hidden, p["down"])
    return jnp.einsum("etd,te->td", out, weights).reshape(x.shape)


def forced_router(params, x):
    """Feature 0 of every token is 1, and the router reads it alone for two
    experts: every token picks expert 0 and none picks expert 3."""
    x = x.at[..., 0].set(1.0)
    router = params["params"]["router"]
    router = router.at[0, 0].set(30.0).at[0, 3].set(-30.0)
    return {"params": {**params["params"], "router": router}}, x


@pytest.mark.parametrize("norm_topk_prob", [False, True])
def test_layer_matches_the_dense_sum_forward_and_backward(norm_topk_prob):
    m = layer(norm_topk_prob=norm_topk_prob)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 24, D))
    params, x = forced_router(m.init(jax.random.PRNGKey(0), x), x)
    ct = jax.random.normal(jax.random.PRNGKey(2), x.shape)
    with jax.default_matmul_precision("highest"):
        out, sown = m.apply(params, x, mutable=[MOE_STATS])
        pairs = np.asarray(sown[MOE_STATS]["expert_pairs"][0])
        assert pairs[0] == 48 and pairs[3] == 0 and pairs.sum() == 48 * K
        np.testing.assert_allclose(out, dense_sum(params, x, norm_topk_prob),
                                   rtol=2e-5, atol=2e-6)
        got = jax.grad(lambda p, x: jnp.sum(m.apply(p, x) * ct),
                       argnums=(0, 1))(params, x)
        want = jax.grad(lambda p, x: jnp.sum(
            dense_sum(p, x, norm_topk_prob) * ct), argnums=(0, 1))(params, x)
    for (path, g), w in zip(jax.tree_util.tree_flatten_with_path(got)[0],
                            jax.tree.leaves(want)):
        err = float(jnp.linalg.norm(g - w) / jnp.linalg.norm(w))
        assert err < 1e-5, (jax.tree_util.keystr(path), err)
    # the empty expert's weights get no gradient, the router's column does
    assert not np.any(np.asarray(got[0]["params"]["gate"][3]))
    assert np.any(np.asarray(got[0]["params"]["router"][:, 3]))


def test_a_permuted_batch_gives_the_permuted_output():
    """Dropless: a token's result does not depend on what else is there."""
    m = layer()
    x = jax.random.normal(jax.random.PRNGKey(3), (1, 40, D))
    params = m.init(jax.random.PRNGKey(0), x)
    perm = jax.random.permutation(jax.random.PRNGKey(4), 40)
    np.testing.assert_allclose(m.apply(params, x[:, perm]),
                               m.apply(params, x)[:, perm],
                               rtol=1e-6, atol=1e-6)
    # and a token alone gets what it got in company
    np.testing.assert_allclose(m.apply(params, x[:, 7:8]),
                               m.apply(params, x)[:, 7:8],
                               rtol=1e-5, atol=1e-6)


def test_auxiliary_losses_are_their_formulas():
    m = layer()
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 16, D))
    params = m.init(jax.random.PRNGKey(0), x)
    _, sown = m.apply(params, x, mutable=[MOE_LOSSES, MOE_STATS])
    logits = x.reshape(-1, D) @ params["params"]["router"]
    probs = jax.nn.softmax(logits, -1)
    picks = np.asarray(sown[MOE_STATS]["picks"][0]).reshape(-1, K)
    np.testing.assert_array_equal(
        np.sort(picks, -1), np.sort(np.asarray(jax.lax.top_k(probs, K)[1]), -1))
    share = np.bincount(picks.ravel(), minlength=E) / picks.size
    np.testing.assert_array_equal(
        sown[MOE_STATS]["expert_pairs"][0], share * picks.size)
    assert float(sown[MOE_LOSSES]["load_balance"][0]) == pytest.approx(
        E * float(np.sum(share * np.asarray(probs.mean(0)))), rel=1e-5)
    assert float(sown[MOE_LOSSES]["router_z"][0]) == pytest.approx(
        float(jnp.mean(jax.nn.logsumexp(logits, -1) ** 2)), rel=1e-5)
    # perfectly even routing and uniform probabilities give 1, the minimum
    assert profiling.expert_load(share * picks.size)["pairs"] == picks.size
    # the helper a user's loss calls: the coefficients times the layers' means
    model = Transformer(TINY)
    tokens = jax.random.randint(jax.random.PRNGKey(6), (2, 16), 0, 97)
    p = model.init(jax.random.PRNGKey(0), tokens)
    assert set(p) == {"params"}             # init returns parameters only
    _, sown = model.apply(p, tokens, mutable=[MOE_LOSSES])
    terms = [sown[MOE_LOSSES][f"layer_{i}"]["moe_mlp"] for i in range(2)]
    assert float(moe_aux_loss(TINY, sown)) == pytest.approx(
        0.01 * float(sum(t["load_balance"][0] for t in terms)) / 2
        + 0.001 * float(sum(t["router_z"][0] for t in terms)) / 2, rel=1e-6)
    assert float(moe_aux_loss(TINY, {})) == 0.0


def test_expert_load_reads_the_sown_counts():
    load = profiling.expert_load(np.array([6, 0, 2, 0]))
    assert load == {"pairs": 8, "max_over_mean": 3.0, "empty_experts": 2}
    assert profiling.expert_load([0, 0])["max_over_mean"] == 0.0


def test_the_two_layouts_are_not_combined():
    x = jnp.zeros((1, 4, D))
    with pytest.raises(ValueError, match="every expert on each device"):
        MoEMLP(embed_dim=D, mlp_dim=F, num_experts=E).init(
            jax.random.PRNGKey(0), x)
    both = dataclasses.replace(TINY, moe_axis="ep")
    with pytest.raises(ValueError, match="two layouts"):
        Transformer(both).init(jax.random.PRNGKey(0),
                               jnp.zeros((1, 4), jnp.int32))


def test_the_four_names_reach_a_compiled_step_forward_and_backward():
    model = Transformer(TINY)
    tokens = jax.random.randint(jax.random.PRNGKey(6), (2, 16), 0, 97)
    params = model.init(jax.random.PRNGKey(0), tokens)

    def step(p, t):
        def loss_fn(p):
            logits, sown = model.apply(p, t, mutable=[MOE_LOSSES])
            return optax.softmax_cross_entropy_with_integer_labels(
                logits[:, :-1], t[:, 1:]).mean() + moe_aux_loss(TINY, sown)
        return jax.value_and_grad(loss_fn)(p)

    compiled = jax.jit(step).lower(params, tokens).compile()
    table = profiling.scope_table(compiled)
    names = [s.op_name for s in table.values()]
    for scope in profiling.MOE_SCOPES:
        assert any(f"/moe_mlp/{scope}/" in n and "transpose(" not in n
                   for n in names), scope
        assert any(f"/moe_mlp/{scope}/" in n and "transpose(" in n
                   for n in names), scope
        assert any(s.module == f"Transformer/layer_N/moe_mlp/{scope}"
                   for s in table.values()), scope
    # nothing of the layer is outside the four
    assert not [s.module for s in table.values()
                if s.module.endswith("/moe_mlp")]


RAGGED_HLO = """HloModule m
ENTRY %main (a: bf16[64,8]) -> f32[64,4] {
  %a = bf16[64,8]{1,0} parameter(0)
  %ragged-dot-metadata = (s32[9]{0}, s32[3]{0}) custom-call(%a), custom_call_target="tpu_custom_call", metadata={op_name="ragged-dot-metadata"}
  %ragged-dot-none.7 = f32[64,4]{1,0} custom-call(%a), custom_call_target="tpu_custom_call", metadata={op_name="ragged-dot-none"}
  ROOT %other = f32[64,4]{1,0} custom-call(%a), custom_call_target="tpu_custom_call", metadata={op_name="jit(f)/jvp(M)/attn/hvd_flash_fwd/pallas_call"}
}
"""


def test_xlas_own_grouped_matmul_kernels_get_the_experts_name_back():
    """XLA:TPU turns ``ragged_dot`` into kernels named afresh, the scope
    gone (deviceless v5e compile, PR 26)."""
    table = profiling.scope_table(RAGGED_HLO)
    assert table["ragged-dot-none.7"].kernel == profiling.MOE_EXPERTS
    assert table["ragged-dot-metadata"].kernel == profiling.MOE_EXPERTS
    assert table["other"].kernel == profiling.FLASH_FWD


def test_without_experts_the_dense_model_is_what_it_was():
    """Defaults reproduce the model before PR 26: the parameter tree has no
    new leaf and the new fields at their defaults change no bit."""
    dense = TransformerConfig(vocab_size=97, num_layers=2, num_heads=4,
                              head_dim=16, embed_dim=D, mlp_dim=F,
                              max_seq_len=64, dtype=jnp.float32)
    assert (dense.num_experts, dense.qk_norm, dense.norm_eps) == (0, False,
                                                                  1e-6)
    tokens = jax.random.randint(jax.random.PRNGKey(6), (2, 16), 0, 97)
    params = Transformer(dense).init(jax.random.PRNGKey(0), tokens)
    paths = {jax.tree_util.keystr(p) for p, _ in
             jax.tree_util.tree_flatten_with_path(params)[0]}
    layer0 = {f"['params']['layer_0']{rest}" for rest in (
        "['attn_norm']['scale']", "['mlp_norm']['scale']",
        "['attn']['q']['kernel']", "['attn']['k']['kernel']",
        "['attn']['v']['kernel']", "['attn']['o']['kernel']",
        "['mlp']['gate']['kernel']", "['mlp']['up']['kernel']",
        "['mlp']['down']['kernel']")}
    assert {p for p in paths if "layer_0" in p} == layer0
    out = Transformer(dense).apply(params, tokens)

    # the model as it was written before, norm by norm, with eps 1e-6
    def rms(x, scale):
        return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True)
                                 + 1e-6) * scale

    from horovod_tpu.models.transformer import (dense_causal_attention,
                                                rope)
    p = params["params"]
    x = p["embed"]["embedding"][tokens]
    pos = jnp.broadcast_to(jnp.arange(16)[None], tokens.shape)
    for i in range(2):
        lay = p[f"layer_{i}"]
        y = rms(x, lay["attn_norm"]["scale"])
        q, k, v = (jnp.einsum("bse,ehd->bshd", y, lay["attn"][n]["kernel"])
                   for n in "qkv")
        a = dense_causal_attention(rope(q, pos, 10000.0),
                                   rope(k, pos, 10000.0), v)
        x = x + jnp.einsum("bshd,hde->bse", a, lay["attn"]["o"]["kernel"])
        y = rms(x, lay["mlp_norm"]["scale"])
        x = x + (nn.silu(y @ lay["mlp"]["gate"]["kernel"])
                 * (y @ lay["mlp"]["up"]["kernel"])) \
            @ lay["mlp"]["down"]["kernel"]
    want = rms(x, p["final_norm"]["scale"]) @ p["lm_head"]["kernel"]
    np.testing.assert_allclose(out, want, rtol=2e-5, atol=2e-5)
    spelled = dataclasses.replace(dense, norm_eps=1e-6, qk_norm=False,
                                  num_experts=0)
    np.testing.assert_array_equal(out, Transformer(spelled).apply(params,
                                                                  tokens))
    # and eps is a field now: another value is another function
    other = dataclasses.replace(dense, norm_eps=1e-2)
    assert not np.array_equal(out, Transformer(other).apply(params, tokens))
