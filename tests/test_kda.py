"""Kimi Delta Attention on the serving path (PR 49): the chunked scan against
the token-by-token recurrence (decays near the lower bound, lengths that are
no whole chunks), the one-step form against the chunked one, a model of
"kda" and "latent_attention" layers with a bias-corrected group-limited
router through ``ServingEngine`` against the plain reference's full forward
(the state taken at the prompt's own length in a padded bucket, carried over
row blocks, untouched by an idle slot, rebuilt for a slot admitted anew), the
router's picks and weights against the reference's, the four chips' shares
of an expert layer against the uncut layer, and what refuses the mixer by
name."""

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

from benchmarks.families import kda_mla_moe_serve as family  # noqa: E402
from benchmarks.reference import kda_mla_moe_serve as reference  # noqa: E402
from horovod_tpu.models import Transformer, TransformerConfig  # noqa: E402
from horovod_tpu.models import transformer as T  # noqa: E402
from horovod_tpu.ops import kda_scan  # noqa: E402
from horovod_tpu.ops.kda_scan import (kda_chunked, kda_recurrent,  # noqa: E402
                                      kda_step)
from horovod_tpu.serving import ServingConfig, ServingEngine  # noqa: E402
from horovod_tpu.serving.engine import (PagedTransformerBackend,  # noqa: E402
                                        TransformerBackend)

from test_bench_ling import TINY, TRAFFIC  # noqa: E402


def drawn(key, b, s, h, dk, dv, near_bound):
    ks = jax.random.split(key, 6)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)  # noqa: E731
    q = unit(jax.random.normal(ks[0], (b, s, h, dk))) * dk ** -0.5
    k = unit(jax.random.normal(ks[1], (b, s, h, dk)))
    v = jax.random.normal(ks[2], (b, s, h, dv))
    # near the bound: most channels decay by e^-5 a step
    g = -5.0 * jax.nn.sigmoid(2 * jax.random.normal(ks[3], (b, s, h, dk))
                              + (6.0 if near_bound else 0.0))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (b, s, h)))
    return q, k, v, g, beta, jax.random.normal(ks[5], (b, h, dk, dv))


# heads, key width, value width: a tiny model's, which runs the XLA form, and
# the served cell's, which runs the kernel (interpret mode off the TPU)
XLA_WIDTHS, KERNEL_WIDTHS = (3, 16, 8), (2, 128, 128)
BOTH_FORMS = pytest.mark.parametrize(
    "widths", [XLA_WIDTHS, KERNEL_WIDTHS], ids=["xla", "kernel"])


def test_the_form_follows_from_the_shapes():
    """The rule: whole 128-lane tiles of keys and values, an even number
    of heads, chunks of 64 in sub-blocks of 16; anything else is XLA's."""
    form = kda_scan.scan_form
    assert form(*KERNEL_WIDTHS) == form(32, 128, 128, 64, 16) == "kernel"
    assert form(*XLA_WIDTHS) == "xla"
    assert form(TINY["num_attention_heads"], TINY["head_dim"],
                TINY["head_dim"]) == "xla"
    assert form(3, 128, 128) == form(2, 64, 128) == form(2, 128, 256) \
        == form(2, 128, 128, 32, 16) == form(2, 128, 128, 64, 8) == "xla"
    assert kda_scan.head_block(32) == 8 and kda_scan.head_block(6) == 6
    assert kda_scan.head_block(20) == 4


@pytest.mark.parametrize("near_bound", [False, True])
@pytest.mark.parametrize("s", [1, 17, 64, 100, 200])
@BOTH_FORMS
def test_the_chunked_form_is_the_recurrence(widths, s, near_bound):
    """(a): chunks of 64 in sub-blocks of 16, lengths that are no whole
    chunks, a state that enters, and decays at the lower bound, where
    e^(+-sum g) over a chunk is e^(+-320) and only pairwise decays are
    representable."""
    args = drawn(jax.random.PRNGKey(s), 2, s, *widths, near_bound)
    with jax.default_matmul_precision("highest"):
        o_r, s_r = kda_recurrent(*args)
        o_c, s_c = jax.jit(kda_chunked)(*args)
    assert bool(jnp.isfinite(o_c).all() and jnp.isfinite(s_c).all())
    np.testing.assert_allclose(o_c, o_r, atol=5e-6)
    np.testing.assert_allclose(s_c, s_r, atol=2e-5)
    if near_bound:
        assert float(jnp.min(args[3])) < -4.99


@BOTH_FORMS
def test_masked_positions_pass_the_state_unchanged(widths):
    """A position with decay 1 and step 0 changes no state: how a padded
    bucket hands over the state at the prompt's own length."""
    q, k, v, g, beta, s0 = drawn(jax.random.PRNGKey(5), 1, 90, *widths,
                                 False)
    live = jnp.arange(90) < 37
    _, masked = kda_chunked(q, k, v, jnp.where(live[None, :, None, None], g,
                                               0.0),
                            jnp.where(live[None, :, None], beta, 0.0), s0)
    _, short = kda_chunked(q[:, :37], k[:, :37], v[:, :37], g[:, :37],
                           beta[:, :37], s0)
    np.testing.assert_allclose(masked, short, atol=1e-6)


def test_a_state_carried_between_two_calls_is_one_call():
    """The kernel's state leaves a call as it would have stayed in VMEM:
    two row blocks of 1024 with the state handed on are one call of 2048
    (what ``_over_rows_carrying`` does with a long prompt)."""
    *rows, s0 = drawn(jax.random.PRNGKey(6), 1, 2048, *KERNEL_WIDTHS, False)
    scan = jax.jit(kda_chunked)
    o_whole, s_whole = scan(*rows, s0)
    o_1, s_1 = scan(*(x[:, :1024] for x in rows), s0)
    o_2, s_2 = scan(*(x[:, 1024:] for x in rows), s_1)
    np.testing.assert_array_equal(jnp.concatenate([o_1, o_2], 1), o_whole)
    np.testing.assert_array_equal(s_2, s_whole)


def test_the_kernel_form_is_differentiated_as_the_xla_form():
    """``jax.grad`` through the kernel (a ``custom_vjp`` whose backward is
    JAX's of the XLA form) is ``jax.grad`` through the XLA form."""
    args = drawn(jax.random.PRNGKey(8), 1, 128, *KERNEL_WIDTHS, False)
    weight = jax.random.normal(jax.random.PRNGKey(1), (1, 128, 2, 128))

    def loss(scan, *args):
        o, state = scan(*args)
        return (o * weight).sum() + (state ** 2).sum()

    every = tuple(range(6))
    ours = jax.jit(jax.grad(lambda *a: loss(kda_chunked, *a), every))(*args)
    xla = jax.jit(jax.grad(lambda *a: loss(kda_scan._scan_xla, *a), every))(
        *args)
    for name, mine, theirs in zip("q k v g beta state".split(), ours, xla):
        np.testing.assert_allclose(mine, theirs, rtol=1e-4, atol=2e-5,
                                   err_msg=name)


def test_the_one_step_form_is_the_chunked_form():
    """(b): the same tokens, a step at a time from the same state."""
    q, k, v, g, beta, s0 = drawn(jax.random.PRNGKey(9), 2, 70, 2, 16, 16,
                                 True)
    o_c, s_c = kda_chunked(q, k, v, g, beta, s0)
    state, outs = s0, []
    for t in range(70):
        o, state = kda_step(q[:, t], k[:, t], v[:, t], g[:, t], beta[:, t],
                            state)
        outs.append(o)
    np.testing.assert_allclose(jnp.stack(outs, 1), o_c, atol=5e-6)
    np.testing.assert_allclose(state, s_c, atol=2e-5)


@pytest.fixture(scope="module")
def built():
    """The tiny configuration of tests/test_bench_ling.py in float32: the
    family's draw in the reference's layout and in the program's."""
    cfg = dict(TINY)
    mcfg = dataclasses.replace(
        family.model_config(cfg, TRAFFIC), dtype=jnp.float32,
        param_dtype=jnp.float32)
    weights = jax.tree.map(lambda x: x.astype(jnp.float32),
                           family.draw(cfg, family.seed_key(3)))
    return cfg, mcfg, Transformer(mcfg), weights, family.to_program(
        weights, cfg)


def reference_logits(cfg, weights, tokens):
    n = len(tokens)
    return reference.logits_of_rows(
        weights, jnp.asarray(tokens, jnp.int32), cfg,
        tuple(cfg["experts_held"]), 0, n)[0]


def test_the_layers_held_and_the_pool_of_two_kinds(built):
    cfg, mcfg, *_ = built
    # published layers 1-4 of a period of 3, two leading dense layers
    assert mcfg.layer_kinds == ("kda", "latent_attention", "kda", "kda")
    assert mcfg.first_dense_layers == 1 and not mcfg.latent
    assert mcfg.cache_layout == (("kda", 0), ("latent", 0), ("kda", 1),
                                 ("kda", 2))
    first, second = T.init_kv_cache(mcfg, 3, 128)
    assert {k: (v.shape, v.dtype) for k, v in first.items()} == {
        "kda": ((3, 3, 4, 8, 8), jnp.float32),
        "latent": ((1, 3, 128, 8), jnp.float32)}
    assert {k: v.shape for k, v in second.items()} == {
        "kda": (3, 3, 3, 3 * 32), "latent": (1, 3, 128, 4)}
    # another model's pool is the two arrays it always was
    plain = TransformerConfig(vocab_size=20, num_layers=2, num_heads=2,
                              head_dim=16, embed_dim=32, mlp_dim=48)
    assert plain.cache_layout is None
    k, v = T.init_kv_cache(plain, 2, 16)
    assert k.shape == v.shape == (2, 2, 16, 2, 16)


def test_a_forward_pass_is_the_references(built):
    cfg, mcfg, model, weights, params = built
    tokens = np.random.default_rng(0).integers(0, 256, 45)
    ours = jax.jit(model.apply)(params, jnp.asarray(tokens)[None])[0]
    np.testing.assert_allclose(ours, reference_logits(cfg, weights, tokens),
                               atol=2e-4)


def engine_of(built, slots=3):
    cfg, mcfg, model, weights, params = built
    backend = TransformerBackend(model, params, mcfg, slots, 128)
    return backend, ServingEngine(backend, ServingConfig(
        num_slots=slots, buckets=(16, 32, 64), max_seq_len=128, eos_id=None,
        record_logits=True))


def test_prefill_in_a_padded_bucket_then_decode_is_the_full_forward(built):
    """(c): through ServingEngine; the state and the convolution's tail are
    the ones at the prompt's own length (21 in a bucket of 32), the bucket's
    padding is routed to no expert, and every decode step's logits are the
    reference's at that position of the whole sequence."""
    cfg, mcfg, model, weights, params = built
    backend, engine = engine_of(built)
    prompt = [int(t) for t in np.random.default_rng(1).integers(0, 256, 21)]
    req = engine.submit(prompt, 9)
    engine.run_until_idle()
    whole = reference_logits(cfg, weights, prompt + req.tokens)
    for i, logits in enumerate(req.logits):
        np.testing.assert_allclose(logits, whole[len(prompt) - 1 + i],
                                   atol=3e-4)
        assert req.tokens[i] == int(jnp.argmax(whole[len(prompt) - 1 + i]))
    # 21 positions x 3 sparse layers x 4 picks, and 8 decode steps of one slot
    assert backend.moe_counters["pairs"] == (21 + 8) * 3 * 4
    assert backend.kda_counters == {"kda_blocks": 1, "state_slots": 8}


def test_a_slot_admitted_anew_and_an_idle_slot_beside_a_live_one(built):
    """(d): one slot serves three requests in turn (each admission starts
    from its prefill's state alone), with two idle slots decoding beside it;
    a fresh engine with every slot busy gives each the same tokens."""
    rng = np.random.default_rng(2)
    prompts = [[int(t) for t in rng.integers(0, 256, n)]
               for n in (13, 30, 19)]
    _, one_by_one = engine_of(built)
    alone = []
    for p in prompts:           # the same slot, again and again
        r = one_by_one.submit(p, 7)
        one_by_one.run_until_idle()
        assert r.slot == 0
        alone.append((r.tokens, r.logits))
    _, together = engine_of(built)
    reqs = [together.submit(p, 7) for p in prompts]
    together.run_until_idle()
    assert sorted(r.slot for r in reqs) == [0, 1, 2]
    for r, (tokens, logits) in zip(reqs, alone):
        assert r.tokens == tokens
        np.testing.assert_allclose(np.stack(r.logits), np.stack(logits),
                                   atol=1e-5)


def test_a_state_carried_over_the_prompts_row_blocks(built):
    """The served prefill's loop (three row blocks of 1024 and more): a
    prompt that ends in the third block of a 4096 bucket hands over the
    state a pass over the prompt alone gives, the blocks past it not run."""
    # published layers 1 and 2 alone, a layer of each kind (the first dense,
    # the second sparse): the pool of two kinds crosses the row blocks as the
    # four layers' does, and the loops of two layers are traced, not of four
    cfg = dict(TINY, num_hidden_layers=2, layers_held=[1, 3])
    long = dataclasses.replace(
        family.model_config(cfg, TRAFFIC), dtype=jnp.float32,
        param_dtype=jnp.float32, max_seq_len=4200)
    assert long.layer_kinds == ("kda", "latent_attention")
    assert long.cache_layout == (("kda", 0), ("latent", 0))
    assert long.first_dense_layers == 1 and long.num_experts
    params = {"params": {k: v for k, v in built[4]["params"].items()
                         if k not in ("layer_2", "layer_3")}}
    model = Transformer(long)
    n = 2100
    tokens = jnp.asarray(np.random.default_rng(4).integers(0, 256, 4096))
    padded = tokens.at[n:].set(0)[None]
    kk, vv = T.init_kv_cache(long, 1, 4200)
    told = dict(return_kv=True, lengths=jnp.array([n]),
                valid=jnp.arange(4096)[None] < n,
                logits_at=jnp.array([n - 1]))
    looped, (k_loop, v_loop) = jax.jit(
        lambda p, t: model.apply(p, t, kv_into=(kk, vv, 0), **told))(
        params, padded)
    assert T.row_blocks(4096) == 4
    exact, (k_one, v_one) = jax.jit(lambda p, t: model.apply(
        p, t, return_kv=True, logits_at=jnp.array([n - 1])))(
        params, tokens[None, :n])
    np.testing.assert_allclose(looped, exact, atol=3e-4)
    np.testing.assert_allclose(k_loop["kda"][:, 0], k_one["kda"][:, 0],
                               atol=3e-4)
    np.testing.assert_allclose(v_loop["kda"][:, 0], v_one["kda"][:, 0],
                               atol=1e-5)
    np.testing.assert_allclose(k_loop["latent"][0, 0, :n],
                               k_one["latent"][0, 0], atol=3e-4)


def test_group_limited_bias_corrected_picks_against_the_reference(built):
    """(e): the program's router against the reference's on the same
    inputs, a bias that changes a pick and a group that is cut."""
    from horovod_tpu.models.moe import MOE_STATS, MoEMLP

    cfg = dict(TINY)
    n, k = 16, 4
    rng = np.random.default_rng(7)
    h = jnp.asarray(rng.normal(size=(1, 24, 32)), jnp.float32)
    w = {"router": jnp.asarray(rng.normal(size=(32, n)), jnp.float32),
         "expert_bias": jnp.asarray(0.3 * rng.normal(size=(n,)),
                                    jnp.float32)}
    layer = MoEMLP(embed_dim=32, mlp_dim=16, axis_name=None,
                   dtype=jnp.float32, num_experts=n, experts_per_token=k,
                   norm_topk_prob=True, selection="sigmoid",
                   routed_scale=2.5, expert_bias=True, groups=4,
                   topk_groups=2)
    params = layer.init(jax.random.PRNGKey(0), h)["params"]
    params = {**params, "router": w["router"],
              "expert_bias": w["expert_bias"]}
    _, sown = layer.apply({"params": params}, h, mutable=[MOE_STATS])
    ours = np.sort(np.asarray(sown[MOE_STATS]["picks"][0][0]), axis=-1)
    picks, weights = reference.route(h[0], w, cfg, lambda x: x)
    np.testing.assert_array_equal(ours, np.sort(np.asarray(picks), -1))
    np.testing.assert_allclose(weights.sum(-1), 2.5, rtol=1e-5)
    scores = jax.nn.sigmoid(h[0] @ w["router"])
    # the bias picks: somewhere the top-4 of s + b inside the kept groups is
    # not the top-4 of s inside them; and it never weighs
    plain, _ = reference.route(h[0], {**w, "expert_bias": jnp.zeros(n)}, cfg,
                               lambda x: x)
    assert (np.sort(np.asarray(plain), -1) != np.sort(np.asarray(picks),
                                                      -1)).any()
    np.testing.assert_allclose(
        weights, 2.5 * jnp.take_along_axis(scores, picks, -1)
        / jnp.take_along_axis(scores, picks, -1).sum(-1, keepdims=True),
        rtol=1e-5)
    # a group is cut: somewhere an expert outside the kept groups scores
    # above a pick, and every pick lies in two groups of four
    biased = scores + w["expert_bias"]
    cut = np.asarray(jnp.sort(biased, -1)[:, -k]) > np.asarray(
        jnp.take_along_axis(biased, picks, -1).min(-1)) + 1e-7
    assert cut.any()
    assert all(len({int(e) // 4 for e in row}) <= 2 for row in
               np.asarray(picks))
    with pytest.raises(ValueError, match="groups"):
        MoEMLP(embed_dim=32, mlp_dim=16, axis_name=None, num_experts=n,
               experts_per_token=k, groups=3, topk_groups=2).init(
            jax.random.PRNGKey(0), h)


def test_the_four_shares_add_up_to_the_uncut_layer():
    """(f): an expert layer of 16 experts on four chips of four; the parts
    the shares give, the shared expert counted once, add up to what the
    reference gives with every expert held."""
    cfg = dict(TINY, num_experts=16, experts_held=[0, 16])
    w = jax.tree.map(
        lambda x: x.astype(jnp.float32),
        family.draw_layer(cfg, "kda", False, jax.random.PRNGKey(11)))
    h = jnp.asarray(np.random.default_rng(3).normal(size=(20, 32)),
                    jnp.float32)
    mm = lambda x, w: x @ w  # noqa: E731
    with jax.default_matmul_precision("highest"):
        whole, picks = reference.feed_forward(h, w, cfg, (0, 16), mm,
                                              lambda x: x)
        shared = reference.glu(h, *(w["shared_experts"][n] for n in (
            "gate_proj", "up_proj", "down_proj")), mm)
    from horovod_tpu.models.moe import MoEMLP

    total = jnp.zeros_like(h)
    for lo in (0, 4, 8, 12):
        layer = MoEMLP(embed_dim=32, mlp_dim=16, axis_name=None,
                       dtype=jnp.float32, num_experts=16,
                       experts_per_token=4, norm_topk_prob=True,
                       selection="sigmoid", routed_scale=2.5,
                       num_shared_experts=1, experts_held=(lo, lo + 4),
                       expert_bias=True, groups=4, topk_groups=2)
        ex, sh = w["experts"], w["shared_experts"]
        params = {"router": w["router"], "expert_bias": w["expert_bias"],
                  **{n: ex[f"{n}_proj"][lo:lo + 4]
                     for n in ("gate", "up", "down")},
                  **{f"shared_{n}": sh[f"{n}_proj"]
                     for n in ("gate", "up", "down")}}
        total = total + layer.apply({"params": params}, h[None])[0] - shared
    np.testing.assert_allclose(total + shared, whole, atol=2e-5)
    assert len(np.unique(np.asarray(picks) // 4)) == 4  # every chip is used


def test_what_refuses_the_mixer_by_name(built):
    """(g): speculation's verify, the paged backend and the prefix cache."""
    cfg, mcfg, model, weights, params = built
    with pytest.raises(NotImplementedError, match="kda layer's recurrent"):
        T.init_kv_pages(mcfg, 4, 16)
    with pytest.raises(NotImplementedError, match="prefix cache"):
        PagedTransformerBackend(model, params, mcfg, 2, 128, cache_pages=4)
    backend = TransformerBackend(model, params, mcfg, 2, 128)
    with pytest.raises(NotImplementedError,
                       match="a kda layer decodes one position"):
        backend.verify(np.zeros((2, 3), np.int32), np.ones((2,), np.int32))
    with pytest.raises(NotImplementedError, match="kda layers beside"):
        dataclasses.replace(mcfg, layer_types=(
            "kda", "eva_attention", "kda", "kda")).cache_layout
    mamba = dataclasses.replace(mcfg, layer_types=(
        "kda", "mamba", "kda", "kda"))
    with pytest.raises(NotImplementedError, match="kda layers beside"):
        mamba.cache_layout
    with pytest.raises(ValueError, match="kda_heads"):
        Transformer(dataclasses.replace(mcfg, kda_heads=0)).init(
            jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    # latent attention beside plain attention is still no pool
    with pytest.raises(NotImplementedError, match="one shape"):
        dataclasses.replace(mcfg, layer_types=(
            "latent_attention", "attention", "kda", "kda")).latent
