"""Latent attention (MLA) behind a leading dense layer and scaled sigmoid
routing, on the program's normal path against the plain reference
(``benchmarks/reference/mla_moe_serve.py``) at a small size, float32 on the
CPU (PR 42): the full forward; prefill then decode through the cache of
latents; the absorbed form against the expanded one on the same weights; the
sixteen shares of a layer adding up to the uncut layer; the gates' 2.5; YaRN's
frequencies and scale against a table written by hand; the flash forward at
key width 192 / value width 128, and everything else of the kernels refusing
that shape by name.

Tolerances.  Program and reference both compute in float32 here, in another
order (fused projections, a grouped matmul over sorted rows, the query
carried into the latent space before the scores): they agree to 2e-6 of the
logits' size, so the bound is 2e-4.  Each fault below moves the logits by 2%
or more and reads a failure.  With bfloat16 operands, the precision below
the one stated here, the same comparison reads 2e-3 or more and fails too.
"""

import dataclasses
import importlib
import math
import os
import sys
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.run import load_module  # noqa: E402
from horovod_tpu.models import Transformer  # noqa: E402
from horovod_tpu.models.moe import MOE_STATS, MoEMLP  # noqa: E402
from horovod_tpu.models.transformer import (  # noqa: E402
    TransformerConfig, dense_causal_attention, init_kv_cache, init_kv_pages,
    yarn_frequencies, yarn_mscale)

# (``horovod_tpu.ops.flash_attention`` the attribute is the function)
fa = importlib.import_module("horovod_tpu.ops.flash_attention")

TOL = 2e-4
CFG = {"family": "mla_moe_serve", "model_type": "axk1",
       "attention_bias": False, "first_k_dense_replace": 1,
       "hidden_act": "silu", "hidden_size": 32, "intermediate_size": 48,
       "kv_lora_rank": 8, "moe_intermediate_size": 16, "moe_layer_freq": 1,
       "n_group": 8, "n_routed_experts": 16,
       "n_routed_experts_published": 16, "experts_held": [0, 16],
       "n_shared_experts": 1, "norm_topk_prob": True,
       "num_attention_heads": 4, "num_experts_per_tok": 4,
       "num_hidden_layers": 3, "num_key_value_heads": 4, "q_lora_rank": 16,
       "qk_nope_head_dim": 8, "qk_rope_head_dim": 4, "rms_norm_eps": 1e-6,
       "rope_theta": 10000,
       "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 32,
                        "mscale": 1, "mscale_all_dim": 1,
                        "original_max_position_embeddings": 16,
                        "type": "yarn"},
       "routed_scaling_factor": 2.5, "scoring_func": "sigmoid",
       "tie_word_embeddings": False, "topk_group": 4, "topk_method": "none",
       "v_head_dim": 6, "vocab_size": 64, "initializer_range": 0.3}
TRAFFIC = {"max_seq_len": 64}
S = 40


@pytest.fixture(scope="module")
def family():
    return load_module("families", "mla_moe_serve")


@pytest.fixture(scope="module")
def reference():
    return load_module("reference", "mla_moe_serve")


def held(cfg, lo, hi):
    return {**cfg, "experts_held": [lo, hi], "n_routed_experts": hi - lo}


def f32(tree):
    return jax.tree.map(lambda x: x.astype(jnp.float32), tree)


def setup(family, cfg, seed=0):
    """(the program's model in float32, its config, its params, the same
    weights in the reference's layout), drawn as the benchmark draws them."""
    mcfg = dataclasses.replace(family.model_config(cfg, TRAFFIC),
                               dtype=jnp.float32, param_dtype=jnp.float32)
    w = f32(family.draw(cfg, family.seed_key(seed)))
    # norm scales off 1, so that every norm's scale is in the comparison
    bump = lambda n, i: 1.0 + 0.1 * jnp.cos(jnp.arange(n) + i)  # noqa: E731
    for i, layer in enumerate(w["layers"]):
        for j, name in enumerate(("input_layernorm", "q_a_layernorm",
                                  "kv_a_layernorm",
                                  "post_attention_layernorm")):
            layer[name] = bump(layer[name].shape[0], 4 * i + j)
    w["norm"] = bump(cfg["hidden_size"], 99)
    return Transformer(mcfg), mcfg, family.to_program(w, cfg), w


def tokens_of(seed, n=S, vocab=64):
    return jax.random.randint(jax.random.PRNGKey(seed), (n,), 0, vocab)


def rel(got, want):
    return float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))


def reference_logits(reference, w, tokens, cfg, **kw):
    return reference.logits_of_rows(
        w, tokens, cfg, tuple(cfg["experts_held"]), 0, tokens.shape[0],
        **kw)[0]


@pytest.mark.parametrize("share", [(0, 16), (4, 8)])
def test_full_forward_matches_the_reference(family, reference, share):
    cfg = held(CFG, *share)
    model, mcfg, params, w = setup(family, cfg)
    tokens = tokens_of(1)
    want = reference_logits(reference, w, tokens, cfg)
    assert rel(jax.jit(model.apply)(params, tokens[None])[0], want) < TOL
    # the reference in query blocks is the reference
    blocked = reference_logits(reference, w, tokens, cfg, query_block=8)
    assert rel(blocked, want) < 1e-5
    # the dense layer beside the sparse ones, each with its own width
    p = params["params"]
    assert "mlp" in p["layer_0"] and "moe_mlp" not in p["layer_0"]
    assert p["layer_0"]["mlp"]["gate"]["kernel"].shape == (32, 48)
    for i in (1, 2):
        assert "mlp" not in p[f"layer_{i}"]
        moe = p[f"layer_{i}"]["moe_mlp"]
        assert moe["gate"].shape == (share[1] - share[0], 32, 16)
        assert moe["shared_gate"].shape == (32, 16)
        assert moe["router"].shape == (32, 16)
    assert jax.tree.map(jnp.shape, params) == jax.tree.map(
        lambda x: x.shape,
        jax.eval_shape(model.init, jax.random.PRNGKey(0), tokens[None]))
    # the feed-forward a chunk at a time, and the head on one position
    chunked = Transformer(dataclasses.replace(mcfg, feed_forward_chunk=16))
    assert rel(jax.jit(chunked.apply)(params, tokens[None])[0], want) < TOL
    at = jax.jit(chunked.apply)(params, tokens[None],
                                logits_at=jnp.array([29]))
    assert at.shape == (1, 64) and rel(at[0], want[29]) < TOL


FAULTS = {
    "gates without the routed scale": {"moe_routed_scale": 1.0},
    "gates not normalised": {"norm_topk_prob": False},
    "softmax selection": {"moe_selection": "softmax"},
    "no YaRN": {"rope_yarn": None},
    "YaRN's frequencies without its scale": {
        "attention_scale": (8 + 4) ** -0.5},
    "half-split rotary pairs": {"rope_interleaved": False},
    "no dense layer's width": {"mlp_dim": 16, "moe_mlp_dim": 48},
}


@pytest.fixture(scope="module")
def sound(family, reference):
    """The uncut model's draw, its tokens and the reference's logits of them:
    the same for every fault below, which changes the program alone."""
    model, mcfg, params, w = setup(family, CFG)
    tokens = tokens_of(1)
    return mcfg, params, tokens, reference_logits(reference, w, tokens, CFG)


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_each_piece_fails_the_comparison_when_wrong(sound, fault):
    mcfg, params, tokens, want = sound
    wrong = Transformer(dataclasses.replace(mcfg, **FAULTS[fault]))
    if fault == "no dense layer's width":
        with pytest.raises(Exception, match="shape|Shape"):
            wrong.apply(params, tokens[None])
        return
    assert rel(jax.jit(wrong.apply)(params, tokens[None])[0], want) > 0.02, \
        fault


def test_a_lower_precision_than_stated_fails(family, reference):
    _, _, _, w = setup(family, CFG)
    tokens = tokens_of(1)
    want = reference_logits(reference, w, tokens, CFG)
    low = reference_logits(reference, w, tokens, CFG,
                           operand_dtype=jnp.bfloat16)
    assert rel(low, want) > 10 * TOL


@pytest.mark.parametrize("share", [(0, 16), (8, 12)])
def test_prefill_then_decode_through_the_latent_cache(family, reference,
                                                      share):
    """The cache path (``return_kv`` prefill of 12 tokens, expanded, then 28
    decode steps, absorbed, over a pool of latents) at every decoded
    position against the reference's full forward over the whole sequence.
    The pool holds latents and one rotary key a token: never K and V."""
    cfg = held(CFG, *share)
    model, mcfg, params, w = setup(family, cfg)
    tokens = tokens_of(2)
    want = reference_logits(reference, w, tokens, cfg)
    prompt = 12
    logits, (lat, rk) = jax.jit(lambda p, t: model.apply(
        p, t, return_kv=True))(params, tokens[None, :prompt])
    assert rel(logits[0], want[:prompt]) < TOL
    assert lat.shape == (3, 1, prompt, 8) and rk.shape == (3, 1, prompt, 4)
    kk, vv = init_kv_cache(mcfg, 2, 64)
    assert kk.shape == (3, 2, 64, 8) and vv.shape == (3, 2, 64, 4)
    kk = kk.at[:, 1, :prompt].set(lat[:, 0])    # slot 1; slot 0 stays empty
    vv = vv.at[:, 1, :prompt].set(rk[:, 0])
    step = jax.jit(lambda kk, vv, tok, n: model.apply(
        params, tok[:, None], kv_cache=(kk, vv), lengths=n))
    for pos in range(prompt, S):
        out, (kk, vv) = step(kk, vv, jnp.array([0, tokens[pos]]),
                             jnp.array([0, pos]))
        assert rel(out[1], want[pos]) < TOL, pos
    # the paged pool of latents is not built, and says so
    with pytest.raises(NotImplementedError, match="paged pool of latents"):
        init_kv_pages(mcfg, 4, 16)
    mixed = dataclasses.replace(mcfg, layer_types=(
        "latent_attention", "attention", "latent_attention"))
    with pytest.raises(NotImplementedError, match="one shape for all"):
        init_kv_cache(mixed, 2, 64)


def test_absorbed_is_expanded_on_the_same_weights(family):
    """One parameter tree, two forms: the whole sequence as ONE block of a
    cache call (absorbed: scores and the weighted sum over the latents) gives
    the logits of the pass without a cache (expanded: K and V built)."""
    model, mcfg, params, _ = setup(family, CFG)
    tokens = tokens_of(3)
    expanded = jax.jit(model.apply)(params, tokens[None])
    kk, vv = init_kv_cache(mcfg, 1, 64)
    absorbed, (kk, vv) = jax.jit(lambda p, t, kk, vv: model.apply(
        p, t, kv_cache=(kk, vv), lengths=jnp.array([0])))(
        params, tokens[None], kk, vv)
    assert rel(absorbed, expanded) < 1e-5
    # and the block left in the pool what a prefill hands back
    _, (lat, rk) = jax.jit(lambda p, t: model.apply(p, t, return_kv=True))(
        params, tokens[None])
    assert rel(kk[:, :, :S], lat) < 1e-5 and rel(vv[:, :, :S], rk) < 1e-5


def layer_of(cfg, lo, hi, w):
    """(MoEMLP holding experts lo..hi-1, its params) from one reference
    layer's weights ``w`` (every expert)."""
    ex, sh = w["experts"], w["shared_experts"]
    m = MoEMLP(embed_dim=cfg["hidden_size"],
               mlp_dim=cfg["moe_intermediate_size"], axis_name=None,
               dtype=jnp.float32,
               num_experts=cfg["n_routed_experts_published"],
               experts_per_token=cfg["num_experts_per_tok"],
               norm_topk_prob=True, selection="sigmoid",
               num_shared_experts=1, experts_held=(lo, hi),
               routed_scale=cfg["routed_scaling_factor"])
    params = {"params": {
        "router": w["router"], "gate": ex["gate_proj"][lo:hi],
        "up": ex["up_proj"][lo:hi], "down": ex["down_proj"][lo:hi],
        "shared_gate": sh["gate_proj"], "shared_up": sh["up_proj"],
        "shared_down": sh["down_proj"]}}
    return m, params


def test_the_sixteen_shares_add_up_to_the_uncut_layer(family, reference):
    """Every share routes over all 16 experts and computes its own one; the
    routed parts of the sixteen shares plus the shared expert, which every
    chip computes alike, counted once, are the uncut reference's layer; and
    the gates carry the routed scale."""
    w = f32(family.draw(CFG, family.seed_key(3)))["layers"][1]
    h = jax.random.normal(jax.random.PRNGKey(4), (1, 24, 32))
    ident = lambda x: x  # noqa: E731
    with jax.default_matmul_precision("highest"):
        mm = lambda x, y: x @ y  # noqa: E731
        whole, picks = reference.feed_forward(h[0], w, CFG, (0, 16), mm,
                                              ident)
        sh = w["shared_experts"]
        shared = reference.glu(h[0], sh["gate_proj"], sh["up_proj"],
                               sh["down_proj"], mm)
        plain, _ = reference.feed_forward(
            h[0], w, {**CFG, "routed_scaling_factor": 1.0}, (0, 16), mm,
            ident)
    # the routed part is 2.5 times what unscaled gates give
    assert rel(whole - shared, 2.5 * (plain - shared)) < 1e-5
    total = shared
    for c in range(16):
        m, params = layer_of(CFG, c, c + 1, w)
        part, sown = m.apply(params, h, mutable=[MOE_STATS])
        total = total + (part[0] - shared)
        got = np.sort(np.asarray(sown[MOE_STATS]["picks"][0][0]), -1)
        assert (got == np.sort(np.asarray(picks), -1)).all()
        assert sown[MOE_STATS]["expert_pairs"][0].tolist() == [
            int((np.asarray(picks) == c).sum())]
    assert rel(total, whole) < 1e-5
    # the program's own layer without the scale is the plain one
    m, params = layer_of({**CFG, "routed_scaling_factor": 1.0}, 0, 16, w)
    assert rel(m.apply(params, h)[0], plain) < 1e-5


def test_yarn_against_a_table_written_by_hand():
    """A.X-K1's own numbers: 32 pairs of a 64-wide rotary key, theta 10000,
    factor 32 over 4096 original positions, beta_fast 32, beta_slow 1.  The
    correction dimensions: 64 ln(4096 / (32 * 2 pi)) / (2 ln 10000) = 10.47
    -> 10, and with 1 turn 22.51 -> 23.  Pairs 0..10 keep theta^(-2i/64),
    pairs 23.. have it over 32, pair 16 is 6/13 of the way."""
    yarn = (32, 4096, 32, 1, 1, 1)
    freq, amplitude = yarn_frequencies(64, 10000.0, yarn)
    table = {0: 1.0, 1: 0.7498942, 10: 0.05623413, 16: 0.005528846,
             23: 4.167262e-05, 31: 4.167262e-06}
    for i, f in table.items():
        assert float(freq[i]) == pytest.approx(f, rel=1e-5), i
    assert amplitude == 1.0                 # mscale / mscale_all_dim
    assert yarn_mscale(32, 1) == pytest.approx(1.3465736, rel=1e-6)
    assert yarn_mscale(1, 1) == 1.0
    # the softmax scale a layer uses: 192^-1/2 m^2
    assert (128 + 64) ** -0.5 * yarn_mscale(32, 1) ** 2 == pytest.approx(
        0.13086, rel=1e-4)
    # the reference's own, written apart, agrees to the last pair
    ref = load_module("reference", "mla_moe_serve")
    inv, amp = ref.yarn_inv_freq(64, 10000.0, CFG["rope_scaling"] | {
        "original_max_position_embeddings": 4096})
    assert np.allclose(np.asarray(inv), np.asarray(freq), rtol=1e-6)
    assert amp == 1.0
    # mscale apart from mscale_all_dim scales cos and sin
    _, louder = yarn_frequencies(64, 10000.0, (32, 4096, 32, 1, 1, 0))
    assert louder == pytest.approx(1.3465736, rel=1e-6)
    assert math.isclose(float(freq[16]),
                        0.01 * (1 - 6 / 13) + 0.01 / 32 * (6 / 13),
                        rel_tol=1e-5)


@pytest.mark.parametrize("s,block", [(256, 128), (200, 64)])
def test_flash_forward_at_key_192_value_128_matches_dense(s, block):
    key = jax.random.PRNGKey(s)
    q = jax.random.normal(key, (1, s, 2, 192), jnp.float32)
    k = jax.random.normal(jax.random.fold_in(key, 1), (1, s, 2, 192))
    v = jax.random.normal(jax.random.fold_in(key, 2), (1, s, 2, 128))
    want = dense_causal_attention(q, k, v, scale=0.13)
    got = fa.flash_attention(q, k, v, scale=0.13, block_q=block,
                             block_k=2 * block, sub=block, interpret=True)
    assert got.shape == (1, s, 2, 128) and rel(got, want) < 1e-5


def test_everything_but_the_forward_refuses_unequal_widths_by_name():
    q = jnp.ones((1, 128, 2, 48))
    v = jnp.ones((1, 128, 2, 32))
    loss = lambda q: fa.flash_attention(  # noqa: E731
        q, q, v, interpret=True).sum()
    said = "value width 32 against key width 48"
    with pytest.raises(NotImplementedError, match=said):
        jax.grad(loss)(q)
    # ring and zigzag attention's two entries
    with pytest.raises(NotImplementedError,
                       match=f"flash_attention_with_lse.*{said}"):
        fa.flash_attention_with_lse(q, q, v, interpret=True)
    stat = jnp.zeros((1, 128, 2))
    with pytest.raises(NotImplementedError,
                       match=f"flash_attention_backward.*{said}"):
        fa.flash_attention_backward(q, q, v, v, stat, stat, True, 0, 0,
                                    128, 128, True)
    # a latent layer over a context axis says so too
    cfg = TransformerConfig(
        num_layers=1, layer_types=("latent_attention",), num_heads=2,
        embed_dim=16, q_lora_rank=8, kv_lora_rank=8, qk_nope_head_dim=8,
        qk_rope_head_dim=4, v_head_dim=4, vocab_size=16,
        context_axis="cp", context_plan=types.SimpleNamespace(remat=False))
    with pytest.raises(NotImplementedError, match="values as wide"):
        Transformer(cfg).init(jax.random.PRNGKey(0),
                              jnp.zeros((1, 8), jnp.int32),
                              positions=jnp.arange(8))


def test_the_vmem_estimate_prices_the_two_widths():
    est = fa._vmem_estimate_bytes
    same = est(1024, 1024, 128)
    assert est(1024, 1024, 128, d_v=128) == same
    # K and q at 192 beside V, dO and the accumulator at 128
    wide = est(1024, 1024, 192, d_v=128)
    assert wide - same == 2 * 1024 * 64 * 2 + 2 * 1024 * 64 * 2
    assert est(1024, 1024, 192) - wide == \
        2 * 1024 * 64 * 2 + 2 * 1024 * 64 * 2 + 1024 * 64 * 4
    assert fa.clamp_blocks_to_vmem(1024, 1024, 192, d_v=128) == (1024, 1024)


def test_the_backend_serves_latents_through_the_flash_prefill(
        family, reference, monkeypatch):
    """``TransformerBackend`` holds the pool the model gives, chooses the
    flash forward from a bucket's own shape (keys wider than values), runs a
    bucket past ``feed_forward_chunk`` in pieces with the head on the last
    position; served greedily through the engine, each token's logits are
    the reference's."""
    from horovod_tpu.serving import ServingConfig, ServingEngine
    from horovod_tpu.serving.engine import TransformerBackend
    from horovod_tpu.utils import profiling

    cfg = held(CFG, 4, 12)
    model, mcfg, params, w = setup(family, cfg)
    mcfg = dataclasses.replace(mcfg, feed_forward_chunk=16)
    model = Transformer(mcfg)
    # both buckets' logits past the limit: 4 heads x 16**2 x 4 bytes
    monkeypatch.setattr(TransformerBackend, "FLASH_PREFILL_LOGITS_BYTES",
                        4 * 4 * 16 ** 2 - 1)
    backend = TransformerBackend(model, params, mcfg, 2, 64)
    assert backend.kk.shape == (3, 2, 64, 8)        # latents
    assert backend.vv.shape == (3, 2, 64, 4)        # their rotary keys
    assert backend.flash_prefill and backend.sparse
    assert [backend.prefill_attention(b) for b in (16, 32)] == ["flash"] * 2
    assert [backend.prefill_chunks(b) for b in (16, 32)] == [1, 2]
    engine = ServingEngine(backend, ServingConfig(
        num_slots=2, buckets=(16, 32), max_seq_len=64, record_logits=True))
    since = lambda mark: [r for r in profiling.spans()  # noqa: E731
                          if r.id > mark and r.name == profiling.SRV_PREFILL]
    mark = profiling.open_span("mark").id
    prompt = [int(t) for t in tokens_of(6, 20)]
    req = engine.submit(prompt, 12)
    engine.run_until_idle()
    seq = jnp.asarray(prompt + req.tokens)
    want, _ = reference.logits_of_rows(
        w, seq, cfg, (4, 12), 0, seq.shape[0])
    for i, logits in enumerate(req.logits):
        assert rel(logits, want[19 + i]) < TOL
    c = backend.moe_counters
    assert c["calls"] == 12 and 0 < c["held_pairs"] < c["pairs"]
    # the prompt's 20 positions and the one live slot, the two SPARSE layers
    assert c["pairs"] == (20 + 11 * 1) * 2 * 4
    assert backend.last_expert_pairs.shape == (2, 8)
    call, = since(mark)
    assert call.fields["attn"] == "flash" and call.fields["chunks"] == 2
    # a short prompt's span carries no chunk count
    mark = profiling.open_span("mark").id
    engine.submit(prompt[:9], 2)
    engine.run_until_idle()
    call, = since(mark)
    assert "chunks" not in call.fields
