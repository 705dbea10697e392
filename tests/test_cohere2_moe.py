"""cohere2_moe on the program's normal path against the plain reference
(``benchmarks/reference/cohere2_moe_serve.py``) at a small size, float32 on
the CPU (PR 37): the full forward of a [sliding, sliding, sliding, full]
stack with a window shorter than the sequence; prefill then decode through
the cache past the window; the flash forward with a window against the dense
attention with the same window, grouped 16:1; the eight shares of a layer
adding up to the uncut layer; sigmoid routing over held and absent experts.

Tolerances.  Program and reference both compute in float32 here, in another
order (fused projections, a grouped matmul over sorted rows, an online
softmax): they agree to 1e-5 of the logits' size, so the bound is 2e-4.
Each fault below (no band, a rotated full layer, half-split rotary pairs, a
sequential block, RMSNorm, softmax selection) moves the logits by 5% or more
and reads a failure.  With bfloat16 operands, the precision below the one
stated here, the same comparison reads 2e-3 or more and fails too."""

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
from horovod_tpu.models import Transformer  # noqa: E402
from horovod_tpu.models.moe import MOE_LOSSES, MOE_STATS, MoEMLP  # noqa: E402
from horovod_tpu.models.transformer import (  # noqa: E402
    cached_decode_attention, dense_causal_attention, init_kv_cache)
from horovod_tpu.ops.flash_attention import flash_attention  # noqa: E402

TOL = 2e-4
CFG = {"family": "cohere2_moe_serve", "model_type": "cohere2_moe",
       "attention_bias": False, "expert_selection_fn": "sigmoid",
       "first_k_dense_replace": 0, "head_dim": 8, "hidden_act": "silu",
       "hidden_size": 32, "intermediate_size": 16, "layer_norm_eps": 1e-05,
       "layer_types": ["sliding_attention", "sliding_attention",
                       "sliding_attention", "full_attention"],
       "logit_scale": 1, "norm_topk_prob": True, "num_attention_heads": 16,
       "num_experts": 16, "num_experts_published": 16,
       "experts_held": [0, 16], "num_experts_per_tok": 4,
       "num_hidden_layers": 4, "num_key_value_heads": 1,
       "num_shared_experts": 2, "position_embedding_type": "rope_gptj",
       "rms_norm_eps": None, "rope_theta": 50000, "rotary_pct": 1,
       "shared_expert_combination_strategy": "average", "sliding_window": 8,
       "tie_word_embeddings": True, "use_gated_activation": True,
       "use_parallel_block": True, "use_qk_norm": False, "vocab_size": 64,
       "initializer_range": 0.3}
TRAFFIC = {"max_seq_len": 64}
S = 40                                  # five windows long


@pytest.fixture(scope="module")
def family():
    return load_module("families", "cohere2_moe_serve")


@pytest.fixture(scope="module")
def reference():
    return load_module("reference", "cohere2_moe_serve")


def held(cfg, lo, hi):
    return {**cfg, "experts_held": [lo, hi], "num_experts": hi - lo}


def f32(tree):
    return jax.tree.map(lambda x: x.astype(jnp.float32), tree)


def setup(family, cfg, seed=0):
    """(the program's model in float32, its params, the same weights in the
    reference's layout), drawn as the benchmark draws them."""
    mcfg = dataclasses.replace(family.model_config(cfg, TRAFFIC),
                               dtype=jnp.float32, param_dtype=jnp.float32)
    w = f32(family.draw(cfg, family.seed_key(seed)))
    # norm scales off 1, so that a norm's scale is in the comparison
    bump = lambda i: 1.0 + 0.1 * jnp.cos(jnp.arange(cfg["hidden_size"]) + i)
    for i, layer in enumerate(w["layers"]):
        layer["input_layernorm"] = bump(i)
    w["norm"] = bump(9)
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
    model, _, params, w = setup(family, cfg)
    tokens = tokens_of(1)
    want = reference_logits(reference, w, tokens, cfg)
    got = jax.jit(model.apply)(params, tokens[None])[0]
    assert rel(got, want) < TOL
    # the reference in query blocks is the reference
    blocked = reference_logits(reference, w, tokens, cfg, query_block=8)
    assert rel(blocked, want) < 1e-5


FAULTS = {
    "no band": {"sliding_window": 4096},
    "the full layer rotated": {"layer_types": ("sliding_attention",) * 4},
    "the window layers not rotated": {"layer_types": ("full_attention",) * 4},
    "half-split rotary pairs": {"rope_interleaved": False},
    "a sequential block": {"parallel_block": False},
    "RMSNorm": {"norm": "rms"},
    "softmax selection": {"moe_selection": "softmax"},
    "gates not normalised": {"norm_topk_prob": False},
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
    if fault == "a sequential block":   # it has a second norm a layer
        params = jax.tree.map(lambda x: x, params)
        for i in range(4):
            params["params"][f"layer_{i}"]["mlp_norm"] = {
                "scale": jnp.ones(32)}
    assert rel(jax.jit(wrong.apply)(params, tokens[None])[0], want) > 0.05


def test_a_lower_precision_than_stated_fails(family, reference):
    model, _, params, w = setup(family, CFG)
    tokens = tokens_of(1)
    want = reference_logits(reference, w, tokens, CFG)
    low = reference_logits(reference, w, tokens, CFG,
                           operand_dtype=jnp.bfloat16)
    assert rel(low, want) > 10 * TOL


@pytest.mark.parametrize("share", [(0, 16), (8, 12)])
def test_prefill_then_decode_past_the_window(family, reference, share):
    """The cache path (``return_kv`` prefill of 12 tokens, then 28 decode
    steps, the window 8) at every decoded position against the reference's
    full forward over the whole sequence."""
    cfg = held(CFG, *share)
    model, mcfg, params, w = setup(family, cfg)
    tokens = tokens_of(2)
    want = reference_logits(reference, w, tokens, cfg)
    prompt = 12
    logits, (pk, pv) = jax.jit(lambda p, t: model.apply(
        p, t, return_kv=True))(params, tokens[None, :prompt])
    assert rel(logits[0], want[:prompt]) < TOL
    kk, vv = init_kv_cache(mcfg, 2, 64)
    kk = kk.at[:, 1, :prompt].set(pk[:, 0])     # slot 1; slot 0 stays empty
    vv = vv.at[:, 1, :prompt].set(pv[:, 0])
    step = jax.jit(lambda kk, vv, tok, n: model.apply(
        params, tok[:, None], kv_cache=(kk, vv), lengths=n))
    for pos in range(prompt, S):
        out, (kk, vv) = step(kk, vv, jnp.array([0, tokens[pos]]),
                             jnp.array([0, pos]))
        assert rel(out[1], want[pos]) < TOL, pos


@pytest.mark.parametrize("s,window,block", [
    (256, 128, 128),    # the band's lower edge on a block boundary
    (320, 100, 128),    # inside blocks, and a padded last block
    (384, 200, 64),     # two sub-tiles a super tile, tiles skipped below
    (200, 512, 128),    # a window longer than the sequence: causal
])
def test_flash_forward_with_a_window_matches_dense(s, window, block):
    key = jax.random.PRNGKey(s)
    q = jax.random.normal(key, (1, s, 16, 32), jnp.float32)
    k = jax.random.normal(jax.random.fold_in(key, 1), (1, s, 1, 32))
    v = jax.random.normal(jax.random.fold_in(key, 2), (1, s, 1, 32))
    want = dense_causal_attention(q, k, v, window=window)
    got = flash_attention(q, k, v, window=window, block_q=block,
                          block_k=2 * block, sub=block, interpret=True)
    assert rel(got, want) < 1e-5
    if window < s:      # and the band is not the triangle
        assert rel(dense_causal_attention(q, k, v), want) > 1e-2
    # the decode mask, at the last position
    lengths = jnp.array([s - 1])
    last = cached_decode_attention(q[:, -1:], k, v, lengths, window=window)
    assert rel(last[0, 0], want[0, -1]) < 1e-5


def test_the_flash_backward_refuses_a_window_by_name():
    q = jnp.ones((1, 128, 2, 32))
    loss = lambda q: flash_attention(  # noqa: E731
        q, q, q, window=64, interpret=True).sum()
    with pytest.raises(NotImplementedError, match="window=64.*no backward"):
        jax.grad(loss)(q)
    with pytest.raises(ValueError, match="causal"):
        flash_attention(q, q, q, causal=False, window=64, interpret=True)


def layer_of(cfg, lo, hi, w):
    """(MoEMLP holding experts lo..hi-1, its params) from one reference
    layer's weights ``w`` (every expert)."""
    n, e, f = cfg["num_shared_experts"], cfg["hidden_size"], \
        cfg["intermediate_size"]
    beside = lambda x: x.transpose(1, 0, 2).reshape(e, n * f)  # noqa: E731
    ex, sh = w["experts"], w["shared_experts"]
    m = MoEMLP(embed_dim=e, mlp_dim=f, axis_name=None, dtype=jnp.float32,
               num_experts=cfg["num_experts_published"],
               experts_per_token=cfg["num_experts_per_tok"],
               norm_topk_prob=True, selection="sigmoid",
               num_shared_experts=n, experts_held=(lo, hi))
    params = {"params": {
        "router": w["router"], "gate": ex["gate_proj"][lo:hi],
        "up": ex["up_proj"][lo:hi], "down": ex["down_proj"][lo:hi],
        "shared_gate": beside(sh["gate_proj"]),
        "shared_up": beside(sh["up_proj"]),
        "shared_down": sh["down_proj"].reshape(n * f, e)}}
    return m, params


def test_the_eight_shares_add_up_to_the_uncut_layer(family, reference):
    """Every share routes over all 16 experts and computes its own two; the
    routed parts of the eight shares plus the shared experts, which every
    chip computes alike, counted once, are the uncut reference's layer."""
    w = f32(family.draw(CFG, family.seed_key(3)))["layers"][0]
    h = jax.random.normal(jax.random.PRNGKey(4), (1, 24, 32))
    ident = lambda x: x  # noqa: E731
    with jax.default_matmul_precision("highest"):
        mm = lambda x, y: x @ y  # noqa: E731
        whole, picks = reference.feed_forward(h[0], w, CFG, (0, 16), mm,
                                              ident)
        shared = sum(reference.glu(
            h[0], *(w["shared_experts"][k][j] for k in
                    ("gate_proj", "up_proj", "down_proj")), mm)
            for j in range(2)) / 2
    total = shared
    for c in range(8):
        m, params = layer_of(CFG, 2 * c, 2 * c + 2, w)
        part, sown = m.apply(params, h, mutable=[MOE_STATS])
        total = total + (part[0] - shared)
        # the share's picks are the uncut layer's, over all 16 experts
        got = np.sort(np.asarray(sown[MOE_STATS]["picks"][0][0]), -1)
        assert (got == np.sort(np.asarray(picks), -1)).all()
        # and its counts are of its own two experts
        want = [(np.asarray(picks) == 2 * c + j).sum() for j in range(2)]
        assert sown[MOE_STATS]["expert_pairs"][0].tolist() == want
    assert rel(total, whole) < 1e-5
    # the reference's own share agrees with the program's
    m, params = layer_of(CFG, 6, 10, w)
    mine = reference.feed_forward(
        h[0], {**w, "experts": jax.tree.map(lambda x: x[6:10],
                                            w["experts"])},
        CFG, (6, 10), mm, ident)[0]
    assert rel(m.apply(params, h)[0], mine) < 1e-5


def test_sigmoid_routing_and_an_absent_pick():
    """Scores are sigmoid(h W_r), normalised over the picks; a pick of an
    absent expert adds nothing and still takes its part of the sum."""
    e, f, n_exp, k = 8, 4, 6, 3
    key = jax.random.PRNGKey(5)
    router = jax.random.normal(key, (e, n_exp))
    x = jax.random.normal(jax.random.fold_in(key, 1), (1, 5, e))
    w = {name: jax.random.normal(jax.random.fold_in(key, i), shape)
         for i, (name, shape) in enumerate(
             (("gate", (n_exp, e, f)), ("up", (n_exp, e, f)),
              ("down", (n_exp, f, e))), 2)}

    def expert(j, h):
        return (jax.nn.silu(h @ w["gate"][j]) * (h @ w["up"][j])) \
            @ w["down"][j]

    scores = jax.nn.sigmoid(x[0] @ router)
    picks = jnp.argsort(-scores, axis=-1)[:, :k]
    gates = jnp.take_along_axis(scores, picks, -1)
    gates = gates / gates.sum(-1, keepdims=True)
    lo, hi = 2, 4
    want = jnp.stack([
        sum(gates[t, i] * expert(int(picks[t, i]), x[0, t])
            for i in range(k) if lo <= int(picks[t, i]) < hi)
        + jnp.zeros(e) for t in range(5)])
    m = MoEMLP(embed_dim=e, mlp_dim=f, axis_name=None, dtype=jnp.float32,
               num_experts=n_exp, experts_per_token=k, norm_topk_prob=True,
               selection="sigmoid", experts_held=(lo, hi))
    params = {"params": {"router": router,
                         **{n: v[lo:hi] for n, v in w.items()}}}
    with jax.default_matmul_precision("highest"):
        got, sown = m.apply(params, x, mutable=[MOE_STATS])
    assert rel(got[0], want) < 1e-5
    # some token picked an absent expert, or the case shows nothing
    assert ((picks < lo) | (picks >= hi)).any()
    assert (np.sort(np.asarray(sown[MOE_STATS]["picks"][0][0]), -1)
            == np.sort(np.asarray(picks), -1)).all()
    # a position that holds no token is routed nowhere and counted nowhere
    valid = jnp.array([[True, False, True, True, True]])
    with jax.default_matmul_precision("highest"):
        masked, sown2 = m.apply(params, x, valid=valid, mutable=[MOE_STATS])
    assert float(jnp.abs(masked[0, 1]).max()) == 0.0
    assert rel(masked[0, jnp.array([0, 2, 3, 4])],
               want[jnp.array([0, 2, 3, 4])]) < 1e-5
    lost = int(((picks[1] >= lo) & (picks[1] < hi)).sum())
    assert int(sown2[MOE_STATS]["expert_pairs"][0].sum()) == \
        int(sown[MOE_STATS]["expert_pairs"][0].sum()) - lost
    # the auxiliary losses are softmax selection's
    with pytest.raises(NotImplementedError, match="softmax selection"):
        m.apply(params, x, mutable=[MOE_LOSSES])
    with pytest.raises(ValueError, match="experts_held"):
        MoEMLP(embed_dim=e, mlp_dim=f, axis_name=None, num_experts=n_exp,
               experts_per_token=k, experts_held=(4, 9)).init(key, x)


def test_the_backend_serves_through_the_flash_prefill(family, reference,
                                                      monkeypatch):
    """``TransformerBackend`` chooses the flash forward from a bucket's own
    shape; served greedily through the engine, prompt longer than the
    window, each token's logits are the reference's."""
    from horovod_tpu.serving import ServingConfig, ServingEngine
    from horovod_tpu.serving.engine import TransformerBackend

    cfg = held(CFG, 4, 12)
    model, mcfg, params, w = setup(family, cfg)
    dense = TransformerBackend(model, params, mcfg, 2, 64)
    assert not dense.flash_prefill
    # both buckets' logits past the limit: 16 heads x 16**2 x 4 bytes
    monkeypatch.setattr(TransformerBackend, "FLASH_PREFILL_LOGITS_BYTES",
                        4 * 16 * 16 ** 2 - 1)
    backend = TransformerBackend(model, params, mcfg, 2, 64)
    assert backend.flash_prefill and backend.sparse
    assert {backend.prefill_attention(b) for b in (16, 32)} == {"flash"}
    engine = ServingEngine(backend, ServingConfig(
        num_slots=2, buckets=(16, 32), max_seq_len=64, record_logits=True))
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
    # the prompt's 20 positions, not the bucket's 32; the one live slot
    assert c["pairs"] == (20 + 11 * 1) * 4 * 4
    assert backend.last_expert_pairs.shape == (4, 8)
