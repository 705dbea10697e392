"""EVA attention (PR 44; ``models/transformer.py::EvaAttention``) at small
sizes on the CPU: the model against the plain reference
(``benchmarks/reference/eva_serve.py``) on seeded weights, every one of the
``num_pred_heads x vocab`` logits; prefill and then decode through the cache
of a window ring and chunk summaries against the full forward, at prompt
lengths and decode runs that meet every edge of a chunk and of a window; a
reused slot; the dense and the merged-partials forms; the float8 control;
the pool's shape and what refuses the mixer by name."""

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
from horovod_tpu.models import Transformer, TransformerConfig  # noqa: E402
from horovod_tpu.models import transformer as T  # noqa: E402
from horovod_tpu.serving.engine import (  # noqa: E402
    ServingConfig, ServingEngine, TransformerBackend)

W, C = 32, 4                # window and chunk of the toy
TINY = {"family": "eva_serve", "model_type": "evabyte",
        "attention_class": "eva", "attention_bias": False,
        "hidden_act": "silu", "hidden_size": 32, "intermediate_size": 48,
        "num_attention_heads": 2, "num_key_value_heads": 2,
        "num_hidden_layers": 2, "num_pred_heads": 8, "vocab_size": 20,
        "window_size": W, "chunk_size": C, "rms_norm_eps": 1e-5,
        "rope_theta": 100000, "rope_scaling": None,
        "norm_add_unit_offset": True, "fp32_skip_add": True,
        "fp32_logits": True, "tie_word_embeddings": False, "init_std": 0.4}
MAX_LEN = 128


@pytest.fixture(scope="module")
def family():
    return load_module("families", "eva_serve")


@pytest.fixture(scope="module")
def built(family):
    """(model config in float32, model, the seed's weights in the program's
    layout, in the reference's, the tokens, the full forward's logits)."""
    mcfg = dataclasses.replace(
        family.model_config(TINY, {"max_seq_len": MAX_LEN}),
        dtype=jnp.float32, param_dtype=jnp.float32)
    weights = jax.tree.map(lambda x: x.astype(jnp.float32),
                           family.draw(TINY, family.seed_key(11)))
    # norm offsets and the summaries' two vectors away from their start, so
    # that a dropped "+ 1" or a dropped mu shows
    key = iter(jax.random.split(jax.random.PRNGKey(3), 64))
    for lay in weights["layers"]:
        for name in ("input_layernorm", "post_attention_layernorm",
                     "adaptive_phi", "adaptive_mu_k"):
            lay[name] = 0.5 * jax.random.normal(next(key), lay[name].shape)
    weights["norm"] = 0.5 * jax.random.normal(next(key),
                                              weights["norm"].shape)
    params = family.to_program(weights, TINY)
    model = Transformer(mcfg)
    tokens = jax.random.randint(jax.random.PRNGKey(5), (1, MAX_LEN), 0, 20)
    full = np.asarray(jax.jit(model.apply)(params, tokens))[0]
    return mcfg, model, params, weights, tokens, full


def test_the_model_is_the_reference_on_every_logit(built):
    mcfg, model, params, weights, tokens, full = built
    reference = load_module("reference", "eva_serve")
    ref = np.asarray(reference.logits_of_rows(
        weights, tokens[0], TINY, 0, MAX_LEN, query_block=32))
    assert full.shape == ref.shape == (MAX_LEN, 8 * 20)
    np.testing.assert_allclose(full, ref, atol=2e-4, rtol=2e-4)
    # and the reference in one block is the reference in blocks
    whole = np.asarray(reference.logits_of_rows(weights, tokens[0], TINY, 0,
                                                MAX_LEN))
    np.testing.assert_allclose(whole, ref, atol=1e-4, rtol=1e-4)


def test_the_reference_mask_is_the_definition():
    reference = load_module("reference", "eva_serve")
    s = 3 * W
    mask = np.asarray(reference.seen(jnp.arange(s), s, W, C))
    for t in (0, 5, W - 1, W, W + 7, 2 * W, s - 1):
        exact = {m for m in range(s) if W * (t // W) <= m <= t}
        summaries = {j for j in range(s // C) if j < (W // C) * (t // W)}
        assert set(np.flatnonzero(mask[t, :s])) == exact
        assert set(np.flatnonzero(mask[t, s:])) == summaries
        # E_t and R_t never cover one position twice
        assert not exact & {C * j + i for j in summaries for i in range(C)}


def backend_of(built, slots=2):
    mcfg, model, params, *_ = built
    return TransformerBackend(model, params, mcfg, slots, MAX_LEN)


def prefill_then_decode(backend, tokens, n, steps, slot=1):
    """The logits of positions n - 1 .. n - 1 + steps: the prefill's, then
    one decode step a position, the true next token fed each time."""
    padded = np.zeros((1, 64 if n <= 64 else MAX_LEN), np.int32)
    padded[0, :n] = np.asarray(tokens[0, :n])
    _, logits = backend.prefill(padded, n, slot)
    out = [logits]
    lengths = np.zeros(backend.num_slots, np.int32)
    last = np.zeros(backend.num_slots, np.int32)
    for t in range(n, n + steps):
        lengths[slot], last[slot] = t + 1, int(tokens[0, t])
        out.append(backend.decode(last, lengths)[1][slot])
    return np.stack(out)


# prompt lengths that end mid-chunk, on a chunk's end, one short of and
# exactly on a window's end; decode runs that cross a chunk's end, a window's
# roll-over, and two windows
@pytest.mark.parametrize("n,steps", [
    (6, 3), (8, 2), (W - 1, 3), (W, 2), (W + 5, 9), (2 * W - 1, 2),
    (2 * W, 6), (9, 2 * W + 8), (W - 2, 2 * W + 6), (3 * W + 3, 20)])
def test_prefill_then_decode_is_the_full_forward(built, n, steps):
    *_, tokens, full = built
    got = prefill_then_decode(backend_of(built), tokens, n, steps)
    np.testing.assert_allclose(got, full[n - 1:n + steps], atol=2e-4,
                               rtol=2e-4)


def test_a_reused_slot_gives_the_cold_result(built):
    """Stale ring rows and summaries of a longer previous occupant lie
    behind the mask: the slot's next request reads what a cold slot reads."""
    *_, tokens, full = built
    backend = backend_of(built)
    other = jnp.roll(tokens, 17, axis=1)
    prefill_then_decode(backend, other, 3 * W + 3, 25)      # the occupant
    stale = np.asarray(backend.kk).copy()
    got = prefill_then_decode(backend, tokens, W + 5, W + 3)
    np.testing.assert_allclose(got, full[W + 4:2 * W + 8], atol=2e-4,
                               rtol=2e-4)
    cold = prefill_then_decode(backend_of(built), tokens, W + 5, W + 3)
    np.testing.assert_array_equal(got, cold)
    # never cleared: the occupant's summaries past the new request's reach
    # are still in the pool
    assert np.array_equal(np.asarray(backend.kk)[:, 1, W + 24:],
                          stale[:, 1, W + 24:])
    assert np.abs(stale[:, 1, W + 24:W + 26]).max() > 0


def test_the_pool_is_a_ring_and_summaries_written_in_place(built):
    mcfg, model, params, *_ = built
    k, v = T.init_kv_cache(mcfg, 3, MAX_LEN)
    assert k.shape == v.shape == (2, 3, W + MAX_LEN // C, 2, 16)
    assert mcfg.eva
    backend = backend_of(built)
    i32 = jax.ShapeDtypeStruct((2,), jnp.int32)
    compiled = backend._decode.lower(params, backend.kk, backend.vv, i32,
                                     i32).compile()
    memory = compiled.memory_analysis()
    # both cache arguments aliased to the outputs: no second pool
    assert memory.alias_size_in_bytes >= 2 * backend.kk.nbytes
    # a decode step's writes: ring row t mod W and summary W + t // C alone
    before = np.asarray(backend.kk).copy()
    lengths = np.array([0, W + 6 + 1], np.int32)
    backend.decode(np.zeros(2, np.int32), lengths)
    changed = np.argwhere(np.any(np.asarray(backend.kk) != before,
                                 axis=(0, 3, 4)))
    assert {tuple(c) for c in changed} <= {
        (0, 0), (0, W), (1, 6), (1, W + (W + 6) // C)}


def test_the_two_prefill_forms_agree():
    """Dense with one mask and merged partials (the flash forward kernels,
    interpreted here) give the same numbers; the form follows the shape."""
    q, k, v = (jax.random.normal(jax.random.PRNGKey(i), (2, 384, 2, 128))
               for i in range(3))
    phi, mu = (jax.random.normal(jax.random.PRNGKey(i), (2, 128))
               for i in (7, 8))
    scale = 128 ** -0.5
    kbar, vbar = T.eva_chunk_summaries(k, v, phi, mu, 16, scale)
    dense = T.eva_dense_attention(q, k, v, kbar, vbar, 128, 16, scale)
    merged = T.eva_merged_attention(q, k, v, kbar, vbar, window=128, chunk=16)
    np.testing.assert_allclose(merged, dense, atol=1e-5, rtol=1e-5)
    # a window at a time, the same summaries
    pieces = T.eva_chunk_summaries(k, v, phi, mu, 16, scale,
                                   rows_at_a_time=128)
    np.testing.assert_allclose(pieces[0], kbar, atol=1e-6)
    np.testing.assert_allclose(pieces[1], vbar, atol=1e-6)
    # one window: the causal triangle alone
    one = T.eva_merged_attention(q[:, :128], k[:, :128], v[:, :128],
                                 kbar[:, :8], vbar[:, :8], window=128,
                                 chunk=16)
    np.testing.assert_allclose(one, dense[:, :128], atol=1e-5, rtol=1e-5)
    with pytest.raises(ValueError, match="whole windows"):
        T.eva_merged_attention(q[:, :200], k[:, :200], v[:, :200], kbar,
                               vbar, window=128, chunk=16)
    cfg = TransformerConfig(num_heads=32, head_dim=128, eva_window=2048,
                            eva_chunk=16)
    assert T.eva_attention_form(cfg, 512) == "dense"
    assert T.eva_attention_form(cfg, 2048) == "merged"
    assert T.eva_attention_form(cfg, 32768) == "merged"
    assert T.eva_attention_form(cfg, 3000) == "dense"   # no whole windows
    assert T.eva_attention_form(
        dataclasses.replace(cfg, num_heads=2, eva_window=32), 64) == "dense"


def test_the_valid_length_of_the_flash_partial():
    """``flash_attention_with_lse(k_len=)``: the keys past it lie behind the
    mask; none at all is an empty partial (lse NEG_INF, out 0)."""
    from horovod_tpu.ops.flash_attention import (NEG_INF,
                                                 flash_attention_with_lse)
    q, k, v = (jax.random.normal(jax.random.PRNGKey(i), (1, 128, 2, 128))
               for i in range(3))
    for n in (128, 40):
        out, lse = flash_attention_with_lse(q, k, v, causal=False,
                                            k_len=jnp.int32(n))
        want, want_lse = flash_attention_with_lse(q, k[:, :n], v[:, :n],
                                                  causal=False)
        np.testing.assert_allclose(out, want, atol=1e-5, rtol=1e-5)
        np.testing.assert_allclose(lse, want_lse, atol=1e-5, rtol=1e-5)
    out, lse = flash_attention_with_lse(q, k, v, causal=False,
                                        k_len=jnp.int32(0))
    assert float(jnp.abs(out).max()) == 0.0
    assert float(lse.max()) == np.float32(NEG_INF)


def test_the_engine_serves_bytes_from_head_zero(built):
    """Through ``ServingEngine``: greedy bytes are the argmax of head 0's 20
    logits of the full forward, all 8 x 20 logits leave the program, and the
    calls' spans carry what they did to the cache."""
    from horovod_tpu.utils import profiling

    mcfg, model, params, _, tokens, _ = built
    backend = backend_of(built)
    engine = ServingEngine(backend, ServingConfig(
        num_slots=2, buckets=(64,), max_seq_len=MAX_LEN, eos_id=None,
        record_logits=True))
    prompt = [int(t) for t in tokens[0, :W + 3]]
    req = engine.submit(prompt, W + 6)
    engine.run_until_idle()
    seq = jnp.asarray([prompt + req.tokens])
    want = np.asarray(jax.jit(model.apply)(params, seq))[0, len(prompt) - 1:-1]
    assert np.asarray(req.logits).shape == (W + 6, 8 * 20)
    assert req.tokens == [int(t) for t in want[:, :20].argmax(-1)]
    assert all(t < 20 for t in req.tokens)
    np.testing.assert_allclose(np.asarray(req.logits), want, atol=2e-4,
                               rtol=2e-4)
    spans = profiling.spans()
    prefill = [r for r in spans if r.name == profiling.SRV_PREFILL][-1]
    assert (prefill.fields["attn"], prefill.fields["windows"],
            prefill.fields["summaries"]) == ("dense", 2, (W + 3) // C)
    decodes = [r.fields for r in spans if r.name == profiling.SRV_DECODE
               ][-(W + 5):]
    # positions W + 3 .. 2 W + 7: chunk ends at t mod 4 = 3, one roll-over
    assert sum(f["chunks_closed"] for f in decodes) == \
        sum(t % C == C - 1 for t in range(W + 3, 2 * W + 8))
    assert sum(f["rollovers"] for f in decodes) == 1
    assert backend.eva_counters["rollovers"] == 1
    assert backend.prefill_attention(64) == "dense"


def test_what_refuses_the_mixer_by_name(built):
    mcfg, model, params, *_ = built
    with pytest.raises(NotImplementedError, match="init_kv_pages"):
        T.init_kv_pages(mcfg, 4, 16)
    k, v = T.init_kv_cache(mcfg, 1, MAX_LEN)
    with pytest.raises(NotImplementedError, match="one position a cache"):
        model.apply(params, jnp.zeros((1, 3), jnp.int32), kv_cache=(k, v),
                    lengths=jnp.zeros((1,), jnp.int32))
    mixed = dataclasses.replace(mcfg, layer_types=("eva_attention",
                                                   "attention"))
    with pytest.raises(NotImplementedError, match="one shape"):
        mixed.eva
    with pytest.raises(ValueError, match="eva_window"):
        Transformer(dataclasses.replace(mcfg, eva_chunk=5)).init(
            jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    # another model's fields at their defaults are no op: a norm's weight is
    # its parameter, the head has vocab_size outputs
    plain = TransformerConfig(vocab_size=20, num_layers=1, num_heads=2,
                              head_dim=16, embed_dim=32, mlp_dim=48)
    shapes = jax.eval_shape(Transformer(plain).init, jax.random.PRNGKey(0),
                            jnp.zeros((1, 8), jnp.int32))["params"]
    assert shapes["lm_head"]["kernel"].shape == (32, 20)
    init = Transformer(mcfg).init(jax.random.PRNGKey(0),
                                  jnp.zeros((1, 8), jnp.int32))["params"]
    assert init["lm_head"]["kernel"].shape == (32, 8 * 20)
    assert float(jnp.abs(init["final_norm"]["scale"]).max()) == 0.0


@pytest.mark.parametrize("seed", [3, 4])
def test_a_float8_operand_control_fails_the_tolerance(built, family, seed):
    """The tolerance the model meets against the reference (2e-4 above) is
    one the reference with float8 operands misses by far."""
    *_, weights, tokens, full = built
    reference = load_module("reference", "eva_serve")
    toks = jnp.roll(tokens[0], seed)
    exact = np.asarray(reference.logits_of_rows(weights, toks, TINY, 0,
                                                MAX_LEN))
    rounded = np.asarray(reference.logits_of_rows(
        weights, toks, TINY, 0, MAX_LEN, operand_dtype=jnp.float8_e4m3fn))
    assert np.abs(rounded - exact).max() > 100 * 2e-4
