"""ZAYA1-8B's layers through ``ServingEngine`` and over the served prefill's
row blocks (``tests/test_cca.py`` has the mixer, the router and the pool, and
the fixture and the helpers used here; a file of its own so that ``--dist
loadfile`` can give the two to two workers): prefill then decode against the
reference's full forward, a slot admitted anew beside idle ones, the tail
handed over the prompt's row blocks."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from test_cca import (F32, TINY, TRAFFIC, T, Transformer,  # noqa: F401
                      built, family, reference_logits)

from horovod_tpu.serving import ServingConfig, ServingEngine
from horovod_tpu.serving.engine import TransformerBackend


def engine_of(built, slots=3):
    cfg, mcfg, model, weights, params = built
    backend = TransformerBackend(model, params, mcfg, slots, 128)
    return backend, ServingEngine(backend, ServingConfig(
        num_slots=slots, buckets=(16, 32, 64), max_seq_len=128, eos_id=None,
        record_logits=True))


@pytest.fixture(scope="module")
def served(built):
    """One backend and its engine for the four prompts below, served one
    after the other: a backend made anew compiles its buckets and its decode
    program anew, and the programs are the same four times."""
    return engine_of(built)


@pytest.mark.parametrize("n", [1, 2, 3, 21])
def test_prefill_then_decode_is_the_full_forward(built, served, n):
    """Through ServingEngine, logits and not tokens.  A prompt of 1, 2 or 3
    positions hands over a tail that is not full (zeros stand before
    position 0); one of 21 ends inside its bucket of 32, and the tail is the
    one at 21, not at the bucket's end.  Float32 throughout: 3e-4 is the
    forward pass's own tolerance; a bfloat16 tail or router would miss it by
    two orders."""
    cfg, mcfg, model, weights, params = built
    backend, engine = served
    before = dict(backend.moe_counters)
    prompt = [int(t) for t in np.random.default_rng(n).integers(0, 256, n)]
    req = engine.submit(prompt, 9)
    engine.run_until_idle()
    whole = reference_logits(cfg, weights, prompt + req.tokens)
    for i, logits in enumerate(req.logits):
        np.testing.assert_allclose(logits, whole[n - 1 + i], atol=3e-4)
        assert req.tokens[i] == int(jnp.argmax(whole[n - 1 + i]))
    # top-1 in 3 layers: a pair a position a layer; 8 decode steps of a slot
    # (this request's: the backend has served the prompts before it)
    for name in ("pairs", "held_pairs"):
        assert backend.moe_counters[name] - before.get(name, 0) \
            == (n + 8) * 3
    # (the ring is the process's: every engine's records)
    decode = engine.span_summary()["hvd_srv_decode"]
    # a live slot touches one expert a layer a step
    assert decode["moe"]["experts_touched"] >= 8 * 3


def test_a_slot_admitted_anew_and_an_idle_slot_beside_a_live_one(built):
    """One slot serves three requests in turn (each admission starts from
    its prefill's tail alone), with two idle slots decoding beside it; a
    fresh engine with every slot busy gives each the same logits."""
    rng = np.random.default_rng(2)
    prompts = [[int(t) for t in rng.integers(0, 256, n)]
               for n in (13, 30, 2)]
    _, one_by_one = engine_of(built)
    alone = []
    for p in prompts:
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


def test_the_tail_handed_over_the_prompts_row_blocks(built):
    """The served prefill's loop (three row blocks of 1024 and more): a
    prompt that ends in the third block of a 4096 bucket gives, to the bit
    where tests/test_kda.py asks a tolerance, what a pass over the prompt
    alone gives: the rows below the prompt's end, the tail at its end (the
    blocks past it are not run and their rows stay 0), the logits at its
    last position."""
    # two of the tiny cell's three layers: layer 0 to the bit and a layer
    # behind it, which takes the first's rounding and the router's state, are
    # every line held below; a third layer's loops are traced for nothing
    cfg = dict(TINY, num_hidden_layers=2, layer_types=["hybrid"] * 2)
    long = dataclasses.replace(family.model_config(cfg, TRAFFIC), dtype=F32,
                               param_dtype=F32, max_seq_len=4200)
    params = {"params": {k: v for k, v in built[4]["params"].items()
                         if k != "layer_2"}}
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
    assert T.row_blocks(4096) == 4 and k_loop["cca"].shape[0] == 2
    exact, (k_one, v_one) = jax.jit(lambda p, t: model.apply(
        p, t, return_kv=True, logits_at=jnp.array([n - 1])))(
        params, tokens[None, :n])
    np.testing.assert_allclose(looped, exact, atol=3e-4)
    for loop, one in ((k_loop, k_one), (v_loop, v_one)):
        # position-wise but for the carried tail: the same sums in the same
        # order, so the rows and the tail agree to the bit
        np.testing.assert_array_equal(loop["cca"][0, 0, :n], one["cca"][0, 0])
        np.testing.assert_array_equal(loop["cca_tail"][0, 0],
                                      one["cca_tail"][0, 0])
        np.testing.assert_allclose(loop["cca"][1:, 0, :n], one["cca"][1:, 0],
                                   atol=3e-4)
        np.testing.assert_allclose(loop["cca_tail"][:, 0],
                                   one["cca_tail"][:, 0], atol=3e-4)
        # the fourth block was never visited
        assert not np.asarray(loop["cca"][:, 0, 3072:4096]).any()
