"""Continuous-batching serving engine (serving/, docs/inference.md).

The load-bearing claims, each pinned here:

* **Bit-exactness** — a sequence decoded in a mixed continuous batch
  (including sequences admitted mid-stream into freed slots) produces
  byte-identical tokens AND logits to the same sequence decoded alone
  through the same-shaped program.  This is what makes continuous
  batching safe to default on: every backend op is batch-row-
  independent, and the program shape is fixed by the slot count, not by
  who is active.
* **No recompiles, warm cache** — program shapes come from the slot
  count and the bucket menu only, so the ``serving.tick`` collective is
  one fixed-signature allreduce per step: steady state is all
  response-cache hits (zero NEGOTIATED), asserted from cache_stats().
* **Scheduler semantics** — per-step admission into freed slots (no
  drain barrier), mid-batch eviction of finished/over-length sequences,
  the static-batching baseline barrier, and the stats surface
  (``hvd.serving_stats()``).
* **Prefix cache** — decode with the radix-trie KV cache ON is bitwise
  identical to a cold prefill (tokens AND logits), on the stub and on
  the real paged transformer backend; refcounted pages pin while
  referenced and only refs==0 leaves LRU-evict under pressure.
* **Speculative decoding** — greedy n-gram speculation emits the exact
  plain-decode stream on both the reject path (positional stub: nothing
  ever accepted) and the accept path (periodic stub: fewer steps, same
  tokens), and bit-exact tokens on the real transformer.
* **Prefill attention by bucket** — ``TransformerBackend`` picks dense or
  flash from the bucket's own logits bytes; a menu that straddles the
  limit serves what an all-dense backend serves, and the
  ``hvd_srv_prefill`` span says which form ran.

The chaos soak (grow + SIGKILL under load, serving/soak.py) runs under
``-m slow``; SERVING_SOAK_REPS repeats it.
"""

from __future__ import annotations

import dataclasses
import os
import socket

import numpy as np
import pytest

from horovod_tpu.serving import engine as engine_mod
from horovod_tpu.serving.engine import (Request, ServingConfig,
                                        ServingEngine, StubBackend,
                                        serving_stats)

jax = pytest.importorskip("jax")
jnp = jax.numpy


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


# ---------------------------------------------------------------------------
# StubBackend scheduler semantics (jax-free path, the soak fleet's unit)
# ---------------------------------------------------------------------------

def test_stub_stream_is_deterministic():
    from horovod_tpu.serving.worker import (completion_crc,
                                            expected_completion)

    eng = ServingEngine(StubBackend(2), ServingConfig(
        num_slots=2, buckets=(8,), max_seq_len=64))
    prompt = [3, 1, 4, 1, 5]
    req = eng.submit(prompt, 6)
    done = eng.run_until_idle()
    assert [r.rid for r in done] == [req.rid]
    assert done[0].tokens == expected_completion(prompt, 6)
    assert completion_crc(done[0].tokens) == completion_crc(
        expected_completion(prompt, 6))
    assert done[0].finish_reason == "max_new_tokens"


def test_continuous_admission_backfills_freed_slots():
    # 2 slots, 4 requests: the short pair finishes first and the waiting
    # pair is admitted into the freed slots while the batch keeps
    # decoding — no drain barrier.
    eng = ServingEngine(StubBackend(2), ServingConfig(
        num_slots=2, buckets=(8,), max_seq_len=64))
    for _ in range(2):
        eng.submit([1, 2], 2)
    for _ in range(2):
        eng.submit([3, 4], 8)
    eng.step()  # both shorts admitted (prefill token #1)
    assert eng.counters["admitted"] == 2 and len(eng.queue) == 2
    eng.step()  # shorts hit max_new=2 and evict; longs admitted next step
    eng.step()
    assert eng.counters["admitted"] == 4
    assert eng.counters["evicted"] >= 2
    done = eng.run_until_idle()
    assert eng.counters["completed"] == 4
    assert all(r.finish_reason == "max_new_tokens"
               for r in done) or eng.counters["completed"] == 4


def test_over_length_evicted_mid_batch():
    eng = ServingEngine(StubBackend(1), ServingConfig(
        num_slots=1, buckets=(8,), max_seq_len=10))
    req = eng.submit([1, 2, 3, 4, 5, 6], 100)  # 6 + 100 >> max_seq_len
    done = eng.run_until_idle()
    assert done[0].rid == req.rid
    assert done[0].finish_reason == "max_seq_len"
    assert len(done[0].tokens) == 4  # 6 prompt + 4 generated = 10


def test_unbucketable_prompt_rejected_not_queued():
    eng = ServingEngine(StubBackend(1), ServingConfig(
        num_slots=1, buckets=(8,), max_seq_len=64))
    req = eng.submit(list(range(9)), 4)  # > max bucket
    assert req.state == "DONE" and req.finish_reason == "rejected"
    assert not eng.queue and eng.counters["rejected"] == 1
    # Not silent: the error names the limit hit and the knob that
    # raises it, so the caller can act without reading engine source.
    assert req.error is not None and "9 tokens" in req.error
    assert "HVD_TPU_SERVE_BUCKETS" in req.error
    assert "HVD_TPU_SERVE_MAX_LEN" in req.error
    assert eng.stats()["rejected"] == 1


def test_eos_finishes_early():
    eng = ServingEngine(StubBackend(1), ServingConfig(
        num_slots=1, buckets=(8,), max_seq_len=64, eos_id=(1 + 2 + 2) % 256))
    req = eng.submit([1, 2], 50)  # first token = (sum+len) % 256 = eos
    done = eng.run_until_idle()
    assert done[0].rid == req.rid and done[0].finish_reason == "eos"
    assert len(done[0].tokens) == 1


def test_serving_stats_accessor(monkeypatch):
    monkeypatch.setattr(engine_mod, "_ACTIVE", None)
    zero = serving_stats()
    assert set(zero) == set(engine_mod._STATS_KEYS)
    assert all(v == 0 for v in zero.values())
    eng = ServingEngine(StubBackend(2), ServingConfig(
        num_slots=2, buckets=(8,), max_seq_len=64))
    eng.submit([1, 2, 3], 4)
    eng.run_until_idle()
    live = serving_stats()  # lazy hvd.serving_stats resolves to this
    assert live["completed"] == 1 and live["tokens"] == 4
    assert live["steps"] == eng.counters["steps"]
    assert live["ttft_p50_ms"] >= 0.0 and live["active_slots"] == 0


def test_completions_survive_aborted_tick():
    # A reconfiguration aborts the serving.tick allreduce with
    # MembershipChanged AFTER the step's evictions.  The completion must
    # still reach on_complete (the worker's DONE line — the soak's
    # no-lost-request proof) and the step() return value must not vanish:
    # it is parked and handed over by the next successful step.
    from horovod_tpu.core.engine import MembershipChanged

    class _FlakyCollective:
        def __init__(self):
            self.blow = False

        def timeline_instant(self, *a, **k):
            pass

        def enqueue(self, name, vec, op):
            if self.blow:
                self.blow = False
                raise MembershipChanged("reconfig mid-tick")
            return "h"

        def synchronize(self, h):
            return np.zeros(9, np.float32)

    coll = _FlakyCollective()
    seen: list[Request] = []
    eng = ServingEngine(StubBackend(1), ServingConfig(
        num_slots=1, buckets=(8,), max_seq_len=64), collective=coll,
        on_complete=seen.append)
    req = eng.submit([1, 2, 3], 1)  # completes on its admission step
    coll.blow = True
    with pytest.raises(MembershipChanged):
        eng.step()
    assert [r.rid for r in seen] == [req.rid]  # delivered before the tick
    assert eng._active_count() == 0  # evicted — the slot really freed
    nxt = eng.submit([4, 5], 1)
    done = eng.step()  # post-reconfigure step flushes the parked request
    assert [r.rid for r in done] == [req.rid, nxt.rid]
    assert [r.rid for r in seen] == [req.rid, nxt.rid]  # no double DONE


# ---------------------------------------------------------------------------
# TransformerBackend: the real-model KV-cache decode path
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def small_model():
    from horovod_tpu.models.transformer import (Transformer,
                                                TransformerConfig)

    cfg = TransformerConfig(vocab_size=64, num_layers=2, num_heads=2,
                            head_dim=8, embed_dim=16, mlp_dim=32,
                            max_seq_len=64, dtype=jnp.float32,
                            logits_dtype=jnp.float32)
    model = Transformer(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))
    return model, params, cfg


def _make_engine(small_model, num_slots: int, record=True) -> ServingEngine:
    from horovod_tpu.serving.engine import TransformerBackend

    model, params, mcfg = small_model
    backend = TransformerBackend(model, params, mcfg, num_slots,
                                 max_seq_len=64)
    return ServingEngine(backend, ServingConfig(
        num_slots=num_slots, buckets=(8, 16), max_seq_len=64,
        record_logits=record))


def test_prefill_logits_match_full_forward(small_model):
    model, params, _ = small_model
    eng = _make_engine(small_model, num_slots=1)
    prompt = [5, 9, 2, 7, 11, 3]
    req = eng.submit(prompt, 1)
    eng.run_until_idle()
    full = model.apply(params, jnp.asarray([prompt], jnp.int32))
    np.testing.assert_allclose(req.logits[0],
                               np.asarray(full[0, len(prompt) - 1]),
                               rtol=2e-5, atol=2e-5)
    assert req.tokens[0] == int(np.argmax(np.asarray(
        full[0, len(prompt) - 1])))


@pytest.mark.parametrize("record", [False, True])
@pytest.mark.parametrize("kind", ["dense", "paged"])
def test_logits_reach_the_host_only_where_they_are_recorded(small_model,
                                                            kind, record):
    """The programs sample on the device and a backend hands its logits
    back there: a call's own ``hvd_srv_fetch`` holds none of them
    (``bytes``), and without ``record_logits`` nothing else fetches them
    and a request keeps none; with it the engine's fetch follows each
    call's, and a decode step's holds every slot's."""
    import jax

    from horovod_tpu.utils import profiling

    if kind == "dense":
        eng = _make_engine(small_model, num_slots=2, record=record)
    else:
        eng = _make_paged_engine(small_model)
        eng.config = dataclasses.replace(eng.config, record_logits=record)
    before = len(profiling.spans())
    req = eng.submit([5, 9, 2, 7, 11, 3], 4)
    eng.run_until_idle()
    assert len(req.tokens) == 4 and len(req.logits) == (4 if record else 0)
    fetched = [r.fields["bytes"] for r in profiling.spans()[before:]
               if r.name == profiling.SRV_FETCH]
    # one prefill (a row of the vocabulary), three decode steps (every slot's)
    assert fetched == ([0, 64 * 4] + [0, 2 * 64 * 4] * 3 if record
                       else [0] * 4)
    nxt, logits = eng.backend.decode(eng.last_tokens, eng.lengths)
    assert isinstance(nxt, np.ndarray) and nxt.shape == (2,)
    assert isinstance(logits, jax.Array)
    assert logits.shape == (2, 64) and logits.dtype == np.float32


def test_batched_decode_bit_exact_vs_sequential(small_model):
    # Mixed lengths + a mid-stream admission: rid 3 is submitted only
    # after the batch has been decoding for 3 steps and lands in a freed
    # slot.  Every request's tokens AND per-step logits must be
    # BIT-identical to decoding it alone through the same-shaped program
    # — batch-row independence is the whole safety argument.
    rng = np.random.RandomState(0)
    prompts = [list(map(int, rng.randint(0, 64, n))) for n in (5, 8, 13, 6)]
    max_news = [6, 4, 9, 7]

    eng = _make_engine(small_model, num_slots=3)
    reqs = [eng.submit(p, m) for p, m in zip(prompts[:3], max_news[:3])]
    for _ in range(3):
        eng.step()
    reqs.append(eng.submit(prompts[3], max_news[3]))  # mid-stream
    eng.run_until_idle()

    solo_eng = _make_engine(small_model, num_slots=3)
    for req, prompt, max_new in zip(reqs, prompts, max_news):
        solo = solo_eng.submit(prompt, max_new)
        solo_eng.run_until_idle()
        assert solo.tokens == req.tokens, (prompt, solo.tokens, req.tokens)
        assert len(solo.logits) == len(req.logits)
        for a, b in zip(solo.logits, req.logits):
            assert np.array_equal(a, b), "logits diverged bitwise"


def test_hot_swap_changes_output_without_recompile(small_model):
    model, params, _ = small_model
    eng = _make_engine(small_model, num_slots=2)
    prompt = [9, 1, 9, 1]
    a = eng.submit(prompt, 5)
    eng.run_until_idle()
    zeroed = jax.tree.map(jnp.zeros_like, params)
    eng.backend.swap_params(zeroed)
    b = eng.submit(prompt, 5)
    eng.run_until_idle()
    eng.backend.swap_params(params)
    c = eng.submit(prompt, 5)
    eng.run_until_idle()
    assert a.tokens == c.tokens  # same weights, same stream
    assert a.tokens != b.tokens  # the swap actually took


def test_decode_program_advances_the_pool_in_place():
    # The decode program writes one position a layer and slot into the
    # donated [L, B, S, H, D] buffers: both are aliased to outputs and the
    # program keeps no second copy of the pool (a stack of updated layer
    # slices was two buffers of temporaries).  The CPU compiler copies one
    # layer's view for the products, a twelfth of a buffer here.
    from horovod_tpu.models.transformer import (Transformer,
                                                TransformerConfig)
    from horovod_tpu.serving.engine import TransformerBackend

    cfg = TransformerConfig(vocab_size=64, num_layers=12, num_heads=2,
                            head_dim=8, embed_dim=16, mlp_dim=32,
                            max_seq_len=256, dtype=jnp.float32,
                            logits_dtype=jnp.float32)
    model = Transformer(cfg)
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    backend = TransformerBackend(model, params, cfg, 4, max_seq_len=256)
    kv = jax.ShapeDtypeStruct(backend.kk.shape, backend.kk.dtype)
    i32 = jax.ShapeDtypeStruct((4,), jnp.int32)
    mem = backend._decode.lower(params, kv, kv, i32,
                                i32).compile().memory_analysis()
    buffer_bytes = backend.kk.nbytes
    assert mem.alias_size_in_bytes >= 2 * buffer_bytes
    assert mem.temp_size_in_bytes < buffer_bytes / 4, (
        mem.temp_size_in_bytes, buffer_bytes)


@pytest.mark.parametrize("case", ["one_position", "verify_block",
                                  "grouped_query", "sliding_layer"])
def test_cache_call_writes_its_block_alone(case):
    # model.apply(kv_cache=...) hands back the pool it was given with the
    # block's K/V at (layer, slot, lengths[slot] ...) and every other entry
    # as it was; K/V and logits there are the full forward pass's.
    from horovod_tpu.models.transformer import (Transformer,
                                                TransformerConfig)

    s_q = 1 if case == "one_position" else 3
    told = {"grouped_query": {"num_heads": 4, "num_kv_heads": 2},
            "sliding_layer": {"layer_types": ("sliding_attention",
                                              "full_attention"),
                              "sliding_window": 4}}.get(case, {})
    cfg = TransformerConfig(**(dict(
        vocab_size=64, num_layers=2, num_heads=2, head_dim=8, embed_dim=16,
        mlp_dim=32, max_seq_len=32, dtype=jnp.float32,
        logits_dtype=jnp.float32) | told))
    model = Transformer(cfg)
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    rng = np.random.RandomState(7)
    lens = [5, 11, 0]                   # a slot's cached prefix; one empty
    seqs = [rng.randint(0, 64, n + s_q) for n in lens]
    shape = (cfg.num_layers, len(lens), 32, cfg.kv_heads, cfg.head_dim)
    # no entry of the pool is zero, so an entry left alone is told from
    # one written over
    k_in = rng.uniform(1.0, 2.0, shape).astype(np.float32)
    v_in = rng.uniform(1.0, 2.0, shape).astype(np.float32)
    full = []
    for b, (n, seq) in enumerate(zip(lens, seqs)):
        logits, (fk, fv) = model.apply(params, jnp.asarray(seq[None]),
                                       return_kv=True)
        full.append((np.asarray(logits[0]), np.asarray(fk[:, 0]),
                     np.asarray(fv[:, 0])))
        k_in[:, b, :n], v_in[:, b, :n] = full[b][1][:, :n], full[b][2][:, :n]
    block = jnp.asarray(np.stack([seq[n:] for n, seq in zip(lens, seqs)]))
    logits, (k_out, v_out) = model.apply(
        params, block, kv_cache=(jnp.asarray(k_in), jnp.asarray(v_in)),
        lengths=jnp.asarray(lens, jnp.int32))
    logits = np.asarray(logits).reshape(len(lens), s_q, -1)
    written = np.zeros(shape, bool)
    for b, n in enumerate(lens):
        written[:, b, n:n + s_q] = True
        np.testing.assert_allclose(logits[b], full[b][0][n:], rtol=2e-5,
                                   atol=2e-5)
        for out, ref in ((k_out, full[b][1]), (v_out, full[b][2])):
            np.testing.assert_allclose(np.asarray(out)[:, b, n:n + s_q],
                                       ref[:, n:], rtol=2e-5, atol=2e-5)
    for out, given in ((k_out, k_in), (v_out, v_in)):
        assert np.array_equal(np.asarray(out)[~written], given[~written])
        assert (np.asarray(out)[written] != given[written]).all()


# ---------------------------------------------------------------------------
# Prefix cache: radix-trie refcounting + bit-exact prefix-attached decode
# ---------------------------------------------------------------------------

def test_prefix_cache_trie_eviction_and_refcount_pinning():
    # Pure-python unit: referenced pages pin, only refs==0 leaves evict,
    # and eviction recycles pages without ever touching a live path.
    from horovod_tpu.serving.prefix_cache import PrefixCache

    pc = PrefixCache(num_slots=2, pages_per_slot=4, cache_pages=2,
                     page_size=4)
    hot = list(range(100, 113))  # 13 tokens -> 3 donated chunks
    a0 = pc.admit(0, hot)
    assert a0.prefix_len == 0 and len(a0.donated) == 3
    assert pc.lookup(hot) == 12
    # A second slot attaches to the donated chunks by reference.
    a1 = pc.admit(1, hot, max_prefix_len=pc.lookup(hot))
    assert a1.prefix_len == 12 and a1.shared == a0.donated
    pc.release(1)
    # Churn distinct prompts through slot 1 until the pool must evict.
    for n in range(8):
        pc.admit(1, [200 + 16 * n + i for i in range(13)])
        pc.release(1)
    assert pc.evictions > 0
    # Slot 0 still holds refs on the hot path: it must have survived
    # every eviction, and a fresh admission still fully shares it.
    assert pc.lookup(hot) == 12
    a2 = pc.admit(1, hot, max_prefix_len=12)
    assert a2.prefix_len == 12 and a2.shared == a0.donated
    pc.release(1)
    pc.release(0)
    # With every ref dropped the hot chunks are evictable in turn.
    for n in range(8):
        pc.admit(0, [600 + 16 * n + i for i in range(13)])
        pc.release(0)
    assert pc.lookup(hot) < 12
    # Conservation: pages never leak — everything resident or free.
    assert pc.resident_pages() + len(pc._free) == pc.num_pages - 1


def _make_paged_engine(small_model, num_slots=2, cache_pages=8):
    from horovod_tpu.serving.engine import PagedTransformerBackend

    model, params, mcfg = small_model
    backend = PagedTransformerBackend(model, params, mcfg, num_slots,
                                      max_seq_len=64,
                                      cache_pages=cache_pages, page_size=8)
    return ServingEngine(backend, ServingConfig(
        num_slots=num_slots, buckets=(8, 16), max_seq_len=64,
        record_logits=True, prefix_cache_pages=cache_pages, page_size=8))


def test_prefix_cache_bit_exact_vs_cold(small_model):
    # Three prompts share a 12-token system prefix.  The first admission
    # donates its chunks; the later two attach to the shared page and
    # prefill only their suffix — while decoding CONCURRENTLY through the
    # same shared page.  Tokens and logits must be bitwise identical to the
    # same engine cold: every prompt prefilled whole, alone, with no hit.
    rng = np.random.RandomState(3)
    shared = list(map(int, rng.randint(0, 64, 12)))
    tails = [list(map(int, rng.randint(0, 64, 4))) for _ in range(3)]

    warm = _make_paged_engine(small_model)
    first = warm.submit(shared + tails[0], 6)
    warm.run_until_idle()
    later = [warm.submit(shared + t, 6) for t in tails[1:]]  # same batch
    warm.run_until_idle()
    st = warm.stats()
    assert st["prefix_hits"] == 2 and st["prefix_hit_tokens"] == 16
    assert st["prefix_hit_rate"] > 0.0

    dense = _make_engine(small_model, num_slots=2)
    for req, tail in zip([first] + later, tails):
        cold = _make_paged_engine(small_model)
        solo = cold.submit(shared + tail, 6)
        cold.run_until_idle()
        assert cold.stats()["prefix_hits"] == 0
        assert solo.tokens == req.tokens, (tail, solo.tokens, req.tokens)
        for a, b in zip(solo.logits, req.logits):
            assert np.array_equal(a, b), \
                "prefix-attached decode diverged bitwise from cold prefill"
        # The dense backend's prefill is another program: causal attention
        # over the bucket's 16 keys, where the paged engine's goes through
        # the cache path over the slot's 64 of which the mask hides 48.
        # XLA:CPU sums the two rows in different orders (6e-7 on logits of
        # order 1 under jax 0.9.0; bitwise under the jax this test was
        # written on), so against it the claim is the tokens, and the
        # logits to a float32 rounding of the sums: docs/inference.md.
        solo = dense.submit(shared + tail, 6)
        dense.run_until_idle()
        assert solo.tokens == req.tokens, (tail, solo.tokens, req.tokens)
        for a, b in zip(solo.logits, req.logits):
            np.testing.assert_allclose(a, b, rtol=0, atol=1e-5)


def test_prefix_cache_lowers_ttft_at_high_sharing():
    # Nine prompts in ten open with one 48-token system prompt.  The stub
    # charges prefill by the token, so the cache's saving is the shared
    # prefix it does not prefill again: the same arrivals see their first
    # token sooner, and the streams are the same either way.
    from horovod_tpu.serving import loadgen

    w = loadgen.Workload(qps=30.0, duration_s=1.0, seed=5,
                         prompt_lens=(6, 14, 30), short_new=4, long_new=16,
                         long_frac=0.1, vocab=256, shared_frac=0.9,
                         shared_prefix_len=48)

    def run(pages):
        eng = ServingEngine(
            StubBackend(8, 256, step_s=0.0002, prefill_s_per_token=0.0008),
            ServingConfig(num_slots=8, buckets=(16, 32, 64, 96),
                          max_seq_len=128, prefix_cache_pages=pages,
                          page_size=8))
        rep = loadgen.run_load(eng, w, max_wall_s=60.0)
        assert rep["completed"] == rep["offered"] > 0, rep
        return rep["ttft_p50_ms"], eng.stats()["prefix_hit_rate"]

    (off_p50, off_rate), (on_p50, on_rate) = run(0), run(32)
    assert off_rate == 0.0 and on_rate > 0.2, (off_rate, on_rate)
    assert on_p50 < off_p50, (on_p50, off_p50)


# ---------------------------------------------------------------------------
# Speculative decoding: lossless greedy acceptance, both paths
# ---------------------------------------------------------------------------

def test_spec_decode_reject_path_identical_stream():
    # The positional stub's next token depends on absolute position, so
    # lookahead drafts never verify: speculation must degrade to plain
    # decode with the identical stream, not corrupt it.
    from horovod_tpu.serving.worker import expected_completion

    eng = ServingEngine(StubBackend(2), ServingConfig(
        num_slots=2, buckets=(8,), max_seq_len=64, spec_k=3))
    prompt = [3, 1, 4, 1, 5]
    req = eng.submit(prompt, 8)
    eng.run_until_idle()
    assert req.tokens == expected_completion(prompt, 8)
    st = eng.stats()
    assert st["spec_drafted"] > 0 and st["spec_accepted"] == 0


@pytest.mark.parametrize("slots, period, k, requests, max_new, uplift", [
    (1, 4, 3, 1, 12, 1.0),
    # the mix the retired serving bench timed: a decode and a verify step
    # cost the same, so 1.3x fewer steps is 1.3x the tokens a second
    (8, 8, 4, 16, 48, 1.3),
])
def test_spec_decode_accept_path_same_tokens_fewer_steps(
        slots, period, k, requests, max_new, uplift):
    # The periodic stub is predictable, so the n-gram proposer's drafts
    # verify: same tokens as plain decode in strictly fewer steps.
    import random

    def run(spec_k):
        eng = ServingEngine(StubBackend(slots, period=period), ServingConfig(
            num_slots=slots, buckets=(16,), max_seq_len=128, spec_k=spec_k))
        rng = random.Random(7)
        reqs = [eng.submit([rng.randrange(period)
                            for _ in range(rng.choice((6, 10)))], max_new)
                for _ in range(requests)]
        eng.run_until_idle()
        return eng, [r.tokens for r in reqs]

    (plain, a), (spec, b) = run(0), run(k)
    assert a == b
    st = spec.stats()
    assert st["spec_accepted"] > 0 and st["spec_accept_rate"] > 0.3
    assert spec.counters["steps"] < plain.counters["steps"]
    assert plain.counters["steps"] >= uplift * spec.counters["steps"]


def test_spec_decode_bit_exact_vs_plain(small_model):
    # Real transformer: greedy speculation emits the exact plain-decode
    # token stream.  Logits ride a different (block-verify) program
    # shape, so they are compared to tolerance, tokens bitwise.
    from horovod_tpu.serving.engine import TransformerBackend

    model, params, mcfg = small_model
    backend = TransformerBackend(model, params, mcfg, 2, max_seq_len=64)
    spec_eng = ServingEngine(backend, ServingConfig(
        num_slots=2, buckets=(8, 16), max_seq_len=64, spec_k=2,
        record_logits=True))
    rng = np.random.RandomState(1)
    prompts = [list(map(int, rng.randint(0, 64, n))) for n in (5, 9)]
    reqs = [spec_eng.submit(p, 7) for p in prompts]
    spec_eng.run_until_idle()
    assert spec_eng.counters["spec_drafted"] > 0

    plain = _make_engine(small_model, num_slots=2)
    for req, prompt in zip(reqs, prompts):
        solo = plain.submit(prompt, 7)
        plain.run_until_idle()
        assert solo.tokens == req.tokens, (prompt, solo.tokens, req.tokens)
        assert len(solo.logits) == len(req.logits)
        for a, b in zip(solo.logits, req.logits):
            np.testing.assert_allclose(a, b, rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# Which attention a prefill bucket runs (PR 41): chosen from the bucket's own
# dense logits, 4 * heads * S**2 bytes, against one constant; a menu that
# straddles the constant serves what an all-dense backend serves; the
# hvd_srv_prefill span says which form ran
# ---------------------------------------------------------------------------

MIB = 2 ** 20


def _shape_only(heads: int, max_seq_len: int):
    """A backend of ``heads`` query heads that holds no weights: the choice
    reads the configuration and the bucket, nothing else."""
    from horovod_tpu.models.transformer import (Transformer,
                                                TransformerConfig)
    from horovod_tpu.serving.engine import TransformerBackend

    cfg = TransformerConfig(vocab_size=8, num_layers=1, num_heads=heads,
                            head_dim=2, embed_dim=4, mlp_dim=4,
                            max_seq_len=max_seq_len)
    return TransformerBackend(Transformer(cfg), None, cfg, 1, max_seq_len)


# (query heads, bucket, its dense logits, the form): dsc1p3b-code-0.8knee's
# four buckets, cmdaplus-code8k-open's smallest and largest, the CPU tests'
@pytest.mark.parametrize("heads, bucket, logits_bytes, form", [
    (16, 512, 16 * MIB, "dense"),
    (16, 1024, 64 * MIB, "dense"),
    (16, 2048, 256 * MIB, "flash"),
    (16, 4096, 1024 * MIB, "flash"),
    (128, 512, 128 * MIB, "flash"),
    (128, 8192, 32768 * MIB, "flash"),
    (2, 16, 2048, "dense"),
    (4, 32, 16384, "dense"),
])
def test_a_bucket_s_attention_follows_its_own_logits(heads, bucket,
                                                     logits_bytes, form):
    from horovod_tpu.serving.engine import TransformerBackend

    assert 4 * heads * bucket ** 2 == logits_bytes
    assert (logits_bytes > TransformerBackend.FLASH_PREFILL_LOGITS_BYTES) \
        == (form == "flash")
    # whatever max_seq_len is, shorter than the bucket or far longer
    for max_seq_len in (8, 8448):
        assert _shape_only(heads, max_seq_len).prefill_attention(bucket) \
            == form


def test_the_limit_is_one_constant_below_every_sparse_bucket():
    from horovod_tpu.serving.engine import TransformerBackend

    limit = TransformerBackend.FLASH_PREFILL_LOGITS_BYTES
    # cmdaplus-code8k-open's 512 bucket (128 heads) stays on the kernel,
    # and the KB-sized shapes of the CPU tests stay dense
    assert 64 * MIB <= limit < 128 * MIB
    assert not hasattr(TransformerBackend, "DENSE_PREFILL_LOGITS_BYTES")


def test_at_the_limit_a_bucket_is_dense_and_a_byte_past_it_flash(monkeypatch):
    from horovod_tpu.serving.engine import TransformerBackend

    backend = _shape_only(16, 64)
    monkeypatch.setattr(TransformerBackend, "FLASH_PREFILL_LOGITS_BYTES",
                        4 * 16 * 1024 ** 2)
    assert backend.prefill_attention(1024) == "dense"
    assert backend.prefill_attention(1025) == "flash"
    assert not backend.flash_prefill            # a prompt of max_seq_len 64
    monkeypatch.setattr(TransformerBackend, "FLASH_PREFILL_LOGITS_BYTES",
                        4 * 16 * 64 ** 2 - 1)
    assert backend.flash_prefill


def test_a_model_s_own_attention_fn_is_kept_in_every_bucket():
    from horovod_tpu.models.transformer import (Transformer,
                                                TransformerConfig,
                                                dense_causal_attention)
    from horovod_tpu.serving.engine import TransformerBackend

    cfg = TransformerConfig(vocab_size=8, num_layers=1, num_heads=128,
                            head_dim=2, embed_dim=4, mlp_dim=4,
                            max_seq_len=16,
                            attention_fn=dense_causal_attention)
    backend = TransformerBackend(Transformer(cfg), None, cfg, 1, 16)
    assert backend.prefill_attention(8192) == "own"
    assert backend._prefill_model(8192) is backend.model
    assert not backend.flash_prefill


def _bucketed_engine(small_model) -> ServingEngine:
    from horovod_tpu.serving.engine import TransformerBackend

    model, params, cfg = small_model
    return ServingEngine(
        TransformerBackend(model, params, cfg, 2, 64),
        ServingConfig(num_slots=2, buckets=(16, 32), max_seq_len=64,
                      record_logits=True))


SHORT = [5, 9, 2, 7, 11, 3, 40, 41, 8]
LONG = [int(t) for t in np.random.RandomState(7).randint(0, 64, 27)]


@pytest.fixture()
def straddling(small_model, monkeypatch):
    """The all-dense engine, then one whose menu straddles the limit: the
    16 bucket's logits (4 * 2 * 16**2 bytes) are at it, the 32 bucket's
    past it."""
    from horovod_tpu.serving.engine import TransformerBackend

    dense = _bucketed_engine(small_model)
    assert [dense.backend.prefill_attention(b) for b in (16, 32)] == [
        "dense", "dense"]
    monkeypatch.setattr(TransformerBackend, "FLASH_PREFILL_LOGITS_BYTES",
                        4 * 2 * 16 ** 2)
    mixed = _bucketed_engine(small_model)
    assert [mixed.backend.prefill_attention(b) for b in (16, 32)] == [
        "dense", "flash"]
    return dense, mixed


def test_a_menu_that_straddles_the_limit_serves_what_dense_serves(straddling):
    dense, mixed = straddling
    served = {}
    for name, eng in (("dense", dense), ("mixed", mixed)):
        reqs = [eng.submit(SHORT, 6), eng.submit(LONG, 6)]
        eng.run_until_idle()
        served[name] = reqs
    rel = lambda got, want: float(  # noqa: E731
        np.max(np.abs(got - want)) / np.max(np.abs(want)))
    for got, want in zip(served["mixed"], served["dense"]):
        assert got.tokens == want.tokens and len(got.tokens) == 6
        for a, b in zip(got.logits, want.logits):
            assert rel(a, b) < 2e-4
    # the short prompt went through the same dense program: bit for bit
    assert all(np.array_equal(a, b) for a, b in zip(
        served["mixed"][0].logits, served["dense"][0].logits))
    # the rows the two forms wrote into the cache (slot 1 held LONG): a
    # layer's K and V are its input's projections, so layer 0's are the
    # same numbers and layer 1's differ by what the attention's rounding
    # differs by, far inside a bfloat16's spacing (2**-8)
    n = len(LONG)
    for pool_m, pool_d in ((mixed.backend.kk, dense.backend.kk),
                           (mixed.backend.vv, dense.backend.vv)):
        rows_m, rows_d = (np.asarray(p[:, 1, :n]) for p in (pool_m, pool_d))
        assert np.array_equal(rows_m[0], rows_d[0])
        assert 0 <= rel(rows_m[1], rows_d[1]) < 1e-5 < 2 ** -8


def test_the_prefill_span_carries_attn_and_the_summary_counts_by_it(
        straddling):
    from horovod_tpu.utils import profiling

    _, mixed = straddling
    profiling._ring.clear()
    for prompt in (SHORT, LONG, LONG[:20], SHORT[:4]):
        mixed.submit(prompt, 2)
    mixed.run_until_idle()
    prefills = [r for r in profiling.spans()
                if r.name == profiling.SRV_PREFILL]
    assert [(r.fields["bucket"], r.fields["length"], r.fields["attn"])
            for r in prefills] == [(16, 9, "dense"), (32, 27, "flash"),
                                   (32, 20, "flash"), (16, 4, "dense")]
    row = mixed.span_summary()[profiling.SRV_PREFILL]
    assert row["count"] == 4
    # a bucket of 32 is one q block of the kernel's: worked whole (PR 45)
    assert row["attn"] == {"dense": {"calls": 2, "prompt_tokens": 13},
                           "flash": {"calls": 2, "prompt_tokens": 47,
                                     "bucket_rows": 64, "attn_rows": 64}}


@pytest.mark.parametrize("kind", ["stub", "paged"])
def test_a_backend_that_chooses_nothing_writes_no_attn(kind, small_model):
    from horovod_tpu.serving.engine import PagedTransformerBackend
    from horovod_tpu.utils import profiling

    if kind == "stub":
        backend = StubBackend(2)
    else:
        model, params, cfg = small_model
        backend = PagedTransformerBackend(model, params, cfg, 2, 64,
                                          cache_pages=4, page_size=8)
    eng = ServingEngine(backend, ServingConfig(
        num_slots=2, buckets=(16,), max_seq_len=64, page_size=8))
    profiling._ring.clear()
    eng.submit(SHORT, 2)
    eng.run_until_idle()
    (prefill,) = [r for r in profiling.spans()
                  if r.name == profiling.SRV_PREFILL]
    assert set(prefill.fields) == {"bucket", "length", "prompt", "hit"}
    assert "attn" not in eng.span_summary()[profiling.SRV_PREFILL]


# ---------------------------------------------------------------------------
# Multi-model router + cross-model budget arbitration
# ---------------------------------------------------------------------------

def test_router_routes_least_loaded_and_scores_slo():
    from horovod_tpu.serving.router import ModelSpec, Router

    def make():
        return ServingEngine(StubBackend(2), ServingConfig(
            num_slots=2, buckets=(8,), max_seq_len=64))

    router = Router()
    router.add_model(ModelSpec("chat", slo_ttft_ms=1000.0), [make(), make()])
    router.add_model(ModelSpec("code", slo_ttft_ms=1000.0), [make()])
    with pytest.raises(KeyError):
        router.submit("nope", [1], 1)
    for i in range(6):
        router.submit("chat" if i % 2 else "code", [1, 2, i], 4)
    router.run_until_idle()
    st = router.stats()
    assert st["chat"]["completed"] == 3 and st["code"]["completed"] == 3
    assert st["chat"]["slo_attainment"] == 1.0  # generous SLO, tiny load
    # Least-loaded admission actually spread chat across both replicas.
    assert all(e.counters["completed"] >= 1
               for e in router._engines["chat"])
    # remove_replica never retires the last seat of a model.
    assert router.remove_replica("code") is None
    assert router.remove_replica("chat") is not None


def test_router_autoscaler_pairs_shrink_with_grow_under_budget():
    from horovod_tpu.serving.autoscale import AutoscaleConfig
    from horovod_tpu.serving.router import (ModelSpec, Router,
                                            RouterAutoscaler)

    def make():
        return ServingEngine(StubBackend(2), ServingConfig(
            num_slots=2, buckets=(8,), max_seq_len=64))

    specs = [ModelSpec("chat"), ModelSpec("code")]
    router = Router()
    router.add_model(specs[0], [make()])
    router.add_model(specs[1], [make(), make()])
    for _ in range(20):  # chat is pressured, code fully idle
        router.submit("chat", [1, 2], 4)
    t = [0.0]
    auto = RouterAutoscaler(
        specs, budget=3,
        config=AutoscaleConfig(min_replicas=1, max_replicas=4,
                               queue_high=4.0, idle_s=1.0, cooldown_s=0.0),
        clock=lambda: t[0])
    # Budget full, donor's idle window not yet elapsed: the grow waits.
    assert auto.decide(router) == []
    t[0] += 2.0
    # Now code's policy independently wants to shrink: the paired move
    # migrates its seat to chat without ever exceeding the budget.
    assert auto.decide(router) == [("code", "shrink"), ("chat", "grow")]


# ---------------------------------------------------------------------------
# The serving.tick collective: fleet counters + response-cache warmth
# ---------------------------------------------------------------------------

def test_tick_collective_warm_cache_and_fleet_counters():
    from horovod_tpu.core.engine import NativeEngine
    from horovod_tpu.core.executors import local_executor

    coll = NativeEngine(0, 1, executor=local_executor,
                        coordinator_host="127.0.0.1",
                        coordinator_port=_free_port(), cycle_time_ms=1.0)
    try:
        eng = ServingEngine(StubBackend(2), ServingConfig(
            num_slots=2, buckets=(8,), max_seq_len=64), collective=coll)
        for k in range(5):
            eng.submit([k + 1, k + 2], 6)
        eng.run_until_idle()
        steps = eng.counters["steps"]
        assert steps > 2
        # Fleet aggregate (size 1: equals local counters).
        assert eng.fleet["completed"] == 5.0
        assert eng.fleet["steps"] == float(steps)
        assert eng.fleet["done_replicas"] == 0.0
        # ONE fixed-signature allreduce per tick: the first negotiates,
        # every later one is a response-cache hit — the zero-NEGOTIATED
        # steady state the ISSUE acceptance demands.
        cs = coll.cache_stats()
        assert cs["misses"] <= 1, cs
        assert cs["hits"] >= steps - 1, (cs, steps)
    finally:
        coll.shutdown()


# ---------------------------------------------------------------------------
# Autoscaler policy (pure decision logic; the fleet soak runs under slow)
# ---------------------------------------------------------------------------

def test_autoscaler_grow_shrink_cooldown():
    from horovod_tpu.serving.autoscale import AutoscaleConfig, Autoscaler

    t = [0.0]
    auto = Autoscaler(AutoscaleConfig(min_replicas=1, max_replicas=3,
                                      queue_high=4.0, idle_s=1.0,
                                      cooldown_s=10.0),
                      clock=lambda: t[0])
    assert auto.decide(1, queued=40, active_slots=8) == "grow"
    t[0] += 1.0  # within cooldown: no flapping
    assert auto.decide(2, queued=40, active_slots=8) is None
    t[0] += 20.0
    assert auto.decide(3, queued=400, active_slots=8) is None  # max cap
    for _ in range(60):  # idle long enough to shrink
        t[0] += 0.5
        d = auto.decide(3, queued=0, active_slots=0)
        if d is not None:
            break
    assert d == "shrink"
    t[0] += 100.0
    assert auto.decide(1, queued=0, active_slots=0) is None  # min floor


@pytest.mark.slow
def test_serving_autoscale_soak():
    """Grow under load + SIGKILL mid-traffic + fleet-wide hot swap: no
    accepted request lost or corrupted, weights cloned over the data
    plane with zero disk reads, bounded end to end.  The chaos scenario
    runs with the prefix cache and speculative decoding enabled in every
    worker — the fast paths must not change a single completion CRC (the
    stub's stream is a pure function of the prompt), and a replica dying
    with slots attached to shared pages must not poison survivors'
    retries."""
    from horovod_tpu.serving import soak

    reps = int(os.environ.get("SERVING_SOAK_REPS", "1"))
    for rep in range(reps):
        r = soak.run_fleet(n=3, qps=40.0, duration_s=4.0, kill=True,
                           join=True, swap=(rep % 2 == 0), seed=rep,
                           prefix_cache=True, spec_k=3)
        assert r["lost"] == 0 and r["completed"] == r["accepted"], r
        assert r["join_disk_reads"] == 0, r
        assert r["killed"] == 1, r
