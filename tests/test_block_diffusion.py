"""A block-diffusion model on the serving path (PR 56): the block-causal
mask in its three forms (dense, over a cache, in the flash forward kernel),
the per-head QK-norm, a prefill that caches whole blocks and passes over a
block through ``TransformerBackend`` against the plain reference's block
states, and the scheduler of ``ServingEngine`` on the stub with scripted
confidences: both unmasking rules, tokens final out of order and handed
over in order, the first stamp (the pass that made the first block whole),
``max_new_tokens`` inside a block,
eviction without the last commit, the guard of ``max_seq_len``, slots out of
phase, the spans' fields, and what is refused by name.  One small model a
module (2 layers, 8 experts top-2, hidden 64, block 4), in float32 so that
the tolerances are the arithmetic's."""

import dataclasses
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from horovod_tpu.serving.engine import (ServingConfig, ServingEngine,  # noqa: E402
                                        StubBackend)
from horovod_tpu.utils import profiling  # noqa: E402

MASK = 95
TINY = {"family": "sdar_moe_serve", "attention_bias": False,
        "decoder_sparse_step": 1, "head_dim": 16, "hidden_act": "silu",
        "hidden_size": 64, "intermediate_size": 96,
        "max_position_embeddings": 128, "mlp_only_layers": [],
        "model_type": "sdar_moe", "moe_intermediate_size": 32,
        "norm_topk_prob": True, "num_attention_heads": 4, "num_experts": 8,
        "num_experts_per_tok": 2, "num_hidden_layers": 2,
        "num_key_value_heads": 2, "rms_norm_eps": 1e-6, "rope_scaling": None,
        "rope_theta": 1000000, "sliding_window": None,
        "tie_word_embeddings": False, "use_sliding_window": False,
        "vocab_size": 96, "initializer_range": 0.3,
        "generation": {"block_length": 4, "denoising_steps": 4,
                       "remasking": "low_confidence_static",
                       "confidence_threshold": 0.9, "mask_token_id": MASK}}
TRAFFIC = {"num_slots": 3, "max_seq_len": 40, "prefill_buckets": [8, 16],
           "compare_requests": 3}


# -- the model against the reference ------------------------------------------

@pytest.fixture(scope="module")
def small():
    """(family, reference, model config in float32, the reference's weights,
    the program's parameters, 20 prompt ids)."""
    import jax
    import jax.numpy as jnp

    from benchmarks.families import sdar_moe_serve as family

    mcfg = dataclasses.replace(
        family.model_config(TINY, TRAFFIC), dtype=jnp.float32,
        param_dtype=jnp.float32)
    weights = family.draw(TINY, family.seed_key(3))
    params = jax.tree.map(lambda x: x.astype(jnp.float32),
                          family.to_program(weights, TINY))
    ids = np.random.default_rng(0).integers(0, MASK, 20).astype(np.int32)
    return family, family.reference, mcfg, weights, params, ids


def test_the_block_causal_forward_is_the_references(small):
    """Logits of a whole sequence, per-head QK-norm and all: float32 both
    sides, so the tolerance is summation order (1e-4 of the logits'
    spread); the causal mask in the mask's place is far outside it."""
    import jax
    import jax.numpy as jnp

    from horovod_tpu.models import Transformer

    _, reference, mcfg, weights, params, ids = small
    got = jax.jit(Transformer(mcfg).apply)(params, ids[None])[0]
    x, keys, _ = reference.sequence(weights, jnp.asarray(ids), TINY, 4,
                                    query_block=4)
    want = reference.head(x, weights, TINY)
    spread = float(jnp.std(want))
    assert float(jnp.abs(got - want).max()) < 1e-4 * spread
    assert keys.shape == (2, 20, 2, 16)
    causal = jax.jit(Transformer(dataclasses.replace(
        mcfg, attention_block=None)).apply)(params, ids[None])[0]
    assert float(jnp.abs(causal - want).max()) > 0.05 * spread


@pytest.fixture(scope="module")
def served(small):
    """Three requests (prompts of 8, 9 and 11 ids: 0, 1 and 3 past their
    whole blocks) through one engine over the real backend, logits kept."""
    from horovod_tpu.models import Transformer
    from horovod_tpu.serving.engine import TransformerBackend

    family, _, mcfg, _, params, ids = small
    scfg = dataclasses.replace(family.serving_config(TINY, TRAFFIC),
                               record_logits=True)
    backend = TransformerBackend(Transformer(mcfg), params, mcfg, 3, 40)
    engine, timed = recorded(family, backend, scfg)
    requests = [engine.submit([int(t) for t in ids[:n]], 10)
                for n in (8, 9, 11)]
    engine.run_until_idle()
    family._STATES.update(timed.by_request)     # (a run's release() does)
    return engine, backend, requests, timed


def recorded(family, backend, scfg, engine=ServingEngine, **more):
    """An engine over ``backend`` inside the family's wrapper, which keeps
    the blocks every pass was given (the engine's ``Request`` does not)."""
    timed = family.TimedPasses(backend, scfg.confidence_threshold)
    return engine(timed, scfg, on_complete=timed.finished, **more), timed


def test_prefill_then_passes_are_the_references_block_states(small, served):
    """Every denoising pass of every block (three or four a request): the
    reference, given the final tokens before the block and the state going
    into the pass, picks the position and the token the next state shows
    final (the request's last pass has no next state); and the logits kept
    for each token handed over are the reference's of the state whose pass
    made its block whole, to 1e-4 of their spread."""
    import jax.numpy as jnp

    _, reference, _, weights, _, _ = small
    family = small[0]
    engine, _, requests, timed = served
    assert timed.above_threshold == 0       # drawn weights: no 0.9 anywhere
    for req in requests:
        assert len(req.tokens) == 10 and req.finish_reason == "max_new_tokens"
        assert MASK not in req.tokens
        seq = np.zeros(40, np.int32)
        seq[:len(req.prompt) + 10] = req.prompt + req.tokens
        _, keys, values = reference.sequence(weights, jnp.asarray(seq), TINY,
                                             4)
        given = timed.by_request[family._ids(req.prompt, req.tokens)]
        log = family.passes_of(given, MASK)
        assert len(given) == req.passes     # every pass, commits and all
        assert len(log) == sum((g[1] == MASK).any() for g in given) - 1
        # ... and the pass it ended in, whose state is the last it was given
        log.append((given[-1][0], tuple(given[-1][1].tolist()), None))
        starts = np.array([p[0] for p in log], np.int32)
        states = np.array([p[1] for p in log], np.int32)
        assert len(set(starts)) >= 3 and starts[0] == len(req.prompt) // 4 * 4
        logits = reference.block_logits(weights, keys, values,
                                        jnp.asarray(starts),
                                        jnp.asarray(states), TINY)
        tokens, conf = reference.confidences(logits, MASK)
        kept = iter(req.logits)
        spread = float(jnp.std(logits))
        for i, (start, state, made) in enumerate(log):
            masked = np.array(state) == MASK
            best = int(np.argmax(np.where(masked, np.asarray(conf[i]),
                                          -np.inf)))
            if made is not None:
                assert made == ((best, int(tokens[i, best])),)
            if masked.sum() > 1:
                continue
            # the pass made its block whole: the block is handed over, past
            # the prompt's tail, with this state's logits
            tail = len(req.prompt) - start if start == starts[0] else 0
            for j in range(tail, 4):
                got = next(kept, None)
                if got is None:         # the rest of the block was dropped
                    break
                assert float(np.abs(got - np.asarray(logits[i, j])).max()) \
                    < 1e-4 * spread
        assert next(kept, None) is None
    c = engine.counters
    assert c["tokens"] == 30 and c["tokens_final"] == c["denoise_passes"]
    assert c["commit_passes"] == 7      # 2 + 2 + 3 blocks left behind


def test_the_runs_comparison_passes_as_served_and_fails_the_control(
        small, served):
    """``compare_passes`` on what the engine recorded: both numbers 0 (the
    program in float32 IS the reference); the float8 control through the
    same code is past (a)'s limit, and a block filled left to right past
    (b)'s."""
    import jax.numpy as jnp

    family = small[0]
    finished = [(np.array(r.prompt), np.array(r.tokens)) for r in served[2]]
    sound = family.compare_passes(TINY, TRAFFIC, finished, 3)
    judged = sum(len(family.passes_of(g, MASK))
                 for g in served[3].by_request.values())
    assert [c["name"] for c in sound] == [
        "served_token_gap_below_reference_best",
        "chosen_position_confidence_gap"]
    assert all(c["ok"] and c["error"] < 1e-3 for c in sound)
    assert sound[0]["requests"] == 3 and sound[0]["states"] == judged > 25
    control = family.compare_passes(TINY, TRAFFIC, finished, 3,
                                    control=jnp.float8_e4m3fn)
    # not correct by one of the limits, (a); (b) moves with it
    assert not control[0]["ok"] and control[1]["error"] > 0.01
    # (b)'s own control, a block filled left to right, is past (b)'s limit
    assert sound[1]["leftmost_rule_gap"] > family.CONFIDENCE_GAP_LIMIT
    assert sound[1]["widest"] < 1e-3
    # nothing recorded is nothing shown
    unseen = family.compare_passes(
        TINY, TRAFFIC, [(np.arange(8), np.arange(4))], 3)
    assert not unseen[0]["ok"] and not unseen[1]["ok"]
    # benchmarks/control.py's one check is (a)
    one, = family.compare_served(TINY, TRAFFIC, finished, 3)
    assert one == sound[0]


def test_a_block_that_is_not_committed_fails_the_comparison(small, served):
    """The proof for a missing commit: an engine that moves on a block
    without the pass over the final block leaves the last denoising pass's
    rows (a mask where the last position became final) in the cache; every
    later block is computed against them and (a) is past its limit."""
    from horovod_tpu.models import Transformer
    from horovod_tpu.serving.engine import TransformerBackend

    family, _, mcfg, _, params, ids = small

    class NoCommit(ServingEngine):
        def _hand_block(self, req, slot, *args):
            handed = super()._hand_block(req, slot, *args)
            if req.state != "DONE":     # on a block, and no pass over it
                self.lengths[slot] += self.block
                self._open_block(slot)
            return handed

    scfg = family.serving_config(TINY, TRAFFIC)
    backend = TransformerBackend(Transformer(mcfg), params, mcfg, 3, 40)
    backend._decode = served[1]._decode     # the program compiled already
    backend._prefill = served[1]._prefill
    engine, timed = recorded(family, backend, scfg, engine=NoCommit)
    requests = [engine.submit([int(t) for t in ids[:n]], 16)
                for n in (8, 9, 11)]
    engine.run_until_idle()
    assert engine.counters["commit_passes"] == 0    # no pass was one
    family._STATES.update(timed.by_request)
    gap, _ = family.compare_passes(
        TINY, TRAFFIC, [(np.array(r.prompt), np.array(r.tokens))
                        for r in requests], 3)
    assert not gap["ok"] and gap["error"] > 2 * family.GAP_LIMIT


def test_the_mask_id_is_never_sampled(small, served):
    """A backend told that the token the model likes best at a position is
    the mask's id answers the second best there, and its confidence."""
    import jax
    import jax.numpy as jnp

    from horovod_tpu.models import Transformer
    from horovod_tpu.serving.engine import TransformerBackend

    _, _, mcfg, _, params, _ = small
    backend = served[1]
    block = np.full((3, 4), MASK, np.int32)
    live = np.array([True, False, False])
    lengths = np.zeros(3, np.int32)
    tokens, _, _ = backend.decode(block, lengths, live)
    best = int(tokens[0, 0])
    # the same ids through a model whose mask is `best`: the same logits
    other = dataclasses.replace(mcfg, mask_token_id=best)
    second = TransformerBackend(Transformer(other), params, other, 3, 40)
    tokens2, logits2, conf2 = second.decode(block, lengths, live)
    row = np.asarray(logits2)[0, 0]
    assert int(np.argmax(row)) == best
    assert int(tokens2[0, 0]) == int(np.argsort(row)[-2])
    assert best not in np.asarray(tokens2)[0]
    want = jax.nn.softmax(jnp.asarray(row))[int(tokens2[0, 0])]
    assert abs(float(conf2[0, 0]) - float(want)) < 1e-6


def test_the_flash_forward_takes_the_block_mask_and_no_gradient():
    import jax
    import jax.numpy as jnp

    from horovod_tpu.models.transformer import (cached_decode_attention,
                                                dense_causal_attention)
    from horovod_tpu.ops.flash_attention import flash_attention

    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(keys[0], (1, 256, 4, 32))
    k = jax.random.normal(keys[1], (1, 256, 2, 32))
    v = jax.random.normal(keys[2], (1, 256, 2, 32))
    dense = dense_causal_attention(q, k, v, block=4)
    for told in ({}, {"q_len": 200, "k_len": 200}):
        got = flash_attention(q, k, v, block=4, block_q=128, block_k=128,
                              sub=64, **told)
        n = told.get("q_len", 256)
        assert float(jnp.abs(got[:, :n] - dense[:, :n]).max()) < 1e-5
    assert float(jnp.abs(dense_causal_attention(q, k, v) - dense).max()) > 0.1
    # a block's rows against the cache: the dense form's rows
    rows = cached_decode_attention(q[:, 96:100], k, v, jnp.array([96]),
                                   block=4)
    assert float(jnp.abs(rows - dense[:, 96:100]).max()) < 1e-5
    with pytest.raises(NotImplementedError, match=r"block=4\) has no "
                       r"backward.*dense_causal_attention\(block="):
        jax.grad(lambda q: flash_attention(q, k, v, block=4).sum())(q)
    # the dense form is differentiated
    assert jnp.isfinite(jax.grad(lambda q: dense_causal_attention(
        q, k, v, block=4).sum())(q)).all()
    with pytest.raises(ValueError, match="power of two"):
        flash_attention(q, k, v, block=3)
    with pytest.raises(ValueError, match="no window"):
        flash_attention(q, k, v, block=4, window=8)


def test_qk_norm_a_head_has_one_vector_and_olmoes_form_is_untouched():
    import jax
    import jax.numpy as jnp

    from horovod_tpu.models import Transformer, TransformerConfig

    base = dict(vocab_size=32, num_layers=1, num_heads=4, num_kv_heads=2,
                head_dim=8, embed_dim=16, mlp_dim=16, max_seq_len=8)
    shapes = {}
    for form in (True, "head"):
        model = Transformer(TransformerConfig(qk_norm=form, **base))
        tree = jax.eval_shape(lambda m=model: m.init(
            jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))
        attn = tree["params"]["layer_0"]["attn"]
        shapes[form] = (attn["q_norm"]["scale"].shape,
                        attn["k_norm"]["scale"].shape)
    assert shapes == {True: ((32,), (16,)), "head": ((8,), (8,))}
    with pytest.raises(ValueError, match="qk_norm is False, True"):
        Transformer(TransformerConfig(qk_norm="heads", **base)).init(
            jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))


# -- the scheduler on the stub -------------------------------------------------

def engine_of(slots=2, rule="low_confidence_static", steps=4, conf=None,
              max_len=64, threshold=0.9, buckets=(8, 16), **more):
    """(engine, stub): the stub inside the family's wrapper, so that the
    blocks every pass was given are kept (``engine.backend.states``,
    ``.by_request``); the block and the mask id are the backend's."""
    from benchmarks.families import sdar_moe_serve as family

    backend = StubBackend(slots, vocab_size=256, block=4, mask_id=255,
                          confidences=conf)
    backend.last_expert_pairs = np.zeros((1, 1), np.int64)  # (not sparse)
    now = [0.0]

    def clock():
        now[0] += 1.0
        return now[0]

    engine, _ = recorded(family, backend, ServingConfig(
        num_slots=slots, buckets=buckets, max_seq_len=max_len,
        denoise_steps=steps, unmask_rule=rule,
        confidence_threshold=threshold, **more), clock=clock)
    return engine, backend


def log_of(engine, req):
    """The request's denoising passes but the one it ended in, as the
    family reads them from the states its slot was given."""
    from benchmarks.families import sdar_moe_serve as family

    return family.passes_of(
        engine.backend.by_request[family._ids(req.prompt, req.tokens)], 255)


def made_final_a_pass(n: int) -> list[int]:
    """``made_final`` of the last ``n`` passes, from the steps they ran in."""
    return [r.fields["made_final"] for r in profiling.spans()
            if r.name == profiling.SRV_STEP and "made_final" in r.fields][-n:]


def stub_tokens(backend, first, n):
    return [backend.block_token(p) for p in range(first, first + n)]


def test_static_rule_one_token_a_pass_and_a_commit_a_block():
    engine, backend = engine_of()
    req = engine.submit(list(range(1, 7)), 5)   # 4 cached, a tail of 2
    engine.run_until_idle()
    assert req.tokens == stub_tokens(backend, 6, 5)
    # two passes fill the first block (a tail of 2), a commit, four fill the
    # second, of which three tokens were asked
    assert (req.finish_reason, req.passes, req.first_token_passes) == (
        "max_new_tokens", 7, 2)
    # submitted at tick 1, the prefill's return 2, the passes' stamps 3 and
    # 4: the first tokens are the second pass's, which made the block whole,
    # not the prefill's and not the first pass's
    assert (req.submitted_t, req.ttft_s) == (1.0, 3.0)
    # a block at one stamp; the commit and four passes until the next
    assert req.token_lat_s == [0.0, 5.0, 0.0, 0.0]
    log = log_of(engine, req)       # (the pass it ended in is not there)
    assert [p[0] for p in log] == [4, 4, 8, 8, 8]
    assert log[0] == (4, (5, 6, 255, 255), ((2, req.tokens[0]),))
    assert made_final_a_pass(7) == [1, 1, 0, 1, 1, 1, 1]
    c = engine.counters
    assert (c["denoise_passes"], c["commit_passes"], c["tokens_final"]) \
        == (6, 1, 6)
    assert c["tokens_per_pass"] == pytest.approx(6 / 7)


@pytest.mark.parametrize("order", [(3, 2, 1, 0), (0, 1, 2, 3), (2, 0, 3, 1)])
def test_a_block_is_handed_over_whole_whatever_the_order(order):
    """Confidence rising along the block (the last position final first),
    falling (the first one first: a final token waits for its block) or
    neither: the block's tokens are handed over in order, at the stamp of
    the pass that made it whole."""
    rank = np.argsort(order)
    engine, backend = engine_of(
        conf=lambda call, block, lengths: np.broadcast_to(
            4.0 - rank, block.shape))
    req = engine.submit(list(range(1, 5)), 8)
    seen = []
    while engine.queue or any(engine.slots):
        engine.step()
        seen.append(len(req.tokens))
    # four passes a block, all four tokens at the fourth; a commit; again
    assert seen == [0, 0, 0, 4, 4, 4, 4, 4, 8]
    assert req.tokens == stub_tokens(backend, 4, 8)
    assert req.first_token_passes == 4
    # the first stamp is the fourth pass's (submitted 1, prefilled 2)
    assert (req.submitted_t, req.ttft_s) == (1.0, 5.0)
    assert req.token_lat_s[:4] == [0.0, 0.0, 0.0, 5.0]
    assert [m for _, _, ((m, _),) in log_of(engine, req)[:4]] == list(order)
    block = engine.span_summary()[profiling.SRV_DECODE]["block"]
    assert block["tokens_final"] >= 8 and block["handed"] >= 8


@pytest.mark.parametrize("rule,steps,conf,per_pass", [
    # two a pass by the static rule at two steps
    ("low_confidence_static", 2, None, [2, 2]),
    # the dynamic rule: all four pass the threshold in one pass
    ("low_confidence_dynamic", 4, [0.95, 0.99, 0.91, 0.97], [4]),
    # ... two pass it, then one a pass (at least block / steps)
    ("low_confidence_dynamic", 4, [0.95, 0.2, 0.1, 0.97], [2, 1, 1]),
    # ... none passes it: the static rule's passes
    ("low_confidence_dynamic", 4, [0.5, 0.4, 0.3, 0.2], [1, 1, 1, 1]),
])
def test_the_two_rules(rule, steps, conf, per_pass):
    script = None if conf is None else (
        lambda call, block, lengths: np.broadcast_to(
            np.array(conf, np.float32), block.shape))
    engine, backend = engine_of(rule=rule, steps=steps, conf=script)
    req = engine.submit(list(range(1, 5)), 4)
    engine.run_until_idle()
    assert made_final_a_pass(len(per_pass)) == per_pass
    assert [len(made) for _, _, made in log_of(engine, req)] == per_pass[:-1]
    assert req.tokens == stub_tokens(backend, 4, 4)
    assert req.passes == len(per_pass)          # evicted without the commit
    assert engine.counters["commit_passes"] == 0
    if conf is not None:        # the family's count of what passed 0.9
        first = sum(c > 0.9 for c in conf)
        assert engine.backend.above_threshold >= first


@pytest.mark.parametrize("asked,passes", [(1, 4), (4, 4), (5, 9)])
def test_max_new_tokens_cuts_inside_a_block(asked, passes):
    """1 and 4 end with the first block (no commit: of 1 asked, the block is
    made whole and its other three positions dropped); 5 needs a commit and
    the second block, whose other positions are dropped."""
    engine, backend = engine_of()
    req = engine.submit(list(range(1, 9)), asked)
    done = engine.run_until_idle()
    assert done == [req] and req.tokens == stub_tokens(backend, 8, asked)
    assert req.passes == passes
    assert engine.counters["commit_passes"] == (1 if asked > 4 else 0)
    assert engine.lengths.tolist() == [0, 0] and not engine.block_masked.any()
    records = [r for r in profiling.spans()
               if r.name == profiling.SRV_REQUEST and r.rid == req.rid]
    assert records[-1].fields["passes"] == passes
    assert records[-1].fields["first_token_passes"] == 4


def test_max_seq_len_is_guarded_a_block_ahead():
    engine, backend = engine_of(max_len=16)
    req = engine.submit(list(range(1, 10)), 100)    # 8 cached, a tail of 1
    engine.run_until_idle()
    # the block at 8 fills (3 tokens) and commits; the block at 12 fills and
    # commits; a block at 16 does not fit
    assert req.finish_reason == "max_seq_len" and len(req.tokens) == 7
    assert req.tokens == stub_tokens(backend, 9, 7)
    # a prompt whose own block just fits fills it and stops at its commit
    late = engine.submit(list(range(1, 16)), 4)     # 12 cached, block to 16
    assert late.finish_reason is None
    engine.run_until_idle()
    assert len(late.tokens) == 1 and late.finish_reason == "max_seq_len"
    # ... and one whose own block does not fit is refused at the door
    engine2, _ = engine_of(max_len=18, buckets=(8, 32))
    refused = engine2.submit(list(range(1, 18)), 4)     # 16 cached: 20 > 18
    assert refused.finish_reason == "rejected"


def test_a_slot_is_reused_and_three_slots_run_out_of_phase():
    engine, backend = engine_of(slots=3)
    lens = [4, 5, 7, 6]
    reqs = [engine.submit(list(range(1, n + 1)), 6) for n in lens]
    phases = []
    while engine.queue or any(engine.slots):
        before = engine.counters["commit_passes"]
        engine.step()
        phases.append(engine.counters["commit_passes"] - before)
    for req, n in zip(reqs, lens):
        assert req.tokens == stub_tokens(backend, n, 6)
    # the fourth request took a slot another left, from a clean block
    assert reqs[3].slot in {r.slot for r in reqs[:3]}
    assert log_of(engine, reqs[3])[0][1] == (5, 6, 255, 255)
    # commits fell in different passes (tails of 0, 1 and 3), never all
    # three slots at once
    assert max(phases) < 3 and sum(phases) == engine.counters["commit_passes"]
    spans = [r.fields for r in profiling.spans()
             if r.name == profiling.SRV_DECODE and "block" in r.fields]
    mixed = [f for f in spans[-len(phases):] if 0 < f["commits"] < f["slots"]]
    assert mixed and all(f["block"] == 4 for f in spans)
    assert set(mixed[0]) >= {"masked_in", "commits", "slots", "live_tokens"}
    # what only a pass's results say is on the step it ran in; the decode
    # span ends as the backend's call returns, as a one-token step's does
    steps = [r for r in profiling.spans() if r.name == profiling.SRV_STEP
             and "made_final" in r.fields][-len(phases):]
    calls = {r.cause: r for r in profiling.spans()
             if r.name == profiling.SRV_DECODE}
    for step in steps:
        call = calls[step.id]
        assert step.fields["made_final"] == \
            call.fields["slots"] - call.fields["commits"]
        assert step.start <= call.start <= call.end <= step.end
    assert sum(s.fields["handed"] for s in steps) == 24


def test_the_prefill_span_says_what_was_cached():
    engine, _ = engine_of()
    engine.submit(list(range(1, 8)), 2)
    engine.run_until_idle()
    call = [r for r in profiling.spans()
            if r.name == profiling.SRV_PREFILL][-1].fields
    assert (call["cached"], call["length"], call["prompt"]) == (4, 4, 7)


# -- what is refused, by name -------------------------------------------------

def test_what_a_block_model_cannot_be_served_with_is_refused_by_name():
    from horovod_tpu.models import TransformerConfig
    from horovod_tpu.models.transformer import init_kv_pages

    block = dict(num_slots=2, buckets=(8,), max_seq_len=32)
    stub = StubBackend(2, block=4, mask_id=255)
    with pytest.raises(NotImplementedError, match="spec_k.*block-diffusion"):
        ServingEngine(stub, ServingConfig(spec_k=2, **block))
    with pytest.raises(NotImplementedError,
                       match="prefix cache.*block-diffusion"):
        ServingEngine(stub, ServingConfig(prefix_cache_pages=8, page_size=4,
                                          **block))
    with pytest.raises(ValueError, match="the backend's mask_id"):
        ServingEngine(StubBackend(2, block=4), ServingConfig(**block))
    # the block and the mask id are the backend's: nothing to set, and the
    # steps default to a position a pass
    assert ServingEngine(stub, ServingConfig(**block)).block == 4
    assert ServingEngine(StubBackend(2), ServingConfig(**block)).block == 0
    with pytest.raises(ValueError, match="denoise_steps dividing"):
        ServingEngine(stub, ServingConfig(**dict(block, denoise_steps=3)))
    with pytest.raises(ValueError, match="unmask_rule"):
        ServingEngine(stub, ServingConfig(**dict(block, unmask_rule="top")))
    cfg = TransformerConfig(vocab_size=32, num_layers=1, num_heads=2,
                            head_dim=8, embed_dim=16, mlp_dim=16,
                            attention_block=4, mask_token_id=31)
    with pytest.raises(NotImplementedError, match="paged pool.*block-causal"):
        init_kv_pages(cfg, 8, 4)
    with pytest.raises(ValueError, match="mask_token_id"):
        from horovod_tpu.serving.engine import TransformerBackend

        TransformerBackend(None, None, dataclasses.replace(
            cfg, mask_token_id=None), 2, 32)
    with pytest.raises(NotImplementedError, match="attention_block beside"):
        import jax
        import jax.numpy as jnp

        from horovod_tpu.models import Transformer

        Transformer(dataclasses.replace(
            cfg, layer_types=("sliding_attention",), sliding_window=4)).init(
                jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))


def test_python_m_serving_serves_a_block_model(tmp_path):
    """``python -m horovod_tpu.serving`` given a model file with
    ``attention_block``: the block and the mask id reach the scheduler
    from the backend, which has them from the model: no environment name
    and no ``ServingConfig`` field of their own."""
    import json
    import subprocess

    model = tmp_path / "model.json"
    model.write_text(json.dumps(dict(
        vocab_size=64, num_layers=1, num_heads=2, num_kv_heads=1, head_dim=8,
        embed_dim=16, mlp_dim=16, qk_norm="head", attention_block=4,
        mask_token_id=63)))
    env = dict(os.environ, JAX_PLATFORMS="cpu", HVD_TPU_SERVE_MODEL=str(model),
               HVD_TPU_SERVE_SLOTS="2", HVD_TPU_SERVE_BUCKETS="8,16",
               HVD_TPU_SERVE_MAX_LEN="64", HVD_TPU_SERVE_QPS="8",
               HVD_TPU_SERVE_DURATION_S="0.5")
    proc = subprocess.run([sys.executable, "-m", "horovod_tpu.serving"],
                          env=env, cwd=ROOT, capture_output=True, text=True,
                          timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
    report = json.loads(proc.stdout.split("SERVE_REPORT ")[1])
    assert report["completed"] > 0 and report["rejected"] == 0
    block = report["spans"]["hvd_srv_decode"]["block"]
    # a position a pass (denoise_steps unset: the block's length)
    assert block["tokens_final"] == block["denoise_passes"] > 0
    assert 0.5 < block["tokens_per_pass"] <= 1.0
