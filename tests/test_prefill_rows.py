"""A served prefill's position-wise layers stop at the prompt's own length
(PR 47): a pass that is told ``lengths`` beside ``return_kv`` over more than
two ``ROW_BLOCK`` of positions runs its projections, MLPs, shared experts and
routers over the prompt's row blocks in a loop with a traced count
(``models/transformer.py``, ``_over_rows``).  On thin models of the four
served kinds at the real 1024-row block: the rows below the length, the first
token, the logits and the pool are what the same call gives with the loops
withheld, to the bit; the rows of the blocks never visited are exactly 0
whatever the padding holds; no other call has the loop; the serving span
carries ``rows_worked``."""

import contextlib
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.models import Transformer, TransformerConfig
from horovod_tpu.models import moe as M
from horovod_tpu.models import transformer as T
from horovod_tpu.ops.flash_attention import make_flash_attention
from horovod_tpu.serving.engine import (ServingConfig, ServingEngine,
                                        TransformerBackend)
from horovod_tpu.utils import profiling

BLOCK = T.ROW_BLOCK
BUCKET = 3 * BLOCK      # the shortest that loops (transformer.row_blocks)
THIN = dict(vocab_size=32, embed_dim=32, mlp_dim=32, dtype=jnp.float32,
            param_dtype=jnp.float32, logits_dtype=jnp.float32,
            max_seq_len=2 * BUCKET)
FLASH = jax.jit(make_flash_attention(),
                static_argnames=("causal", "scale", "window"))
SPARSE = dict(num_experts=4, experts_per_token=2, moe_selection="sigmoid",
              num_shared_experts=1, norm_topk_prob=True)
# the four served kinds: dense attention through the flash forward; banded
# and full grouped-query attention in a parallel block over shared and held
# experts; latent attention over held experts behind one dense layer, its
# feed-forward in chunks; EVA attention (the merged form) on a float32 stream
KINDS = {
    "dense_flash": TransformerConfig(
        num_layers=2, num_heads=2, head_dim=16, attention_fn=FLASH, **THIN),
    "banded_shared_held": TransformerConfig(
        num_layers=2, num_heads=4, num_kv_heads=1, head_dim=16,
        layer_types=("sliding_attention", "full_attention"),
        sliding_window=1200, parallel_block=True, experts_held=(1, 3),
        **SPARSE, **THIN),
    "latent_behind_dense": TransformerConfig(
        num_layers=2, num_heads=2, layer_types=("latent_attention",) * 2,
        q_lora_rank=16, kv_lora_rank=8, qk_nope_head_dim=16,
        qk_rope_head_dim=8, v_head_dim=16, first_dense_layers=1,
        experts_held=(0, 2), feed_forward_chunk=BUCKET, **SPARSE, **THIN),
    "eva_merged": TransformerConfig(
        num_layers=2, num_heads=2, head_dim=16,
        layer_types=("eva_attention",) * 2, eva_window=512, eva_chunk=16,
        residual_dtype=jnp.float32, norm_offset=1.0, **THIN),
}
# loops a layer: the mixer's two sides and what follows it; EVA's summaries;
# a sparse feed-forward's router and shared experts in place of the third
WHILES = {"dense_flash": 3 + 3, "banded_shared_held": 4 + 4,
          "latent_behind_dense": 3 + 4, "eva_merged": 4 + 4}
LENGTHS = (1, BLOCK - 1, BLOCK, BLOCK + 1, BUCKET - 1, BUCKET)
NAN_ID = THIN["vocab_size"] - 1     # no prompt holds it


@contextlib.contextmanager
def loops_withheld():
    """The same calls with no row loop, no kernel bound: PR 44's pass."""
    merged = T.eva_merged_attention
    with pytest.MonkeyPatch.context() as m:
        m.setattr(T, "row_blocks", lambda s: 0)
        m.setattr(M, "row_blocks", lambda s: 0)
        m.setattr(T, "_prompt_end", lambda cfg, lengths: {})
        m.setattr(T, "eva_merged_attention",
                  lambda *a, length=None, **kw: merged(*a, **kw))
        yield


@pytest.fixture
def merged_eva(monkeypatch):
    # the merged form is the one a long bucket takes; these thin models'
    # logits would fit the dense form's limit
    monkeypatch.setattr(T, "EVA_DENSE_LOGITS_BYTES", -1)


@functools.lru_cache(maxsize=None)
def _params(kind):
    return jax.jit(Transformer(KINDS[kind]).init)(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))


def _prefill(kind, params, tokens, length):
    """(logits of the last prompt position [V], kv) of one padded row."""
    cfg = KINDS[kind]
    length = jnp.asarray(length, jnp.int32)
    told = {"valid": jnp.arange(tokens.shape[1])[None] < length} \
        if cfg.num_experts else {}
    if cfg.feed_forward_chunk:
        told["logits_at"] = jnp.reshape(length - 1, (1,))
    logits, kv = Transformer(cfg).apply(
        params, tokens, return_kv=True, lengths=jnp.reshape(length, (1,)),
        **told)
    return (logits[0] if cfg.feed_forward_chunk
            else logits[0, length - 1]), kv


@functools.lru_cache(maxsize=None)
def _traced(kind):
    return jax.jit(functools.partial(_prefill, kind))


def _tokens(n, bucket=BUCKET, pad=0):
    tokens = np.full((1, bucket), pad, np.int32)
    tokens[0, :n] = np.random.RandomState(n).randint(1, NAN_ID, n)
    return tokens


@functools.lru_cache(maxsize=None)
def _held(kind, n):
    """What the prompt of ``n`` tokens gives with the loops withheld."""
    with loops_withheld():
        return jax.jit(functools.partial(_prefill, kind))(
            _params(kind), _tokens(n), n)


def _pool_rows(cfg, n, s=BUCKET):
    """(the rows of a layer's cache block a prompt of ``n`` filled, the
    rows that belong to row blocks / windows it never reached)."""
    if cfg.eva:
        w, c = cfg.eva_window, cfg.eva_chunk
        reached = -(-n // w) * w
        return np.r_[0:n % w, w:w + n // c], np.r_[w + reached // c:w + s // c]
    return np.r_[0:n], np.r_[-(-n // BLOCK) * BLOCK:s]


# -- the helper ----------------------------------------------------------


@pytest.mark.parametrize("traced", [False, True], ids=["static", "traced"])
@pytest.mark.parametrize("rows", [0, 1, 7, 8, 9, 31, 32])
def test_over_rows_is_the_whole_call_below_the_count_and_zero_past_its_block(
        rows, traced):
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 32, 5))
    w = jax.random.normal(jax.random.PRNGKey(2), (5, 3))
    # past the block the count ends in, the input may hold anything
    reached = -(-rows // 8) * 8
    x = x.at[:, reached:].set(jnp.nan)
    fn = lambda x, p: {"y": jnp.tanh(x @ w) + p[..., None],  # noqa: E731
                       "sum": (x.sum(-1), x[..., :1])}
    p = jnp.arange(64.0).reshape(2, 32)
    run = lambda n: T._over_rows(fn, n, x, p, block=8)  # noqa: E731
    got = jax.jit(run)(rows) if traced else run(rows)
    want = fn(x, p)
    for g, w_ in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert g.shape == w_.shape and g.dtype == w_.dtype
        np.testing.assert_array_equal(g[:, :reached], w_[:, :reached])
        assert not np.asarray(g[:, reached:]).any()


def test_over_rows_without_a_count_is_the_call_as_written():
    x = jnp.arange(24.0).reshape(1, 8, 3)
    assert "while" not in str(jax.make_jaxpr(
        lambda x: T._over_rows(jnp.sin, None, x))(x))
    np.testing.assert_array_equal(T._over_rows(jnp.sin, None, x), jnp.sin(x))


@pytest.mark.parametrize("rows", [0, 5, 16, 17, 48])
def test_over_rows_takes_a_function_that_gives_a_row_a_chunk(rows):
    """EVA's summaries: a window of rows in, a row a chunk out."""
    x = jax.random.normal(jax.random.PRNGKey(3), (1, 48, 4))
    fn = lambda x: x.reshape(1, -1, 4, 4).mean(2)  # noqa: E731
    got = jax.jit(lambda n: T._over_rows(fn, n, x, block=16))(rows)
    reached = -(-rows // 16) * 4
    assert got.shape == (1, 12, 4)
    np.testing.assert_array_equal(got[:, :reached], fn(x)[:, :reached])
    assert not np.asarray(got[:, reached:]).any()


@pytest.mark.parametrize("blocks", [2, 4, 16])
def test_over_rows_is_one_body_however_many_blocks(blocks):
    x = jnp.zeros((1, 8 * blocks, 3))
    text = str(jax.make_jaxpr(
        lambda x, n: T._over_rows(jnp.sin, n, x, block=8))(x, 3))
    assert text.count("while[") == 1 and text.count(" sin ") == 1


# -- the four served kinds, a prompt at a time ---------------------------


@pytest.mark.parametrize("traced", [False, True], ids=["static", "traced"])
@pytest.mark.parametrize("n", LENGTHS)
@pytest.mark.parametrize("kind", list(KINDS))
def test_the_prompts_rows_are_what_the_whole_bucket_gave(kind, n, traced,
                                                         merged_eva):
    """Last-position logits, first token and the cache block's rows below the
    length, to the bit, against the loops withheld; the cache rows of the
    blocks never visited are exactly 0.  ``static``: the length a constant
    of the program (XLA folds the trip count); ``traced``: an argument."""
    cfg, params = KINDS[kind], _params(kind)
    if traced:
        logits, kv = _traced(kind)(params, _tokens(n), n)
    else:
        logits, kv = jax.jit(lambda params, tokens: _prefill(
            kind, params, tokens, np.int32(n)))(params, _tokens(n))
    want_logits, want_kv = _held(kind, n)
    np.testing.assert_array_equal(logits, want_logits)
    assert int(jnp.argmax(logits)) == int(jnp.argmax(want_logits))
    filled, unreached = _pool_rows(cfg, n)
    for got, want in zip(kv, want_kv):
        assert np.isfinite(got).all()
        np.testing.assert_array_equal(got[:, 0, filled], want[:, 0, filled])
        assert not np.asarray(got[:, 0, unreached]).any()


@pytest.mark.parametrize("kind", list(KINDS))
def test_what_the_padding_holds_past_the_prompts_block_reaches_nothing(
        kind, merged_eva):
    """NaN and inf in the embedding of the tokens that pad the blocks the
    prompt never reaches: the logits and the cache block are the clean
    call's, and finite."""
    cfg, n = KINDS[kind], BLOCK - 3
    poisoned = jax.tree.map(lambda x: x, _params(kind))
    table = poisoned["params"]["embed"]["embedding"]
    poisoned["params"]["embed"]["embedding"] = table.at[NAN_ID].set(
        jnp.where(jnp.arange(table.shape[1]) % 2, jnp.nan, jnp.inf))
    tokens = _tokens(n)
    tokens[0, BLOCK:] = NAN_ID
    logits, kv = _traced(kind)(poisoned, tokens, n)
    clean_logits, clean_kv = _traced(kind)(_params(kind), _tokens(n), n)
    np.testing.assert_array_equal(logits, clean_logits)
    for got, want in zip(kv, clean_kv):
        assert np.isfinite(got).all()
        np.testing.assert_array_equal(got, want)
    # with the loops withheld the same padding is in the cache
    with loops_withheld():
        _, held_kv = jax.jit(functools.partial(_prefill, kind))(
            poisoned, tokens, n)
    assert not all(np.isfinite(x).all() for x in held_kv)


def test_rows_of_two_prompts_stop_at_the_longer_ones_block():
    cfg = dataclasses.replace(KINDS["dense_flash"], attention_fn=None)
    model, params = Transformer(cfg), _params("dense_flash")
    tokens = np.concatenate([_tokens(700), _tokens(1500)])
    lengths = jnp.array([700, 1500])
    logits, kv = jax.jit(lambda p, t: model.apply(
        p, t, return_kv=True, lengths=lengths))(params, tokens)
    want, want_kv = jax.jit(lambda p, t: model.apply(
        p, t, return_kv=True))(params, tokens)
    for b, n in enumerate((700, 1500)):
        np.testing.assert_array_equal(logits[b, :n], want[b, :n])
        for got, held in zip(kv, want_kv):
            np.testing.assert_array_equal(got[:, b, :n], held[:, b, :n])


# -- where the loop is, and where it is not ------------------------------


def _whiles(fn, *args) -> int:
    """The ``while`` loops of ``fn``'s jaxpr, every nested jaxpr's too but a
    kernel's own (the flash forward sweeps its sub-tiles in one)."""
    def count(jaxpr) -> int:
        total = 0
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                continue
            total += eqn.primitive.name == "while"
            for sub in jax.core.jaxprs_in_params(eqn.params):
                total += count(sub)
        return total

    return count(jax.make_jaxpr(fn)(*args).jaxpr)


@pytest.mark.parametrize("bucket", [BUCKET, 2 * BUCKET])
@pytest.mark.parametrize("kind", list(KINDS))
def test_a_long_bucket_holds_one_loop_a_call_site(kind, bucket, merged_eva):
    """A constant a layer, whatever the bucket's count of blocks (the
    latent kind's feed-forward chunk is this file's BUCKET: its sparse
    layer's two loops are once a chunk)."""
    want = WHILES[kind]
    if kind == "latent_behind_dense" and bucket > BUCKET:
        want += 2
    assert _whiles(_traced(kind), _params(kind),
                   jnp.zeros((1, bucket), jnp.int32), 5) == want


@pytest.mark.parametrize("blocks", [1, 2])
@pytest.mark.parametrize("kind", ["dense_flash", "latent_behind_dense",
                                  "eva_merged"])
def test_a_bucket_of_one_or_two_blocks_has_no_loop(kind, blocks):
    assert _whiles(_traced(kind), _params(kind),
                   jnp.zeros((1, blocks * BLOCK), jnp.int32), 5) == 0


@pytest.mark.parametrize("kind", ["dense_flash", "latent_behind_dense",
                                  "eva_merged"])
def test_a_pass_that_names_no_length_has_no_loop(kind):
    model, tokens = Transformer(KINDS[kind]), jnp.zeros((1, BUCKET), jnp.int32)
    assert _whiles(lambda p: model.apply(p, tokens), _params(kind)) == 0
    assert _whiles(lambda p: model.apply(p, tokens, return_kv=True),
                   _params(kind)) == 0


def test_a_sparse_pass_with_no_valid_positions_named_has_no_loop():
    model = Transformer(KINDS["banded_shared_held"])
    tokens = jnp.zeros((1, BUCKET), jnp.int32)
    assert _whiles(lambda p: model.apply(p, tokens),
                   _params("banded_shared_held")) == 0


@pytest.mark.parametrize("block", [1, 4, BUCKET], ids=[
    "decode", "verify", "suffix_prefill"])
@pytest.mark.parametrize("kind", ["dense_flash", "latent_behind_dense"])
def test_a_cache_call_has_no_loop(kind, block):
    cfg = KINDS[kind]
    model = Transformer(cfg)
    pool = T.init_kv_cache(cfg, 2, 2 * BUCKET)
    tokens = jnp.zeros((2, block), jnp.int32)
    assert _whiles(lambda p, pool: model.apply(
        p, tokens, kv_cache=pool, lengths=jnp.array([3, 9])),
        _params(kind), pool) == 0


def test_a_model_with_its_own_attention_function_is_told_no_length():
    cfg = KINDS["dense_flash"]
    backend = TransformerBackend(Transformer(cfg), _params("dense_flash"),
                                 cfg, 1, BUCKET)
    assert backend.prefill_attention(BUCKET) == "own"
    assert backend.prefill_rows(BUCKET, 5) is None
    assert _whiles(backend._prefill_fn, backend.params, backend.kk,
                   backend.vv, jnp.zeros((1, BUCKET), jnp.int32), 5, 0) == 0


# -- through the backend: the pool, and the steps after ------------------

STEPS = 20


def _serve(cfg, params, bucket, prompt):
    backend = TransformerBackend(Transformer(cfg), params, cfg, 2,
                                 cfg.max_seq_len)
    padded = np.zeros((1, bucket), np.int32)
    padded[0, :len(prompt)] = prompt
    first, logits = backend.prefill(padded, len(prompt), 1)
    pool = (np.asarray(backend.kk), np.asarray(backend.vv))
    tokens, steps = [first], []
    lengths = np.array([0, len(prompt)], np.int32)
    for _ in range(STEPS):
        lengths[1] += 1
        nxt, step_logits = backend.decode(
            np.array([0, tokens[-1]], np.int32), lengths)
        tokens.append(int(nxt[1]))
        steps.append(np.asarray(step_logits[1]))
    return first, np.asarray(logits), pool, tokens, np.stack(steps), backend


@pytest.mark.parametrize("kind", list(KINDS))
def test_a_served_prompt_and_twenty_steps_after_it_are_what_they_were(
        kind, monkeypatch, merged_eva):
    """Through ``TransformerBackend``: a prompt that ends in the second of
    six row blocks, the pool's rows below it and twenty decode steps, to
    the bit against the loops and the kernels' bounds withheld; the pool's
    rows of the blocks never visited are exactly 0."""
    monkeypatch.setattr(TransformerBackend, "FLASH_PREFILL_LOGITS_BYTES", -1)
    cfg = dataclasses.replace(KINDS[kind], attention_fn=None)
    params, bucket = _params(kind), 2 * BUCKET
    n = BLOCK + 77
    prompt = [int(t) for t in np.random.RandomState(5).randint(1, NAN_ID, n)]
    told = _serve(cfg, params, bucket, prompt)
    backend = told[-1]
    assert backend.prefill_rows(bucket, n) == 2 * BLOCK
    assert backend.prefill_chunks(bucket) == (
        2 if kind == "latent_behind_dense" else 1)
    with loops_withheld():
        held = _serve(cfg, params, bucket, prompt)
    assert told[0] == held[0]
    np.testing.assert_array_equal(told[1], held[1])
    filled, unreached = _pool_rows(cfg, n, bucket)
    for got, want in zip(told[2], held[2]):
        assert np.isfinite(got).all()
        np.testing.assert_array_equal(got[:, 1, filled], want[:, 1, filled])
        assert not got[:, 1, unreached].any()
    assert told[3] == held[3]
    assert np.isfinite(told[4]).all()
    np.testing.assert_array_equal(told[4], held[4])


@pytest.mark.parametrize("bucket,length,rows", [
    (512, 300, None), (1024, 1024, None), (2048, 1500, None),
    (3072, 1, 1024), (4096, 1024, 1024), (4096, 1025, 2048),
    (4096, 2500, 3072), (4096, 4096, 4096), (32768, 23554, 24576),
    (2560, 1200, None)])
def test_prefill_rows_counts_whole_blocks_up_to_the_prompts(bucket, length,
                                                            rows):
    backend = TransformerBackend.__new__(TransformerBackend)
    backend._model_cfg, backend.eva = dataclasses.replace(
        KINDS["dense_flash"], attention_fn=None), False
    assert backend.prefill_rows(bucket, length) == rows


def test_the_prefill_span_carries_the_rows_the_layers_worked():
    cfg = dataclasses.replace(KINDS["dense_flash"], attention_fn=None,
                              num_layers=1)
    engine = ServingEngine(
        TransformerBackend(Transformer(cfg), _params("dense_flash"), cfg, 1,
                           BUCKET),
        ServingConfig(num_slots=1, buckets=(BLOCK, BUCKET),
                      max_seq_len=BUCKET))
    mark = profiling.open_span("mark").id
    for n in (900, 1000, 1100, 3000):
        engine.submit([1 + i % 30 for i in range(n)], 1)
        engine.run_until_idle()
    calls = [r.fields for r in profiling.spans()
             if r.id > mark and r.name == profiling.SRV_PREFILL]
    assert [c.get("rows_worked") for c in calls] == [None, None, 2048, 3072]
    rows = ServingEngine.span_summary()[profiling.SRV_PREFILL]["rows"]
    assert rows["calls"] >= 2 and rows["rows_worked"] <= rows["bucket_rows"]


def test_the_summary_sums_rows_worked_beside_the_buckets_rows():
    mark = profiling.open_span("mark").id
    for bucket, rows in ((4096, 3072), (8192, 5120)):
        with profiling.span(profiling.SRV_PREFILL, bucket=bucket,
                            length=rows - 5, attn="flash", rows_worked=rows):
            pass
    with profiling.span(profiling.SRV_PREFILL, bucket=1024, length=9,
                        attn="dense"):
        pass
    ours = [r for r in profiling.spans() if r.id > mark]
    with pytest.MonkeyPatch.context() as m:
        m.setattr(profiling, "spans", lambda: ours)
        rows = ServingEngine.span_summary()[profiling.SRV_PREFILL]["rows"]
    assert rows == {"calls": 2, "bucket_rows": 12288, "rows_worked": 8192}


# -- every other program is the parent's, to the text ---------------------
# (the block between the two marks runs alone in a checkout of the parent
# commit, with this file's imports, to refresh PARENTS)
# --8<-- programs
import hashlib  # noqa: E402
import re  # noqa: E402

import optax  # noqa: E402

SMALL = dict(vocab_size=32, embed_dim=32, mlp_dim=64, dtype=jnp.bfloat16,
             param_dtype=jnp.float32, max_seq_len=64)
PROGRAM_KINDS = {
    "dense": TransformerConfig(num_layers=2, num_heads=2, head_dim=16,
                               **SMALL),
    "qk_norm_every_expert": TransformerConfig(
        num_layers=2, num_heads=2, head_dim=16, qk_norm=True, num_experts=4,
        experts_per_token=2, **SMALL),
    "banded_parallel_held": TransformerConfig(
        num_layers=2, num_heads=4, num_kv_heads=1, head_dim=16,
        layer_types=("sliding_attention", "full_attention"),
        sliding_window=12, parallel_block=True, norm="layer", num_experts=4,
        experts_per_token=2, moe_selection="sigmoid", num_shared_experts=2,
        norm_topk_prob=True, experts_held=(1, 3), **SMALL),
    "latent_behind_dense": TransformerConfig(
        num_layers=2, num_heads=2, layer_types=("latent_attention",) * 2,
        q_lora_rank=16, kv_lora_rank=8, qk_nope_head_dim=16,
        qk_rope_head_dim=8, v_head_dim=16, first_dense_layers=1,
        num_experts=4, experts_per_token=2, moe_selection="sigmoid",
        num_shared_experts=1, experts_held=(0, 2), moe_routed_scale=2.5,
        feed_forward_chunk=16, **SMALL),
    "eva": TransformerConfig(
        num_layers=2, num_heads=2, head_dim=16,
        layer_types=("eva_attention",) * 2, eva_window=16, eva_chunk=4,
        residual_dtype=jnp.float32, norm_offset=1.0, num_pred_heads=2,
        feed_forward_chunk=16, **SMALL),
}


def _program_text(kind: str, program: str) -> str:
    """The jaxpr of one program of a small model, source positions out."""
    cfg = PROGRAM_KINDS[kind]
    model = Transformer(cfg)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                            jnp.zeros((1, 8), jnp.int32))
    tokens = jnp.zeros((2, 32), jnp.int32)
    if program == "train":
        from horovod_tpu.models.moe import MOE_LOSSES
        every_expert = cfg.num_experts and cfg.experts_held is None

        def loss(params, tokens):
            if every_expert:
                logits, sown = model.apply(params, tokens,
                                           mutable=[MOE_LOSSES])
                extra = sum(jnp.sum(x) for x in jax.tree.leaves(sown))
            else:
                logits, extra = model.apply(params, tokens), 0.0
            return optax.softmax_cross_entropy_with_integer_labels(
                logits[..., :cfg.vocab_size].astype(jnp.float32),
                tokens).mean() + extra

        # a share of the experts has no backward: its forward is the program
        fn = loss if cfg.experts_held else jax.value_and_grad(loss)
        args = (params, tokens)
    else:
        backend = TransformerBackend.__new__(TransformerBackend)
        backend._jax, backend.model, backend._model_cfg = jax, model, cfg
        backend.sparse, backend.eva = cfg.num_experts > 0, cfg.eva
        backend._flash_model = None
        backend._sparse_layers = range(cfg.first_dense_layers,
                                       cfg.num_layers)
        pool = jax.eval_shape(lambda: T.init_kv_cache(cfg, 2, 64))
        if program == "decode":
            fn, args = backend._decode_fn, (
                params, *pool, jnp.zeros((2,), jnp.int32),
                jnp.ones((2,), jnp.int32))
        elif program == "prefill_one_block":
            fn, args = backend._prefill_fn, (
                params, *pool, tokens[:1], jnp.int32(20), jnp.int32(1))
        else:   # a pass that hands back its cache block and names no length
            fn, args = (lambda p, t: model.apply(p, t, return_kv=True)), (
                params, tokens)
    return re.sub(r" at [^\s]+\.py:\d+", "", str(jax.make_jaxpr(fn)(*args)))


PROGRAMS = ("train", "decode", "prefill_one_block", "return_kv")
# --8<-- programs
# sha256 of the texts at commit 7d461c2 (PR 45's tree, this PR's parent)
PARENTS = {
    "dense.train": "725642f5ced94d83",
    "dense.decode": "35fae70854e65599",
    "dense.prefill_one_block": "bf15e46b96b7e693",
    "dense.return_kv": "5e4fd9e7aeff5300",
    "qk_norm_every_expert.train": "e1cc591082bcc8f8",
    "qk_norm_every_expert.decode": "322dfc8965ca32a9",
    "qk_norm_every_expert.prefill_one_block": "70ddbff0552af965",
    "qk_norm_every_expert.return_kv": "5dc4aa33c2923301",
    "banded_parallel_held.train": "e02310e245baf02e",
    "banded_parallel_held.decode": "44e0b16408316d7b",
    "banded_parallel_held.prefill_one_block": "0eddc9cd8914df3b",
    "banded_parallel_held.return_kv": "59a31c09607c519a",
    "latent_behind_dense.train": "7394ad07eb2df9e9",
    "latent_behind_dense.decode": "fbefb90e3b7bb9a0",
    "latent_behind_dense.prefill_one_block": "336719468cbe65ef",
    "latent_behind_dense.return_kv": "18d948cb7f92d1ad",
    "eva.train": "66fa01546820aa4b",
    "eva.decode": "04c7d68cb05a7b64",
    "eva.prefill_one_block": "99fc5af7ba42b289",
    "eva.return_kv": "a34ebdb9ff272602",
}


@pytest.mark.parametrize("program", PROGRAMS)
@pytest.mark.parametrize("kind", list(PROGRAM_KINDS))
def test_a_program_without_the_loop_is_the_parents_to_the_text(kind, program):
    text = _program_text(kind, program)
    assert "while" not in text or PROGRAM_KINDS[kind].eva \
        or PROGRAM_KINDS[kind].experts_held
    assert hashlib.sha256(text.encode()).hexdigest()[:16] \
        == PARENTS[f"{kind}.{program}"]
