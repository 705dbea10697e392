"""A block model's K/V pool held as rows (PR 62): ``init_kv_cache`` gives
``[L, slots, S, KV D]`` where ``attention_block`` is set and every other
model the shape it had, ``Attention``'s cache branch picks its products by
the rank of the pool it is handed, ``rows_decode_attention`` is
``cached_decode_attention`` to float32 rounding under every mask, a served
block model hands over from a rows pool what the model gives from a
``[.., KV, D]`` pool handed to it directly, and ``pool_form`` says which on
``hvd_setup_pool`` and in ``span_summary()``."""

import dataclasses
import importlib
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.models import transformer as T
from horovod_tpu.models.transformer import (Transformer, TransformerConfig,
                                            cached_decode_attention,
                                            rows_decode_attention)
from horovod_tpu.serving.engine import (PagedTransformerBackend,
                                        ServingEngine, TransformerBackend)
from horovod_tpu.utils import profiling

F32 = jnp.float32
MASK = 95
BLOCK_MODEL = TransformerConfig(
    vocab_size=96, num_layers=2, num_heads=4, num_kv_heads=2, head_dim=16,
    embed_dim=32, mlp_dim=64, max_seq_len=3200, qk_norm="head",
    attention_block=4, mask_token_id=MASK, dtype=F32, param_dtype=F32)


# -- the row form against the grouped form ---------------------------------

MASKS = {"block": {"block": 4}, "window": {"window": 7}, "neither": {}}


@pytest.mark.parametrize("mask", list(MASKS))
@pytest.mark.parametrize("s_q", [1, 4])
@pytest.mark.parametrize("heads,kv_heads", [(4, 2), (8, 2), (32, 4)])
def test_the_row_form_is_the_grouped_form(heads, kv_heads, s_q, mask):
    """Ragged lengths (a slot at 0, one mid-block, one at the pool's end):
    the same numbers to float32 rounding, under the block's mask, a window's
    and the causal one, for one position a slot and for a block of four."""
    b, s, d = 3, 40, 16
    keys = jax.random.split(jax.random.PRNGKey(heads + s_q), 3)
    q = jax.random.normal(keys[0], (b, s_q, heads, d))
    k = jax.random.normal(keys[1], (b, s, kv_heads, d))
    v = jax.random.normal(keys[2], (b, s, kv_heads, d))
    lengths = jnp.array([0, 13, s - s_q])
    told = dict(MASKS[mask], scale=0.3)
    want = jax.jit(lambda *a: cached_decode_attention(*a, **told))(
        q, k, v, lengths)
    got = jax.jit(lambda *a: rows_decode_attention(*a, **told))(
        q, k.reshape(b, s, -1), v.reshape(b, s, -1), lengths)
    assert got.shape == want.shape == q.shape
    assert float(jnp.abs(got - want).max()) < 1e-5
    # and the mask is in it: the causal form is far outside the rounding
    if mask != "neither" and (s_q > 1 or mask == "window"):
        causal = cached_decode_attention(q, k, v, lengths, scale=0.3)
        assert float(jnp.abs(causal - want).max()) > 1e-3


def test_a_block_mask_with_a_window_is_refused_by_both_forms():
    q, rows = jnp.zeros((1, 4, 2, 8)), jnp.zeros((1, 16, 16))
    for attend, pool in ((rows_decode_attention, rows),
                         (cached_decode_attention,
                          rows.reshape(1, 16, 2, 8))):
        with pytest.raises(ValueError, match="block-causal mask has no "
                                             "window"):
            attend(q, pool, pool, jnp.zeros((1,), jnp.int32), window=4,
                   block=4)


# -- the pool's shape by model ----------------------------------------------

# (file, its tiny served configuration's pool at 3 slots of 64): the shapes
# the PARENT's init_kv_cache gave, read in a checkout of it
PARENT_POOLS = {
    "test_bench_axk1": ((3, 3, 64, 8), (3, 3, 64, 4)),
    "test_bench_cohere2": ((4, 3, 64, 1, 8), (4, 3, 64, 1, 8)),
    "test_bench_evabyte": ((3, 3, 48, 2, 16), (3, 3, 48, 2, 16)),
    "test_bench_ling": ({"kda": (3, 3, 4, 8, 8), "latent": (1, 3, 64, 8)},
                        {"kda": (3, 3, 3, 96), "latent": (1, 3, 64, 4)}),
    "test_bench_xing": ((2, 3, 64, 8), (2, 3, 64, 4)),
    "test_bench_zaya": ({"cca": (3, 3, 64, 16), "cca_tail": (3, 3, 2, 48)},
                        {"cca": (3, 3, 64, 16), "cca_tail": (3, 3, 1, 8)}),
    "rows.dense": ((2, 3, 64, 2, 16), (2, 3, 64, 2, 16)),
    "rows.qk_norm_every_expert": ((2, 3, 64, 2, 16), (2, 3, 64, 2, 16)),
    "rows.banded_parallel_held": ((2, 3, 64, 1, 16), (2, 3, 64, 1, 16)),
    "rows.latent_behind_dense": ((2, 3, 64, 8), (2, 3, 64, 8)),
    "rows.eva": ((2, 3, 32, 2, 16), (2, 3, 32, 2, 16)),
}
POOL_FORMS = {"test_bench_axk1": "latents", "test_bench_xing": "latents",
              "test_bench_ling": "latents", "test_bench_zaya": "rows",
              "rows.latent_behind_dense": "latents"}


def _config_of(name: str) -> TransformerConfig:
    if name.startswith("rows."):
        return importlib.import_module("test_prefill_rows").PROGRAM_KINDS[
            name[len("rows."):]]
    from benchmarks import run as harness

    file = importlib.import_module(name)
    family = harness.load_module("families", file.TINY["family"])
    return family.model_config(dict(file.TINY), file.TRAFFIC)


def _shapes(cfg, slots=3, max_len=64):
    return jax.tree.map(lambda x: x.shape, jax.eval_shape(
        lambda: T.init_kv_cache(cfg, slots, max_len)))


@pytest.mark.parametrize("name", list(PARENT_POOLS))
def test_every_other_models_pool_keeps_its_shape(name):
    cfg = _config_of(name)
    assert not cfg.attention_block
    assert _shapes(cfg) == PARENT_POOLS[name]
    assert T.kv_pool_form(cfg) == POOL_FORMS.get(name, "heads")


@pytest.mark.parametrize("name", ["test_block_diffusion", "plain"])
def test_a_block_models_pool_is_rows(name):
    """The same bytes a position, one axis fewer; without ``attention_block``
    the same configuration gets the parent's ``[.., KV, D]``."""
    cfg = BLOCK_MODEL if name == "plain" else _config_of(name)
    assert cfg.attention_block == 4
    rows = (cfg.num_layers, 3, 64, cfg.kv_heads * cfg.head_dim)
    assert _shapes(cfg) == (rows, rows)
    assert T.kv_pool_form(cfg) == "rows"
    causal = dataclasses.replace(cfg, attention_block=None)
    heads = rows[:3] + (cfg.kv_heads, cfg.head_dim)
    assert _shapes(causal) == (heads, heads)
    assert T.kv_pool_form(causal) == "heads"
    pool = T.init_kv_cache(cfg, 3, 64)
    assert all(p.dtype == cfg.dtype and not p.any() for p in pool)


# -- a block model served from rows -----------------------------------------

@pytest.fixture(scope="module")
def block_model():
    model = Transformer(BLOCK_MODEL)
    params = jax.jit(model.init)(jax.random.PRNGKey(1),
                                 jnp.zeros((1, 8), jnp.int32))
    return model, params


def _from_a_pool_of_heads(model, params, prompts, bucket, slots, max_len):
    """What the MODEL gives when it is handed ``[L, slots, S, KV, D]`` arrays
    directly (the grouped products): each prompt's whole blocks from a pass
    without a cache, then one pass over every slot's block."""
    cfg = model.cfg
    shape = (cfg.num_layers, slots, max_len, cfg.kv_heads, cfg.head_dim)
    kk, vv = jnp.zeros(shape, cfg.dtype), jnp.zeros(shape, cfg.dtype)
    prefill = jax.jit(lambda p, t: model.apply(p, t, return_kv=True))
    for slot, ids in prompts.items():
        padded = np.zeros((1, bucket), np.int32)
        padded[0, :len(ids)] = ids
        _, (k, v) = prefill(params, padded)
        assert k.shape == (cfg.num_layers, 1, bucket, cfg.kv_heads,
                           cfg.head_dim)
        kk = kk.at[:, slot, :bucket].set(k[:, 0])
        vv = vv.at[:, slot, :bucket].set(v[:, 0])
    block, lengths, _ = _first_blocks(prompts, slots, cfg.attention_block)
    logits, (kk, vv) = jax.jit(
        lambda p, t, kk, vv, n: model.apply(p, t, kv_cache=(kk, vv),
                                            lengths=n))(
        params, block, kk, vv, lengths)
    return logits, kk, vv


def _first_blocks(prompts, slots, b):
    """Every slot's first block after its prompt's whole blocks: the
    prompt's tail, the mask id behind it."""
    block = np.full((slots, b), MASK, np.int32)
    lengths = np.zeros((slots,), np.int32)
    live = np.zeros((slots,), bool)
    for slot, ids in prompts.items():
        whole = len(ids) // b * b
        block[slot, :len(ids) - whole] = ids[whole:]
        lengths[slot], live[slot] = whole, True
    return block, lengths, live


@pytest.mark.parametrize("bucket,lens", [(16, (8, 11)), (16, (9, 16)),
                                         (3072, (2500, 2049))])
def test_a_served_block_model_hands_over_what_a_pool_of_heads_gives(
        block_model, monkeypatch, bucket, lens):
    """Two prompts into slots 0 and 2 of three (ragged, one slot empty)
    through ``TransformerBackend``'s prefill and one pass: tokens,
    confidences and logits are the model's from a 5-D pool, and the rows the
    calls wrote are that pool's, reshaped.  The bucket of three row blocks
    writes its blocks into the pool layer by layer (``kv_into``), the short
    one whole (``dynamic_update_slice``)."""
    # (dense logits either way: the comparison is of the pool, not of flash)
    monkeypatch.setattr(TransformerBackend, "FLASH_PREFILL_LOGITS_BYTES",
                        2 ** 62)
    model, params = block_model
    cfg, slots, max_len = model.cfg, 3, bucket + 8
    rng = np.random.default_rng(bucket)
    prompts = {slot: rng.integers(0, MASK, n).astype(np.int32)
               for slot, n in zip((0, 2), lens)}
    backend = TransformerBackend(model, params, cfg, slots, max_len)
    assert backend.kk.shape == (cfg.num_layers, slots, max_len,
                                cfg.kv_heads * cfg.head_dim)
    assert bool(backend.prefill_rows(bucket, 1)) == (bucket == 3072)
    for slot, ids in prompts.items():
        padded = np.zeros((1, bucket), np.int32)
        padded[0, :len(ids)] = ids
        backend.prefill(padded, len(ids), slot)
    block, lengths, live = _first_blocks(prompts, slots, cfg.attention_block)
    tokens, logits, conf = backend.decode(block, lengths, live)
    want, kk, vv = _from_a_pool_of_heads(model, params, prompts, bucket,
                                         slots, max_len)
    got = np.asarray(logits)
    spread = float(jnp.std(want[live]))
    assert np.abs(got - np.asarray(want))[live].max() < 1e-4 * spread
    kept = np.where(np.arange(cfg.vocab_size) == MASK, -np.inf,
                    np.asarray(want))
    assert (np.asarray(tokens)[live] == kept.argmax(-1)[live]).all()
    want_conf = np.exp(kept.max(-1) - jax.nn.logsumexp(want, axis=-1))
    assert np.abs(np.asarray(conf) - want_conf)[live].max() < 1e-6
    for rows, heads in ((backend.kk, kk), (backend.vv, vv)):
        rows = np.asarray(rows).reshape(heads.shape)
        for slot, ids in prompts.items():
            upto = len(ids) // 4 * 4 + 4    # the whole blocks and the pass's
            assert np.abs(rows[:, slot, :upto]
                          - np.asarray(heads)[:, slot, :upto]).max() < 1e-5
        # nobody's slot: the pass's own block at 0, as the other pool has it
        assert not rows[:, 1, 4:].any()
        assert np.abs(rows[:, 1] - np.asarray(heads)[:, 1]).max() < 1e-5


def _equations(jaxpr, name):
    """Every equation named ``name`` in ``jaxpr`` and the jaxprs inside it."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == name:
            yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _equations(sub, name)


@pytest.mark.parametrize("form", ["rows", "heads"])
def test_the_pass_transposes_nothing_of_a_views_size(block_model, form):
    """The pass's jaxpr over a rows pool: no ``transpose`` of anything as
    large as a layer's view ``[slots, S, KV D]``, and its four large
    products are ``dot_general``s that take the view itself.  (What XLA:TPU
    makes of it is tests/test_tpu_structure_served.py's.)  The same model
    handed a pool of heads goes through the grouped products: the branch is
    the pool's rank, and nothing else."""
    model, params = block_model
    cfg, slots, max_len = model.cfg, 3, 64
    shape = (cfg.num_layers, slots, max_len) + (
        (cfg.kv_heads * cfg.head_dim,) if form == "rows"
        else (cfg.kv_heads, cfg.head_dim))
    pool = jax.ShapeDtypeStruct(shape, cfg.dtype)
    view = int(np.prod(shape[1:]))
    jaxpr = jax.make_jaxpr(
        lambda p, t, kk, vv, n: model.apply(p, t, kv_cache=(kk, vv),
                                            lengths=n))(
        params, jnp.zeros((slots, 4), jnp.int32), pool, pool,
        jnp.zeros((slots,), jnp.int32)).jaxpr
    assert not [e for e in _equations(jaxpr, "transpose")
                if any(v.aval.size >= view for v in e.invars)]
    over_views = [e for e in _equations(jaxpr, "dot_general")
                  if e.invars[0].aval.shape == shape[1:]]
    assert len(over_views) == 2 * cfg.num_layers
    # the view's batch axes: rows, the slot alone; heads, the slot and the
    # KV head
    assert {e.params["dimension_numbers"][1][0] for e in over_views} == {
        (0,) if form == "rows" else (0, 2)}


# -- the counter ------------------------------------------------------------

LATENT = TransformerConfig(
    vocab_size=32, num_layers=2, num_heads=2, embed_dim=32, mlp_dim=64,
    max_seq_len=64, layer_types=("latent_attention",) * 2, q_lora_rank=16,
    kv_lora_rank=8, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16)
PLAIN = TransformerConfig(vocab_size=32, num_layers=2, num_heads=2,
                          head_dim=16, embed_dim=32, mlp_dim=64,
                          max_seq_len=64)


@pytest.mark.parametrize("name,cfg,backend,form", [
    ("block", dataclasses.replace(BLOCK_MODEL, max_seq_len=64),
     TransformerBackend, "rows"),
    ("plain", PLAIN, TransformerBackend, "heads"),
    ("latent", LATENT, TransformerBackend, "latents"),
    ("paged", PLAIN, PagedTransformerBackend, "heads")])
def test_pool_form_is_on_the_pools_span_and_in_the_summary(name, cfg,
                                                           backend, form):
    began = time.perf_counter()
    more = {"page_size": 16} if backend is PagedTransformerBackend else {}
    backend(Transformer(cfg), None, cfg, 2, 64, **more)
    pool = [r for r in profiling.spans()
            if r.name == profiling.SETUP_POOL and r.start >= began]
    assert [r.fields["pool_form"] for r in pool] == [form]
    assert pool[0].fields["bytes"] > 0
    # the newest backend's
    assert ServingEngine.span_summary()[profiling.SETUP_POOL][
        "pool_form"] == form
