"""The serving cells' programs as the v5e's compiler leaves them, described
and not attached (``tests/test_tpu_structure.py`` has the account and the
helpers): a prefill bucket of several row blocks, the decode program beside
its pool, a bucket's products where every expert is held.  Nothing
executes."""

import dataclasses
import math
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from test_tpu_structure import (_arrays, _entry_instructions,  # noqa: F401
                                _kernels_named, one_chip_mesh)


_SERVED_PREFILLS = {}


def _served_prefill(mesh, monkeypatch, cell: str, bucket: int, max_len=None,
                    **cut):
    """(the model's configuration, the backend, the compiled prefill program
    of ``bucket`` positions) of a served cell with ``cut`` replaced in its
    configuration, beside a pool of two slots; nothing runs.  One compile a
    (cell, bucket, cut) a module: a case that reads a program another case
    compiled is handed it."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    key = (cell, bucket, max_len, tuple(sorted(cut.items())))
    if key not in _SERVED_PREFILLS:
        _SERVED_PREFILLS[key] = _compile_served_prefill(
            mesh, monkeypatch, cell, bucket, max_len, cut)
    return _SERVED_PREFILLS[key]


def _compile_served_prefill(mesh, monkeypatch, cell, bucket, max_len, cut):
    import dataclasses
    import os

    from benchmarks import run as harness
    from horovod_tpu.models import transformer as T
    from horovod_tpu.serving.engine import TransformerBackend

    one_chip = NamedSharding(mesh, P())
    on_chip = lambda a: jax.ShapeDtypeStruct(  # noqa: E731
        a.shape, a.dtype, sharding=one_chip)
    manifest = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "BENCHMARK.json")
    _, _, config, traffic = harness.load_cell(manifest, cell)
    family = harness.load_module("families", config["family"])
    cfg = dataclasses.replace(family.model_config(config, traffic), **cut)
    model = T.Transformer(cfg)
    params = jax.tree.map(on_chip, jax.eval_shape(
        model.init, jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))
    slots, max_len = 2, max_len or int(traffic["max_seq_len"])
    pool = jax.tree.map(on_chip, jax.eval_shape(
        lambda: T.init_kv_cache(cfg, slots, max_len)))
    with monkeypatch.context() as m:    # no pool is made: nothing runs
        m.setattr(T, "init_kv_cache", lambda *a, **kw: (None, None))
        backend = TransformerBackend(model, None, cfg, slots, max_len)
    i32 = on_chip(jax.ShapeDtypeStruct((), jnp.int32))
    return cfg, backend, backend._prefill.lower(
        params, *pool, on_chip(jax.ShapeDtypeStruct((1, bucket), jnp.int32)),
        i32, i32).compile()

def test_a_served_kda_prefill_holds_one_kernel_a_layer_under_its_scope(
        one_chip_mesh, monkeypatch):
    """The served cell's prefill of several row blocks, two "kda" layers of
    it and the latent one: one custom call of the kernel's name a layer, inside
    the loop over the prompt's blocks, its ``op_name`` under the layer's
    ``hvd_kda_scan`` (which is how PR 34's rule files its time under that
    scope's metrics)."""
    from horovod_tpu.utils import profiling

    _, _, compiled = _served_prefill(
        one_chip_mesh, monkeypatch, "ling3f-longdoc32k-open", 4096,
        max_len=8192, num_layers=3, first_dense_layers=2, vocab_size=1024,
        layer_types=("kda", "kda", "latent_attention"))
    text = compiled.as_text()
    kernels = _kernels_named(text, profiling.KDA_CHUNK)
    assert len(kernels) == 2
    for layer, name in enumerate(sorted(kernels)):
        assert f"/layer_{layer}/kda/while/body/{profiling.KDA_SCAN}/" in name
        assert profiling.module_of(name) == (
            f"Transformer/layer_N/kda/{profiling.KDA_SCAN}/"
            f"{profiling.KDA_CHUNK}")

@pytest.mark.parametrize("heads,kv_heads,layer_types,window,slots,s,views", [
    (16, 16, None, None, 8, 4352, 0),
    (128, 8, ("sliding_attention",) * 3 + ("full_attention",), 4096, 8,
     8448, 8)])
def test_decode_program_keeps_no_copy_of_the_kv_pool(
        one_chip_mesh, heads, kv_heads, layer_types, window, slots, s, views):
    """``TransformerBackend``'s decode program at the served widths, as the
    chip's compiler leaves it (PR 38): the two donated ``[L, B, S, KV, D]``
    buffers are aliased to outputs and stay in the layout they came in,
    every op whose result is as large as the pool is the in-place update of
    one slot's rows, and the program's temporaries are under a quarter of
    one buffer beside the two layer views a grouped-query model copies (its
    parent sliced every layer out and stacked them again: two buffers of
    temporaries, 74% of a decode step)."""
    from horovod_tpu.models.transformer import (Transformer,
                                                TransformerConfig)
    from horovod_tpu.serving.engine import TransformerBackend

    one_chip = NamedSharding(one_chip_mesh, P())
    cfg = TransformerConfig(
        vocab_size=32256, num_layers=4, num_heads=heads, head_dim=128,
        num_kv_heads=kv_heads, embed_dim=2048, mlp_dim=5504, max_seq_len=s,
        layer_types=layer_types, sliding_window=window,
        dtype=jnp.bfloat16, param_dtype=jnp.bfloat16)
    model = Transformer(cfg)
    on_chip = lambda a: jax.ShapeDtypeStruct(  # noqa: E731
        a.shape, a.dtype, sharding=one_chip)
    params = jax.tree.map(on_chip, jax.eval_shape(
        model.init, jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))
    backend = TransformerBackend.__new__(TransformerBackend)
    backend._jax, backend.model, backend.sparse = jax, model, False
    kv = on_chip(jax.ShapeDtypeStruct((4, slots, s, kv_heads, 128),
                                      jnp.bfloat16))
    i32 = on_chip(jax.ShapeDtypeStruct((slots,), jnp.int32))
    compiled = jax.jit(backend._decode_fn, donate_argnums=(1, 2)).lower(
        params, kv, kv, i32, i32).compile()
    view = math.prod(kv.shape[1:])          # elements; bf16 is 2 bytes
    buffer_bytes, view_bytes = 2 * math.prod(kv.shape), 2 * view
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= 2 * buffer_bytes
    # a copied view of K and one of V are live at a time
    assert mem.temp_size_in_bytes < buffer_bytes / 4 + (
        2 * view_bytes if views else 0)
    large = [i for i in _entry_instructions(compiled.as_text())
             if i["opcode"] not in ("parameter", "tuple", "get-tuple-element")
             and any(n >= view for _, n in _arrays(i["shape"]))]
    whole = [i for i in large
             if any(n > view for _, n in _arrays(i["shape"]))]
    assert whole and all(
        i["opcode"] == "fusion" and "dynamic-update-slice" in i["name"]
        and "{4,3,2,1,0" in i["shape"] for i in whole), [
            (i["name"], i["shape"]) for i in whole]
    assert len(large) - len(whole) <= views, [
        i["name"] for i in large if i not in whole]

@pytest.mark.parametrize("form,copies", [("rows", 0), ("heads", 4)])
def test_a_block_models_pass_reads_its_pool_of_rows_as_it_lies(
        one_chip_mesh, monkeypatch, form, copies):
    """``sdar30b-chat4k-open``'s pass at the cell's widths, slots and
    positions, two layers of it, as the chip's compiler leaves it (PR 62).
    Over the pool ``init_kv_cache`` gives a block model, rows ``[L, slots,
    S, KV D]``, the only entry ops as large as a layer's view are the
    in-place writes of the blocks: the two products take the view where it
    lies.  Handed ``[L, slots, S, KV, D]`` arrays the same model goes through
    the grouped products and XLA copies K's and V's view of every layer out
    head-major first (``slice_bitcast_fusion``, 264 MB each: a third of the
    parent's pass)."""
    import os

    from benchmarks import run as harness
    from horovod_tpu.models import transformer as T
    from horovod_tpu.serving.engine import TransformerBackend

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    one_chip = NamedSharding(one_chip_mesh, P())
    on_chip = lambda a: jax.ShapeDtypeStruct(  # noqa: E731
        a.shape, a.dtype, sharding=one_chip)
    manifest = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "BENCHMARK.json")
    _, _, config, traffic = harness.load_cell(manifest,
                                              "sdar30b-chat4k-open")
    family = harness.load_module("families", config["family"])
    cfg = dataclasses.replace(family.model_config(config, traffic),
                              num_layers=2)
    model = T.Transformer(cfg)
    params = jax.tree.map(on_chip, jax.eval_shape(
        model.init, jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))
    slots, max_len = int(traffic["num_slots"]), int(traffic["max_seq_len"])
    rows = (cfg.num_layers, slots, max_len, cfg.kv_heads * cfg.head_dim)
    assert tuple(p.shape for p in jax.eval_shape(
        lambda: T.init_kv_cache(cfg, slots, max_len))) == (rows, rows)
    shape = rows if form == "rows" else rows[:3] + (cfg.kv_heads,
                                                    cfg.head_dim)
    pool = on_chip(jax.ShapeDtypeStruct(shape, cfg.dtype))
    with monkeypatch.context() as m:    # no pool is made: nothing runs
        m.setattr(T, "init_kv_cache", lambda *a, **kw: (None, None))
        backend = TransformerBackend(model, None, cfg, slots, max_len)
    compiled = backend._decode.lower(
        params, pool, pool,
        on_chip(jax.ShapeDtypeStruct((slots, cfg.attention_block),
                                     jnp.int32)),
        on_chip(jax.ShapeDtypeStruct((slots,), jnp.int32)),
        on_chip(jax.ShapeDtypeStruct((slots,), jnp.bool_))).compile()
    view = math.prod(shape[1:])
    large = [i for i in _entry_instructions(compiled.as_text())
             if i["opcode"] not in ("parameter", "tuple", "get-tuple-element",
                                    "bitcast")
             and any(n >= view for _, n in _arrays(i["shape"]))]
    written = [i for i in large if "dynamic-update-slice" in i["opcode"]
               or "dynamic-update-slice" in i["name"]]
    assert len(written) == 2 * cfg.num_layers * slots
    copied = [i for i in large if i not in written]
    assert len(copied) == copies, [(i["name"], i["shape"]) for i in copied]
    assert all("slice_bitcast_fusion" in i["name"] for i in copied)
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= 2 * 2 * math.prod(shape)
    # a copied view of K and one of V are live at a time
    assert mem.temp_size_in_bytes < 0.2e9 + (2 * 2 * view if copies else 0)


@pytest.mark.parametrize("b,s,ours", [(1, 1024, 2), (1, 8192, 2), (24, 1, 0)],
                         ids=["shortest_bucket", "longest_bucket",
                              "a_decode_step"])
def test_every_expert_held_compiles_a_buckets_products_as_the_kernel(
        one_chip_mesh, monkeypatch, b, s, ours):
    """ZAYA1-8B's expert layer (16 experts of 2048 x 2048, top-1 by an MLP
    router with a state, every one held) given ``valid``, compiled for the
    chip (PR 53): over its shortest and its longest bucket the two
    ``hvd_moe_grouped`` calls under the experts' scope, no ``ragged-dot``
    and no walk; over a decode step's 24 rows XLA's ``ragged-dot`` kernels
    and none of ours."""
    from horovod_tpu.models import moe
    from horovod_tpu.utils import profiling

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    one_chip = NamedSharding(one_chip_mesh, P())
    d, rd = 2048, 256
    m = moe.MoEMLP(embed_dim=d, mlp_dim=d, axis_name=None,
                   dtype=jnp.bfloat16, param_dtype=jnp.bfloat16,
                   num_experts=16, experts_per_token=1, router_dim=rd)
    shaped = lambda *shape, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dtype, sharding=one_chip)
    params = jax.tree.map(
        lambda a: shaped(*a.shape, dtype=a.dtype),
        jax.eval_shape(lambda: m.init(
            jax.random.PRNGKey(0), jnp.zeros((1, 8, d), jnp.bfloat16),
            router_state=jnp.zeros((1, 8, rd)))))
    text = jax.jit(lambda p, x, v, r: m.apply(
        p, x, valid=v, router_state=r)).lower(
            params, shaped(b, s, d), shaped(b, s, dtype=jnp.bool_),
            shaped(b, s, rd, dtype=jnp.float32)).compile().as_text()
    kernels = _kernels_named(text, profiling.MOE_GROUPED)
    assert len(kernels) == ours
    assert all(f"/{profiling.MOE_EXPERTS}/" in name for name in kernels)
    assert ("ragged-dot" in text) == (not ours)
    # (nothing is walked: the only loop is the router's over the row blocks
    # of the longest bucket, and no sum by token follows the products)
    assert profiling.TOKEN_SUM not in text

# The four serving cells cut to two layers at their own widths (for A.X-K1
# the dense layer and one sparse one; for command-a-plus a sliding and a full
# layer), two slots: (cell, what the cut replaces, {bucket: (the ``while``
# ops the compiled prefill holds, the same program's peak at PR 45's tree
# [commit 7d461c2], bytes)}), at the 4096 bucket and at the cell's longest.
# The loops a layer: the mixer's two sides and the dense feed-forward with
# its residual adds, 3; a sparse feed-forward has its router's and its
# shared experts' in the third's place, once a chunk, beside the walk of its
# held pairs; EVA attention has its summaries' too, beside its merged form's
# own map over the windows.
PREFILL_CELLS = [
    ("dsc1p3b-code-0.8knee", {}, {4096: (2 * 3, 873636352)}),
    ("cmdaplus-code8k-open",
     {"layer_types": ("sliding_attention", "full_attention")},
     {4096: (2 * 5, 5666695168), 8192: (2 * 5, 6327314432)}),
    ("axk1-longdoc16k-open", {"layer_types": ("latent_attention",) * 2},
     {4096: (3 + 2 + 3, 3726478848), 16384: (3 + 2 + 4 * 3, 5359136768)}),
    ("evabyte-code32k-open", {"layer_types": ("eva_attention",) * 2},
     {4096: (2 * 5, 1338628608), 32768: (2 * 5, 3517683712)}),
]


@pytest.mark.parametrize("cell,cut,bucket", [
    (cell, cut, bucket) for cell, cut, buckets in PREFILL_CELLS
    for bucket in buckets], ids=lambda v: str(v) if not isinstance(v, dict)
    else "")
def test_a_served_prefill_holds_one_loop_a_call_site_and_no_wider_buffer(
        one_chip_mesh, monkeypatch, cell, cut, bucket):
    """A prefill bucket of several row blocks, as the chip's compiler leaves
    it (PR 47): the position-wise layers are ONE loop body a call site, as
    many ``while`` ops at 4096 as at the cell's longest bucket (a chunk's
    apart), no ``[bucket, mlp_dim]`` array is left (a block's instead), and
    the program's peak is no larger than its parent's."""
    from horovod_tpu.models import transformer as T

    cfg, backend, compiled = _served_prefill(
        one_chip_mesh, monkeypatch, cell, bucket, num_layers=2, **cut)
    assert backend.prefill_rows(bucket, bucket) == bucket
    text = compiled.as_text()
    whiles, parent_peak = dict(
        (c, b) for c, _, b in PREFILL_CELLS)[cell][bucket]
    assert len(re.findall(r" while\(", text)) == whiles
    # the widths of a feed-forward's hidden rows, where [bucket, width] is
    # no other array's shape: not a weight's (a bucket as long as the stream
    # is wide) nor the block of the walk of the held pairs
    from horovod_tpu.models import moe

    sparse = (cfg.moe_mlp_dim or cfg.mlp_dim) * max(cfg.num_shared_experts, 1)
    held = cfg.experts_held[1] - cfg.experts_held[0] if cfg.experts_held \
        else 0
    walked = held and moe.held_block_rows(
        min(bucket, cfg.feed_forward_chunk or bucket)
        * cfg.experts_per_token, held, cfg.num_experts)
    widths = {cfg.mlp_dim, sparse} - {cfg.embed_dim} \
        - ({sparse} if walked == bucket else set())
    assert widths and (bucket == cfg.embed_dim or not re.search(
        rf"(?:bf16|f32)\[(?:1,)?{bucket},(?:{'|'.join(map(str, widths))})\]",
        text))
    assert re.search(rf"bf16\[(?:1,)?{T.ROW_BLOCK},"
                     rf"(?:{'|'.join(map(str, widths))})\]", text)
    # (a megabyte for what is no array: the loops' counters, the code)
    assert compiled.memory_analysis().peak_memory_in_bytes \
        <= parent_peak + 2 ** 20
