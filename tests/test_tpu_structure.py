"""Structure of programs compiled for a TPU v5e that is described, not
attached (``jax.experimental.topologies``): what the chip's own compiler
makes of a step, read off its optimized text.  Nothing executes.  Three
files of the suite load the TPU compiler, so that ``--dist loadfile`` can
give them to three workers: this one (the kernels and the layers around
them, and the helpers the other two import), ``test_tpu_structure_served.py``
(the serving cells' prefill and decode programs) and
``test_tpu_structure_trained.py`` (the sparse layer as it is trained and as a
share of it is held).  Keep such tests in them, and the topology inside the
fixture (a module that describes it while being imported gives
pytest-xdist's workers different tests to collect)."""

import math
import re

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

@pytest.fixture(scope="module")
def one_chip_mesh():
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here, or another process's
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return Mesh(np.asarray(topo.devices[:1]), ("hvd",))


def _kernels_named(text: str, name: str) -> list[str]:
    """The ``op_name`` of every Mosaic custom call of ``text`` that holds
    ``name``."""
    return [line.split("op_name=")[1].split('"')[1]
            for line in text.splitlines()
            if 'custom_call_target="tpu_custom_call"' in line
            and name in line.split("op_name=")[1]]

def _arrays(shape: str) -> list[tuple[str, int]]:
    """[(dtype, elements)] of every array in a shape's text."""
    from horovod_tpu.utils.profiling import _ARRAY

    return [(t, math.prod(int(n) for n in dims.split(",") if n))
            for t, _, dims in _ARRAY.findall(shape)]


def _entry_instructions(text: str) -> list[dict]:
    """The ENTRY computation's instructions: name, opcode, result shape,
    the operands' shapes, and whether a matmul is inside (its own opcode,
    or the computation a fusion calls)."""
    from horovod_tpu.utils.profiling import _computations

    comps = _computations(text)
    shape_of = {name: shape for body in comps.values()
                for name, _, _, _, shape in body}
    with_matmul = {c for c, body in comps.items()
                   if any(op in ("convolution", "dot")
                          for _, op, _, _, _ in body)}
    entry = text[text.index("\nENTRY "):]
    out = []
    for line in entry.splitlines()[1:]:
        m = re.match(r"^\s+(?:ROOT )?%?([^\s=]+) = .*?\s([\w\-]+)\((.*)$", line)
        if not m or m.group(1) not in shape_of:
            continue
        name, opcode, rest = m.groups()
        operands = re.findall(r"%([\w.\-]+)", rest.split("), ")[0])
        calls = re.search(r"calls=%?([\w.\-]+)", rest)
        out.append({
            "name": name, "opcode": opcode, "shape": shape_of[name],
            "operands": [shape_of[o] for o in operands if o in shape_of],
            "kernel": 'custom_call_target="tpu_custom_call"' in line,
            "matmul": opcode in ("convolution", "dot")
            or bool(calls and calls.group(1) in with_matmul)})
    return out


# (B, S): at most this many full-size ``copy`` ops, none of them float32,
# and at most this many GB moved by XLA ops that are neither matmul nor
# kernel.  The parent of PR 31 (f32 kernel outputs, residuals in
# [B, S, H, D]): 6 copies, two of them f32, 3.35 GB; 0 copies, 1.84 GB.

def test_width1_update_is_no_epilogue_of_a_weight_gradient_matmul(
        hvd, one_chip_mesh):
    """Left alone, XLA:TPU fuses adamw into the convolution that produces
    each weight gradient, and such a fusion costs more than the matmul and
    the update one after the other (PERF.md, PR 25).  With the width-1
    plan's barrier on every large gradient, no fusion of the compiled step
    holds both a convolution and adamw's ``sqrt``."""
    from horovod_tpu.ops import schedule_plan as sp
    from horovod_tpu.utils.profiling import _computations

    mesh = one_chip_mesh
    opt = hvd.DistributedOptimizer(optax.adamw(3e-4))

    def step(state, x):
        params, opt_state = state

        def loss(p):
            h = x
            for w in p:
                h = jnp.tanh(h @ w.astype(jnp.bfloat16))
            return jnp.mean(h.astype(jnp.float32) ** 2)

        updates, opt_state = opt.update(jax.grad(loss)(params), opt_state,
                                        params)
        return optax.apply_updates(params, updates), opt_state

    replicated = NamedSharding(mesh, P())

    def shaped(tree):
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=replicated), tree)

    params = [jax.ShapeDtypeStruct((2048, 2048), jnp.float32)] * 4
    assert 2048 * 2048 * 4 >= sp.MATERIALIZE_MIN_BYTES
    state = shaped((params, jax.eval_shape(opt.init, params)))
    x = jax.ShapeDtypeStruct((16384, 2048), jnp.bfloat16,
                             sharding=NamedSharding(mesh, P("hvd")))
    compiled = jax.jit(
        jax.shard_map(step, mesh=mesh, in_specs=(P(), P("hvd")),
                      out_specs=P(), check_vma=False),
        donate_argnums=(0,)).lower(state, x).compile()
    plan = hvd.overlap_plan()
    assert plan["materialized_leaves"] == 4, plan

    opcodes = [{ins[1] for ins in body}
               for body in _computations(compiled.as_text()).values()]
    with_sqrt = [ops for ops in opcodes if ops & {"sqrt", "rsqrt"}]
    assert len(with_sqrt) >= 4, "adamw's sqrt is nowhere: wrong probe"
    assert not [ops for ops in with_sqrt if "convolution" in ops]

@pytest.mark.parametrize("b,s", [(8, 2048), (4, 4096), (1, 16384),
                                  (1, 32768)])
def test_fused_flash_backward_compiles_within_the_vmem_it_asks_for(
        one_chip_mesh, b, s):
    """The backward's one kernel holds a head's whole f32 dq accumulator in
    VMEM (8 MiB at S=16384, 16 at S=32768) and the dq output block beside
    it (half that in bf16, as much again in float32): past Mosaic's 16 MiB
    default, so the call asks for its own limit.  The chip's compiler
    takes the kernel at the benchmark's three geometries and at S=32768
    (H=16, d=128, bf16, default tiles), with the gradients in the compute
    dtype (``flash_attention``'s own backward) and in float32 (the entry
    ring attention calls), and makes one custom call of it."""
    import importlib

    from horovod_tpu.utils import profiling
    fa = importlib.import_module("horovod_tpu.ops.flash_attention")

    one_chip = NamedSharding(one_chip_mesh, P())
    x = jax.ShapeDtypeStruct((b, s, 16, 128), jnp.bfloat16, sharding=one_chip)
    stat = jax.ShapeDtypeStruct((b, s, 16), jnp.float32, sharding=one_chip)
    xb = jax.ShapeDtypeStruct((b * 16, s, 128), jnp.bfloat16,
                              sharding=one_chip)
    lse_b = jax.ShapeDtypeStruct((b * 16, 8, s), jnp.float32,
                                 sharding=one_chip)
    delta_b = jax.ShapeDtypeStruct((b * 16, s), jnp.float32,
                                   sharding=one_chip)
    tiles = (1024, fa._default_block_k(s, 128))

    def partials(q, k, v, do, lse, delta):
        return fa.flash_attention_backward(
            q, k, v, do, lse, delta, True, 0, 0, *tiles, False)

    def compute_dtype(qb, kb, vb, dob, lse_b, delta_b):
        return fa._backward_bh(qb, kb, vb, dob, lse_b, delta_b, s, s, True,
                               0, 0, *tiles, False, 1024, jnp.bfloat16)

    for fn, args, dtype in (
            (partials, (x, x, x, x, stat, stat), "f32"),
            (compute_dtype, (xb, xb, xb, xb, lse_b, delta_b), "bf16")):
        text = jax.jit(fn).lower(*args).compile().as_text()
        kernels = [line for line in text.splitlines()
                   if 'custom_call_target="tpu_custom_call"' in line]
        assert len(kernels) == 1 and profiling.FLASH_BWD in kernels[0]
        assert kernels[0].split(" = ")[1].startswith(
            f"({dtype}[{b * 16},{s},128]")

@pytest.mark.parametrize("q,n,heads,groups,dtype", [
    (256, 128, 64, 1, jnp.bfloat16),    # granite-4.0-h-micro's mixer
    (256, 256, 32, 1, jnp.bfloat16), (128, 128, 8, 2, jnp.bfloat16),
    (256, 256, 64, 8, jnp.float32)])
def test_the_scans_kernels_compile_wherever_their_rule_sends_them(
        one_chip_mesh, monkeypatch, q, n, heads, groups, dtype):
    """``ssd_scan`` decides by shape alone whether the kernels run, so every
    shape its rule admits has to be one the chip's compiler takes, within
    Mosaic's default VMEM (the kernels ask for no limit of their own): the
    cell's widths, the rule's largest chunk, state and block, its smallest,
    and float32 operands.  Forward and backward are one custom call each,
    under their names; off the rule there is none."""
    from horovod_tpu.ops import ssd_scan as ssd
    from horovod_tpu.utils import profiling

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    one_chip = NamedSharding(one_chip_mesh, P())
    shape = lambda *dims, dtype=jnp.float32: jax.ShapeDtypeStruct(  # noqa: E731
        dims, dtype, sharding=one_chip)

    def kernels_of(q, p):
        args = (shape(2, 4 * q, heads, p, dtype=dtype),
                shape(2, 4 * q, heads), shape(heads),
                shape(2, 4 * q, groups, n, dtype=dtype),
                shape(2, 4 * q, groups, n, dtype=dtype), shape(heads))
        grad = jax.grad(lambda *a: ssd.ssd_scan(*a, q).astype(
            jnp.float32).sum(), tuple(range(6)))
        text = jax.jit(grad).lower(*args).compile().as_text()
        return sorted(
            next(k for k in profiling.SSD_PASSES
                 if k in line.split("op_name=")[1])
            for line in text.splitlines()
            if 'custom_call_target="tpu_custom_call"' in line)

    assert ssd.head_block(q, heads // groups, 64, n) == min(
        heads // groups, 16)
    assert kernels_of(q, 64) == [profiling.SSD_BWD, profiling.SSD_FWD]
    assert ssd.head_block(2 * q, heads // groups, 32, n) is None
    assert kernels_of(2 * q, 32) == []

@pytest.mark.parametrize("s,start,widths,taps,dtype", [
    # granite-4.0-h-micro's mixer: x | B | C at column 4096 of 8512
    (8192, 4096, (4096, 128, 128), 4, jnp.bfloat16),
    (256, 0, (256,), 2, jnp.bfloat16),          # the rule's least tile
    (1024, 128, (128, 512), 8, jnp.float32)])   # float32, the most taps
def test_the_convs_kernels_compile_wherever_their_rule_sends_them(
        one_chip_mesh, monkeypatch, s, start, widths, taps, dtype):
    """``causal_conv_silu`` decides by shape alone whether the kernels run,
    so what its rule admits has to be what the chip's compiler takes within
    Mosaic's default VMEM: the cell's widths, the smallest tile, float32
    with the most taps.  A call a part, forward and backward under their
    names; the stream is an operand of the kernels as it stands (no copy, no
    slice of it in the text); off the rule there is no kernel."""
    from horovod_tpu.ops import causal_conv as cc
    from horovod_tpu.utils import profiling

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    one_chip = NamedSharding(one_chip_mesh, P())
    shape = lambda *dims, dtype=jnp.float32: jax.ShapeDtypeStruct(  # noqa: E731
        dims, dtype, sharding=one_chip)
    width = start + sum(widths) + 64

    def compiled_text(s):
        def loss(x, w, taps_, bias):
            # the stream as a projection leaves it, not an entry parameter
            stream = jnp.dot(x, w, preferred_element_type=dtype)
            parts = cc.causal_conv_silu(stream, taps_, bias, start, widths)
            return sum(jnp.square(part.astype(jnp.float32)).sum()
                       for part in parts) \
                + stream[..., :start].astype(jnp.float32).sum()
        return jax.jit(jax.grad(loss, (0, 1, 2, 3))).lower(
            shape(1, s, 128, dtype=dtype), shape(128, width, dtype=dtype),
            shape(taps, sum(widths)), shape(sum(widths))).compile().as_text()

    assert cc.conv_form(s, taps, start, widths) == "kernel"
    text = compiled_text(s)
    assert len(_kernels_named(text, profiling.CAUSAL_CONV_FWD)) \
        == len(_kernels_named(text, profiling.CAUSAL_CONV_BWD)) \
        == len(widths)
    stream_shape = f"[1,{s},{width}]"
    moved = [line for line in text.splitlines()
             if re.search(r" (copy|slice)\(", line)
             and stream_shape in line.split("=")[1]]
    assert not moved, moved
    assert cc.conv_form(s - 8, taps, start, widths) == "xla"
    assert not _kernels_named(compiled_text(s - 8), "hvd_causal_conv")

@pytest.mark.parametrize("b,s,heads,d", [
    (1, 1024, 32, 128),     # a row block of Ling-3.0-flash's mixer
    (1, 2048, 32, 128),     # its 2048 bucket: no loop, 32 chunks a call
    (2, 64, 2, 128),        # the rule's least: one pair of heads, one chunk
    (1, 100, 20, 128)])     # blocks of four heads of twenty, a padded length
def test_the_delta_rules_kernel_compiles_wherever_its_rule_sends_it(
        one_chip_mesh, monkeypatch, b, s, heads, d):
    """``kda_chunked`` decides by shape alone whether the kernel runs, so
    every shape its rule admits has to be one the chip's compiler takes,
    within Mosaic's default VMEM (the kernel asks for no limit of its own):
    one custom call under its name, the state out as it came in; off the
    rule there is none."""
    from horovod_tpu.ops import kda_scan
    from horovod_tpu.utils import profiling

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    one_chip = NamedSharding(one_chip_mesh, P())
    shape = lambda *dims: jax.ShapeDtypeStruct(  # noqa: E731
        dims, jnp.float32, sharding=one_chip)

    def kernels_of(heads, d):
        args = (*(shape(b, s, heads, d),) * 4, shape(b, s, heads),
                shape(b, heads, d, d))
        o, state = jax.eval_shape(kda_scan.kda_chunked, *args)
        assert (o.shape, state.shape) == ((b, s, heads, d), args[-1].shape)
        text = jax.jit(kda_scan.kda_chunked).lower(*args).compile().as_text()
        return _kernels_named(text, profiling.KDA_CHUNK)

    assert kda_scan.scan_form(heads, d, d) == "kernel"
    assert len(kernels_of(heads, d)) == 1
    for off in ((heads + 1, d), (heads, d // 2)):
        assert kda_scan.scan_form(*off, off[1]) == "xla"
        assert kernels_of(*off) == []

@pytest.mark.parametrize("b,s,copies,glue_gb", [(8, 2048, 6, 2.8),
                                                (1, 16384, 0, 1.75)])
def test_attention_layer_keeps_no_f32_activation_between_its_kernels(
        hvd, one_chip_mesh, monkeypatch, b, s, copies, glue_gb):
    """One ``Attention`` layer at the benchmark's widths (16 heads of 128,
    bf16), forward and backward, as the chip's compiler leaves it: the
    flash kernels read and write the compute dtype (no float32 array of
    B·H·S·D elements goes into or comes out of a kernel, and no standalone
    ``convert`` reads one), what changes a layout between a projection
    and a kernel does so on bf16, and XLA's own traffic around the
    kernels stays under what PR 31 left (2.66 / 1.69 GB a layer)."""
    from horovod_tpu.models.transformer import Attention, TransformerConfig
    from horovod_tpu.utils.profiling import _shape_bytes

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")  # Mosaic
    one_chip = NamedSharding(one_chip_mesh, P())
    layer = Attention(TransformerConfig(
        num_heads=16, head_dim=128, embed_dim=2048,
        attention_fn=hvd.make_flash_attention()))
    x = jax.ShapeDtypeStruct((b, s, 2048), jnp.bfloat16, sharding=one_chip)
    pos = jax.ShapeDtypeStruct((b, s), jnp.int32, sharding=one_chip)
    params = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        jax.eval_shape(layer.init, jax.random.PRNGKey(0), x, pos))

    def forward_and_backward(params, x, pos, g):
        out, vjp = jax.vjp(lambda p, x: layer.apply(p, x, pos), params, x)
        return (out,) + vjp(g)

    text = jax.jit(forward_and_backward).lower(
        params, x, pos, x).compile().as_text()
    full = b * s * 16 * 128
    f32_full = lambda shapes: [  # noqa: E731
        sh for sh in shapes
        if any(t == "f32" and n >= full for t, n in _arrays(sh))]
    instructions = _entry_instructions(text)
    kernels = [i for i in instructions if i["kernel"]]
    assert len(kernels) == 2
    for k in kernels:
        assert not f32_full([k["shape"]] + k["operands"]), k["name"]
    for i in instructions:
        if i["opcode"] == "convert":
            assert not f32_full(i["operands"]), i["name"]
    full_copies = [i for i in instructions if i["opcode"] == "copy"
                   and any(n >= full for _, n in _arrays(i["shape"]))]
    assert not f32_full([i["shape"] for i in full_copies])
    assert len(full_copies) <= copies, [i["name"] for i in full_copies]
    moved = sum(
        _shape_bytes(sh)
        for i in instructions
        if not (i["kernel"] or i["matmul"] or i["opcode"] in (
            "parameter", "constant", "tuple", "get-tuple-element",
            "bitcast", "iota", "custom-call")
            or i["opcode"].endswith(("-start", "-done")))
        for sh in [i["shape"]] + i["operands"])
    assert moved / 1e9 <= glue_gb

def test_grouped_flash_attention_at_head_size_64_compiles_for_the_chip(
        one_chip_mesh, monkeypatch):
    """``granite4hm-s8192``'s one attention layer (PR 33): 32 query heads
    reading 8 KV heads of 64, no rotary embedding, the softmax scale
    2**-6, one 8192-token sequence.  The chip's compiler takes the forward
    and the backward kernel at d = 64 through the public entry, K and V
    repeated to the query heads outside them, and the caller's scale is a
    constant of the kernels: no op of its own on q."""
    import importlib

    from horovod_tpu.utils import profiling
    fa = importlib.import_module("horovod_tpu.ops.flash_attention")

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    one_chip = NamedSharding(one_chip_mesh, P())
    q = jax.ShapeDtypeStruct((1, 8192, 32, 64), jnp.bfloat16,
                             sharding=one_chip)
    kv = jax.ShapeDtypeStruct((1, 8192, 8, 64), jnp.bfloat16,
                              sharding=one_chip)

    def loss(q, k, v):
        return fa.flash_attention(q, k, v, scale=0.015625).astype(
            jnp.float32).sum()

    compiled = jax.jit(jax.grad(loss, (0, 1, 2))).lower(q, kv, kv).compile()
    text = compiled.as_text()
    kernels = [line for line in text.splitlines()
               if 'custom_call_target="tpu_custom_call"' in line]
    assert len(kernels) == 2
    assert sum(profiling.FLASH_FWD in k for k in kernels) == 1
    assert sum(profiling.FLASH_BWD in k for k in kernels) == 1
    # dk and dv leave at the KV heads' shape
    root = [line for line in text.splitlines() if "ROOT" in line][-1]
    assert "bf16[1,8192,32,64]" in root and root.count(
        "bf16[1,8192,8,64]") == 2


# the forward told where a prompt ends (PR 45), at the shapes the four served
# cells' longest buckets hand it: (S, heads, KV heads, key width, value
# width, what else the call is told)

@pytest.mark.parametrize("s,heads,kv_heads,d,d_v,told", [
    (4096, 16, 16, 128, 128, {}),                       # dsc1p3b-code-0.8knee
    (8192, 128, 8, 128, 128, {"window": 4096}),         # cmdaplus, sliding
    (16384, 64, 64, 192, 128, {"scale": 0.1147}),       # axk1, expanded MLA
    (2048, 32, 32, 128, 128, {"causal": False}),        # evabyte's rectangle
], ids=["dense_mha", "banded_gqa", "keys_192_values_128", "rectangle"])
def test_the_bounded_flash_forward_compiles_for_the_chip(
        one_chip_mesh, monkeypatch, s, heads, kv_heads, d, d_v, told):
    """The chip's compiler takes the forward kernel with ``q_len`` and
    ``k_len`` traced: the meta prefetched into SMEM for the K / V index map,
    the scaled q tile in VMEM scratch, one kernel and no backward."""
    import importlib

    from horovod_tpu.utils import profiling
    fa = importlib.import_module("horovod_tpu.ops.flash_attention")

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    one_chip = NamedSharding(one_chip_mesh, P())
    shape = lambda h, w: jax.ShapeDtypeStruct(  # noqa: E731
        (1, s, h, w), jnp.bfloat16, sharding=one_chip)
    length = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    entry = fa.flash_attention if told.get("causal", True) \
        else fa.flash_attention_with_lse
    compiled = jax.jit(lambda q, k, v, n: entry(
        q, k, v, q_len=n, k_len=n, **told)).lower(
        shape(heads, d), shape(kv_heads, d), shape(kv_heads, d_v),
        length).compile()
    kernels = [line for line in compiled.as_text().splitlines()
               if 'custom_call_target="tpu_custom_call"' in line]
    assert len(kernels) == 1 and profiling.FLASH_FWD in kernels[0]


# the two served shapes (heads, KV heads, layer types, window, slots, S) at
# a sixth of the dense model's depth and the sparse one's one period, and
# how many copies of one layer's view XLA may make: none where the products
# are a loop fusion over the pool as it lies (one query a head), one for K
# and one for V a layer where they are a convolution (a group of queries a
# KV head), whose operand XLA:TPU does not take from a slice of the pool
