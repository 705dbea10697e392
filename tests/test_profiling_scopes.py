"""The compiled path's names (``horovod_tpu/utils/profiling.py``): the phase
rule on single ``op_name``s, ``scope_table`` on hand-written optimized HLO
and on a tiny compiled step, the scopes the program writes into a
``DistributedOptimizer`` step, the flash kernels and the loss, the three host
spans of ``data.py`` in a CPU profile, and the planner's record of where its
headroom came from."""

import glob
import itertools
import os

import numpy as np
import pytest

from horovod_tpu.utils import profiling

STEP = "jit(step)/shard_map/"


@pytest.mark.parametrize("op_name,opcode,phase", [
    (STEP + "jvp(Transformer)/layer_1/mlp/up/dot_general", "fusion",
     "forward"),
    (STEP + "transpose(jvp(Transformer))/layer_1/mlp/up/dot_general",
     "convolution", "backward"),
    (STEP + "transpose(jvp(Transformer))/jvp(Transformer)/checkpoint/"
     "rematted_computation/layer_0/attn/q/dot_general", "fusion",
     "recompute"),
    (STEP + "transpose(jvp(Transformer))/jvp(Transformer)/checkpoint/"
     "layer_0/attn/q/dot_general", "fusion", "backward"),
    (STEP + "hvd_optimizer/mul", "multiply", "optimizer"),
    # adamw of a parameter is the optimizer's even inside a transform
    (STEP + "jvp(hvd_optimizer)/mul", "multiply", "optimizer"),
    (STEP + "hvd_allreduce/hvd_bucket_2/psum", "all-reduce", "collective"),
    (STEP + "transpose(jvp(x))/psum", "all-reduce-start", "collective"),
    (STEP + "hvd_allreduce/div", "divide", "unscoped"),
    ("", "copy", "unscoped"),
])
def test_phase_of(op_name, opcode, phase):
    assert profiling.phase_of(op_name, opcode) == phase


@pytest.mark.parametrize("op_name,module", [
    (STEP + "transpose(jvp(Transformer))/layer_11/mlp/up/dot_general",
     "Transformer/layer_N/mlp/up"),
    (STEP + "jvp(Transformer)/layer_1/mlp/jit(silu)/mul",
     "Transformer/layer_N/mlp"),
    ("jit(train_step)/transpose(jvp(hvd_loss))/convert_element_type",
     "hvd_loss"),
    (STEP + "transpose(jvp(Transformer))/jvp(Transformer)/checkpoint/"
     "rematted_computation/layer_0/attn/q/dot_general",
     "Transformer/layer_N/attn/q"),
    (STEP + "hvd_optimizer/mul", "hvd_optimizer"),
    (STEP + "jvp(ResNet)/BottleneckBlock_3/conv1/conv_general_dilated",
     "ResNet/BottleneckBlock_N/conv1"),
    (STEP + "while/body/closed_call/jvp(ResNet)/BottleneckBlock_3/Conv_0/"
     "conv_general_dilated", "ResNet/BottleneckBlock_N/Conv_N"),
    (STEP + "mul", ""),
    ("", ""),
])
def test_module_of(op_name, module):
    assert profiling.module_of(op_name) == module


def md(op_name):
    return f'metadata={{op_name="{op_name}" source_file="x.py" source_line=1}}'


FWD = STEP + "jvp(Net)/layer_0/up/"
BWD = STEP + "transpose(jvp(Net))/layer_0/up/"
OPT = STEP + "hvd_optimizer/"

# Optimized HLO as ``compiled.as_text()`` prints it, cut to what the parser
# reads: fused computations, a while with its body, and an entry.
HLO = f"""HloModule jit_step, is_scheduled=true

%fused_computation.1 (p0: bf16[8,16], p1: f32[16,4], p2: f32[16,4]) -> f32[16,4] {{
  %p0 = bf16[8,16]{{1,0}} parameter(0)
  %p1 = f32[16,4]{{1,0}} parameter(1)
  %p2 = f32[16,4]{{1,0}} parameter(2)
  %convert.1 = bf16[16,4]{{1,0}} convert(%p1), {md(FWD + "convert_element_type")}
  %copy.9 = bf16[16,4]{{1,0}} copy(%convert.1)
  %convolution.1 = f32[16,4]{{1,0}} convolution(%p0, %copy.9), dim_labels=bf_io->bf, {md(BWD + "dot_general")}
  %multiply.1 = f32[16,4]{{1,0}} multiply(%convolution.1, %p2), {md(OPT + "mul")}
  ROOT %add.1 = f32[16,4]{{1,0}} add(%multiply.1, %p1), {md(OPT + "add")}
}}

%fused_computation.2 (p0: bf16[8,16], p1: f32[16,4]) -> f32[8,4] {{
  %p0 = bf16[8,16]{{1,0}} parameter(0)
  %p1 = f32[16,4]{{1,0}} parameter(1)
  %convert.2 = bf16[16,4]{{1,0}} convert(%p1), {md(FWD + "convert_element_type")}
  %exponential.2 = bf16[16,4]{{1,0}} exponential(%convert.2), {md(FWD + "exp")}
  ROOT %convolution.2 = f32[8,4]{{1,0}} convolution(%p0, %exponential.2), dim_labels=bf_io->bf, {md(BWD + "transpose")}
}}

%fused_computation.3 (p0: bf16[8,16], p1: f32[16,4]) -> (f32[8,4], f32[8,4]) {{
  %p0 = bf16[8,16]{{1,0}} parameter(0)
  %p1 = f32[16,4]{{1,0}} parameter(1)
  %convolution.3 = f32[8,4]{{1,0}} convolution(%p0, %p1), dim_labels=bf_io->bf, {md(STEP + "jvp(Net)/head/dot_general")}
  %subtract.3 = f32[8,4]{{1,0}} subtract(%convolution.3, %convolution.3), {md(STEP + "transpose(jvp(hvd_loss))/sub")}
  ROOT %tuple.3 = (f32[8,4]{{1,0}}, f32[8,4]{{1,0}}) tuple(%convolution.3, %subtract.3)
}}

%fused_computation.4 (p0: f32[16,4]) -> f32[16,4] {{
  %p0 = f32[16,4]{{1,0}} parameter(0)
  ROOT %copy.4 = f32[16,4]{{1,0}} copy(%p0)
}}

%sum (a: f32[], b: f32[]) -> f32[] {{
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %add.s = f32[] add(%a, %b)
}}

%body (arg: (s32[], f32[8,4])) -> (s32[], f32[8,4]) {{
  %arg = (s32[], f32[8,4]{{1,0}}) parameter(0)
  %gte.0 = s32[] get-tuple-element(%arg), index=0
  %gte.1 = f32[8,4]{{1,0}} get-tuple-element(%arg), index=1
  %negate.7 = f32[8,4]{{1,0}} negate(%gte.1), {md(STEP + "jvp(Net)/while/body/layer_3/neg")}
  ROOT %tuple.7 = (s32[], f32[8,4]{{1,0}}) tuple(%gte.0, %negate.7)
}}

%cond (arg: (s32[], f32[8,4])) -> pred[] {{
  %arg.c = (s32[], f32[8,4]{{1,0}}) parameter(0)
  ROOT %lt = pred[] constant(true)
}}

ENTRY %main.1 (x: bf16[8,16], w: f32[16,4], m: f32[16,4]) -> f32[16,4] {{
  %x = bf16[8,16]{{1,0}} parameter(0)
  %w = f32[16,4]{{1,0}} parameter(1)
  %m = f32[16,4]{{1,0}} parameter(2)
  %fusion.3 = (f32[8,4]{{1,0}}, f32[8,4]{{1,0}}) fusion(%x, %w), kind=kOutput, calls=%fused_computation.3, {md(STEP + "jvp(Net)/head/dot_general")}
  %while.1 = (s32[], f32[8,4]{{1,0}}) while(%fusion.3), condition=%cond, body=%body, {md(STEP + "jvp(Net)/while")}
  %hvd_flash_dq.4 = f32[8,4]{{1,0:T(8,128)}} custom-call(%x), custom_call_target="tpu_custom_call", {md(STEP + "transpose(jvp(Net))/layer_0/attn/hvd_flash_dq/pallas_call")}
  %hvd_flash_bwd.8 = (f32[8,4]{{1,0:T(8,128)}}, f32[8,4]{{1,0:T(8,128)}}, f32[8,4]{{1,0:T(8,128)}}) custom-call(%x), custom_call_target="tpu_custom_call", {md(STEP + "transpose(jvp(Net))/layer_0/attn/hvd_flash_bwd/pallas_call")}
  %fusion.2 = f32[8,4]{{1,0}} fusion(%x, %w), kind=kOutput, calls=%fused_computation.2, {md(BWD + "transpose")}
  %psum.5 = f32[16,4]{{1,0}} all-reduce(%w), channel_id=1, replica_groups={{{{0,1,2,3}}}}, to_apply=%sum, {md(STEP + "hvd_allreduce/hvd_bucket_2/psum")}
  %all-reduce.6 = f32[] all-reduce(%w), channel_id=2, to_apply=%sum, {md(STEP + "psum")}
  %fusion.4 = f32[16,4]{{1,0}} fusion(%w), kind=kLoop, calls=%fused_computation.4
  ROOT %fusion.1 = f32[16,4]{{1,0}} fusion(%x, %w, %m), kind=kOutput, calls=%fused_computation.1, {md(BWD + "dot_general")}
}}
"""


@pytest.fixture(scope="module")
def table():
    return profiling.scope_table(HLO)


@pytest.mark.parametrize("name,label", [
    # a weight-gradient matmul with the update in its epilogue keeps the
    # pair; the forward cast riding in it adds no phase
    ("fusion.1", "backward+optimizer"),
    # forward arithmetic the compiler re-did inside a backward fusion
    ("fusion.2", "backward"),
    # a forward contraction is the forward pass's own work
    ("fusion.3", "forward+backward"),
    ("fusion.4", "unscoped"),           # nothing inside has a name
    ("while.1", "forward"),
    ("negate.7", "forward"),            # a while body's instruction
    ("psum.5", "collective"),           # by opcode, not by name
    ("all-reduce.6", "collective"),
    ("hvd_flash_dq.4", "backward"),
    ("hvd_flash_bwd.8", "backward"),
    ("multiply.1", "optimizer"),        # inside a fusion: still in the table
])
def test_scope_table_labels(table, name, label):
    assert table[name].label == label
    assert table[name].phase == ("mixed" if "+" in label else label)


def test_scope_table_reads_buckets_kernels_modules_and_bytes(table):
    assert table["psum.5"].bucket == "2" and table["psum.5"].bytes == 256
    assert table["all-reduce.6"].bucket is None
    # an older program's two backward passes keep their names; the one
    # fused pass has its own
    assert table["hvd_flash_dq.4"].kernel == profiling.FLASH_DQ
    assert profiling.FLASH_DKV == "hvd_flash_dkv"
    assert table["hvd_flash_bwd.8"].kernel == profiling.FLASH_BWD
    assert table["hvd_flash_bwd.8"].bytes == 3 * 8 * 4 * 4
    assert table["fusion.1"].kernel is None
    assert table["fusion.1"].module == "Net/layer_N/up"
    assert table["negate.7"].module == "Net/layer_N"     # the loop is jax's
    assert table["fusion.3"].bytes == 2 * 8 * 4 * 4          # a tuple's arrays
    assert table["fusion.3"].opcode == "fusion"
    assert table["while.1"].opcode == "while"
    assert "sum" not in table and "add.s" in table


@pytest.fixture(scope="module")
def tiny_step(hvd_module):
    """A two-layer flax model through value_and_grad, a fori_loop (a while
    body) and DistributedOptimizer(adamw), compiled for the 8-device mesh."""
    import flax.linen as nn
    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import PartitionSpec as P

    hvd = hvd_module

    class Net(nn.Module):
        @nn.compact
        def __call__(self, x):
            for _ in range(2):
                x = nn.relu(nn.Dense(16)(x))
            return jax.lax.fori_loop(0, 3, lambda i, y: jnp.sin(y), x)

    net = Net()
    opt = hvd.DistributedOptimizer(optax.adamw(1e-3))

    def loss_fn(params, x):
        return (net.apply(params, x) ** 2).mean()

    def step(params, state, x):
        loss, grads = jax.value_and_grad(loss_fn)(params, x)
        updates, state = opt.update(grads, state, params)
        return optax.apply_updates(params, updates), state, loss

    params = net.init(jax.random.PRNGKey(0), jnp.zeros((1, 16)))
    fn = jax.jit(hvd.shard(step, in_specs=(P(), P(), hvd.batch_spec(2)),
                           out_specs=(P(), P(), P())))
    return fn.lower(params, opt.init(params), jnp.zeros((8, 16))).compile()


@pytest.fixture(scope="module")
def hvd_module():
    import horovod_tpu as hvd

    hvd.init()
    return hvd


def test_scope_table_of_a_compiled_step(tiny_step):
    table = profiling.scope_table(tiny_step)
    assert table == profiling.scope_table(tiny_step.as_text())
    phases = {s.phase for s in table.values()}
    assert {"forward", "backward", "optimizer", "collective",
            "unscoped"} <= phases
    assert any(s.module.startswith("Net/Dense_N") for s in table.values())
    assert any("hvd_optimizer" in s.module for s in table.values())
    # the loop's body is a computation of its own; its instructions are in
    # the table under their names
    assert any("/while/body/" in s.op_name and s.phase == "forward"
               for s in table.values())
    assert {s.bucket for s in table.values() if s.phase == "collective"
            and s.bucket} == {"0", "1", "2", "3"}
    named = sum(1 for s in table.values() if s.op_name)
    assert named > len(table) / 4


def lowered_names(lowered) -> str:
    return lowered.as_text(debug_info=True)


@pytest.mark.parametrize("buckets,expected", [
    (4, [f"hvd_bucket_{k}" for k in range(4)]),
    (0, ["hvd_bucket_all"]),
])
def test_distributed_optimizer_step_carries_the_scopes(hvd_module, buckets,
                                                       expected):
    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import Mesh, PartitionSpec as P

    hvd = hvd_module
    opt = hvd.DistributedOptimizer(
        optax.sgd(0.1), planner=hvd.AdaptivePlanner(default_depth=buckets))
    params = {f"w{i}": jnp.ones((4, 4)) for i in range(8)}

    def step(params, state, x):
        grads = jax.grad(lambda p: sum(
            (x @ w).sum() for w in p.values()))(params)
        return opt.update(grads, state, params)

    mesh = Mesh(np.asarray(jax.devices()[:4]), ("hvd",))
    fn = jax.jit(jax.shard_map(step, mesh=mesh, in_specs=(P(), P(), P("hvd")),
                               out_specs=(P(), P()), check_vma=False))
    text = lowered_names(fn.lower(params, opt.init(params), jnp.ones((8, 4))))
    for name in expected + ["hvd_allreduce", "hvd_optimizer"]:
        assert name in text, name
    assert ("hvd_bucket_all" in text) == (buckets == 0)
    assert ("hvd_chain_gate" in text) == (buckets == 4)
    assert "hvd_bucket_4" not in text
    plan = hvd.overlap_plan()
    assert plan["chain_depth"] == buckets and plan["width"] == 4


def test_int8_distributed_optimizer_carries_the_scopes(hvd_module):
    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import PartitionSpec as P

    hvd = hvd_module
    opt = hvd.DistributedOptimizer(optax.sgd(0.1),
                                   compression=hvd.Compression.int8)
    params = {"w": jnp.ones((4, 4))}

    def step(params, state, x):
        grads = jax.grad(lambda p: (x @ p["w"]).sum())(params)
        return opt.update(grads, state, params)

    fn = jax.jit(hvd.shard(step, in_specs=(P(), P(), hvd.batch_spec(2)),
                           out_specs=(P(), P())))
    text = lowered_names(fn.lower(params, opt.init(params), jnp.ones((8, 4))))
    assert "hvd_allreduce" in text and "hvd_optimizer" in text


def test_flash_passes_and_loss_are_tellable_in_interpret_mode():
    import jax
    import jax.numpy as jnp

    from horovod_tpu.ops.flash_attention import flash_attention
    from horovod_tpu.ops.losses import softmax_cross_entropy

    q = jnp.ones((1, 128, 2, 64), jnp.float32)

    def loss(q):
        out = flash_attention(q, q, q, causal=True, interpret=True)
        logits = out.reshape(1, 128, 128)
        return softmax_cross_entropy(
            logits, jnp.zeros((1, 128), jnp.int32)).mean()

    forward = lowered_names(jax.jit(loss).lower(q))
    assert profiling.FLASH_FWD in forward and profiling.LOSS in forward
    assert profiling.FLASH_BWD not in forward
    both = lowered_names(jax.jit(jax.grad(loss)).lower(q))
    for name in (profiling.FLASH_FWD, profiling.FLASH_BWD, profiling.LOSS):
        assert name in both, name
    # the backward is one kernel: the two passes' names are an older
    # program's
    assert profiling.FLASH_DQ not in both and profiling.FLASH_DKV not in both
    table = profiling.scope_table(jax.jit(jax.grad(loss)).lower(q).compile())
    assert {s.phase for s in table.values()
            if profiling.LOSS in s.op_name} >= {"forward", "backward"}


def test_remat_leaves_jaxs_marker_on_the_recomputed_forward():
    import flax.linen as nn
    import jax
    import jax.numpy as jnp

    class Block(nn.Module):
        @nn.compact
        def __call__(self, x):
            return jnp.tanh(nn.Dense(8)(x))

    class Net(nn.Module):
        @nn.compact
        def __call__(self, x):
            return nn.remat(Block)()(nn.remat(Block)()(x)).sum()

    net = Net()
    params = net.init(jax.random.PRNGKey(0), jnp.zeros((2, 8)))
    compiled = jax.jit(jax.grad(net.apply)).lower(
        params, jnp.ones((2, 8))).compile()
    table = profiling.scope_table(compiled)
    assert any(s.phase == "recompute" for s in table.values())
    assert all("rematted_computation" in s.op_name
               for s in table.values() if s.phase == "recompute")


def test_the_mamba_mixers_four_scopes_reach_the_table_in_every_phase():
    """``hvd_ssm_proj`` / ``_conv`` / ``_scan`` / ``_gate`` on a compiled
    step of a rematted hybrid: forward, backward and recomputed instructions
    keep the name, as a folded module path under the layer's ``mamba``."""
    import jax
    import jax.numpy as jnp

    from horovod_tpu.models import Transformer, TransformerConfig

    cfg = TransformerConfig(
        vocab_size=64, num_layers=2, num_heads=2, head_dim=8, embed_dim=16,
        mlp_dim=32, dtype=jnp.float32, remat=True,
        layer_types=("mamba", "mamba"), mamba_heads=4, mamba_head_dim=8,
        mamba_state_dim=8, mamba_chunk=8)
    model = Transformer(cfg)
    tokens = jnp.zeros((1, 24), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), tokens)
    loss = lambda p: model.apply(p, tokens).sum()  # noqa: E731
    table = profiling.scope_table(
        jax.jit(jax.value_and_grad(loss)).lower(params).compile())
    assert len(profiling.SSM_SCOPES) == 4
    for name in profiling.SSM_SCOPES:
        phases = set()
        for s in table.values():
            parts = s.module.split("/")
            if name in parts:
                assert parts[:3] == ["Transformer", "layer_N", "mamba"], s
                phases |= set(s.phases)
        assert {"forward", "backward", "recompute"} <= phases, (name, phases)
    # the projections are flax modules under the scope's name
    modules = {s.module for s in table.values()}
    assert f"Transformer/layer_N/mamba/{profiling.SSM_PROJ}/in_proj" in modules
    assert f"Transformer/layer_N/mamba/{profiling.SSM_PROJ}/out_proj" \
        in modules


def pallas_calls(jaxpr, outer=()):
    """(kernel name, name stack) of every ``pallas_call``; an equation
    inside a ``jit`` carries the stack from that ``jit`` inwards."""
    for eqn in jaxpr.eqns:
        stack = outer + tuple(
            part for part in str(eqn.source_info.name_stack).split("/")
            if part)
        if eqn.primitive.name == "pallas_call":
            yield eqn.params["name"], "/".join(stack)
        inner = stack if eqn.primitive.name in ("pjit", "jit") else outer
        for v in eqn.params.values():
            for sub in v if isinstance(v, (list, tuple)) else [v]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from pallas_calls(sub, inner)


def test_the_scans_kernels_are_launched_inside_the_mixers_scan_scope():
    """At widths that meet the kernels' tiling rule the gradient of a tiny
    rematted hybrid holds three ``pallas_call``s a mamba layer (forward, the
    forward again under remat, backward), each named by its constant of
    ``profiling`` and each under a name stack that holds ``hvd_ssm_scan``:
    the module path the benchmark's ``ssm_scan_ms`` finds them by.  They are
    no flash pass."""
    import jax
    import jax.numpy as jnp

    from horovod_tpu.models import Transformer, TransformerConfig

    cfg = TransformerConfig(
        vocab_size=64, num_layers=1, num_heads=2, head_dim=8, embed_dim=16,
        mlp_dim=32, dtype=jnp.float32, remat=True, layer_types=("mamba",),
        mamba_heads=2, mamba_head_dim=64, mamba_state_dim=128,
        mamba_chunk=128)
    model = Transformer(cfg)
    tokens = jnp.zeros((1, 128), jnp.int32)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0), tokens)
    jaxpr = jax.make_jaxpr(
        jax.grad(lambda p: model.apply(p, tokens).sum()))(params)

    found = list(pallas_calls(jaxpr.jaxpr))
    assert sorted(name for name, _ in found) == [
        profiling.SSD_BWD, profiling.SSD_FWD, profiling.SSD_FWD]
    for name, stack in found:
        parts = stack.split("/")
        assert parts[-4:] == ["layer_0", "mamba", profiling.SSM_SCAN, name]
    assert any("rematted_computation" in stack for _, stack in found)
    assert not set(profiling.SSD_PASSES) & set(profiling.FLASH_PASSES)
    # and the table of a compiled program names such a kernel by its pass
    text = """HloModule m
ENTRY %main (x: f32[8]) -> f32[8] {
  %x = f32[8]{0} parameter(0)
  ROOT %hvd_ssd_bwd.1 = f32[8]{0} custom-call(%x), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/transpose(jvp(Transformer))/layer_3/mamba/hvd_ssm_scan/hvd_ssd_bwd/pallas_call"}
}
"""
    scope = profiling.scope_table(text)["hvd_ssd_bwd.1"]
    assert scope.kernel == profiling.SSD_BWD and scope.phase == "backward"
    assert scope.module == "Transformer/layer_N/mamba/hvd_ssm_scan/" \
        + profiling.SSD_BWD


def test_the_convs_kernels_are_launched_inside_the_mixers_conv_scope():
    """At a length of a row tile the same tiny hybrid's convolution is the
    kernels' too: a call a part (x, B, C) a pass, each named by its constant
    and under ``hvd_ssm_conv``, where ``ssm_conv_ms`` finds them; the scan's
    stay under ``hvd_ssm_scan``."""
    import jax
    import jax.numpy as jnp

    from horovod_tpu.models import Transformer, TransformerConfig

    cfg = TransformerConfig(
        vocab_size=64, num_layers=1, num_heads=2, head_dim=8, embed_dim=16,
        mlp_dim=32, dtype=jnp.float32, remat=True, layer_types=("mamba",),
        mamba_heads=2, mamba_head_dim=64, mamba_state_dim=128,
        mamba_chunk=128)
    model = Transformer(cfg)
    tokens = jnp.zeros((1, 256), jnp.int32)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0), tokens)
    found = list(pallas_calls(jax.make_jaxpr(
        jax.grad(lambda p: model.apply(p, tokens).sum()))(params).jaxpr))
    conv = [(name, stack) for name, stack in found
            if name in profiling.CAUSAL_CONV_PASSES]
    assert sorted(name for name, _ in conv) == \
        [profiling.CAUSAL_CONV_BWD] * 3 + [profiling.CAUSAL_CONV_FWD] * 6
    for name, stack in conv:
        assert stack.split("/")[-4:] == ["layer_0", "mamba",
                                         profiling.SSM_CONV, name]
    assert sum("rematted_computation" in stack for _, stack in conv) == 3
    assert sorted(name for name, _ in found if name not in
                  profiling.CAUSAL_CONV_PASSES) == [
        profiling.SSD_BWD, profiling.SSD_FWD, profiling.SSD_FWD]
    assert not set(profiling.CAUSAL_CONV_PASSES) & set(
        profiling.FLASH_PASSES + profiling.SSD_PASSES)
    text = """HloModule m
ENTRY %main (x: f32[8]) -> f32[8] {
  %x = f32[8]{0} parameter(0)
  ROOT %hvd_causal_conv_fwd.7 = f32[8]{0} custom-call(%x), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/transpose(jvp(Transformer))/jvp(Transformer)/checkpoint/rematted_computation/layer_3/mamba/hvd_ssm_conv/jit(_forward)/hvd_causal_conv_fwd/pallas_call"}
}
"""
    scope = profiling.scope_table(text)["hvd_causal_conv_fwd.7"]
    assert scope.kernel == profiling.CAUSAL_CONV_FWD
    assert scope.phase == "recompute"
    assert scope.module == "Transformer/layer_N/mamba/hvd_ssm_conv/" \
        + profiling.CAUSAL_CONV_FWD


def host_events(logdir):
    """{span name: [(line, start_ns, end_ns)]} off the host planes; a line
    (one thread) is its place in the file, threads sharing their name."""
    from jax.profiler import ProfileData

    path = sorted(glob.glob(os.path.join(
        logdir, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    found = {}
    for i, plane in enumerate(ProfileData.from_file(path).planes):
        if plane.name.startswith("/device:"):
            continue
        for j, line in enumerate(plane.lines):
            for e in line.events:
                if e.name.startswith("hvd_") or e.name == "caller":
                    found.setdefault(e.name, []).append(
                        ((i, j), e.start_ns, e.start_ns + e.duration_ns))
    return found


def test_loader_spans_nest_inside_the_callers_span(hvd_module, tmp_path):
    hvd = hvd_module
    source = ((np.full((8, 4), i, np.float32),) for i in range(6))
    with hvd.profiling.trace(str(tmp_path)):
        batches = hvd.data.prefetch_to_device(
            hvd.data.BackgroundLoader(source, depth=2), size=2)
        seen = []
        while True:
            with hvd.profiling.annotate("caller"):
                batch = next(batches, None)
            if batch is None:
                break
            seen.append(float(batch[0][0, 0]))
    assert seen == [0.0, 1.0, 2.0, 3.0, 4.0, 5.0]
    events = host_events(str(tmp_path))
    callers = events["caller"]
    # one wait a batch and one for the end of the source; one put a batch
    assert len(events[profiling.LOADER_WAIT]) == 7
    assert len(events[profiling.H2D_PUT]) == 6
    assert len(events[profiling.LOADER_PRODUCE]) == 7
    for name in (profiling.LOADER_WAIT, profiling.H2D_PUT):
        for line, start, end in events[name]:
            assert line == callers[0][0]            # the consumer's thread
            assert any(a <= start and end <= b for _, a, b in callers), name
    # the source runs on the producer's thread, on a line of its own
    assert {line for line, _, _ in events[profiling.LOADER_PRODUCE]} \
        != {callers[0][0]}


def test_every_name_of_the_vocabulary_is_written_once():
    names = [v for k, v in vars(profiling).items()
             if k.isupper() and isinstance(v, str) and v.startswith("hvd_")]
    # five of models/moe.py, one of ops/token_sum.py, four of models/mamba.py,
    # two of ops/ssd_scan.py, two of ops/causal_conv.py, ten of
    # serving/engine.py, four of
    # models/transformer.LatentAttention, two of EvaAttention, five of
    # models/kda.py, one of ops/kda_scan.py, one of ops/grouped_matmul.py,
    # one of ops/moe_rows.py, four of models/cca.py, four of models/hyper.py,
    # five start-up spans and the compile ledger's three
    assert len(names) == len(set(names)) == 65
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    for directory, _, files in itertools.chain(
            os.walk(os.path.join(root, "horovod_tpu")),
            os.walk(os.path.join(root, "benchmarks"))):
        if "tests" in directory.split(os.sep):
            continue
        for f in files:
            path = os.path.join(directory, f)
            if not f.endswith(".py") or path == profiling.__file__:
                continue
            with open(path) as fh:
                code = "\n".join(line.split("#")[0] for line in fh
                                 if not line.lstrip().startswith(('"', "'")))
            for name in names:
                assert f'"{name}' not in code, (path, name)


@pytest.fixture()
def fresh_planner(monkeypatch):
    from horovod_tpu.ops import schedule_plan as sp

    for v in ("HOROVOD_DEVICE_HEADROOM_MB", "HVD_TPU_DEVICE_HEADROOM_MB"):
        monkeypatch.delenv(v, raising=False)
    sp._reset_for_tests()
    yield sp
    sp._reset_for_tests()


class FakeDevice:
    def __init__(self, in_use):
        self.in_use = in_use

    def memory_stats(self):
        return {"bytes_limit": 16 << 30, "bytes_in_use": self.in_use}


def test_a_plan_records_the_probe_that_fixed_its_headroom(fresh_planner,
                                                          monkeypatch):
    import jax

    sp = fresh_planner
    tensors = [np.zeros((64, 64), np.float32)] * 8
    devices = [FakeDevice(1 << 30), FakeDevice(3 << 30)]
    monkeypatch.setattr(jax, "local_devices", lambda: devices)
    first = sp.plan_overlap(tensors, width=4).as_dict()
    assert first["headroom_source"] == "probe"
    assert first["headroom_probe"] == {          # the fullest device's
        "bytes_in_use": 3 << 30, "bytes_limit": 16 << 30, "plans_before": 0}
    assert first["plans_before"] == 0
    # a later program is planned with the cached answer, and says so: more
    # memory is in use by now, the record still shows the first trace's
    devices[1].in_use = 9 << 30
    later = sp.plan_overlap(tensors, width=4).as_dict()
    assert later["plans_before"] == 1
    assert later["headroom_probe"] == first["headroom_probe"]
    assert later["headroom_mb"] == first["headroom_mb"]
    ctx = sp.plan_context(sp.ContextWorkload(
        seq_len=4096, num_heads=4, head_dim=64), 4).as_dict()
    assert ctx["headroom_source"] == "probe" and ctx["plans_before"] == 2
    assert ctx["headroom_probe"] == first["headroom_probe"]
    import horovod_tpu as hvd

    assert hvd.context_plan() == ctx and hvd.overlap_plan() == later


def test_a_plan_records_an_env_or_given_headroom(fresh_planner, monkeypatch):
    sp = fresh_planner
    tensors = [np.zeros((64, 64), np.float32)] * 8
    unknown = sp.plan_overlap(tensors, width=4).as_dict()
    assert unknown["headroom_source"] == "probe"      # the CPU keeps no stats
    assert unknown["headroom_probe"] is None and unknown["headroom_mb"] is None
    monkeypatch.setenv("HVD_TPU_DEVICE_HEADROOM_MB", "50")
    from_env = sp.plan_overlap(tensors, width=4).as_dict()
    assert from_env["headroom_source"] == "env"
    assert from_env["headroom_probe"] is None and from_env["plans_before"] == 1
    given = sp.plan_context(sp.ContextWorkload(
        seq_len=4096, num_heads=4, head_dim=64), 4,
        headroom_mb=64.0).as_dict()
    assert given["headroom_source"] == "given"
