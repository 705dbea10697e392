"""The sparse layer as it is trained, and as a share of its experts is
held, compiled for a v5e that is described and not attached
(``tests/test_tpu_structure.py`` has the account and the helpers).  Nothing
executes."""

import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from test_tpu_structure import _kernels_named, one_chip_mesh  # noqa: F401


@pytest.mark.parametrize("t,d,f,e,held,shared,block", [
    (4096, 7168, 2048, 192, 12, 1, 4096),   # A.X-K1's chunk, a sixteenth
    (8192, 4096, 4096, 128, 16, 4, 16384),  # command-a-plus's 8192 bucket
    (512, 4096, 4096, 128, 16, 4, 1024)])   # ... and its shortest
def test_a_share_of_the_experts_keeps_no_array_of_all_its_pairs(
        one_chip_mesh, monkeypatch, t, d, f, e, held, shared, block):
    """A layer that holds a share of the experts walks its held pairs in
    blocks (PR 43): compiled for the chip at the served cells' widths, the
    program holds the block's ``[C, D]`` and ``[C, F]`` rows, the token-sum
    kernel under its name inside one ``while``, and no ``[T * k, D]`` or
    ``[T * k, F]`` array; the same layer with every expert of ``held`` held
    carries them all, as it did, through the same kernels (PR 53)."""
    from horovod_tpu.models import moe
    from horovod_tpu.utils import profiling

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    one_chip = NamedSharding(one_chip_mesh, P())
    k = 8
    assert moe.held_block_rows(t * k, held, e) == block

    def text_of(experts, experts_held):
        m = moe.MoEMLP(embed_dim=d, mlp_dim=f, axis_name=None,
                       dtype=jnp.bfloat16, param_dtype=jnp.bfloat16,
                       num_experts=experts, experts_per_token=k,
                       selection="sigmoid", norm_topk_prob=True,
                       num_shared_experts=shared, experts_held=experts_held)
        x = jax.ShapeDtypeStruct((1, t, d), jnp.bfloat16, sharding=one_chip)
        valid = jax.ShapeDtypeStruct((1, t), jnp.bool_, sharding=one_chip)
        params = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=one_chip),
            jax.eval_shape(lambda: m.init(jax.random.PRNGKey(0),
                                          jnp.zeros((1, t, d), jnp.bfloat16))))
        return jax.jit(lambda p, x, v: m.apply(p, x, valid=v)).lower(
            params, x, valid).compile().as_text()

    all_pairs = re.compile(rf"(?:bf16|f32)\[{t * k},(?:{d}|{f})\]")
    walked = text_of(e, (0, held))
    assert not all_pairs.search(walked)
    assert re.search(rf"bf16\[{block},{d}\]", walked)
    kernels = [line for line in walked.splitlines()
               if 'custom_call_target="tpu_custom_call"' in line
               and profiling.TOKEN_SUM in line]
    assert len(kernels) == 1 and profiling.MOE_COMBINE in kernels[0]
    assert walked.count(" while(") >= 1
    # the walk's products are the grouped kernel (PR 51: gate, up and the
    # activation one call, down another), named under the experts' scope
    # and the layer's path as the token-sum is under the combine's, so a
    # join by module keeps their time in the layer's; XLA's own
    # ``ragged-dot`` kernels are the carried layer's alone
    grouped = _kernels_named(walked, profiling.MOE_GROUPED)
    assert len(grouped) == 2
    path = re.search(r'op_name="([^"]*)' + profiling.TOKEN_SUM,
                     kernels[0]).group(1).split(profiling.MOE_COMBINE)[0]
    assert all(name.startswith(f"{path}{profiling.MOE_EXPERTS}/")
               for name in grouped)
    assert "ragged-dot" not in walked
    carried = text_of(held, None)
    assert all_pairs.search(carried) and profiling.TOKEN_SUM not in carried
    assert "ragged-dot" not in carried
    assert len(_kernels_named(carried, profiling.MOE_GROUPED)) == 2

_OLMOE_LAYER = {}


def _olmoe_training_layer(one_chip_mesh, monkeypatch):
    """OLMoE's expert layer as ``olmoe-s4096`` trains it (64 experts of 2048
    x 1024, top-8, 4 x 4096 tokens: 131072 pairs), its gradient to the
    parameters and the rows compiled for the chip, once a module."""
    from horovod_tpu.models import moe

    if not _OLMOE_LAYER:
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        one_chip = NamedSharding(one_chip_mesh, P())
        d, f, e, k, b, s = 2048, 1024, 64, 8, 4, 4096
        assert moe.grouped_row_tile(b * s * k, e) == moe.WIDE_ROW_TILE == 256
        m = moe.MoEMLP(embed_dim=d, mlp_dim=f, axis_name=None,
                       dtype=jnp.bfloat16, num_experts=e, experts_per_token=k)
        shaped = lambda a: jax.ShapeDtypeStruct(  # noqa: E731
            a.shape, a.dtype, sharding=one_chip)
        params = jax.tree.map(shaped, jax.eval_shape(lambda: m.init(
            jax.random.PRNGKey(0), jnp.zeros((1, 8, d), jnp.bfloat16))))
        x = jax.ShapeDtypeStruct((b, s, d), jnp.bfloat16, sharding=one_chip)
        # (not linear in the layer's result, so that its forward stays)
        loss = lambda p, x: jnp.square(m.apply(  # noqa: E731
            p, x).astype(jnp.float32)).sum()
        _OLMOE_LAYER["compiled"] = jax.jit(
            jax.grad(loss, argnums=(0, 1))).lower(params, x).compile()
    return _OLMOE_LAYER["compiled"]


def test_the_training_layer_compiles_its_products_and_their_backward_as_the_kernel(
        one_chip_mesh, monkeypatch):
    """OLMoE's expert layer as ``olmoe-s4096`` trains it (2048 rows an even
    share, so 256-row tiles), forward and backward compiled for the chip
    (PR 55): six ``hvd_moe_grouped`` calls (gate and up fused: the forward,
    the sum of their rows' gradients, their two weights' gradients; down's
    three) and no ``ragged-dot``; the scope table files each as that kernel
    under the layer's path inside ``hvd_moe_experts``, two forward and four
    backward."""
    from horovod_tpu.utils import profiling

    compiled = _olmoe_training_layer(one_chip_mesh, monkeypatch)
    text = compiled.as_text()
    kernels = _kernels_named(text, profiling.MOE_GROUPED)
    assert len(kernels) == 6 and "ragged-dot" not in text
    assert all(f"/{profiling.MOE_EXPERTS}/" in name for name in kernels)
    ours = [scope for scope in profiling.scope_table(compiled).values()
            if scope.kernel == profiling.MOE_GROUPED]
    assert sorted(scope.phase for scope in ours) \
        == ["backward"] * 4 + ["forward"] * 2
    assert {scope.module for scope in ours} == {
        f"MoEMLP/{profiling.MOE_EXPERTS}/{profiling.MOE_GROUPED}"}


def test_the_training_layer_moves_its_rows_by_kernel_and_gathers_none(
        one_chip_mesh, monkeypatch):
    """The same compiled layer (PR 57): its four row moves are eight
    ``hvd_moe_rows`` calls, Mosaic's at these shapes (a row a tile of 8 x
    128 words): the dispatch's two forward (the tokens as tiles, the fetch)
    and its backward's two (the cotangent rows sent to their pairs' slots,
    the sum over k) under ``hvd_moe_dispatch``, the combine's send and sum
    forward and its backward's two (``gates * dout`` spread with the gates'
    gradient, the fetch into expert order) under ``hvd_moe_combine``; and
    XLA gathers no ``[131072, 2048]`` array any more, nor a ``[131072]``
    one."""
    from horovod_tpu.utils import profiling

    compiled = _olmoe_training_layer(one_chip_mesh, monkeypatch)
    text = compiled.as_text()
    kernels = _kernels_named(text, profiling.MOE_ROWS)
    assert len(kernels) == 8
    ours = [scope for scope in profiling.scope_table(compiled).values()
            if scope.kernel == profiling.MOE_ROWS]
    assert sorted((scope.module, scope.phase) for scope in ours) == sorted(
        (f"MoEMLP/{where}/{profiling.MOE_ROWS}", phase)
        for where, phase in [(profiling.MOE_DISPATCH, "forward"),
                             (profiling.MOE_DISPATCH, "forward"),
                             (profiling.MOE_DISPATCH, "backward"),
                             (profiling.MOE_DISPATCH, "backward"),
                             (profiling.MOE_COMBINE, "forward"),
                             (profiling.MOE_COMBINE, "forward"),
                             (profiling.MOE_COMBINE, "backward"),
                             (profiling.MOE_COMBINE, "backward")])
    assert not re.search(r"\[131072\]\S* gather\(", text)
    assert not re.search(r"\[131072,2048\]\S* gather\(", text)
