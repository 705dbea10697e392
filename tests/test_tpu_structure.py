"""Structure of programs compiled for a TPU v5e that is described, not
attached (``jax.experimental.topologies``): what the chip's own compiler
makes of a step, read off its optimized text.  Nothing executes.  The one
file of the suite that loads the TPU compiler: keep such tests here, and
the topology inside the fixture (a module that describes it while being
imported gives pytest-xdist's workers different tests to collect)."""

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
    VMEM (8 MiB at S=16384, 16 at S=32768): past Mosaic's 16 MiB default,
    so the call asks for its own limit.  The chip's compiler takes the
    kernel at the benchmark's three geometries and at S=32768 (H=16,
    d=128, bf16, default tiles) and makes one custom call of it."""
    import importlib

    from horovod_tpu.utils import profiling
    fa = importlib.import_module("horovod_tpu.ops.flash_attention")

    one_chip = NamedSharding(one_chip_mesh, P())
    x = jax.ShapeDtypeStruct((b, s, 16, 128), jnp.bfloat16, sharding=one_chip)
    stat = jax.ShapeDtypeStruct((b, s, 16), jnp.float32, sharding=one_chip)

    def backward(q, k, v, do, lse, delta):
        return fa.flash_attention_backward(
            q, k, v, do, lse, delta, True, 0, 0, 1024,
            fa._default_block_k(s, 128), False)

    text = jax.jit(backward).lower(x, x, x, x, stat, stat).compile().as_text()
    kernels = [line for line in text.splitlines()
               if 'custom_call_target="tpu_custom_call"' in line]
    assert len(kernels) == 1 and profiling.FLASH_BWD in kernels[0]
