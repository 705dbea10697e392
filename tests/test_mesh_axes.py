"""Extensibility: extra model-parallel mesh axes must not change collective
semantics (data-axis width, not total device count, is the denominator).
The reference has no model parallelism (SURVEY §2.9); these tests pin down
the contract that our mesh design leaves room for it."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P


@pytest.fixture()
def hvd_tp2():
    import horovod_tpu as hvd

    hvd.shutdown()
    hvd.init(mesh_axes={"tp": 2})
    yield hvd
    hvd.shutdown()
    hvd.init()


def test_average_uses_data_width(hvd_tp2):
    hvd = hvd_tp2
    assert dict(hvd.global_mesh().shape) == {"hvd": 4, "tp": 2}
    x = jnp.ones((4, 2, 3))

    fn = hvd.shard(lambda v: hvd.allreduce(v, average=True),
                   in_specs=P("hvd", "tp"), out_specs=P("hvd", "tp"))
    out = np.asarray(fn(x))
    # average over the 4-wide data axis of all-ones must be exactly 1.0
    np.testing.assert_allclose(out, np.ones((4, 2, 3)), rtol=1e-6)


def test_mesh_rebuild_conflict_errors(hvd_tp2):
    from horovod_tpu import mesh

    with pytest.raises(RuntimeError, match="already built"):
        mesh.build_global_mesh({"pp": 4})
    # matching request is fine
    m = mesh.build_global_mesh({"tp": 2})
    assert m is mesh.global_mesh()


def test_custom_axis_name_gets_in_mesh_semantics(hvd):
    """A shard_map over a user's own mesh — single axis with a custom name —
    must reduce over that bound axis, not fall back to eager process-level
    semantics.  Pins the `_bound_axis_names` contract so private-JAX-API
    drift (jax._src.core.get_axis_env) is caught loudly (advisor round 1)."""
    from jax.sharding import Mesh

    devs = np.array(jax.devices()[:4])
    custom = Mesh(devs, ("workers",))
    x = jnp.ones((4, 3), jnp.float32)

    fn = jax.shard_map(lambda v: hvd.allreduce(v, average=False),
                       mesh=custom, in_specs=P("workers"),
                       out_specs=P("workers"))
    out = np.asarray(jax.jit(fn)(x))
    # sum over the 4-wide custom axis of all-ones must be exactly 4.0
    np.testing.assert_allclose(out, np.full((4, 3), 4.0), rtol=1e-6)


def test_bound_axis_names_fallback_probes_custom_mesh(hvd, monkeypatch):
    """Force the private-API path to fail and verify the fallback still
    discovers a bound custom axis via the active physical mesh."""
    from jax.sharding import Mesh
    from horovod_tpu.ops import collective_ops

    def boom():
        raise AttributeError("simulated private-API drift")

    monkeypatch.setattr(collective_ops, "_private_axis_env_names", boom)

    devs = np.array(jax.devices()[:4])
    custom = Mesh(devs, ("workers",))
    x = jnp.ones((4, 3), jnp.float32)
    with custom:
        fn = jax.shard_map(
            lambda v: collective_ops.allreduce(v, average=False),
            mesh=custom, in_specs=P("workers"), out_specs=P("workers"))
        out = np.asarray(jax.jit(fn)(x))
    np.testing.assert_allclose(out, np.full((4, 3), 4.0), rtol=1e-6)
