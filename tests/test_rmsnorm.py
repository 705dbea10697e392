"""The decoder's RMSNorm (models/transformer.py): numerics against an f32
reference written here, and the parameter paths a checkpoint holds."""

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.models import Transformer, TransformerConfig
from horovod_tpu.models.transformer import RMSNorm


def reference(x, scale, eps):
    """x / sqrt(mean(x^2) + eps) * scale, all in f32."""
    x = np.asarray(x, np.float32)
    rms = np.sqrt(np.mean(x * x, axis=-1, keepdims=True) + eps)
    return x / rms * np.asarray(scale, np.float32)


def reference_grads(x, scale, eps, dy):
    """d/dx and d/dscale of sum(reference * dy), by the closed form."""
    x, dy = np.asarray(x, np.float64), np.asarray(dy, np.float64)
    scale = np.asarray(scale, np.float64)
    inv = 1.0 / np.sqrt(np.mean(x * x, axis=-1, keepdims=True) + eps)
    xhat, g = x * inv, dy * scale
    dx = inv * (g - xhat * np.mean(g * xhat, axis=-1, keepdims=True))
    return dx, np.sum(dy * xhat, axis=tuple(range(x.ndim - 1)))


@pytest.mark.parametrize("width", [256, 200])     # 200: no multiple of 128
@pytest.mark.parametrize("eps", [1e-6, 1e-5])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rmsnorm_forward_and_grad_match_f32_reference(hvd, dtype, eps, width):
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 24, width),
                          jnp.float32) * 3.0
    # small enough that x^2 is of eps's order in some rows: eps matters
    x = x.at[0, :4].multiply(1e-3)
    scale = 1.0 + 0.1 * jax.random.normal(jax.random.PRNGKey(1), (width,))
    dy = jax.random.normal(jax.random.PRNGKey(2), x.shape, jnp.float32)
    mod = RMSNorm(dtype=dtype, epsilon=eps)
    params = {"params": {"scale": scale}}

    y = mod.apply(params, x)
    assert y.dtype == dtype and y.shape == x.shape
    seen = x.astype(dtype)            # what the module computes from
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               reference(seen, scale, eps),
                               rtol=tol, atol=tol)

    def loss(p, x):
        return jnp.sum(mod.apply(p, x).astype(jnp.float32) * dy)

    gp, gx = jax.grad(loss, argnums=(0, 1))(params, x)
    dx, dscale = reference_grads(seen, scale, eps, dy)
    gtol = 1e-4 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(gx), dx, rtol=gtol,
                               atol=gtol * np.abs(dx).max())
    np.testing.assert_allclose(np.asarray(gp["params"]["scale"]), dscale,
                               rtol=gtol, atol=gtol * np.abs(dscale).max())


def test_module_matches_flax_rmsnorm(hvd):
    """RMSNorm ≈ nn.RMSNorm, and the parameter structure is identical (one
    'scale' leaf) so checkpoints interchange."""
    x = jax.random.normal(jax.random.PRNGKey(6), (64, 128), jnp.float32)
    flax_mod = nn.RMSNorm(epsilon=1e-6)
    flax_params = flax_mod.init(jax.random.PRNGKey(7), x)

    mod = RMSNorm()
    params = mod.init(jax.random.PRNGKey(7), x)
    assert jax.tree.structure(params) == jax.tree.structure(flax_params)
    got = mod.apply(flax_params, x)  # flax params drive ours directly
    want = flax_mod.apply(flax_params, x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


# The norm leaves of a checkpoint written before PR 29 (when a class factory
# in ops/ built these modules): the paths must not move.
DENSE_NORMS = ["final_norm/scale", "layer_0/attn_norm/scale",
               "layer_0/mlp_norm/scale", "layer_1/attn_norm/scale",
               "layer_1/mlp_norm/scale"]
QK_NORMS = ["layer_0/attn/k_norm/scale", "layer_0/attn/q_norm/scale",
            "layer_1/attn/k_norm/scale", "layer_1/attn/q_norm/scale"]


@pytest.mark.parametrize("qk_norm,expected", [
    (False, DENSE_NORMS), (True, sorted(DENSE_NORMS + QK_NORMS))])
def test_transformer_norm_parameter_paths(hvd, qk_norm, expected):
    cfg = TransformerConfig(vocab_size=64, num_layers=2, num_heads=2,
                            head_dim=8, embed_dim=16, mlp_dim=32,
                            max_seq_len=8, qk_norm=qk_norm)
    params = jax.eval_shape(Transformer(cfg).init, jax.random.PRNGKey(0),
                            jnp.zeros((1, 8), jnp.int32))["params"]
    leaves = jax.tree_util.tree_flatten_with_path(params)[0]
    paths = {"/".join(k.key for k in path): leaf for path, leaf in leaves}
    norms = sorted(p for p in paths if p.endswith("norm/scale"))
    assert norms == expected
    for p in norms:
        assert paths[p].shape == (16,) and paths[p].dtype == jnp.float32
