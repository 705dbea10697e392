"""Model-zoo smoke tests for the reference's headline benchmark families.

The reference's published numbers cover Inception V3, ResNet-101, and
VGG-16 (reference README.md:45-51, docs/benchmarks.md:1-7); the models live
in tf_cnn_benchmarks/torchvision there.  These tests pin our in-tree
equivalents: output shapes, canonical channel progressions, a training step
with finite gradients, and the BN-free/BN branch split.
"""

import jax
import jax.numpy as jnp
import optax
import pytest

from horovod_tpu.models import VGG16, InceptionV3, ResNet50


def test_vgg16_forward_shape_and_params():
    model = VGG16(num_classes=10, dtype=jnp.float32)
    x = jnp.ones((2, 32, 32, 3))
    variables = model.init(jax.random.PRNGKey(0), x, train=False)
    assert "batch_stats" not in variables  # classic VGG: no BN
    logits = model.apply(variables, x, train=False)
    assert logits.shape == (2, 10)
    # 13 convs + 2 FC + head = 16 weight layers — the "16" in VGG-16.
    n_kernels = sum(1 for p in jax.tree.leaves_with_path(variables["params"])
                    if p[0][-1].key == "kernel")
    assert n_kernels == 16


def test_vgg16_bn_variant_has_stats():
    model = VGG16(num_classes=4, dtype=jnp.float32, batch_norm=True)
    variables = model.init(jax.random.PRNGKey(0), jnp.ones((1, 32, 32, 3)),
                           train=False)
    assert "batch_stats" in variables


def test_vgg16_train_step_finite_grads():
    model = VGG16(num_classes=4, dtype=jnp.float32, dropout_rate=0.5)
    x = jnp.ones((2, 32, 32, 3))
    y = jnp.zeros((2,), jnp.int32)
    variables = model.init(
        {"params": jax.random.PRNGKey(0), "dropout": jax.random.PRNGKey(1)},
        x, train=True)

    def loss_fn(p):
        logits = model.apply({"params": p}, x, train=True,
                             rngs={"dropout": jax.random.PRNGKey(2)})
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, y).mean()

    loss, grads = jax.value_and_grad(loss_fn)(variables["params"])
    assert jnp.isfinite(loss)
    assert all(jnp.all(jnp.isfinite(g)) for g in jax.tree.leaves(grads))


@pytest.fixture(scope="module")
def inception():
    """One draw of Inception-v3's weights for the two cases that run it,
    made under ``jit``: eagerly, ``init`` compiles and dispatches a program
    for every layer's every operation.  139² is the smallest resolution
    whose 17×17-level grid (7×7 here) survives the aux head's 5×5/3 VALID
    pool."""
    model = InceptionV3(num_classes=4, dtype=jnp.float32, aux_logits=True)
    x = jnp.ones((1, 139, 139, 3))
    return model, x, jax.jit(
        lambda: model.init(jax.random.PRNGKey(0), x, train=True))()


def test_inception_v3_forward_shape(inception):
    # the model as it is built by default, without the aux head (whose
    # weights in the shared draw go unread)
    model = InceptionV3(num_classes=4, dtype=jnp.float32)
    x = jnp.ones((1, 96, 96, 3))  # ≥75×75 minimum; tiny keeps compile fast
    logits = jax.jit(lambda v: model.apply(v, x, train=False, mutable=False))(
        inception[2])
    assert logits.shape == (1, 4)


def test_inception_v3_channel_progression():
    """The stem and mixed blocks must hit the canonical channel counts
    (35×35×256/288, 17×17×768, 8×8×2048) — that IS the architecture."""
    model = InceptionV3(num_classes=10, dtype=jnp.float32)
    x = jnp.ones((1, 299, 299, 3))
    variables = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), x, train=False))
    _, intermediates = jax.eval_shape(
        lambda v: model.apply(v, x, train=False,
                              capture_intermediates=True,
                              mutable=["intermediates"]), variables)
    inter = intermediates["intermediates"]
    assert inter["InceptionA_0"]["__call__"][0].shape == (1, 35, 35, 256)
    assert inter["InceptionA_2"]["__call__"][0].shape == (1, 35, 35, 288)
    assert inter["InceptionC_3"]["__call__"][0].shape == (1, 17, 17, 768)
    assert inter["InceptionE_1"]["__call__"][0].shape == (1, 8, 8, 2048)


def test_inception_v3_aux_head_and_grads(inception):
    model, x, variables = inception
    y = jnp.zeros((1,), jnp.int32)

    def loss_fn(p):
        (logits, aux), _ = model.apply(
            {"params": p, "batch_stats": variables["batch_stats"]}, x,
            train=True, mutable=["batch_stats"])
        ce = optax.softmax_cross_entropy_with_integer_labels
        return ce(logits, y).mean() + 0.4 * ce(aux, y).mean()

    loss, grads = jax.jit(jax.value_and_grad(loss_fn))(variables["params"])
    assert jnp.isfinite(loss)
    assert all(jnp.all(jnp.isfinite(g)) for g in jax.tree.leaves(grads))
    # eval mode returns bare logits (no aux head)
    out = jax.jit(lambda v: model.apply(v, x, train=False, mutable=False))(
        variables)
    assert out.shape == (1, 4)


@pytest.mark.parametrize("cls,size", [(ResNet50, 224)])
def test_resnet_reference_resolution_still_works(cls, size):
    """Guard: the shared harness path (init at 2×size²) stays traceable."""
    model = cls(num_classes=10, dtype=jnp.float32)
    shapes = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0),
                           jnp.zeros((2, size, size, 3)), train=False))
    assert "batch_stats" in shapes


def test_transformer_remat_matches_plain():
    """cfg.remat=True (jax.checkpoint per block — the long-context memory
    trade) must be numerically identical to the plain forward/backward."""
    import numpy as np

    from horovod_tpu.models import Transformer, TransformerConfig

    base = dict(vocab_size=128, num_layers=2, num_heads=2, head_dim=8,
                embed_dim=16, mlp_dim=32, max_seq_len=64, dtype=jnp.float32)
    tok = jnp.asarray(np.random.RandomState(0).randint(0, 128, (2, 32)))
    m1 = Transformer(TransformerConfig(**base))
    m2 = Transformer(TransformerConfig(**base, remat=True))
    p = m1.init(jax.random.PRNGKey(0), tok)
    np.testing.assert_allclose(m1.apply(p, tok), m2.apply(p, tok), atol=1e-6)
    g1 = jax.grad(lambda p: (m1.apply(p, tok) ** 2).sum())(p)
    g2 = jax.grad(lambda p: (m2.apply(p, tok) ** 2).sum())(p)
    for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(g2)):
        # The remat recompute runs under a different fusion schedule, so
        # f32 sums reassociate: grads of magnitude O(1e2) here land within
        # a few 1e-4 of the plain backward on this XLA build, not 1e-5.
        np.testing.assert_allclose(a, b, atol=1e-3, rtol=1e-3)
