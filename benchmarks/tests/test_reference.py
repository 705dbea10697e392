"""The plain references against the program's models at a tiny size.

The program's model runs in float32 with dense attention here, so the two
sides differ by summation order alone and must agree closely; the bf16
tolerances of the chip comparison live in ``benchmarks/families``.
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from benchmarks import compare
from benchmarks.run import load_module as load


@pytest.fixture(scope="module")
def hvd():
    import horovod_tpu as hvd

    hvd.init()
    yield hvd
    hvd.shutdown()


LM = dict(hidden_size=64, intermediate_size=160, num_attention_heads=4,
          num_key_value_heads=4, num_hidden_layers=2,
          max_position_embeddings=256, rms_norm_eps=1e-6, rope_theta=100000,
          rope_scaling={"factor": 4.0, "type": "linear"}, vocab_size=97,
          tie_word_embeddings=False)


def test_decoder_reference_matches_models_transformer(hvd):
    import dataclasses

    from horovod_tpu.models import Transformer

    family, reference = load("families", "decoder_lm"), \
        load("reference", "decoder_lm")
    mcfg = dataclasses.replace(
        family.model_config(LM, {"remat": False}), dtype=jnp.float32,
        logits_dtype=jnp.float32, attention_fn=None)
    model = Transformer(mcfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (1, 96), 0, 97)
    params = model.init(jax.random.PRNGKey(0), tokens)
    positions = jnp.arange(96, dtype=jnp.float32) / 4.0

    def loss_fn(p):
        logits = model.apply(p, tokens, positions=positions)
        return optax.softmax_cross_entropy_with_integer_labels(
            logits[:, :-1], tokens[:, 1:]).mean()

    with jax.default_matmul_precision("highest"):
        loss, grads = jax.value_and_grad(loss_fn)(params)
        logits = model.apply(params, tokens, positions=positions)[0, -16:]
    ref_params = family.to_reference(params, LM)
    ref_loss, ref_grads = reference.loss_and_grads(ref_params, tokens[0], LM)
    assert float(loss) == pytest.approx(float(ref_loss), rel=1e-5)
    got = compare.check_tree("grads", family.to_reference(grads, LM),
                             ref_grads, 1e-4)
    assert got["ok"], got
    # the long-context path: queries in blocks against the whole context
    blocked = reference.logits_last(ref_params, tokens[0], LM, last=16,
                                    query_block=32)
    assert compare.relative_l2(logits, blocked) < 1e-5


def test_resnet_reference_matches_models_resnet(hvd):
    from horovod_tpu.models import ResNet50

    family, reference = load("families", "resnet"), load("reference", "resnet")
    model = ResNet50(num_classes=10, dtype=jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (8, 64, 64, 3))
    y = jnp.arange(8) % 10
    v = model.init(jax.random.PRNGKey(0), x, train=True)
    # as the chip comparison does: the zero-initialised last scale of every
    # block would zero most gradients
    params = jax.tree_util.tree_map_with_path(
        lambda p, leaf: jnp.where(jnp.all(leaf == 0), family.REDRAWN_SCALE,
                                  leaf)
        if p[-1].key == "scale" else leaf, v["params"])

    def apply(p):
        return model.apply({"params": p, "batch_stats": v["batch_stats"]},
                           x, train=True, mutable=["batch_stats"])[0]

    def loss_fn(p):
        return optax.softmax_cross_entropy_with_integer_labels(
            apply(p), y).mean()

    with jax.default_matmul_precision("highest"):
        loss, grads = jax.value_and_grad(loss_fn)(params)
        logits = apply(params)
    ref_params = family.to_reference(params)
    ref_loss, ref_grads = reference.loss_and_grads(ref_params, x, y)
    # forward: the same function to float32's rounding
    assert compare.relative_l2(
        logits, reference.logits(ref_params, x)) < 1e-3
    assert float(loss) == pytest.approx(float(ref_loss), rel=1e-5)
    # backward: 53 batch norms amplify float32's rounding to the percent
    # level on eight 64 x 64 images (the reference in f32 differs from
    # itself in f64 by 0.6%); a wrong term would be of order one
    got = compare.check_tree("grads", family.to_reference(grads), ref_grads,
                             0.06)
    assert got["ok"], got
    assert got["median_leaf_error"] < 0.03
