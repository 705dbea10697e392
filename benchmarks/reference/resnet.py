"""Plain reference for the ``resnet`` family: ResNet-50 v1.5 (He et al.,
"Deep Residual Learning for Image Recognition", with the stride of a
down-sampling bottleneck on its 3x3 convolution, as torchvision and the
MLPerf reference have it), forward in training mode, loss and gradients.

``jax.numpy``/``jax.lax`` in float32 at "highest" matmul precision, NHWC,
nothing imported from the program under test.  Batch normalisation uses
the statistics of the batch it is given (biased variance, eps 1e-5).

Parameter layout (the reference's own)::

    {"conv1": [7, 7, 3, 64], "bn1": {"scale", "bias"},
     "stages": [[{"conv1", "bn1", "conv2", "bn2", "conv3", "bn3",
                  "down_conv"?, "down_bn"?}, ...] x 3, 4, 6, 3],
     "fc": {"kernel": [2048, classes], "bias": [classes]}}

Departure from the published description, taken over from the program so
that the two compute one function: a strided convolution and the max-pool
pad as XLA's "SAME" does (total padding split low = total // 2, the odd
pixel at the high end), where torchvision pads symmetrically; the two
differ by a one-pixel shift of the sampling grid, not in any size.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

BN_EPS = 1e-5


def conv(x, w, stride=1):
    return jax.lax.conv_general_dilated(
        x, w, (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))


def batch_norm(x, p):
    mean = jnp.mean(x, axis=(0, 1, 2))
    var = jnp.mean(jnp.square(x - mean), axis=(0, 1, 2))
    return (x - mean) * jax.lax.rsqrt(var + BN_EPS) * p["scale"] + p["bias"]


def bottleneck(x, p, stride):
    y = jax.nn.relu(batch_norm(conv(x, p["conv1"]), p["bn1"]))
    y = jax.nn.relu(batch_norm(conv(y, p["conv2"], stride), p["bn2"]))
    y = batch_norm(conv(y, p["conv3"]), p["bn3"])
    if "down_conv" in p:
        x = batch_norm(conv(x, p["down_conv"], stride), p["down_bn"])
    return jax.nn.relu(x + y)


def logits(params, images):
    with jax.default_matmul_precision("highest"):
        x = jax.nn.relu(batch_norm(conv(images, params["conv1"], 2),
                                   params["bn1"]))
        x = jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, (1, 3, 3, 1),
                                  (1, 2, 2, 1), "SAME")
        for i, stage in enumerate(params["stages"]):
            for j, block in enumerate(stage):
                x = bottleneck(x, block, 2 if i > 0 and j == 0 else 1)
        x = jnp.mean(x, axis=(1, 2))
        return x @ params["fc"]["kernel"] + params["fc"]["bias"]


def loss(params, images, labels):
    logp = jax.nn.log_softmax(logits(params, images), axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], -1))


def loss_and_grads(params, images, labels):
    return jax.value_and_grad(loss)(params, images, labels)
