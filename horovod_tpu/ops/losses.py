"""Loss ops tuned for the TPU memory system.

``softmax_cross_entropy`` is a reverse-mode drop-in for
``optax.softmax_cross_entropy_with_integer_labels`` for large-vocab LM
heads (forward-mode AD — ``jvp``/``jacfwd``/``hessian`` — is NOT
supported: ``custom_vjp``).  Forward computes logsumexp and the gathered
true-class logit in f32 (full softmax numerics — bf16 logits upcast
inside the fusion, never materialized to HBM at f32); the custom
backward emits the cotangent ``(softmax - onehot)·g`` cast to the logits
dtype, so a bf16 head gets a half-width dlogits tensor and
bf16-eligible downstream matmuls.  The cast costs one bf16 rounding on
probability-scale entries (|d| ≤ 1) — noise below what mixed-precision
backward already carries (accuracy pinned vs optax in
tests/test_losses.py).

Measured honestly (round 3's transformer profile, an earlier chip): at
the 162M/32k-vocab benchmark size this is PERF-NEUTRAL — XLA still
keeps an f32 logits-sized intermediate inside the CE fusion, and the
loss chain overlaps with async DMA, so it sits off the critical path.
The op stands as the numerics-safe way to keep a bf16 cotangent where a
model IS bound by the head chain.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from horovod_tpu.utils import profiling


@jax.custom_vjp
def softmax_cross_entropy(logits, labels):
    """Per-example cross entropy: f32 softmax numerics, logits-dtype
    cotangent.  ``logits``: [..., V] (any float dtype), ``labels``:
    [...] int.  Returns f32 [...] losses (reduce them yourself)."""
    loss, _ = _ce_fwd(logits, labels)
    return loss


@jax.named_scope(profiling.LOSS)
def _ce_fwd(logits, labels):
    lf = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(lf, axis=-1)
    true_logit = jnp.take_along_axis(lf, labels[..., None], axis=-1)[..., 0]
    return lse - true_logit, (logits, lse, labels)


@jax.named_scope(profiling.LOSS)
def _ce_bwd(res, g):
    logits, lse, labels = res
    p = jnp.exp(logits.astype(jnp.float32) - lse[..., None])
    d = p - jax.nn.one_hot(labels, logits.shape[-1], dtype=jnp.float32)
    return (d * g[..., None]).astype(logits.dtype), None


softmax_cross_entropy.defvjp(_ce_fwd, _ce_bwd)
