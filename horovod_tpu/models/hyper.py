"""Manifold-constrained hyper-connections (mHC; DeepSeek-AI, "mHC:
Manifold-Constrained Hyper-Connections"), ``TransformerConfig.hyper_streams``:
the residual stream of a position is not one vector but ``n`` of them, ``X``
[n, C], and each of a layer's two sublayers ``F`` (the mixer, the
feed-forward, each with the norm it has in every other model) reads one
mix of the rows and writes into another, under coefficients made from the
stream itself.  A sublayer owns ``phi_pre``, ``phi_post`` [nC, n],
``phi_res`` [nC, n^2], ``b_pre``, ``b_post`` [n], ``b_res`` [n, n] and three
scalars ``alpha`` and computes, in float32::

    x~     = vec(X) / sqrt(mean(vec(X)^2) + eps)      all nC values, no scale
    H_pre  = sigmoid(alpha_pre (x~ phi_pre) + b_pre)                    [n]
    H_post = 2 sigmoid(alpha_post (x~ phi_post) + b_post)               [n]
    M      = exp(clip(alpha_res mat(x~ phi_res) + b_res, lo, hi))       [n, n]
    iters times:  M = M / (colsum(M) + eps);  M = M / (rowsum(M) + eps)
    H_res  = M                                         (Sinkhorn: near doubly
                                                       stochastic, rows exact)
    h      = sum_j H_pre[j] X[j]                       what the sublayer reads
    y      = F(norm(h))
    X'[i]  = sum_j H_res[i, j] X[j] + H_post[i] y      what goes on

The model copies a token's embedding into every row before its first layer
and sums the rows after its last (``models/transformer.py``).  Everything
here is position-wise: a served prefill runs it over the prompt's own row
blocks, a cache call on the block's positions.

Parameters, a sublayer (the three ``phi`` side by side, pre | post | res,
the biases likewise, so that a position's coefficients are one product)::

    phi [n, C, n (n + 2)],  bias [n (n + 2)],  alpha [3]

How it is laid out for the chip.  ``x~ phi`` is taken as ``(vec(X) phi) /
rms``: the stream and ``phi`` meet in the stream's dtype with float32
accumulation, which for a bfloat16 stream is exact in every product, and no
float32 copy of the stream is made.  The Sinkhorn loop runs on ``M`` laid
out [n, n, positions], the positions in the lanes: a reduction over a minor
axis of 4 would leave 124 lanes of 128 idle.  Its ``2 iters`` normalisations
a sublayer are a chain of small dependent programs: in a decode step
latency, not work.  (Written out entry by entry, 16 arrays a position, the
chain fused into a handful of programs, and a decode program of eight
layers took XLA:TPU 123 s to compile, a CPU test minutes: PERF.md section 6,
PR 59.)  The two mixes are written as their ``n`` and ``n^2`` scaled adds
over rows of the stream, not as a product batched over positions: 4 x 4
matrices are no work for the MXU.  A sublayer's coefficients travel as one
array [B, S, n (n + 2)], pre | post | res row by row.

Scopes (``utils/profiling``): ``hvd_mhc_coef`` (the flat norm, the
projection, the sigmoids), ``hvd_mhc_sinkhorn``, ``hvd_mhc_pre`` (h),
``hvd_mhc_post`` (X').  :class:`HyperConnection` also hands back the largest
``|colsum(H_res) - 1|`` it met (the rows are normalised last and are exact,
the columns are what the iterations leave open); ``Block`` sows it under
:data:`MHC_STATS`.
"""

from __future__ import annotations

from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from horovod_tpu.utils import profiling

F32 = jnp.float32
MHC_STATS = "mhc_stats"     # "col_sum_err": float32, a layer's largest


def spread(x, n: int):
    """The stream's start: ``x`` [B, S, C] in each of ``n`` rows."""
    return jnp.broadcast_to(x[:, :, None, :],
                            x.shape[:2] + (n,) + x.shape[2:])


def gathered(x):
    """The stream's end: its rows summed, [..., n, C] -> [..., C]."""
    return jnp.sum(x.astype(F32), axis=-2).astype(x.dtype)


def sinkhorn(logits, iters: int, eps: float, clamp: tuple):
    """``logits`` [n, n, T] (row i, column j, a position) -> H_res alike:
    exp of the clamped logits, then ``iters`` times the columns and the
    rows divided by their sums, columns first."""
    with jax.named_scope(profiling.MHC_SINKHORN):
        m = jnp.exp(jnp.clip(logits, *clamp))
        for _ in range(iters):
            m = m / (jnp.sum(m, axis=0, keepdims=True) + eps)
            m = m / (jnp.sum(m, axis=1, keepdims=True) + eps)
        return m


def column_error(m):
    """The largest ``|colsum - 1|`` of ``m`` [n, n, T], a position: [T]."""
    return jnp.max(jnp.abs(jnp.sum(m, axis=0) - 1.0), axis=0)


def pre_mix(coef, x):
    """h = sum_j H_pre[j] X[j]: ``coef`` [B, S, n (n + 2)] float32
    (:class:`HyperConnection`'s), ``x`` [B, S, n, C] -> [B, S, C] in the
    stream's dtype."""
    with jax.named_scope(profiling.MHC_PRE):
        return sum(coef[..., j, None] * x[:, :, j].astype(F32)
                   for j in range(x.shape[2])).astype(x.dtype)


def post_mix(coef, x, y):
    """X'[i] = sum_j H_res[i, j] X[j] + H_post[i] y: ``coef`` [B, S, n (n +
    2)] float32, ``x`` [B, S, n, C], ``y`` [B, S, C] -> [B, S, n, C] in the
    stream's dtype."""
    n = x.shape[2]
    with jax.named_scope(profiling.MHC_POST):
        rows = [x[:, :, j].astype(F32) for j in range(n)]
        y = y.astype(F32)
        return jnp.stack(
            [sum(coef[..., (2 + i) * n + j, None] * rows[j] for j in range(n))
             + coef[..., n + i, None] * y for i in range(n)],
            axis=2).astype(x.dtype)


# the standard deviation phi is initialised at (a served model's weights are
# the benchmark's own draw)
INIT_RANGE = 0.02


class HyperConnection(nn.Module):
    """One sublayer's coefficients: ``x`` [B, S, n, C] -> (H_pre | H_post |
    H_res row by row, side by side [B, S, n (n + 2)] float32, the largest
    column error of the rows [B, 1])."""

    streams: int
    sinkhorn_iters: int = 20
    eps: float = 1e-6
    res_clamp: tuple = (-30.0, 30.0)
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        b, s, n, c = x.shape
        if n != self.streams:
            raise ValueError(f"a stream of {n} rows; hyper_streams is "
                             f"{self.streams}")
        wide = n * (n + 2)
        phi = self.param("phi", nn.initializers.normal(INIT_RANGE),
                         (n, c, wide), self.param_dtype)
        bias = self.param("bias", nn.initializers.zeros, (wide,),
                          self.param_dtype).astype(F32)
        alpha = self.param("alpha", nn.initializers.ones, (3,),
                           self.param_dtype).astype(F32)
        with jax.named_scope(profiling.MHC_COEF):
            xf = x.astype(F32)
            inv = jax.lax.rsqrt(jnp.mean(xf * xf, axis=(2, 3)) + self.eps)
            # (vec(X) phi) / rms: the products in the stream's dtype,
            # summed in float32 (exact for a bfloat16 stream)
            proj = jnp.einsum("bsjc,jcp->bsp", x, phi.astype(x.dtype),
                              preferred_element_type=F32,
                              precision=jax.lax.Precision.HIGHEST)
            proj = proj * inv[..., None] * jnp.repeat(
                alpha, np.array([n, n, n * n]),
                total_repeat_length=wide) + bias
            pre = jax.nn.sigmoid(proj[..., :n])
            post = 2.0 * jax.nn.sigmoid(proj[..., n:2 * n])
        # positions last, in the lanes: [n, n, T]
        m = sinkhorn(proj[..., 2 * n:].reshape(b * s, n, n).transpose(
            1, 2, 0), self.sinkhorn_iters, self.eps, self.res_clamp)
        with jax.named_scope(profiling.MHC_SINKHORN):
            err = jnp.max(column_error(m).reshape(b, s), axis=1,
                          keepdims=True)
            res = m.transpose(2, 0, 1).reshape(b, s, n * n)
        return jnp.concatenate([pre, post, res], axis=-1), err
