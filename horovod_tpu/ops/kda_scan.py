"""The gated delta rule of Kimi Delta Attention (KDA; Kimi Linear,
arXiv:2510.26692), a linear attention whose state forgets by a decay a KEY
CHANNEL and learns by the delta rule.  Per head, with a state ``S`` [Dk, Dv]::

    S_t = (I - b_t k_t k_t^T) Diag(a_t) S_{t-1} + b_t k_t v_t^T
    o_t = S_t^T q_t

``a_t = exp(g_t)`` in (0, 1]^Dk (``g`` the log decay, <= 0), ``b_t`` in
[0, 1] a scalar.  Two forms of the same numbers:

* :func:`kda_step`: one position, the recurrence as written (a decode step).
* :func:`kda_chunked`: a sequence, ``chunk`` positions at a time (a prefill,
  a forward pass).  With ``G_r`` the sum of ``g`` over a chunk's positions
  ``<= r`` and ``u_t = b_t (v_t - (Diag(a_t) S_{t-1})^T k_t)`` the value the
  delta rule really writes (``S_t = Diag(a_t) S_{t-1} + k_t u_t^T``), a
  chunk that starts from ``S_0`` has

      (I + A) U = b * V - (b * K * e^G) S_0,
          A[r, i] = b_r sum_c k_rc k_ic e^(G_rc - G_ic)       for i < r
      O = (Q * e^G) S_0 + P U,
          P[r, i] = sum_c q_rc k_ic e^(G_rc - G_ic)           for i <= r
      S_C = Diag(e^G_C) S_0 + (K * e^(G_C - G))^T U

  ``I + A`` is unit lower triangular (the UT / WY transform of the delta
  rule), so ``W = (I + A)^-1 (b * K * e^G)`` and ``(I + A)^-1 (b * V)``
  are made for every chunk at once and only three products a chunk wait
  for the state before it.

**The decays are formed pairwise.**  ``e^(G_r - G_i)`` is at most 1, but its
factors ``e^(G_r)`` and ``e^(-G_i)`` are not representable over a chunk of
64 positions at ``g`` near -5 (``e^(+-320)``).  So a chunk's rows are taken in
sub-blocks of ``sub`` positions, each with a reference point ``ref`` at its
middle: the row side carries ``e^(G_r - ref)``, within ``e^(+-|g| sub / 2)``,
and the column side ``e^(ref - G_i)``, at most that for the columns of the
same sub-block, below 1 for the earlier ones and clamped for the later ones,
which the triangle's mask never reads.  Safe while ``|g| * sub / 2`` stays
under float32's range (``|g| <= 5``, ``sub`` 16: ``e^(+-40)``).

Everything here is float32 and XLA's: ``jax.numpy`` products and one
triangular solve a call.  Forward only is what serving needs; the function
is differentiable as ``jax.numpy`` is, unsparingly.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32
# The scan's own products (all over a chunk's 64 rows): float32 operands in
# full, where XLA:TPU's default would round them to bfloat16 first.  The
# delta rule writes v - S^T k, a difference of like numbers; what the
# rounding costs there is not the model's bfloat16 noise.
PRECISION = jax.lax.Precision.HIGHEST
# the largest exponent the column side may carry (the clamp above)
_CLAMP = 60.0
# positions a chunk of the chunked form, and a sub-block of its decays
CHUNK, SUB = 64, 16


def kda_step(q, k, v, g, beta, state):
    """One position: ``q``, ``k``, ``g`` [B, H, Dk], ``v`` [B, H, Dv],
    ``beta`` [B, H], ``state`` [B, H, Dk, Dv] float32 -> (o [B, H, Dv]
    float32, the new state)."""
    q, k, v, g, beta = (x.astype(F32) for x in (q, k, v, g, beta))
    state = state * jnp.exp(g)[..., None]
    kept = jnp.einsum("bhkv,bhk->bhv", state, k, precision=PRECISION)
    u = beta[..., None] * (v - kept)
    state = state + k[..., None] * u[..., None, :]
    return jnp.einsum("bhkv,bhk->bhv", state, q, precision=PRECISION), state


def kda_recurrent(q, k, v, g, beta, state):
    """:func:`kda_step` over a sequence, a position at a time: the shapes
    of :func:`kda_chunked`.  What the chunked form is held to in the
    tests; nothing serves through it."""
    def one(state, xs):
        o, state = kda_step(*xs, state)
        return state, o

    state, o = jax.lax.scan(one, state.astype(F32), tuple(
        jnp.moveaxis(x, 1, 0) for x in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1), state


def kda_chunked(q, k, v, g, beta, state, chunk: int = CHUNK,
                sub: int = SUB):
    """``q``, ``k``, ``g`` [B, S, H, Dk], ``v`` [B, S, H, Dv], ``beta``
    [B, S, H], ``state`` [B, H, Dk, Dv] -> (o [B, S, H, Dv] float32, the
    state after position S - 1, float32).  ``S`` is padded to whole chunks
    with positions that change nothing (``g`` 0, ``beta`` 0), which is also
    how a caller masks positions of its own: the state then passes them
    unchanged."""
    sub = min(sub, chunk)
    if chunk % sub or sub % 2:
        raise ValueError(f"chunk {chunk} is not whole sub-blocks of {sub} "
                         f"positions with a middle")
    b, s, h, dk = q.shape
    dv = v.shape[-1]
    pad = -s % chunk
    n, m = (s + pad) // chunk, chunk // sub

    def chunks(x):      # [B, S, H, ...] -> [n, B, H, chunk, ...]
        x = x.astype(F32)
        if pad:
            x = jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
        x = x.reshape(b, n, chunk, *x.shape[2:])
        return jnp.moveaxis(x, (1, 0, 3, 2), (0, 1, 2, 3))

    q, k, v, g, beta = (chunks(x) for x in (q, k, v, g, beta))
    beta = beta[..., None]                              # [n, B, H, C, 1]
    big_g = jnp.cumsum(g, axis=-2)                      # G_r, inclusive
    # a sub-block's reference: G at its middle position
    ref = big_g[..., sub // 2 - 1::sub, :]              # [n, B, H, m, Dk]
    by_sub = lambda x: x.reshape(*x.shape[:3], m, sub, x.shape[-1])  # noqa: E731
    row_decay = jnp.exp(by_sub(big_g) - ref[..., None, :])
    col = k[..., None, :, :] * jnp.exp(jnp.minimum(
        ref[..., None, :] - big_g[..., None, :, :], _CLAMP))  # [.., m, C, Dk]
    pairs = lambda rows: jnp.einsum(  # noqa: E731
        "...mrc,...mic->...mri", by_sub(rows) * row_decay, col,
        precision=PRECISION).reshape(*rows.shape[:3], chunk, chunk)
    r = jnp.arange(chunk)
    a = jnp.where(r[:, None] > r[None, :], pairs(k) * beta, 0.0)
    p = jnp.where(r[:, None] >= r[None, :], pairs(q), 0.0)
    decay = jnp.exp(big_g)                              # from the chunk's start
    solved = jax.lax.linalg.triangular_solve(
        a + jnp.eye(chunk, dtype=F32),
        jnp.concatenate([beta * k * decay, beta * v], axis=-1),
        left_side=True, lower=True, unit_diagonal=True)
    w, u_free = solved[..., :dk], solved[..., dk:]
    to_end = jnp.exp(big_g[..., -1:, :] - big_g)        # e^(G_C - G_i) <= 1
    mm = lambda spec, x, y: jnp.einsum(  # noqa: E731
        spec, x, y, precision=PRECISION)

    def one(state, xs):
        w, u_free, q_in, p, k_out, last = xs
        u = u_free - mm("bhck,bhkv->bhcv", w, state)
        o = mm("bhck,bhkv->bhcv", q_in, state) + mm("bhci,bhiv->bhcv", p, u)
        state = state * last[..., None] + mm("bhck,bhcv->bhkv", k_out, u)
        return state, o

    state, o = jax.lax.scan(one, state.astype(F32), (
        w, u_free, q * decay, p, k * to_end, decay[..., -1, :]))
    # [n, B, H, C, Dv] -> [B, S, H, Dv]
    o = jnp.moveaxis(o, (1, 0, 3, 2), (0, 1, 2, 3))
    return o.reshape(b, n * chunk, h, dv)[:, :s], state
