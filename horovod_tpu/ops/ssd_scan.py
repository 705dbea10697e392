"""The state-space-dual scan of a Mamba-2 layer, in chunks, as matrix products.

Per head, with state ``H`` [P, N], a scalar decay and one ``B`` / ``C`` pair a
group of heads (arXiv:2405.21060)::

    H_t = exp(dt_t * a) H_{t-1} + dt_t x_t B_t'        y_t = H_t C_t + D x_t

The recurrence is linear, so a chunk of ``Q`` positions is two products:
inside the chunk the masked ``(L o C B')(dt x)`` with
``L[t, s] = exp(sum_{s < r <= t} dt_r a)``, and from the chunks before it the
state they leave, ``C_t H`` decayed to ``t``.  The state a chunk leaves is one
more product (``B`` against the inputs decayed to the chunk's end), and only
the hand-over from chunk to chunk is sequential: ``S / Q`` steps of
elementwise work on [heads, P, N], a ``lax.scan`` whose transpose carries the
state's cotangent backwards across the chunks.  XLA's ops, differentiated by
JAX; no kernel.

Precision, the program's mixed one: ``dt``, the decay sums and every
exponential in float32 (within a chunk the sums stay under ``Q * max(dt * -a)``,
so a difference of two of them keeps its digits; across chunks decays are
multiplied, never subtracted); every product's operands in ``x``'s dtype with
float32 accumulation; the carried state in float32.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32


def carried_state_bytes(heads: int, head_dim: int, state_dim: int) -> int:
    """Bytes of the float32 state one sequence hands from a chunk to the
    next, a layer."""
    return heads * head_dim * state_dim * 4


def ssd_scan(x, dt, a, b, c, d_skip, chunk: int):
    """``x`` [B, S, H, P] (the compute dtype); ``dt`` [B, S, H] float32 and
    positive (after its softplus); ``a`` [H] float32, negative; ``b``, ``c``
    [B, S, G, N] with G dividing H (head h reads group h // (H / G));
    ``d_skip`` [H].  Returns y [B, S, H, P] in ``x``'s dtype.  A length that
    is no multiple of ``chunk`` is padded with ``dt`` = 0: such a position
    neither decays the state nor adds to it, and its output is dropped."""
    bsz, s, h, p = x.shape
    g, n = b.shape[2:]
    if h % g:
        raise ValueError(f"{g} groups of B and C do not divide {h} heads")
    q = min(chunk, s)
    pad = -s % q
    if pad:
        x, dt, b, c = (jnp.pad(v, [(0, 0), (0, pad)] + [(0, 0)] * (v.ndim - 2))
                       for v in (x, dt, b, c))
    nc = (s + pad) // q
    dtype = x.dtype
    # heads as (group, head of the group), chunks as (chunk, position)
    xr = x.reshape(bsz, nc, q, g, h // g, p)
    br, cr = b.reshape(bsz, nc, q, g, n), c.reshape(bsz, nc, q, g, n)
    dtr = dt.astype(F32).reshape(bsz, nc, q, g, h // g)
    # [B, nc, G, Hg, Q]: the decay's exponent summed from the chunk's start
    cum = jnp.cumsum(dtr * a.astype(F32).reshape(g, h // g), axis=2)
    cum = cum.transpose(0, 1, 3, 4, 2)
    dt_t = dtr.transpose(0, 1, 3, 4, 2)

    # inside a chunk: y_t = sum_{s <= t} L[t, s] (C_t . B_s) dt_s x_s
    cb = jnp.einsum("bcqgn,bcsgn->bcgqs", cr, br, preferred_element_type=F32)
    seg = cum[..., :, None] - cum[..., None, :]           # [.., t, s]
    causal = jnp.tril(jnp.ones((q, q), bool))
    mix = (cb[:, :, :, None] * jnp.exp(jnp.where(causal, seg, -jnp.inf))
           * dt_t[..., None, :]).astype(dtype)            # [B,nc,G,Hg,Q,Q]
    y = jnp.einsum("bcghqs,bcsghp->bcqghp", mix, xr,
                   preferred_element_type=F32)

    # the state a chunk adds: its inputs decayed to the chunk's end
    to_end = (jnp.exp(cum[..., -1:] - cum) * dt_t).transpose(0, 1, 4, 2, 3)
    added = jnp.einsum(
        "bcqghp,bcqgn->bcghpn",
        (xr.astype(F32) * to_end[..., None]).astype(dtype), br,
        preferred_element_type=F32)
    chunk_decay = jnp.exp(cum[..., -1])                   # [B, nc, G, Hg]

    def hand_over(state, this):
        decay, new = this
        return state * decay[..., None, None] + new, state

    _, entering = jax.lax.scan(
        hand_over, jnp.zeros((bsz, g, h // g, p, n), F32),
        (chunk_decay.swapaxes(0, 1), added.swapaxes(0, 1)))
    # from the chunks before: C_t . (the entering state decayed to t)
    y = y + jnp.einsum(
        "bcqgn,cbghpn->bcqghp", cr, entering.astype(dtype),
        preferred_element_type=F32) \
        * jnp.exp(cum).transpose(0, 1, 4, 2, 3)[..., None]
    y = y + xr.astype(F32) * d_skip.astype(F32).reshape(g, h // g, 1)
    return y.reshape(bsz, nc * q, h, p)[:, :s].astype(dtype)
