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

One algorithm in two forms, chosen by shape alone (:func:`scan_form`; no
argument, environment name or model name chooses), float32 throughout and
every product's operands in full (``PRECISION``):

``"kernel"``: one Pallas kernel (``profiling.KDA_CHUNK``; Mosaic on a TPU,
interpret mode elsewhere).  A program is one (sequence, block of heads,
chunk); the chunk axis is last and sequential, and each head's state lives in
VMEM scratch across it: it enters from the ``state`` operand at the first
chunk and leaves at the last.  The cumulative decay, the pairwise decays,
``A``, ``P`` and the inverse of ``I + A`` are formed in VMEM, used and
dropped: no [chunk, chunk] matrix reaches HBM.  ``I + A`` is inverted by
halves, as products: with ``T`` its inverse over diagonal blocks of w rows
and ``E`` what ``A`` holds between the halves of a block of 2 w, the inverse
over blocks of 2 w is ``T - (T E) T`` (the recursion of a blocked triangular
inverse, from blocks of 2 rows, where it is ``I - A``, to the chunk).  The
tiling rule: keys and values one 128-lane tile wide and an even number of
heads, what the chip's compiler takes within Mosaic's default 16 MiB of VMEM
(``tests/test_tpu_structure.py``).  Differentiated through a ``custom_vjp``
whose backward is JAX's of the other form (no cell trains through the scan).

``"xla"``: the same chunked form as ``jax.numpy`` products, one triangular
solve a call and a ``lax.scan`` handing the state on; for shapes outside the
rule (tiny test models, odd widths), and what the tests hold the kernel to.
"""

from __future__ import annotations

import functools
import itertools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from horovod_tpu.utils import profiling

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
# the kernel's rule: the lanes of a vreg, which is the width of key and value
# it takes, and the most heads a program (eight measured no slower than
# sixteen and a sixth faster than four: the pairs' chains of products overlap)
_TILE, _BLOCK_HEADS = 128, 8
_NN = (((1,), (0,)), ((), ()))      # a b
_NT = (((1,), (1,)), ((), ()))      # a b'
_TN = (((0,), (0,)), ((), ()))      # a' b


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


def scan_form(heads: int, dk: int, dv: int, chunk: int = CHUNK,
              sub: int = SUB) -> str:
    """``"kernel"`` or ``"xla"``: the form :func:`kda_chunked` runs at these
    shapes (what ``kda.kda_plan`` reports is this function's answer).  The
    kernel's tiling rule: keys and values one 128-lane tile wide, chunks of
    64 in sub-blocks of 16, and an even number of heads, which it takes two
    at a time."""
    return "kernel" if dk == dv == _TILE and (chunk, sub) == (CHUNK, SUB) \
        and heads % 2 == 0 else "xla"


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
    s = q.shape[1]
    pad = -s % chunk
    q, k, v, g, beta, state = (x.astype(F32) for x in (q, k, v, g, beta,
                                                       state))
    if pad:
        q, k, v, g, beta = (
            jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
            for x in (q, k, v, g, beta))
    if scan_form(q.shape[2], q.shape[3], v.shape[3], chunk, sub) == "kernel":
        o, state = _scan_kernel(q, k, v, g, beta, state)
    else:
        o, state = _scan_xla(q, k, v, g, beta, state, chunk, sub)
    return o[:, :s], state


def _scan_xla(q, k, v, g, beta, state, chunk: int = CHUNK, sub: int = SUB):
    """The chunked form as XLA's ops, a ``lax.scan`` handing the state on:
    whole chunks, float32 in."""
    b, s, h, dk = q.shape
    dv = v.shape[-1]
    n, m = s // chunk, chunk // sub

    def chunks(x):      # [B, S, H, ...] -> [n, B, H, chunk, ...]
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

    state, o = jax.lax.scan(one, state, (
        w, u_free, q * decay, p, k * to_end, decay[..., -1, :]))
    # [n, B, H, C, Dv] -> [B, S, H, Dv]
    o = jnp.moveaxis(o, (1, 0, 3, 2), (0, 1, 2, 3))
    return o.reshape(b, s, h, dv), state


# -- the kernel ---------------------------------------------------------------
# A program is one (sequence, block of heads, chunk).  It takes its heads two
# at a time, their chunks' rows one under the other: 128 rows, so every
# [rows, rows] matrix of the chunked form (A, P, the inverse of I + A) is one
# whole [128, 128] tile, block diagonal with a block a head, and one product
# serves both heads.  A product that crosses the two heads is computed and
# dropped by the block's mask (a select: what it drops may be inf).

def _mm(a, b, dims=_NN):
    return jax.lax.dot_general(a, b, dims, precision=PRECISION,
                               preferred_element_type=F32)


def _side_by_side(x, b: int):
    """``x`` [rows, rows], block diagonal in blocks of ``b`` rows, as its
    blocks side by side: [b, rows], block j in the lanes it had."""
    return functools.reduce(
        jnp.add, [x[j:j + b] for j in range(0, x.shape[0], b)])


def _chunk_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, s0_ref, o_ref, s_ref,
                  state):
    """One chunk of one block of heads.  ``state`` [heads, Dv, Dk] holds
    each head's state TRANSPOSED, a key channel a lane as ``g`` has it, so
    the chunk's decay of the state is a row spread over sublanes."""
    heads, dv, dk = state.shape
    chunk, m = CHUNK, CHUNK // SUB
    rows = 2 * chunk
    at_chunk = pl.program_id(2)

    @pl.when(at_chunk == 0)
    def _first_chunk():
        for h in range(heads):
            state[h] = s0_ref[0, h].T

    r = jax.lax.broadcasted_iota(jnp.int32, (rows, rows), 0)
    i = jax.lax.broadcasted_iota(jnp.int32, (rows, rows), 1)
    # r and i in one block of ``w`` rows (a power of two)
    same = lambda w: (r ^ i) < w  # noqa: E731
    below, causal = same(chunk) & (r > i), same(chunk) & (r >= i)
    eye = jnp.where(r == i, 1.0, 0.0).astype(F32)
    ones = jnp.where(causal, 1.0, 0.0).astype(F32)
    # the inverse's levels: (b, A's part between the halves of a block of
    # 2 w, r and i in one block of b), b the rows a level's products take
    levels, w = [], 2
    while w < chunk:
        b = max(2 * w, 8)
        levels.append((b, same(2 * w) & ~same(w), same(b)))
        w *= 2
    spread = lambda row, n: jnp.broadcast_to(row, (n, row.shape[1]))  # noqa: E731
    under = lambda parts: jnp.concatenate(parts, axis=0)  # noqa: E731

    def one_pair(of):
        both = lambda ref, d: under(  # noqa: E731
            [ref[0, :, h * d:(h + 1) * d] for h in of])
        q, k, g = both(q_ref, dk), both(k_ref, dk), both(g_ref, dk)
        v = both(v_ref, dv)
        beta = under([beta_ref[0, 0, :, h:h + 1] for h in of])  # [rows, 1]
        # G_r, inclusive, a head a block of the triangle of ones
        big_g = _mm(ones, g)
        yield
        mid = lambda j: big_g[j * SUB + SUB // 2 - 1:j * SUB + SUB // 2]  # noqa: E731
        # each row's own sub-block's reference
        ref = under([spread(mid(j), SUB) for j in range(2 * m)])
        row_decay = jnp.exp(big_g - ref)
        kr, qr = k * row_decay, q * row_decay
        a_rows, p_rows = [[None] * (2 * m) for _ in range(2)]
        for j in range(m):
            # sub-block j of both heads against every column of its head
            ref_j = under([spread(mid(j), chunk), spread(mid(m + j), chunk)])
            col = k * jnp.exp(jnp.minimum(ref_j - big_g, _CLAMP))
            lo, hi = j * SUB, (m + j) * SUB
            out = _mm(under([kr[lo:lo + SUB], qr[lo:lo + SUB],
                             kr[hi:hi + SUB], qr[hi:hi + SUB]]), col, _NT)
            a_rows[j], p_rows[j] = out[:SUB], out[SUB:2 * SUB]
            a_rows[m + j], p_rows[m + j] = out[2 * SUB:3 * SUB], out[3 * SUB:]
            yield
        a = jnp.where(below, under(a_rows) * beta, 0.0)
        p = jnp.where(causal, under(p_rows), 0.0)
        # (I + A)^-1, block by block of the diagonal: with T the inverse
        # over blocks of w rows and E what A holds between the two halves
        # of a block of 2 w, (T E)^2 = 0 and the inverse over blocks of
        # 2 w is T - (T E) T.  Blocks of 2 rows: I - A there.  A product
        # X W of two matrices block diagonal in blocks of b rows is taken
        # with X's blocks side by side, [b, rows]: b rows through the MXU,
        # not all of them.
        inv = eye - jnp.where(same(2), a, 0.0)
        for b, halves, block in levels:
            side = _side_by_side(inv, b)
            across = _mm(side, jnp.where(halves, a, 0.0))
            yield
            side = side - _mm(across, inv)
            yield
            inv = jnp.where(block, under([side] * (rows // b)), 0.0)
        decay = jnp.exp(big_g)                          # from the chunk's start
        k_in, q_in, v_in = beta * k * decay, q * decay, beta * v
        last = [big_g[(t + 1) * chunk - 1:(t + 1) * chunk] for t in range(2)]
        k_out = k * jnp.exp(under([spread(x, chunk) for x in last]) - big_g)
        rest, from_state = [], []
        for t, h in enumerate(of):
            mine = slice(t * chunk, (t + 1) * chunk)
            read = _mm(under([k_in[mine], q_in[mine]]), state[h], _NT)
            rest.append(v_in[mine] - read[:chunk])
            from_state.append(read[chunk:])
            yield
        u = _mm(inv, under(rest))
        yield
        o = under(from_state) + _mm(p, u)
        yield
        for t, h in enumerate(of):
            mine = slice(t * chunk, (t + 1) * chunk)
            o_ref[0, :, h * dv:(h + 1) * dv] = o[mine]
            state[h] = state[h] * jnp.exp(last[t]) \
                + _mm(u[mine], k_out[mine], _TN)
            yield

    # The pairs of a program in step, a product of each in turn: one
    # pair's products are a chain, each waiting for the one before, and
    # the compiler keeps the order it is given.
    for _ in itertools.zip_longest(
            *(one_pair((2 * n, 2 * n + 1)) for n in range(heads // 2))):
        pass

    @pl.when(at_chunk == pl.num_programs(2) - 1)
    def _last_chunk():
        for h in range(heads):
            s_ref[0, h] = state[h].T


def head_block(heads: int) -> int:
    """Heads a program: the largest even count up to ``_BLOCK_HEADS`` that
    divides ``heads``."""
    return next(n for n in range(min(heads, _BLOCK_HEADS), 0, -1)
                if heads % n == 0 and n % 2 == 0)


# Jitted on its own: the kernel's body is a few thousand operations to trace
# and lower, and every layer of a model then shares one tracing.
@jax.jit
def _forward(q, k, v, g, beta, state):
    b, s, h, dk = q.shape
    dv = v.shape[-1]
    heads, n = head_block(h), s // CHUNK
    spec = pl.BlockSpec
    wide = lambda d: spec((1, CHUNK, heads * d),  # noqa: E731
                          lambda bi, hi, ci: (bi, ci, hi))
    held = spec((1, heads, dk, dv), lambda bi, hi, ci: (bi, hi, 0, 0))
    flat = lambda x: x.reshape(b, s, -1)  # noqa: E731
    o, state = pl.pallas_call(
        _chunk_kernel,
        grid=(b, h // heads, n),
        in_specs=[wide(dk), wide(dk), wide(dv), wide(dk),
                  spec((1, 1, CHUNK, heads),
                       lambda bi, hi, ci: (bi, hi, ci, 0)), held],
        out_specs=(wide(dv), held),
        out_shape=(jax.ShapeDtypeStruct((b, s, h * dv), F32),
                   jax.ShapeDtypeStruct(state.shape, F32)),
        scratch_shapes=[pltpu.VMEM((heads, dv, dk), F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=jax.default_backend() != "tpu",
        name=profiling.KDA_CHUNK,
    )(flat(q), flat(k), flat(v), flat(g),
      # [B, S, H] -> [B, blocks, S, heads]: a program's heads a lane each
      beta.reshape(b, s, h // heads, heads).transpose(0, 2, 1, 3), state)
    return o.reshape(b, s, h, dv), state


@jax.custom_vjp
def _scan_kernel(q, k, v, g, beta, state):
    return _forward(q, k, v, g, beta, state)


def _scan_kernel_fwd(*args):
    return _forward(*args), args


def _scan_kernel_bwd(args, cotangents):
    # no cell trains through the scan: the backward is JAX's, of the XLA form
    return jax.vjp(_scan_xla, *args)[1](cotangents)


_scan_kernel.defvjp(_scan_kernel_fwd, _scan_kernel_bwd)
