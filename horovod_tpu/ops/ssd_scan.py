"""The state-space-dual scan of a Mamba-2 layer, in chunks, as matrix products.

Per head, with state ``H`` [P, N], a scalar decay and one ``B`` / ``C`` pair a
group of heads (arXiv:2405.21060)::

    H_t = exp(dt_t * a) H_{t-1} + dt_t x_t B_t'        y_t = H_t C_t + D x_t

The recurrence is linear, so a chunk of ``Q`` positions is two products:
inside the chunk the masked ``(L o C B')(dt x)`` with
``L[t, s] = exp(sum_{s < r <= t} dt_r a)``, and from the chunks before it the
state they leave, ``C_t H`` decayed to ``t``.  The state a chunk leaves is one
more product (``B`` against the inputs decayed to the chunk's end), and only
the hand-over from chunk to chunk is sequential.

One algorithm in two forms, chosen by shape alone (:func:`scan_form`; no
argument, environment name or model name chooses):

``"kernel"``: two Pallas kernels (``profiling.SSD_FWD`` / ``SSD_BWD``; Mosaic
on a TPU, interpret mode elsewhere) under one ``custom_vjp``.  A program is
one (sequence, block of heads, chunk); the chunk axis is last and sequential,
and the block's state [heads x P, N] float32 lives in VMEM scratch across it
(the backward walks the chunks from the last to the first with the state's
cotangent there).  ``C B'``, ``L`` and ``C B' o L o dt`` are formed in VMEM,
used and dropped: no [Q, Q] matrix reaches HBM.  The ``custom_vjp`` takes
``(x, dt, cum, b, c, d)`` with ``cum`` the decay's exponent summed from the
chunk's start, so softplus, ``a`` and the cumulative sum stay JAX's to
differentiate ([S, H] float32).  Residuals: the inputs, and the float32 state
that entered each chunk ([S / Q, H P, N], which the forward writes).  The
tiling rule (:func:`head_block`): a chunk of 128 or 256, N 128 or 256, P = 64
(Mamba-2's head size), and an even block of heads (at most ``_BLOCK_LANES``
lanes of ``heads x P``) that divides a group: what the chip's compiler takes
within Mosaic's default 16 MiB of VMEM (``tests/test_tpu_structure.py``).
Two heads share a 128-lane tile: a product runs on the whole tile with the
other head's half zeroed or dropped, so no slice leaves the tile grid.

``"xla"``: the same products as XLA's ops, differentiated by JAX, a
``lax.scan`` handing the state on; for shapes outside the rule (tiny test
models, odd widths).

Precision, the program's mixed one, in both forms: ``dt``, the decay sums and
every exponential in float32 (within a chunk the sums stay under
``Q * max(dt * -a)``, so a difference of two of them keeps its digits; across
chunks decays are multiplied, never subtracted); every product's operands in
``x``'s dtype with float32 accumulation; the carried state, and every term of
the gradients of ``dt`` and ``cum``, in float32.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from horovod_tpu.utils import profiling

F32 = jnp.float32
_BLOCK_LANES = 1024     # heads x P of one program: 16 heads of 64
_P, _TILE = 64, 128     # the head size the kernels take; the lanes of a vreg
_PAIR = _TILE // _P     # heads a tile
_NT = (((1,), (1,)), ((), ()))      # a b'
_TN = (((0,), (0,)), ((), ()))      # a' b


def carried_state_bytes(heads: int, head_dim: int, state_dim: int) -> int:
    """Bytes of the float32 state one sequence hands from a chunk to the
    next, a layer."""
    return heads * head_dim * state_dim * 4


def head_block(q: int, heads_per_group: int, p: int, n: int) -> int | None:
    """The kernels' tiling rule: how many heads one program takes, or None
    where the shapes do not meet the rule and the XLA form runs.  ``q`` is
    the chunk as run (``min(chunk, S)``)."""
    if q not in (128, 256) or n not in (128, 256) or p != _P:
        return None
    for heads in range(min(heads_per_group, _BLOCK_LANES // p), 0, -1):
        if heads_per_group % heads == 0 and heads % _PAIR == 0:
            return heads
    return None


def scan_form(seq_len: int, chunk: int, heads: int, groups: int, p: int,
              n: int) -> str:
    """``"kernel"`` or ``"xla"``: the form :func:`ssd_scan` runs at these
    shapes (what ``mamba.ssm_plan`` reports is this function's answer)."""
    return "kernel" if head_block(min(chunk, seq_len), heads // groups, p,
                                  n) else "xla"


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
    dt = dt.astype(F32)
    # [B, nc, Q, H]: the decay's exponent summed from the chunk's start
    cum = jnp.cumsum(dt.reshape(bsz, nc, q, h) * a.astype(F32), axis=2)
    form = _scan_kernels if scan_form(s, chunk, h, g, p, n) == "kernel" \
        else _scan_xla
    return form(x, dt, cum, b, c, d_skip)[:, :s]


def _scan_xla(x, dt, cum, b, c, d_skip):
    bsz, nc, q, h = cum.shape
    p = x.shape[-1]
    g, n = b.shape[2:]
    dtype = x.dtype
    # heads as (group, head of the group), chunks as (chunk, position)
    xr = x.reshape(bsz, nc, q, g, h // g, p)
    br, cr = b.reshape(bsz, nc, q, g, n), c.reshape(bsz, nc, q, g, n)
    # [B, nc, G, Hg, Q]
    cum = cum.reshape(bsz, nc, q, g, h // g).transpose(0, 1, 3, 4, 2)
    dt_t = dt.reshape(bsz, nc, q, g, h // g).transpose(0, 1, 3, 4, 2)

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
    return y.reshape(bsz, nc * q, h, p).astype(dtype)


# -- the kernels --------------------------------------------------------------
# A program sees its heads side by side on the lane axis, ``heads x P`` wide,
# and walks them a 128-lane tile, two heads, at a time.  ``where`` below is
# an index along the axis that holds the tile's ``P``s: a lane of [Q, tile],
# a row of [tile, Q].

def _by_head(parts, where):
    """One tile from one result a head of the tile: the first head's
    [0, P) from ``parts[0]``, the second's [P, 2 P) from ``parts[1]``."""
    first, second = parts
    return jnp.where(where < _P, first, second)


def _only_head(v, i, lane):
    """``v`` [Q, tile] with the lanes of the tile's other head zeroed."""
    return jnp.where((lane < _P) == (i == 0), v, jnp.zeros_like(v))


def _decays(cumc_ref, cumr_ref, h, causal):
    """L[t, s] = exp(cum_t - cum_s) at s <= t, 0 above the diagonal, masked
    before the exponential (the difference there is positive and can
    overflow); and cum_t itself along the lanes of a row, which the
    difference had to make anyway: spreading a column over lanes is the one
    costly move here, so whoever needs exp(cum_t) so spread takes it from
    this."""
    q = causal.shape[0]
    cum_t = jnp.broadcast_to(cumc_ref[0, 0, 0, :, h:h + 1], (q, q))
    seg = cum_t - cumr_ref[0, 0, 0, h:h + 1, :]
    return jnp.exp(jnp.where(causal, seg, -jnp.inf)), cum_t


def _chunk_terms(cumr_ref, dtr_ref):
    """A position a lane, [heads, Q]: exp(cum_t); the weight
    exp(cum_Q - cum_s) dt_s of an input in the state its chunk leaves; and
    the chunk's whole decay exp(cum_Q), [heads, 1]."""
    cum = cumr_ref[0, 0, 0]
    end = cum[:, cum.shape[1] - 1:]
    return jnp.exp(cum), jnp.exp(end - cum) * dtr_ref[0, 0, 0], jnp.exp(end)


def _causal(q: int):
    return jax.lax.broadcasted_iota(jnp.int32, (q, q), 0) \
        >= jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)


def _fwd_kernel(x_ref, b_ref, c_ref, cumc_ref, cumr_ref, dtr_ref, d_ref,
                y_ref, sin_ref, state):
    """One chunk of one block of heads: with S the state that enters,

        Y = (C B' o L o dt_s) X + exp(cum_t) o (C S') + D X
        S <- exp(cum_Q) S + (X o w)' B,   w_s = exp(cum_Q - cum_s) dt_s

    and S as it entered written out for the backward."""
    q, width = x_ref.shape[1:]
    dtype = x_ref.dtype

    @pl.when(pl.program_id(2) == 0)
    def _first_chunk():
        state[...] = jnp.zeros_like(state)

    entering = state[...]
    sin_ref[0, 0] = entering
    bm, cm = b_ref[0], c_ref[0]
    cb = jax.lax.dot_general(cm, bm, _NT, preferred_element_type=F32)
    from_state = jax.lax.dot_general(cm, entering.astype(dtype), _NT,
                                     preferred_element_type=F32)
    _, w_r, decay = _chunk_terms(cumr_ref, dtr_ref)
    causal = _causal(q)
    lane = jax.lax.broadcasted_iota(jnp.int32, (q, _TILE), 1)
    row = jax.lax.broadcasted_iota(jnp.int32, (_TILE, q), 0)
    for j in range(width // _TILE):
        at = slice(j * _TILE, (j + 1) * _TILE)
        of = range(j * _PAIR, (j + 1) * _PAIR)
        xt = x_ref[0, :, at]
        inside, e_t = [], []
        for h in of:
            decays, cum_t = _decays(cumc_ref, cumr_ref, h, causal)
            e_t.append(jnp.exp(cum_t[:, :_TILE]))
            inside.append(jnp.dot(
                (cb * decays * dtr_ref[0, 0, 0, h:h + 1, :]).astype(dtype),
                xt, preferred_element_type=F32))
        y = _by_head(inside, lane) \
            + _by_head(e_t, lane) * from_state[:, at] \
            + xt.astype(F32) * d_ref[0, :, at]
        y_ref[0, :, at] = y.astype(dtype)
        # (X o w)' B with the positions of X' on lanes, as w_r has them
        weighted = (xt.T.astype(F32)
                    * _by_head([w_r[h:h + 1] for h in of], row)
                    ).astype(dtype)
        state[at, :] = state[at, :] \
            * _by_head([decay[h:h + 1] for h in of], row[:, :1]) \
            + jnp.dot(weighted, bm, preferred_element_type=F32)


def _bwd_kernel(x_ref, b_ref, c_ref, cumc_ref, cumr_ref, dtr_ref, d_ref,
                sin_ref, dy_ref, dx_ref, db_ref, dc_ref, rows_ref, kept_ref,
                dw_ref, cols_ref, sd_ref, dd_ref, dstate):
    """One chunk's cotangents, the chunks walked from the last to the first.
    With M = C B' o L o dt_s (``mix``), dM = dY X' and dS the cotangent of
    the state the chunk leaves:

        dX = M' dY + D dY + w o (B dS')
        dC = (sum_h dM o L o dt_s) B + sum_h (dY o exp(cum_t)) S_in
        dB = (sum_h dM o L o dt_s)' C + sum_h (X o w) dS
        dS_in = exp(cum_Q) dS + (dY o exp(cum_t))' C

    (the sum over a block's heads of dM o L o dt_s is taken before its two
    products, which are the largest of the pass) and, all float32, for the
    wrapper to put together as d cum and d dt: ``rows`` [Q, heads] = sum_s
    dM o M (what cum_t gets from its row of M); ``kept`` [heads, Q] = sum_p
    dY o exp(cum_t) o (C S_in') (what it gets from the state that entered);
    ``cols`` [heads, Q] = sum_t dM o M (minus it is what cum_s gets from its
    column; over dt_s, d dt_s directly); ``dw`` [heads, Q] = sum_p X o
    (B dS'), the cotangent of w; ``sd`` the lane-wise partial sums of
    S_in o dS a head (the cotangent of the chunk's decay); ``dd`` the sums
    over positions of dY o X (of D).

    Everything [Q, tile] but the two operands of dM is worked on transposed,
    a position a lane: weights and decays a position are then rows (spread
    over sublanes for nothing, where a column costs a permute a vreg), a sum
    over a head's P is a sum over sublanes, and no [Q, Q] matrix is ever
    transposed: a tile of X, of dY and of dX is."""
    q, width = x_ref.shape[1:]
    dtype = x_ref.dtype

    @pl.when(pl.program_id(2) == 0)
    def _last_chunk():
        dstate[...] = jnp.zeros_like(dstate)

    leaving = dstate[...]
    bm, cm = b_ref[0], c_ref[0]
    cb = jax.lax.dot_general(cm, bm, _NT, preferred_element_type=F32)
    both = sin_ref[0, 0] * leaving
    for h in range(width // _P):
        sd_ref[0, 0, 0, h:h + 1, :] = jnp.sum(both[h * _P:(h + 1) * _P],
                                              axis=0, keepdims=True)
    e_r, w_r, decay = _chunk_terms(cumr_ref, dtr_ref)
    causal = _causal(q)
    lane = jax.lax.broadcasted_iota(jnp.int32, (q, _TILE), 1)
    row = jax.lax.broadcasted_iota(jnp.int32, (_TILE, q), 0)
    diagonal = jax.lax.broadcasted_iota(jnp.int32, (_TILE, _TILE), 0) \
        == jax.lax.broadcasted_iota(jnp.int32, (_TILE, _TILE), 1)
    head_lane = jax.lax.broadcasted_iota(jnp.int32, (q, width // _P), 1)
    dcb = jnp.zeros((q, q), F32)
    dc_t = jnp.zeros(cm.shape[::-1], F32)       # [N, Q]
    db_t = jnp.zeros(bm.shape[::-1], F32)
    rows = jnp.zeros((q, width // _P), F32)
    for j in range(width // _TILE):
        at = slice(j * _TILE, (j + 1) * _TILE)
        of = range(j * _PAIR, (j + 1) * _PAIR)
        xt, dyt = x_ref[0, :, at], dy_ref[0, :, at]
        x_t, dy_t = xt.T, dyt.T                             # [tile, Q]
        entering = sin_ref[0, 0, at, :].astype(dtype)
        going = leaving[at, :].astype(dtype)
        dd_ref[0, 0, 0, :, at] = jnp.sum(
            jnp.where(diagonal, jnp.dot(dy_t, xt, preferred_element_type=F32),
                      0.0), axis=0, keepdims=True)
        inside = []
        for i, h in enumerate(of):
            l_dt = _decays(cumc_ref, cumr_ref, h, causal)[0] \
                * dtr_ref[0, 0, 0, h:h + 1, :]
            d_cb = jax.lax.dot_general(
                _only_head(dyt, i, lane), xt, _NT,
                preferred_element_type=F32) * l_dt
            dcb = dcb + d_cb
            both = d_cb * cb                                # dM o M
            cols_ref[0, 0, 0, h:h + 1, :] = jnp.sum(both, axis=0,
                                                    keepdims=True)
            rows = jnp.where(head_lane == h,
                             jnp.sum(both, axis=1, keepdims=True), rows)
            # (M' dY)' = dY' M: head h's rows of it
            inside.append(jnp.dot(dy_t, (cb * l_dt).astype(dtype),
                                  preferred_element_type=F32))
        xf, dyf = x_t.astype(F32), dy_t.astype(F32)
        w_t = _by_head([w_r[h:h + 1] for h in of], row)
        to_state = jax.lax.dot_general(going, bm, _NT,      # (B dS')'
                                       preferred_element_type=F32)
        from_state = jax.lax.dot_general(entering, cm, _NT,     # (C S_in')'
                                         preferred_element_type=F32)
        dx = _by_head(inside, row) + dyf * d_ref[0, at, :] + w_t * to_state
        dx_ref[0, :, at] = dx.astype(dtype).T
        decayed = dyf * _by_head([e_r[h:h + 1] for h in of], row)
        by_state, by_decay = xf * to_state, decayed * from_state
        for i, h in enumerate(of):
            mine = slice(i * _P, (i + 1) * _P)
            dw_ref[0, 0, 0, h:h + 1, :] = jnp.sum(by_state[mine], axis=0,
                                                  keepdims=True)
            kept_ref[0, 0, 0, h:h + 1, :] = jnp.sum(by_decay[mine], axis=0,
                                                    keepdims=True)
        decayed = decayed.astype(dtype)
        dc_t = dc_t + jax.lax.dot_general(entering, decayed, _TN,
                                          preferred_element_type=F32)
        db_t = db_t + jax.lax.dot_general(going, (xf * w_t).astype(dtype),
                                          _TN, preferred_element_type=F32)
        dstate[at, :] = leaving[at, :] \
            * _by_head([decay[h:h + 1] for h in of], row[:, :1]) \
            + jnp.dot(decayed, cm, preferred_element_type=F32)
    dcb = dcb.astype(dtype)
    dc_ref[0, 0] = dc_t.T + jnp.dot(dcb, bm, preferred_element_type=F32)
    db_ref[0, 0] = db_t.T + jax.lax.dot_general(
        dcb, cm, _TN, preferred_element_type=F32)
    rows_ref[0, 0, 0] = rows


def _by_block(v, heads: int):
    """[B, nc, Q, H] as the kernels read it: a block of heads' [Q, heads]
    (a position a sublane) and [heads, Q] (a position a lane)."""
    bsz, nc, q, h = v.shape
    v = v.reshape(bsz, nc, q, h // heads, heads)
    return v.transpose(0, 1, 3, 2, 4), v.transpose(0, 1, 3, 4, 2)


def _specs(x, b, cum, heads: int, backwards: bool):
    """The grid (sequence, block of heads, chunk) and the block of each
    operand at a step of it; ``backwards`` walks the chunks from the last."""
    bsz, nc, q, h = cum.shape
    p = x.shape[-1]
    g, n = b.shape[2:]
    width, blocks = heads * p, h // heads
    a_group = blocks // g
    at = (lambda ci: nc - 1 - ci) if backwards else (lambda ci: ci)
    spec = pl.BlockSpec
    return dict(
        grid=(bsz, blocks, nc),
        x=spec((1, q, width), lambda bi, hi, ci: (bi, at(ci), hi)),
        bc=spec((1, q, n), lambda bi, hi, ci: (bi, at(ci), hi // a_group)),
        col=spec((1, 1, 1, q, heads),
                 lambda bi, hi, ci: (bi, at(ci), hi, 0, 0)),
        row=spec((1, 1, 1, heads, q),
                 lambda bi, hi, ci: (bi, at(ci), hi, 0, 0)),
        d=spec((1, 1, width), lambda bi, hi, ci: (hi, 0, 0)),
        d_col=spec((1, width, 1), lambda bi, hi, ci: (hi, 0, 0)),
        state=spec((1, 1, width, n), lambda bi, hi, ci: (bi, at(ci), hi, 0)),
        # a group's dB and dC are sums over its blocks of heads: each block
        # writes its own float32 part, one XLA add puts them together
        part=spec((1, 1, q, n),
                  lambda bi, hi, ci: (bi, hi % a_group, at(ci),
                                      hi // a_group)),
        sd=spec((1, 1, 1, heads, n),
                lambda bi, hi, ci: (bi, at(ci), hi, 0, 0)),
        dd=spec((1, 1, 1, 1, width),
                lambda bi, hi, ci: (bi, at(ci), hi, 0, 0)),
        params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=jax.default_backend() != "tpu")


def _operands(x, dt, cum, b, c, d_skip, heads: int, d_shape: tuple):
    """What both kernels read, in their order; D a lane a row for the
    forward, a sublane a row for the backward, which works transposed."""
    bsz, s, h, p = x.shape
    cum_c, cum_r = _by_block(cum, heads)
    return (x.reshape(bsz, s, h * p), b.reshape(bsz, s, -1),
            c.reshape(bsz, s, -1), cum_c, cum_r,
            _by_block(dt.reshape(cum.shape), heads)[1],
            jnp.repeat(d_skip.astype(F32), p).reshape(
                (h // heads,) + d_shape))


# Both passes are jitted on their own: a kernel's body is a few thousand
# operations to trace and lower, and every layer of a model, its recomputed
# forward included, then shares one tracing of each (a third of a minute of
# set-up in the nine-layer cell without it).
@jax.jit
def _forward(x, dt, cum, b, c, d_skip):
    bsz, s, h, p = x.shape
    nc, q = cum.shape[1:3]
    g, n = b.shape[2:]
    heads = head_block(q, h // g, p, n)
    at = _specs(x, b, cum, heads, backwards=False)
    y, entering = pl.pallas_call(
        _fwd_kernel,
        grid=at["grid"],
        in_specs=[at["x"], at["bc"], at["bc"], at["col"], at["row"],
                  at["row"], at["d"]],
        out_specs=(at["x"], at["state"]),
        out_shape=(jax.ShapeDtypeStruct((bsz, s, h * p), x.dtype),
                   jax.ShapeDtypeStruct((bsz, nc, h * p, n), F32)),
        scratch_shapes=[pltpu.VMEM((heads * p, n), F32)],
        compiler_params=at["params"], interpret=at["interpret"],
        name=profiling.SSD_FWD,
    )(*_operands(x, dt, cum, b, c, d_skip, heads, (1, heads * p)))
    return y.reshape(x.shape), entering


@jax.custom_vjp
def _scan_kernels(x, dt, cum, b, c, d_skip):
    return _forward(x, dt, cum, b, c, d_skip)[0]


def _scan_kernels_fwd(x, dt, cum, b, c, d_skip):
    y, entering = _forward(x, dt, cum, b, c, d_skip)
    return y, (x, dt, cum, b, c, d_skip, entering)


@jax.jit
def _scan_kernels_bwd(res, dy):
    x, dt, cum, b, c, d_skip, entering = res
    bsz, s, h, p = x.shape
    nc, q = cum.shape[1:3]
    g, n = b.shape[2:]
    heads = head_block(q, h // g, p, n)
    blocks = h // heads
    at = _specs(x, b, cum, heads, backwards=True)
    by_block = lambda *tail: jax.ShapeDtypeStruct(  # noqa: E731
        (bsz, nc, blocks) + tail, F32)
    part = jax.ShapeDtypeStruct((bsz, blocks // g, s, g * n), F32)
    dx, db, dc, rows, kept, dw, cols, sd, dd = pl.pallas_call(
        _bwd_kernel,
        grid=at["grid"],
        in_specs=[at["x"], at["bc"], at["bc"], at["col"], at["row"],
                  at["row"], at["d_col"], at["state"], at["x"]],
        out_specs=(at["x"], at["part"], at["part"], at["col"], at["row"],
                   at["row"], at["row"], at["sd"], at["dd"]),
        out_shape=(jax.ShapeDtypeStruct((bsz, s, h * p), x.dtype), part, part,
                   by_block(q, heads), by_block(heads, q), by_block(heads, q),
                   by_block(heads, q), by_block(heads, n),
                   by_block(1, heads * p)),
        scratch_shapes=[pltpu.VMEM((heads * p, n), F32)],
        compiler_params=at["params"], interpret=at["interpret"],
        name=profiling.SSD_BWD,
    )(*_operands(x, dt, cum, b, c, d_skip, heads, (heads * p, 1)), entering,
      dy.reshape(bsz, s, h * p))
    # the float32 pieces put together, [B, nc, Q, H] each
    kept, dw, cols = (v.transpose(0, 1, 4, 2, 3).reshape(cum.shape)
                      for v in (kept, dw, cols))
    rows = rows.transpose(0, 1, 3, 2, 4).reshape(cum.shape) + kept
    dt_c = dt.reshape(cum.shape)
    end = cum[:, :, -1:]
    to_end = jnp.exp(end - cum)
    through_w = dw * to_end * dt_c
    d_cum = rows - cols - through_w
    d_cum = d_cum.at[:, :, -1].add(
        through_w.sum(2) + jnp.exp(end[:, :, 0]) * sd.sum(-1).reshape(
            bsz, nc, h))
    # M is dt_s times what does not hold dt_s, so sum_t dM o M over dt_s is
    # M's share of d dt_s; where dt_s is 0 (padding) M's column is 0
    d_dt = (jnp.where(dt_c > 0, cols / dt_c, 0.0)
            + dw * to_end).reshape(dt.shape)
    d_d = dd.reshape(bsz, nc, h, p).sum((0, 1, 3)).astype(d_skip.dtype)
    return (dx.reshape(x.shape), d_dt, d_cum,
            db.sum(1).reshape(b.shape).astype(b.dtype),
            dc.sum(1).reshape(c.shape).astype(c.dtype), d_d)


_scan_kernels.defvjp(_scan_kernels_fwd, _scan_kernels_bwd)
