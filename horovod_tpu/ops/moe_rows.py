"""Rows moved by index between token order and expert order:
``out[i] = x[src[i]]`` and ``out[t] = sum_j w[t, j] * y[pair t*k+j]``.

The two row moves of a sparse layer that holds every expert and carries all
its pairs (models/moe.py: the training layer) and their backwards.  XLA:TPU's
gather takes 43 ns a row of 2048 bfloat16 (a quarter of the chip's
bandwidth; PERF.md section 6, PR 57), and two of the four moves stand beside
a pass that exists only because the gather writes ``[T*k, D]``: the
gate-weighted sum over k, and its backward's broadcast ``gates * dout``.

A row cannot be fetched from a ``[N, D]`` array as XLA:TPU lays it: its tile
holds 8 rows (16 of bfloat16, two a 32-bit word), and Mosaic refuses a slice
of a tiled dimension that is no whole tile.  So a row travels as a TILE OF
ITS OWN: ``[N, D]`` becomes ``[N, S, 128]`` 32-bit words, S * 128 of them a
row (bfloat16: column c of the first half of a row in the low half of word
c, column ``D/2 + c`` in the high half, so that both halves widen to float32
by a shift or a mask), and row n is one contiguous piece of HBM that a DMA
moves by its index on the untiled leading axis.  Between a tile of such rows
and the same rows as a ``[R, D]`` block lies one strided load or store a 128
columns, in VMEM, inside the kernel that moves them.  Five kernels:

* **tiles** (:func:`_tiles`): the small side, a step's tokens ``[T, D]``
  (67 MB where the rows are 537), packed a block at a time as it lies.
* **fetch** (:func:`_fetch`): a ``[R, D]`` block of the result a grid step,
  its R rows fetched as tiles by index (R DMAs in flight, the next block's
  started before this one's are awaited), unpacked in VMEM and written in
  order.  tiles + fetch are the dispatch's forward; a fetch is also the
  last step of the combine's backward.
* **send** (:func:`_send`): the other way: a ``[R, D]`` block read in order,
  packed in VMEM, row i sent as a tile to slot ``dst[i]`` (awaited two
  blocks later).  What a fetch from a LARGE operand would be: the rows of
  ``[T*k, D]`` in expert order go to their pairs' slots in token order.
* **sum** (:func:`_sum`): a token's k tiles, now side by side, summed in
  float32 in j's order, weighted by its gates or not, rounded once where
  the result is no float32.  send + sum are the combine's forward (no
  ``[T, k, D]`` array of float32, no gather) and the dispatch's backward.
* **spread** (:func:`_spread`): the combine's backward in token order, one
  pass: ``gates[t, j] * dout[t]`` rounded once to the rows' dtype and
  written as pair ``t*k + j``'s tile (a fetch by ``order`` brings them to
  expert order), and the gates' gradient ``<dout[t], row of pair t*k + j>``
  in float32 (128 partial sums a pair; XLA adds them) from the tiles the
  forward's send left: they are the residual, where the gathered
  ``by_token`` was.

Nothing is gathered or scattered by XLA, rows or scalars (its gather of
``[T*k]`` float32 scalars took 0.94 ms, 7 ns each), and ``order``'s inverse
permutation is not needed.  A DMA a row costs its ISSUE on the scalar core,
16-18 ns on a v5e whatever the block (128 to 512 rows; PERF.md section 6,
PR 57), where a 4 KB row's bytes take 10: fetch and send run at 2.1-2.35 ms
for 131 072 rows where XLA:TPU's gather took 4.3-4.5.  The bodies are loops,
not unrolled: a kernel is traced and lowered at every start, and the
unrolled forms (400-1500 equations a kernel where ``hvd_moe_grouped``'s hold
43-86) cost olmoe-s4096 nine seconds of ``setup_s``.

:func:`dispatch_rows` and :func:`combine_rows` are the two ``custom_vjp``s
the layer calls.  A width that is no whole number of 128-word lines is
padded with zero columns by the wrappers (a test's; a model's is).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from horovod_tpu.utils import profiling

LANES = 128
ROW_TILE = 256      # rows a grid step moves (PERF.md section 6, PR 57)
_HIGH = 0xFFFF0000


def _packed(dtype) -> bool:
    """Two columns a word: bfloat16 alone, whose bits are a float32's high
    half.  Every other dtype travels as float32."""
    return jnp.dtype(dtype) == jnp.bfloat16


def _lines(d: int, dtype) -> tuple[int, int, int]:
    """(columns after padding, 128-word lines a row, lines a row's tile
    holds: whole sublane groups of 8) for rows of ``d`` columns."""
    granule = 2 * LANES if _packed(dtype) else LANES
    wide = -(-d // granule) * granule
    sub = wide // granule
    return wide, sub, -(-sub // 8) * 8


def _widen(words, packed: bool):
    """A line of words [R, 128] as float32: (columns c, columns D/2 + c) of
    a packed row, (columns c,) of a float32 one."""
    f32 = functools.partial(lax.bitcast_convert_type, new_dtype=jnp.float32)
    if packed:
        return f32(words << 16), f32(words & jnp.uint32(_HIGH))
    return (f32(words),)


def _narrow(parts, packed: bool):
    """``_widen``'s inverse for float32 values that are exact in the rows'
    dtype."""
    u32 = functools.partial(lax.bitcast_convert_type, new_dtype=jnp.uint32)
    if packed:
        return (u32(parts[0]) >> 16) | (u32(parts[1]) & jnp.uint32(_HIGH))
    return u32(parts[0])


def _columns(c, sub: int, packed: bool):
    """The column slices of a [R, D] block that line ``c`` (traced or not)
    holds."""
    at = lambda line: pl.ds(pl.multiple_of(line * LANES, LANES),  # noqa: E731
                            LANES)
    return (at(c), at(sub + c)) if packed else (at(c),)


def _row_tile(c: int) -> int:
    if c % LANES:
        raise ValueError(f"moe_rows: {c} rows, not a multiple of {LANES}")
    return ROW_TILE if c % ROW_TILE == 0 else LANES


def _token_tile(t: int, k: int) -> int:
    """Tokens a step of the sum works: about a row tile of pairs, whole
    sublane groups, a divisor of ``t`` (or all of them)."""
    for tile in range(max(8, ROW_TILE // k // 8 * 8), 0, -8):
        if t % tile == 0:
            return tile
    return t


UNROLL = 16     # rows a trip of a block's copy loop: the scalar core that
                # issues a DMA a row also runs the loop.  Fetch and send took
                # 4.4 and 3.7 ms for 131 072 rows at one row a trip, 2.8 and
                # 2.5 at four, 2.35 and 2.1 at eight; the layer's kernels
                # together 13.4 ms at eight, 13.0 at sixteen, 12.8 at
                # thirty-two (v5e; PERF.md section 6, PR 57), and every row
                # of a trip is traced and lowered at every start


def _each(n: int, act, unroll: int = 1):
    """``act(i)`` for i in 0 .. n-1, in a loop of ``unroll`` a trip: a
    kernel's body is traced and lowered at every start of every program
    that holds it, and unrolled ones cost seconds of it (PERF.md section 6,
    PR 57)."""
    def trip(at, carry):
        for i in range(unroll):
            act(at * unroll + i)
        return carry
    lax.fori_loop(0, n // unroll, trip, 0)


def _all_rows(buf, sem, slot):
    """What awaits ALL of a block's row copies at once: a DMA semaphore
    counts bytes, and a descriptor of the whole buffer waits for as many as
    the rows' copies bring together."""
    return pltpu.make_async_copy(buf.at[slot], buf.at[slot], sem.at[slot])


def _fetch_kernel(src_ref, x_hbm, out_ref, buf, sem, *, rows, sub, sub_p,
                  packed):
    # step i starts block i's copies and finishes block i - 1 (the grid has
    # one step more than blocks, and the first writes nothing: its output
    # block is the second's, which fills it)
    i, n = pl.program_id(0), pl.num_programs(0) - 1

    @pl.when(i < n)
    def _():
        slot, first = lax.rem(i, 2), i * rows
        _each(rows, lambda r: pltpu.make_async_copy(
            x_hbm.at[src_ref[first + r]],
            buf.at[slot, pl.ds(pl.multiple_of(r * sub_p, 8), sub_p)],
            sem.at[slot]).start(), UNROLL)

    @pl.when(i > 0)
    def _():
        slot = lax.rem(i - 1, 2)
        _all_rows(buf, sem, slot).wait()
        held = buf.at[slot]

        def line(c):
            words = held[pl.ds(c, rows, stride=sub_p), :]
            for part, cols in zip(_widen(words, packed),
                                  _columns(c, sub, packed)):
                out_ref[:, cols] = part.astype(out_ref.dtype)
        _each(sub, line)


def _pack_lines(y_ref, held, rows, sub, sub_p, packed):
    """The rows of block ``y_ref`` [R, D] into ``held`` [R * S, 128]: row r
    lines ``r * S ..``, a strided store a line."""
    def line(c):
        held[pl.ds(c, rows, stride=sub_p), :] = _narrow(
            [y_ref[:, cols].astype(jnp.float32)
             for cols in _columns(c, sub, packed)], packed)
    _each(sub, line)


def _tiles_kernel(y_ref, out_ref, *, rows, sub, sub_p, packed):
    _pack_lines(y_ref, out_ref, rows, sub, sub_p, packed)


def _send_kernel(dst_ref, y_ref, z_hbm, buf, sem, *, rows, sub, sub_p,
                 packed):
    # step i awaits block i - 2's copies, whose buffer it then fills with
    # block i and sends (the grid has two steps more than blocks)
    i, n = pl.program_id(0), pl.num_programs(0) - 2
    slot = lax.rem(i, 2)

    @pl.when(i >= 2)
    def _():
        _all_rows(buf, sem, slot).wait()

    @pl.when(i < n)
    def _():
        first = i * rows
        _pack_lines(y_ref, buf.at[slot], rows, sub, sub_p, packed)
        _each(rows, lambda r: pltpu.make_async_copy(
            buf.at[slot, pl.ds(pl.multiple_of(r * sub_p, 8), sub_p)],
            z_hbm.at[dst_ref[first + r]], sem.at[slot]).start(), UNROLL)


def _gates(w_ref, across_ref):
    """Each pair's gate across the lanes, ``across_ref`` [k, tokens, 128],
    from ``w_ref`` [tokens, k]: once a block, for all of a pair's lines."""
    k, tokens, _ = across_ref.shape
    for j in range(k):
        across_ref[j] = jnp.broadcast_to(w_ref[:, j:j + 1], (tokens, LANES))


def _sum_kernel(*refs, k, sub, sub_p, packed, weighted):
    if weighted:
        w_ref, z_ref, out_ref, across_ref = refs
        _gates(w_ref, across_ref)
    else:
        z_ref, out_ref = refs
    tokens = out_ref.shape[0]

    def line(c):
        def pair(j, total):
            parts = _widen(
                z_ref[pl.ds(j * sub_p + c, tokens, stride=k * sub_p), :],
                packed)
            if weighted:
                parts = [part * across_ref[j] for part in parts]
            return tuple(a + b for a, b in zip(total, parts))
        zero = jnp.zeros((tokens, LANES), jnp.float32)
        total = lax.fori_loop(0, k, pair, (zero,) * (1 + packed))
        for part, cols in zip(total, _columns(c, sub, packed)):
            out_ref[:, cols] = part.astype(out_ref.dtype)
    _each(sub, line)


def _spread_kernel(w_ref, g_ref, z_ref, out_ref, dot_ref, across_ref, *, k,
                   sub, sub_p, packed, dtype):
    tokens = g_ref.shape[0]
    _gates(w_ref, across_ref)

    def pair(j):
        weight = across_ref[j]

        def line(c, dot):
            lines = pl.ds(j * sub_p + c, tokens, stride=k * sub_p)
            grads = [g_ref[:, cols].astype(jnp.float32)
                     for cols in _columns(c, sub, packed)]
            for grad, row in zip(grads, _widen(z_ref[lines, :], packed)):
                dot = dot + grad * row
            # (rounded to the rows' dtype, once, before it is packed)
            out_ref[lines, :] = _narrow(
                [(grad * weight).astype(dtype).astype(jnp.float32)
                 for grad in grads], packed)
            return dot
        dot_ref[:, pl.ds(pl.multiple_of(j * LANES, LANES), LANES)] = \
            lax.fori_loop(0, sub, line,
                          jnp.zeros((tokens, LANES), jnp.float32))
    _each(k, pair)


def _mode(interpret):
    return jax.default_backend() != "tpu" if interpret is None else interpret


def _params(need: int):
    return pltpu.CompilerParams(
        dimension_semantics=("arbitrary",),
        vmem_limit_bytes=max(16 * 2 ** 20, need + need // 4))


def _padded(x, wide: int):
    return x if x.shape[1] == wide else jnp.pad(
        x, ((0, 0), (0, wide - x.shape[1])))


def _tiles(x, interpret):
    """``x`` [N, D] as tiles [N, S, 128] in its own order (the small side,
    a step's tokens: no copy a row, a block written as it lies)."""
    n, d = x.shape
    rows = next(r for r in (ROW_TILE, LANES, 64, 32, 16, n) if n % r == 0)
    wide, sub, sub_p = _lines(d, x.dtype)
    out = pl.pallas_call(
        functools.partial(_tiles_kernel, rows=rows, sub=sub, sub_p=sub_p,
                          packed=_packed(x.dtype)),
        grid=(n // rows,),
        in_specs=[pl.BlockSpec((rows, wide), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((rows * sub_p, LANES), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n * sub_p, LANES), jnp.uint32),
        compiler_params=_params(2 * rows * (sub_p * LANES * 4
                                            + wide * x.dtype.itemsize)),
        interpret=_mode(interpret), name=profiling.MOE_ROWS,
    )(_padded(x, wide))
    return out.reshape(n, sub_p, LANES)


def _fetch(tiles, src, d: int, dtype, interpret):
    """``out[i]`` [C, D] in ``dtype``: the row that tile ``src[i]`` of
    ``tiles`` [N, S, 128] holds."""
    c, = src.shape
    rows = _row_tile(c)
    wide, sub, sub_p = _lines(d, dtype)
    out = pl.pallas_call(
        functools.partial(_fetch_kernel, rows=rows, sub=sub, sub_p=sub_p,
                          packed=_packed(dtype)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(c // rows + 1,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec(
                (rows, wide), lambda i, src: (jnp.maximum(i - 1, 0), 0)),
            scratch_shapes=[pltpu.VMEM((2, rows * sub_p, LANES), jnp.uint32),
                            pltpu.SemaphoreType.DMA((2,))]),
        out_shape=jax.ShapeDtypeStruct((c, wide), dtype),
        compiler_params=_params(2 * rows * (
            sub_p * LANES * 4 + wide * jnp.dtype(dtype).itemsize)),
        interpret=_mode(interpret), name=profiling.MOE_ROWS,
    )(src.astype(jnp.int32), tiles)
    return out[:, :d]


def _send(y, dst, interpret):
    """``y`` [C, D]'s row i as tile ``dst[i]`` of [C, S, 128] (``dst`` a
    permutation)."""
    c, d = y.shape
    rows = _row_tile(c)
    wide, sub, sub_p = _lines(d, y.dtype)
    return pl.pallas_call(
        functools.partial(_send_kernel, rows=rows, sub=sub, sub_p=sub_p,
                          packed=_packed(y.dtype)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(c // rows + 2,),
            in_specs=[pl.BlockSpec(
                (rows, wide),
                lambda i, dst: (jnp.minimum(i, c // rows - 1), 0))],
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            scratch_shapes=[pltpu.VMEM((2, rows * sub_p, LANES), jnp.uint32),
                            pltpu.SemaphoreType.DMA((2,))]),
        out_shape=jax.ShapeDtypeStruct((c, sub_p, LANES), jnp.uint32),
        compiler_params=_params(2 * rows * (sub_p * LANES * 4
                                            + wide * y.dtype.itemsize)),
        interpret=_mode(interpret), name=profiling.MOE_ROWS,
    )(dst.astype(jnp.int32), _padded(y, wide))


def _by_token(tokens: int, *shape):
    return pl.BlockSpec((tokens * shape[0], *shape[1:]), lambda i: (i, 0))


def _sum(tiles, w, k: int, d: int, dtype, out_dtype, interpret):
    """``out[t] = sum_j w[t, j] * tile t*k+j`` [T, D] in ``out_dtype``, for
    ``tiles`` [T*k, S, 128] of rows in ``dtype``: summed in float32 in j's
    order, rounded once; ``w`` None: the plain sum."""
    c, sub_p, _ = tiles.shape
    t = c // k
    wide, sub, _ = _lines(d, dtype)
    tokens = _token_tile(t, k)
    weighted = w is not None
    out = pl.pallas_call(
        functools.partial(_sum_kernel, k=k, sub=sub, sub_p=sub_p,
                          packed=_packed(dtype), weighted=weighted),
        grid=(t // tokens,),
        in_specs=[_by_token(tokens, 1, k)] * weighted
        + [_by_token(tokens, k * sub_p, LANES)],
        out_specs=_by_token(tokens, 1, wide),
        scratch_shapes=[pltpu.VMEM((k, tokens, LANES), jnp.float32)]
        * weighted,
        out_shape=jax.ShapeDtypeStruct((t, wide), out_dtype),
        compiler_params=_params(2 * tokens * (
            k * sub_p * LANES * 4 + wide * jnp.dtype(out_dtype).itemsize)),
        interpret=_mode(interpret), name=profiling.MOE_ROWS,
    )(*([w.astype(jnp.float32)] if weighted else []),
      tiles.reshape(c * sub_p, LANES))
    return out[:, :d]


def _spread(g, w, tiles, dtype, interpret):
    """For ``g`` [T, D], ``w`` [T, k] and ``tiles`` [T*k, S, 128] of
    rows y in ``dtype``: (tile t*k+j of ``w[t, j] * g[t]`` rounded to
    ``dtype``, as [T*k, S, 128]; ``<g[t], y[t*k+j]>`` [T, k] float32)."""
    c, sub_p, _ = tiles.shape
    t, k = w.shape
    d = g.shape[1]
    wide, sub, _ = _lines(d, dtype)
    tokens = _token_tile(t, k)
    out, dots = pl.pallas_call(
        functools.partial(_spread_kernel, k=k, sub=sub, sub_p=sub_p,
                          packed=_packed(dtype), dtype=dtype),
        grid=(t // tokens,),
        in_specs=[_by_token(tokens, 1, k), _by_token(tokens, 1, wide),
                  _by_token(tokens, k * sub_p, LANES)],
        out_specs=[_by_token(tokens, k * sub_p, LANES),
                   _by_token(tokens, 1, k * LANES)],
        out_shape=[jax.ShapeDtypeStruct((c * sub_p, LANES), jnp.uint32),
                   jax.ShapeDtypeStruct((t, k * LANES), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((k, tokens, LANES), jnp.float32)],
        compiler_params=_params(2 * tokens * (
            2 * k * sub_p * LANES * 4 + wide * g.dtype.itemsize
            + k * LANES * 4)),
        interpret=_mode(interpret), name=profiling.MOE_ROWS,
    )(w.astype(jnp.float32), _padded(g, wide),
      tiles.reshape(c * sub_p, LANES))
    return (out.reshape(c, sub_p, LANES),
            dots.reshape(t, k, LANES).sum(axis=2))


# Each form is jitted by itself and the custom_vjp stands outside them, as
# ops/grouped_matmul.py's: a program traces each kernel it runs once, and a
# differentiated one none that it does not run.
@functools.partial(jax.jit, static_argnums=(2, 3))
def _dispatch_alone(tokens, order, k, interpret):
    return _fetch(_tiles(tokens, interpret), order // k, tokens.shape[1],
                  tokens.dtype, interpret)


@functools.partial(jax.jit, static_argnums=(2, 3))
def _dispatch_fwd(tokens, order, k, interpret):
    return _dispatch_alone(tokens, order, k, interpret), order


@functools.partial(jax.jit, static_argnums=(0, 1))
def _dispatch_bwd(k, interpret, order, g):
    return _sum(_send(g, order, interpret), None, k, g.shape[1], g.dtype,
                g.dtype, interpret), None


_dispatch = jax.custom_vjp(_dispatch_alone, nondiff_argnums=(2, 3))
_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@functools.partial(jax.jit, static_argnums=(3, 4))
def _combine_alone(out_rows, gates, order, out_dtype, interpret):
    return _combine_fwd(out_rows, gates, order, out_dtype, interpret)[0]


@functools.partial(jax.jit, static_argnums=(3, 4))
def _combine_fwd(out_rows, gates, order, out_dtype, interpret):
    tiles = _send(out_rows, order, interpret)
    # (the rows in token order, as tiles, are what the backward reads: kept
    # where the rows in expert order would be; an empty array of the rows'
    # dtype says what the tiles hold)
    return (_sum(tiles, gates, gates.shape[1], out_rows.shape[1],
                 out_rows.dtype, out_dtype, interpret),
            (tiles, gates, order, jnp.zeros((0,), out_rows.dtype)))


@functools.partial(jax.jit, static_argnums=(0, 1))
def _combine_bwd(out_dtype, interpret, kept, g):
    tiles, gates, order, of_dtype = kept
    spread, d_gates = _spread(g, gates, tiles, of_dtype.dtype, interpret)
    d_rows = _fetch(spread, order, g.shape[1], of_dtype.dtype, interpret)
    return d_rows, d_gates.astype(gates.dtype), None


_combine = jax.custom_vjp(_combine_alone, nondiff_argnums=(3, 4))
_combine.defvjp(_combine_fwd, _combine_bwd)


def dispatch_rows(tokens, order, k: int, *, interpret: bool | None = None):
    """Rows of ``tokens`` [T, D] in pair order, [T*k, D]: row ``i`` is the
    token of pair ``order[i]`` (pair ``p`` is token ``p // k``; ``order`` a
    permutation of the T*k pairs, T*k a multiple of 128).  Backward: a
    token's k cotangent rows summed in float32, rounded once."""
    return _dispatch(tokens, order, k, interpret)


def combine_rows(out_rows, gates, order, out_dtype=jnp.float32, *,
                 interpret: bool | None = None):
    """``sum_j gates[t, j] * (the row of pair t*k + j)`` [T, D] in
    ``out_dtype`` for ``out_rows`` [T*k, D] in pair order (row ``i`` is pair
    ``order[i]``'s) and ``gates`` [T, k] float32: summed in float32 in j's
    order, rounded once where ``out_dtype`` is no float32.  Backward: the
    rows' cotangent ``gates * g`` (float32) rounded once to the rows' dtype,
    and the gates' ``<g[t], the row of pair t*k + j>`` in float32."""
    return _combine(out_rows, gates, order, jnp.dtype(out_dtype), interpret)
