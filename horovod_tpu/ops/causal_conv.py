"""A depthwise causal convolution along S, its bias and a silu, over a run of
columns of a wider stream, each part of the run written out apart.

``stream`` [B, S, W] is what a projection left (Mamba-2's ``in_proj``: z | x |
B | C | dt side by side); columns ``[start, start + sum(widths))`` are the
convolved channels, ``taps`` [K, C] and ``bias`` [C] (or None) theirs::

    pre[t, c] = bias[c] + sum_k taps[k, c] * stream[t - (K-1) + k, start + c]
    out[t, c] = pre * sigmoid(pre)          zeros before the sequence's start

and the result is one array a part, [B, S, width] each, as the consumer reads
them (``ssd_scan`` takes x, B and C as three arrays).

One algorithm in two forms, chosen by shape alone (:func:`conv_form`; no
argument, environment name or model name chooses):

``"kernel"``: two Pallas kernels (``profiling.CAUSAL_CONV_FWD`` / ``_BWD``;
Mosaic on a TPU, interpret mode elsewhere) under one ``custom_vjp``, one call a
part.  A program is one (block of columns, sequence, tile of rows).  The
stream is read where it lies, through the block's column index, so neither a
slice of it nor a padded copy is made; the K - 1 rows before a tile come
through a second, 16-row block on the same array (zeros before row 0).
float32 inside (convert, the K multiply-adds in the order of the ``jnp`` form,
bias, silu) and one rounding at the store.  The backward takes a part's
cotangent, recomputes the pre-activation from the stream (no residual but the
inputs), walks the row tiles from the last to the first with d(pre)'s first
rows handed on in VMEM (the convolution's transpose reads K - 1 rows AFTER a
tile), and sums d(taps) and d(bias) in float32 in VMEM over every row of a
column block.  The rule: every part's width and first column a multiple of
128, S a multiple of a row tile (``_ROW_TILES``), at most 9 taps.

``"xla"``: the same arithmetic as ``jnp`` ops (pad, K shifted multiply-adds,
``nn.silu``, a split), differentiated by JAX; for shapes outside the rule
(tiny test models, odd widths), and the tests' reference.

The two agree to the rounding of the sigmoid: the sums are taken in the same
order, the exponential is Mosaic's in one and XLA's in the other, so a
bfloat16 result may differ by one unit in the last place where float32's
last digits decide a rounding (interpreted on a CPU they are equal).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from horovod_tpu.utils import profiling

F32 = jnp.float32
_HALO = 16              # rows of the block before a tile: one bf16 tile
_CARRY = 8              # rows handed from chunk to chunk: one float32 tile
_ROW_TILES = (1024, 512, 256)
_COLUMN_BLOCKS = (512, 256, 128)
_CHUNK = 32             # rows worked on at once, in registers (the chip: 32
                        # beat 64 and 128 at 512 columns, PERF.md section 6)


def row_tile(seq_len: int) -> int | None:
    return next((t for t in _ROW_TILES if seq_len % t == 0), None)


def column_block(start: int, width: int) -> int | None:
    return next((c for c in _COLUMN_BLOCKS
                 if start % c == 0 and width % c == 0), None)


def _parts(start: int, widths: tuple):
    """(first column in the stream, first channel, width) of each part."""
    done = 0
    for width in widths:
        yield start + done, done, width
        done += width


def conv_form(seq_len: int, taps: int, start: int, widths: tuple) -> str:
    """``"kernel"`` or ``"xla"``: the form :func:`causal_conv_silu` runs at
    these shapes."""
    fits = row_tile(seq_len) is not None and taps <= _CARRY + 1 and all(
        column_block(first, width) for first, _, width in _parts(start,
                                                                 widths))
    return "kernel" if fits else "xla"


def causal_conv_silu(stream, taps, bias, start: int, widths: tuple):
    """``stream`` [B, S, W]; ``taps`` [K, C] and ``bias`` [C] or None with
    C = sum(widths); returns a tuple of [B, S, width], in ``stream``'s
    dtype."""
    widths = tuple(int(w) for w in widths)
    if taps.shape[1] != sum(widths) or \
            start + sum(widths) > stream.shape[-1]:
        raise ValueError(
            f"{taps.shape[1]} channels of taps for parts {widths} at column "
            f"{start} of {stream.shape[-1]}")
    if bias is None:
        bias = jnp.zeros((taps.shape[1],), taps.dtype)
    if conv_form(stream.shape[1], taps.shape[0], start, widths) == "kernel":
        return _conv_kernels(stream, taps, bias, start, widths)
    return _conv_xla(stream, taps, bias, start, widths)


def causal_conv(x, kernel, bias):
    """Depthwise convolution along S of x [B, S, C] with kernel [K, C]:
    position t sees t-(K-1)..t, zeros before the start.  float32 inside one
    fusion, result in x's dtype."""
    k = kernel.shape[0]
    s = x.shape[1]
    padded = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    out = bias.astype(F32)
    for tap in range(k):
        out = out + padded[:, tap:tap + s].astype(F32) \
            * kernel[tap].astype(F32)
    return out.astype(x.dtype)


def _conv_xla(stream, taps, bias, start, widths):
    run = jax.lax.slice_in_dim(stream, start, start + sum(widths), axis=-1)
    out = jax.nn.silu(causal_conv(run, taps, bias))
    ends = [sum(widths[:i + 1]) for i in range(len(widths) - 1)]
    return tuple(jnp.split(out, ends, axis=-1))


# -- the kernels --------------------------------------------------------------
# A tile of rows is worked on a chunk of ``_CHUNK`` rows at a time, float32 in
# registers.  ``back[j]`` of a chunk is the chunk moved j rows towards the
# start: rows t-j, with the rows before the chunk (the chunk before it, the
# halo block before the tile's first chunk, zeros before row 0) moved in.  A
# move is a roll along sublanes of the chunk with its ``_CARRY`` neighbour
# rows, cut back to the chunk: a rotate and a select a vreg.

def _halo_rows(halo_ref, first_tile):
    """The ``_CARRY`` rows before the tile, float32: zeros before row 0."""
    halo = halo_ref[0].astype(F32)[_HALO - _CARRY:]
    return jnp.where(first_tile, jnp.zeros_like(halo), halo)


def _pre_activation(cur, before, w, bias):
    """(pre, [back[0], .., back[K-1]]) of the chunk ``cur`` [rows, cw] that
    follows the rows ``before`` [_CARRY, cw]."""
    k = w.shape[0]
    cat = jnp.concatenate([before, cur], axis=0)
    back = [cur] + [pltpu.roll(cat, j, 0)[_CARRY:] for j in range(1, k)]
    pre = bias
    for tap in range(k):        # the jnp form's order: the oldest row first
        pre = pre + back[k - 1 - tap] * w[tap:tap + 1]
    return pre, back


def _fwd_kernel(x_ref, halo_ref, w_ref, b_ref, o_ref):
    tile, rows = x_ref.shape[1], _CHUNK
    w, bias = w_ref[...].astype(F32), b_ref[...].astype(F32)

    def chunk(r, before):
        at = pl.ds(pl.multiple_of(r * rows, rows), rows)
        cur = x_ref[0, at, :].astype(F32)
        pre, _ = _pre_activation(cur, before, w, bias)
        o_ref[0, at, :] = (pre * jax.nn.sigmoid(pre)).astype(o_ref.dtype)
        return cur[rows - _CARRY:]

    jax.lax.fori_loop(0, tile // rows, chunk,
                      _halo_rows(halo_ref, pl.program_id(2) == 0))


def _bwd_kernel(x_ref, halo_ref, w_ref, b_ref, g_ref, dx_ref, dw_ref, db_ref,
                after, dw_acc, db_acc):
    """One tile's d(stream), the tiles walked from a sequence's last to its
    first: with dP = g * silu'(pre),

        dX[t] = sum_j taps[K-1-j] dP[t+j]       (rows after the tile: from
                                                 the tile walked before)
        d taps[K-1-j] += sum_t dP[t] X[t-j]     d bias += sum_t dP[t]

    the two sums kept a sublane apart ([8, cw] each) until the column block's
    last program adds the sublanes up."""
    tile, rows = x_ref.shape[1], _CHUNK
    k = w_ref.shape[0]
    w, bias = w_ref[...].astype(F32), b_ref[...].astype(F32)
    step, steps = pl.program_id(2), pl.num_programs(2)
    first_tile = step == steps - 1          # of the sequence: walked last
    first_program = jnp.logical_and(pl.program_id(1) == 0, step == 0)
    last_program = jnp.logical_and(
        pl.program_id(1) == pl.num_programs(1) - 1, first_tile)

    @pl.when(first_program)
    def _new_column_block():
        dw_acc[...] = jnp.zeros_like(dw_acc)
        db_acc[...] = jnp.zeros_like(db_acc)

    @pl.when(step == 0)
    def _last_tile_of_a_sequence():
        after[...] = jnp.zeros_like(after)

    def by_sublane(v):
        """[rows, cw] summed to [8, cw]: whole vregs added, no sublane
        crossed."""
        return sum(v[m:m + _CARRY] for m in range(0, v.shape[0], _CARRY))

    def chunk(n, head):
        r0 = pl.multiple_of((tile // rows - 1 - n) * rows, rows)
        at = pl.ds(r0, rows)
        # the rows before the chunk: the tile's own, the halo's at its start
        inside = x_ref[0, pl.ds(pl.multiple_of(jnp.maximum(r0 - _HALO, 0),
                                               _HALO), _HALO), :]
        before = jnp.where(r0 == 0, halo,
                           inside.astype(F32)[_HALO - _CARRY:])
        pre, back = _pre_activation(x_ref[0, at, :].astype(F32), before, w,
                                    bias)
        s = jax.nn.sigmoid(pre)
        dp = g_ref[0, at, :].astype(F32) * (s * (1.0 + pre * (1.0 - s)))
        cat = jnp.concatenate([dp, head], axis=0)
        dx = dp * w[k - 1:k]
        for j in range(1, k):   # rows t+j: the chunk moved towards the end
            dx = dx + pltpu.roll(cat, rows + _CARRY - j, 0)[:rows] \
                * w[k - 1 - j:k - j]
        dx_ref[0, at, :] = dx.astype(dx_ref.dtype)
        for j in range(k):
            tap = pl.ds((k - 1 - j) * _CARRY, _CARRY)
            dw_acc[tap, :] = dw_acc[tap, :] + by_sublane(dp * back[j])
        db_acc[...] = db_acc[...] + by_sublane(dp)
        return dp[:_CARRY]

    halo = _halo_rows(halo_ref, first_tile)
    after[...] = jax.lax.fori_loop(0, tile // rows, chunk, after[...])

    @pl.when(last_program)
    def _column_block_done():
        for tap in range(k):
            dw_ref[tap:tap + 1, :] = jnp.sum(
                dw_acc[tap * _CARRY:(tap + 1) * _CARRY, :], axis=0,
                keepdims=True)
        db_ref[...] = jnp.sum(db_acc[...], axis=0, keepdims=True)


def _specs(stream, k: int, first: int, width: int, backwards: bool):
    """The grid (block of columns, sequence, tile of rows) of one part and
    the block of each operand at a step of it; ``backwards`` walks the tiles
    from the last."""
    bsz, s, _ = stream.shape
    tile, cw = row_tile(s), column_block(first, width)
    tiles, offset, halos = s // tile, first // cw, tile // _HALO
    at = (lambda ri: tiles - 1 - ri) if backwards else (lambda ri: ri)
    spec = pl.BlockSpec
    return dict(
        grid=(width // cw, bsz, tiles), cw=cw,
        stream=spec((1, tile, cw), lambda ci, bi, ri: (bi, at(ri),
                                                       offset + ci)),
        # the 16 rows before the tile; the first tile's are masked to zeros
        halo=spec((1, _HALO, cw), lambda ci, bi, ri: (
            bi, jnp.maximum(at(ri) * halos - 1, 0), offset + ci)),
        part=spec((1, tile, cw), lambda ci, bi, ri: (bi, at(ri), ci)),
        taps=spec((k, cw), lambda ci, bi, ri: (0, ci)),
        bias=spec((1, cw), lambda ci, bi, ri: (0, ci)),
        params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        interpret=jax.default_backend() != "tpu")


# Both passes are jitted on their own, as ``ops/ssd_scan.py``'s are: every
# layer of a model, its recomputed forward included, shares one tracing.
@functools.partial(jax.jit, static_argnames=("start", "widths"))
def _forward(stream, taps, bias, start, widths):
    bsz, s, _ = stream.shape
    k = taps.shape[0]
    outs = []
    for first, channel, width in _parts(start, widths):
        at = _specs(stream, k, first, width, backwards=False)
        outs.append(pl.pallas_call(
            _fwd_kernel,
            grid=at["grid"],
            in_specs=[at["stream"], at["halo"], at["taps"], at["bias"]],
            out_specs=at["part"],
            out_shape=jax.ShapeDtypeStruct((bsz, s, width), stream.dtype),
            compiler_params=at["params"], interpret=at["interpret"],
            name=profiling.CAUSAL_CONV_FWD,
        )(stream, stream, taps[:, channel:channel + width],
          bias[None, channel:channel + width]))
    return tuple(outs)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _conv_kernels(stream, taps, bias, start, widths):
    return _forward(stream, taps, bias, start, widths)


def _conv_kernels_fwd(stream, taps, bias, start, widths):
    return _forward(stream, taps, bias, start, widths), (stream, taps, bias)


@functools.partial(jax.jit, static_argnames=("start", "widths"))
def _backward(stream, taps, bias, cotangents, start, widths):
    bsz, s, total = stream.shape
    k = taps.shape[0]
    d_run, d_taps, d_bias = [], [], []
    for (first, channel, width), g in zip(_parts(start, widths), cotangents):
        at = _specs(stream, k, first, width, backwards=True)
        dx, dw, db = pl.pallas_call(
            _bwd_kernel,
            grid=at["grid"],
            in_specs=[at["stream"], at["halo"], at["taps"], at["bias"],
                      at["part"]],
            out_specs=(at["part"], at["taps"], at["bias"]),
            out_shape=(jax.ShapeDtypeStruct((bsz, s, width), stream.dtype),
                       jax.ShapeDtypeStruct((k, width), F32),
                       jax.ShapeDtypeStruct((1, width), F32)),
            scratch_shapes=[pltpu.VMEM((_CARRY, at["cw"]), F32),
                            pltpu.VMEM((k * _CARRY, at["cw"]), F32),
                            pltpu.VMEM((_CARRY, at["cw"]), F32)],
            compiler_params=at["params"], interpret=at["interpret"],
            name=profiling.CAUSAL_CONV_BWD,
        )(stream, stream, taps[:, channel:channel + width],
          bias[None, channel:channel + width], g)
        d_run.append(dx)
        d_taps.append(dw)
        d_bias.append(db[0])
    # the stream's other columns took no part: zeros beside the run, which
    # XLA fuses into whatever adds the other readers' cotangents to this
    d_stream = jnp.pad(
        jnp.concatenate(d_run, axis=-1),
        ((0, 0), (0, 0), (start, total - start - sum(widths))))
    return (d_stream, jnp.concatenate(d_taps, axis=-1).astype(taps.dtype),
            jnp.concatenate(d_bias).astype(bias.dtype))


def _conv_kernels_bwd(start, widths, residuals, cotangents):
    return _backward(*residuals, cotangents, start, widths)


_conv_kernels.defvjp(_conv_kernels_fwd, _conv_kernels_bwd)
