"""Grouped matmul over a work list of small row tiles:
``out[r] = rows[r] @ weights[group of r]``.

The three products of a sparse layer (models/moe.py): a block of the walk of
a share's pairs in a served prefill (``_walk_held``), or all the rows a layer
carries (``_experts_in_tiles``: a served bucket's, a training step's): C
rows sorted by expert, ``sizes[g]`` of them expert g's and the
rest behind the last group, each expert a ``[K, N]`` matrix.  XLA:TPU's
kernels for ``lax.ragged_dot`` work 512-row tiles, and every (row tile,
group) pair that meets is one whole 512-row product: at 60-250 rows an
expert a layer pays for two to nine times its rows (PERF.md section 6, PR
51 and PR 53), and at thousands of rows an expert still stand at 52% of the
MXU's peak (PR 55).  Here the row tile is the caller's, smaller, and the
grid runs over a work list of (row tile,
group) VISITS made outside the kernel from ``cumsum(sizes)``, at most
``C / tile + G`` of them, in group order:

* a visit is one MXU product of its row tile with its group's weights, kept
  for the rows of the tile that are the group's (the others keep what an
  earlier visit of the tile left, or are a later visit's);
* consecutive visits of one row tile find the output tile in VMEM (the block
  index does not change, so Pallas neither writes nor fetches it);
* a group's weights are fetched once, a GROUP ahead: the kernel copies them
  itself into one of two VMEM buffers, starting the next group's copy at a
  group's first visit.  Pallas's own pipeline fetches a block one grid STEP
  ahead, so a group of two visits left the copy engine idle for one of
  them; where an expert holds a tile or less the layer is bound by the
  weights' bytes, and that was a fifth of the kernel's time;
* what is left of the list past the last visit repeats it and does nothing.

K stays whole and N is cut so that a visit's weight tiles fit
:data:`WEIGHT_TILE_BYTES`: a tile of K would be fetched again every visit,
a tile of N once a group.  float32 accumulation, the result in the rows'
dtype, as ``ragged_dot`` gives it.  A row past the last group holds whatever
the kernel left there.

Both functions differentiate (PR 55: the training layer's products are these
kernels too, models/moe.py), by two more kernels over the same work list:

* **the rows' gradient** ``g [C, N] x weights[g]^T``: the forward's visits,
  copies and mask with the contraction on the weights' LAST axis (the MXU
  takes the transposed operand as it lies: no ``[G, N, K]`` copy is made)
  and the column tiles cut from K.  Gate's and up's are one kernel that adds
  the two products in float32.  It leaves no row unwritten: a tile's first
  visit starts from zeros, and the tiles behind the last group get a dead
  visit each that writes zeros (``_whole_visits``).
* **the weights' gradient** ``rows[g]^T @ g[g]`` a group: a grid over
  (column tile, visit) whose output block is the visit's GROUP, so a group's
  consecutive visits find their float32 accumulator in VMEM: zeroed at the
  group's first visit, written once at its last.  The rows of a tile outside
  ``[lo, hi)`` are zeroed in one operand before the product (a tile two
  groups share is visited by both).  A group with no rows has no visit, so
  what is left of the list behind the last one writes each a block of zeros
  (``_group_visits``).  Gate's and up's share the transposed rows.  The
  result has the dtype of the weights AS GIVEN: float32 parameters under
  bfloat16 rows are cast by the wrappers here, and get their gradient from
  the float32 sums with no rounding and no pass of XLA's between.

What the backward keeps is what ``ragged_dot``'s keeps: the rows, and of the
fused gate-and-up its two products before the activation, which the kernel
that a differentiated call runs writes beside their activated product (and,
since they are kept, with zeros behind the last group).  The activation's
backward runs inside gate's and up's two kernels, a tile at a time, from
(the product's cotangent, gate, up): float32, each cotangent rounded once to
the rows' dtype for the MXU, no array of either.  ``sizes`` gets no
cotangent.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from horovod_tpu.ops.token_sum import _lane_tile
from horovod_tpu.utils import profiling

# what a visit's weight tiles may take of VMEM, of which the kernel keeps two
# buffers: [2560, 768] gate and up side by side fit whole, [4096, 4096] and
# [7168, 2048] are cut by columns, and a row tile is read again a column
# tile (at 16 MiB a walk's kernels took 6-14% less than at 8 at those two
# shapes, PERF.md section 6, PR 51)
WEIGHT_TILE_BYTES = 16 * 2 ** 20


def _column_tile(k: int, n: int, bytes_a_column: int) -> int:
    """The widest multiple of 128 that divides ``n`` with ``k`` rows of it
    inside :data:`WEIGHT_TILE_BYTES`; 128 where not even that fits, ``n``
    itself where no multiple of 128 divides it."""
    return _lane_tile(n, max(WEIGHT_TILE_BYTES // (k * bytes_a_column), 128))


def _tiles_by_group(sizes, tile: int):
    """Per group of ``sizes`` [G] laid end to end: its first row, the row
    past its last, the first row tile of ``tile`` rows it lies in and how
    many it lies in (0 for an empty group).  In lax's own operations, as
    ``token_sum._visits`` is: traced once a bucket of every served program."""
    ends = lax.cumsum(sizes)
    starts = lax.sub(ends, sizes)
    rows, one = jnp.int32(tile), jnp.int32(1)
    first = lax.div(starts, rows)
    count = lax.select(
        lax.gt(sizes, jnp.int32(0)),
        lax.add(lax.sub(lax.div(lax.sub(ends, one), rows), first), one),
        lax.full_like(sizes, 0))
    return starts, ends, first, count


def visited_rows(sizes, tile: int):
    """Rows of the row tiles the work list visits: ``tile`` times its
    visits.  ``sizes.sum()`` would waste nothing."""
    return lax.mul(jnp.int32(tile), lax.reduce_sum(
        _tiles_by_group(sizes, tile)[3], (0,)))


def _visits(sizes, n_tiles: int, tile: int):
    """The work list of ``sizes`` [G] over ``n_tiles`` row tiles: per visit
    the row tile, the group, the rows ``[lo, hi)`` of the tile that are the
    group's, whether it is a visit at all, whether it is its group's first
    (the visit that waits for the weights), which of the two weight buffers
    is the group's, and the group visited next (-1 after the last).
    ``n_tiles + G`` long whatever the sizes; past the last visit the list
    repeats it, not live."""
    i32 = functools.partial(jnp.asarray, dtype=jnp.int32)
    g = sizes.shape[0]
    starts, ends, first, count = _tiles_by_group(sizes, tile)
    through = lax.cumsum(count)
    total = lax.index_in_dim(through, g - 1, keepdims=False)
    i = lax.iota(jnp.int32, n_tiles + g)
    at = lax.max(lax.min(i, lax.sub(total, i32(1))), i32(0))
    past = lax.le(lax.broadcast_in_dim(through, (i.size, g), (1,)),
                  lax.broadcast_in_dim(at, (i.size, g), (0,)))
    group = lax.min(lax.reduce_sum(past.astype(jnp.int32), (1,)), i32(g - 1))
    of_group = lambda x: x.at[group].get(mode="promise_in_bounds")  # noqa: E731
    opening = of_group(lax.sub(through, count))     # the group's first visit
    row_tile = lax.clamp(i32(0), lax.sub(lax.add(of_group(first), at),
                                         opening), i32(n_tiles - 1))
    top = lax.mul(row_tile, i32(tile))
    inside = lambda x: lax.clamp(  # noqa: E731
        i32(0), lax.sub(of_group(x), top), i32(tile))
    live = lax.lt(i, total)
    # by group: its place among the groups that hold a row, and the next of
    # them (the least index past it, by a running minimum from the right)
    holds = lax.gt(count, i32(0))
    place = lax.sub(lax.cumsum(holds.astype(jnp.int32)), i32(1))
    later = lax.cummin(lax.select(holds, lax.iota(jnp.int32, g),
                                  lax.full_like(count, g)), reverse=True)
    following = lax.concatenate([lax.slice_in_dim(later, 1, g),
                                 lax.full((1,), g, jnp.int32)], 0)
    following = lax.select(lax.lt(following, i32(g)), following,
                           lax.full_like(following, -1))
    opens = lax.bitwise_and(live, lax.eq(at, opening))
    return (row_tile, group, inside(starts), inside(ends),
            live.astype(jnp.int32), opens.astype(jnp.int32),
            of_group(lax.rem(place, i32(2))), of_group(following))


# (jitted, as the list of the weights' gradient below: a step's kernels that
# share a list share its tracing; the forward's own list stays inline, as the
# served programs have it)
@functools.partial(jax.jit, static_argnums=(1, 2))
def _whole_visits(sizes, n_tiles: int, tile: int):
    """:func:`_visits` for a kernel that leaves no row of its result
    unwritten, and two more per visit: whether it is its row tile's first
    (the tile then starts from zeros, not from what the buffer held), and
    whether it is a DEAD visit, one for each row tile behind the last group,
    which writes the tile zeros.  What is left behind those repeats the last
    of them."""
    i32 = functools.partial(jnp.asarray, dtype=jnp.int32)
    row_tile, group, lo, hi, live, *weights = _visits(sizes, n_tiles, tile)
    n = row_tile.shape[0]
    i = lax.iota(jnp.int32, n)
    total = lax.reduce_sum(live, (0,))
    before = lax.concatenate([lax.full((1,), -1, jnp.int32),
                              lax.slice_in_dim(row_tile, 0, n - 1)], 0)
    fresh = lax.bitwise_and(live, lax.ne(row_tile, before).astype(jnp.int32))
    # (past the last visit the list repeats it: the last visited row tile)
    last = lax.select(lax.gt(total, i32(0)),
                      lax.index_in_dim(row_tile, n - 1, keepdims=False),
                      i32(-1))
    behind = lax.ge(i, total)
    dead_tile = lax.sub(lax.add(lax.add(last, i32(1)), i), total)
    dead = lax.bitwise_and(behind, lax.lt(dead_tile, i32(n_tiles)))
    row_tile = lax.select(behind, lax.min(dead_tile, i32(n_tiles - 1)),
                          row_tile)
    return (row_tile, group, lo, hi, live, *weights, fresh,
            dead.astype(jnp.int32))


@functools.partial(jax.jit, static_argnums=(1, 2))
def _group_visits(sizes, n_tiles: int, tile: int):
    """The work list of the weights' gradient: :func:`_visits`' row tile,
    group, rows ``[lo, hi)``, live and first-of-its-group, then whether a
    visit is its group's LAST (the accumulator is written then) and whether
    it is a DEAD visit, one for each group without a row, behind the live
    ones, which writes the group's block zeros.  What is left behind those
    repeats the last of them."""
    i32 = functools.partial(jnp.asarray, dtype=jnp.int32)
    g = sizes.shape[0]
    row_tile, group, lo, hi, live, opens, _, _ = _visits(sizes, n_tiles, tile)
    n = row_tile.shape[0]
    i = lax.iota(jnp.int32, n)
    total = lax.reduce_sum(live, (0,))
    none = lax.full((1,), 0, jnp.int32)
    closes = lax.bitwise_and(live, lax.bitwise_or(
        lax.concatenate([lax.slice_in_dim(opens, 1, n), none], 0),
        lax.sub(i32(1), lax.concatenate([lax.slice_in_dim(live, 1, n), none],
                                        0))))
    empty = lax.le(sizes, i32(0))
    through = lax.cumsum(empty.astype(jnp.int32))
    n_empty = lax.index_in_dim(through, g - 1, keepdims=False)
    # the n-th group without a row: as many groups lie before it as have
    # n or fewer empty ones up to and with themselves
    nth = lax.sub(i, total)
    which = lax.reduce_sum(lax.le(
        lax.broadcast_in_dim(through, (n, g), (1,)),
        lax.broadcast_in_dim(nth, (n, g), (0,))).astype(jnp.int32), (1,))
    last_empty = lax.reduce_max(
        lax.select(empty, lax.iota(jnp.int32, g), lax.full_like(sizes, -1)),
        (0,))
    behind = lax.ge(i, total)
    dead = lax.bitwise_and(behind, lax.lt(nth, n_empty))
    group = lax.select(lax.bitwise_and(behind, lax.gt(n_empty, i32(0))),
                       lax.min(which, last_empty), group)
    return (row_tile, group, lo, hi, live, opens, closes,
            dead.astype(jnp.int32))


def _is(flag):
    """A work list's flag read as a condition (in lax's own operations, here
    and below, as the lists are: a ``jnp`` function or an operator on a
    traced value is one more jitted function traced at every call, 446
    records a step where these leave 47, PERF.md section 6, PR 55)."""
    return lax.eq(flag, jnp.int32(1))


def _a_group_ahead(i, copies, group_ref, live_ref, opens_ref, buffer_ref,
                   following_ref):
    """The weights' copies of visit ``i``: the first group's started where a
    pass over the list starts, a group's awaited at its first visit and the
    next group's started there.  ``copies(group, buffer)`` lists them."""
    # a pass over the list starts with nothing on its way
    @pl.when(lax.bitwise_and(lax.eq(i, jnp.int32(0)), _is(live_ref[0])))
    def _():
        for copy in copies(group_ref[0], 0):
            copy.start()

    @pl.when(_is(opens_ref[i]))
    def _():
        for copy in copies(group_ref[i], buffer_ref[i]):
            copy.wait()

        # the other buffer's group has had its last visit: the next group's
        # weights go there while this one's visits run
        @pl.when(lax.ge(following_ref[i], jnp.int32(0)))
        def _():
            for copy in copies(following_ref[i],
                               lax.sub(jnp.int32(1), buffer_ref[i])):
                copy.start()


def _exact(dtype):
    """(float32 operands at every pass: what the MXU keeps of them at one
    pass is bfloat16's)"""
    return lax.Precision.HIGHEST if dtype == jnp.float32 else None


def _at_tile(j, columns: int):
    """Where column tile ``j`` of ``columns`` begins."""
    at = lax.mul(j, jnp.int32(columns))
    return pl.multiple_of(at, 128) if columns % 128 == 0 else at


def _mine(shape, lo_ref, hi_ref, i):
    """[tile, ...] bool: the rows of a tile that are visit ``i``'s."""
    row = lax.broadcasted_iota(jnp.int32, shape, 0)
    return lax.bitwise_and(lax.ge(row, lax.broadcast(lo_ref[i], shape)),
                           lax.lt(row, lax.broadcast(hi_ref[i], shape)))


def _through_the_activation(g, gate, up):
    """The cotangents of gate and up ``[tile, N']`` under ``g``, that of
    ``silu(gate) * up``: in float32, each rounded once to ``g``'s dtype."""
    dtype = g.dtype
    g, gate, up = (x.astype(jnp.float32) for x in (g, gate, up))
    sigmoid = lax.logistic(gate)
    silu = lax.mul(gate, sigmoid)
    slope = lax.add(sigmoid, lax.mul(silu, lax.sub(
        lax.full(gate.shape, 1, jnp.float32), sigmoid)))
    return (lax.mul(lax.mul(g, up), slope).astype(dtype),
            lax.mul(g, silu).astype(dtype))


def _kernel(tile_ref, group_ref, lo_ref, hi_ref, live_ref, opens_ref,
            buffer_ref, following_ref, rows_ref, *rest, columns: int):
    *weights, out_ref, held, arrived = rest     # weights: whole, in HBM
    j, i = pl.program_id(0), pl.program_id(1)
    left = _at_tile(j, columns)

    def copies(group, buffer):
        """``group``'s column tile j of every weight into ``buffer``."""
        return [pltpu.make_async_copy(
            w.at[group, :, pl.ds(left, columns)], held.at[buffer, n],
            arrived.at[buffer, n]) for n, w in enumerate(weights)]

    _a_group_ahead(i, copies, group_ref, live_ref, opens_ref, buffer_ref,
                   following_ref)

    @pl.when(_is(live_ref[i]))
    def _():
        rows = rows_ref[...]                                    # [tile, K]
        product = lambda n: lax.dot_general(                    # noqa: E731
            rows, held[buffer_ref[i], n], (((1,), (0,)), ((), ())),
            precision=_exact(rows.dtype), preferred_element_type=jnp.float32)
        acc = product(0)                                        # [tile, N']
        if len(weights) == 2:           # the GLU: float32, rounded once
            acc = lax.mul(lax.mul(acc, lax.logistic(acc)), product(1))
        # (the mask is of 32-bit lanes: select there, then narrow)
        out_ref[...] = lax.select(
            _mine(acc.shape, lo_ref, hi_ref, i), acc,
            out_ref[...].astype(jnp.float32)).astype(out_ref.dtype)


def _whole_kernel(tile_ref, group_ref, lo_ref, hi_ref, live_ref, opens_ref,
                  buffer_ref, following_ref, fresh_ref, dead_ref, *rest,
                  columns: int, form: str):
    """A differentiated call's kernel over :func:`_whole_visits`, by
    ``form``.  ``"glu"``: the forward of the fused gate-and-up, ``rows
    [tile, K]`` times the two weights' column tile, that writes gate, up and
    their activated product.  ``"rows"``: the rows' gradient, each of one or
    two ``g [tile, N]`` times its weight's ROWS ``[columns, N]`` transposed,
    added in float32.  ``"rows_of_glu"``: the same of gate's and up's, their
    two cotangents made here of (the activated product's, gate, up)."""
    *rest, held, arrived = rest
    glu = form == "glu"
    n_out = 3 if glu else 1
    outs, rest = rest[-n_out:], rest[:-n_out]
    n_in = {"glu": 1, "rows_of_glu": 3}.get(form, len(rest) // 2)
    operands, weights = rest[:n_in], rest[n_in:]    # weights: whole, in HBM
    j, i = pl.program_id(0), pl.program_id(1)
    at = _at_tile(j, columns)

    def copies(group, buffer):
        """``group``'s tile j of every weight into ``buffer``: of its
        columns, or for the rows' gradient of its rows."""
        part = (lambda w: w.at[group, :, pl.ds(at, columns)]) if glu \
            else (lambda w: w.at[group, pl.ds(at, columns), :])
        return [pltpu.make_async_copy(part(w), held.at[buffer, n],
                                      arrived.at[buffer, n])
                for n, w in enumerate(weights)]

    _a_group_ahead(i, copies, group_ref, live_ref, opens_ref, buffer_ref,
                   following_ref)

    @pl.when(_is(live_ref[i]))
    def _():
        product = lambda x, n, contracted: lax.dot_general(     # noqa: E731
            x, held[buffer_ref[i], n], (((1,), (contracted,)), ((), ())),
            precision=_exact(x.dtype), preferred_element_type=jnp.float32)
        given = [x[...] for x in operands]
        if glu:
            gate, up = (product(given[0], n, 0) for n in range(2))
            results = (gate, up, lax.mul(lax.mul(gate, lax.logistic(gate)),
                                         up))
        else:                                                   # [tile, K']
            if form == "rows_of_glu":
                given = _through_the_activation(*given)
            results = (functools.reduce(lax.add, (
                product(g, n, 1) for n, g in enumerate(given))),)
        shape = results[0].shape
        mine = _mine(shape, lo_ref, hi_ref, i)
        fresh = lax.broadcast(_is(fresh_ref[i]), shape)
        for out_ref, acc in zip(outs, results):
            left = lax.select(fresh, lax.full(shape, 0, jnp.float32),
                              out_ref[...].astype(jnp.float32))
            out_ref[...] = lax.select(mine, acc, left).astype(out_ref.dtype)

    @pl.when(_is(dead_ref[i]))
    def _():
        for out_ref in outs:
            out_ref[...] = lax.full(out_ref.shape, 0, out_ref.dtype)


def _dw_kernel(tile_ref, group_ref, lo_ref, hi_ref, live_ref, opens_ref,
               closes_ref, dead_ref, rows_ref, *rest, of_glu: bool):
    """The weights' gradient over :func:`_group_visits`: ``rows [tile, K]``
    transposed times each ``g [tile, N']``, a group's sum in ``acc``;
    ``of_glu``: gate's and up's, their two cotangents made here of (the
    activated product's, gate, up)."""
    *rest, acc = rest                       # acc: [n, K, N'] float32
    outs = rest[-acc.shape[0]:]
    grads = rest[:-acc.shape[0]]
    i = pl.program_id(1)

    @pl.when(_is(opens_ref[i]))
    def _():
        acc[...] = lax.full(acc.shape, 0, acc.dtype)

    @pl.when(_is(live_ref[i]))
    def _():
        rows = rows_ref[...]
        # (the mask is of 32-bit lanes: select there, then narrow)
        rows = lax.transpose(lax.select(
            _mine(rows.shape, lo_ref, hi_ref, i),
            rows.astype(jnp.float32), lax.full(rows.shape, 0, jnp.float32)
        ).astype(rows.dtype), (1, 0))                           # [K, tile]
        given = [g[...] for g in grads]
        if of_glu:
            given = _through_the_activation(*given)
        for n, g in enumerate(given):
            acc[n] = lax.add(acc[n], lax.dot_general(
                rows, g, (((1,), (0,)), ((), ())),
                precision=_exact(rows.dtype),
                preferred_element_type=jnp.float32))

    @pl.when(_is(closes_ref[i]))
    def _():
        for n, out_ref in enumerate(outs):
            out_ref[...] = acc[n].astype(out_ref.dtype)

    @pl.when(_is(dead_ref[i]))
    def _():
        for out_ref in outs:
            out_ref[...] = lax.full(out_ref.shape, 0, out_ref.dtype)


def _checked(rows_shape, weights, sizes, tile, interpret):
    """(interpret or not, C, K, G, N) of rows ``[C, K]`` against ``weights``
    ``[G, K, N]``, or a ValueError that names the shapes."""
    c, k = rows_shape
    g, _, n = weights[0].shape
    if c % tile or any(w.shape != (g, k, n) for w in weights) \
            or sizes.shape != (g,):
        raise ValueError(
            f"grouped matmul: rows {rows_shape} in tiles of {tile}, weights "
            f"{[w.shape for w in weights]}, sizes {sizes.shape}")
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    return interpret, c, k, g, n


def _params(need: int):
    """(a pass over the list leaves no copy on its way and no sum open, so
    the passes of the column tiles stand alone); ``need`` bytes of VMEM and
    a quarter more, never under Mosaic's 16 MiB default."""
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary"),
        vmem_limit_bytes=max(16 * 2 ** 20, need + need // 4))


def _grouped(rows, weights, sizes, tile, interpret):
    interpret, c, k, g, n = _checked(rows.shape, weights, sizes, tile,
                                     interpret)
    n_tiles = c // tile
    item = weights[0].dtype.itemsize
    columns = _column_tile(k, n, item * len(weights))
    # two buffers of every block, the float32 products beside them
    need = (2 * (tile * k * rows.dtype.itemsize
                 + len(weights) * k * columns * item
                 + tile * columns * rows.dtype.itemsize)
            + 3 * tile * columns * 4)
    return pl.pallas_call(
        functools.partial(_kernel, columns=columns),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=8,
            grid=(n // columns, n_tiles + g),
            in_specs=[pl.BlockSpec((tile, k),
                                   lambda j, i, tile_, *_: (tile_[i], 0))]
            + [pl.BlockSpec(memory_space=pl.ANY)] * len(weights),
            out_specs=pl.BlockSpec((tile, columns),
                                   lambda j, i, tile_, *_: (tile_[i], j)),
            scratch_shapes=[
                pltpu.VMEM((2, len(weights), k, columns), weights[0].dtype),
                pltpu.SemaphoreType.DMA((2, len(weights)))]),
        out_shape=jax.ShapeDtypeStruct((c, n), rows.dtype),
        compiler_params=_params(need),
        interpret=interpret, name=profiling.MOE_GROUPED,
    )(*_visits(sizes.astype(jnp.int32), n_tiles, tile), rows, *weights)


def _grouped_whole(operands, weights, sizes, tile, interpret, form):
    """:func:`_whole_kernel` over ``operands`` by its ``form``: ``"glu"``,
    one ``rows [C, K]`` to (gate, up, their activated product) ``[C, N]``;
    ``"rows"``, one or two ``g [C, N]`` to the rows' gradient ``[C, K]``;
    ``"rows_of_glu"``, (g, gate, up) ``[C, N]`` to the same."""
    glu = form == "glu"
    g, k, n = weights[0].shape
    interpret, c, *_ = _checked((operands[0].shape[0], k), weights, sizes,
                                tile, interpret)
    if any(x.shape != (c, k if glu else n) for x in operands):
        raise ValueError(f"grouped matmul: {[x.shape for x in operands]} "
                         f"against weights {weights[0].shape}")
    n_tiles = c // tile
    dtype, item = operands[0].dtype, weights[0].dtype.itemsize
    inner, outer = (k, n) if glu else (n, k)    # contracted, the result's
    columns = _column_tile(inner, outer, item * len(weights))
    n_out = 3 if glu else 1
    need = (2 * (len(operands) * tile * inner * dtype.itemsize
                 + len(weights) * inner * columns * item
                 + n_out * tile * columns * dtype.itemsize)
            + (2 + n_out) * tile * columns * 4
            + 6 * tile * inner * 4 * (form == "rows_of_glu"))
    whole_rows = pl.BlockSpec((tile, inner),
                              lambda j, i, tile_, *_: (tile_[i], 0))
    a_tile = pl.BlockSpec((tile, columns),
                          lambda j, i, tile_, *_: (tile_[i], j))
    out = pl.pallas_call(
        functools.partial(_whole_kernel, columns=columns, form=form),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=10,
            grid=(outer // columns, n_tiles + g),
            in_specs=[whole_rows] * len(operands)
            + [pl.BlockSpec(memory_space=pl.ANY)] * len(weights),
            out_specs=[a_tile] * n_out,
            scratch_shapes=[
                pltpu.VMEM((2, len(weights)) + ((k, columns) if glu
                                                 else (columns, n)),
                           weights[0].dtype),
                pltpu.SemaphoreType.DMA((2, len(weights)))]),
        out_shape=[jax.ShapeDtypeStruct((c, outer), dtype)] * n_out,
        compiler_params=_params(need),
        interpret=interpret, name=profiling.MOE_GROUPED,
    )(*_whole_visits(sizes.astype(jnp.int32), n_tiles, tile), *operands,
      *weights)
    return out if glu else out[0]


def _grouped_dw(rows, grads, sizes, tile, interpret, dtype, of_glu=False):
    """``rows [C, K]`` transposed times each of ``grads`` ``[C, N]`` group
    by group: as many ``[G, K, N]`` in ``dtype``.  ``of_glu``: ``grads`` are
    (the activated product's cotangent, gate, up) and the two are gate's
    and up's."""
    g = sizes.shape[0]
    (c, k), n = rows.shape, grads[0].shape[1]
    if c % tile or any(x.shape != (c, n) for x in grads):
        raise ValueError(
            f"grouped matmul: rows {rows.shape} in tiles of {tile} against "
            f"{[x.shape for x in grads]}")
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    n_tiles = c // tile
    n_out = 2 if of_glu else len(grads)
    item = jnp.dtype(dtype).itemsize
    # the accumulators take the budget a visit's weight tiles take elsewhere
    columns = _column_tile(k, n, 4 * n_out)
    need = (2 * (tile * k * rows.dtype.itemsize
                 + len(grads) * tile * columns * grads[0].dtype.itemsize
                 + n_out * k * columns * item)
            + n_out * k * columns * 4 * 2 + tile * k * 6
            + 6 * tile * columns * 4 * of_glu)
    return pl.pallas_call(
        functools.partial(_dw_kernel, of_glu=of_glu),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=8,
            grid=(n // columns, n_tiles + g),
            in_specs=[pl.BlockSpec((tile, k),
                                   lambda j, i, tile_, *_: (tile_[i], 0))]
            + [pl.BlockSpec((tile, columns),
                            lambda j, i, tile_, *_: (tile_[i], j))]
            * len(grads),
            out_specs=[pl.BlockSpec(
                (None, k, columns),
                lambda j, i, tile_, group_, *_: (group_[i], 0, j))] * n_out,
            scratch_shapes=[pltpu.VMEM((n_out, k, columns), jnp.float32)]),
        out_shape=[jax.ShapeDtypeStruct((g, k, n), dtype)] * n_out,
        compiler_params=_params(need),
        interpret=interpret, name=profiling.MOE_GROUPED,
    )(*_group_visits(sizes.astype(jnp.int32), n_tiles, tile), rows, *grads)


# Each of the six functions below is jitted by itself, and the custom_vjp
# stands OUTSIDE them: a program's layers then share one tracing and one
# lowering of whichever of them it runs (PERF.md section 6, PR 43), and a
# differentiated program never traces the undifferentiated kernels (a jit
# around the custom_vjp traced them first: two kernels of eight, a fifth of
# what the step's tracing gained, PR 55).
@functools.partial(jax.jit, static_argnums=(3, 4))
def _matmul_alone(rows, weights, sizes, tile, interpret):
    return _grouped(rows, (weights.astype(rows.dtype),), sizes, tile,
                    interpret)


@functools.partial(jax.jit, static_argnums=(3, 4))
def _matmul_fwd(rows, weights, sizes, tile, interpret):
    cast = weights.astype(rows.dtype)
    # (the weights as given are kept for their dtype alone)
    return (_grouped(rows, (cast,), sizes, tile, interpret),
            (rows, cast, sizes, weights))


@functools.partial(jax.jit, static_argnums=(0, 1))
def _matmul_bwd(tile, interpret, kept, g):
    rows, cast, sizes, weights = kept
    d_rows = _grouped_whole((g,), (cast,), sizes, tile, interpret, "rows")
    d_weights, = _grouped_dw(rows, (g,), sizes, tile, interpret,
                             weights.dtype)
    return d_rows, d_weights, None


_matmul = jax.custom_vjp(_matmul_alone, nondiff_argnums=(3, 4))
_matmul.defvjp(_matmul_fwd, _matmul_bwd)


@functools.partial(jax.jit, static_argnums=(4, 5))
def _glu_alone(rows, w_gate, w_up, sizes, tile, interpret):
    return _grouped(rows, (w_gate.astype(rows.dtype),
                           w_up.astype(rows.dtype)), sizes, tile, interpret)


@functools.partial(jax.jit, static_argnums=(4, 5))
def _glu_fwd(rows, w_gate, w_up, sizes, tile, interpret):
    cast = (w_gate.astype(rows.dtype), w_up.astype(rows.dtype))
    gate, up, hidden = _grouped_whole((rows,), cast, sizes, tile, interpret,
                                      "glu")
    return hidden, (rows, cast, sizes, gate, up, w_gate)


@functools.partial(jax.jit, static_argnums=(0, 1))
def _glu_bwd(tile, interpret, kept, g):
    rows, cast, sizes, gate, up, w_gate = kept
    # (gate's and up's cotangents are made in the two kernels, a tile at a
    # time: no array of them, and no pass of XLA's over g, gate and up)
    d_rows = _grouped_whole((g, gate, up), cast, sizes, tile, interpret,
                            "rows_of_glu")
    d_w_gate, d_w_up = _grouped_dw(rows, (g, gate, up), sizes, tile,
                                   interpret, w_gate.dtype, of_glu=True)
    return d_rows, d_w_gate, d_w_up, None


_glu = jax.custom_vjp(_glu_alone, nondiff_argnums=(4, 5))
_glu.defvjp(_glu_fwd, _glu_bwd)


def grouped_matmul(rows, weights, sizes, *, tile: int,
                   interpret: bool | None = None):
    """``rows`` [C, K] times ``weights`` [G, K, N] group by group: rows
    ``[sizes[:g].sum(), sizes[:g + 1].sum())`` times ``weights[g]``, in row
    tiles of ``tile`` (C a multiple of it).  [C, N] in the rows' dtype.
    Weights in another dtype (float32 parameters under bfloat16 rows) are
    multiplied in the rows'; their gradient comes back in their own, from
    the float32 sums with no rounding to the rows' between."""
    return _matmul(rows, weights, sizes, tile, interpret)


def grouped_glu(rows, w_gate, w_up, sizes, *, tile: int,
                interpret: bool | None = None):
    """``silu(rows @ w_gate[g]) * (rows @ w_up[g])`` group by group in one
    kernel that reads a row tile once: both products and the activation in
    float32, rounded once to the rows' dtype.  Weights in another dtype: as
    :func:`grouped_matmul`."""
    return _glu(rows, w_gate, w_up, sizes, tile, interpret)
