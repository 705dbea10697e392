"""Grouped matmul over a work list of small row tiles:
``out[r] = rows[r] @ weights[group of r]``.

The three products of a sparse layer in a served prefill (models/moe.py): a
block of the walk of a share's pairs (``_walk_held``) or all the rows a
serving layer carries (``_experts_in_tiles``: every expert held, a bucket's
rows): C rows sorted by expert, ``sizes[g]`` of them expert g's and the
rest behind the last group, each expert a ``[K, N]`` matrix.  XLA:TPU's
kernels for ``lax.ragged_dot`` work 512-row tiles, and every (row tile,
group) pair that meets is one whole 512-row product: at 60-250 rows an
expert a layer pays for two to nine times its rows (PERF.md section 6, PR
51 and PR 53).  Here the row tile is
the caller's, far smaller, and the grid runs over a work list of (row tile,
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
the kernel left there.  No backward: a served prefill has none.
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
    starts = ends - sizes
    rows = jnp.int32(tile)
    first = lax.div(starts, rows)
    count = lax.select(lax.gt(sizes, jnp.int32(0)),
                       lax.div(ends - jnp.int32(1), rows) - first
                       + jnp.int32(1), lax.full_like(sizes, 0))
    return starts, ends, first, count


def visited_rows(sizes, tile: int):
    """Rows of the row tiles the work list visits: ``tile`` times its
    visits.  ``sizes.sum()`` would waste nothing."""
    return jnp.int32(tile) * lax.reduce_sum(
        _tiles_by_group(sizes, tile)[3], (0,))


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
    total = through[g - 1]
    i = lax.iota(jnp.int32, n_tiles + g)
    at = lax.max(lax.min(i, total - i32(1)), i32(0))
    past = lax.le(lax.broadcast_in_dim(through, (i.size, g), (1,)),
                  lax.broadcast_in_dim(at, (i.size, g), (0,)))
    group = lax.min(lax.reduce_sum(past.astype(jnp.int32), (1,)), i32(g - 1))
    of_group = lambda x: x.at[group].get(mode="promise_in_bounds")  # noqa: E731
    opening = of_group(through - count)     # the group's first visit
    row_tile = lax.clamp(i32(0), of_group(first) + at - opening,
                         i32(n_tiles - 1))
    top = row_tile * i32(tile)
    inside = lambda x: lax.clamp(i32(0), of_group(x) - top, i32(tile))  # noqa: E731
    live = lax.lt(i, total)
    # by group: its place among the groups that hold a row, and the next of
    # them (the least index past it, by a running minimum from the right)
    holds = lax.gt(count, i32(0))
    place = lax.cumsum(holds.astype(jnp.int32)) - i32(1)
    later = lax.cummin(lax.select(holds, lax.iota(jnp.int32, g),
                                  lax.full_like(count, g)), reverse=True)
    following = lax.concatenate([later[1:], jnp.full((1,), g, jnp.int32)], 0)
    following = lax.select(lax.lt(following, i32(g)), following,
                           lax.full_like(following, -1))
    opens = lax.bitwise_and(live, lax.eq(at, opening))
    return (row_tile, group, inside(starts), inside(ends),
            live.astype(jnp.int32), opens.astype(jnp.int32),
            of_group(lax.rem(place, i32(2))), of_group(following))


def _kernel(tile_ref, group_ref, lo_ref, hi_ref, live_ref, opens_ref,
            buffer_ref, following_ref, rows_ref, *rest, columns: int):
    *weights, out_ref, held, arrived = rest     # weights: whole, in HBM
    j, i = pl.program_id(0), pl.program_id(1)
    left = j * columns
    if columns % 128 == 0:
        left = pl.multiple_of(left, 128)

    def copies(group, buffer):
        """``group``'s column tile j of every weight into ``buffer``."""
        return [pltpu.make_async_copy(
            w.at[group, :, pl.ds(left, columns)], held.at[buffer, n],
            arrived.at[buffer, n]) for n, w in enumerate(weights)]

    # a pass over the list starts with nothing on its way
    @pl.when((i == 0) & (live_ref[0] == 1))
    def _():
        for copy in copies(group_ref[0], 0):
            copy.start()

    @pl.when(opens_ref[i] == 1)
    def _():
        for copy in copies(group_ref[i], buffer_ref[i]):
            copy.wait()

        # the other buffer's group has had its last visit: the next group's
        # weights go there while this one's visits run
        @pl.when(following_ref[i] >= 0)
        def _():
            for copy in copies(following_ref[i], 1 - buffer_ref[i]):
                copy.start()

    @pl.when(live_ref[i] == 1)
    def _():
        rows = rows_ref[...]                                    # [tile, K]
        # (float32 operands at every pass: what the MXU keeps of them at one
        # pass is bfloat16's)
        exact = lax.Precision.HIGHEST if rows.dtype == jnp.float32 else None
        product = lambda n: jnp.dot(                            # noqa: E731
            rows, held[buffer_ref[i], n], precision=exact,
            preferred_element_type=jnp.float32)
        acc = product(0)                                        # [tile, N']
        if len(weights) == 2:           # the GLU: float32, rounded once
            acc = jax.nn.silu(acc) * product(1)
        row = lax.broadcasted_iota(jnp.int32, acc.shape, 0)
        mine = (row >= lo_ref[i]) & (row < hi_ref[i])
        # (the mask is of 32-bit lanes: select there, then narrow)
        out_ref[...] = jnp.where(mine, acc, out_ref[...].astype(jnp.float32)
                                 ).astype(out_ref.dtype)


def _grouped(rows, weights, sizes, tile, interpret):
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    c, k = rows.shape
    g, _, n = weights[0].shape
    if c % tile or any(w.shape != (g, k, n) for w in weights) \
            or sizes.shape != (g,):
        raise ValueError(
            f"grouped matmul: rows {rows.shape} in tiles of {tile}, weights "
            f"{[w.shape for w in weights]}, sizes {sizes.shape}")
    n_tiles = c // tile
    item = weights[0].dtype.itemsize
    columns = _column_tile(k, n, item * len(weights))
    # two buffers of every block, the float32 products beside them, and a
    # quarter more; never under Mosaic's 16 MiB default
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
        # (a pass over the list leaves no copy on its way, so the passes
        # of the column tiles stand alone)
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=max(16 * 2 ** 20, need + need // 4)),
        interpret=interpret, name=profiling.MOE_GROUPED,
    )(*_visits(sizes.astype(jnp.int32), n_tiles, tile), rows, *weights)


@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def grouped_matmul(rows, weights, sizes, *, tile: int,
                   interpret: bool | None = None):
    """``rows`` [C, K] times ``weights`` [G, K, N] group by group: rows
    ``[sizes[:g].sum(), sizes[:g + 1].sum())`` times ``weights[g]``, in row
    tiles of ``tile`` (C a multiple of it).  [C, N] in the rows' dtype."""
    return _grouped(rows, (weights,), sizes, tile, interpret)


@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def grouped_glu(rows, w_gate, w_up, sizes, *, tile: int,
                interpret: bool | None = None):
    """``silu(rows @ w_gate[g]) * (rows @ w_up[g])`` group by group in one
    kernel that reads a row tile once: both products and the activation in
    float32, rounded once to the rows' dtype."""
    return _grouped(rows, (w_gate, w_up), sizes, tile, interpret)
