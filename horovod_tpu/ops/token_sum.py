"""Rows summed into their tokens: ``out[token[r]] += weight[r] * rows[r]``.

The combine of a sparse layer that walks a share's pairs in blocks
(models/moe.py, ``_walk_held``): C rows in the compute dtype, each the
product of one (token, expert) pair, a float32 gate each, and a float32
``[T, D]`` sum they are added into.  XLA:TPU's scatter-add takes 1.1 us a
row of 7168 (4.7 ms for 4096 rows, PERF.md section 6, PR 43), so the sum is a
Pallas kernel over the rows SORTED BY TOKEN: a chunk of 128 sorted rows then
lies in one or a few blocks of 128 tokens, and a (chunk, block) visit is one
MXU product of a [128, 128] matrix that holds row r's gate at (its token,
r) with the chunk's rows.  The visits are a work list made outside the
kernel, at most ``C / 128 + T / 128`` of them, in token order, so a block's
visits follow each other and its sum stays in VMEM between them.

Exact in float32: a gate is split into three bfloat16 pieces that add up to
it bit for bit, each piece's products with the bfloat16 rows are exact in
float32, and the MXU accumulates in float32.  A row in another dtype than
bfloat16 takes one product at ``Precision.HIGHEST``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from horovod_tpu.utils import profiling

ROWS, TOKENS = 128, 128     # a visit: a chunk of sorted rows, a token block
MAX_LANES = 2048            # of D a program instance works at a time


def _lane_tile(d: int, at_most: int = MAX_LANES) -> int:
    """The widest multiple of 128 that divides ``d`` and is at most
    ``at_most``; ``d`` itself where none does."""
    for lanes in range(min(d, at_most) // 128 * 128, 0, -128):
        if d % lanes == 0:
            return lanes
    return d


def _kernel(chunk_ref, block_ref, first_ref, live_ref, token_ref, weight_ref,
            rows_ref, into_ref, out_ref):
    i = pl.program_id(1)

    @pl.when(first_ref[i] == 1)
    def _():
        out_ref[...] = into_ref[...]

    @pl.when(live_ref[i] == 1)
    def _():
        rows = rows_ref[...]                                    # [ROWS, lanes]
        at = token_ref[...] - block_ref[i] * TOKENS             # [1, ROWS]
        hit = lax.broadcasted_iota(jnp.int32, (TOKENS, ROWS), 0) == at
        weight = weight_ref[...]                                # [1, ROWS] f32
        if rows.dtype == jnp.bfloat16:
            parts = []
            for _ in range(3):
                piece = weight.astype(jnp.bfloat16).astype(jnp.float32)
                weight = weight - piece
                # (the mask is of 32-bit lanes: select there, then narrow)
                placed = jnp.where(hit, piece, 0.0).astype(jnp.bfloat16)
                parts.append(jnp.dot(placed, rows,
                                     preferred_element_type=jnp.float32))
            total = parts[2] + parts[1] + parts[0]              # small first
        else:
            total = jnp.dot(jnp.where(hit, weight, 0.0),
                            rows.astype(jnp.float32),
                            preferred_element_type=jnp.float32,
                            precision=lax.Precision.HIGHEST)
        out_ref[...] += total


def _visits(token, n_chunks: int, n_blocks: int):
    """The work list of ``token`` [C] (sorted; a dead row's is
    ``n_blocks * TOKENS``): per visit the chunk of rows, the token block,
    whether it is the block's first and whether it is a visit at all (the
    list has a static length; what is left of it repeats the last visit
    and does nothing).  In lax's own operations: the list is traced once a
    bucket of every served program, and jnp's wrappers cost more host time
    there than what they wrap."""
    i32 = functools.partial(jnp.asarray, dtype=jnp.int32)
    by_chunk = lax.reshape(token, (n_chunks, ROWS))
    shift = i32(TOKENS.bit_length() - 1)
    lo = lax.shift_right_arithmetic(by_chunk[:, 0], shift)
    last_live = lax.reduce_max(lax.select(
        lax.lt(by_chunk, i32(n_blocks * TOKENS)), by_chunk,
        lax.full_like(by_chunk, -1)), (1,))
    hi = lax.shift_right_arithmetic(last_live, shift)   # -1: no live row
    count = lax.max(hi - lo + i32(1), i32(0))
    ends = lax.cumsum(count)
    starts = ends - count
    total = ends[n_chunks - 1]
    i = lax.iota(jnp.int32, n_chunks + n_blocks)
    at = lax.max(lax.min(i, total - i32(1)), i32(0))
    past = lax.le(lax.broadcast_in_dim(ends, (i.size, n_chunks), (1,)),
                  lax.broadcast_in_dim(at, (i.size, n_chunks), (0,)))
    chunk = lax.min(lax.reduce_sum(past.astype(jnp.int32), (1,)),
                    i32(n_chunks - 1))
    at_chunk = lambda x: x.at[chunk].get(mode="promise_in_bounds")  # noqa: E731
    block = lax.clamp(i32(0), at_chunk(lo) + at - at_chunk(starts),
                      i32(n_blocks - 1))
    first = lax.concatenate([jnp.ones((1,), jnp.int32), lax.ne(
        block[1:], block[:-1]).astype(jnp.int32)], 0)
    return chunk, block, first, lax.lt(i, total).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("interpret",))
def add_rows_by_token(out, rows, weight, token, live, *,
                      interpret: bool | None = None):
    """``out`` [T, D] float32 plus ``weight[r] * rows[r]`` at row
    ``token[r]`` for every r with ``live[r]``; ``rows`` [C, D] with C a
    multiple of 128, ``weight`` [C] float32.  What a row that is not live
    holds counts for nothing."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    c, d = rows.shape
    t = out.shape[0]
    if c % ROWS:
        raise ValueError(f"add_rows_by_token: {c} rows, not a multiple of "
                         f"{ROWS}")
    n_chunks, n_blocks = c // ROWS, -(-t // TOKENS)
    dead = n_blocks * TOKENS
    token, by = lax.sort_key_val(
        jnp.where(live, token, dead).astype(jnp.int32),
        lax.iota(jnp.int32, c))
    # (masked after the gather, so that the two are one pass over the rows)
    in_order = lambda x: x.at[by].get(mode="promise_in_bounds")  # noqa: E731
    rows = jnp.where((token < dead)[:, None], in_order(rows), 0)
    weight = jnp.where(token < dead, in_order(weight.astype(jnp.float32)),
                       0.0)
    lanes = _lane_tile(d)
    a_chunk = pl.BlockSpec((None, 1, ROWS),
                           lambda j, i, chunk, *_: (chunk[i], 0, 0))
    a_block = pl.BlockSpec((TOKENS, lanes),
                           lambda j, i, chunk, block, *_: (block[i], j))
    return pl.pallas_call(
        _kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(d // lanes, n_chunks + n_blocks),
            in_specs=[a_chunk, a_chunk,
                      pl.BlockSpec((ROWS, lanes),
                                   lambda j, i, chunk, *_: (chunk[i], j)),
                      a_block],
            out_specs=a_block),
        out_shape=jax.ShapeDtypeStruct(out.shape, jnp.float32),
        # the sum is updated where it lies: a block no row belongs to is
        # never visited and keeps what it held
        input_output_aliases={7: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret, name=profiling.TOKEN_SUM,
    )(*_visits(token, n_chunks, n_blocks),
      token.reshape(n_chunks, 1, ROWS), weight.reshape(n_chunks, 1, ROWS),
      rows, out)
