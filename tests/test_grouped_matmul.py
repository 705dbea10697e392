"""The walked experts' grouped matmul (PR 51, ``ops/grouped_matmul.py``,
interpreted here as ``add_rows_by_token`` is) against ``lax.ragged_dot`` on
the grouped rows: what a row past the last group holds is nobody's.

float32 cases agree to rounding (one sum in another order); bfloat16 cases to
one rounding of the result.  The fused gate-and-up form computes the
activation in float32 and rounds once, so it lies within one bfloat16
rounding of today's ``silu(ragged_dot) * ragged_dot``, which rounds three
times, and nearer to the float32 arithmetic than that does."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from horovod_tpu.ops import grouped_matmul as gm

C, K, N = 512, 64, 48

# name: group sizes over C = 512 rows
SIZES = {
    "empty_groups": [0, 100, 0, 0, 60, 93, 0],
    "a_group_across_several_tiles": [20, 400, 30],
    "several_groups_in_one_tile": [3, 5, 7, 2, 9, 1, 4, 6],
    "every_row_in_one_group": [0, 512, 0],
    "no_row_in_any": [0, 0, 0, 0],
    "a_sum_that_is_no_whole_tile": [70, 70, 71],
    "groups_that_end_on_tile_edges": [128, 64, 64, 256],
}


def drawn(sizes, dtype, seed=0):
    rng = np.random.default_rng(seed)
    g = len(sizes)
    rows = jnp.asarray(rng.standard_normal((C, K)), dtype)
    w = [jnp.asarray(rng.standard_normal((g, K, N)) * K ** -0.5, dtype)
         for _ in range(2)]
    return rows, w, jnp.asarray(sizes, jnp.int32)


def f32(x):
    return np.asarray(x.astype(jnp.float32))


@pytest.mark.parametrize("tile", [32, 128])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("case", sorted(SIZES))
def test_the_kernel_equals_ragged_dot_on_the_grouped_rows(case, dtype, tile):
    rows, (w, _), sizes = drawn(SIZES[case], dtype)
    live = int(sizes.sum())
    got = gm.grouped_matmul(rows, w, sizes, tile=tile)
    assert got.shape == (C, N) and got.dtype == dtype
    with jax.default_matmul_precision("highest"):
        want = lax.ragged_dot(rows, w, sizes)
    # float32: a sum in another order; bfloat16: the same float32 sum
    # rounded once on both sides, up to an ulp where the orders differ
    tol = 2e-6 if dtype == jnp.float32 else 2 ** -8
    size = max(float(np.abs(f32(want[:live])).max(initial=0.0)), 1.0)
    assert float(np.abs(f32(got[:live]) - f32(want[:live])).max(
        initial=0.0)) <= tol * size
    # the work list: never longer than C / tile + G, and as many live visits
    # as (row tile, group) pairs meet
    row_tile, group, lo, hi, is_live, opens, buffer, following = (
        np.asarray(v) for v in gm._visits(sizes, C // tile, tile))
    assert is_live.size == C // tile + len(SIZES[case])
    ends = np.cumsum(SIZES[case])
    meets = sum(len(range(s // tile, (e - 1) // tile + 1))
                for s, e in zip(ends - SIZES[case], ends) if e > s)
    assert int(is_live.sum()) == meets
    assert int(gm.visited_rows(sizes, tile)) == meets * tile
    assert int((hi - lo)[is_live == 1].sum()) == live
    # in group order, a row tile's visits side by side
    assert (np.diff(group[is_live == 1]) >= 0).all()
    assert (np.diff(row_tile[is_live == 1]) >= 0).all()
    # what is left of the list repeats the last visit
    if 0 < meets < is_live.size:
        assert (row_tile[meets:] == row_tile[meets - 1]).all()
        assert (group[meets:] == group[meets - 1]).all()
    # a group's weights: waited for at its first visit and no other, in the
    # buffer the group before it does not hold, the next group that holds a
    # row named there (-1 after the last), so every copy started is awaited
    held = [g for g, n in enumerate(SIZES[case]) if n]
    firsts = np.flatnonzero(opens)
    assert (is_live[firsts] == 1).all()
    assert list(group[firsts]) == held
    assert list(following[firsts]) == held[1:] + [-1] * bool(held)
    assert list(buffer[firsts]) == [n % 2 for n in range(len(held))]
    for first, last in zip(firsts, list(firsts[1:]) + [meets]):
        assert (buffer[first:last] == buffer[first]).all()


@pytest.mark.parametrize("tile", [32, 128])
@pytest.mark.parametrize("case", ["empty_groups",
                                  "several_groups_in_one_tile",
                                  "a_group_across_several_tiles"])
def test_the_fused_gate_and_up_is_todays_form_rounded_once(case, tile):
    rows, (w_gate, w_up), sizes = drawn(SIZES[case], jnp.bfloat16, seed=1)
    live = int(sizes.sum())
    got = gm.grouped_glu(rows, w_gate, w_up, sizes, tile=tile)
    assert got.dtype == jnp.bfloat16
    grouped = lambda x, w: lax.ragged_dot(x, w, sizes)  # noqa: E731
    today = jax.nn.silu(grouped(rows, w_gate)) * grouped(rows, w_up)
    with jax.default_matmul_precision("highest"):
        exact = (lambda a, b: jax.nn.silu(a) * b)(*(lax.ragged_dot(
            rows.astype(jnp.float32), w.astype(jnp.float32), sizes)
            for w in (w_gate, w_up)))
    got, today, exact = f32(got[:live]), f32(today[:live]), f32(exact[:live])
    # within one bfloat16 rounding (2 ** -8 relative) of the exact value,
    # where today's three roundings lie within three
    scale = np.maximum(np.abs(exact), 2.0 ** -6)
    assert float((np.abs(got - exact) / scale).max()) <= 2 ** -8
    assert float((np.abs(today - exact) / scale).max()) <= 5 * 2 ** -8
    assert float((np.abs(got - today) / scale).max()) <= 6 * 2 ** -8
    assert np.abs(got - exact).mean() <= np.abs(today - exact).mean()
    # float32 rows: the two kernels are one arithmetic
    rows32, (g32, u32), _ = drawn(SIZES[case], jnp.float32, seed=1)
    fused = gm.grouped_glu(rows32, g32, u32, sizes, tile=tile)
    apart = jax.nn.silu(gm.grouped_matmul(rows32, g32, sizes, tile=tile)) \
        * gm.grouped_matmul(rows32, u32, sizes, tile=tile)
    assert float(jnp.abs(fused[:live] - apart[:live]).max()) < 1e-6


def test_a_weight_too_wide_for_vmem_is_cut_by_columns():
    # the three served shapes: gate and up side by side, then down
    assert gm._column_tile(2560, 768, 2 * 2) == 768
    assert gm._column_tile(768, 2560, 2) == 2560
    for k, n, weights in [(4096, 4096, 2), (4096, 4096, 1), (7168, 2048, 2),
                          (2048, 7168, 1)]:
        columns = gm._column_tile(k, n, 2 * weights)
        assert n % columns == 0 and columns % 128 == 0 and columns < n
        assert weights * k * columns * 2 <= gm.WEIGHT_TILE_BYTES
        assert 2 * weights * k * columns * 2 > gm.WEIGHT_TILE_BYTES \
            or n % (2 * columns)
    # no multiple of 128 divides it (a test's width): whole
    assert gm._column_tile(64, 48, 4) == 48
    with pytest.raises(ValueError, match="in tiles of 128"):
        gm.grouped_matmul(jnp.zeros((100, 8)), jnp.zeros((2, 8, 8)),
                          jnp.zeros((2,), jnp.int32), tile=128)


def test_each_pass_over_a_column_tile_fetches_its_own_weights(monkeypatch):
    """N in two column tiles: the list is walked once a tile, each pass
    starting with nothing on its way and leaving nothing."""
    monkeypatch.setattr(gm, "WEIGHT_TILE_BYTES", 64 * 128 * 2 * 2)
    jax.clear_caches()
    rng = np.random.default_rng(2)
    sizes = jnp.asarray([0, 100, 3, 0, 60, 93], jnp.int32)
    rows = jnp.asarray(rng.standard_normal((256, 64)), jnp.bfloat16)
    w_gate, w_up = (jnp.asarray(rng.standard_normal((6, 64, 256)) / 8,
                                jnp.bfloat16) for _ in range(2))
    assert gm._column_tile(64, 256, 2 * 2) == 128
    try:
        got = gm.grouped_glu(rows, w_gate, w_up, sizes, tile=64)
        down = gm.grouped_matmul(rows, w_gate, sizes, tile=64)
    finally:        # (the jitted functions read the constant as they trace)
        jax.clear_caches()
    with jax.default_matmul_precision("highest"):
        exact = (lambda a, b: jax.nn.silu(a) * b)(*(lax.ragged_dot(
            rows.astype(jnp.float32), w.astype(jnp.float32), sizes)
            for w in (w_gate, w_up)))
        plain = lax.ragged_dot(rows, w_gate, sizes)
    scale = np.maximum(np.abs(f32(exact[:256])), 2.0 ** -6)
    assert float((np.abs(f32(got) - f32(exact)) / scale).max()) <= 2 ** -8
    assert float(np.abs(f32(down) - f32(plain)).max()) <= 2 ** -8 * max(
        float(np.abs(f32(plain)).max()), 1.0)


# -- the backward (PR 55) ---------------------------------------------------
def _layer(grouped_glu, grouped_matmul, cotangent, live):
    """sum(cotangent * down(glu(rows))) over the live rows, through the given
    two grouped functions."""
    def loss(rows, w_gate, w_up, w_down):
        out = grouped_matmul(grouped_glu(rows, w_gate, w_up), w_down)
        return (out[:live].astype(jnp.float32) * cotangent[:live]).sum()
    return jax.grad(loss, argnums=(0, 1, 2, 3))


def _both_gradients(sizes, dtype, tile, seed=3):
    """(ours, ``jax.grad`` of the ``ragged_dot`` form at "highest") of rows
    [C, K], gate and up [G, K, N] and down [G, N, K]."""
    rows, (w_gate, w_up), sizes = drawn(sizes, dtype, seed)
    rng = np.random.default_rng(seed + 1)
    w_down = jnp.asarray(rng.standard_normal((len(sizes), N, K)) * N ** -0.5,
                         dtype)
    cotangent = jnp.asarray(rng.standard_normal((C, K)), jnp.float32)
    live = int(sizes.sum())
    ours = _layer(
        lambda x, a, b: gm.grouped_glu(x, a, b, sizes, tile=tile),
        lambda x, w: gm.grouped_matmul(x, w, sizes, tile=tile),
        cotangent, live)(rows, w_gate, w_up, w_down)
    ragged = functools.partial(lax.ragged_dot, group_sizes=sizes,
                               precision="highest")
    want = _layer(
        lambda x, a, b: jax.nn.silu(ragged(x, a)) * ragged(x, b), ragged,
        cotangent, live)(rows, w_gate, w_up, w_down)
    return ours, want, live


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("case", sorted(SIZES))
def test_the_backward_equals_the_gradient_of_the_ragged_dot_form(case, dtype):
    """The rows' gradient by the forward's work list and the weights'
    accumulated a group: every leaf against ``jax.grad`` of ``silu(ragged_dot)
    * ragged_dot`` into ``ragged_dot``; the rows behind the last group get
    exactly 0, and so does every weight of a group without a row."""
    ours, want, live = _both_gradients(SIZES[case], dtype, tile=128)
    # float32: sums in another order; bfloat16: each product rounded once
    # here, three times there, and the cotangents after them
    tol = 2e-6 if dtype == jnp.float32 else 2 ** -6
    for got, exact in zip(ours, want):
        assert got.shape == exact.shape and got.dtype == dtype
        size = max(float(np.abs(f32(exact)).max()), 1e-3)
        assert float(np.abs(f32(got) - f32(exact)).max()) <= tol * size
    assert float(np.abs(f32(ours[0][live:])).max(initial=0.0)) == 0.0
    for g, n in enumerate(SIZES[case]):
        if n == 0:
            assert all(float(np.abs(f32(d[g])).max()) == 0.0
                       for d in ours[1:])


def test_the_backward_of_a_weight_cut_by_columns(monkeypatch):
    """K and N in two column tiles each: the rows' gradient cuts the weights'
    ROWS, the weights' gradient its accumulators, each pass over the list
    standing alone."""
    monkeypatch.setattr(gm, "WEIGHT_TILE_BYTES", 64 * 128 * 2 * 2)
    jax.clear_caches()
    rng = np.random.default_rng(5)
    sizes = jnp.asarray([0, 100, 3, 0, 60, 29], jnp.int32)
    rows = jnp.asarray(rng.standard_normal((256, 256)), jnp.bfloat16)
    w = jnp.asarray(rng.standard_normal((6, 256, 256)) / 16, jnp.bfloat16)
    cotangent = jnp.asarray(rng.standard_normal((256, 256)), jnp.float32)
    assert gm._column_tile(256, 256, 2) == 128
    loss = lambda fn: lambda x, w: (  # noqa: E731
        fn(x, w)[:192].astype(jnp.float32) * cotangent[:192]).sum()
    try:
        ours = jax.grad(loss(lambda x, w: gm.grouped_matmul(
            x, w, sizes, tile=64)), argnums=(0, 1))(rows, w)
    finally:        # (the jitted functions read the constant as they trace)
        jax.clear_caches()
    want = jax.grad(loss(lambda x, w: lax.ragged_dot(
        x, w, sizes, precision="highest")), argnums=(0, 1))(rows, w)
    for got, exact in zip(ours, want):
        assert float(np.abs(f32(got) - f32(exact)).max()) \
            <= 2 ** -7 * float(np.abs(f32(exact)).max())
    assert float(np.abs(f32(ours[0][192:])).max()) == 0.0
    assert float(np.abs(f32(ours[1][jnp.asarray([0, 3])])).max()) == 0.0


def test_the_backward_lists_leave_no_block_unwritten():
    """The two work lists behind the forward's: every row tile is some
    visit's first or a dead visit's, every group closes once or gets a dead
    visit, and what is left repeats the last block written."""
    tile, n_tiles = 32, C // 32
    for sizes in SIZES.values():
        given = jnp.asarray(sizes, jnp.int32)
        row_tile, _, _, _, live, *_, fresh, dead = (
            np.asarray(v) for v in gm._whole_visits(given, n_tiles, tile))
        written = np.concatenate([row_tile[fresh == 1], row_tile[dead == 1]])
        assert sorted(written) == list(range(n_tiles))
        assert not (live & dead).any() and not (fresh & ~live).any()
        after = np.flatnonzero((live | dead) == 0)
        assert (row_tile[after] == n_tiles - 1).all() or not dead.any()
        _, group, _, _, live, opens, closes, dead = (
            np.asarray(v) for v in gm._group_visits(given, n_tiles, tile))
        assert sorted(np.concatenate([group[closes == 1], group[dead == 1]])
                      ) == list(range(len(sizes)))
        assert list(group[opens == 1]) == list(group[closes == 1]) \
            == [g for g, n in enumerate(sizes) if n]
        assert list(group[dead == 1]) == [g for g, n in enumerate(sizes)
                                          if not n]
        last = np.flatnonzero(live | dead).max()
        assert (group[last:] == group[last]).all()


def test_float32_weights_under_bfloat16_rows_get_their_gradient_unrounded():
    """The training layer's parameters: float32 leaves multiplied in the
    rows' bfloat16.  The forward is the product with the cast weights to the
    bit; the weights' gradient comes back float32 from the float32 sums, so
    it lies nearer the exact one than the bfloat16 gradient cast up."""
    rows, (w, _), sizes = drawn(SIZES["a_group_across_several_tiles"],
                                jnp.bfloat16, seed=6)
    w32 = w.astype(jnp.float32) * (1 + 2.0 ** -10)  # not bfloat16's own
    cotangent = jnp.asarray(        # one the result's dtype holds
        np.random.default_rng(7).standard_normal((C, N)),
        jnp.bfloat16).astype(jnp.float32)
    live = int(sizes.sum())
    loss = lambda x, w: (gm.grouped_matmul(  # noqa: E731
        x, w, sizes, tile=128)[:live].astype(jnp.float32)
        * cotangent[:live]).sum()
    cast = w32.astype(jnp.bfloat16)
    assert bool((gm.grouped_matmul(rows, w32, sizes, tile=128)[:live]
                 == gm.grouped_matmul(rows, cast, sizes, tile=128)[:live]
                 ).all())
    d_rows, d_w = jax.grad(loss, argnums=(0, 1))(rows, w32)
    rounded_rows, rounded = jax.grad(loss, argnums=(0, 1))(rows, cast)
    assert d_w.dtype == jnp.float32 and rounded.dtype == jnp.bfloat16
    assert bool((d_rows == rounded_rows).all())
    exact = jax.grad(lambda w: (lax.ragged_dot(
        rows.astype(jnp.float32), w, sizes, precision="highest")[:live]
        * cotangent[:live]).sum())(cast.astype(jnp.float32))
    assert float(np.abs(f32(d_w) - f32(exact)).max()) \
        <= 1e-5 * float(np.abs(f32(exact)).max())
    assert float(np.abs(f32(rounded) - f32(exact)).max()) \
        > 1e-3 * float(np.abs(f32(exact)).max())
