"""``ops/moe_rows``: the training layer's two row moves and their backwards
as Pallas kernels (interpreted here), against the gathers of
``models/moe.py``'s ``_dispatch`` / ``_permute`` that they replace."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.models import moe
from horovod_tpu.ops import moe_rows

EXPERTS = 8
# (k, routing, pairs T*k, rows' dtype, columns): 2048 bfloat16 columns are a
# whole 8-line tile a row, as on the chip; 96 is padded to whole lines
CASES = [
    (1, "random", 512, jnp.bfloat16, 256),
    (2, "random", 512, jnp.float32, 128),
    (8, "random", 512, jnp.bfloat16, 2048),
    (8, "random", 4096, jnp.bfloat16, 256),
    (8, "an_empty_expert", 512, jnp.bfloat16, 256),
    (8, "one_expert_holds_every_pair", 512, jnp.bfloat16, 256),
    (2, "an_empty_expert", 4096, jnp.float32, 128),
    (1, "one_expert_holds_every_pair", 512, jnp.bfloat16, 256),
    (2, "one_expert_holds_every_pair", 640, jnp.bfloat16, 96),
]
IDS = [f"top{k}-{routing}-{pairs}-{jnp.dtype(dtype).name}-{d}"
       for k, routing, pairs, dtype, d in CASES]
cases = pytest.mark.parametrize("k, routing, pairs, dtype, d", CASES, ids=IDS)


def routed(k, routing, pairs, seed=0):
    """(order, inverse) of ``pairs`` (token, expert) pairs as the layer
    sorts them: by expert, an expert's by token."""
    rng = np.random.default_rng(seed)
    if routing == "one_expert_holds_every_pair":
        expert = np.full(pairs, 3)
    else:
        expert = rng.integers(0, EXPERTS, pairs)
        if routing == "an_empty_expert":
            expert[expert == 2] = 5
    order = jnp.argsort(jnp.asarray(expert, jnp.int32), stable=True)
    return order, jnp.argsort(order)


def drawn(pairs, k, d, dtype, seed=1):
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    t = pairs // k
    return (jax.random.normal(keys[0], (t, d), dtype),          # tokens
            jax.random.normal(keys[1], (pairs, d), dtype),      # sorted rows
            jax.random.uniform(keys[2], (t, k), jnp.float32),   # gates
            jax.random.normal(keys[3], (t, d), jnp.float32))    # a cotangent


@cases
def test_fetch_moves_rows_and_spread_scales_them_bit_for_bit(k, routing, pairs,
                                                             dtype, d):
    order, inverse = routed(k, routing, pairs)
    x, y, gates, ct = drawn(pairs, k, d, dtype)
    t, src = pairs // k, order // k
    assert bool((moe_rows.dispatch_rows(x, order, k) == x[src]).all())
    # the combine's backward: gates * dout rounded once, in pair order, and
    # the gates' gradient, from the tiles the forward's send left
    tiles = moe_rows._send(y, order, None)
    spread, d_gates = moe_rows._spread(ct, gates, tiles, dtype, None)
    got = moe_rows._fetch(spread, order, d, dtype, None)
    s = gates.reshape(-1)[order]
    assert got.dtype == dtype and d_gates.dtype == jnp.float32
    assert bool((got == (s[:, None] * ct[src]).astype(dtype)).all())
    by_token = y[inverse].reshape(t, k, d).astype(jnp.float32)
    want = (ct[:, None, :] * by_token).sum(axis=2)
    assert float(jnp.abs(d_gates - want).max()) \
        < 1e-5 * float(jnp.abs(ct[:, None, :] * by_token).sum(2).max())


@cases
def test_send_and_sum_are_the_float32_sum_over_k_rounded_once(k, routing,
                                                              pairs, dtype, d):
    order, inverse = routed(k, routing, pairs)
    _, y, gates, _ = drawn(pairs, k, d, dtype)
    t = pairs // k
    by_token = y[inverse].reshape(t, k, d).astype(jnp.float32)
    got = moe_rows.combine_rows(y, gates, order)
    want = (by_token * gates[..., None]).sum(1)
    assert got.dtype == jnp.float32 and got.shape == (t, d)
    assert float(jnp.abs(got - want).max()) \
        < 4e-7 * k * float(jnp.abs(want).max())
    # handed on in the rows' dtype: the same sum, rounded once in the kernel
    rounded = moe_rows.combine_rows(y, gates, order, dtype)
    assert rounded.dtype == dtype
    assert bool((rounded == got.astype(dtype)).all())
    # no weights, the rows' dtype: the dispatch's backward to the letter
    plain = moe_rows._sum(moe_rows._send(y, order, None), None, k, d, dtype,
                          dtype, None)
    want, = jax.vjp(lambda x: moe._dispatch(x, order, inverse, k),
                    jnp.zeros((t, d), dtype))[1](y)
    assert plain.dtype == dtype
    if k <= 2:      # one addition: no order to differ in
        assert bool((plain == want).all())
    spacing = 2.0 ** -7 if dtype == jnp.bfloat16 else 2.0 ** -22
    assert float(jnp.abs(plain.astype(jnp.float32)
                         - want.astype(jnp.float32)).max()) \
        <= spacing * float(jnp.abs(want.astype(jnp.float32)).max())


@cases
def test_both_backwards_and_the_gates_gradient_are_the_parents(
        k, routing, pairs, dtype, d):
    order, inverse = routed(k, routing, pairs)
    x, y, gates, ct = drawn(pairs, k, d, dtype)
    t = pairs // k

    # (the sum handed on in float32, as beside shared experts, or in the
    # rows' dtype, as the layer's result: the cotangent comes back in it)
    out_dtype = jnp.float32 if k == 2 else dtype

    def ours(x, y, gates):
        rows = moe_rows.dispatch_rows(x, order, k) * y
        out = moe_rows.combine_rows(rows, gates, order, out_dtype)
        return (out.astype(jnp.float32) * ct).sum()

    def parents(x, y, gates):
        rows = moe._dispatch(x, order, inverse, k) * y
        by_token = moe._permute(rows, inverse, order).reshape(t, k, d)
        out = (by_token.astype(jnp.float32) * gates[..., None]).sum(1)
        return (out.astype(out_dtype).astype(jnp.float32) * ct).sum()

    got = jax.jit(jax.grad(ours, argnums=(0, 1, 2)))(x, y, gates)
    want = jax.jit(jax.grad(parents, argnums=(0, 1, 2)))(x, y, gates)
    # one rounding of the rows' dtype where the sums' order differs
    spacing = 2.0 ** -7 if dtype == jnp.bfloat16 else 2.0 ** -20
    for name, a, b in zip(("tokens", "rows", "gates"), got, want):
        assert a.dtype == b.dtype and a.shape == b.shape, name
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
        bound = (2.0 ** -20 if name == "gates" else spacing) \
            * float(jnp.abs(b).max())
        assert float(jnp.abs(a - b).max()) <= bound, name
    # the rows' cotangent has no sum in it: the same bits
    assert bool((got[1] == want[1]).all())


def test_rows_that_are_no_whole_tiles_are_refused_by_name():
    order, _ = routed(2, "random", 200)
    x, y, gates, _ = drawn(200, 2, 128, jnp.float32)
    with pytest.raises(ValueError, match="multiple of 128"):
        moe_rows.dispatch_rows(x, order, 2)
    with pytest.raises(ValueError, match="multiple of 128"):
        moe_rows.combine_rows(y, gates, order)
