"""The flash forward told how many of its rows and keys count (PR 45;
``ops/flash_attention``: ``q_len`` / ``k_len``), interpreted on the CPU at
small tiles: the rows below ``q_len`` are, to the bit, what the call without
the lengths gives them; the rows at and past it come out 0 with lse NEG_INF
whatever the padding held, infinities included, because no tile of the
padding is run; ``q_len`` 0 and S; the lengths traced; a differentiated
bounded call refuses by name; and a call that names no length has the jaxpr
it had before the lengths existed, the backward's too."""

import hashlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.ops.flash_attention import (
    NEG_INF, flash_attention, flash_attention_with_lse, rows_worked)

S, H = 64, 2
TILES = {"block_q": 16, "block_k": 32, "sub": 16}
INF = float("inf")


def _draw(d, d_v, s_k=S):
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(d + s_k), 3)
    return (jax.random.normal(kq, (1, S, H, d), jnp.float32),
            jax.random.normal(kk, (1, s_k, H, d), jnp.float32),
            jax.random.normal(kv, (1, s_k, H, d_v), jnp.float32))


# form -> (key width, value width, keys, what the call is told beside q, k, v)
FORMS = {
    "causal": (16, 16, S, {}),
    "banded": (16, 16, S, {"window": 12}),
    "keys_192_values_128": (24, 16, S, {}),
    "rectangle": (16, 16, 48, {"causal": False}),
}
# the rectangle's valid keys: fewer than the 48 it is handed, inside a tile
RECT_KEYS = 20


def _call(form, q, k, v, q_len=None, k_len=None):
    """(o, lse or None) of one form's call, the lengths as given."""
    told = dict(FORMS[form][3], **TILES)
    if q_len is not None:
        told["q_len"] = q_len
    if k_len is not None:
        told["k_len"] = k_len
    if form == "rectangle":
        return flash_attention_with_lse(q, k, v, **told)
    return flash_attention(q, k, v, **told), None


@pytest.mark.parametrize("traced", [False, True], ids=["static", "traced"])
@pytest.mark.parametrize("q_len", [0, 16, 23, 40, S])
@pytest.mark.parametrize("form", list(FORMS))
def test_the_forward_stops_at_the_lengths(form, q_len, traced):
    d, d_v, s_k, _ = FORMS[form]
    q, k, v = _draw(d, d_v, s_k)
    if form == "rectangle":
        # the unbounded side knows the valid keys too (the parent's mask);
        # what this call adds is the q bound and the sweep's end
        k_len = RECT_KEYS
        want, want_lse = _call(form, q, k, v, k_len=k_len)
    else:
        k_len = q_len       # a prefill: the prompt's length, both ways
        want, want_lse = _call(form, q, k, v)
    # everything the bounded call must not read is infinite: the rows at and
    # past q_len, and the keys and values of every sub-tile wholly past k_len
    # (a sub-tile k_len cuts is run behind the mask, as the parent runs it)
    past_k = -(-k_len // TILES["sub"]) * TILES["sub"]
    q_in = q.at[:, q_len:].set(INF)
    k_in, v_in = k.at[:, past_k:].set(INF), v.at[:, past_k:].set(INF)
    run = lambda q, k, v, a, b: _call(form, q, k, v, a, b)  # noqa: E731
    if traced:
        got, got_lse = jax.jit(run)(q_in, k_in, v_in, jnp.int32(q_len),
                                    jnp.int32(k_len))
    else:
        got, got_lse = run(q_in, k_in, v_in, q_len, k_len)
    got = np.asarray(got)
    assert np.isfinite(got).all()
    np.testing.assert_array_equal(got[:, :q_len], np.asarray(want)[:, :q_len])
    assert (got[:, q_len:] == 0).all()
    if got_lse is not None:
        got_lse = np.asarray(got_lse)
        np.testing.assert_array_equal(got_lse[:, :q_len],
                                      np.asarray(want_lse)[:, :q_len])
        assert (got_lse[:, q_len:] == NEG_INF).all()


def test_a_row_past_the_keys_it_was_told_sees_none():
    """``k_len`` below ``q_len`` (no prefill's, any caller's): a causal row
    sees the keys below both, and the keys past ``k_len`` are not read."""
    q, k, v = _draw(16, 16)
    got = flash_attention(q, k.at[:, 32:].set(INF), v.at[:, 32:].set(INF),
                          q_len=S, k_len=20, **TILES)
    want = flash_attention(q[:, :20], k[:, :20], v[:, :20], **TILES)
    np.testing.assert_allclose(np.asarray(got)[:, :20], np.asarray(want),
                               rtol=1e-6, atol=1e-6)
    assert np.isfinite(np.asarray(got)).all()


def test_the_rows_the_kernel_works():
    assert rows_worked(8323, 16384) == 9216
    assert rows_worked(16384, 16384) == rows_worked(16000, 16384) == 16384
    assert rows_worked(0, 2048) == 0 and rows_worked(1, 2048) == 1024
    assert rows_worked(20, 32) == 32            # the block is the sequence
    assert rows_worked(1025, 1536) == 1536      # capped at the bucket


def test_a_bounded_call_refuses_its_backward_by_name():
    q, k, v = _draw(16, 16)
    loss = lambda q: flash_attention(  # noqa: E731
        q, k, v, q_len=40, k_len=40, **TILES).sum()
    with pytest.raises(NotImplementedError, match="q_len"):
        jax.grad(loss)(q)


def _text(fn, *args):
    """A call's jaxpr with the kernels' source positions taken out."""
    return re.sub(r" at [^\s]+\.py:\d+", "", str(jax.make_jaxpr(fn)(*args)))


# sha256 of the texts below at commit 3113fb8 (PR 44), the parent of the
# lengths: this file's own ``_text`` run in that checkout.  A change to the
# training path's kernels moves them, and says so here.
PARENTS = {
    "causal": "76ecaf006c3956b5",
    "banded": "9b40cc4935a8f5f5",
    "with_lse_at_offsets": "3ef356f206f1d4f6",
    "backward": "341aff3a8e76ef80",
}
NO_LENGTH = {
    "causal": lambda q: flash_attention(q, q, q, **TILES),
    "banded": lambda q: flash_attention(q, q, q, window=12, **TILES),
    "with_lse_at_offsets": lambda q: flash_attention_with_lse(
        q, q, q, q_offset=64, k_offset=0, **TILES),
    "backward": lambda q: jax.grad(lambda q: flash_attention(
        q, q, q, **TILES).astype(jnp.float32).sum())(q),
}


@pytest.mark.parametrize("call", list(NO_LENGTH))
def test_a_call_that_names_no_length_is_the_parent_s(call):
    q = jnp.zeros((1, S, H, 16), jnp.bfloat16)
    text = _text(NO_LENGTH[call], q)
    assert "i32[3]" in text and "i32[4]" not in text     # the kernel's meta
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == PARENTS[call]
    if call == "backward":
        return
    # ... and told a length, the same call is another kernel
    bounded = _text(lambda q: flash_attention(q, q, q, q_len=40, **TILES), q)
    assert "i32[4]" in bounded
