"""The Mamba-2 mixer (``models/mamba.py``) and its chunked scan
(``ops/ssd_scan.py``), in both its forms (XLA's ops at the tiny widths, the
two Pallas kernels, interpreted here, at widths that meet their tiling rule):
the scan against the recurrence itself, one position after the other, and
against the quadratic form over the whole sequence (both written here,
sharing nothing with the program's chunks), values and the gradient of every
input; the two forms against each other; and the convolution's causality.  The
mixer as a layer of ``models.Transformer`` is ``tests/test_mamba2_model.py``'s
(a file of its own so that ``--dist loadfile`` can give the two to two
workers)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.models import mamba
from horovod_tpu.ops.causal_conv import (causal_conv, causal_conv_silu,
                                          conv_form)
from horovod_tpu.ops import ssd_scan as ssd_scan_module
from horovod_tpu.ops.ssd_scan import head_block, scan_form, ssd_scan

H, P, G, N = 4, 8, 2, 16
TINY = dict(h=H, p=P, g=G, n=N)                 # the XLA form
KERNEL = dict(h=4, p=64, n=128, batch=1)        # the kernels: chunk 128


def inputs(s, dtype, seed=0, batch=2, h=H, p=P, g=G, n=N):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    x = jax.random.normal(ks[0], (batch, s, h, p), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (batch, s, h)) - 2.0)
    a = -jnp.exp(jax.random.uniform(ks[2], (h,), minval=0.0, maxval=2.7))
    b = jax.random.normal(ks[3], (batch, s, g, n), jnp.float32)
    c = jax.random.normal(ks[4], (batch, s, g, n), jnp.float32)
    d = jax.random.normal(ks[5], (h,), jnp.float32)
    return (x.astype(dtype), dt, a, b.astype(dtype), c.astype(dtype), d)


def form_of(args, chunk):
    x, b = args[0], args[3]
    return scan_form(x.shape[1], chunk, x.shape[2], b.shape[2], x.shape[3],
                     b.shape[3])


def xla_form(*args):
    """``ssd_scan`` held to XLA's ops whatever the shapes."""
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(ssd_scan_module, "scan_form", lambda *shape: "xla")
        return ssd_scan(*args)


def sequential(x, dt, a, b, c, d):
    """H_t = exp(dt_t a) H_{t-1} + dt_t x_t B_t';  y_t = H_t C_t + D x_t."""
    h, p = x.shape[2:]
    n = b.shape[-1]
    x, b, c = (v.astype(jnp.float32) for v in (x, b, c))
    b, c = (jnp.repeat(v, h // v.shape[2], axis=2) for v in (b, c))

    def one(x, dt, b, c):
        def step(state, t):
            x_t, dt_t, b_t, c_t = t
            state = state * jnp.exp(dt_t * a)[:, None, None] \
                + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :]
            return state, jnp.einsum("hpn,hn->hp", state, c_t)
        return jax.lax.scan(step, jnp.zeros((h, p, n)), (x, dt, b, c))[1]

    return jax.vmap(one)(x, dt, b, c) + d[None, None, :, None] * x


def quadratic(x, dt, a, b, c, d):
    """y_t = sum_{s<=t} exp(sum_{s<r<=t} dt_r a) (C_t . B_s) dt_s x_s + D x_t."""
    x, b, c = (v.astype(jnp.float32) for v in (x, b, c))
    b, c = (jnp.repeat(v, x.shape[2] // v.shape[2], axis=2) for v in (b, c))
    s = x.shape[1]
    cum = jnp.cumsum(dt * a, axis=1)
    lower = jnp.tril(jnp.ones((s, s), bool))[None, :, :, None]
    seg = jnp.where(lower, cum[:, :, None] - cum[:, None, :], -jnp.inf)
    scores = jnp.einsum("bthn,bshn->btsh", c, b) * jnp.exp(seg)
    return jnp.einsum("btsh,bshp->bthp", scores, dt[..., None] * x) \
        + d[None, None, :, None] * x


def loss_of(fn, weights):
    return lambda *args: jnp.sum(fn(*args).astype(jnp.float32) * weights)


# length, chunk, widths: one chunk, several whole chunks, a ragged last chunk,
# a sequence shorter than the chunk; then the kernels: two chunks, the state
# crossing three chunks with two groups, a ragged last chunk
SHAPES = [(16, 16, TINY), (64, 16, TINY), (50, 16, TINY), (10, 16, TINY),
          (33, 8, TINY), (256, 128, dict(KERNEL, g=1)),
          (384, 128, dict(KERNEL, g=2)), (200, 128, dict(KERNEL, g=1))]
IDS = [f"{s}-{chunk}-{'kernel' if dims is not TINY else 'xla'}"
       for s, chunk, dims in SHAPES]


@pytest.mark.parametrize("other", [sequential, quadratic])
@pytest.mark.parametrize("s,chunk,dims", SHAPES, ids=IDS)
def test_scan_matches_the_recurrence_in_float32(s, chunk, dims, other):
    args = inputs(s, jnp.float32, **dims)
    assert form_of(args, chunk) == ("xla" if dims is TINY else "kernel")
    weights = jax.random.normal(jax.random.PRNGKey(9), args[0].shape)
    with jax.default_matmul_precision("highest"):
        got = ssd_scan(*args, chunk)
        want = other(*args)
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)
        every = tuple(range(6))
        g_got = jax.grad(loss_of(lambda *a: ssd_scan(*a, chunk), weights),
                         every)(*args)
        g_want = jax.grad(loss_of(other, weights), every)(*args)
    for name, g, w in zip("x dt a b c d".split(), g_got, g_want):
        assert np.all(np.isfinite(g)), name
        np.testing.assert_allclose(g, w, rtol=2e-3, atol=2e-3, err_msg=name)


def rel(g, w):
    g, w = (np.asarray(v, np.float32) for v in (g, w))
    return np.linalg.norm(g - w) / np.linalg.norm(w)


@pytest.mark.parametrize("other", [sequential, quadratic])
@pytest.mark.parametrize("s,chunk,dims", [SHAPES[1], SHAPES[2], SHAPES[6]],
                         ids=[IDS[1], IDS[2], IDS[6]])
def test_scan_in_bfloat16_stays_within_its_roundings(s, chunk, dims, other):
    """The program's precision: bf16 operands, f32 accumulation, f32 decays.
    Against the f32 recurrence on the same (bf16-rounded) inputs the result
    and every gradient agree to a few bf16 roundings of their norm."""
    args = inputs(s, jnp.bfloat16, **dims)
    weights = jax.random.normal(jax.random.PRNGKey(9), args[0].shape)
    every = tuple(range(6))
    got = ssd_scan(*args, chunk)
    assert got.dtype == jnp.bfloat16
    g_got = jax.grad(loss_of(lambda *a: ssd_scan(*a, chunk), weights),
                     every)(*args)
    with jax.default_matmul_precision("highest"):
        want = other(*args)
        g_want = jax.grad(loss_of(other, weights), every)(*args)

    assert rel(got, want) < 0.02
    for name, g, w in zip("x dt a b c d".split(), g_got, g_want):
        assert rel(g, w) < 0.03, (name, rel(g, w))


@pytest.mark.parametrize("dtype,within", [(jnp.float32, 1e-4),
                                          (jnp.bfloat16, 0.01)],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("s,g", [(384, 1), (200, 2)])
def test_the_kernels_are_the_xla_form(s, g, dtype, within):
    """One algorithm in two forms: at shapes that meet the kernels' rule the
    result and every gradient are those of XLA's ops on the same inputs, to
    float32's last digits, and in bfloat16 far inside the distance either
    keeps from the recurrence (the two round the same operands)."""
    args = inputs(s, dtype, **dict(KERNEL, g=g))
    assert form_of(args, 128) == "kernel"
    weights = jax.random.normal(jax.random.PRNGKey(9), args[0].shape)
    every = tuple(range(6))
    with jax.default_matmul_precision("highest"):
        got, want = ssd_scan(*args, 128), xla_form(*args, 128)
        g_got = jax.grad(loss_of(lambda *a: ssd_scan(*a, 128), weights),
                         every)(*args)
        g_want = jax.grad(loss_of(lambda *a: xla_form(*a, 128), weights),
                          every)(*args)
    assert got.dtype == want.dtype == dtype
    assert rel(got, want) < within
    for name, g_k, g_x in zip("x dt a b c d".split(), g_got, g_want):
        assert g_k.dtype == g_x.dtype and g_k.shape == g_x.shape, name
        assert rel(g_k, g_x) < within, (name, rel(g_k, g_x))


@pytest.mark.parametrize("long,cut,chunk,dims", [
    (48, 37, 16, TINY), (256, 150, 128, dict(KERNEL, g=1))],
    ids=["xla", "kernel"])
def test_padding_neither_decays_nor_feeds_the_state(long, cut, chunk, dims):
    """A ragged length is padded inside the scan; the real positions read
    what they read in a longer sequence cut at the same place."""
    whole = inputs(long, jnp.float32, **dims)
    short = tuple(v[:, :cut] if v.ndim > 1 else v for v in whole)
    assert form_of(short, chunk) == ("xla" if dims is TINY else "kernel")
    with jax.default_matmul_precision("highest"):
        np.testing.assert_allclose(ssd_scan(*short, chunk),
                                   ssd_scan(*whole, chunk)[:, :cut],
                                   rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("shape,heads", [
    ((256, 64, 64, 128), 16),       # granite-4.0-h-micro: 16 heads a program
    ((128, 4, 64, 128), 4), ((128, 2, 64, 128), 2), ((256, 6, 64, 256), 6),
    ((256, 34, 64, 128), 2),        # no larger even block divides the group
    ((256, 64, 64, 16), None),      # N off the lane grid
    ((64, 64, 64, 128), None),      # the chunk off it
    ((512, 64, 64, 128), None),     # a chunk whose matrices outgrow VMEM
    ((256, 3, 64, 128), None),      # heads do not pair into tiles
    ((256, 8, 32, 128), None), ((256, 8, 128, 128), None),
    ((16, 2, 8, 16), None)])
def test_the_tiling_rule(shape, heads):
    assert head_block(*shape) == heads
    q, per_group, p, n = shape
    assert scan_form(4 * q, q, 2 * per_group, 2, p, n) \
        == ("kernel" if heads else "xla")
    # a sequence shorter than the chunk is one chunk of its own length
    assert scan_form(q, 4 * q, per_group, 1, p, n) \
        == ("kernel" if heads else "xla")


def test_the_scan_runs_the_form_the_plan_reports(monkeypatch):
    """``ssd_scan`` dispatches on, and ``ssm_plan`` reports, one function."""
    assert mamba.scan_form is ssd_scan_module.scan_form is scan_form
    asked = []
    monkeypatch.setattr(ssd_scan_module, "scan_form",
                        lambda *shape: asked.append(shape) or "xla")
    args = inputs(256, jnp.float32, **dict(KERNEL, g=1))
    assert "pallas_call" not in str(jax.make_jaxpr(
        lambda *a: ssd_scan(*a, 128))(*args))
    assert asked == [(256, 128, 4, 1, 64, 128)]
    monkeypatch.undo()
    assert "pallas_call" in str(jax.make_jaxpr(
        lambda *a: ssd_scan(*a, 128))(*args))


def test_groups_must_divide_heads():
    x, dt, a, b, c, d = inputs(16, jnp.float32)
    with pytest.raises(ValueError, match="do not divide"):
        ssd_scan(x, dt, a, b[:, :, :1].repeat(3, 2), c[:, :, :1].repeat(3, 2),
                 d, 16)


def _conv_alone(x, kernel, bias):
    return causal_conv(x, kernel, bias), lambda pre: pre


def _conv_as_the_kernels(x, kernel, bias):
    """The same convolution through ``ops/causal_conv``'s kernels (the run
    is the whole stream, one part), which apply the silu as well."""
    assert conv_form(x.shape[1], kernel.shape[0], 0, (x.shape[2],)) \
        == "kernel"
    (y,) = causal_conv_silu(x, kernel, bias, 0, (x.shape[2],))
    return y, jax.nn.silu


# the kernels at a length of two row tiles, the moved position a tile's last
# but one: the taps that see it lie on both sides of the edge
@pytest.mark.parametrize("conv,length,channels,at", [
    (_conv_alone, 20, 6, 11), (_conv_as_the_kernels, 512, 128, 254)],
    ids=["jnp", "kernel"])
def test_conv_is_causal_and_has_its_bias(conv, length, channels, at):
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (1, length, channels))
    kernel = jax.random.normal(jax.random.PRNGKey(1), (4, channels))
    bias = jax.random.normal(jax.random.PRNGKey(2), (channels,))
    y, act = conv(x, kernel, bias)
    moved, _ = conv(x.at[0, at].add(1.0), kernel, bias)
    changed = np.abs(np.asarray(moved - y)).sum(-1)[0] > 0
    assert not changed[:at].any()          # nothing before t moves
    assert changed[at:at + 4].all() and not changed[at + 4:].any()  # 4 taps
    # position 0 sees zeros before it: the last tap and the bias alone
    np.testing.assert_allclose(y[0, 0], act(bias + kernel[3] * x[0, 0]),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(
        y[0, 5], act(bias + sum(kernel[k] * x[0, 2 + k] for k in range(4))),
        rtol=1e-5, atol=1e-6)
