"""The causal convolution, bias and silu over a run of a stream's columns
(``ops/causal_conv.py``), in both its forms: the two Pallas kernels
(interpreted here) against the ``jnp`` form at shapes that meet the kernels'
rule, values and the gradient of every input, at a length of several row
tiles so that the rows a tile borrows cross a tile's edge in both directions;
what position 0 sees; which shapes take which form; and that the mixer runs,
and ``ssm_plan`` reports, the form one function chooses."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.models import TransformerConfig, mamba
from horovod_tpu.models.mamba import Mamba2Mixer, ssm_plan
from horovod_tpu.ops import causal_conv as conv_module
from horovod_tpu.ops.causal_conv import (causal_conv, causal_conv_silu,
                                         column_block, conv_form, row_tile)
from horovod_tpu.utils import profiling

# z | x | B | C | dt as a Mamba-2 projection leaves them: the run starts at a
# column block's edge, its parts are 256, 128 and 128 wide, 64 columns follow
START, WIDTHS, TAIL = 256, (256, 128, 128), 64
WIDTH = START + sum(WIDTHS) + TAIL
S = 768                                         # three row tiles of 256


def inputs(dtype, s=S, k=4, batch=2, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3 + len(WIDTHS))
    stream = jax.random.normal(ks[0], (batch, s, WIDTH), jnp.float32)
    taps = jax.random.normal(ks[1], (k, sum(WIDTHS))) * 0.5
    bias = jax.random.normal(ks[2], (sum(WIDTHS),))
    cotangents = [jax.random.normal(key, (batch, s, w))
                  for key, w in zip(ks[3:], WIDTHS)]
    return stream.astype(dtype), taps, bias, cotangents


def jnp_form(stream, taps, bias, start=START, widths=WIDTHS):
    """``causal_conv_silu`` held to ``jnp`` ops whatever the shapes."""
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(conv_module, "conv_form", lambda *shape: "xla")
        return causal_conv_silu(stream, taps, bias, start, widths)


def loss_of(fn, cotangents):
    return lambda *args: sum(
        jnp.sum(out.astype(jnp.float32) * ct)
        for out, ct in zip(fn(*args), cotangents))


def rel(g, w):
    g, w = (np.asarray(v, np.float32) for v in (g, w))
    return np.linalg.norm(g - w) / np.linalg.norm(w)


def runs_the_kernels(fn, *args) -> bool:
    return profiling.CAUSAL_CONV_FWD in str(jax.make_jaxpr(fn)(*args))


@pytest.mark.parametrize("taps", [4, 2])
@pytest.mark.parametrize("dtype,ulps,within", [
    (jnp.float32, 16, 1e-5), (jnp.bfloat16, 2, 0.01)],
    ids=["float32", "bfloat16"])
def test_the_kernels_are_the_jnp_form(dtype, ulps, within, taps):
    """One algorithm in two forms: each part is the ``jnp`` form's to the
    result's last place (the ``jnp`` form rounds the pre-activation to the
    stream's dtype before the silu, the kernel rounds once, at the store), and
    the three gradients agree as the scan's two forms do."""
    stream, w, bias, cts = inputs(dtype, k=taps)
    assert conv_form(S, taps, START, WIDTHS) == "kernel"
    assert runs_the_kernels(
        lambda *a: causal_conv_silu(*a, START, WIDTHS), stream, w, bias)
    got = causal_conv_silu(stream, w, bias, START, WIDTHS)
    want = jnp_form(stream, w, bias)
    assert [g.shape for g in got] == [(2, S, width) for width in WIDTHS]
    eps = float(jnp.finfo(dtype).eps)
    for g, v in zip(got, want):
        assert g.dtype == v.dtype == dtype
        g, v = (np.asarray(a, np.float32) for a in (g, v))
        assert np.all(np.abs(g - v) <= ulps * eps * np.maximum(np.abs(v),
                                                               0.25))
    every = (0, 1, 2)
    g_got = jax.grad(loss_of(
        lambda *a: causal_conv_silu(*a, START, WIDTHS), cts), every)(
        stream, w, bias)
    g_want = jax.grad(loss_of(jnp_form, cts), every)(stream, w, bias)
    for name, g_k, g_x in zip(("stream", "taps", "bias"), g_got, g_want):
        assert g_k.dtype == g_x.dtype and g_k.shape == g_x.shape, name
        assert np.all(np.isfinite(g_k)), name
        assert rel(g_k, g_x) < within, (name, rel(g_k, g_x))
    # the stream's other columns took no part
    d_stream = np.asarray(g_got[0], np.float32)
    assert not d_stream[..., :START].any()
    assert not d_stream[..., START + sum(WIDTHS):].any()


def test_a_tile_borrows_rows_across_its_edges_and_zeros_before_the_start():
    """Row 255 is a tile's last: the three rows it feeds are the next
    tile's; the cotangent of row 256, a tile's first, reaches the three
    rows before it, the tile's before."""
    stream, w, bias, _ = inputs(jnp.float32, batch=1)
    run = lambda st: jnp.concatenate(  # noqa: E731
        causal_conv_silu(st, w, bias, START, WIDTHS), axis=-1)
    y = run(stream)
    moved = run(stream.at[0, 255, START:START + sum(WIDTHS)].add(1.0))
    changed = np.abs(np.asarray(moved - y)).sum(-1)[0] > 0
    assert not changed[:255].any()
    assert changed[255:259].all() and not changed[259:].any()
    # position 0 sees zeros before it: the last tap and the bias alone
    first = bias + w[3] * stream[0, 0, START:START + sum(WIDTHS)]
    np.testing.assert_allclose(y[0, 0], first * jax.nn.sigmoid(first),
                               rtol=1e-5, atol=1e-6)
    at_256 = jax.grad(lambda st: run(st)[0, 256].sum())(stream)
    reached = np.abs(np.asarray(at_256)).sum(-1)[0] > 0
    assert reached[253:257].all()
    assert not reached[:253].any() and not reached[257:].any()


def test_no_bias_is_a_bias_of_zeros():
    stream, w, bias, _ = inputs(jnp.float32, batch=1, s=256)
    none = causal_conv_silu(stream, w, None, START, WIDTHS)
    zeros = causal_conv_silu(stream, w, jnp.zeros_like(bias), START, WIDTHS)
    for a, b in zip(none, zeros):
        np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("s,k,start,widths,form", [
    (8192, 4, 4096, (4096, 128, 128), "kernel"),    # granite-4.0-h-micro
    (1024, 4, 4096, (4096, 128, 128), "kernel"),    # its comparison's length
    (768, 4, 256, (256, 128, 128), "kernel"),
    (256, 2, 0, (128,), "kernel"),
    (128, 4, 4096, (4096, 128, 128), "xla"),        # model.init's length
    (200, 4, 256, (256, 128, 128), "xla"),          # no row tile divides it
    (768, 4, 64, (64, 16, 16), "xla"),              # the tests' tiny widths
    (768, 4, 256, (256, 64, 64), "xla"),            # a part off the lane grid
    (768, 4, 192, (256, 128, 128), "xla"),          # the run's first column
    (768, 10, 256, (256, 128, 128), "xla")])        # more taps than a carry
def test_the_shape_rule(s, k, start, widths, form):
    assert conv_form(s, k, start, widths) == form
    stream = jnp.zeros((1, s, start + sum(widths) + 8), jnp.float32)
    taps = jnp.zeros((k, sum(widths)))
    assert runs_the_kernels(
        lambda st, t: causal_conv_silu(st, t, None, start, widths),
        stream, taps) == (form == "kernel")


def test_tiles_and_blocks():
    assert [row_tile(s) for s in (8192, 1536, 768, 128, 1000)] == [
        1024, 512, 256, None, None]
    assert [column_block(*at) for at in (
        (4096, 4096), (8192, 128), (8320, 128), (256, 256), (128, 256),
        (0, 64))] == [512, 128, 128, 256, 128, None]


def test_taps_must_cover_the_run():
    stream, w, bias, _ = inputs(jnp.float32, s=256)
    with pytest.raises(ValueError, match="channels of taps"):
        causal_conv_silu(stream, w[:, :-1], None, START, WIDTHS)
    with pytest.raises(ValueError, match="channels of taps"):
        causal_conv_silu(stream, w, bias, START + TAIL + 1, WIDTHS)


def test_the_jnp_form_is_the_convolution_the_mixer_had():
    """``causal_conv`` + silu + split, on the run's columns."""
    stream, w, bias, _ = inputs(jnp.float32, s=40, batch=1)
    got = jnp_form(stream, w, bias)
    run = stream[..., START:START + sum(WIDTHS)]
    want = jnp.split(jax.nn.silu(causal_conv(run, w, bias)), [256, 384],
                     axis=-1)
    for g, v in zip(got, want):
        np.testing.assert_array_equal(g, v)


# the widths meet both rules: two heads of 64 (x 128 wide at column 128), a
# state of 128 (B and C)
ON_RULE = dict(vocab_size=64, num_layers=1, num_heads=4, head_dim=8,
               embed_dim=32, mlp_dim=64, max_seq_len=256, dtype=jnp.float32,
               layer_types=("mamba",), mamba_heads=2, mamba_head_dim=64,
               mamba_state_dim=128, mamba_chunk=128)


def test_the_conv_runs_the_form_the_plan_reports(monkeypatch):
    """``causal_conv_silu`` dispatches on, and ``ssm_plan`` reports, one
    function."""
    assert mamba.conv_form is conv_module.conv_form is conv_form
    cfg = TransformerConfig(**ON_RULE)
    assert ssm_plan(cfg, 256)["conv"] == "kernel"
    assert ssm_plan(cfg, 200)["conv"] == "xla"
    mixer = Mamba2Mixer(cfg)
    x = jnp.zeros((1, 256, 32))
    params = jax.eval_shape(mixer.init, jax.random.PRNGKey(0), x)
    asked = []
    monkeypatch.setattr(conv_module, "conv_form",
                        lambda *shape: asked.append(shape) or "xla")
    assert not runs_the_kernels(mixer.apply, params, x)
    assert asked == [(256, 4, 128, (128, 128, 128))]
    monkeypatch.undo()
    assert runs_the_kernels(mixer.apply, params, x)
    text = str(jax.make_jaxpr(jax.grad(
        lambda p, v: mixer.apply(p, v).sum()))(params, x))
    assert profiling.CAUSAL_CONV_BWD in text
    assert set(profiling.CAUSAL_CONV_PASSES) == {
        profiling.CAUSAL_CONV_FWD, profiling.CAUSAL_CONV_BWD}
