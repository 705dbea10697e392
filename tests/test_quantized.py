"""int8 quantized allreduce with per-tensor pmax scales + error feedback.

Beyond the reference's cast-based Compression pair (reference
compression.py:42-63): the wire carries int8 (4x smaller than float32),
correctness comes from per-tensor pmax-agreed scales with a sum-fitting
range, and ``DistributedOptimizer(compression=Compression.int8)`` carries
the quantization residual as error feedback.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import PartitionSpec as P

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

import horovod_tpu as hvd
from horovod_tpu.ops import quantized_grouped_allreduce
from horovod_tpu.training import DistributedEFState, DistributedState


def _chipwise(fn):
    """Run fn per-chip under shard_map with one scalar-batch input row (one
    program: eagerly a shard_map dispatches an operation at a time)."""
    return jax.jit(hvd.shard(fn, in_specs=hvd.batch_spec(2), out_specs=P()))


def test_quantized_allreduce_within_quantization_bound(hvd):
    n = hvd.num_chips()
    rng = np.random.RandomState(1)
    per_chip = rng.randn(n, 33).astype(np.float32)

    @_chipwise
    def reduce_q(x):
        (r,), _ = quantized_grouped_allreduce([x[0]], average=True)
        return r

    got = np.asarray(reduce_q(jnp.asarray(per_chip)))
    want = per_chip.mean(axis=0)
    # Per-element error bound: each chip rounds to its nearest level of
    # size scale = amax/qcap, so |err| <= n*(scale/2)/n = scale/2.
    qcap = max(127 // n, 1)
    scale = np.abs(per_chip).max() / qcap
    np.testing.assert_allclose(got, want, atol=scale / 2 + 1e-7)


def test_quantized_allreduce_exact_on_grid_values(hvd):
    """Values already on the shared quantization grid reduce exactly."""
    n = hvd.num_chips()
    qcap = max(127 // n, 1)
    rng = np.random.RandomState(2)
    levels = rng.randint(-qcap, qcap + 1, size=(n, 16)).astype(np.float32)
    # make amax map exactly: ensure at least one chip holds ±qcap
    levels[0, 0] = qcap

    @_chipwise
    def reduce_q(x):
        (r,), _ = quantized_grouped_allreduce([x[0]], average=False)
        return r

    got = np.asarray(reduce_q(jnp.asarray(levels)))
    np.testing.assert_allclose(got, levels.sum(axis=0), rtol=0, atol=0)


def test_quantized_wire_is_int8(hvd):
    """The all-reduced operand must be int8 in the lowered program — the
    whole point of the feature."""
    n = hvd.num_chips()

    @_chipwise
    def reduce_q(x):
        (r,), _ = quantized_grouped_allreduce([x[0]], average=True)
        return r

    jaxpr = str(jax.make_jaxpr(reduce_q)(jnp.ones((n, 130), jnp.float32)))
    assert "i8[" in jaxpr, jaxpr


def test_quantized_residual_is_the_quantization_error(hvd):
    n = hvd.num_chips()
    rng = np.random.RandomState(3)
    vals = rng.randn(n, 8).astype(np.float32)

    @hvd.shard(in_specs=hvd.batch_spec(2), out_specs=hvd.batch_spec(1))
    def residual(x):
        (r,), (e,) = quantized_grouped_allreduce([x[0]], average=False)
        # local value minus its dequantized representation
        return e[None]

    resid = np.asarray(residual(jnp.asarray(vals)))
    qcap = max(127 // n, 1)
    scale = np.abs(vals).max() / qcap
    assert np.abs(resid).max() <= scale / 2 + 1e-7
    # residual + dequantized(local q) == original value
    q = np.clip(np.round(vals / scale), -qcap, qcap)
    np.testing.assert_allclose(resid, vals - q * scale, atol=1e-6)


def test_int8_error_feedback_training_matches_fp32(hvd):
    """A quadratic problem trained with the int8+EF DistributedOptimizer
    must converge to (nearly) the same parameters as the f32 baseline —
    the error-feedback contract."""
    n = hvd.num_chips()
    rng = np.random.RandomState(4)
    target = rng.randn(6).astype(np.float32)
    x_all = rng.randn(n * 4, 6).astype(np.float32)

    def make_step(opt):
        @jax.jit
        @hvd.shard(in_specs=(P(), P(), hvd.batch_spec(2)),
                   out_specs=(P(), P(), P()))
        def step(w, opt_state, xb):
            def loss_fn(w):
                return jnp.mean((xb @ (w - jnp.asarray(target))) ** 2)

            loss, g = jax.value_and_grad(loss_fn)(w)
            updates, opt_state = opt.update({"w": g}, opt_state, {"w": w})
            return w + updates["w"], opt_state, loss

        return step

    results = {}
    for name, compression in (("f32", hvd.Compression.none),
                              ("int8", hvd.Compression.int8)):
        opt = hvd.DistributedOptimizer(optax.sgd(0.05),
                                       compression=compression)
        w = jnp.zeros(6)
        opt_state = opt.init({"w": w})
        step = make_step(opt)
        for _ in range(200):
            w, opt_state, loss = step(w, opt_state, jnp.asarray(x_all))
        results[name] = (np.asarray(w), float(loss))

    # both converge to the target; int8+EF lands close to the f32 result
    np.testing.assert_allclose(results["f32"][0], target, atol=1e-3)
    np.testing.assert_allclose(results["int8"][0], target, atol=5e-3)


def test_int8_state_carries_error(hvd):
    opt = hvd.DistributedOptimizer(optax.sgd(0.1),
                                   compression=hvd.Compression.int8)
    params = {"w": jnp.ones((4,))}
    state = opt.init(params)
    assert isinstance(state, DistributedEFState)
    np.testing.assert_array_equal(np.asarray(state.error["w"]), np.zeros(4))

    @jax.jit
    @hvd.shard(in_specs=(P(), P()), out_specs=(P(), P()))
    def one(params, state):
        grads = {"w": jnp.asarray([0.33, -0.77, 0.5, 0.0])}
        updates, state = opt.update(grads, state, params)
        return updates, state

    _, state2 = one(params, state)
    assert isinstance(state2, DistributedEFState)
    # residual generally nonzero after a quantized step
    assert np.abs(np.asarray(state2.error["w"])).sum() > 0


def test_int8_compressor_rejects_cast_use(hvd):
    with pytest.raises(NotImplementedError, match="quantized"):
        hvd.Compression.int8.compress(jnp.ones(3))


def test_quantized_eager_process_level(hvd):
    """Eager (no mesh axis bound) routes through the process-level
    (scale, int8) payload path — single process: dequantized round-trip
    within the quantization grid, residual = the local error."""
    vals = jnp.asarray(np.linspace(-1, 1, 9).astype(np.float32))
    (r,), (e,) = quantized_grouped_allreduce([vals], average=False)
    scale = 1.0 / 127.0
    np.testing.assert_allclose(np.asarray(r), np.asarray(vals),
                               atol=scale / 2 + 1e-7)
    np.testing.assert_allclose(np.asarray(r) + np.asarray(e),
                               np.asarray(vals), atol=1e-6)
    with pytest.raises(ValueError, match="floating"):
        quantized_grouped_allreduce([jnp.ones(3, jnp.int32)])


def test_quantized_hierarchical_on_dcn_ici_mesh(hvd):
    """Multi-slice meshes route the int8 sum hierarchically (ICI scatter →
    DCN → ICI gather) — only the int8 shard crosses DCN."""
    import numpy as _np
    from jax.sharding import Mesh

    devs = _np.array(jax.devices()[:8]).reshape(2, 4)
    m = Mesh(devs, ("dcn", "ici"))
    rng = _np.random.RandomState(7)
    vals = rng.randn(8, 256).astype(_np.float32)

    def reduce_q(x):
        (r,), _ = quantized_grouped_allreduce([x[0]], average=True)
        return r

    f = jax.jit(jax.shard_map(reduce_q, mesh=m,
                              in_specs=P(("dcn", "ici")), out_specs=P(),
                              check_vma=False))
    got = _np.asarray(f(jnp.asarray(vals)))
    qcap = 127 // 8
    scale = _np.abs(vals).max() / qcap
    _np.testing.assert_allclose(got, vals.mean(axis=0), atol=scale / 2 + 1e-7)
    jaxpr = str(jax.make_jaxpr(f)(jnp.asarray(vals)))
    assert "i8[" in jaxpr


def test_quantized_all_zero_bucket_stays_finite(hvd):
    """All-zero gradients must reduce to zero, not NaN, in every wire
    dtype (the scale floor guards in the working dtype)."""
    n = hvd.num_chips()
    for dtype in (jnp.float32, jnp.float16, jnp.bfloat16):
        @_chipwise
        def reduce_q(x):
            (r,), (e,) = quantized_grouped_allreduce([x[0]], average=True)
            return r

        got = np.asarray(reduce_q(jnp.zeros((n, 8), dtype)).astype(jnp.float32))
        assert np.isfinite(got).all(), dtype
        np.testing.assert_array_equal(got, np.zeros(8, np.float32))


def test_quantized_rejects_integer_grads(hvd):
    @_chipwise
    def reduce_q(x):
        (r,), _ = quantized_grouped_allreduce([x[0].astype(jnp.int32)])
        return r.astype(jnp.float32)

    with pytest.raises(ValueError, match="floating"):
        reduce_q(jnp.ones((hvd.num_chips(), 4)))


def test_quantized_rejects_width_over_127(hvd, monkeypatch):
    from horovod_tpu.ops import collective_ops

    monkeypatch.setattr(collective_ops, "_data_width", lambda axes: 256)

    @_chipwise
    def reduce_q(x):
        (r,), _ = quantized_grouped_allreduce([x[0]])
        return r

    with pytest.raises(ValueError, match="127"):
        reduce_q(jnp.ones((hvd.num_chips(), 4)))


def test_single_allreduce_int8_routes_to_quantized(hvd):
    n = hvd.num_chips()
    rng = np.random.RandomState(9)
    vals = rng.randn(n, 12).astype(np.float32)

    @_chipwise
    def reduce_one(x):
        return hvd.allreduce(x[0], average=True,
                             compression=hvd.Compression.int8)

    got = np.asarray(reduce_one(jnp.asarray(vals)))
    qcap = max(127 // n, 1)
    scale = np.abs(vals).max() / qcap
    np.testing.assert_allclose(got, vals.mean(axis=0), atol=scale / 2 + 1e-7)


def test_int8_ef_state_checkpoints(hvd, tmp_path):
    """DistributedEFState (inner + error residual) must round-trip through
    the checkpoint layer like any optimizer state — resuming an int8 run
    keeps its error feedback."""
    from horovod_tpu import checkpoint

    opt = hvd.DistributedOptimizer(optax.sgd(0.1),
                                   compression=hvd.Compression.int8)
    params = {"w": jnp.ones((4,))}
    state = opt.init(params)

    @jax.jit
    @hvd.shard(in_specs=(P(), P()), out_specs=(P(), P()))
    def one(params, state):
        grads = {"w": jnp.asarray([0.3, -0.7, 0.5, 0.01])}
        updates, state = opt.update(grads, state, params)
        return updates, state

    _, state = one(params, state)
    checkpoint.save(tmp_path / "ef", state)
    # Restore into a ZEROED template: values must come from disk, not be
    # the template handed back.
    zeros = jax.tree.map(jnp.zeros_like, state)
    restored = checkpoint.restore(tmp_path / "ef", template=zeros)
    assert isinstance(restored, DistributedEFState)
    assert np.abs(np.asarray(state.error["w"])).sum() > 0
    np.testing.assert_allclose(np.asarray(restored.error["w"]),
                               np.asarray(state.error["w"]), atol=1e-7)


def test_checkpoint_migrates_across_compression_modes(hvd, tmp_path):
    """Toggling DistributedOptimizer compression between save and resume
    must migrate the optimizer state (reference keras/__init__.py:115-148
    restore-must-rewrap contract): a plain checkpoint restores into an
    int8-EF optimizer with zero residuals; an EF checkpoint restores into
    a plain optimizer dropping residuals with a warning."""
    from horovod_tpu import checkpoint

    params = {"w": jnp.ones((4,)), "b": jnp.zeros((2,))}
    grads = {"w": jnp.asarray([0.3, -0.7, 0.5, 0.01]),
             "b": jnp.asarray([0.2, -0.1])}
    plain = hvd.DistributedOptimizer(optax.sgd(0.1, momentum=0.9))
    ef = hvd.DistributedOptimizer(optax.sgd(0.1, momentum=0.9),
                                  compression=hvd.Compression.int8)

    # plain save → EF resume: residuals zero-initialized, inner survives.
    _, ps = plain.update(grads, plain.init(params), params)  # momentum != 0
    checkpoint.save(tmp_path / "plain", ps)
    ef_template = jax.tree.map(jnp.zeros_like, ef.init(params))
    with pytest.warns(UserWarning, match="initialized to zero"):
        restored = checkpoint.restore(tmp_path / "plain",
                                      template=ef_template)
    assert isinstance(restored, DistributedEFState)
    for got, want in zip(jax.tree.leaves(restored.inner),
                         jax.tree.leaves(ps.inner)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want))
    for leaf in jax.tree.leaves(restored.error):
        np.testing.assert_array_equal(np.asarray(leaf), 0.0)

    # EF save (non-zero residual) → plain resume: residuals dropped, warned.
    @jax.jit
    @hvd.shard(in_specs=(P(), P()), out_specs=(P(), P()))
    def one(params, state):
        updates, state = ef.update(grads, state, params)
        return updates, state

    _, es = one(params, ef.init(params))
    assert sum(float(np.abs(np.asarray(leaf)).sum())
               for leaf in jax.tree.leaves(es.error)) > 0
    checkpoint.save(tmp_path / "ef2", es)
    plain_template = jax.tree.map(jnp.zeros_like, plain.init(params))
    with pytest.warns(UserWarning, match="dropped"):
        restored2 = checkpoint.restore(tmp_path / "ef2",
                                       template=plain_template)
    assert isinstance(restored2, DistributedState)
    for got, want in zip(jax.tree.leaves(restored2.inner),
                         jax.tree.leaves(es.inner)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want))

    # A genuinely incompatible checkpoint still fails loudly.
    checkpoint.save(tmp_path / "other", {"unrelated": jnp.ones(3)})
    with pytest.raises(Exception):
        checkpoint.restore(tmp_path / "other", template=ef_template)


def test_tiered_int8_on_hierarchical_mesh(hvd):
    """(dcn, ici) mesh: the int8 collective sum-fits PER TIER (ICI
    reduce-scatter at ±(127//ici), requantize, int8 DCN psum) — the route
    that lifts the flat 127-worker cap (reference operations.cc:1025-1177
    hierarchy re-derived for the int8 wire)."""
    import jax as _jax
    from jax.sharding import Mesh

    mesh = Mesh(np.array(_jax.devices()).reshape(2, 4), ("dcn", "ici"))
    vals = np.linspace(-1, 1, 8 * 16).astype(np.float32).reshape(8, 16)

    def f(x):
        (r,), _ = quantized_grouped_allreduce([x[0]], average=False)
        return r

    out = jax.jit(jax.shard_map(f, mesh=mesh, in_specs=P(("dcn", "ici")),
                                out_specs=P(), check_vma=False))(
        jnp.asarray(vals))
    expect = vals.sum(axis=0)
    qcap = 127 // 4
    scale = np.abs(vals).max() / qcap
    # stage-1 rounding (width*scale/2) + stage-2 per-tier requantization
    # (dcn * s1_max/(2*qcap2) grid counts, in value terms times scale).
    bound = 8 * scale / 2 + 2 * (4 * qcap) * scale / (2 * 63) + 1e-6
    assert np.abs(np.asarray(out) - expect).max() <= bound


_WIDTH32_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=32"
os.environ["JAX_PLATFORMS"] = "cpu"
import jax
jax.config.update("jax_platforms", "cpu")
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import Mesh, PartitionSpec as P

import horovod_tpu as hvd

hvd.init()
W, B, D = 32, 4, 16
mesh = Mesh(np.array(jax.devices()).reshape(4, 8), ("dcn", "ici"))
rng = np.random.RandomState(0)
x = rng.randn(W * B, D).astype(np.float32)
w_true = rng.randn(D).astype(np.float32)
y = x @ w_true + 0.01 * rng.randn(W * B).astype(np.float32)
spec = P(("dcn", "ici"))


def run(compression):
    opt = hvd.DistributedOptimizer(optax.sgd(0.05), compression=compression)
    params = {"w": jnp.zeros(D), "b": jnp.zeros(())}
    state = opt.init(params)

    @jax.jit
    def step(params, state, xs, ys):
        def inner(p, s, xb, yb):
            def loss_fn(q):
                pred = xb @ q["w"] + q["b"]
                return jnp.mean((pred - yb) ** 2)
            loss, g = jax.value_and_grad(loss_fn)(p)
            u, s = opt.update(g, s, p)
            return optax.apply_updates(p, u), s, jax.lax.pmean(
                loss, ("dcn", "ici"))
        return jax.shard_map(inner, mesh=mesh,
                             in_specs=(P(), P(), spec, spec),
                             out_specs=(P(), P(), P()),
                             check_vma=False)(params, state, xs, ys)

    losses = []
    for _ in range(25):
        params, state, loss = step(params, state, x, y)
        losses.append(float(loss))
    return losses


base = run(hvd.Compression.none)
q8 = run(hvd.Compression.int8)
print("BASE", base[0], base[-1])
print("Q8", q8[0], q8[-1])
assert q8[-1] < 0.25 * q8[0], f"int8-EF failed to converge: {q8}"
rel = abs(q8[-1] - base[-1]) / max(base[-1], 1e-6)
# Width 32 on the (4, 8) tiered grid: +-15 levels + error feedback tracks
# the fp32 trajectory; a flat 127//32=+-3 grid would not be this close.
assert rel < 0.5, f"int8-EF diverged from fp32: {base[-1]} vs {q8[-1]}"
print("WIDTH32 OK")
"""


def test_int8_ef_convergence_width32(tmp_path):
    """Hierarchical tiered int8 at data width 32 ((dcn=4, ici=8) mesh):
    EF-carried training must track fp32 closely — the VERDICT-r2 concern
    that nobody had measured convergence past width 8."""
    import subprocess
    import sys

    from _timing import scaled

    script = tmp_path / "width32.py"
    script.write_text(_WIDTH32_SCRIPT)
    env = {k: v for k, v in os.environ.items()}
    env["PYTHONPATH"] = REPO
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, str(script)], capture_output=True,
                         text=True, timeout=scaled(420), env=env, cwd=REPO)
    assert out.returncode == 0, (out.stdout[-2000:], out.stderr[-2000:])
    assert "WIDTH32 OK" in out.stdout


def test_quantized_per_tensor_scales_in_mesh(hvd):
    """Compiled path: a tiny tensor grouped with a huge one keeps its own
    quantization grid (per-tensor scales, not per fused bucket)."""
    n = hvd.num_chips()

    @_chipwise
    def reduce_two(x):
        big = jnp.full(4, 10.0) * (x[0, 0] * 0 + 1)   # shard-dependent noop
        tiny = jnp.full(4, 1e-6) * (x[0, 0] * 0 + 1)
        (rb, rt), _ = quantized_grouped_allreduce([big, tiny], average=False)
        return jnp.stack([rb, rt])

    out = np.asarray(reduce_two(jnp.ones((n, 2), jnp.float32)))
    np.testing.assert_allclose(out[0], np.full(4, 10.0 * n), rtol=0.01)
    np.testing.assert_allclose(out[1], np.full(4, 1e-6 * n), rtol=0.01)
    assert np.all(out[1] > 0), "tiny tensor zeroed by a shared bucket scale"


def test_quantized_nonfinite_propagates_in_mesh(hvd):
    """Compiled path: a NaN gradient must dequantize to NaN, not finite."""
    n = hvd.num_chips()

    @_chipwise
    def reduce_nan(x):
        bad = jnp.ones(4) * x[0, 0]   # x carries the NaN in shard 0
        (r,), _ = quantized_grouped_allreduce([bad], average=False)
        return r

    x = np.ones((n, 2), np.float32)
    x[0, 0] = np.nan
    out = np.asarray(reduce_nan(jnp.asarray(x)))
    assert not np.isfinite(out).all(), out


def test_quantized_empty_tensor_in_mesh(hvd):
    """Zero-size leaves (an empty head) must not crash the per-tensor amax."""
    n = hvd.num_chips()

    @_chipwise
    def reduce_with_empty(x):
        full = jnp.ones(4) * x[0, 0]
        empty = jnp.zeros((0,), jnp.float32)
        (rf, re), _ = quantized_grouped_allreduce([full, empty],
                                                  average=False)
        return rf

    out = np.asarray(reduce_with_empty(jnp.ones((n, 2), jnp.float32)))
    np.testing.assert_allclose(out, np.full(4, float(n)), rtol=1e-6)
