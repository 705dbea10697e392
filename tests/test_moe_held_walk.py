"""A share of the experts walks its own pairs in blocks (PR 43,
``models/moe.py::_walk_held``) against the full-width layer, whose arithmetic
is kept here as a plain function: every one of the ``T * k`` rows gathered,
multiplied expert by expert and summed a token at a time.

Sizes.  ``T * k`` = 2048 pairs; at shares of 1/8 and 1/16 a block is the
grouped matmul's row tile, 512 rows, so a layer walks one block while the
routing is even, two and more with a router skewed onto the held experts,
four with every pair held, none with none.  float32 on the CPU: the walk and
the plain function sum a token's pairs in another order, so they agree to
rounding (1e-6 of the output's size); a dropped pair, a pair weighted by
another's gate or a row added to another token reads 1e-2 or more."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.models import moe
from horovod_tpu.models.moe import MOE_STATS, MoEMLP

T, K, E, D, F = 256, 8, 64, 32, 16
BLOCK = 512


def rel(got, want):
    return float(jnp.abs(got - want).max() / jnp.abs(want).max())


def layer(held, shared=1, experts=E):
    m = MoEMLP(embed_dim=D, mlp_dim=F, axis_name=None, dtype=jnp.float32,
               num_experts=experts, experts_per_token=K, selection="sigmoid",
               norm_topk_prob=True, routed_scale=2.5,
               num_shared_experts=shared, experts_held=held)
    x = inputs(jax.random.PRNGKey(0), T)
    return m, m.init(jax.random.PRNGKey(1), x), x


def inputs(key, t):
    """[1, t, D] normal, feature 0 held at 1 (what ``skewed`` pushes on)."""
    return jax.random.normal(key, (1, t, D)).at[..., 0].set(1.0)


def skewed(params, lo, hi, by):
    """The router pushed towards experts lo..hi-1: their logits gain ``by``
    for every token (feature 0 of the inputs is 1)."""
    router = params["params"]["router"]
    return {"params": {**params["params"],
                       "router": router.at[0, lo:hi].add(by)}}


def full_width(m, params, x, valid=None):
    """The parent's arithmetic: all T * k rows, expert by expert."""
    p = params["params"]
    lo, hi = m.experts_held
    tokens = x.reshape(-1, D)
    scores = jax.nn.sigmoid(jnp.dot(tokens, p["router"], precision="highest"))
    _, picks = jax.lax.top_k(scores, K)
    gates = jnp.take_along_axis(scores, picks, -1)
    gates = gates / gates.sum(-1, keepdims=True) * m.routed_scale
    on_chip = (picks >= lo) & (picks < hi)
    if valid is not None:
        on_chip &= valid.reshape(-1, 1)
    out = jnp.zeros((T, D))
    with jax.default_matmul_precision("highest"):
        for j in range(hi - lo):
            each = (jax.nn.silu(tokens @ p["gate"][j]) * (tokens @ p["up"][j])
                    ) @ p["down"][j]
            mine = jnp.where(on_chip & (picks == lo + j), gates, 0).sum(-1)
            out = out + mine[:, None] * each
        if m.num_shared_experts:
            out = out + (jax.nn.silu(tokens @ p["shared_gate"])
                         * (tokens @ p["shared_up"])) @ p["shared_down"] \
                / m.num_shared_experts
    return out.reshape(x.shape), int(on_chip.sum())


def walked(m, params, x, valid=None):
    with jax.default_matmul_precision("highest"):
        out, sown = m.apply(params, x, valid=valid, mutable=[MOE_STATS])
    stats = sown[MOE_STATS]
    return out, int(stats["expert_pairs"][0].sum()), \
        int(stats["rows_visited"][0])


HALF_VALID = jnp.arange(T)[None, :] < 150


@pytest.mark.parametrize("valid", [None, HALF_VALID],
                         ids=["every_position", "a_padded_bucket"])
@pytest.mark.parametrize("held", [(8, 16), (40, 44)],
                         ids=["an_eighth", "a_sixteenth"])
def test_a_share_walks_one_block_and_equals_the_full_width_layer(held, valid):
    m, params, x = layer(held)
    assert moe.held_block_rows(T * K, held[1] - held[0], E) == BLOCK
    want, n_held = full_width(m, params, x, valid)
    got, counted, rows = walked(m, params, x, valid)
    assert 0 < n_held <= BLOCK and counted == n_held
    assert rows == BLOCK                     # one block, of T * k = 2048 rows
    assert rel(got, want) < 1e-5
    if valid is not None:                    # padding: the shared part alone
        assert float(jnp.abs(got[0, 150:] - full_width(
            m, params, x, jnp.zeros((1, T), bool))[0][0, 150:]).max()) < 1e-5


@pytest.mark.parametrize("by, blocks", [(0.7, 2), (1.6, 3), (40.0, 4)],
                         ids=["two_blocks", "three_blocks", "every_pair_held"])
def test_held_pairs_that_overflow_a_block_are_all_visited(by, blocks):
    """A router skewed onto the eight held experts: the held pairs fill two
    blocks, three, and at last all T * k / C = 4 (every token picks exactly
    the eight held experts); no pair is dropped at any load."""
    m, params, x = layer((8, 16))
    params = skewed(params, 8, 16, by)
    want, n_held = full_width(m, params, x)
    got, counted, rows = walked(m, params, x)
    assert counted == n_held and -(-n_held // BLOCK) == blocks
    assert rows == blocks * BLOCK
    if blocks == 4:
        assert n_held == T * K
    assert rel(got, want) < 1e-5


def test_a_share_that_holds_no_pair_walks_no_block():
    m, params, x = layer((8, 16))
    params = skewed(params, 8, 16, -40.0)
    want, n_held = full_width(m, params, x)
    got, counted, rows = walked(m, params, x)
    assert n_held == counted == rows == 0
    assert rel(got, want) < 1e-5             # the shared expert alone
    # and with no position valid, whatever the router says
    none = jnp.zeros((1, T), bool)
    got, counted, rows = walked(*layer((8, 16))[:2], x, none)
    assert counted == rows == 0


def test_a_last_block_that_is_not_whole_is_walked():
    """T * k need not be a multiple of the block: 2100 pairs in blocks of
    1024, every pair held, so the third block holds 52 of them."""
    t, k, e, held = 700, 3, 64, (0, 8)       # 2100 pairs, C = 1024
    m = MoEMLP(embed_dim=D, mlp_dim=F, axis_name=None, dtype=jnp.float32,
               num_experts=e, experts_per_token=k, selection="sigmoid",
               experts_held=held)
    x = inputs(jax.random.PRNGKey(2), t)
    params = skewed(m.init(jax.random.PRNGKey(3), x), 0, 8, 40.0)
    assert moe.held_block_rows(t * k, 8, e) == 1024
    with jax.default_matmul_precision("highest"):
        got, sown = m.apply(params, x, mutable=[MOE_STATS])
    assert int(sown[MOE_STATS]["expert_pairs"][0].sum()) == t * k
    assert int(sown[MOE_STATS]["rows_visited"][0]) == 3 * 1024
    # every pair held: the layer is the one that holds all its experts, cut
    # to these eight by the router
    p = params["params"]
    tokens = x[0]
    probs = jax.nn.sigmoid(jnp.dot(tokens, p["router"], precision="highest"))
    _, picks = jax.lax.top_k(probs, k)
    gates = jnp.take_along_axis(probs, picks, -1)
    want = jnp.zeros((t, D))
    with jax.default_matmul_precision("highest"):
        for j in range(8):
            each = (jax.nn.silu(tokens @ p["gate"][j]) * (tokens @ p["up"][j])
                    ) @ p["down"][j]
            want = want + jnp.where(picks == j, gates, 0).sum(-1)[:, None] \
                * each
    assert rel(got[0], want) < 1e-5


def loops_in(m, params, *args, **kwargs):
    jaxpr = jax.make_jaxpr(lambda p, *a: m.apply(p, *a, **kwargs))(
        params, *args)
    return str(jaxpr).count("while[")


@pytest.mark.parametrize("case", ["decode_shaped", "experts_held_none",
                                  "a_short_bucket", "valid_alone"])
def test_where_a_block_holds_every_pair_no_loop_is_traced(case):
    """``C >= T * k`` (a decode step's 16 slots, a short bucket), every
    expert held, or ``valid`` with every expert held: no block is walked,
    all ``T * k`` rows carried and counted as visited."""
    held = None if case in ("experts_held_none", "valid_alone") else (8, 16)
    m, params, x = layer(held)
    if case in ("decode_shaped", "a_short_bucket"):
        x = x[:, :16 if case == "decode_shaped" else 32]
    kwargs = {"valid": jnp.ones(x.shape[:2], bool)} \
        if case != "experts_held_none" else {}
    assert loops_in(m, params, x, **kwargs) == 0
    _, sown = m.apply(params, x, mutable=[MOE_STATS], **kwargs)
    assert int(sown[MOE_STATS]["rows_visited"][0]) == x.shape[1] * K
    # the same layer at the walked size does trace one
    if held is not None:
        assert loops_in(*layer(held)) == 1


def test_the_block_follows_from_the_static_shapes():
    # A.X-K1's chunk and command-a-plus's longest bucket
    assert moe.held_block_rows(4096 * 8, 12, 192) == 4096
    assert moe.held_block_rows(8192 * 8, 16, 128) == 16384
    # command-a-plus's shortest bucket; a decode step never walks
    assert moe.held_block_rows(512 * 8, 16, 128) == 1024
    assert moe.held_block_rows(16 * 8, 12, 192) >= 16 * 8
    assert moe.held_block_rows(8 * 8, 16, 128) >= 8 * 8
    # half the experts and more: twice the even share is every pair
    assert moe.held_block_rows(4096 * 8, 32, 64) >= 4096 * 8


def test_the_walk_refuses_a_gradient_by_name():
    m, params, x = layer((8, 16))
    loss = lambda p: m.apply(p, x).sum()  # noqa: E731
    with pytest.raises(NotImplementedError,
                       match="experts_held.*blocks of 512.*no backward"):
        jax.grad(loss)(params)
    # where no block is walked the layer differentiates as before
    grads = jax.grad(lambda p: m.apply(p, x[:, :32]).sum())(params)
    assert float(jnp.abs(grads["params"]["gate"]).max()) > 0


def test_the_backend_counts_the_rows_its_layers_visited():
    """``TransformerBackend`` sums the layers' ``rows_visited`` into
    ``moe_counters`` out of the array that brings the pair counts, puts
    ``moe_rows`` on the call's span, and ``last_expert_pairs`` keeps its
    shape."""
    from horovod_tpu.models import Transformer, TransformerConfig
    from horovod_tpu.serving import ServingConfig, ServingEngine
    from horovod_tpu.serving.engine import TransformerBackend
    from horovod_tpu.utils import profiling

    cfg = TransformerConfig(
        vocab_size=64, num_layers=2, num_heads=2, embed_dim=16, mlp_dim=8,
        max_seq_len=320, dtype=jnp.float32, num_experts=E,
        experts_per_token=K, experts_held=(8, 16), moe_selection="sigmoid")
    model = Transformer(cfg)
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    backend = TransformerBackend(model, params, cfg, 2, 320)
    engine = ServingEngine(backend, ServingConfig(
        num_slots=2, buckets=(32, 256), max_seq_len=320))
    rng = np.random.default_rng(0)
    engine.submit([int(t) for t in rng.integers(0, 64, 200)], 3)
    engine.run_until_idle()
    c = backend.moe_counters
    # the 256 bucket walks blocks of 512 of its 2048 pairs, a layer; the two
    # decode steps' two slots carry their 16 pairs a layer as they are
    assert c["calls"] == 3 and c["pairs"] == (200 + 2) * 2 * K
    assert (c["rows_visited"] - 2 * 2 * 2 * K) % BLOCK == 0
    assert 2 * BLOCK <= c["rows_visited"] - 2 * 2 * 2 * K < 2 * 256 * K
    assert backend.last_expert_pairs.shape == (2, 8)
    calls = [r for r in profiling.spans()
             if r.name in (profiling.SRV_PREFILL, profiling.SRV_DECODE)][-3:]
    assert [r.name for r in calls] == [profiling.SRV_PREFILL] \
        + [profiling.SRV_DECODE] * 2
    assert sum(r.fields["moe_rows"] for r in calls) == c["rows_visited"]
    assert sum(r.fields["moe_held"] for r in calls) == c["held_pairs"]
    assert calls[1].fields["moe_rows"] == 2 * 2 * K
    summary = engine.span_summary()[profiling.SRV_PREFILL]["moe"]
    assert summary["rows"] >= calls[0].fields["moe_rows"]
    assert summary["rows_per_held_pair"] >= 1.0
    # the prefill walked: the rows of the row tiles its grouped matmuls
    # worked, whole tiles that hold every held pair and at most a tile an
    # expert more; a decode step walks nothing and says nothing
    tile = moe.WALK_ROW_TILE
    tile_rows = calls[0].fields["moe_tile_rows"]
    held = calls[0].fields["moe_held"]
    assert tile_rows == c["tile_rows"] and tile_rows % tile == 0
    assert held <= tile_rows <= calls[0].fields["moe_rows"] + 2 * 8 * tile
    assert all("moe_tile_rows" not in r.fields for r in calls[1:])
    assert summary["tile_rows"] >= tile_rows
    assert 1.0 <= summary["tile_rows_per_held_pair"]
    assert "tile_rows" not in engine.span_summary()[
        profiling.SRV_DECODE]["moe"]
    # no served layer moves its rows by hvd_moe_rows (PR 57): the counter
    # is on every sparse call's span and in both summaries, 0
    assert all(r.fields["moe_kernel_rows"] == 0 for r in calls)
    assert summary["kernel_rows"] == 0 == engine.span_summary()[
        profiling.SRV_DECODE]["moe"]["kernel_rows"]


def test_the_walk_runs_the_grouped_kernel_and_the_rest_xlas():
    """The walk's jaxpr holds the Pallas grouped matmul under its name (the
    fused gate-and-up and the down product) and no ``ragged_dot``; a decode
    step's holds ``ragged_dot`` and no kernel; the training layer's 2048
    pairs are carried through the same two kernels, no loop (PR 55)."""
    from horovod_tpu.utils import profiling

    def text(held, x):
        m, params, _ = layer(held)
        return str(jax.make_jaxpr(lambda p, x: m.apply(
            p, x, mutable=[MOE_STATS]))(params, x))

    _, _, x = layer((8, 16))
    walk = text((8, 16), x)
    assert walk.count(f"name={profiling.MOE_GROUPED}") == 2
    assert "ragged_dot" not in walk
    carried = text((8, 16), x[:, :16])
    assert carried.count("ragged_dot_general[") == 3
    assert profiling.MOE_GROUPED not in carried
    trained = text(None, x)
    assert trained.count(f"name={profiling.MOE_GROUPED}") == 2
    assert "ragged_dot" not in trained and "while[" not in trained
    # and the walk sows what its tiles held, the carried layers do not
    m, params, _ = layer((8, 16))
    _, sown = m.apply(params, x, mutable=[MOE_STATS])
    stats = sown[MOE_STATS]
    tile = moe.WALK_ROW_TILE
    n_held = int(stats["expert_pairs"][0].sum())
    assert n_held <= int(stats["tile_rows"][0]) <= n_held + 8 * tile
    assert int(stats["tile_rows"][0]) % tile == 0
    _, sown = m.apply(params, x[:, :16], mutable=[MOE_STATS])
    assert "tile_rows" not in sown[MOE_STATS]


def every_expert(routing):
    """An every-expert layer in bfloat16, as served: (the module, its
    parameters, x [1, T, D] over ``T * k`` = 512 pairs, a bucket's least, and
    for the MLP router the state the layer before handed on)."""
    k, e, router_dim = {"top1_with_a_router_state": (1, 4, 16),
                        "top8": (8, 16, 0)}[routing]
    m = MoEMLP(embed_dim=D, mlp_dim=F, axis_name=None, dtype=jnp.bfloat16,
               num_experts=e, experts_per_token=k, router_dim=router_dim)
    x = inputs(jax.random.PRNGKey(0), 512 // k).astype(jnp.bfloat16)
    more = {"router_state": jax.random.normal(
        jax.random.PRNGKey(4), (*x.shape[:2], router_dim))} \
        if router_dim else {}
    return m, m.init(jax.random.PRNGKey(1), x, **more), x, more


@pytest.mark.parametrize("hole", ["half_the_bucket_padding",
                                  "an_expert_left_empty"])
@pytest.mark.parametrize("routing", ["top1_with_a_router_state", "top8"])
def test_every_expert_given_valid_takes_a_bucket_through_the_kernel(
        routing, hole, monkeypatch):
    """A served prefill whose layer holds every expert (PR 53): given
    ``valid`` over a bucket's rows it carries them all, walks nothing, and
    multiplies them in the walk's kernels: two ``hvd_moe_grouped`` calls and
    no ``ragged_dot``, ``tile_rows`` sown, the router's state handed on as
    it was, a position that holds no token 0, and every other equal to the
    ``ragged_dot`` layer's within bfloat16's rounding (the fused gate-and-up
    rounds once where three products round three times: a dropped or
    misplaced pair reads 0.1 and more of the output's size)."""
    from horovod_tpu.utils import profiling

    m, params, x, more = every_expert(routing)
    t, k = x.shape[1], m.experts_per_token
    with monkeypatch.context() as patch:    # the ragged_dot layer: these
        # 512 pairs on a decode step's side of the line
        patch.setattr(moe, "GROUPED_ROW_TILE", 1024)
        carried, sown = jax.jit(lambda p, x: m.apply(
            p, x, mutable=[MOE_STATS], **more))(params, x)
    assert "tile_rows" not in sown[MOE_STATS]
    if hole == "half_the_bucket_padding":
        valid = jnp.arange(t)[None, :] < t // 2
    else:       # no live token picks expert 0
        valid = (sown[MOE_STATS]["picks"][0] != 0).all(-1)
        assert t // 4 < int(valid.sum()) < t
    apply = lambda p, x: m.apply(  # noqa: E731
        p, x, valid=valid, mutable=[MOE_STATS], **more)
    text = str(jax.make_jaxpr(apply)(params, x))
    assert text.count(f"name={profiling.MOE_GROUPED}") == 2
    assert "ragged_dot" not in text and "while[" not in text
    tiled, sown = jax.jit(apply)(params, x)
    if more:
        (tiled, state), (carried, want_state) = tiled, carried
        assert bool((state == want_state).all())
    stats = sown[MOE_STATS]
    n_live = int(valid.sum()) * k
    pairs = np.asarray(stats["expert_pairs"][0])
    assert pairs.sum() == n_live
    assert (pairs[0] == 0) == (hole == "an_expert_left_empty")
    assert int(stats["rows_visited"][0]) == t * k       # carried, all
    # ... by _dispatch and _permute: a served layer's rows may be dead, and
    # the move kernels (ops/moe_rows.py, PR 57) carry no mask
    assert int(stats["kernel_rows"][0]) == 0 and profiling.MOE_ROWS not in text
    tile = moe.WALK_ROW_TILE
    assert n_live <= int(stats["tile_rows"][0]) <= n_live + len(pairs) * tile
    assert int(stats["tile_rows"][0]) % tile == 0
    got, want = (y[0].astype(jnp.float32) for y in (tiled, carried))
    assert float(jnp.abs(got[~valid[0]]).max()) == 0.0
    assert float(jnp.abs(got - want)[valid[0]].max()) \
        < 2 ** -6 * float(jnp.abs(want).max())


@pytest.mark.parametrize("call", ["training", "a_decode_step"])
@pytest.mark.parametrize("routing", ["top1_with_a_router_state", "top8"])
def test_every_expert_in_training_and_in_a_decode_step_keeps_ragged_dot(
        routing, call, monkeypatch):
    """The same layer given ``valid`` over a decode step's rows keeps three
    ``ragged_dot`` and no kernel, and so does the training layer (no
    ``valid``) under 512 pairs.  At 512 pairs and more the training layer's
    products are the kernels too (PR 55): two ``hvd_moe_grouped`` calls
    forward (gate and up fused, down) and four more backward, ``tile_rows``
    sown, every gradient (the rows', the router's, the three weights') equal
    to the ``ragged_dot`` layer's within bfloat16's rounding; and the
    serving form over a bucket's rows, which refused a gradient by name,
    differentiates alike.  The training layer also moves its rows by kernel
    (PR 57): four ``hvd_moe_rows`` calls forward (the dispatch's tiles and
    fetch, the combine's send and sum), four more backward, ``kernel_rows``
    = 2 T k sown, 0 by every other form."""
    from horovod_tpu.utils import profiling

    m, params, x, more = every_expert(routing)
    first = lambda out: out[0] if more else out  # noqa: E731
    if call == "a_decode_step":         # 24 slots, a position each
        x = x[0, :24, None]
        more = {n: v[0, :24, None] for n, v in more.items()}
        more["valid"] = jnp.arange(24)[:, None] % 3 > 0
    apply = lambda p, x=x, more=more: m.apply(  # noqa: E731
        p, x, mutable=[MOE_STATS], **more)
    grouped = f"name={profiling.MOE_GROUPED}"
    if call == "a_decode_step":
        text = str(jax.make_jaxpr(apply)(params))
        assert text.count("ragged_dot_general[") == 3
        assert profiling.MOE_GROUPED not in text and "while[" not in text
        assert profiling.MOE_ROWS not in text
        sown = jax.jit(apply)(params)[1][MOE_STATS]
        assert "tile_rows" not in sown and int(sown["kernel_rows"][0]) == 0
        return
    # half the tokens, 256 pairs: XLA's kernels as before
    few = {n: v[:, :x.shape[1] // 2] for n, v in more.items()}
    text = str(jax.make_jaxpr(lambda p: apply(
        p, x[:, :x.shape[1] // 2], few))(params))
    assert text.count("ragged_dot_general[") == 3 and grouped not in text
    assert profiling.MOE_ROWS not in text
    moved = f"name={profiling.MOE_ROWS}"
    text = str(jax.make_jaxpr(apply)(params))
    assert text.count(grouped) == 2 and text.count(moved) == 4
    assert "ragged_dot" not in text and "while[" not in text
    sown = jax.jit(apply)(params)[1][MOE_STATS]
    assert int(sown["kernel_rows"][0]) == 2 * 512
    pairs, tile = np.asarray(sown["expert_pairs"][0]), moe.WALK_ROW_TILE
    assert pairs.sum() == 512 and int(sown["rows_visited"][0]) == 512
    assert 512 <= int(sown["tile_rows"][0]) <= 512 + len(pairs) * tile
    assert int(sown["tile_rows"][0]) % tile == 0

    cotangent = jax.random.normal(jax.random.PRNGKey(7), x.shape)
    loss = lambda p, x, **given: (first(m.apply(  # noqa: E731
        p, x, **more, **given)).astype(jnp.float32) * cotangent).sum()
    both = jax.grad(loss, argnums=(0, 1))
    text = str(jax.make_jaxpr(both)(params, x))
    assert text.count(grouped) == 6 and "ragged_dot" not in text
    # (four calls forward, four backward)
    assert text.count(moved) == 8
    ours = jax.jit(both)(params, x)
    over_a_bucket = functools.partial(loss, valid=jnp.ones(x.shape[:2], bool))
    assert profiling.MOE_ROWS not in str(jax.make_jaxpr(jax.grad(
        over_a_bucket, argnums=(0, 1)))(params, x))
    served = jax.jit(jax.grad(over_a_bucket, argnums=(0, 1)))(params, x)
    # the ragged_dot layer: the line between a step's rows and a bucket's
    # moved past these 512
    monkeypatch.setattr(moe, "GROUPED_ROW_TILE", 1024)
    both = jax.grad(loss, argnums=(0, 1))    # (traced anew)
    text = str(jax.make_jaxpr(both)(params, x))
    assert "ragged_dot" in text and profiling.MOE_ROWS not in text
    want = both(params, x)
    leaves = lambda grads: {**grads[0]["params"],  # noqa: E731
                            "the rows": grads[1]}
    assert {"gate", "up", "down"} < set(leaves(want))
    for name, exact in leaves(want).items():
        size = float(jnp.abs(exact).max())
        assert size > 0
        for got in (ours, served):
            assert float(jnp.abs(leaves(got)[name].astype(jnp.float32)
                                 - exact.astype(jnp.float32)).max()) \
                < 2 ** -5 * size, name


@pytest.mark.parametrize("t, c, d, dtype, live_share", [
    (256, 512, 32, jnp.bfloat16, 0.5), (700, 1024, 256, jnp.bfloat16, 0.9),
    (256, 512, 32, jnp.float32, 1.0), (256, 512, 32, jnp.bfloat16, 0.0),
    (300, 256, 384, jnp.bfloat16, 0.3)],
    ids=["half_live", "a_ragged_last_token_block", "float32_rows",
         "no_live_row", "lanes_in_three_tiles"])
def test_the_token_sum_kernel_is_a_float32_sum(t, c, d, dtype, live_share):
    """``ops/token_sum.add_rows_by_token`` (interpreted here) against the
    sum in float64: a row that is not live may hold anything."""
    from horovod_tpu.ops.token_sum import add_rows_by_token

    rng = np.random.default_rng(0)
    token = rng.integers(0, t, c).astype(np.int32)
    live = rng.permutation(np.arange(c) < int(c * live_share))
    rows = jnp.where(live[:, None],
                     jnp.asarray(rng.standard_normal((c, d)), dtype), jnp.nan)
    weight = jnp.asarray(rng.random(c), jnp.float32)
    into = jnp.asarray(rng.standard_normal((t, d)), jnp.float32)
    got = add_rows_by_token(into, rows, weight, jnp.asarray(token),
                            jnp.asarray(live))
    want = np.asarray(into, np.float64)
    exact = np.asarray(weight, np.float64)[:, None] \
        * np.asarray(rows.astype(jnp.float32), np.float64)
    np.add.at(want, token[live], exact[live])
    assert float(np.abs(np.asarray(got) - want).max()) < 2e-6
    with pytest.raises(ValueError, match="multiple of 128"):
        add_rows_by_token(into, rows[:100], weight[:100],
                          jnp.asarray(token[:100]), jnp.asarray(live[:100]))
