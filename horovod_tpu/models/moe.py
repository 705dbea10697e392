"""MoE transformer blocks — the sparse feed-forward of ``models/transformer.py``.

Beyond reference scope (the reference has no attention or MoE code; SURVEY
§2.9 lists EP as absent).  ``MoEMLP`` is a drop-in for the Transformer's
dense GLU MLP, in one of two layouts:

* **every expert here** (``num_experts`` > 0, ``axis_name=None``;
  ``TransformerConfig.num_experts``): the experts are three stacked leaves
  ``[E, D, F]``, ``[E, D, F]``, ``[E, F, D]`` beside a router ``[D, E]``.
  A token goes to its ``experts_per_token`` most probable experts and
  nothing is dropped: the (token, expert) pairs are sorted by expert, the
  tokens gathered into that order, the three matmuls run as grouped matmuls
  (``lax.ragged_dot``) with the per-expert counts as group sizes, and the
  results return to token order and are summed with their gate weights.
  Shapes are static (``tokens × experts_per_token`` rows) and a token's
  result does not depend on what else is in the batch.  The layer sows the
  two auxiliary losses a sparse model is trained with and its per-expert
  load (``MOE_LOSSES``, ``MOE_STATS``; docs/parallelism.md).  Data-parallel
  like any other layer: every chip holds all experts.  A chip's SHARE of
  the experts (``experts_held``, a serving layout) routes over all of them
  and computes its own; where its pairs are a small part of the ``T * k`` it
  walks them in blocks (``_walk_held``).  In a SERVED PREFILL, walked or not
  (a share held, or ``valid`` given, over a bucket's rows), and in the
  TRAINING layer (every expert held and no ``valid``), wherever the layer
  carries :data:`GROUPED_ROW_TILE` pairs and more, the three products are
  Pallas kernels over a work list of row tiles (ops/grouped_matmul.py,
  ``hvd_moe_grouped``: :func:`grouped_row_tile` rows a tile), forward and,
  where the layer is differentiated, backward (PR 55); so does a DECODE
  program that carries that many (a block-diffusion model's pass: slots x
  block positions, PR 56).  Fewer pairs, a one-token decode step's slots or
  a test's few tokens, keep XLA:TPU's own kernels for ``lax.ragged_dot``.
* **one expert per device** (``num_experts`` = 0; ``axis_name`` a bound mesh
  axis, ``TransformerConfig.moe_axis``): a router picks one expert per token
  (switch routing), tokens travel to the device holding their expert over
  ``lax.all_to_all`` (parallel/expert.py, a dense ``[T, E, C]`` one-hot
  dispatch), and the residual connection carries dropped (over-capacity)
  tokens unchanged.  Must run inside shard_map with the axis bound; each
  device holds ONE expert's weights (distinct via per-shard RNG folding —
  the same contract as tensor_parallel / pipeline stages).

Either way the parameter count is the number of experts times one expert's
while per-token FLOPs follow the experts a token visits — the MoE scaling
trade.
"""

from __future__ import annotations

import functools
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp
from flax.traverse_util import flatten_dict
from jax import lax

from horovod_tpu.models.transformer import _over_rows, row_blocks
from horovod_tpu.ops.grouped_matmul import (grouped_glu, grouped_matmul,
                                            visited_rows)
from horovod_tpu.ops.moe_rows import combine_rows, dispatch_rows
from horovod_tpu.ops.token_sum import add_rows_by_token
from horovod_tpu.parallel.common import shard_init_rng
from horovod_tpu.parallel.expert import expert_parallel_moe
from horovod_tpu.utils import profiling

# Collections the every-expert-here layout sows into, per layer, when the
# caller makes them mutable (``model.apply(..., mutable=[MOE_LOSSES])``):
MOE_LOSSES = "moe_losses"   # "load_balance", "router_z": f32 scalars
MOE_STATS = "moe_stats"     # "expert_pairs": [E] int32, pairs per expert
                            # (profiling.expert_load reads it); "picks":
                            # [B, S, k] int32, each token's experts;
                            # "rows_visited": int32, the rows the layer
                            # gathered, multiplied and combined for its pairs;
                            # where its products were hvd_moe_grouped's (a
                            # served prefill, a training step) "tile_rows":
                            # int32, the rows of
                            # the row tiles the kernel worked (a product's
                            # visits x tile rows); "kernel_rows": int32, the
                            # rows hvd_moe_rows moved between token order and
                            # expert order in this forward (2 * T * k in the
                            # training layer from GROUPED_ROW_TILE pairs on,
                            # 0 wherever _dispatch and _permute move them)


def moe_aux_loss(cfg, collections) -> jax.Array:
    """The auxiliary loss a sparse ``Transformer`` is trained with, from the
    collections ``model.apply(..., mutable=[MOE_LOSSES])`` returned: the
    layers' mean load-balancing loss times ``cfg.moe_load_balance_coef``
    plus their mean router z-loss times ``cfg.moe_router_z_coef``.  A user's
    loss adds it to the cross-entropy."""
    sown = flatten_dict(collections.get(MOE_LOSSES, {}))  # leaves: tuples
    if not sown:
        return jnp.zeros((), jnp.float32)

    def mean(name):
        values = [v for path, vs in sown.items() if path[-1] == name
                  for v in vs]
        return sum(values) / len(values)

    return (cfg.moe_load_balance_coef * mean("load_balance")
            + cfg.moe_router_z_coef * mean("router_z"))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _dispatch(tokens, order, inverse, k):
    """Rows of ``tokens`` [T, D] in pair order: row ``i`` is the token of
    pair ``order[i]`` (pair ``p`` is token ``p // k``).  A gather both ways:
    backward brings the cotangent back to token order through ``inverse``
    (``order``'s inverse permutation) and sums a token's ``k`` copies, where
    the gather's own transpose would be a scatter-add of T·k rows."""
    return tokens[order // k]


def _dispatch_fwd(tokens, order, inverse, k):
    return tokens[order // k], inverse


def _dispatch_bwd(k, inverse, g):
    back = g[inverse].reshape(-1, k, g.shape[-1])
    return back.sum(axis=1, dtype=jnp.float32).astype(g.dtype), None, None


_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@jax.custom_vjp
def _permute(x, perm, inverse):
    """``x[perm]`` for a permutation whose inverse is at hand: backward is
    the gather ``g[inverse]``, not a scatter."""
    return x[perm]


_permute.defvjp(lambda x, perm, inverse: (x[perm], inverse),
                lambda inverse, g: (g[inverse], None, None))


# rows a tile of XLA:TPU's grouped matmul (lax.ragged_dot) holds: the kernels
# of a decode step, what a walked block is a whole number of, and the line
# between a step's rows (slots x k: 24 to 128 in the served cells) and a
# bucket's or a training step's (1024 pairs and more): a layer that carries
# this many pairs multiplies them in hvd_moe_grouped's smaller tiles.  The
# rule is over the static shapes alone (t * k pairs, whole 128-row tiles) and
# a decode program may cross it: a block-diffusion model's pass carries
# slots x block x k pairs, 1536 at 48 slots of 4 positions and top-8 of 128
# experts, 12 rows an expert, and there the kernel took 10.3 ms of a pass's
# six layers where lax.ragged_dot's took 26.4, every slot live or half of
# them (v5e, at sdar30b-chat4k-open's size; PERF.md section 6, PR 56): few
# rows an expert are no reason to leave the work list, which visits a (row
# tile, expert) pair that meet and fetches an expert's weights once.
GROUPED_ROW_TILE = 512


def held_block_rows(pairs: int, held: int, experts: int) -> int:
    """Rows in a block of the walk (``_walk_held``), from the static shapes alone:
    twice the ``pairs * held / experts`` that ``held`` of ``experts`` get
    when the routing is even, up to a whole :data:`GROUPED_ROW_TILE`.  A
    layer whose block would hold all its ``pairs`` (``>= pairs``: half the
    experts held and more, a short bucket, a decode step) does not walk: it
    carries them all, the dead ones behind the last group."""
    twice_even = -(-2 * pairs * held // experts)
    return -(-twice_even // GROUPED_ROW_TILE) * GROUPED_ROW_TILE


# rows a row tile of a served prefill's grouped matmuls holds, walked or
# carried (ops/grouped_matmul.py): the MXU's own edge.  Swept on the chip at
# 64 to 512 rows over the three shares' served shapes, 32 to 520 rows an
# expert (PERF.md section 6, PR 51): 128 was the fastest or within 0.1% of it
# at every one, because the work list adapts to the rows a group holds by
# itself (a group of 500 rows is four visits that share one fetch of its
# weights, a group of 30 shares its tile with three others), so nothing is
# left for a rule over the static shapes to choose; 512, XLA:TPU's own, was
# 1.4-1.9 times slower.
WALK_ROW_TILE = 128
# ... and where the even share of the pairs, ``pairs // experts``, is
# WIDE_EVEN_SHARE rows and more.  One shape has been read there, the training
# layer of olmoe-s4096 (131072 pairs over 64 experts: 2048 rows an even share,
# 5 to 11705 by the cell's own routing, max over mean 5.7), the eight kernels
# of one jitted forward-and-backward (PERF.md section 6, PR 55): 27.13 ms at
# 128 rows, 26.73 at 256, 28.95 at 512 (XLA:TPU's ragged_dot kernels 40.59).
# A group of thousands of rows amortises a 256-row visit's fixed part (the
# grid step, the mask, the result's read-back) over twice the product and
# wastes at most 64 x 256 of 131072 rows; at 512 the wasted rows outweigh it.
WIDE_ROW_TILE = 256
WIDE_EVEN_SHARE = 1024


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _walk_blocks(static, tokens, order, pairs, gates, w_gate, w_up, w_down):
    """The routed sum [T, D] float32 of a layer that holds a share of the
    experts, over its own pairs alone.  ``order`` [T*k] has the held pairs
    first, by expert and inside an expert by token; ``pairs`` [held] is each
    expert's count.  Block ``i`` is sorted positions ``[i*C, (i+1)*C)``:
    its tokens gathered, its part of each expert's group through the three
    grouped matmuls, its rows weighted by their pairs' ``gates`` [T, k] in
    float32 and added to their tokens.  As many blocks run as the held
    pairs fill, ``ceil(pairs.sum() / C)``: one while the routing is near
    even, ``T*k / C`` if every pair were held; none is dropped at any load
    and no array has ``T*k`` rows.  The grouped matmuls are
    ops/grouped_matmul.py's, in row tiles of :data:`WALK_ROW_TILE`: gate, up
    and the activation one kernel (float32, rounded once), down another."""
    k, c = static
    t, d = tokens.shape
    n_held = pairs.sum()
    ends = jnp.cumsum(pairs)
    starts = ends - pairs
    # to whole blocks: a position past the held pairs counts for nothing
    order = jnp.pad(order, (0, -(t * k) % c))
    flat_gates = gates.reshape(t * k)

    def block(carry):
        i, out = carry
        at = i * c
        with jax.named_scope(profiling.MOE_DISPATCH):
            pair = lax.dynamic_slice(order, (at,), (c,))
            token = pair // k
            rows = tokens[token]                               # [C, D]
        with jax.named_scope(profiling.MOE_EXPERTS):
            sizes = (jnp.clip(ends - at, 0, c)
                     - jnp.clip(starts - at, 0, c))
            hidden = grouped_glu(rows, w_gate, w_up, sizes,
                                 tile=WALK_ROW_TILE)
            out_rows = grouped_matmul(hidden, w_down, sizes,   # [C, D]
                                      tile=WALK_ROW_TILE)
        with jax.named_scope(profiling.MOE_COMBINE):
            # rows past the last group are in no product: whatever the
            # kernels left there is taken out, not weighted
            live = at + jnp.arange(c) < n_held
            out = add_rows_by_token(out, out_rows, flat_gates[pair], token,
                                    live)
        return i + 1, out

    _, out = lax.while_loop(lambda carry: carry[0] * c < n_held, block,
                            (jnp.int32(0), jnp.zeros((t, d), jnp.float32)))
    return out


def _walk_blocks_fwd(static, *operands):
    return _walk_blocks(static, *operands), None


def _walk_blocks_bwd(static, _, g):
    raise NotImplementedError(
        f"MoEMLP(experts_held=...) walks its held pairs in blocks of "
        f"{static[1]} rows (models/moe.py, _walk_held) and has no backward: "
        f"a share of the experts is a serving layout.  Differentiate the "
        f"layer with every expert held (experts_held=None)")


_walk_blocks.defvjp(_walk_blocks_fwd, _walk_blocks_bwd)
# jitted, so that a program's layers (and a long bucket's chunks) share one
# tracing and one lowering of the walk: twenty a program otherwise, host
# seconds of every process's set-up (PERF.md section 6, PR 43)
_walk_held = jax.jit(_walk_blocks, static_argnums=0)


def grouped_row_tile(pairs: int, experts: int) -> int:
    """Rows a row tile of ``hvd_moe_grouped`` holds where a layer carries
    the ``pairs`` rows of a routing over ``experts``, from the static shapes
    alone: the even share ``pairs // experts`` decides.  Under
    :data:`WIDE_EVEN_SHARE` rows an expert :data:`WALK_ROW_TILE` (the served
    buckets: 32 to 520); from it on :data:`WIDE_ROW_TILE` where the pairs
    are whole tiles of it (the training layer: 2048)."""
    if pairs // experts >= WIDE_EVEN_SHARE and pairs % WIDE_ROW_TILE == 0:
        return WIDE_ROW_TILE
    return WALK_ROW_TILE


def _experts_in_tiles(tile, rows, pairs, w_gate, w_up, w_down):
    """The three products of a layer that carries its sorted rows [T*k, D],
    ``pairs`` [held] of them each expert's and the dead ones (a serving
    layer's) behind the last group: the walk's two kernels over them all,
    and where the layer is differentiated their backward's."""
    hidden = grouped_glu(rows, w_gate, w_up, pairs, tile=tile)
    return grouped_matmul(hidden, w_down, pairs, tile=tile)


# how the router's logits [T, E] become an expert's score for a token
SELECTIONS = {"softmax": functools.partial(jax.nn.softmax, axis=-1),
              "sigmoid": jax.nn.sigmoid}


class MoEMLP(nn.Module):
    """Sparse GLU feed-forward: [B, S, D] → [B, S, D].

    ``num_experts`` > 0 holds every expert here and routes each token to
    ``experts_per_token`` of them, dropless (``axis_name`` must be None).
    ``num_experts`` = 0 is the switch layout: one expert per device on
    ``axis_name``, ``capacity_factor`` bounding each expert's per-call
    token budget.
    """

    embed_dim: int
    mlp_dim: int
    axis_name: str | None = "ep"
    capacity_factor: float = 2.0
    dtype: Any = jnp.bfloat16
    num_experts: int = 0
    experts_per_token: int = 1
    # divide a token's gate weights by their sum (OLMoE does not)
    norm_topk_prob: bool = False
    param_dtype: Any = jnp.float32
    # how an expert is scored for a token: "softmax" over all experts'
    # logits (OLMoE) or "sigmoid" of each logit alone
    selection: str = "softmax"
    # experts every token visits, beside the routed ones: their mean is
    # added to the routed sum (leaves shared_gate / shared_up / shared_down)
    num_shared_experts: int = 0
    # (lo, hi): this chip holds routed experts lo..hi-1 of ``num_experts``
    # (the expert leaves are [hi - lo, ...]; the router keeps every output).
    # None holds them all.
    experts_held: tuple | None = None
    # what a token's gate weights are multiplied by, after norm_topk_prob
    # (a "routed scaling factor"); at 1 no op at all
    routed_scale: float = 1.0
    # "noaux_tc" routing: a bias an expert (parameter ``expert_bias`` [E])
    # is added to the scores that PICK a token's experts; the gate weights
    # are taken from the scores without it
    expert_bias: bool = False
    # group-limited picks: the experts in ``groups`` groups of E / groups,
    # a group's score the sum of its two best picking scores, a token's
    # experts taken inside its ``topk_groups`` best groups.  0: no groups.
    groups: int = 0
    topk_groups: int = 0
    # > 0: the router is no one matrix but an MLP with a state (the ``zaya``
    # family's): r = x W_d + b_d, that wide, plus ``router_decay`` * the r
    # of the layer before where the caller hands one (``router_state``);
    # the experts' logits are W_3 gelu(W_2 gelu(W_1 norm(r))) with biases on
    # the first two, norm an RMSNorm at ``norm_eps``; all in float32.  The
    # call then returns (out, r [B, S, router_dim] float32) for the next
    # layer.  Parameters ``router_down`` / ``_bias``, ``router_decay``,
    # ``router_norm``, ``router_w1`` / ``_b1``, ``router_w2`` / ``_b2``,
    # ``router_w3`` in place of ``router``.
    router_dim: int = 0
    norm_eps: float = 1e-6

    @nn.compact
    def __call__(self, x, valid=None, router_state=None):
        """``valid`` ([B, S] bool, ``num_experts`` > 0 only): positions that
        hold a token.  One that does not (a bucket's padding, a slot with no
        request) is routed to no expert: its pairs are in no group of the
        grouped matmuls and in no count.  ``router_state`` ([B, S,
        router_dim] float32, ``router_dim`` > 0 only): the state the layer
        before returned."""
        if self.num_experts > 0:
            return _all_experts_here(self, x, valid, router_state)
        if router_state is not None:
            raise ValueError("router_state is the every-expert-here "
                             "layout's MLP router's")
        if valid is not None:
            raise ValueError("valid is the every-expert-here layout's")
        n_experts = lax.axis_size(self.axis_name)
        b, s, d = x.shape
        if d != self.embed_dim:
            raise ValueError(
                f"MoEMLP(embed_dim={self.embed_dim}) got feature dim {d}")

        def expert_init(base):
            def init(rng, shape, dtype=jnp.float32):
                return base(shard_init_rng(rng, self.axis_name), shape,
                            dtype)
            return init

        lecun = nn.initializers.lecun_normal()
        router_w = self.param("router", nn.initializers.lecun_normal(),
                              (d, n_experts), jnp.float32)
        w_gate = self.param("gate", expert_init(lecun), (d, self.mlp_dim))
        w_up = self.param("up", expert_init(lecun), (d, self.mlp_dim))
        w_down = self.param("down", expert_init(lecun), (self.mlp_dim, d))

        def expert_fn(params, h):
            wg, wu, wd = params
            h = h.astype(self.dtype)
            return ((nn.silu(h @ wg.astype(self.dtype))
                     * (h @ wu.astype(self.dtype)))
                    @ wd.astype(self.dtype))

        tokens = x.reshape(b * s, d)
        out = expert_parallel_moe(
            expert_fn, (w_gate, w_up, w_down), router_w, tokens,
            capacity_factor=self.capacity_factor, axis_name=self.axis_name)
        return out.reshape(b, s, d).astype(x.dtype)


def _all_experts_here(m: MoEMLP, x, valid=None, router_state=None):
    """``MoEMLP.__call__`` for ``num_experts`` > 0 (a function, so that flax
    adds no method's name to the module path of what it traces)."""
    if m.axis_name is not None:
        raise ValueError(
            "MoEMLP(num_experts > 0) holds every expert on each device; "
            "sharding them over a mesh axis is the switch layout's "
            "(num_experts=0) and not combined with it yet")
    e, k, f = m.num_experts, m.experts_per_token, m.mlp_dim
    b, s, d = x.shape
    if d != m.embed_dim:
        raise ValueError(
            f"MoEMLP(embed_dim={m.embed_dim}) got feature dim {d}")
    if not 0 < k <= e:
        raise ValueError(f"experts_per_token={k} of num_experts={e}")
    if m.selection not in SELECTIONS:
        raise ValueError(f"selection {m.selection!r}; models/moe.py has "
                         f"{sorted(SELECTIONS)}")
    lo, hi = (0, e) if m.experts_held is None else m.experts_held
    if not 0 <= lo < hi <= e:
        raise ValueError(f"experts_held={m.experts_held} of num_experts={e}")
    held = hi - lo
    if m.groups and (e % m.groups or not 0 < m.topk_groups <= m.groups
                     or e // m.groups < 2
                     or k > m.topk_groups * (e // m.groups)):
        raise ValueError(
            f"groups={m.groups}, topk_groups={m.topk_groups}: {e} experts "
            f"in whole groups of two or more, and {k} picks inside the "
            f"groups kept")
    lecun = nn.initializers.lecun_normal
    r_dim = m.router_dim
    if router_state is not None and not r_dim:
        raise ValueError("router_state is an MLP router's (router_dim > 0)")
    if r_dim:
        f32 = lambda name, init, *shape: m.param(  # noqa: E731
            name, init, shape, m.param_dtype).astype(jnp.float32)
        zeros, ones = nn.initializers.zeros, nn.initializers.ones
        mlp_w = {"down": f32("router_down", lecun(), d, r_dim),
                 "down_bias": f32("router_down_bias", zeros, r_dim),
                 "norm": f32("router_norm", ones, r_dim),
                 "w1": f32("router_w1", lecun(), r_dim, r_dim),
                 "b1": f32("router_b1", zeros, r_dim),
                 "w2": f32("router_w2", lecun(), r_dim, r_dim),
                 "b2": f32("router_b2", zeros, r_dim),
                 "w3": f32("router_w3", lecun(), r_dim, e)}
        if router_state is not None:
            mlp_w["decay"] = f32("router_decay", ones, r_dim)
    else:
        router_w = m.param("router", lecun(), (d, e), m.param_dtype)
    if m.expert_bias:
        pick_bias = m.param("expert_bias", nn.initializers.zeros, (e,),
                            m.param_dtype)
    # fan-in is axis 1 of [E, in, out]; the experts are a batch
    stacked = lecun(in_axis=1, out_axis=2, batch_axis=0)
    w_gate = m.param("gate", stacked, (held, d, f), m.param_dtype)
    w_up = m.param("up", stacked, (held, d, f), m.param_dtype)
    w_down = m.param("down", stacked, (held, f, d), m.param_dtype)
    n_shared = m.num_shared_experts
    if n_shared:
        # the shared experts side by side are one GLU of width n * F:
        # expert j owns columns (rows of down) j * F .. (j + 1) * F
        sw_gate = m.param("shared_gate", lecun(), (d, n_shared * f),
                          m.param_dtype)
        sw_up = m.param("shared_up", lecun(), (d, n_shared * f),
                        m.param_dtype)
        sw_down = m.param("shared_down", lecun(), (n_shared * f, d),
                          m.param_dtype)
    t = b * s
    # A caller that says which positions hold a token, over several row
    # blocks of them (row_blocks: a served prefill's bucket or chunk; never
    # training, which names none, nor a decode step): the position-wise
    # parts of the layer, its router and its shared experts, run over the
    # row blocks up to the last position that holds one
    # (transformer._over_rows: one body in a loop), and the rows of the
    # blocks past it are 0.  The routed experts visit the held pairs of the
    # positions that hold a token whatever this does.  Not where the caller
    # collects the losses: they read every position's probabilities.
    live = None
    if valid is not None and row_blocks(s) \
            and not m.is_mutable_collection(MOE_LOSSES):
        live = jnp.max(jnp.where(valid, jnp.arange(1, s + 1), 0))

    def by_rows(fn, tokens, *more):
        """``fn(tokens, *more)`` for a position-wise ``fn`` [T', D] (and
        further [T', ...] arrays) -> a tree of [T', ...], a row block of
        ``x`` at a time up to ``live``."""
        if live is None:
            return fn(tokens, *more)
        out = _over_rows(
            lambda x, *more: jax.tree.map(
                lambda y: y.reshape(b, -1, *y.shape[1:]),
                fn(x.reshape(-1, d).astype(m.dtype),
                   *(y.reshape(-1, y.shape[-1]) for y in more))), live, x,
            *(y.reshape(b, s, -1) for y in more))
        return jax.tree.map(lambda y: y.reshape(t, *y.shape[2:]), out)

    def mlp_logits(tokens, before):
        """(the experts' logits [T, E], the state r [T, router_dim] this
        layer hands the next) of the MLP router, float32 throughout."""
        dot = functools.partial(jnp.dot, precision=lax.Precision.HIGHEST)
        r = dot(tokens.astype(jnp.float32), mlp_w["down"]) \
            + mlp_w["down_bias"]
        if before:
            r = r + mlp_w["decay"] * before[0]
        y = r * lax.rsqrt(jnp.mean(r * r, axis=-1, keepdims=True)
                          + m.norm_eps) * mlp_w["norm"]
        y = jax.nn.gelu(dot(y, mlp_w["w1"]) + mlp_w["b1"], approximate=False)
        y = jax.nn.gelu(dot(y, mlp_w["w2"]) + mlp_w["b2"], approximate=False)
        return dot(y, mlp_w["w3"]), r

    def route(tokens, *before):
        # in float32 whatever the compute dtype: a pick is a comparison
        if r_dim:
            logits, handed = mlp_logits(tokens, before)
        else:
            logits = jnp.dot(tokens.astype(jnp.float32),
                             router_w.astype(jnp.float32),
                             precision=lax.Precision.HIGHEST)
        probs = SELECTIONS[m.selection](logits)               # [T, E]
        chosen_by = probs       # what picks; the gates are probs' own
        if m.expert_bias:
            chosen_by = chosen_by + pick_bias.astype(jnp.float32)
        if m.groups:
            by_group = chosen_by.reshape(-1, m.groups, e // m.groups)
            best_two = lax.top_k(by_group, 2)[0].sum(axis=-1)  # [T, G]
            kept = lax.top_k(best_two, m.topk_groups)[1]
            kept = (kept[..., None] == jnp.arange(m.groups)).any(axis=-2)
            chosen_by = jnp.where(kept[..., None], by_group,
                                  -jnp.inf).reshape(chosen_by.shape)
        _, picks = lax.top_k(lax.stop_gradient(chosen_by), k)  # [T, k]
        gates = jnp.take_along_axis(probs, picks, axis=-1)
        if m.norm_topk_prob:
            gates = gates / gates.sum(axis=-1, keepdims=True)
        if m.routed_scale != 1.0:
            gates = gates * m.routed_scale
        # the losses' operands leave no loop: nobody reads them there
        out = (picks, gates) if live is not None \
            else (picks, gates, logits, probs)
        return ((handed,) if r_dim else ()) + out

    # Everything the layer does is under one of five scopes
    # (utils/profiling.py), so a trace's time in them is the layer's.
    with jax.named_scope(profiling.MOE_ROUTE):
        tokens = x.reshape(t, d).astype(m.dtype)
        before = () if router_state is None else (
            router_state.reshape(t, r_dim).astype(jnp.float32),)
        routed = by_rows(route, tokens, *before)
        if r_dim:
            handed, *routed = routed
        picks, gates, *every = routed

        everything = held == e and valid is None
        if everything:
            local = picks
        else:
            # The router, the picks and the gates are over all e experts.
            # A pair on an expert another chip holds, or of a position that
            # holds no token, sorts behind every held one, belongs to no
            # group of the grouped matmuls and adds nothing here; an absent
            # expert's gate has taken its part of the sum above.
            on_chip = (picks >= lo) & (picks < hi)
            if valid is not None:
                on_chip &= valid.reshape(t, 1)
            local = jnp.where(on_chip, picks - lo, held)
        pair_expert = local.reshape(t * k)      # pair p: token p // k
        order = jnp.argsort(pair_expert, stable=True)
        # A share of the experts visits its own pairs only, block_rows of
        # the sorted order at a time (_walk_held); where one block would
        # hold every pair (all experts here, a decode step, a short
        # bucket) the three scopes below carry the T*k rows as they are.
        block_rows = held_block_rows(t * k, held, e)
        walks = not everything and block_rows < t * k
        # A layer that carries a bucket's or a training step's rows (whole
        # row tiles of them; a decode step has fewer) multiplies them in the
        # walk's kernels: a visit a (row tile, expert) that meet, none for
        # the padding behind the last group.  At 16 experts and top-1 a layer
        # alone took 1.81 -> 0.87 ms at 2867 live rows of 4096, and walking
        # them in one block 1.01: its sum's float32 buffer, second sort and
        # hvd_token_sum cost more than _dispatch and _permute (PERF.md
        # section 6, PR 53).
        # (not while init runs the layer for its parameters' shapes: it
        # would trace and lower two kernels that nothing runs)
        in_tiles = not walks and t * k >= GROUPED_ROW_TILE \
            and t * k % WALK_ROW_TILE == 0 and not m.is_initializing()
        tile = WALK_ROW_TILE if walks else grouped_row_tile(t * k, e)
        # ... and where no row is dead (every expert held, no ``valid``: the
        # training layer) its two row moves and their backwards are
        # hvd_moe_rows' kernels, which carry no mask (ops/moe_rows.py:
        # 22.5 -> 13 ms of olmoe-s4096's step, PERF.md section 6, PR 57);
        # everywhere else _dispatch and _permute, XLA's gathers.
        in_kernels = everything and in_tiles
        if not walks and not in_kernels:
            inverse = jnp.argsort(order)    # (the kernels go by ``order``)
        pairs = (local[..., None] == jnp.arange(held)).sum(
            axis=(0, 1), dtype=jnp.int32)                     # [held]
        rows_visited = block_rows * (-(-pairs.sum() // block_rows)) \
            if walks else jnp.int32(t * k)
        if not m.is_initializing():  # init returns parameters only
            m.sow(MOE_STATS, "expert_pairs", pairs)
            m.sow(MOE_STATS, "rows_visited", rows_visited)
            if walks or in_tiles:
                # (a block is whole tiles, so the blocks' visits are those
                # of the held pairs laid end to end)
                m.sow(MOE_STATS, "tile_rows", visited_rows(pairs, tile))
            m.sow(MOE_STATS, "kernel_rows",
                  jnp.int32(2 * t * k if in_kernels else 0))
            m.sow(MOE_STATS, "picks", picks.reshape(b, s, k))
            if m.selection == "softmax" and live is None:
                logits, probs = every
                # E * sum_e f_e P_e: f_e the share of the pairs on expert e
                # (a constant to the gradient), P_e e's mean probability
                m.sow(MOE_LOSSES, "load_balance", e * jnp.sum(
                    pairs.astype(jnp.float32) / (t * k)
                    * probs.mean(axis=0)))
                m.sow(MOE_LOSSES, "router_z", jnp.mean(jnp.square(
                    jax.nn.logsumexp(logits, axis=-1))))
            elif m.is_mutable_collection(MOE_LOSSES):
                raise NotImplementedError(
                    f"the load-balancing and router z losses are defined "
                    f"for softmax selection; with selection="
                    f"{m.selection!r} the layer sows none: do not make "
                    f"{MOE_LOSSES!r} mutable")

    def done(out):
        """The layer's result, and for an MLP router its state beside."""
        out = out.reshape(b, s, d).astype(x.dtype)
        return (out, handed.reshape(b, s, r_dim)) if r_dim else out

    if walks:
        out = _walk_held((k, block_rows), tokens, order, pairs, gates,
                         w_gate.astype(m.dtype), w_up.astype(m.dtype),
                         w_down.astype(m.dtype))
        if not n_shared:
            return done(out)
    else:
        with jax.named_scope(profiling.MOE_DISPATCH):
            rows = dispatch_rows(tokens, order, k) if in_kernels \
                else _dispatch(tokens, order, inverse, k)     # [T*k, D]

        with jax.named_scope(profiling.MOE_EXPERTS):
            if in_tiles:
                # (the kernels' wrappers cast the weights themselves, so
                # that their gradients come back in the parameters' dtype)
                out_rows = _experts_in_tiles(tile, rows, pairs, w_gate,
                                             w_up, w_down)
            else:
                grouped = functools.partial(lax.ragged_dot,
                                            group_sizes=pairs)
                hidden = (nn.silu(grouped(rows, w_gate.astype(m.dtype)))
                          * grouped(rows, w_up.astype(m.dtype)))
                out_rows = grouped(hidden, w_down.astype(m.dtype))  # [T*k, D]

        with jax.named_scope(profiling.MOE_COMBINE):
            if in_kernels:
                # (where nothing is added to the sum in float32, it leaves
                # the kernel in the dtype it is handed on in: one rounding,
                # done()'s, and no pass of XLA's over [T, D] to make it)
                out = combine_rows(out_rows, gates, order,
                                   jnp.float32 if n_shared else x.dtype)
            else:
                by_token = _permute(out_rows, inverse, order).reshape(
                    t, k, d)
                if not everything:
                    # rows past the last group are in no product: whatever
                    # the grouped matmul left there is taken out, not
                    # weighted
                    by_token = jnp.where(on_chip[..., None], by_token, 0)
                out = (by_token.astype(jnp.float32)
                       * gates[..., None]).sum(1)
            if not n_shared:
                return done(out)

    def shared_sum(tokens):
        act = (nn.silu(tokens @ sw_gate.astype(m.dtype))
               * (tokens @ sw_up.astype(m.dtype)))
        return act @ sw_down.astype(m.dtype)     # the n experts' sum

    with jax.named_scope(profiling.MOE_SHARED):
        shared = by_rows(shared_sum, tokens)
        out = out + shared.astype(jnp.float32) * (1.0 / n_shared)
        return done(out)
