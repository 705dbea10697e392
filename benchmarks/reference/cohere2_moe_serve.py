"""Plain reference for the ``cohere2_moe_serve`` family: the forward pass of
a ``cohere2_moe`` decoder (CohereLabs' command-a-plus-05-2026 as its
published ``config.json`` and the catalog's ``described_as`` give it) over
one whole sequence, prompt and served tokens together, and the logits of a
run of its positions.

Written from the published description, in ``jax.numpy`` and float32 under
``jax.default_matmul_precision("highest")``, the layers written out one
after another: no cache, no batching, no kernel, nothing imported from the
program under test.  The weights are the benchmark's own, drawn from the
seed by ``families/cohere2_moe_serve.py`` in the type the model is served
in (bfloat16) and cast up here, a matrix at a time.

A layer, with x the residual stream (one norm a layer, a parallel block)::

    h = LN(x)          mean taken out, eps 1e-5, a scale and no bias
    a = attention(h)   q 128 heads, k and v 8 heads of 128, query head n
                       reads KV head n // 16, softmax(q k^T / sqrt 128) in
                       float32.  "sliding_attention": q and k rotated in
                       ADJACENT pairs (2i, 2i+1), theta 50000, and position i
                       sees i - 4096 < j <= i.  "full_attention": no
                       rotation, every j <= i.
    s = sigmoid(h Wr)  over all 128 experts; picks = top-8 of s;
                       g = s[picks] / sum s[picks]
    routed = sum over picks that are HELD of g_e E_e(h)
    shared = 1/4 sum of the four shared experts (``assumed``: "average" is
             the mean of the shared experts, added to the routed sum)
    E(h) = (silu(h W_gate) * h W_up) W_down
    x <- x + a + routed + shared

and after the last layer LN again and ``logit_scale`` x E^T with the tied
embedding.

**The chip's share.**  ``held = (lo, hi)`` says which routed experts the
weights hold (``layers[i]["experts"]`` is stacked ``[hi - lo, ...]``, expert
``lo + j`` at row ``j``).  The router keeps all its outputs, the top-8 and
the normalisation run over all of them, and what an absent expert would add
is left out: the partial result goes on to the next layer, as on a chip of
an expert-parallel group before the exchange.  ``held = (0, num_experts)``
with every expert's weights is the uncut layer.

Parameter layout (the reference's own; ``x @ W`` orientation)::

    {"embed_tokens": [V, E],
     "layers": [{"input_layernorm": [E], "q_proj": [E, H*D],
                 "k_proj": [E, KV*D], "v_proj": [E, KV*D],
                 "o_proj": [H*D, E], "router": [E, N],
                 "experts": {"gate_proj": [held, E, F],
                             "up_proj": [held, E, F],
                             "down_proj": [held, F, E]},
                 "shared_experts": {"gate_proj": [n, E, F],
                                    "up_proj": [n, E, F],
                                    "down_proj": [n, F, E]}}, ...],
     "norm": [E]}

Departures from the description.  (1) No vision tower: text only.
(2) ``query_block`` only bounds memory: a query still sees every key its
layer type lets it see.

``operand_dtype`` is the control's switch, never the benchmark's: with
``jnp.float8_e4m3fn`` both operands of every product are rounded to that
type first (the router's too), the step below bfloat16.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32


def layer_norm(x, weight, eps):
    x = x - jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * weight


def rotary_adjacent(x, positions, theta):
    """x [S, H, D]; the pair is (2i, 2i + 1) ("rope_gptj"), angle
    position * theta^(-2i / D)."""
    d = x.shape[-1]
    inv_freq = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=F32) / d)
    ang = positions[:, None] * inv_freq[None, :]            # [S, D/2]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    even, odd = x[..., 0::2], x[..., 1::2]
    out = jnp.stack([even * cos - odd * sin, even * sin + odd * cos], -1)
    return out.reshape(x.shape)


def _rounder(operand_dtype):
    if operand_dtype is None:
        return lambda x: x
    return lambda x: x.astype(operand_dtype).astype(F32)


def attention(q, k, v, window, query_block, r):
    """q [S, H, D], k/v [S, KV, D] -> [S, H, D]; softmax in float32; with
    ``window`` position i sees i - window < j <= i."""
    s, h, d = q.shape
    k = jnp.repeat(k, h // k.shape[1], axis=1)
    v = jnp.repeat(v, h // v.shape[1], axis=1)
    key_pos = jnp.arange(s)

    def block(args):
        qb, qpos = args
        scores = jnp.einsum("qhd,khd->hqk", r(qb), r(k)) / jnp.sqrt(F32(d))
        mask = key_pos[None, None, :] <= qpos[None, :, None]
        if window is not None:
            mask &= key_pos[None, None, :] > qpos[None, :, None] - window
        scores = jnp.where(mask, scores, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", r(jax.nn.softmax(scores, -1)),
                          r(v))

    if query_block is None or query_block >= s:
        return block((q, key_pos))
    n = s // query_block
    out = jax.lax.map(block, (q.reshape(n, query_block, h, d),
                              key_pos.reshape(n, query_block)))
    return out.reshape(s, h, d)


def glu(x, gate, up, down, mm):
    return mm(jax.nn.silu(mm(x, gate)) * mm(x, up), down)


def route(h, router, k, r):
    """Scores [S, N] (sigmoid of each expert's logit) and the top-k experts
    [S, k] of each position."""
    scores = jax.nn.sigmoid(r(h) @ r(router.astype(F32)))
    return scores, jax.lax.top_k(scores, k)[1]


def feed_forward(h, w, cfg, held, mm, r):
    """routed + shared for h [S, E]; returns (out, picks [S, k])."""
    k, n_shared = cfg["num_experts_per_tok"], cfg["num_shared_experts"]
    lo, hi = held
    scores, picks = route(h, w["router"], k, r)
    gates = jnp.take_along_axis(scores, picks, axis=-1)
    if cfg["norm_topk_prob"]:
        gates = gates / gates.sum(axis=-1, keepdims=True)

    def one(total, expert):
        j, gate, up, down = expert
        # this expert's gate for each position: 0 where it was not picked
        weight = jnp.sum(jnp.where(picks == lo + j, gates, 0.0), axis=-1)
        return total + weight[:, None] * glu(h, gate, up, down, mm), None

    ex = w["experts"]
    routed, _ = jax.lax.scan(one, jnp.zeros_like(h), (
        jnp.arange(hi - lo), ex["gate_proj"], ex["up_proj"],
        ex["down_proj"]))
    sh = w["shared_experts"]
    shared = sum(glu(h, sh["gate_proj"][j], sh["up_proj"][j],
                     sh["down_proj"][j], mm) for j in range(n_shared))
    return routed + shared / n_shared, picks


def hidden_states(params, tokens, cfg, held, query_block=None,
                  operand_dtype=None):
    """tokens [S] -> (final-norm hidden states [S, E] float32, the picks of
    every layer [L, S, k])."""
    h_, kv_ = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d = cfg["head_dim"]
    eps, theta = cfg["layer_norm_eps"], float(cfg["rope_theta"])
    s = tokens.shape[0]
    pos = jnp.arange(s, dtype=F32)
    r = _rounder(operand_dtype)

    def mm(x, w):
        return r(x) @ r(w.astype(F32))

    x = params["embed_tokens"][tokens].astype(F32)
    all_picks = []
    for kind, w in zip(cfg["layer_types"], params["layers"]):
        h = layer_norm(x, w["input_layernorm"].astype(F32), eps)
        q = mm(h, w["q_proj"]).reshape(s, h_, d)
        k = mm(h, w["k_proj"]).reshape(s, kv_, d)
        v = mm(h, w["v_proj"]).reshape(s, kv_, d)
        if kind == "sliding_attention":
            q, k = (rotary_adjacent(q, pos, theta),
                    rotary_adjacent(k, pos, theta))
            window = cfg["sliding_window"]
        elif kind == "full_attention":
            window = None
        else:
            raise ValueError(f"layer type {kind!r}")
        a = attention(q, k, v, window, query_block, r).reshape(s, h_ * d)
        f, picks = feed_forward(h, w, cfg, held, mm, r)
        x = x + mm(a, w["o_proj"]) + f
        all_picks.append(picks)
    return (layer_norm(x, params["norm"].astype(F32), eps),
            jnp.stack(all_picks))


def logits_of_rows(params, tokens, cfg, held, start, rows: int,
                   query_block=None, operand_dtype=None):
    """(logits [rows, V], picks [L, rows, k]) of positions ``start .. start
    + rows - 1`` of one sequence ``tokens`` [S].  ``start`` may be traced;
    ``rows`` is a shape.  The logits are over the rows of the embedding the
    weights hold (a slice of the vocabulary is a smaller vocabulary)."""
    with jax.default_matmul_precision("highest"):
        x, picks = hidden_states(params, tokens, cfg, held, query_block,
                                 operand_dtype)
        x = jax.lax.dynamic_slice_in_dim(x, start, rows, axis=0)
        picks = jax.lax.dynamic_slice_in_dim(picks, start, rows, axis=1)
        r = _rounder(operand_dtype)
        logits = r(x) @ r(params["embed_tokens"].astype(F32)).T
        return cfg["logit_scale"] * logits, picks
