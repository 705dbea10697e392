"""Plain reference for the ``mla_moe_serve`` family: the forward pass of an
``axk1`` decoder (SK Telecom's A.X-K1 as its published ``config.json``
gives it: latent attention in every layer, one leading dense layer, then
sigmoid-routed experts beside a shared one) over one whole sequence, prompt
and served tokens together, and the logits of a run of its positions.

Written from the published description, in ``jax.numpy`` and float32 under
``jax.default_matmul_precision("highest")``, the layers written out one
after another, latent attention in its EXPANDED form only (K and V built
from the latent for every position): no cache, no absorbed products, no
batching, no kernel, nothing imported from the program under test.  The
weights are the benchmark's own, drawn from the seed by
``families/mla_moe_serve.py`` in the type the model is served in (bfloat16)
and cast up here, a matrix at a time.

A layer, with x the residual stream (a sequential pre-norm block, RMSNorm
with ``rms_norm_eps``)::

    h = RMSNorm(x)
    c_q   = RMSNorm(h W_DQ)                                [q_lora_rank]
    q     = c_q W_UQ -> heads x (nope | rope); the rope part rotated
    ckv   = h W_DKV                                        [kv_lora_rank | rope]
    c_kv  = RMSNorm(ckv[:kv_lora_rank]);  k_rope = rotate(ckv[kv_lora_rank:])
    [k_nope | v] = c_kv W_UKV -> heads x (nope | v_head_dim)
    k     = [k_nope | k_rope, the same for every head]
    p     = softmax(q k^T (nope + rope)^-1/2 m^2), causal, in float32
            m = 0.1 mscale_all_dim ln(factor) + 1   (YaRN)
    x    += (p v) W_O
    h = RMSNorm(x)
    layer < first_k_dense_replace:  x += (silu(h W_g) * h W_u) W_d
    else:  s = sigmoid(h W_r); picks = top-k of s over ALL experts
           g = routed_scaling_factor * s[picks] / sum s[picks]
           x += sum over picks that are HELD of g_e E_e(h) + E_shared(h)

and after the last layer RMSNorm and the untied head.

**Rotation.**  Pairs are ADJACENT (2i, 2i + 1), ``assumed`` in the
configuration: the family's checkpoints store the rotary dimensions so and
its code permutes q and k alike before a half-split rotation, which leaves
every q.k as it is.  The angle of pair i at position t is t f_i with YaRN's
frequencies: f_i = theta^(-2i/d) for the pairs that turn more than
``beta_fast`` times over the ``original_max_position_embeddings``, that
over ``factor`` for those that turn fewer than ``beta_slow`` times, and the
linear blend between the two correction dimensions; cos and sin are
multiplied by yarn_mscale(factor, mscale) / yarn_mscale(factor,
mscale_all_dim).

**The chip's share.**  ``held = (lo, hi)`` says which routed experts the
weights hold (``layers[i]["experts"]`` is stacked ``[hi - lo, ...]``).  The
router keeps all its outputs, the top-k and the normalisation run over all
of them, and what an absent expert would add is left out, as on a chip of
an expert-parallel group before the exchange.  ``held = (0,
n_routed_experts)`` with every expert's weights is the uncut layer.

Parameter layout (the reference's own; ``x @ W`` orientation)::

    {"embed_tokens": [V, E], "lm_head": [E, V], "norm": [E],
     "layers": [{"input_layernorm": [E], "post_attention_layernorm": [E],
                 "q_a_proj": [E, Rq], "q_a_layernorm": [Rq],
                 "q_b_proj": [Rq, H * (nope + rope)],
                 "kv_a_proj_with_mqa": [E, Rkv + rope],
                 "kv_a_layernorm": [Rkv],
                 "kv_b_proj": [Rkv, H * (nope + v)], "o_proj": [H * v, E],
                 # a dense layer:
                 "mlp": {"gate_proj": [E, F], "up_proj": [E, F],
                         "down_proj": [F, E]},
                 # a sparse layer:
                 "router": [E, N],
                 "experts": {"gate_proj": [held, E, Fm], "up_proj": ...,
                             "down_proj": [held, Fm, E]},
                 "shared_experts": {"gate_proj": [E, n Fm], "up_proj": ...,
                                    "down_proj": [n Fm, E]}}, ...]}

Departures from the description.  (1) ``topk_method: "none"`` is read as no
group limit and no correction term (``assumed``): ``n_group`` and
``topk_group`` are not read.  (2) ``query_block`` only bounds memory: the
attention of a block of queries still sees every earlier key, and the dense
layer's feed-forward runs a block of positions at a time.

``operand_dtype`` is the control's switch, never the benchmark's: with
``jnp.float8_e4m3fn`` both operands of every product are rounded to that
type first (the router's too), the step below bfloat16.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

F32 = jnp.float32


def rms_norm(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * weight


def yarn_get_mscale(scale, mscale):
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


def yarn_inv_freq(dim, theta, scaling):
    """([dim/2] frequencies, the factor on cos and sin) of ``rope_scaling``
    of type "yarn"."""
    factor = scaling["factor"]
    original = scaling["original_max_position_embeddings"]

    def correction_dim(rotations):
        return dim * math.log(original / (rotations * 2 * math.pi)) / (
            2 * math.log(theta))

    low = max(math.floor(correction_dim(scaling["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(scaling["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    freq_extra = 1.0 / theta ** (jnp.arange(0, dim, 2, dtype=F32) / dim)
    freq_inter = freq_extra / factor
    # 1 where the published frequency is kept, 0 where it is interpolated
    keep = 1.0 - jnp.clip((jnp.arange(dim // 2, dtype=F32) - low)
                          / (high - low), 0.0, 1.0)
    return (freq_inter * (1.0 - keep) + freq_extra * keep,
            yarn_get_mscale(factor, scaling["mscale"])
            / yarn_get_mscale(factor, scaling["mscale_all_dim"]))


def rotary_adjacent(x, positions, inv_freq, amplitude):
    """x [S, H, D]; the pair is (2i, 2i + 1)."""
    ang = positions[:, None] * inv_freq[None, :]            # [S, D/2]
    cos = amplitude * jnp.cos(ang)[:, None, :]
    sin = amplitude * jnp.sin(ang)[:, None, :]
    even, odd = x[..., 0::2], x[..., 1::2]
    out = jnp.stack([even * cos - odd * sin, even * sin + odd * cos], -1)
    return out.reshape(x.shape)


def _rounder(operand_dtype):
    if operand_dtype is None:
        return lambda x: x
    return lambda x: x.astype(operand_dtype).astype(F32)


def attention(c_q, queries, k, v, scale, query_block, r):
    """c_q [S, Rq] and ``queries(rows of c_q, their positions) -> [n, H,
    Dk]`` (a block's queries are made in the block: only K and V lie whole),
    k [S, H, Dk], v [S, H, Dv] -> [S, H, Dv]; causal; softmax in float32."""
    s, h = k.shape[:2]
    key_pos = jnp.arange(s)

    def block(args):
        cb, qpos = args
        scores = jnp.einsum("qhd,khd->hqk", r(queries(cb, qpos)),
                            r(k)) * scale
        mask = key_pos[None, None, :] <= qpos[None, :, None]
        scores = jnp.where(mask, scores, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", r(jax.nn.softmax(scores, -1)),
                          r(v))

    if query_block is None or query_block >= s:
        return block((c_q, key_pos))
    n = s // query_block
    out = jax.lax.map(block, (c_q.reshape(n, query_block, -1),
                              key_pos.reshape(n, query_block)))
    return out.reshape(s, h, v.shape[-1])


def glu(x, gate, up, down, mm):
    return mm(jax.nn.silu(mm(x, gate)) * mm(x, up), down)


def feed_forward(h, w, cfg, held, mm, r):
    """routed + shared for h [S, E]; returns (out, picks [S, k])."""
    k = cfg["num_experts_per_tok"]
    lo, hi = held
    scores = jax.nn.sigmoid(r(h) @ r(w["router"].astype(F32)))
    picks = jax.lax.top_k(scores, k)[1]
    gates = jnp.take_along_axis(scores, picks, axis=-1)
    if cfg["norm_topk_prob"]:
        gates = gates / gates.sum(axis=-1, keepdims=True)
    gates = gates * cfg["routed_scaling_factor"]

    def one(total, expert):
        j, gate, up, down = expert
        # this expert's gate for each position: 0 where it was not picked
        weight = jnp.sum(jnp.where(picks == lo + j, gates, 0.0), axis=-1)
        return total + weight[:, None] * glu(h, gate, up, down, mm), None

    ex, sh = w["experts"], w["shared_experts"]
    routed, _ = jax.lax.scan(one, jnp.zeros_like(h), (
        jnp.arange(hi - lo), ex["gate_proj"], ex["up_proj"],
        ex["down_proj"]))
    # n shared experts side by side are one GLU of width n Fm: their sum
    shared = glu(h, sh["gate_proj"], sh["up_proj"], sh["down_proj"], mm)
    return routed + shared, picks


def hidden_states(params, tokens, cfg, held, query_block=None,
                  operand_dtype=None):
    """tokens [S] -> (final-norm hidden states [S, E] float32, the picks of
    every sparse layer [L_sparse, S, k])."""
    h_ = cfg["num_attention_heads"]
    nope, rot, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                     cfg["v_head_dim"])
    rkv, eps = cfg["kv_lora_rank"], cfg["rms_norm_eps"]
    scaling = cfg["rope_scaling"]
    inv_freq, amplitude = yarn_inv_freq(rot, float(cfg["rope_theta"]),
                                        scaling)
    scale = (nope + rot) ** -0.5 * yarn_get_mscale(
        scaling["factor"], scaling["mscale_all_dim"]) ** 2
    s = tokens.shape[0]
    pos = jnp.arange(s, dtype=F32)
    r = _rounder(operand_dtype)

    def mm(x, w):
        return r(x) @ r(w.astype(F32))

    def norm(x, w):
        return rms_norm(x, w.astype(F32), eps)

    x = params["embed_tokens"][tokens].astype(F32)
    all_picks = []
    for i, w in enumerate(params["layers"]):
        h = norm(x, w["input_layernorm"])
        c_q = norm(mm(h, w["q_a_proj"]), w["q_a_layernorm"])

        def queries(c, at, w=w):
            q = mm(c, w["q_b_proj"]).reshape(-1, h_, nope + rot)
            return jnp.concatenate([q[..., :nope], rotary_adjacent(
                q[..., nope:], at.astype(F32), inv_freq, amplitude)], -1)

        ckv = mm(h, w["kv_a_proj_with_mqa"])
        c_kv = norm(ckv[:, :rkv], w["kv_a_layernorm"])
        k_rope = rotary_adjacent(ckv[:, None, rkv:], pos, inv_freq,
                                 amplitude)                 # [S, 1, rot]
        kv = mm(c_kv, w["kv_b_proj"]).reshape(s, h_, nope + dv)
        k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(
            k_rope, (s, h_, rot))], axis=-1)
        a = attention(c_q, queries, k, kv[..., nope:], scale, query_block,
                      r)
        x = x + mm(a.reshape(s, h_ * dv), w["o_proj"])
        h = norm(x, w["post_attention_layernorm"])
        if i < cfg["first_k_dense_replace"]:
            mlp = w["mlp"]
            dense = lambda hb: glu(hb, mlp["gate_proj"],  # noqa: E731
                                   mlp["up_proj"], mlp["down_proj"], mm)
            if query_block is None or query_block >= s:
                x = x + dense(h)
            else:
                x = x + jax.lax.map(dense, h.reshape(
                    s // query_block, query_block, -1)).reshape(h.shape)
        else:
            f, picks = feed_forward(h, w, cfg, held, mm, r)
            x = x + f
            all_picks.append(picks)
    return norm(x, params["norm"]), jnp.stack(all_picks)


def logits_of_rows(params, tokens, cfg, held, start, rows: int,
                   query_block=None, operand_dtype=None):
    """(logits [rows, V], picks [L_sparse, rows, k]) of positions ``start
    .. start + rows - 1`` of one sequence ``tokens`` [S].  ``start`` may be
    traced; ``rows`` is a shape.  The logits are over the rows of the head
    the weights hold (a slice of the vocabulary is a smaller vocabulary)."""
    with jax.default_matmul_precision("highest"):
        x, picks = hidden_states(params, tokens, cfg, held, query_block,
                                 operand_dtype)
        x = jax.lax.dynamic_slice_in_dim(x, start, rows, axis=0)
        picks = jax.lax.dynamic_slice_in_dim(picks, start, rows, axis=1)
        r = _rounder(operand_dtype)
        return r(x) @ r(params["lm_head"].astype(F32)), picks
