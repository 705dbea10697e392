"""Plain reference for the ``mhc_mla_moe_serve`` family: the forward pass of
a ``xing4_0`` decoder (XingChen-AGI's Xing4.0-29B-A4B as its published
``config.json`` gives it: a residual stream of ``hc_mult`` rows mixed by
manifold-constrained hyper-connections around latent attention in every
layer, leading dense layers, then sigmoid-routed experts picked by a
bias-corrected top-k beside a shared one) over one whole sequence, prompt
and served tokens together, and the logits of a run of its positions.

Written from the published description and DeepSeek-AI's "mHC:
Manifold-Constrained Hyper-Connections", in ``jax.numpy`` and float32 under
``jax.default_matmul_precision("highest")``, a layer a function so that a
caller may draw each layer's weights as it is reached (the cut model's 5 666
M parameters are 22.7 GB in float32): the Sinkhorn loop written as the
iterations it is, latent attention in its EXPANDED form over the whole
sequence, every expert in a loop, no cache, no batching, no kernel, nothing
imported from the program under test.  The weights are the benchmark's own,
drawn from the seed by ``families/mhc_mla_moe_serve.py`` in the type the
model is served in (bfloat16) and cast up here, a matrix at a time.

The stream of a position is X [n, C] (n = ``hc_mult``), the token's
embedding in each of its n rows before the first layer.  A layer has two
sublayers F (latent attention, the feed-forward), each with its RMSNorm and
its own hyper-connection ``hc``::

    x~     = vec(X) / sqrt(mean(vec(X)^2) + hc_eps)          all nC values
    H_pre  = sigmoid(alpha_pre (x~ phi_pre) + b_pre)                      [n]
    H_post = 2 sigmoid(alpha_post (x~ phi_post) + b_post)                 [n]
    M      = exp(clip(alpha_res mat(x~ phi_res) + b_res,
                      mhc_h_res_clamp_min, mhc_h_res_clamp_max))       [n, n]
    hc_sinkhorn_iters times:
        M = M / (colsum(M) + hc_eps);  M = M / (rowsum(M) + hc_eps)
    H_res  = M
    h      = sum_j H_pre[j] X[j]
    y      = F(RMSNorm(h))
    X'[i]  = sum_j H_res[i, j] X[j] + H_post[i] y

After the last layer the n rows are summed, then RMSNorm and the untied
head.  ``mat`` fills [n, n] row by row (entry i n + j of ``x~ phi_res`` is
H~res[i, j]).

**Latent attention** is ``reference/mla_moe_serve.py``'s, copied: c_q =
RMSNorm(h W_DQ), q = c_q W_UQ -> heads x (nope | rope), [c_kv | k_R] = h
W_DKV, c_kv = RMSNorm(c_kv), [k_N | v] = c_kv W_UKV, the rope parts rotated
(pairs ADJACENT, YaRN's frequencies and its factor on cos and sin), p =
softmax(q k^T (nope + rope)^-1/2 m^2) causal, m = 0.1 mscale_all_dim
ln(factor) + 1, out = (p v) W_O.

**Routed** (``topk_method`` "noaux_tc"; ``n_group`` 1 and ``topk_group`` 1
are no group limit)::

    s = sigmoid(h W_r);  s' = s + e_score_correction_bias      (float32)
    picks = the k largest s';   w_e = routed_scaling_factor s_e / sum over
    picks of s  (s, not s');    routed(h) = sum over picks of w_e E_e(h)

``held = (lo, hi)`` says which routed experts the weights hold, as in every
sparse family here; this configuration holds them all.

Parameter layout (the reference's own; ``x @ W`` orientation)::

    {"embed_tokens": [V, E], "lm_head": [E, V], "norm": [E],
     "layers": [{"input_layernorm": [E], "post_attention_layernorm": [E],
                 "attn_hc", "mlp_hc": {"phi_pre": [n E, n], "phi_post":
                     [n E, n], "phi_res": [n E, n n], "b_pre": [n],
                     "b_post": [n], "b_res": [n, n], "alpha": [3] (pre,
                     post, res)},
                 "q_a_proj": [E, Rq], "q_a_layernorm": [Rq],
                 "q_b_proj": [Rq, H (nope + rope)],
                 "kv_a_proj_with_mqa": [E, Rkv + rope],
                 "kv_a_layernorm": [Rkv],
                 "kv_b_proj": [Rkv, H (nope + v)], "o_proj": [H v, E],
                 # a dense layer:
                 "mlp": {"gate_proj": [E, F], "up_proj": ..., "down_proj"},
                 # a sparse layer:
                 "router": [E, N], "e_score_correction_bias": [N],
                 "experts": {"gate_proj": [held, E, Fm], ...},
                 "shared_experts": {"gate_proj": [E, n_shared Fm], ...}}]}

Departures from the description, each under ``assumed`` in the
configuration's file: the catalog's ``config.json`` gives the five ``hc_*``
numbers and nothing else of the residual path, so the forms above (sigmoid,
2 sigmoid, exp then Sinkhorn, columns before rows, the clamp before exp,
``hc_eps`` in both denominators and in the flat norm, no learned scale on
the flat norm, the embedding copied to all rows, the rows summed at the
end, parameters a sublayer) are the paper's as recalled; the multi-token
prediction module is not loaded.  ``query_block`` only bounds memory.

``operand_dtype`` is the control's switch, never the benchmark's: with
``jnp.float8_e4m3fn`` both operands of every product are rounded to that
type first (the router's and the hyper-connections' too), the step below
bfloat16.
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


def sinkhorn(m, iters: int, eps: float):
    """``m`` [..., n, n] positive -> after ``iters`` rounds of its columns,
    then its rows, divided by their sums."""
    for _ in range(iters):
        m = m / (m.sum(axis=-2, keepdims=True) + eps)
        m = m / (m.sum(axis=-1, keepdims=True) + eps)
    return m


def hyper_coefficients(x, w, cfg, mm):
    """The stream x [S, n, C] -> (H_pre [S, n], H_post [S, n], H_res [S, n,
    n]) of one sublayer's hyper-connection ``w``."""
    s, n, _ = x.shape
    eps = cfg["hc_eps"]
    flat = x.reshape(s, -1)
    flat = flat / jnp.sqrt(jnp.mean(flat * flat, axis=-1, keepdims=True)
                           + eps)
    alpha = w["alpha"].astype(F32)
    pre = jax.nn.sigmoid(alpha[0] * mm(flat, w["phi_pre"])
                         + w["b_pre"].astype(F32))
    post = 2.0 * jax.nn.sigmoid(alpha[1] * mm(flat, w["phi_post"])
                                + w["b_post"].astype(F32))
    res = alpha[2] * mm(flat, w["phi_res"]).reshape(s, n, n) \
        + w["b_res"].astype(F32)
    res = jnp.exp(jnp.clip(res, cfg["mhc_h_res_clamp_min"],
                           cfg["mhc_h_res_clamp_max"]))
    return pre, post, sinkhorn(res, cfg["hc_sinkhorn_iters"], eps)


def hyper_read(pre, x):
    """h [S, C] = sum_j H_pre[j] X[j]."""
    return jnp.einsum("sj,sjc->sc", pre, x)


def hyper_write(res, post, x, y):
    """X' [S, n, C] = H_res X + H_post y."""
    return jnp.einsum("sij,sjc->sic", res, x) + post[:, :, None] \
        * y[:, None, :]


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


def latent_attention(h, w, cfg, mm, r, query_block):
    """h [S, E] (normed) -> [S, E]."""
    h_ = cfg["num_attention_heads"]
    nope, rot, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                     cfg["v_head_dim"])
    rkv, eps = cfg["kv_lora_rank"], cfg["rms_norm_eps"]
    scaling = cfg["rope_scaling"]
    inv_freq, amplitude = yarn_inv_freq(rot, float(cfg["rope_theta"]),
                                        scaling)
    scale = (nope + rot) ** -0.5 * yarn_get_mscale(
        scaling["factor"], scaling["mscale_all_dim"]) ** 2
    s = h.shape[0]
    pos = jnp.arange(s, dtype=F32)
    c_q = rms_norm(mm(h, w["q_a_proj"]), w["q_a_layernorm"].astype(F32), eps)

    def queries(c, at):
        q = mm(c, w["q_b_proj"]).reshape(-1, h_, nope + rot)
        return jnp.concatenate([q[..., :nope], rotary_adjacent(
            q[..., nope:], at.astype(F32), inv_freq, amplitude)], -1)

    ckv = mm(h, w["kv_a_proj_with_mqa"])
    c_kv = rms_norm(ckv[:, :rkv], w["kv_a_layernorm"].astype(F32), eps)
    k_rope = rotary_adjacent(ckv[:, None, rkv:], pos, inv_freq, amplitude)
    kv = mm(c_kv, w["kv_b_proj"]).reshape(s, h_, nope + dv)
    k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(
        k_rope, (s, h_, rot))], axis=-1)
    a = attention(c_q, queries, k, kv[..., nope:], scale, query_block, r)
    return mm(a.reshape(s, h_ * dv), w["o_proj"])


def glu(x, gate, up, down, mm):
    return mm(jax.nn.silu(mm(x, gate)) * mm(x, up), down)


def route(h, w, cfg, r):
    """(picks [S, k], their weights [S, k]) by the reference's own scores."""
    s = jax.nn.sigmoid(r(h) @ r(w["router"].astype(F32)))
    biased = s + w["e_score_correction_bias"].astype(F32)
    picks = jax.lax.top_k(biased, cfg["num_experts_per_tok"])[1]
    weights = jnp.take_along_axis(s, picks, axis=-1)        # s, not s + bias
    if cfg["norm_topk_prob"]:
        weights = weights / weights.sum(axis=-1, keepdims=True)
    return picks, weights * cfg["routed_scaling_factor"]


def feed_forward(h, w, cfg, held, mm, r):
    """routed + shared for h [S, E]; returns (out, picks [S, k])."""
    lo, hi = held
    picks, weights = route(h, w, cfg, r)

    def one(total, expert):
        j, gate, up, down = expert
        # this expert's weight for each position: 0 where it was not picked
        weight = jnp.sum(jnp.where(picks == lo + j, weights, 0.0), axis=-1)
        return total + weight[:, None] * glu(h, gate, up, down, mm), None

    ex, sh = w["experts"], w["shared_experts"]
    routed, _ = jax.lax.scan(one, jnp.zeros_like(h), (
        jnp.arange(hi - lo), ex["gate_proj"], ex["up_proj"],
        ex["down_proj"]))
    # n shared experts side by side are one GLU of width n Fm: their sum
    shared = glu(h, sh["gate_proj"], sh["up_proj"], sh["down_proj"], mm)
    return routed + shared, picks


def layer(x, w, cfg, local: int, held, query_block=None, operand_dtype=None):
    """One layer over the stream x [S, n, C] float32 -> (x, picks [S, k] or
    None for a dense layer); ``local`` is its index among the layers held
    (the first ``first_k_dense_replace`` are dense)."""
    with jax.default_matmul_precision("highest"):
        r = _rounder(operand_dtype)
        eps = cfg["rms_norm_eps"]

        def mm(x, w):
            return r(x) @ r(w.astype(F32))

        pre, post, res = hyper_coefficients(x, w["attn_hc"], cfg, mm)
        h = rms_norm(hyper_read(pre, x), w["input_layernorm"].astype(F32),
                     eps)
        x = hyper_write(res, post, x, latent_attention(
            h, w, cfg, mm, r, query_block))
        pre, post, res = hyper_coefficients(x, w["mlp_hc"], cfg, mm)
        h = rms_norm(hyper_read(pre, x),
                     w["post_attention_layernorm"].astype(F32), eps)
        if local >= cfg["first_k_dense_replace"]:
            f, picks = feed_forward(h, w, cfg, held, mm, r)
            return hyper_write(res, post, x, f), picks
        mlp = w["mlp"]
        ff = lambda hb: glu(hb, mlp["gate_proj"], mlp["up_proj"],  # noqa: E731
                            mlp["down_proj"], mm)
        s = h.shape[0]
        if query_block is None or query_block >= s:
            return hyper_write(res, post, x, ff(h)), None
        return hyper_write(res, post, x, jax.lax.map(ff, h.reshape(
            s // query_block, query_block, -1)).reshape(h.shape)), None


def embed(embed_tokens, tokens, cfg):
    """tokens [S] -> the stream's start [S, n, C]: the embedding in every
    row."""
    x = embed_tokens[tokens].astype(F32)
    return jnp.broadcast_to(x[:, None, :], (x.shape[0], cfg["hc_mult"],
                                            x.shape[1]))


def head_rows(x, norm, lm_head, cfg, start, rows: int, operand_dtype=None):
    """Logits [rows, V] of positions ``start .. start + rows - 1`` of the
    stream x [S, n, C] after the last layer (``start`` may be traced): the
    rows summed, the norm, the head."""
    with jax.default_matmul_precision("highest"):
        r = _rounder(operand_dtype)
        x = jax.lax.dynamic_slice_in_dim(x, start, rows, axis=0).sum(axis=1)
        x = rms_norm(x, norm.astype(F32), cfg["rms_norm_eps"])
        return r(x) @ r(lm_head.astype(F32))


def logits_of_rows(params, tokens, cfg, held, start, rows: int,
                   query_block=None, operand_dtype=None):
    """(logits [rows, V], picks [L_sparse, rows, k]) of positions ``start
    .. start + rows - 1`` of one sequence ``tokens`` [S], the layers one
    after another."""
    x = embed(params["embed_tokens"], tokens, cfg)
    all_picks = []
    for local, w in enumerate(params["layers"]):
        x, picks = layer(x, w, cfg, local, held, query_block, operand_dtype)
        if picks is not None:
            all_picks.append(
                jax.lax.dynamic_slice_in_dim(picks, start, rows, axis=0))
    return (head_rows(x, params["norm"], params["lm_head"], cfg, start, rows,
                      operand_dtype), jnp.stack(all_picks))
