"""Plain reference for the ``kda_mla_moe_serve`` family: the forward pass of
a ``bailing_hybrid`` decoder (inclusionAI's Ling-3.0-flash as its published
``config.json`` gives it: Kimi Delta Attention in five layers of six, latent
attention in the sixth, leading dense layers, then sigmoid-routed experts
picked by a bias-corrected, group-limited top-k beside a shared one) over one
whole sequence, prompt and served tokens together, and the logits of a run of
its positions.

Written from the published description, in ``jax.numpy`` and float32 under
``jax.default_matmul_precision("highest")``, a layer a function so that a
caller may run 33 000 positions a layer at a time: the delta rule as its
token-by-token recurrence (a ``lax.scan`` over positions: no chunks, no
cache), latent attention EXPANDED over the whole sequence, no batching, no
kernel, nothing imported from the program under test.  The weights are the
benchmark's own, drawn from the seed by ``families/kda_mla_moe_serve.py`` in
the type the model is served in (bfloat16) and cast up here.

Layer ``i`` (the PUBLISHED index: the weights hold layers ``layers_held[0]
.. layers_held[1] - 1``), with x the residual stream (a sequential pre-norm
block, RMSNorm with ``rms_norm_eps``)::

    h = RMSNorm(x)
    (i + 1) % layer_group_size == 0:  x += MLA(h)       else:  x += KDA(h)
    h = RMSNorm(x)
    i < first_k_dense_replace:  x += (silu(h W_g) * h W_u) W_d
    else:                       x += routed(h) + E_shared(h)

**KDA** (H heads, D = head_dim for keys and values, K = short_conv_kernel_size)::

    q~, k~, v~ = h W_q, h W_k, h W_v                       [H D] each
    q', k', v' = silu(y),  y_t = sum_j c_j * x_(t - (K - 1) + j)   (depthwise,
                 causal, zeros before the start; ``linear_silu``)
    q = q' / |q'| D^-1/2,  k = k' / |k'|  (a head; |.|^2 + 1e-6 under the root)
    g = kda_lower_bound * sigmoid(exp(A_log_h) (h W_f + dt_bias))   [H, D]
    b = sigmoid(h W_b)                                              [H]
    S_t = (I - b k k^T) Diag(exp(g)) S_(t-1) + b k v^T;   o_t = S_t^T q_t
    out = (RMSNorm over a head's D of o, one weight [D]) * sigmoid(h W_g)
    KDA(h) = out W_o

**MLA** (H heads, nope + rope wide queries and keys, v wide values)::

    q = h W_q -> heads x (nope | rope)           (q_lora_rank null: no down)
    q = RMSNorm over a head's nope + rope of q, weight [nope + rope]
    [c | k_R] = h W_DKV;  c = RMSNorm(c);  k_R = RMSNorm(k_R), weight [rope]
    rope parts of q and k_R rotated, pairs ADJACENT (rope_interleave),
        angle t theta^(-2i/rope)
    [k_N | v] = c W_UKV -> heads x (nope | v)
    p = softmax([q_N | q_R] [k_N | k_R]^T (nope + rope)^-1/2), causal
    o_h = (p v)_h * sigmoid(h w_gate,h)                 (head-wise gate)
    MLA(h) = o W_O

**Routed** (N experts in n_group groups, k a token)::

    s = sigmoid(h W_r);  s' = s + bias                   (float32)
    a group's score = its two largest s';  the topk_group best groups kept
    picks = the k largest s' inside the kept groups
    w_e = routed_scaling_factor * s_e / sum over picks of s      (s, not s')
    routed(h) = sum over picks that are HELD of w_e E_e(h)

**Departures from the published description**, each under ``assumed`` in
the configuration: (1) the decay's parametrisation is the ``lower_bound``
form of the family's public kernels (``kda_safe_gate``), recalled, with
``A_log`` a head and ``dt_bias`` a channel; (2) ``use_qk_norm`` is read as
the two norms above: a norm a head on the NOPE part of a key, after W_UKV,
could not be served from a cache of latents, and the latent is normed
already; (3) the bias is no trained one: drawn from the seed at
``expert_bias_scale``; (4) ``expert_swiglu_limit_list`` is 0 for every layer
held: no clamp is written; (5) the multi-token-prediction module is left
out.  ``query_block`` only bounds memory.

**The chip's share.**  ``held = (lo, hi)`` says which routed experts the
weights hold; the router, the bias, the groups and the top-k are over all
``num_experts_published``, and what an absent expert would add is left out.

Parameter layout (the reference's own; ``x @ W`` orientation)::

    {"embed_tokens": [V, E], "lm_head": [E, V], "norm": [E],
     "layers": [{"input_layernorm": [E], "post_attention_layernorm": [E],
                 "kda": {"q_proj", "k_proj", "v_proj", "f_proj", "g_proj":
                         [E, H D], "q_conv", "k_conv", "v_conv": [K, H D],
                         "dt_bias": [H D], "A_log": [H], "b_proj": [E, H],
                         "o_norm": [D], "o_proj": [H D, E]},
                 # or
                 "mla": {"q_proj": [E, H (nope + rope)], "q_norm": [nope +
                         rope], "kv_a_proj_with_mqa": [E, R + rope],
                         "kv_a_layernorm": [R], "k_rope_norm": [rope],
                         "kv_b_proj": [R, H (nope + v)], "gate_proj": [E, H],
                         "o_proj": [H v, E]},
                 "mlp": {"gate_proj": [E, F], "up_proj", "down_proj"},
                 # or
                 "router": [E, N], "expert_bias": [N],
                 "experts": {"gate_proj": [held, E, Fm], "up_proj": ...,
                             "down_proj": [held, Fm, E]},
                 "shared_experts": {"gate_proj": [E, Fs], ...}}, ...]}

``operand_dtype`` is the control's switch, never the benchmark's: with
``jnp.float8_e4m3fn`` both operands of every product are rounded to that
type first (the router's and the recurrence's too), the step below bfloat16.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32


def rms_norm(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * weight


def rotary_adjacent(x, positions, theta):
    """x [S, H, D]; the pair is (2i, 2i + 1), its angle t theta^(-2i/D)."""
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


def layer_kind(cfg: dict, local: int) -> tuple[str, bool]:
    """("kda" | "mla", whether its feed-forward is the dense one) of the
    ``local``-th layer held, from its published index."""
    i = cfg["layers_held"][0] + local
    return ("mla" if (i + 1) % cfg["layer_group_size"] == 0 else "kda",
            i < cfg["first_k_dense_replace"])


def short_conv(x, taps):
    """Depthwise causal convolution of x [S, C] with ``taps`` [K, C]: tap j
    multiplies position t - (K - 1) + j, zeros before the start."""
    k, s = taps.shape[0], x.shape[0]
    padded = jnp.concatenate([jnp.zeros((k - 1, x.shape[1]), F32), x])
    return sum(padded[j:j + s] * taps[j] for j in range(k))


def l2_norm(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def delta_rule(q, k, v, g, beta, r):
    """The recurrence, a position at a time: q, k, g [S, H, D], v [S, H, D],
    beta [S, H] -> o [S, H, D].  The state starts at 0."""
    h, d = q.shape[1:]

    def step(state, xs):
        q, k, v, g, beta = xs
        state = state * jnp.exp(g)[..., None]               # Diag(a) S
        kept = jnp.einsum("hkv,hk->hv", state, r(k))
        state = state + r(k)[..., None] * (
            beta[:, None] * (r(v) - kept))[:, None, :]
        return state, jnp.einsum("hkv,hk->hv", state, r(q))

    _, o = jax.lax.scan(step, jnp.zeros((h, d, v.shape[-1]), F32),
                        (q, k, v, g, beta))
    return o


def kda(h, w, cfg, mm, r):
    """KDA(h) for h [S, E]."""
    heads, d = cfg["num_attention_heads"], cfg["head_dim"]
    s = h.shape[0]
    by_head = lambda x: x.reshape(s, heads, d)  # noqa: E731
    q, k, v = (by_head(jax.nn.silu(short_conv(
        mm(h, w[f"{n}_proj"]), w[f"{n}_conv"].astype(F32)))) for n in "qkv")
    q, k = l2_norm(q) * d ** -0.5, l2_norm(k)
    g = cfg["kda_lower_bound"] * jax.nn.sigmoid(
        jnp.exp(w["A_log"].astype(F32))[:, None]
        * by_head(mm(h, w["f_proj"]) + w["dt_bias"].astype(F32)))
    beta = jax.nn.sigmoid(mm(h, w["b_proj"]))
    o = delta_rule(q, k, v, g, beta, r)
    o = rms_norm(o, w["o_norm"].astype(F32), cfg["rms_norm_eps"])
    o = o * jax.nn.sigmoid(by_head(mm(h, w["g_proj"])))
    return mm(o.reshape(s, heads * d), w["o_proj"])


def attention(q_of, k, v, scale, query_block, r):
    """``q_of(positions [n]) -> [n, H, Dk]`` (a block's queries are made in
    the block), k [S, H, Dk], v [S, H, Dv] -> [S, H, Dv]; causal; softmax in
    float32."""
    s, h = k.shape[:2]
    key_pos = jnp.arange(s)

    def block(qpos):
        scores = jnp.einsum("qhd,khd->hqk", r(q_of(qpos)), r(k)) * scale
        mask = key_pos[None, None, :] <= qpos[None, :, None]
        scores = jnp.where(mask, scores, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", r(jax.nn.softmax(scores, -1)),
                          r(v))

    if query_block is None or query_block >= s:
        return block(key_pos)
    out = jax.lax.map(block, key_pos.reshape(s // query_block, query_block))
    return out.reshape(s, h, v.shape[-1])


def mla(h, w, cfg, mm, r, query_block):
    """MLA(h) for h [S, E], expanded."""
    heads = cfg["num_attention_heads"]
    nope, rot, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                     cfg["v_head_dim"])
    rank, eps, theta = (cfg["kv_lora_rank"], cfg["rms_norm_eps"],
                        float(cfg["rope_theta"]))
    s = h.shape[0]
    pos = jnp.arange(s, dtype=F32)

    def q_of(at):
        q = mm(h[at], w["q_proj"]).reshape(-1, heads, nope + rot)
        q = rms_norm(q, w["q_norm"].astype(F32), eps)
        return jnp.concatenate([q[..., :nope], rotary_adjacent(
            q[..., nope:], at.astype(F32), theta)], -1)

    ckv = mm(h, w["kv_a_proj_with_mqa"])
    c = rms_norm(ckv[:, :rank], w["kv_a_layernorm"].astype(F32), eps)
    k_rope = rotary_adjacent(rms_norm(
        ckv[:, None, rank:], w["k_rope_norm"].astype(F32), eps), pos, theta)
    kv = mm(c, w["kv_b_proj"]).reshape(s, heads, nope + dv)
    k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(
        k_rope, (s, heads, rot))], axis=-1)
    o = attention(q_of, k, kv[..., nope:], (nope + rot) ** -0.5,
                  query_block, r)
    o = o * jax.nn.sigmoid(mm(h, w["gate_proj"]))[..., None]
    return mm(o.reshape(s, heads * dv), w["o_proj"])


def glu(x, gate, up, down, mm):
    return mm(jax.nn.silu(mm(x, gate)) * mm(x, up), down)


def route(h, w, cfg, r):
    """(picks [S, k], their weights [S, k]) by the reference's own scores."""
    n, k = cfg["num_experts_published"], cfg["num_experts_per_tok"]
    groups, kept = cfg["n_group"], cfg["topk_group"]
    s = jax.nn.sigmoid(r(h) @ r(w["router"].astype(F32)))
    biased = s + w["expert_bias"].astype(F32)
    by_group = biased.reshape(-1, groups, n // groups)
    group_score = jax.lax.top_k(by_group, 2)[0].sum(-1)     # [S, groups]
    best = jax.lax.top_k(group_score, kept)[1]
    in_best = (best[..., None] == jnp.arange(groups)).any(-2)
    inside = jnp.where(in_best[..., None], by_group, -jnp.inf).reshape(
        biased.shape)
    picks = jax.lax.top_k(inside, k)[1]
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
    shared = glu(h, sh["gate_proj"], sh["up_proj"], sh["down_proj"], mm)
    return routed + shared, picks


def layer(x, w, cfg, local: int, held, query_block=None, operand_dtype=None):
    """One layer over the stream x [S, E] float32 -> (x, picks [S, k] or
    None); ``local`` is its index among the layers held."""
    with jax.default_matmul_precision("highest"):
        r = _rounder(operand_dtype)
        eps = cfg["rms_norm_eps"]

        def mm(x, w):
            return r(x) @ r(w.astype(F32))

        kind, dense = layer_kind(cfg, local)
        h = rms_norm(x, w["input_layernorm"].astype(F32), eps)
        x = x + (mla(h, w["mla"], cfg, mm, r, query_block) if kind == "mla"
                 else kda(h, w["kda"], cfg, mm, r))
        h = rms_norm(x, w["post_attention_layernorm"].astype(F32), eps)
        if not dense:
            f, picks = feed_forward(h, w, cfg, held, mm, r)
            return x + f, picks
        mlp = w["mlp"]
        ff = lambda hb: glu(hb, mlp["gate_proj"], mlp["up_proj"],  # noqa: E731
                            mlp["down_proj"], mm)
        s = h.shape[0]
        if query_block is None or query_block >= s:
            return x + ff(h), None
        return x + jax.lax.map(ff, h.reshape(
            s // query_block, query_block, -1)).reshape(h.shape), None


def embed(embed_tokens, tokens):
    return embed_tokens[tokens].astype(F32)


def head_rows(x, norm, lm_head, cfg, start, rows: int, operand_dtype=None):
    """Logits [rows, V] of positions ``start .. start + rows - 1`` of the
    stream x [S, E] after the last layer (``start`` may be traced)."""
    with jax.default_matmul_precision("highest"):
        r = _rounder(operand_dtype)
        x = jax.lax.dynamic_slice_in_dim(x, start, rows, axis=0)
        x = rms_norm(x, norm.astype(F32), cfg["rms_norm_eps"])
        return r(x) @ r(lm_head.astype(F32))


def logits_of_rows(params, tokens, cfg, held, start, rows: int,
                   query_block=None, operand_dtype=None):
    """(logits [rows, V], picks [L_sparse, rows, k]) of positions ``start
    .. start + rows - 1`` of one sequence ``tokens`` [S], the layers one
    after another.  The logits are over the rows of the head the weights
    hold (a slice of the vocabulary is a smaller vocabulary)."""
    x = embed(params["embed_tokens"], tokens)
    all_picks = []
    for local, w in enumerate(params["layers"]):
        x, picks = layer(x, w, cfg, local, held, query_block, operand_dtype)
        if picks is not None:
            all_picks.append(
                jax.lax.dynamic_slice_in_dim(picks, start, rows, axis=0))
    return (head_rows(x, params["norm"], params["lm_head"], cfg, start, rows,
                      operand_dtype), jnp.stack(all_picks))
