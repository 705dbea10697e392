"""Plain reference for the ``cca_moe_serve`` family: the forward pass of a
``zaya`` decoder (Zyphra's ZAYA1-8B as its published ``config.json`` gives
it: compressed convolutional attention, CCA, in every layer, then a top-1
sparse feed-forward whose router is an MLP with a state carried down the
layers, the residual merges scaled, the head tied to the embedding) over one
whole sequence, prompt and served tokens together, and the logits of a run
of its positions.

Written from the published description (CCA: Figliolia et al.,
arXiv:2510.04476; the model: Zyphra's ZAYA1 report, arXiv:2511.17127), in
``jax.numpy`` and float32 under ``jax.default_matmul_precision("highest")``,
a layer a function so that a caller may run 10 000 positions a layer at a
time: no cache, no row blocks, no kernel, no batching, nothing imported from
the program under test.  The convolutions are shifted sums, the router is as
written below, and each token goes through its own pick's expert.  The
weights are the benchmark's own, drawn from the seed by
``families/cca_moe_serve.py`` in the type the model is served in (bfloat16)
and cast up here.

A layer, with x the residual stream and RMSNorm at ``rms_norm_eps``::

    h = RMSNorm(x);   x = merge_a(x, CCA(h))
    h = RMSNorm(x);   x = merge_m(x, MoE(h))
    merge(x, f) = (x + b_r) * s_r + (f + b_f) * s_f     four vectors each

**CCA** (H query and KV key heads of D; G = H / KV; C = (H + KV) D)::

    q~ = h W_q [H D],  k~ = h W_k [KV D];   u = [q~ ; k~]
    c1_t = sum_j a_j * u_(t - (K0 - 1) + j) + bias          depthwise, K0 taps
    c2_t = sum_j c1_(t - (K1 - 1) + j)[g] A_(g, j) + bias   a head g's own D
                                          channels mixed, K1 taps
           (zeros before position 0 for both)
    [q^ ; k^] = c2
    q_j = q^_j + (q~_j + k~_(j // G)) / 2
    k_i = k^_i + (mean_j in i's group of q~_j + k~_i) / 2
    v_t = a KV head i: [ (h_t W_v1)[i] ; (h_(t-1) W_v2)[i] ]   each D / 2,
          h_(-1) = 0
    q, k <- x * rsqrt(mean(x^2) + eps) a head (length sqrt(D)); k times a
            scalar a KV head
    rotary on the first partial_rotary_factor * D channels of q and k,
        pairs (i, i + rot / 2), angle t theta^(-2i/rot)
    o = softmax(q k^T D^-1/2, causal) v, query head j on key head j // G
    CCA(h) = o W_o

**The router and the experts** (N experts, one a token)::

    r_l = h W_d + b_d  (+ gamma_l * r_(l-1) from the second layer on)
    z = W_3 gelu(W_2 gelu(W_1 RMSNorm(r_l) + b_1) + b_2)     (exact gelu)
    p = softmax(z);  pick = argmax(p + bias);  gate = p[pick]
    MoE(h) = gate * (silu(h W_g^pick) * h W_u^pick) W_d^pick
    r_l is handed to the next layer as it is.

What the published ``config.json`` does not settle is listed under
``assumed`` in the configuration file (the halves' layout of the value
shift, the key scale's form, exact GELU, the depth averaging's presence, the
residual merge's vectors), and ``zaya_use_mod`` (a router output that lets a
token skip its expert) under ``departures``: it is not written here.

Parameter layout (the reference's own; ``x @ W`` orientation)::

    {"embed_tokens": [V, E], "norm": [E],
     "layers": [{"input_layernorm": [E], "post_attention_layernorm": [E],
                 "cca": {"q_proj": [E, H D], "k_proj": [E, KV D],
                         "v_now_proj", "v_prev_proj": [E, KV D / 2],
                         "conv0": [K0, C], "conv0_bias": [C],
                         "conv1": [H + KV, K1, D, D], "conv1_bias": [C],
                         "k_scale": [KV], "o_proj": [H D, E]},
                 "router": {"down": [E, R], "down_bias": [R],
                            "decay": [R] (not in the first layer),
                            "norm": [R], "w1": [R, R], "b1": [R],
                            "w2": [R, R], "b2": [R], "w3": [R, N]},
                 "expert_bias": [N],
                 "experts": {"gate_proj": [N, E, F], "up_proj": [N, E, F],
                             "down_proj": [N, F, E]},
                 "merge": {"attn": {"residual_bias", "residual_scale",
                                    "branch_bias", "branch_scale": [E]},
                           "mlp": {...}}}, ...]}

``operand_dtype`` is the control's switch, never the benchmark's: with
``jnp.float8_e4m3fn`` both operands of every product are rounded to that
type first (the convolutions', the router's and the attention's too), the
step below bfloat16.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32


def rms_norm(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * weight


def _rounder(operand_dtype):
    if operand_dtype is None:
        return lambda x: x
    return lambda x: x.astype(operand_dtype).astype(F32)


def shifted(x, by: int):
    """x [S, ...] moved ``by`` positions later, zeros before the start."""
    if by == 0:
        return x
    return jnp.concatenate([jnp.zeros((by,) + x.shape[1:], x.dtype),
                            x[:x.shape[0] - by]])


def rotary_part(x, positions, theta: float, rot: int):
    """x [S, H, D]: the first ``rot`` channels rotated, pairs (i, i + rot /
    2), the rest as they are."""
    inv_freq = 1.0 / theta ** (jnp.arange(0, rot, 2, dtype=F32) / rot)
    ang = positions[:, None] * inv_freq[None, :]            # [S, rot/2]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., :rot // 2], x[..., rot // 2:rot]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos,
                            x[..., rot:]], axis=-1)


def attention(q_of, k, v, scale, query_block, r):
    """``q_of(positions [n]) -> [n, H, D]``, k, v [S, KV, D] -> [S, H, D];
    causal; query head j reads key head j // (H / KV); softmax in float32."""
    s, kv, d = k.shape
    key_pos = jnp.arange(s)

    def block(qpos):
        q = q_of(qpos)
        grouped = q.reshape(q.shape[0], kv, -1, d)
        scores = jnp.einsum("qhgd,khd->hgqk", r(grouped), r(k)) * scale
        mask = key_pos[None, None, None, :] <= qpos[None, None, :, None]
        scores = jnp.where(mask, scores, -jnp.inf)
        return jnp.einsum("hgqk,khd->qhgd", r(jax.nn.softmax(scores, -1)),
                          r(v)).reshape(q.shape)

    if query_block is None or query_block >= s:
        return block(key_pos)
    out = jax.lax.map(block, key_pos.reshape(s // query_block, query_block))
    return out.reshape(s, -1, d)


def cca(h, w, cfg, mm, r, query_block):
    """CCA(h) for h [S, E]."""
    heads, kv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                    cfg["head_dim"])
    k0, k1 = cfg["cca_time0"], cfg["cca_time1"]
    group, s = heads // kv, h.shape[0]
    eps = cfg["rms_norm_eps"]
    rope = cfg["rope_parameters"]["hybrid"]
    rot = int(d * rope["partial_rotary_factor"])
    q_plain, k_plain = mm(h, w["q_proj"]), mm(h, w["k_proj"])
    u = jnp.concatenate([q_plain, k_plain], axis=-1)        # [S, C]
    taps0 = w["conv0"].astype(F32)
    c1 = sum(r(shifted(u, k0 - 1 - j)) * r(taps0[j]) for j in range(k0)) \
        + w["conv0_bias"].astype(F32)
    by_head = c1.reshape(s, heads + kv, d)
    taps1 = w["conv1"].astype(F32)                          # [G, K1, D, D]
    c2 = sum(jnp.einsum("sgd,gde->sge", r(shifted(by_head, k1 - 1 - j)),
                        r(taps1[:, j])) for j in range(k1)) \
        + w["conv1_bias"].astype(F32).reshape(heads + kv, d)
    q_plain = q_plain.reshape(s, kv, group, d)
    k_plain = k_plain.reshape(s, kv, d)
    q = c2[:, :heads].reshape(s, kv, group, d) \
        + (q_plain + k_plain[:, :, None]) / 2
    k = c2[:, heads:] + (q_plain.mean(axis=2) + k_plain) / 2
    v = jnp.concatenate(
        [mm(h, w["v_now_proj"]).reshape(s, kv, d // 2),
         shifted(mm(h, w["v_prev_proj"]), 1).reshape(s, kv, d // 2)], axis=-1)
    to_length = lambda x: x * jax.lax.rsqrt(  # noqa: E731
        jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    pos = jnp.arange(s, dtype=F32)
    theta = float(rope["rope_theta"])
    q = rotary_part(to_length(q.reshape(s, heads, d)), pos, theta, rot)
    k = rotary_part(to_length(k) * w["k_scale"].astype(F32)[:, None], pos,
                    theta, rot)
    o = attention(lambda at: q[at], k, v, d ** -0.5, query_block, r)
    return mm(o.reshape(s, heads * d), w["o_proj"])


def route(h, state, w, bias, cfg, r):
    """(pick [S], its gate [S], the state handed on [S, R]) by the
    reference's own scores; ``state`` is the layer before's, or None."""
    f = lambda name: w[name].astype(F32)  # noqa: E731
    hand = r(h) @ r(f("down")) + f("down_bias")
    if state is not None:
        hand = hand + f("decay") * state
    y = rms_norm(hand, f("norm"), cfg["rms_norm_eps"])
    y = jax.nn.gelu(r(y) @ r(f("w1")) + f("b1"), approximate=False)
    y = jax.nn.gelu(r(y) @ r(f("w2")) + f("b2"), approximate=False)
    p = jax.nn.softmax(r(y) @ r(f("w3")), axis=-1)
    pick = jnp.argmax(p + bias.astype(F32), axis=-1)
    return pick, jnp.take_along_axis(p, pick[:, None], axis=-1)[:, 0], hand


def feed_forward(h, state, w, cfg, mm, r):
    """(MoE(h), picks [S], the router's state) for h [S, E]: an expert at a
    time over the positions that picked it (the others weigh 0)."""
    pick, gate, hand = route(h, state, w["router"], w["expert_bias"], cfg, r)

    def one(total, expert):
        j, w_gate, w_up, w_down = expert
        weight = jnp.where(pick == j, gate, 0.0)
        out = mm(jax.nn.silu(mm(h, w_gate)) * mm(h, w_up), w_down)
        return total + weight[:, None] * out, None

    ex = w["experts"]
    routed, _ = jax.lax.scan(one, jnp.zeros_like(h), (
        jnp.arange(cfg["num_experts"]), ex["gate_proj"], ex["up_proj"],
        ex["down_proj"]))
    return routed, pick, hand


def merge(x, f, w):
    g = lambda name: w[name].astype(F32)  # noqa: E731
    return (x + g("residual_bias")) * g("residual_scale") \
        + (f + g("branch_bias")) * g("branch_scale")


def layer(x, state, w, cfg, query_block=None, operand_dtype=None):
    """One layer over the stream x [S, E] float32 and the router's state
    [S, R] (None: the first layer) -> (x, state, picks [S])."""
    with jax.default_matmul_precision("highest"):
        r = _rounder(operand_dtype)
        eps = cfg["rms_norm_eps"]

        def mm(x, w):
            return r(x) @ r(w.astype(F32))

        h = rms_norm(x, w["input_layernorm"].astype(F32), eps)
        x = merge(x, cca(h, w["cca"], cfg, mm, r, query_block),
                  w["merge"]["attn"])
        h = rms_norm(x, w["post_attention_layernorm"].astype(F32), eps)
        f, picks, state = feed_forward(h, state, w, cfg, mm, r)
        return merge(x, f, w["merge"]["mlp"]), state, picks


def embed(embed_tokens, tokens):
    return embed_tokens[tokens].astype(F32)


def head_rows(x, norm, embed_tokens, cfg, start, rows: int,
              operand_dtype=None):
    """Logits [rows, V] of positions ``start .. start + rows - 1`` of the
    stream x [S, E] after the last layer (``start`` may be traced); the head
    is the embedding transposed."""
    with jax.default_matmul_precision("highest"):
        r = _rounder(operand_dtype)
        x = jax.lax.dynamic_slice_in_dim(x, start, rows, axis=0)
        x = rms_norm(x, norm.astype(F32), cfg["rms_norm_eps"])
        return r(x) @ r(embed_tokens.astype(F32)).T


def logits_of_rows(params, tokens, cfg, start, rows: int, query_block=None,
                   operand_dtype=None):
    """(logits [rows, V], picks [L, rows]) of positions ``start .. start +
    rows - 1`` of one sequence ``tokens`` [S], the layers one after
    another."""
    x, state = embed(params["embed_tokens"], tokens), None
    all_picks = []
    for w in params["layers"]:
        x, state, picks = layer(x, state, w, cfg, query_block, operand_dtype)
        all_picks.append(
            jax.lax.dynamic_slice_in_dim(picks, start, rows, axis=0))
    return (head_rows(x, params["norm"], params["embed_tokens"], cfg, start,
                      rows, operand_dtype), jnp.stack(all_picks))
