"""Plain reference for the ``eva_serve`` family: the forward pass of an
``evabyte`` decoder (EvaByte as its published ``config.json`` gives it: a
byte-level model, EVA attention in every layer, SwiGLU, RMSNorm with a unit
offset, a head of ``num_pred_heads`` x ``vocab_size`` outputs) over one whole
sequence, prompt and served bytes together, and the logits of a run of its
positions.

Written from the description below, in ``jax.numpy`` and float32 under
``jax.default_matmul_precision("highest")``, the layers written out one
after another: no cache, no ring, no kernel, no batching, nothing imported
from the program under test.  The weights are the benchmark's own, drawn
from the seed by ``families/eva_serve.py`` in the type the model is served
in (bfloat16) and raised here, a matrix at a time.

A layer, with x the residual stream (float32; a sequential pre-norm block),
d = head size, s = d^-1/2, c = ``chunk_size``, W = ``window_size``::

    norm(x) = x / sqrt(mean(x^2) + eps) * (1 + g)        (norm_add_unit_offset)
    n = norm(x)
    q_t = rope(n_t W_q), k_t = rope(n_t W_k), v_t = n_t W_v    heads x d
        rotate-half pairs (i, i + d/2), angle t * theta^(-2i/d)
    chunk j = positions cj .. cj + c - 1, from its own rows alone:
        a_{j,m} = softmax over m in chunk j of (s phi_h . k_m)
        kbar_j = sum_m a_{j,m} k_m + mu_h;  vbar_j = sum_m a_{j,m} v_m
    query t, w(t) = floor(t / W):
        E_t = {m : W w(t) <= m <= t}                 exact keys
        R_t = {j : j < (W / c) w(t)}                 summaries of the windows
                                                     wholly behind t
        p = softmax over E_t and R_t together of (s q_t . k_m | s q_t . kbar_j)
        o_t = sum_E p_m v_m + sum_R p_j vbar_j
    x += [o_t over heads] W_o
    n = norm(x);  x += (silu(n W_gate) * n W_up) W_down

and after the last layer the norm and the bias-free head: logits [S, P V],
head i (columns i V .. i V + V - 1) predicts the byte at t + 1 + i.

The attention is ONE mask over [S, S + S / c] built from the definitions of
E_t and R_t, a block of queries at a time so that it fits (``query_block``
only bounds memory: a block still sees every key and every summary its mask
admits; the feed-forward runs a block of positions at a time).

Parameter layout (the reference's own; ``x @ W`` orientation)::

    {"embed_tokens": [V, E], "lm_head": [E, P V], "norm": [E],
     "layers": [{"input_layernorm": [E], "post_attention_layernorm": [E],
                 "q_proj": [E, H d], "k_proj": [E, H d], "v_proj": [E, H d],
                 "o_proj": [H d, E], "adaptive_phi": [H, d],
                 "adaptive_mu_k": [H, d], "gate_proj": [E, F],
                 "up_proj": [E, F], "down_proj": [F, E]}, ...]}

``assumed`` (the configuration file says the same): the summary's weights
and k-bar as above, and a summary seen only once its whole window is behind
the query; recalled from the family's published modeling code and EVA's
equations (Zheng et al., "Efficient Attention via Control Variates").

``operand_dtype`` is the control's switch, never the benchmark's: with
``jnp.float8_e4m3fn`` both operands of every product are rounded to that
type first, the step below bfloat16.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32


def rms_norm(x, offset_weight, eps):
    """x / rms(x) * (1 + g): the unit offset is part of the model."""
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * (1.0 + offset_weight)


def rotate_half(x, positions, theta):
    """x [S, H, D]; pair i is (x[i], x[i + D/2]): x cos + turn(x) sin with
    turn(x) = (-x[D/2:], x[:D/2]) and the angles laid out twice over D."""
    d = x.shape[-1]
    freq = theta ** (-jnp.arange(0, d, 2, dtype=F32) / d)
    ang = positions[:, None] * jnp.concatenate([freq, freq])[None, :]
    turned = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], axis=-1)
    return x * jnp.cos(ang)[:, None, :] + turned * jnp.sin(ang)[:, None, :]


def _rounder(operand_dtype):
    if operand_dtype is None:
        return lambda x: x
    return lambda x: x.astype(operand_dtype).astype(F32)


def chunk_summaries(k, v, phi, mu, chunk, scale, r):
    """k, v [S, H, D] (S whole chunks) -> kbar, vbar [S / chunk, H, D]."""
    s, h, d = k.shape
    kc, vc = (x.reshape(s // chunk, chunk, h, d) for x in (k, v))
    a = jax.nn.softmax(jnp.einsum("jmhd,hd->jmh", r(kc), r(phi)) * scale,
                       axis=1)
    return (jnp.einsum("jmh,jmhd->jhd", r(a), r(kc)) + mu,
            jnp.einsum("jmh,jmhd->jhd", r(a), r(vc)))


def seen(query_pos, s: int, window: int, chunk: int):
    """The mask [n, S + S / chunk] of the queries at ``query_pos`` [n], from
    the definitions: exact keys E_t, then summaries R_t."""
    t = query_pos[:, None]
    m = jnp.arange(s)[None, :]
    j = jnp.arange(s // chunk)[None, :]
    exact = (m >= window * (t // window)) & (m <= t)
    summarised = j < (window // chunk) * (t // window)
    return jnp.concatenate([exact, summarised], axis=1)


def attention(q, k, v, kbar, vbar, window, chunk, scale, query_block, r):
    """q, k, v [S, H, D], kbar, vbar [S / chunk, H, D] -> [S, H, D]."""
    s = q.shape[0]
    keys = jnp.concatenate([k, kbar], axis=0)
    values = jnp.concatenate([v, vbar], axis=0)

    def block(args):
        qb, qpos = args
        scores = jnp.einsum("qhd,khd->hqk", r(qb), r(keys)) * scale
        scores = jnp.where(seen(qpos, s, window, chunk)[None], scores,
                           -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", r(jax.nn.softmax(scores, -1)),
                          r(values))

    pos = jnp.arange(s)
    if query_block is None or query_block >= s:
        return block((q, pos))
    n = s // query_block
    out = jax.lax.map(block, (q.reshape(n, query_block, *q.shape[1:]),
                              pos.reshape(n, query_block)))
    return out.reshape(q.shape)


def layer(x, w, cfg, query_block=None, operand_dtype=None):
    """One layer on the residual stream x [S, E] (S whole chunks), ``w`` its
    weights in the module's layout."""
    h = cfg["num_attention_heads"]
    d = cfg["hidden_size"] // h
    window, chunk = cfg["window_size"], cfg["chunk_size"]
    eps, theta = cfg["rms_norm_eps"], float(cfg["rope_theta"])
    scale = d ** -0.5
    s = x.shape[0]
    pos = jnp.arange(s, dtype=F32)
    r = _rounder(operand_dtype)

    def mm(x, w):
        return r(x) @ r(w.astype(F32))

    def blocks(fn, x):          # fn over [S, ...] a block of positions a time
        if query_block is None or query_block >= s:
            return fn(x)
        return jax.lax.map(fn, x.reshape(s // query_block, query_block, -1)
                           ).reshape(s, -1)

    with jax.default_matmul_precision("highest"):
        n = rms_norm(x, w["input_layernorm"].astype(F32), eps)
        heads = lambda name: blocks(  # noqa: E731
            lambda nb: mm(nb, w[name]), n).reshape(s, h, d)
        q = rotate_half(heads("q_proj"), pos, theta)
        k = rotate_half(heads("k_proj"), pos, theta)
        v = heads("v_proj")
        kbar, vbar = chunk_summaries(
            k, v, w["adaptive_phi"].astype(F32),
            w["adaptive_mu_k"].astype(F32), chunk, scale, r)
        a = attention(q, k, v, kbar, vbar, window, chunk, scale, query_block,
                      r)
        x = x + blocks(lambda ab: mm(ab, w["o_proj"]), a.reshape(s, h * d))
        n = rms_norm(x, w["post_attention_layernorm"].astype(F32), eps)
        return x + blocks(lambda nb: mm(
            jax.nn.silu(mm(nb, w["gate_proj"])) * mm(nb, w["up_proj"]),
            w["down_proj"]), n)


def embed(params, tokens):
    """tokens [S] -> the residual stream's start [S, E] float32."""
    return params["embed_tokens"][tokens].astype(F32)


def head_rows(x, norm, lm_head, cfg, start, rows: int, operand_dtype=None):
    """The final norm and the head on rows ``start .. start + rows - 1`` of
    the last layer's x [S, E]: logits [rows, P V]."""
    with jax.default_matmul_precision("highest"):
        x = jax.lax.dynamic_slice_in_dim(x, start, rows, axis=0)
        x = rms_norm(x, norm.astype(F32), cfg["rms_norm_eps"])
        r = _rounder(operand_dtype)
        return r(x) @ r(lm_head.astype(F32))


def logits_of_rows(params, tokens, cfg, start, rows: int, query_block=None,
                   operand_dtype=None):
    """Logits [rows, P V] (every prediction head) of positions ``start ..
    start + rows - 1`` of one sequence ``tokens`` [S] (S whole chunks).
    ``start`` may be traced; ``rows`` is a shape.  :func:`embed`, then
    :func:`layer` once a layer, then :func:`head_rows`: a caller whose
    sequence is too long for one program to hold (32768 positions on one
    chip) calls the three itself, a layer's program at a time."""
    x = embed(params, tokens)
    for w in params["layers"]:
        x = layer(x, w, cfg, query_block, operand_dtype)
    return head_rows(x, params["norm"], params["lm_head"], cfg, start, rows,
                     operand_dtype)
