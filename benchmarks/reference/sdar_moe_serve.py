"""Plain reference for the ``sdar_moe_serve`` family: an ``sdar_moe`` decoder
(JetLM's SDAR-30B-A3B-Chat as its published ``config.json`` and the
catalog's ``described_as`` give it: Qwen3-MoE's layer under a BLOCK-CAUSAL
mask, generating by diffusion over blocks) in two functions:
:func:`sequence`, the forward pass over one whole sequence of final tokens,
and :func:`block_logits`, the logits of one block STATE (final tokens and
``[MASK]`` in the rest) given everything before it.

Written from the published description, in ``jax.numpy`` and float32 under
``jax.default_matmul_precision("highest")``, the layers written out one
after another: no cache object, no batching of requests, no kernel, nothing
imported from the program under test.  The weights are the benchmark's own,
drawn from the seed by ``families/sdar_moe_serve.py`` in the type the model
is served in (bfloat16) and cast up here, a matrix at a time.

A layer, with x the residual stream (pre-norm, RMSNorm eps 1e-6, no bias)::

    h = RMSNorm(x)
    q = h Wq  (32 heads of 128)   k = h Wk, v = h Wv  (4 heads of 128)
    q, k <- RMSNorm over each head's 128 channels, ONE learned vector of
            128 for q and one for k shared by the heads (``assumed``:
            Qwen3-MoE's q_norm / k_norm; the config has no key for it),
            then rotate-half RoPE, theta 1e6, on the whole head
    a = softmax(q k^T / sqrt 128) v, query head n reads KV head n // 8,
        under the BLOCK-CAUSAL mask: position i sees position j iff
        j < (i // B + 1) * B  (every earlier block and the whole of its own)
    x <- x + a Wo
    h = RMSNorm(x)
    p = softmax(h Wr) over 128 experts in float32; picks = top-8 of p;
        g = p[picks] / sum p[picks]            (``norm_topk_prob`` true)
    x <- x + sum over picks of g_e (silu(h W_gate_e) * h W_up_e) W_down_e

and after the last layer RMSNorm and the untied head.  The logits at
position i predict position i's OWN token (no shift; ``assumed``).

**Generation**, as the family judges it.  A block state is B token ids, some
``[MASK]``; the positions before the block are final.  A denoising pass
reads, at each masked position, the best token with the mask's own id left
out and its softmax probability over the whole vocabulary, the confidence
(:func:`confidences`).  Every block before the one denoised is final, and a
block sees earlier blocks only, so the keys and values the earlier
positions show a block state are those of :func:`sequence` over the final
sequence: the family computes them once a request and hands each state the
part before its block, as arrays.

Parameter layout (the reference's own; ``x @ W`` orientation)::

    {"embed_tokens": [V, E],
     "layers": [{"input_layernorm": [E], "post_attention_layernorm": [E],
                 "q_proj": [E, H*D], "k_proj": [E, KV*D], "v_proj": [E, KV*D],
                 "o_proj": [H*D, E], "q_norm": [D], "k_norm": [D],
                 "router": [E, N],
                 "experts": {"gate_proj": [N, E, F], "up_proj": [N, E, F],
                             "down_proj": [N, F, E]}}, ...],
     "norm": [E], "lm_head": [E, V]}

Departures from the published description (the configuration's
``departures`` has them too).  (1) The mask's own id is left out of the
picks (the description samples over the whole vocabulary; on trained weights
a mask is never the best token, on drawn ones it is once in 151 936).
(2) ``query_block`` only bounds memory: the mask is an explicit array a
block of query rows at a time, and a query still sees every key the mask
lets it see.  (3) The experts are visited one after another over all
positions, a position that did not pick an expert weighing its output 0:
the sum the description gives, not its dispatch.

``operand_dtype`` is the control's switch, never the benchmark's: with
``jnp.float8_e4m3fn`` both operands of every product are rounded to that
type first (the router's too), the step below bfloat16.
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


def rotary_half(x, positions, theta):
    """x [..., S, H, D], positions [..., S]; the pair is (i, i + D/2), angle
    position * theta^(-2i / D)."""
    d = x.shape[-1]
    inv_freq = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=F32) / d)
    ang = positions[..., None].astype(F32) * inv_freq           # [.., S, D/2]
    cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def block_mask(q_pos, k_pos, block: int):
    """[Q, K] bool: query position i sees key position j iff
    j < (i // block + 1) * block."""
    return k_pos[None, :] < ((q_pos // block + 1) * block)[:, None]


def heads(h, w, cfg, positions, mm):
    """q [.., S, H, D], k and v [.., S, KV, D] of the normed stream h
    [.., S, E] at ``positions`` [.., S]: projected, q and k normed a head,
    then rotated."""
    n, kv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                cfg["head_dim"])
    eps, theta = cfg["rms_norm_eps"], float(cfg["rope_theta"])
    lead = h.shape[:-1]
    q = mm(h, w["q_proj"]).reshape(*lead, n, d)
    k = mm(h, w["k_proj"]).reshape(*lead, kv, d)
    v = mm(h, w["v_proj"]).reshape(*lead, kv, d)
    q = rotary_half(rms_norm(q, w["q_norm"].astype(F32), eps), positions,
                    theta)
    k = rotary_half(rms_norm(k, w["k_norm"].astype(F32), eps), positions,
                    theta)
    return q, k, v


def scores(q, k, r):
    """q [Q, H, D], k [K, KV, D] -> [KV, H / KV, Q, K]: query head n reads
    KV head n // (H / KV)."""
    n, d = q.shape[-2:]
    kv = k.shape[-2]
    grouped = q.reshape(q.shape[0], kv, n // kv, d)
    return jnp.einsum("qkgd,skd->kgqs", r(grouped), r(k)) / jnp.sqrt(F32(d))


def mixed(p, v, r):
    """p [KV, G, Q, K] probabilities, v [K, KV, D] -> [Q, H, D]."""
    out = jnp.einsum("kgqs,skd->qkgd", r(p), r(v))
    return out.reshape(out.shape[0], -1, out.shape[-1])


def attend(q, k, v, mask, r):
    """q [Q, H, D], k / v [K, KV, D], mask [Q, K] -> [Q, H, D]; softmax in
    float32."""
    s = jnp.where(mask[None, None], scores(q, k, r), -jnp.inf)
    return mixed(jax.nn.softmax(s, -1), v, r)


def route(h, router, cfg, r):
    """(picks [T, k], their weights [T, k]) by the reference's own scores:
    softmax over all experts in float32, the top k, divided by their sum."""
    p = jax.nn.softmax(r(h) @ r(router.astype(F32)), axis=-1)
    weights, picks = jax.lax.top_k(p, cfg["num_experts_per_tok"])
    if cfg["norm_topk_prob"]:
        weights = weights / weights.sum(axis=-1, keepdims=True)
    return picks, weights


def moe(h, w, cfg, mm, r):
    """MoE(h) for h [T, E]: an expert at a time over every position, a
    position that did not pick it weighing its output 0."""
    picks, weights = route(h, w["router"], cfg, r)

    def one(total, expert):
        j, w_gate, w_up, w_down = expert
        weight = jnp.where(picks == j, weights, 0.0).sum(axis=-1)
        out = mm(jax.nn.silu(mm(h, w_gate)) * mm(h, w_up), w_down)
        return total + weight[:, None] * out, None

    ex = w["experts"]
    routed, _ = jax.lax.scan(one, jnp.zeros_like(h), (
        jnp.arange(cfg["num_experts"]), ex["gate_proj"], ex["up_proj"],
        ex["down_proj"]))
    return routed


def _products(operand_dtype):
    r = _rounder(operand_dtype)
    return r, lambda x, w: r(x) @ r(w.astype(F32))


def sequence(params, tokens, cfg, block: int, query_block=None,
             operand_dtype=None):
    """The forward pass over one sequence ``tokens`` [S] under the
    block-causal mask: (the stream after the last layer [S, E], the layers'
    keys [L, S, KV, D] and values, as each layer's attention read them)."""
    with jax.default_matmul_precision("highest"):
        r, mm = _products(operand_dtype)
        eps = cfg["rms_norm_eps"]
        s = tokens.shape[0]
        pos = jnp.arange(s)
        x = params["embed_tokens"][tokens].astype(F32)
        keys, values = [], []
        for w in params["layers"]:
            h = rms_norm(x, w["input_layernorm"].astype(F32), eps)
            q, k, v = heads(h, w, cfg, pos, mm)
            if query_block is None or query_block >= s:
                a = attend(q, k, v, block_mask(pos, pos, block), r)
            else:
                # the explicit mask in row blocks, so that it fits
                rows = lambda x: x.reshape(  # noqa: E731
                    s // query_block, query_block, *x.shape[1:])
                a = jax.lax.map(
                    lambda qp: attend(qp[0], k, v,
                                      block_mask(qp[1], pos, block), r),
                    (rows(q), rows(pos))).reshape(q.shape)
            x = x + mm(a.reshape(s, -1), w["o_proj"])
            h = rms_norm(x, w["post_attention_layernorm"].astype(F32), eps)
            x = x + moe(h, w, cfg, mm, r)
            keys.append(k)
            values.append(v)
        return x, jnp.stack(keys), jnp.stack(values)


def head(x, params, cfg, operand_dtype=None):
    """Logits [.., V] of the stream x [.., E] after the last layer."""
    with jax.default_matmul_precision("highest"):
        r, mm = _products(operand_dtype)
        return mm(rms_norm(x, params["norm"].astype(F32),
                           cfg["rms_norm_eps"]), params["lm_head"])


def block_logits(params, keys, values, starts, states, cfg,
                 operand_dtype=None):
    """The logits [N, B, V] of N block states ``states`` [N, B] (token ids,
    ``[MASK]`` where a position is not final), state n at positions
    ``starts[n] .. starts[n] + B - 1``, given everything before it: ``keys``
    / ``values`` [L, S, KV, D] are :func:`sequence`'s over the final
    sequence, of which state n sees the positions below ``starts[n]`` (the
    earlier blocks) beside its own block's, whole."""
    with jax.default_matmul_precision("highest"):
        r, mm = _products(operand_dtype)
        eps = cfg["rms_norm_eps"]
        n, b = states.shape
        pos = starts[:, None] + jnp.arange(b)[None, :]              # [N, B]
        # what a row of state n sees of the earlier positions: those below
        # its block's start; of its own block: every row
        before = jnp.arange(keys.shape[1])[None, :] < starts[:, None]
        x = params["embed_tokens"][states].astype(F32)              # [N,B,E]
        for w, k_before, v_before in zip(params["layers"], keys, values):
            h = rms_norm(x, w["input_layernorm"].astype(F32), eps)
            q, k, v = heads(h, w, cfg, pos, mm)

            def one(q, k, v, before):
                # one softmax over the earlier positions and the block
                early = jnp.where(before[None, None, None, :],
                                  scores(q, k_before, r), -jnp.inf)
                p = jax.nn.softmax(jnp.concatenate(
                    [early, scores(q, k, r)], axis=-1), -1)
                return mixed(p[..., :-b], v_before, r) \
                    + mixed(p[..., -b:], v, r)

            a = jax.vmap(one)(q, k, v, before)
            x = x + mm(a.reshape(n, b, -1), w["o_proj"])
            h = rms_norm(x, w["post_attention_layernorm"].astype(F32), eps)
            x = x + moe(h.reshape(n * b, -1), w, cfg, mm, r).reshape(x.shape)
        return head(x, params, cfg, operand_dtype)


def confidences(logits, mask_id: int):
    """(the best token [..] with the mask's own id left out, the log of its
    softmax probability over the whole vocabulary [..]) of logits [.., V]."""
    total = jax.nn.logsumexp(logits, axis=-1)
    kept = logits.at[..., mask_id].set(-jnp.inf)
    return jnp.argmax(kept, axis=-1), jnp.max(kept, axis=-1) - total
