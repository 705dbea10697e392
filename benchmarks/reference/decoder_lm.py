"""Plain reference for the ``decoder_lm`` family: a Llama-style decoder
(``LlamaForCausalLM`` as the published ``config.json`` files of
deepseek-coder and its relatives describe it), forward, loss and gradients.

Written from the published description, in ``jax.numpy`` and float32 under
``jax.default_matmul_precision("highest")``: no kernel, no cache, no
batching tricks, nothing imported from the program under test.  The keys
read from the configuration are Hugging Face's own.

Parameter layout (the reference's own; ``x @ W`` orientation, i.e. the
transpose of how the checkpoints store ``nn.Linear.weight``)::

    {"embed_tokens": [V, E],
     "layers": [{"input_layernorm": [E], "q_proj": [E, H*D],
                 "k_proj": [E, KV*D], "v_proj": [E, KV*D], "o_proj": [H*D, E],
                 "post_attention_layernorm": [E], "gate_proj": [E, I],
                 "up_proj": [E, I], "down_proj": [I, E]}, ...],
     "norm": [E], "lm_head": [E, V]}

Departure from the description: none in the mathematics.  ``query_block``
only bounds memory (scores for a block of queries at a time); every query
still attends to every earlier key.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def rms_norm(x, weight, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * weight


def rotary(x, positions, theta):
    """x [S, H, D]; rotate_half convention: pair (i, i + D/2)."""
    d = x.shape[-1]
    inv_freq = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = positions[:, None] * inv_freq[None, :]            # [S, D/2]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def positions_of(cfg: dict, seq_len: int):
    pos = jnp.arange(seq_len, dtype=jnp.float32)
    scaling = cfg.get("rope_scaling")
    if scaling:
        if scaling.get("type", scaling.get("rope_type")) != "linear":
            raise ValueError(f"reference knows linear rope scaling only, "
                             f"not {scaling}")
        pos = pos / float(scaling["factor"])
    return pos


def causal_attention(q, k, v, query_block=None):
    """q [S, H, D], k/v [S, KV, D] -> [S, H, D]; softmax in float32."""
    s, h, d = q.shape
    k = jnp.repeat(k, h // k.shape[1], axis=1)
    v = jnp.repeat(v, h // v.shape[1], axis=1)
    key_pos = jnp.arange(s)

    def block(args):
        qb, qpos = args                                       # [b, H, D], [b]
        scores = jnp.einsum("qhd,khd->hqk", qb, k) / jnp.sqrt(jnp.float32(d))
        mask = key_pos[None, None, :] <= qpos[None, :, None]
        scores = jnp.where(mask, scores, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, -1), v)

    if query_block is None or query_block >= s:
        return block((q, key_pos))
    n = s // query_block
    out = jax.lax.map(block, (q.reshape(n, query_block, h, d),
                              key_pos.reshape(n, query_block)))
    return out.reshape(s, h, d)


def hidden_states(params, tokens, cfg, query_block=None):
    """tokens [S] -> final-norm hidden states [S, E]."""
    h_, kv_ = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d = cfg["hidden_size"] // h_
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    s = tokens.shape[0]
    pos = positions_of(cfg, s)
    x = params["embed_tokens"][tokens]
    for layer in params["layers"]:
        y = rms_norm(x, layer["input_layernorm"], eps)
        q = rotary((y @ layer["q_proj"]).reshape(s, h_, d), pos, theta)
        k = rotary((y @ layer["k_proj"]).reshape(s, kv_, d), pos, theta)
        v = (y @ layer["v_proj"]).reshape(s, kv_, d)
        a = causal_attention(q, k, v, query_block).reshape(s, h_ * d)
        x = x + a @ layer["o_proj"]
        y = rms_norm(x, layer["post_attention_layernorm"], eps)
        x = x + (jax.nn.silu(y @ layer["gate_proj"])
                 * (y @ layer["up_proj"])) @ layer["down_proj"]
    return rms_norm(x, params["norm"], eps)


def logits_last(params, tokens, cfg, last: int, query_block=None):
    """Logits [last, V] of the final ``last`` positions of one sequence,
    each attending to the whole context before it."""
    with jax.default_matmul_precision("highest"):
        x = hidden_states(params, tokens, cfg, query_block)
        return x[-last:] @ params["lm_head"]


def loss(params, tokens, cfg):
    """Mean next-token cross entropy of one sequence, tokens [S]."""
    with jax.default_matmul_precision("highest"):
        logits = hidden_states(params, tokens, cfg) @ params["lm_head"]
        logp = jax.nn.log_softmax(logits[:-1], axis=-1)
        return -jnp.mean(jnp.take_along_axis(logp, tokens[1:, None], -1))


def loss_and_grads(params, tokens, cfg):
    return jax.value_and_grad(loss)(params, tokens, cfg)
