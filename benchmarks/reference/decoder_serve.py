"""Plain reference for the ``decoder_serve`` family: the forward pass of a
Llama-style decoder (``LlamaForCausalLM`` as deepseek-coder's published
``config.json`` describes it) over one whole sequence, prompt and served
tokens together, and the logits of a run of its positions.

Written from the published description, in ``jax.numpy`` and float32 under
``jax.default_matmul_precision("highest")``: no cache, no batching, no
kernel, nothing imported from the program under test and nothing taken from
it.  The weights are the benchmark's own, drawn from the seed by
``families/decoder_serve.py`` in the type the model is served in (bfloat16)
and cast up here, a layer at a time.

Parameter layout (the reference's own; ``x @ W`` orientation; the layers
stacked on a leading axis so that one layer's program is compiled once)::

    {"embed_tokens": [V, E],
     "layers": {"input_layernorm": [L, E], "q_proj": [L, E, H*D],
                "k_proj": [L, E, KV*D], "v_proj": [L, E, KV*D],
                "o_proj": [L, H*D, E], "post_attention_layernorm": [L, E],
                "gate_proj": [L, E, I], "up_proj": [L, E, I],
                "down_proj": [L, I, E]},
     "norm": [E], "lm_head": [E, V]}

Departures from the description.  (1) Positions are 0, 1, 2, ... as they
are: the published ``rope_scaling`` (linear, factor 4) is NOT applied,
because the serving path of the program passes no positions
(``serving/engine.py`` calls the model without them) and the benchmark
measures that path as it stands; the configuration file says so under
``departures``.  Same work, other angles.  (2) ``query_block`` only bounds
memory: every query still attends to every earlier key.

``operand_dtype`` is the control's switch, never the benchmark's: with
``jnp.float8_e4m3fn`` both operands of every product are rounded to that
type first, the step below bfloat16 that would tempt a later PR.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32


def rms_norm(x, weight, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * weight


def rotary(x, positions, theta):
    """x [S, H, D]; rotate_half convention: pair (i, i + D/2)."""
    d = x.shape[-1]
    inv_freq = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=F32) / d)
    ang = positions[:, None] * inv_freq[None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def _rounder(operand_dtype):
    if operand_dtype is None:
        return lambda x: x
    return lambda x: x.astype(operand_dtype).astype(F32)


def causal_attention(q, k, v, query_block, r):
    """q [S, H, D], k/v [S, KV, D] -> [S, H, D]; softmax in float32."""
    s, h, d = q.shape
    k = jnp.repeat(k, h // k.shape[1], axis=1)
    v = jnp.repeat(v, h // v.shape[1], axis=1)
    key_pos = jnp.arange(s)

    def block(args):
        qb, qpos = args
        scores = jnp.einsum("qhd,khd->hqk", r(qb), r(k)) / jnp.sqrt(F32(d))
        mask = key_pos[None, None, :] <= qpos[None, :, None]
        scores = jnp.where(mask, scores, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", r(jax.nn.softmax(scores, -1)),
                          r(v))

    if query_block is None or query_block >= s:
        return block((q, key_pos))
    n = s // query_block
    out = jax.lax.map(block, (q.reshape(n, query_block, h, d),
                              key_pos.reshape(n, query_block)))
    return out.reshape(s, h, d)


def hidden_states(params, tokens, cfg, query_block=None, operand_dtype=None):
    """tokens [S] -> final-norm hidden states [S, E], float32."""
    h_, kv_ = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d = cfg["hidden_size"] // h_
    eps, theta = cfg["rms_norm_eps"], float(cfg["rope_theta"])
    s = tokens.shape[0]
    pos = jnp.arange(s, dtype=F32)
    r = _rounder(operand_dtype)

    def mm(x, w):
        return r(x) @ r(w.astype(F32))

    def layer(x, w):
        y = rms_norm(x, w["input_layernorm"].astype(F32), eps)
        q = rotary(mm(y, w["q_proj"]).reshape(s, h_, d), pos, theta)
        k = rotary(mm(y, w["k_proj"]).reshape(s, kv_, d), pos, theta)
        v = mm(y, w["v_proj"]).reshape(s, kv_, d)
        a = causal_attention(q, k, v, query_block, r).reshape(s, h_ * d)
        x = x + mm(a, w["o_proj"])
        y = rms_norm(x, w["post_attention_layernorm"].astype(F32), eps)
        x = x + mm(jax.nn.silu(mm(y, w["gate_proj"])) * mm(y, w["up_proj"]),
                   w["down_proj"])
        return x, None

    x = params["embed_tokens"][tokens].astype(F32)
    x, _ = jax.lax.scan(layer, x, params["layers"])
    return rms_norm(x, params["norm"].astype(F32), eps)


def logits_of_rows(params, tokens, cfg, start, rows: int, query_block=None,
                   operand_dtype=None):
    """Logits [rows, V] of positions ``start .. start + rows - 1`` of one
    sequence ``tokens`` [S], each position attending to all before it.
    ``start`` may be traced; ``rows`` is a shape."""
    with jax.default_matmul_precision("highest"):
        x = hidden_states(params, tokens, cfg, query_block, operand_dtype)
        x = jax.lax.dynamic_slice_in_dim(x, start, rows, axis=0)
        r = _rounder(operand_dtype)
        return r(x) @ r(params["lm_head"].astype(F32))
