"""Plain reference for the ``hybrid_lm`` family: a Mamba-2 / attention hybrid
decoder (``GraniteMoeHybridForCausalLM`` with no experts, as the published
``config.json`` of granite-4.0-h-micro and transformers'
``modeling_granitemoehybrid.py`` / Bamba's Mamba-2 mixer describe it),
forward, loss and gradients.

Written from the published description, in ``jax.numpy`` and float32 under
``jax.default_matmul_precision("highest")``: no kernel, no cache, no chunks,
nothing imported from the program under test, and no algorithm shared with
it.  The recurrence of a Mamba-2 layer is computed two ways, neither of them
the program's chunked one:

* :func:`ssm_quadratic` -- for each head the whole lower-triangular
  ``L o (C B')`` over the sequence, ``L[t, s] = exp(sum_{s<r<=t} dt_r a)``,
  one product with ``dt x``.  O(S^2); what loss and gradients use.
* :func:`ssm_sequential` -- the recurrence itself, one position after the
  other, ``H_t = exp(dt_t a) H_{t-1} + dt_t x_t B_t'``, ``y_t = H_t C_t``;
  forward only, no stored states; what the long-context logits use.

Equations.  h0 = embedding_multiplier * E[tokens].  For layer i with mixer
M_i (``layer_types[i]``): u = h + residual_multiplier * M_i(rms(h));
h' = u + residual_multiplier * W_down(silu(W_gate n) * (W_up n)), n = rms(u).
Logits = rms(h_L) E' / logits_scaling (the head is the embedding, tied).

* attention: q = n W_q [H x D], k = n W_k, v = n W_v [KV x D]; query head j
  reads KV head j // (H / KV); softmax(attention_multiplier * q k' + causal
  mask) v; W_o.  No positions ("nope"), no bias, no QK-norm.
* mamba: [z | xBC | dt] = n W_in (I | I + 2 G N | H).  xBC <- silu(causal
  depthwise conv, K taps, with bias).  xBC = [x (H x P) | B (G x N) |
  C (G x N)]; head h reads group h // (H / G).  dt <- softplus(dt + dt_bias)
  (time_step_limit (0, inf): no clamp), a = -exp(A_log).  y = recurrence
  + D x.  g = y * silu(z); rms over all I channels as one group with a
  scale; W_out.

Parameter layout (the reference's own; ``x @ W`` orientation, i.e. the
transpose of how the checkpoints store ``nn.Linear.weight``; the checkpoint's
``shared_mlp.input_linear`` [2 I_mlp, E] is ``gate_proj`` then ``up_proj``
stacked, ``output_linear`` is ``down_proj``)::

    {"embed_tokens": [V, E],
     "layers": [{"input_layernorm": [E], "post_attention_layernorm": [E],
                 "gate_proj": [E, F], "up_proj": [E, F], "down_proj": [F, E],
                 # an attention layer:
                 "q_proj": [E, H*D], "k_proj": [E, KV*D], "v_proj": [E, KV*D],
                 "o_proj": [H*D, E],
                 # a mamba layer:
                 "in_proj": [E, 2I + 2GN + Hm], "conv_weight": [K, I + 2GN]
                 (tap k multiplies position t-(K-1)+k: conv1d.weight[c,0,k]),
                 "conv_bias": [I + 2GN], "dt_bias": [Hm], "A_log": [Hm],
                 "D": [Hm], "mamba_norm": [I], "out_proj": [I, E]}, ...],
     "norm": [E]}

Departure from the description: none in the mathematics.  ``query_block``
only bounds memory; ``jax.checkpoint`` around a layer only bounds what the
backward keeps.  ``operand_dtype`` (None everywhere but where a tolerance is
being set) rounds both operands of every matrix product to that type before
a float32 product: what a program computing in that precision would give.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def rounder(operand_dtype):
    """x -> x rounded to ``operand_dtype`` and back (the identity at None)."""
    if operand_dtype is None:
        return lambda x: x
    return lambda x: x.astype(operand_dtype).astype(jnp.float32)


def rms_norm(x, weight, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * weight


def causal_attention(q, k, v, scale, query_block=None, r=rounder(None)):
    """q [S, H, D], k/v [S, KV, D] -> [S, H, D]; softmax in float32."""
    s, h, d = q.shape
    k = jnp.repeat(k, h // k.shape[1], axis=1)
    v = jnp.repeat(v, h // v.shape[1], axis=1)
    key_pos = jnp.arange(s)

    def block(args):
        qb, qpos = args                                       # [b, H, D], [b]
        scores = jnp.einsum("qhd,khd->hqk", r(qb), r(k)) * scale
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


def causal_conv(x, weight, bias):
    """x [S, C], weight [K, C]: y_t = bias + sum_k weight[k] x_{t-(K-1)+k},
    zeros before the sequence starts."""
    taps = weight.shape[0]
    s = x.shape[0]
    padded = jnp.concatenate([jnp.zeros((taps - 1, x.shape[1]), x.dtype), x])
    return bias + sum(weight[k] * padded[k:k + s] for k in range(taps))


def ssm_quadratic(x, dt, a, b, c, r=rounder(None)):
    """x [S, H, P], dt [S, H], a [H], b/c [S, H, N] -> y [S, H, P]:
    y_t = sum_{s<=t} exp(sum_{s<r<=t} dt_r a) (C_t . B_s) dt_s x_s."""
    s = x.shape[0]
    cum = jnp.cumsum(dt * a, axis=0)                          # [S, H]
    seg = cum[:, None, :] - cum[None, :, :]                   # [t, s, H]
    lower = jnp.tril(jnp.ones((s, s), bool))[:, :, None]
    decay = jnp.where(lower, jnp.exp(jnp.where(lower, seg, 0.0)), 0.0)
    scores = jnp.einsum("thn,shn->tsh", r(c), r(b)) * decay
    return jnp.einsum("tsh,shp->thp", r(scores), r(dt[:, :, None] * x))


def ssm_sequential(x, dt, a, b, c, r=rounder(None)):
    """The same y, by the recurrence, one position at a time."""
    def step(state, inputs):                                  # [H, P, N]
        x_t, dt_t, b_t, c_t = inputs
        state = state * jnp.exp(dt_t * a)[:, None, None] \
            + r(dt_t[:, None] * x_t)[:, :, None] * r(b_t)[:, None, :]
        return state, jnp.einsum("hpn,hn->hp", r(state), r(c_t))

    h, p = x.shape[1:]
    # (unroll: the same steps, fewer trips round a loop of tiny operations)
    _, y = jax.lax.scan(step, jnp.zeros((h, p, b.shape[-1]), x.dtype),
                        (x, dt, b, c), unroll=8)
    return y


def mamba_mixer(n, layer, cfg, ssm, r):
    s = n.shape[0]
    h, p = cfg["mamba_n_heads"], cfg["mamba_d_head"]
    g, state = cfg["mamba_n_groups"], cfg["mamba_d_state"]
    inner = h * p
    zxbcdt = r(n) @ r(layer["in_proj"])
    z, xbc, dt = jnp.split(zxbcdt, [inner, 2 * inner + 2 * g * state], axis=-1)
    xbc = jax.nn.silu(causal_conv(xbc, layer["conv_weight"],
                                  layer["conv_bias"]))
    x, b, c = jnp.split(xbc, [inner, inner + g * state], axis=-1)
    x = x.reshape(s, h, p)
    b = jnp.repeat(b.reshape(s, g, state), h // g, axis=1)
    c = jnp.repeat(c.reshape(s, g, state), h // g, axis=1)
    dt = jax.nn.softplus(dt + layer["dt_bias"])
    y = ssm(x, dt, -jnp.exp(layer["A_log"]), b, c, r) \
        + layer["D"][None, :, None] * x
    gated = y.reshape(s, inner) * jax.nn.silu(z)
    return r(rms_norm(gated, layer["mamba_norm"], cfg["rms_norm_eps"])) \
        @ r(layer["out_proj"])


def attention_mixer(n, layer, cfg, query_block, r):
    s = n.shape[0]
    h_, kv_ = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d = cfg["hidden_size"] // h_
    if cfg["position_embedding_type"] != "nope":
        raise ValueError("this reference knows attention without positions "
                         "only (position_embedding_type nope)")
    n = r(n)
    q = (n @ r(layer["q_proj"])).reshape(s, h_, d)
    k = (n @ r(layer["k_proj"])).reshape(s, kv_, d)
    v = (n @ r(layer["v_proj"])).reshape(s, kv_, d)
    out = causal_attention(q, k, v, cfg["attention_multiplier"], query_block,
                           r)
    return r(out.reshape(s, h_ * d)) @ r(layer["o_proj"])


def hidden_states(params, tokens, cfg, ssm, query_block=None, r=rounder(None)):
    """tokens [S] -> final-norm hidden states [S, E]."""
    eps, res = cfg["rms_norm_eps"], cfg["residual_multiplier"]
    if not cfg["tie_word_embeddings"]:
        raise ValueError("this reference knows a tied head only")

    def one_layer(x, layer, kind):
        n = rms_norm(x, layer["input_layernorm"], eps)
        if kind == "mamba":
            mixed = mamba_mixer(n, layer, cfg, ssm, r)
        elif kind == "attention":
            mixed = attention_mixer(n, layer, cfg, query_block, r)
        else:
            raise ValueError(f"layer type {kind!r}")
        x = x + res * mixed
        n = r(rms_norm(x, layer["post_attention_layernorm"], eps))
        return x + res * (r(jax.nn.silu(n @ r(layer["gate_proj"]))
                            * (n @ r(layer["up_proj"])))
                          @ r(layer["down_proj"]))

    x = cfg["embedding_multiplier"] * params["embed_tokens"][tokens]
    for layer, kind in zip(params["layers"], cfg["layer_types"], strict=True):
        x = jax.checkpoint(one_layer, static_argnums=2)(x, layer, kind)
    return rms_norm(x, params["norm"], eps)


def logits_last(params, tokens, cfg, last: int, query_block=None,
                operand_dtype=None):
    """Logits [last, V] of the final ``last`` positions of one sequence, each
    with the whole context before it, by the sequential recurrence."""
    r = rounder(operand_dtype)
    with jax.default_matmul_precision("highest"):
        x = hidden_states(params, tokens, cfg, ssm_sequential, query_block, r)
        return r(x[-last:]) @ r(params["embed_tokens"]).T \
            / cfg["logits_scaling"]


def loss(params, tokens, cfg, operand_dtype=None):
    """Mean next-token cross entropy of one sequence, tokens [S], by the
    quadratic form of the recurrence."""
    r = rounder(operand_dtype)
    with jax.default_matmul_precision("highest"):
        x = hidden_states(params, tokens, cfg, ssm_quadratic, r=r)
        logits = r(x) @ r(params["embed_tokens"]).T / cfg["logits_scaling"]
        logp = jax.nn.log_softmax(logits[:-1], axis=-1)
        return -jnp.mean(jnp.take_along_axis(logp, tokens[1:, None], -1))


def loss_and_grads(params, tokens, cfg, operand_dtype=None):
    return jax.value_and_grad(loss)(params, tokens, cfg, operand_dtype)
