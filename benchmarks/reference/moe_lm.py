"""Plain reference for the ``moe_lm`` family: OLMoE's decoder
(``OlmoeForCausalLM`` as ``modeling_olmoe.py`` and arXiv:2409.02060 describe
it), forward, training loss and gradients.

Written from the published description, in ``jax.numpy`` and float32 under
``jax.default_matmul_precision("highest")``.  Nothing of the program under
test is imported, and there is no sort, no grouped matmul and no kernel:
every expert is applied to every token and the results are masked by the
top-k weights.  What OLMoE shares with a Llama decoder (RMSNorm, rotary
embedding, causal attention) is the sibling reference's.  The keys read from
the configuration are Hugging Face's own.

Per layer: pre-norm attention with RMSNorm on the whole q and k projections
before the split into heads (``q_norm``, ``k_norm``), then a pre-norm sparse
feed-forward: router logits ``x @ router``, softmax over all experts in
float32, the ``num_experts_per_tok`` largest probabilities as gate weights
(divided by their sum only if ``norm_topk_prob``), each picked expert a
SwiGLU MLP of width ``intermediate_size``, no shared expert.

Training loss: cross entropy ``+ lb_coef * L_lb + z_coef * L_z`` with
``L_lb = E * sum_e f_e P_e`` (``f_e`` the share of the T*k assignments on
expert ``e``, ``P_e`` the mean router probability of ``e``) and ``L_z =
mean(logsumexp(logits)**2)``, each a mean over the layers.

Parameter layout (the reference's own; ``x @ W`` orientation)::

    {"embed_tokens": [V, E],
     "layers": [{"input_layernorm": [E], "q_proj": [E, H*D], "q_norm": [H*D],
                 "k_proj": [E, KV*D], "k_norm": [KV*D], "v_proj": [E, KV*D],
                 "o_proj": [H*D, E], "post_attention_layernorm": [E],
                 "router": [E, N], "gate_proj": [N, E, I],
                 "up_proj": [N, E, I], "down_proj": [N, I, E]}, ...],
     "norm": [E], "lm_head": [E, V]}

Departures from the published description:

* ``L_lb`` is the paper's formula.  ``transformers``'
  ``load_balancing_loss_func`` sums the same product over the k pick slots,
  which is k times this value; the coefficient 0.01 is the paper's, for the
  paper's formula.
* ``query_block`` and ``token_block`` only bound memory (scores for a block
  of queries, experts for a block of tokens at a time); every query still
  attends to every earlier key and every expert sees every token.
* ``picks`` (a layer's [T, k] expert indices), where given, replaces the
  reference's own top-k choice and nothing else: the gate weights are still
  its own float32 probabilities at those experts.  The caller uses it to
  settle ties the way the program settled them (benchmarks/families/
  moe_lm.py, "Routing ties").
* ``operand_dtype``, where given, rounds both operands of every matrix
  product to that type first: the reading that has to come out as NOT
  correct (a float8 product), never part of a comparison that passes.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.reference.decoder_lm import (causal_attention, rms_norm,
                                             rotary)


def _matmul(operand_dtype):
    if operand_dtype is None:
        return jnp.matmul
    r = lambda a: a.astype(operand_dtype).astype(jnp.float32)  # noqa: E731
    return lambda a, b: jnp.matmul(r(a), r(b))


def sparse_mlp(layer, x, cfg, picks=None, token_block=None, mm=jnp.matmul):
    """x [T, E] -> (y [T, E], routing): every expert on every token, masked."""
    n, k = cfg["num_experts"], cfg["num_experts_per_tok"]
    logits = mm(x, layer["router"])                              # [T, N]
    probs = jax.nn.softmax(logits, axis=-1)
    if picks is None:
        picks = jax.lax.top_k(probs, k)[1]
    mask = jnp.sum(jax.nn.one_hot(picks, n, dtype=jnp.float32), axis=1)
    weights = probs * mask                                       # [T, N]
    if cfg["norm_topk_prob"]:
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)

    def experts(args):
        xb, wb = args                                   # [b, E], [b, N]
        hidden = (jax.nn.silu(mm(xb[None], layer["gate_proj"]))
                  * mm(xb[None], layer["up_proj"]))              # [N, b, I]
        out = mm(hidden, layer["down_proj"])                     # [N, b, E]
        return jnp.einsum("nbe,bn->be", out, wb)

    t = x.shape[0]
    if token_block is None or token_block >= t:
        y = experts((x, weights))
    else:
        y = jax.lax.map(experts, (
            x.reshape(t // token_block, token_block, -1),
            weights.reshape(t // token_block, token_block, n))
        ).reshape(t, -1)
    pairs = jnp.sum(mask, axis=0)                                # [N]
    routing = {
        "picks": picks, "probs": probs,
        "load_balance": n * jnp.sum(
            jax.lax.stop_gradient(pairs / jnp.sum(pairs))
            * jnp.mean(probs, axis=0)),
        "router_z": jnp.mean(jnp.square(
            jax.nn.logsumexp(logits, axis=-1)))}
    return y, routing


def hidden_states(params, tokens, cfg, query_block=None, picks=None,
                  token_block=None, operand_dtype=None):
    """tokens [S] -> (final-norm hidden states [S, E], [routing a layer])."""
    h_, kv_ = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d = cfg["hidden_size"] // h_
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    mm = _matmul(operand_dtype)
    s = tokens.shape[0]
    pos = jnp.arange(s, dtype=jnp.float32)
    x = params["embed_tokens"][tokens]
    routings = []
    for i, layer in enumerate(params["layers"]):
        y = rms_norm(x, layer["input_layernorm"], eps)
        q = rms_norm(mm(y, layer["q_proj"]), layer["q_norm"], eps)
        k = rms_norm(mm(y, layer["k_proj"]), layer["k_norm"], eps)
        q = rotary(q.reshape(s, h_, d), pos, theta)
        k = rotary(k.reshape(s, kv_, d), pos, theta)
        v = mm(y, layer["v_proj"]).reshape(s, kv_, d)
        a = causal_attention(q, k, v, query_block).reshape(s, h_ * d)
        x = x + mm(a, layer["o_proj"])
        y = rms_norm(x, layer["post_attention_layernorm"], eps)
        moe, routing = sparse_mlp(
            layer, y, cfg, None if picks is None else picks[i], token_block,
            mm)
        x = x + moe
        routings.append(routing)
    return rms_norm(x, params["norm"], eps), routings


def logits_last(params, tokens, cfg, last: int, query_block=None,
                picks=None, token_block=None):
    """Logits [last, V] of the final ``last`` positions of one sequence,
    each attending to the whole context before it."""
    with jax.default_matmul_precision("highest"):
        x, _ = hidden_states(params, tokens, cfg, query_block, picks,
                             token_block)
        return x[-last:] @ params["lm_head"]


def loss_terms(params, tokens, cfg, picks=None, operand_dtype=None):
    """(training loss, its terms) of one sequence, tokens [S]: the terms are
    ``cross_entropy``, ``load_balance``, ``router_z`` and ``routing``, a
    layer's ``picks`` [S, k] and router ``probs`` [S, N]."""
    with jax.default_matmul_precision("highest"):
        x, routings = hidden_states(params, tokens, cfg, picks=picks,
                                    operand_dtype=operand_dtype)
        logits = _matmul(operand_dtype)(x, params["lm_head"])
        logp = jax.nn.log_softmax(logits[:-1], axis=-1)
        ce = -jnp.mean(jnp.take_along_axis(logp, tokens[1:, None], -1))
        mean = lambda key: sum(r[key] for r in routings) / len(routings)  # noqa: E731
        lb, z = mean("load_balance"), mean("router_z")
        total = ce + cfg["router_aux_loss_coef"] * lb \
            + cfg["router_z_loss_coef"] * z
        return total, {"cross_entropy": ce, "load_balance": lb,
                       "router_z": z, "routing": [
                           {k: r[k] for k in ("picks", "probs")}
                           for r in routings]}


def loss(params, tokens, cfg, picks=None):
    return loss_terms(params, tokens, cfg, picks)[0]


def loss_and_grads(params, tokens, cfg, picks=None, operand_dtype=None):
    """((loss, terms), gradients in the parameters' layout)."""
    return jax.value_and_grad(loss_terms, has_aux=True)(
        params, tokens, cfg, picks, operand_dtype)
