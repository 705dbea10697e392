"""Operations and bytes of a Mamba-2 / attention hybrid decoder that
``benchmarks/flops.py`` lacks, from shapes alone.  Nothing here looks at the
program, except that the recurrence is counted in its chunked
(state-space-dual) form, which is how anyone runs it on a matrix unit: the
chunk is the configuration's own ``mamba_chunk_size``.
"""

from __future__ import annotations


def ssd_scan_forward_flops_per_token(cfg: dict) -> float:
    """One Mamba-2 layer's scan, forward, per token, in chunks of Q: inside
    a chunk C B' (one a group, 2 N a pair of positions) and the masked
    product with dt x (2 P a pair, a head), both over the lower triangle only
    (a position pairs with (Q + 1) / 2 positions on average); the state a
    chunk leaves (2 P N a head and token); and the output from the state that
    enters (2 P N).  The elementwise work (decays, softplus, the D skip) is
    not matrix work and is left out."""
    h, p = cfg["mamba_n_heads"], cfg["mamba_d_head"]
    g, n = cfg["mamba_n_groups"], cfg["mamba_d_state"]
    pairs = (cfg["mamba_chunk_size"] + 1) / 2.0
    return 2.0 * pairs * (g * n + h * p) + 4.0 * h * p * n


def ssd_scan_step_flops(cfg: dict, tokens: int, remat: bool) -> float:
    """All Mamba-2 layers' scans over ``tokens`` tokens as a training step
    executes them: forward, backward (twice the forward: every product has
    two cotangents), and the forward once more where a layer remats."""
    layers = cfg["layer_types"].count("mamba")
    passes = 4.0 if remat else 3.0
    return passes * layers * tokens * ssd_scan_forward_flops_per_token(cfg)


def ssd_scan_step_bytes(cfg: dict, tokens: int, remat: bool,
                        act_bytes: int = 2) -> float:
    """HBM traffic those scans cannot avoid.  A forward pass reads x [H P],
    B and C [G N] each in the compute dtype and dt [H] in float32 a token,
    and writes y [H P]; the backward reads the same and y's cotangent, and
    writes a cotangent for each input; a rematted layer runs the forward
    twice.  The state a sequence carries between chunks (H P N float32) can
    stay on chip and is left out."""
    h, p = cfg["mamba_n_heads"], cfg["mamba_d_head"]
    g, n = cfg["mamba_n_groups"], cfg["mamba_d_state"]
    inputs = (h * p + 2 * g * n) * act_bytes + h * 4
    y = h * p * act_bytes
    forward = inputs + y
    backward = inputs + y + inputs
    layers = cfg["layer_types"].count("mamba")
    return float(layers * tokens
                 * ((2 if remat else 1) * forward + backward))


def hybrid_lm_train_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Forward and backward, nothing recomputed, per token: 6 per matmul
    parameter (the tied head is a matmul, the embedding lookup is not), 3
    times the scan's forward a Mamba-2 layer, causal attention's two products
    forward and four backward over on average S/2 keys an attention layer
    (every query head: grouped K and V save parameters, not products)."""
    e, f = cfg["hidden_size"], cfg["shared_intermediate_size"]
    h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d = e // h
    inner = cfg["mamba_n_heads"] * cfg["mamba_d_head"]
    in_proj = 2 * inner + 2 * cfg["mamba_n_groups"] * cfg["mamba_d_state"] \
        + cfg["mamba_n_heads"]
    mixers = {"attention": 6.0 * (2 * e * h * d + 2 * e * kv * d)
              + 6.0 * seq_len * h * d,
              "mamba": 6.0 * (e * in_proj + inner * e)
              + 3.0 * ssd_scan_forward_flops_per_token(cfg)}
    return sum(mixers[kind] + 6.0 * 3 * e * f for kind in cfg["layer_types"]) \
        + 6.0 * e * cfg["vocab_size"]


def hybrid_lm_head_share(cfg: dict, seq_len: int) -> float:
    """The output head's share of the training FLOPs at this depth."""
    return 6.0 * cfg["hidden_size"] * cfg["vocab_size"] \
        / hybrid_lm_train_flops_per_token(cfg, seq_len)


def flash_calls(cfg: dict, batch: int, seq_len: int) -> list[dict]:
    """The attention layers' kernel calls of one step on one chip, as
    ``flops.flash_train_flops`` takes them."""
    h = cfg["num_attention_heads"]
    return [dict(b=batch, h=h, s=seq_len, d=cfg["hidden_size"] // h,
                 causal=True)] * cfg["layer_types"].count("attention")
