"""Operations and bytes of the ``mla_moe_serve`` family's work, from shapes
alone: latent attention expanded over a prompt (a prefill) and absorbed over
a cache of latents (a decode step), and how many of the layers route (the
others are leading dense layers).  Needed work
only: what a padded bucket, a masked tile or a slot with no request costs
beyond it is not counted, so a share of a roofline computed from these
cannot pass 100% by over-counting.
"""

from __future__ import annotations


def prefill_attention_flops(cfg: dict, lengths) -> float:
    """The two products of every layer's expanded attention over prompts of
    ``lengths`` tokens: q k^T over keys of nope + rope, p v over values of
    v_head_dim, 2 operations a (query, seen key) pair and unit of width,
    each head, a causal triangle in every layer."""
    per_pair = 2.0 * cfg["num_attention_heads"] * (
        cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"] + cfg["v_head_dim"])
    pairs = sum(int(n) * (int(n) + 1) // 2 for n in lengths)
    return per_pair * pairs * cfg["num_hidden_layers"]


def _cached(cfg: dict) -> int:
    """Values a cached token holds in one layer: the latent and the one
    rotary key."""
    return cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]


def decode_attention_bytes(cfg: dict, steps_lengths, itemsize: int = 2
                           ) -> float:
    """HBM traffic the absorbed attention of decode steps cannot avoid: each
    live slot's cached latents and rotary keys, once a layer.
    ``steps_lengths``: for each step, its live slots' lengths."""
    return float(_cached(cfg) * itemsize * cfg["num_hidden_layers"] * sum(
        int(n) for lengths in steps_lengths for n in lengths))


def decode_attention_flops(cfg: dict, steps_lengths) -> float:
    """The absorbed products of the same steps: scores over latent + rotary
    key, the weighted sum over the latent, every head against the one
    cached row: 2 H (2 rank + rope) a cached token a layer."""
    per_token = 2.0 * cfg["num_attention_heads"] * (
        _cached(cfg) + cfg["kv_lora_rank"])
    return per_token * cfg["num_hidden_layers"] * sum(
        int(n) for lengths in steps_lengths for n in lengths)


def sparse_layers(cfg: dict) -> int:
    return cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]
