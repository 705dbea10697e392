"""Operations and bytes of the ``cohere2_moe_serve`` family's two new pieces
of work, from shapes alone: attention over a causal band (a sliding window
beside full layers) and a decode step through an expert layer of which the
chip holds a share.  Needed work only: what a padded bucket, a masked tile
or a slot with no request costs beyond it is not counted, so a share of a
roofline computed from these cannot pass 100% by over-counting.
"""

from __future__ import annotations


def seen_positions(length: int, window: int | None) -> int:
    """Sum over the ``length`` positions of a sequence of how many keys each
    sees: ``i + 1`` under a causal mask, at most ``window`` under a band."""
    if window is None or length <= window:
        return length * (length + 1) // 2
    return window * (window + 1) // 2 + (length - window) * window


def prefill_attention_flops(cfg: dict, lengths) -> float:
    """The two products (q k^T and p v) of every layer's attention over
    prompts of ``lengths`` tokens: 4 operations a (query, key) pair and
    unit of head size, each head, each layer by its type."""
    per_pair = 4.0 * cfg["num_attention_heads"] * cfg["head_dim"]
    pairs = sum(
        seen_positions(int(n), cfg["sliding_window"]
                       if kind == "sliding_attention" else None)
        for n in lengths for kind in cfg["layer_types"])
    return per_pair * pairs


def decode_attention_window_bytes(cfg: dict, steps_lengths,
                                  itemsize: int = 2) -> float:
    """HBM traffic the attention of decode steps cannot avoid: each live
    slot's cached keys and values, in a sliding layer the last ``window`` of
    them alone.  ``steps_lengths``: for each step, its live slots'
    lengths."""
    per_position = 2 * cfg["num_key_value_heads"] * cfg["head_dim"] * itemsize
    window = cfg["sliding_window"]
    return float(per_position * sum(
        min(int(n), window) if kind == "sliding_attention" else int(n)
        for lengths in steps_lengths for n in lengths
        for kind in cfg["layer_types"]))


def moe_decode_bytes(cfg: dict, steps_pairs, itemsize: int = 2) -> float:
    """HBM traffic the expert layers of decode steps cannot avoid: every
    step reads each layer's router and shared experts once and each held
    expert that some token of the step picked once (three ``hidden x
    intermediate`` matrices an expert).  The activations of a handful of
    tokens are thousands of times smaller and left out.  ``steps_pairs``:
    for each step, ``[L][held]`` pairs each held expert was given."""
    e, f = cfg["hidden_size"], cfg["intermediate_size"]
    expert = 3 * e * f * itemsize
    fixed = (cfg["num_shared_experts"] * expert
             + e * cfg["num_experts_published"] * itemsize)
    return float(sum(
        fixed + expert * sum(1 for p in layer if p > 0)
        for step in steps_pairs for layer in step))
