"""Operations and bytes of the ``cca_moe_serve`` family's work, from shapes
alone: attention inside the compressed latent over a prompt (a prefill) and
over the cached rows of the live slots (a decode step), and the weights of
the experts a top-1 decode step's picks touch.  Needed work only, what the
mathematics asks whatever implements it (a bucket's padding, a masked
position, a slot with no request or an expert no token picked cost beyond
it), so a share of a roofline computed from these cannot pass 100% by
over-counting and survives a later kernel.
"""

from __future__ import annotations


def _latent(cfg: dict) -> int:
    """The query side's width: heads x head_dim."""
    return cfg["num_attention_heads"] * cfg["head_dim"]


def prefill_attention_flops(cfg: dict, lengths) -> float:
    """The two products of causal attention over prompts of ``lengths``
    tokens at their OWN lengths: 2 FLOPs a multiply-add, QK' and PV, over a
    head's channels for every head, a (query, key) pair of the causal
    triangle, every layer."""
    pairs = sum(int(n) * (int(n) + 1) // 2 for n in lengths)
    return 4.0 * _latent(cfg) * pairs * cfg["num_hidden_layers"]


def cached_bytes_per_token(cfg: dict, itemsize: int = 2) -> int:
    """K and V rows of one cached position, a layer."""
    return 2 * cfg["num_key_value_heads"] * cfg["head_dim"] * itemsize


def decode_attention_bytes(cfg: dict, live_rows, itemsize: int = 2) -> float:
    """HBM traffic a decode step's attention cannot avoid: each live slot's
    cached rows, K and V, read once a layer.  ``live_rows``: for each step,
    the sum of its live slots' lengths."""
    return float(cached_bytes_per_token(cfg, itemsize)
                 * cfg["num_hidden_layers"] * sum(int(n) for n in live_rows))


def expert_bytes(cfg: dict, itemsize: int = 2) -> int:
    """One expert's three matrices."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"] * itemsize


def top1_decode_bytes(cfg: dict, experts_touched: int,
                      itemsize: int = 2) -> float:
    """HBM traffic the grouped products of decode steps cannot avoid: the
    weights of each expert some live slot picked, read once a step and
    layer.  ``experts_touched``: distinct experts picked, summed over the
    layers and the steps (the program's own count)."""
    return float(expert_bytes(cfg, itemsize) * int(experts_touched))
