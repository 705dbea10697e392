"""Operations and bytes of the ``sdar_moe_serve`` family's work, from shapes
alone: attention under the block-causal mask over a prompt's whole blocks (a
prefill) and the weights of the experts a pass's picks touch (a decode call
of a block-diffusion model: slots x block positions through top-k of every
expert).  Needed work only, what the mathematics asks whatever implements it
(a bucket's padding, a masked tile, a slot with no request or an expert no
position picked cost beyond it), so a share of a roofline computed from
these cannot pass 100% by over-counting and survives a later kernel.
"""

from __future__ import annotations

from benchmarks import flops_cca


def seen_positions(length: int, block: int) -> int:
    """Sum over the ``length`` positions of a sequence of how many keys each
    sees under the block-causal mask: position i sees the ``(i // block +
    1) * block`` positions up to its own block's end, held to ``length``."""
    whole, rest = divmod(int(length), block)
    return block * block * whole * (whole + 1) // 2 + rest * int(length)


def prefill_attention_flops(cfg: dict, lengths) -> float:
    """The two products (q k^T and p v) of every layer's attention over the
    cached parts of prompts, ``lengths`` positions each (whole blocks): 4
    operations a (query, key) pair and unit of head size, each head, each
    layer."""
    per_pair = 4.0 * cfg["num_attention_heads"] * cfg["head_dim"]
    block = int(cfg["generation"]["block_length"])
    return per_pair * cfg["num_hidden_layers"] * sum(
        seen_positions(n, block) for n in lengths)


# one expert's three matrices: 9.437 MB at 2048 x 768 in bfloat16
expert_bytes = flops_cca.expert_bytes


def block_decode_bytes(cfg: dict, experts_touched: int,
                       itemsize: int = 2) -> float:
    """HBM traffic the grouped products of passes cannot avoid: the weights
    of each expert some live position picked, read once a pass and layer.
    ``experts_touched``: distinct experts picked, summed over the layers and
    the passes (the program's own count)."""
    return float(expert_bytes(cfg, itemsize) * int(experts_touched))
