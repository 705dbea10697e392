"""Operations and bytes of the ``kda_mla_moe_serve`` family's work, from
shapes alone: the delta rule's recurrence over a prompt (a prefill) and over
one position a live slot (a decode step), the ONE kind of layer in six that
is latent attention, and how many of the layers route.  Needed work only,
what the mathematics asks whatever implements it (a chunked form does more
operations a position than are counted here; a padded bucket, a masked
position or a slot with no request costs beyond it), so a share of a roofline
computed from these cannot pass 100% by over-counting and survives a later
kernel.
"""

from __future__ import annotations

from benchmarks import flops_mla


def layers(cfg: dict) -> dict:
    """How many of the layers held are ``"kda"``, ``"mla"`` and ``"sparse"``,
    by their published indices (layer i is latent attention when (i + 1) %
    layer_group_size == 0, dense below first_k_dense_replace)."""
    held = range(*cfg["layers_held"])
    mla = sum((i + 1) % cfg["layer_group_size"] == 0 for i in held)
    return {"kda": len(held) - mla, "mla": mla,
            "sparse": sum(i >= cfg["first_k_dense_replace"] for i in held)}


def _state(cfg: dict) -> int:
    """Values of one layer's state: heads x head_dim x head_dim."""
    return cfg["num_attention_heads"] * cfg["head_dim"] ** 2


def scan_flops(cfg: dict, positions: int) -> float:
    """The recurrence as written, a position a head: the decay of the state
    (D^2), S^T k (2 D^2), the rank-one write (2 D^2) and S^T q (2 D^2);
    every kda layer."""
    return 7.0 * _state(cfg) * int(positions) * layers(cfg)["kda"]


def scan_bytes(cfg: dict, prompts, itemsize: int = 2) -> float:
    """HBM traffic the recurrence over ``prompts`` (their lengths) cannot
    avoid, every kda layer: q, k, v and the decay in and o out a position
    (5 x heads x head_dim values in the model's dtype) and the float32
    state written once a prompt."""
    wide = cfg["num_attention_heads"] * cfg["head_dim"]
    return float(layers(cfg)["kda"] * (
        5 * wide * itemsize * sum(int(n) for n in prompts)
        + 4 * _state(cfg) * len(prompts)))


def decode_state_bytes(cfg: dict, steps_slots) -> float:
    """HBM traffic a decode step's recurrence cannot avoid: each live
    slot's float32 state read and written once a kda layer.
    ``steps_slots``: for each step, its live slots."""
    return float(2 * 4 * _state(cfg) * layers(cfg)["kda"]
                 * sum(int(n) for n in steps_slots))


def latent_prefill_flops(cfg: dict, lengths) -> float:
    """``flops_mla.prefill_attention_flops`` for the latent layers alone:
    the two products of expanded attention over prompts of ``lengths``
    tokens, a causal triangle in each LATENT layer held."""
    return flops_mla.prefill_attention_flops(
        {**cfg, "num_hidden_layers": layers(cfg)["mla"]}, lengths)
