"""Bytes of the ``mhc_mla_moe_serve`` family's residual path, from shapes
alone: what the hyper-connections of a prefill cannot avoid moving through
HBM, whatever implements them.  A sublayer reads the stream of a position
(``hc_mult`` rows of ``hidden_size``) TWICE and writes it ONCE: once for
the coefficients and the mix the sublayer reads (the coefficients need the
whole position's flat norm and projection before any row can be weighed,
but a position's rows fit on the chip, so one pass can serve both), once
more for the rows' own mix, which needs what the sublayer computed in
between, and the new rows written.  The sublayer's own input and output
(one row each) and the coefficients are the sublayer's and a few values a
position: not counted.  Needed work only, at the prompts' own lengths: what
a padded bucket or a third read costs beyond it is in the time and not in
the count, so a share of a roofline computed from this cannot pass 100% by
over-counting.
"""

from __future__ import annotations

SUBLAYERS = 2       # a layer's mixer and its feed-forward
PASSES = 3          # the stream read twice and written once a sublayer


def stream_bytes(cfg: dict, itemsize: int = 2) -> int:
    """A position's stream: hc_mult rows of hidden_size."""
    return cfg["hc_mult"] * cfg["hidden_size"] * itemsize


def prefill_bytes(cfg: dict, lengths, itemsize: int = 2) -> float:
    """The least HBM traffic of every layer's hyper-connections over
    prompts of ``lengths`` tokens."""
    return float(PASSES * SUBLAYERS * cfg["num_hidden_layers"]
                 * stream_bytes(cfg, itemsize) * sum(int(n) for n in lengths))
