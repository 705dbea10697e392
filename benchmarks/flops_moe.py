"""Operations and bytes of a sparse (mixture-of-experts) decoder that
``benchmarks/flops.py`` lacks, from shapes alone: a token pays for the
experts it visits (``num_experts_per_tok`` of ``num_experts``), never for the
rest, and nothing the program recomputes is counted.
"""

from __future__ import annotations

from benchmarks import flops


def moe_lm_train_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Forward and backward of an OLMoE-style decoder, per token: a dense
    decoder's count (``flops.decoder_lm_train_flops_per_token``: 6 per
    matmul parameter, causal attention over on average S/2 keys) whose MLP
    is the k experts a token visits, plus 6 per parameter of each layer's
    router."""
    k, e = cfg["num_experts_per_tok"], cfg["hidden_size"]
    as_dense = {**cfg, "intermediate_size": k * cfg["intermediate_size"]}
    return flops.decoder_lm_train_flops_per_token(as_dense, seq_len) \
        + 6.0 * cfg["num_hidden_layers"] * e * cfg["num_experts"]


def moe_lm_head_share(cfg: dict, seq_len: int) -> float:
    """The output head's share of the training FLOPs at this depth."""
    return 6.0 * cfg["hidden_size"] * cfg["vocab_size"] \
        / moe_lm_train_flops_per_token(cfg, seq_len)


def grouped_matmul_train_flops(pairs: int, d: int, f: int) -> float:
    """One expert layer's three grouped matmuls, forward and backward, over
    ``pairs`` (token, expert) rows: 2 operations a multiply-add forward, 4
    backward (the input's gradient and the weight's), three ``d x f``
    matrices a row."""
    return 6.0 * pairs * 3 * d * f


def grouped_matmul_train_bytes(pairs: int, experts: int, d: int, f: int,
                               act_bytes: int = 2, grad_bytes: int = 4
                               ) -> float:
    """HBM traffic those matmuls cannot avoid, with activations in bf16 and
    weight gradients in f32.  Forward: read the rows [P, d], write gate and
    up [P, f] each, read their product, write the result [P, d]; each
    weight read once.  Backward: read the result's cotangent [P, d] and the
    product, write the product's cotangent, read it back as gate's and
    up's, read the rows, write the rows' cotangent; each weight read once
    more and its gradient written once."""
    rows_d = pairs * d * act_bytes
    rows_f = pairs * f * act_bytes
    weights = 3 * experts * d * f
    forward = rows_d + 2 * rows_f + rows_f + rows_d + weights * act_bytes
    backward = (rows_d + rows_f + rows_f + 2 * rows_f + rows_d + rows_d
                + weights * act_bytes + weights * grad_bytes)
    return float(forward + backward)
