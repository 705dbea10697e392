"""Operations and bytes that the mathematics needs, from shapes alone.
Nothing here looks at the program: recomputed work is never counted, and a
causal product counts the lower triangle only.
"""

from __future__ import annotations


def decoder_lm_train_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Forward and backward of a Llama-style decoder, per token: 6 per
    matmul parameter (2 forward, 4 backward; the embedding lookup is no
    matmul, the untied head is), plus causal attention's two products
    forward and four backward over on average S/2 keys."""
    e, i = cfg["hidden_size"], cfg["intermediate_size"]
    h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d = e // h
    layer = e * h * d * 2 + e * kv * d * 2 + 3 * e * i
    matmul_params = cfg["num_hidden_layers"] * layer + e * cfg["vocab_size"]
    attention = cfg["num_hidden_layers"] * 6 * seq_len * h * d
    return 6.0 * matmul_params + attention


def decoder_lm_head_share(cfg: dict, seq_len: int) -> float:
    """The output head's share of the training FLOPs at this depth."""
    head = 6.0 * cfg["hidden_size"] * cfg["vocab_size"]
    return head / decoder_lm_train_flops_per_token(cfg, seq_len)


def flash_train_flops(b: int, h: int, s: int, d: int, causal: bool) -> float:
    """One attention layer's forward and backward as the flash algorithm
    states them: 2 products forward (QK', PV), 5 backward (QK' again from
    the saved log-sum-exp, dO V', dS K, dS' Q, P' dO) -- FlashAttention's own
    count, backward = 2.5 x forward.  Each is 2 S^2 D a head, halved when
    causal.  Since PR 27 the program's one backward kernel forms each once,
    so 7 is also what the kernels execute, but for a rematerialised layer,
    whose forward runs twice and is counted once."""
    per_product = 2.0 * b * h * s * s * d * (0.5 if causal else 1.0)
    return 7.0 * per_product


def flash_train_bytes(b: int, h: int, s: int, d: int, itemsize: int = 2
                      ) -> float:
    """HBM traffic the algorithm cannot avoid: forward reads q, k, v and
    writes o; backward reads q, k, v, o, dO and writes dq, dk, dv (the
    log-sum-exp and delta rows are D times smaller and left out)."""
    return float(4 + 8) * b * h * s * d * itemsize


def decode_attention_bytes(kv_bytes_per_token: int, live_tokens) -> float:
    """HBM traffic the attention of decode steps cannot avoid: every cached
    key and value of every live slot is read once a step (``live_tokens``:
    for each step, the sum of its slots' live lengths).  The one position a
    step writes and the queries are thousands of times smaller and left
    out."""
    return float(kv_bytes_per_token) * float(sum(live_tokens))


def conv_macs(out_hw: int, k: int, cin: int, cout: int) -> int:
    return out_hw * out_hw * k * k * cin * cout


def resnet50_forward_macs(image: int = 224, classes: int = 1000) -> int:
    """Multiply-accumulates of one image through ResNet-50 v1.5
    (convolutions and the classifier; batch norm, ReLU and pooling are not
    matrix work and are left out)."""
    hw = image // 2
    macs = conv_macs(hw, 7, 3, 64)
    hw //= 2                                    # max-pool
    cin = 64
    for stage, blocks in enumerate((3, 4, 6, 3)):
        width = 64 * 2 ** stage
        for block in range(blocks):
            stride = 2 if stage > 0 and block == 0 else 1
            macs += conv_macs(hw, 1, cin, width)            # 1x1, full res
            hw_out = hw // stride
            macs += conv_macs(hw_out, 3, width, width)      # 3x3 strided
            macs += conv_macs(hw_out, 1, width, 4 * width)
            if block == 0:
                macs += conv_macs(hw_out, 1, cin, 4 * width)  # projection
            cin, hw = 4 * width, hw_out
    return macs + cin * classes


def resnet50_train_flops_per_image(image: int = 224, classes: int = 1000
                                   ) -> float:
    """Forward 2 FLOPs a MAC, backward twice that."""
    return 6.0 * resnet50_forward_macs(image, classes)
