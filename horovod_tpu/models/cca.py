"""Compressed Convolutional Attention (CCA; Figliolia et al.,
arXiv:2510.04476, as the ``zaya`` family runs it), the ``"cca"`` entry of
``TransformerConfig.layer_types``: queries, keys and values live in a latent
narrower than the stream (``num_heads`` and ``num_kv_heads`` heads of
``head_dim``), are mixed over the sequence by two short causal convolutions,
and attention never leaves the latent.  On the layer's normed input ``h_t``::

    q~ = h W_q  [H D],  k~ = h W_k  [KV D];   u = [q~ ; k~]   (C = (H + KV) D)
    c1 = depthwise causal conv of taps[0] positions on u, a bias a channel
    [q^ ; k^] = grouped causal conv of taps[1] positions on c1: H + KV groups
                (the heads), each mixing its own D channels, a bias a channel
    q_j = q^_j + (q~_j + k~_(j // G)) / 2                 G = H / KV
    k_i = k^_i + (mean of q~_j over i's G query heads + k~_i) / 2
    v_t = per KV head [ (h_t W_v1)_i ; (h_(t-1) W_v2)_i ]   each half D / 2
    q, k: a head's vector to length sqrt(D) (x * rsqrt(mean x^2 + eps)),
          k times a learned scalar a KV head; rotary on the first
          ``rotary_fraction`` of a head's channels
    o = causal softmax attention, H query over KV key heads;   y = o W_o

Both convolutions and the value shift see zeros before position 0.

Parameters, all the layer's own::

    q / kernel [E, H D], k / kernel [E, KV D]
    v_now, v_prev / kernel [E, KV D / 2]
    conv0 [taps0, C], conv0_bias [C]        tap j multiplies position
    conv1 [H + KV, taps1, D, D], conv1_bias [C]      t - (taps - 1) + j
    k_scale [KV]
    o / kernel [H D, E]

Three passes of one parameter tree:

* *without a cache* (training's forward, a serving prefill): everything
  before the attention is position-wise but for what the convolutions and
  the shift carry over from the position before (their TAIL); a served
  prefill over several row blocks runs it a row block at a time up to the
  prompt's end, the tail handed from block to block
  (``transformer._over_rows_carrying``), attention over the whole prompt
  through ``cfg.attention_fn`` (the flash forward, told where the prompt
  ends) and the output projection over the row blocks again.  ``return_kv``
  hands back what the cache holds: the rotated keys and the values a
  position (``[B, S, KV D]``), and the tail at each row's OWN length (``lengths``): ``u`` at
  the last ``taps0 - 1`` positions, ``c1`` at the last ``taps1 - 1`` and
  ``h W_v2`` at the last, in float32.
* *with a cache* (one position a slot): the tail stands for the positions
  before, the new key and value go into the pool's rows at the slot's
  length, attention reads the slot's rows
  (``transformer.rows_decode_attention``)
  and the tail is replaced.  A block of more positions (speculative verify, a
  prefix-attached suffix) is refused by name.

The cache is of TWO kinds in one layer (``transformer.init_kv_cache``):
``"cca"`` rows a position, K in the first tree and V in the second, each
row its KV heads side by side ``[KV D]``, and ``"cca_tail"`` a slot whatever
its length, the convolutions' in the first and the shift's in the second.
A decode step reads the rows AS THEY LIE
(``transformer.rows_decode_attention``, a block model's passes too): a
pool kept ``[.., S, KV, D]`` and read through a product batched over the KV
heads had each layer's view copied out first, 15.4 of a 27.2 ms step at 24
slots of 10496 (read on a v5e, PERF.md section 6, PR 52).

Everything the layer does is under one of four scopes (``utils/profiling``:
``hvd_cca_proj`` / ``_conv`` / ``_attn`` / ``_out``).
"""

from __future__ import annotations

import flax.linen as nn
import jax
import jax.numpy as jnp

from horovod_tpu.models.transformer import (TransformerConfig,
                                            _over_rows, _over_rows_carrying,
                                            _prompt_end, _prompt_rows,
                                            dense_causal_attention, rope,
                                            rows_decode_attention,
                                            write_kv_block)
from horovod_tpu.utils import profiling

F32 = jnp.float32


def cca_sizes(cfg: TransformerConfig) -> dict:
    """The widths a ``"cca"`` layer of ``cfg`` works at and what it keeps,
    from the configuration alone."""
    h, kv, d = cfg.num_heads, cfg.kv_heads, cfg.head_dim
    t0, t1 = cfg.cca_taps
    channels = (h + kv) * d
    item = jnp.dtype(cfg.dtype).itemsize
    return {"heads": h, "kv_heads": kv, "head_dim": d,
            "query_width": h * d, "key_width": kv * d, "channels": channels,
            "taps": [t0, t1], "rotary_channels": int(d * cfg.rotary_fraction),
            "value_half": kv * d // 2,
            # K and V rows of one cached position, a layer
            "bytes_per_token_and_layer": 2 * kv * d * item,
            # a slot's tail whatever its length, a layer (float32)
            "tail_values_per_layer_and_slot":
                (t0 - 1 + t1 - 1) * channels + kv * d // 2,
            "tail_bytes_per_layer_and_slot":
                4 * ((t0 - 1 + t1 - 1) * channels + kv * d // 2)}


def _to_length(x, eps: float):
    """Each vector of the last axis to length sqrt(its width), float32."""
    x = x.astype(F32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def _tail_at(seq, count, keep: int):
    """``seq`` [B, keep + n, ...] holds ``keep`` carried rows and then a
    block's n: the ``keep`` rows that end at the block's ``count``-th
    (``count`` [B], or None: at its last)."""
    if count is None:
        return seq[:, seq.shape[1] - keep:]
    return jnp.stack([jax.lax.dynamic_slice_in_dim(seq[i], count[i], keep,
                                                   axis=0)
                      for i in range(seq.shape[0])])


class CCAMixer(nn.Module):
    cfg: TransformerConfig

    @nn.compact
    def __call__(self, x, positions=None, cache=None, return_kv=False,
                 lengths=None):
        cfg = self.cfg
        if cfg.context_axis is not None:
            raise NotImplementedError(
                "context parallelism across a cca layer's convolutions is "
                "not built: it needs its whole sequence on one chip")
        h, kv, d = cfg.num_heads, cfg.kv_heads, cfg.head_dim
        t0, t1 = cfg.cca_taps
        rot = int(d * cfg.rotary_fraction)
        if h % kv or d % 2 or rot % 2 or min(t0, t1) < 2:
            raise ValueError(
                "a cca layer needs num_kv_heads to divide num_heads, an even "
                "head_dim and rotary share of it, and cca_taps of 2 or more")
        group, channels, half = h // kv, (h + kv) * d, kv * d // 2
        bsz = x.shape[0]
        # a served prefill over several row blocks: one loop, and what is
        # called inside it is called unbound (transformer._over_rows)
        rows, made = _prompt_rows(x, cache, return_kv, lengths)
        dense = lambda name, width: made(nn.Dense(  # noqa: E731
            width, use_bias=False, dtype=cfg.dtype,
            param_dtype=cfg.param_dtype, name=name))
        own = lambda name, init, shape: self.param(  # noqa: E731
            name, init, shape, cfg.param_dtype)
        proj = {"q": dense("q", h * d), "k": dense("k", kv * d),
                "v_now": dense("v_now", half), "v_prev": dense("v_prev", half),
                "o": dense("o", cfg.embed_dim)}
        lecun = nn.initializers.lecun_normal
        w0 = own("conv0", lecun(in_axis=0, out_axis=1),
                 (t0, channels)).astype(F32)
        b0 = own("conv0_bias", nn.initializers.zeros, (channels,)).astype(F32)
        # fan-in is the taps and a head's channels; the heads are a batch
        w1 = own("conv1", lecun(in_axis=(1, 2), out_axis=3, batch_axis=0),
                 (h + kv, t1, d, d)).astype(F32)
        b1 = own("conv1_bias", nn.initializers.zeros,
                 (channels,)).astype(F32).reshape(h + kv, d)
        k_scale = own("k_scale", nn.initializers.ones, (kv,)).astype(F32)

        def before(carry, x, positions):
            """(the tail after the block, (q [B, n, H, D], k, v [B, n, KV,
            D])) of a block of n positions that follows the tail
            ``carry``."""
            tail_u, tail_c, tail_v = carry
            n = x.shape[1]
            with jax.named_scope(profiling.CCA_PROJ):
                q_in, k_in = proj["q"](x), proj["k"](x)
                v_now, v_prev = proj["v_now"](x), proj["v_prev"](x)
            with jax.named_scope(profiling.CCA_CONV):
                seq_u = jnp.concatenate(
                    [tail_u, jnp.concatenate([q_in, k_in], -1).astype(F32)],
                    axis=1)
                c1 = sum(seq_u[:, j:j + n] * w0[j] for j in range(t0)) + b0
                seq_c = jnp.concatenate([tail_c, c1], axis=1)
                # (float32 operands at the default precision: one bfloat16
                # pass of the MXU, float32 sums, as the model's other
                # products; a float32 model's are exact)
                by_head = seq_c.reshape(bsz, -1, h + kv, d)
                c2 = sum(jnp.einsum("bngd,gde->bnge", by_head[:, j:j + n],
                                    w1[:, j]) for j in range(t1)) + b1
                # the q-k mean: each head also sees the plain projections of
                # its group, a key head the mean of its query heads'
                q_plain = q_in.astype(F32).reshape(bsz, n, kv, group, d)
                k_plain = k_in.astype(F32).reshape(bsz, n, kv, d)
                q = c2[:, :, :h].reshape(q_plain.shape) \
                    + (q_plain + k_plain[:, :, :, None]) / 2
                k = c2[:, :, h:] + (q_plain.mean(axis=3) + k_plain) / 2
                q = q.reshape(bsz, n, h, d)
                # the value shift: half a head's channels from this position
                # and half from the one before
                seq_v = jnp.concatenate([tail_v, v_prev.astype(F32)], axis=1)
                v = jnp.concatenate(
                    [v_now.reshape(bsz, n, kv, d // 2),
                     seq_v[:, :n].astype(cfg.dtype).reshape(
                         bsz, n, kv, d // 2)], axis=-1)
                # the tail a step at the prompt's own end expects
                count = None if lengths is None \
                    else jnp.clip(lengths - positions[:, 0], 0, n)
                carry = (_tail_at(seq_u, count, t0 - 1),
                         _tail_at(seq_c, count, t1 - 1),
                         _tail_at(seq_v, count, 1))
            with jax.named_scope(profiling.CCA_ATTN):
                q = _to_length(q, cfg.norm_eps)
                k = _to_length(k, cfg.norm_eps) * k_scale[:, None]

                def turned(y):
                    if not rot:
                        return y.astype(cfg.dtype)
                    return jnp.concatenate(
                        [rope(y[..., :rot], positions, cfg.rope_theta,
                              interleaved=cfg.rope_interleaved),
                         y[..., rot:]], axis=-1).astype(cfg.dtype)

                return carry, (turned(q), turned(k), v)

        def after(o):
            with jax.named_scope(profiling.CCA_OUT):
                return proj["o"](o.reshape(*o.shape[:2], h * d))

        told = ({} if cfg.attention_scale is None
                else {"scale": cfg.attention_scale})
        if cache is not None:
            if x.shape[1] != 1:
                raise NotImplementedError(
                    "a cca layer decodes one position a cache call: a block "
                    "of more (speculative verify, a prefix-attached suffix "
                    "prefill) would need the convolutions' tail taken back "
                    "past a rejected position, which is not built")
            first, second, at, layer = cache
            conv_tail, shift_tail = first["cca_tail"], second["cca_tail"]
            carry, (q, k, v) = before(
                (conv_tail[layer][:, :t0 - 1], conv_tail[layer][:, t0 - 1:],
                 shift_tail[layer]), x, positions)
            with jax.named_scope(profiling.CCA_CONV):
                conv_tail = jax.lax.dynamic_update_slice(
                    conv_tail, jnp.concatenate(carry[:2], axis=1)[None],
                    (layer, 0, 0, 0))
                shift_tail = jax.lax.dynamic_update_slice(
                    shift_tail, carry[2][None], (layer, 0, 0, 0))
            with jax.named_scope(profiling.CCA_ATTN):
                k_rows = write_kv_block(first["cca"], k.reshape(bsz, 1, -1),
                                        layer, at)
                v_rows = write_kv_block(second["cca"], v.reshape(bsz, 1, -1),
                                        layer, at)
                o = rows_decode_attention(q, k_rows[layer], v_rows[layer],
                                          at, **told)
            return after(o), ({"cca": k_rows, "cca_tail": conv_tail},
                              {"cca": v_rows, "cca_tail": shift_tail})

        empty = (jnp.zeros((bsz, t0 - 1, channels), F32),
                 jnp.zeros((bsz, t1 - 1, channels), F32),
                 jnp.zeros((bsz, 1, half), F32))
        if positions is None:
            positions = jnp.broadcast_to(jnp.arange(x.shape[1])[None],
                                         x.shape[:2])
        kept, (q, k, v) = _over_rows_carrying(before, rows, empty, x,
                                              positions)
        with jax.named_scope(profiling.CCA_ATTN):
            attn = cfg.attention_fn or dense_causal_attention
            o = attn(q, k, v, causal=True, **told,
                     **_prompt_end(cfg, lengths))
        out = _over_rows(after, rows, o)
        if not return_kv:
            return out
        rows = lambda y: y.reshape(*y.shape[:2], kv * d)  # noqa: E731
        return out, ({"cca": rows(k),
                      "cca_tail": jnp.concatenate(kept[:2], axis=1)},
                     {"cca": rows(v), "cca_tail": kept[2]})
