"""The Kimi Delta Attention sequence mixer (KDA; Kimi Linear,
arXiv:2510.26692, as the ``bailing_hybrid`` family runs it), the ``"kda"``
entry of ``TransformerConfig.layer_types``: a linear attention whose state a
head, ``S`` [D, D], forgets by a decay a key channel and learns by the delta
rule (``ops/kda_scan.py``).  On the layer's normed input ``x_t``::

    q~, k~, v~ = x W_q, x W_k, x W_v             each heads x D wide
    q', k', v' = silu(causal conv of `taps` positions, depthwise, on each)
    q = l2norm(q') D^-1/2,  k = l2norm(k'),  v = v'          a head
    g = lower * sigmoid(exp(A_log_h) (x W_f + dt_bias))      a key channel,
                                                 in (lower, 0); a = exp(g)
    b = sigmoid(x W_b)                                        a head
    S_t = (I - b k k^T) Diag(a) S_{t-1} + b k v^T;   o = S_t^T q
    y = (rmsnorm over a head's D of o, one weight) * sigmoid(x W_g);  y W_o

Parameters, all the layer's own (the family's names in brackets)::

    q, k, v / kernel    [E, H D]      (q_proj, k_proj, v_proj)
    q_conv, k_conv, v_conv  [taps, H D]   tap j multiplies position
                                      t - (taps - 1) + j (conv1d.weight[c, 0, j])
    f / kernel          [E, H D]      (f_proj: full rank, no_kda_lora)
    dt_bias [H D], A_log [H]
    b / kernel          [E, H]        (b_proj)
    g / kernel          [E, H D]      (g_proj: full rank)
    o_norm / scale      [D]           (o_norm.weight)
    o / kernel          [H D, E]      (o_proj)

Three passes of one parameter tree:

* *without a cache* (training's forward, a serving prefill): the chunked
  scan (``kda_chunked``) from a state of zeros.  ``return_kv`` hands back
  what the cache holds a slot: the state after the last position and the
  convolution's last ``taps - 1`` inputs; with ``lengths`` those are taken at
  each row's OWN length (the positions past it change no state: their decay
  is 1 and their step 0), and over several row blocks
  (``transformer.row_blocks``) the whole mixer runs in one loop over the
  prompt's blocks, state and tail carried from block to block.
* *with a cache*, one position a slot: one step of the recurrence
  (``kda_step``) on the slot's state, written back where it lies.  A block
  of more positions (speculative verify, a prefix-attached suffix) is
  refused by name.

Everything the layer does is under one of five scopes (``utils/profiling``:
``hvd_kda_proj`` / ``_conv`` / ``_gate`` / ``_scan`` / ``_out``).
"""

from __future__ import annotations

import flax.linen as nn
import jax
import jax.numpy as jnp

from horovod_tpu.models.mamba import _a_log_init, _dt_bias_init, _uniform
from horovod_tpu.models.transformer import (RMSNorm, TransformerConfig,
                                            _over_rows_carrying,
                                            _prompt_rows)
from horovod_tpu.ops.kda_scan import (CHUNK, kda_chunked, kda_step,
                                      scan_form)
from horovod_tpu.utils import profiling

F32 = jnp.float32


def _l2norm(x):
    """x [..., D] / its length, in float32."""
    x = x.astype(F32)
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


class KDAMixer(nn.Module):
    cfg: TransformerConfig

    @nn.compact
    def __call__(self, x, positions=None, cache=None, return_kv=False,
                 lengths=None):
        cfg = self.cfg
        if cfg.context_axis is not None:
            raise NotImplementedError(
                "context parallelism across a scan is not supported yet: a "
                "kda layer needs its whole sequence on one chip")
        h, d, taps = cfg.kda_heads, cfg.kda_head_dim, cfg.kda_conv_width
        if not (h and d and taps > 1):
            raise ValueError("a kda layer needs TransformerConfig's "
                             "kda_heads, kda_head_dim and kda_conv_width > 1")
        inner = h * d
        # a served prefill over several row blocks: one loop, and what is
        # called inside it is called unbound (transformer._over_rows)
        rows, made = _prompt_rows(x, cache, return_kv, lengths)
        dense = lambda name, width: made(nn.Dense(  # noqa: E731
            width, use_bias=False, dtype=cfg.dtype,
            param_dtype=cfg.param_dtype, name=name))
        own = lambda name, init, shape: self.param(  # noqa: E731
            name, init, shape, cfg.param_dtype)
        proj = {name: dense(name, inner) for name in "qkvfg"}
        proj["b"], proj["o"] = dense("b", h), dense("o", cfg.embed_dim)
        conv_w = jnp.concatenate(
            [own(f"{name}_conv", _uniform(taps ** -0.5), (taps, inner))
             for name in "qkv"], axis=-1).astype(F32)        # [taps, 3 I]
        dt_bias = own("dt_bias", _dt_bias_init, (inner,)).astype(F32)
        a = jnp.exp(own("A_log", _a_log_init, (h,)).astype(F32))
        o_norm = made(RMSNorm(dtype=cfg.dtype, param_dtype=cfg.param_dtype,
                              epsilon=cfg.norm_eps, name="o_norm"))
        bsz = x.shape[0]

        def before(tail, x):
            """(q, k, v [B, L, H, D] float32, the streams' inputs [B, taps
            - 1 + L, 3 I]) of a block that follows ``tail``."""
            with jax.named_scope(profiling.KDA_PROJ):
                qkv = jnp.concatenate(
                    [proj[name](x) for name in "qkv"], axis=-1)
            with jax.named_scope(profiling.KDA_CONV):
                seq = jnp.concatenate([tail.astype(qkv.dtype), qkv], axis=1)
                n = x.shape[1]
                mixed = sum(seq[:, j:j + n].astype(F32) * conv_w[j]
                            for j in range(taps))
                q, k, v = (y.reshape(bsz, n, h, d) for y in jnp.split(
                    nn.silu(mixed).astype(cfg.dtype), 3, axis=-1))
                return (_l2norm(q) * d ** -0.5, _l2norm(k), v.astype(F32),
                        seq)

        def gates(x):
            """(log decay [B, L, H, D], step size [B, L, H]), float32."""
            with jax.named_scope(profiling.KDA_PROJ):
                f, b = proj["f"](x), proj["b"](x)
            with jax.named_scope(profiling.KDA_GATE):
                f = (f.astype(F32) + dt_bias).reshape(*f.shape[:2], h, d)
                return (cfg.kda_lower_bound * jax.nn.sigmoid(
                    a[:, None] * f), jax.nn.sigmoid(b.astype(F32)))

        def after(o, x):
            with jax.named_scope(profiling.KDA_PROJ):
                gate = proj["g"](x)
            with jax.named_scope(profiling.KDA_OUT):
                y = o_norm(o.astype(cfg.dtype)).astype(F32) * jax.nn.sigmoid(
                    gate.astype(F32).reshape(o.shape))
                y = y.astype(cfg.dtype).reshape(*o.shape[:2], inner)
            with jax.named_scope(profiling.KDA_PROJ):
                return proj["o"](y)

        if cache is not None:
            if x.shape[1] != 1:
                raise NotImplementedError(
                    "a kda layer decodes one position a cache call: a block "
                    "of more (speculative verify, a prefix-attached suffix "
                    "prefill) would need the state taken back past a "
                    "rejected position, which is not built")
            states, tails, _, layer = cache
            q, k, v, seq = before(tails[layer], x)
            g, beta = gates(x)
            with jax.named_scope(profiling.KDA_SCAN):
                o, state = kda_step(q[:, 0], k[:, 0], v[:, 0], g[:, 0],
                                    beta[:, 0], states[layer])
                states = jax.lax.dynamic_update_slice(
                    states, state[None], (layer, 0, 0, 0, 0))
            with jax.named_scope(profiling.KDA_CONV):
                tails = jax.lax.dynamic_update_slice(
                    tails, seq[None, :, 1:].astype(tails.dtype),
                    (layer, 0, 0, 0))
            return after(o[:, None], x), (states, tails)

        def block(carry, x, positions):
            state, tail = carry
            q, k, v, seq = before(tail, x)
            g, beta = gates(x)
            n = x.shape[1]
            count = jnp.full((bsz,), n)
            if lengths is not None:
                # the positions past a row's prompt change no state, and
                # the tail is the one a step at the prompt's end expects
                live = positions < lengths[:, None]
                g = jnp.where(live[..., None, None], g, 0.0)
                beta = jnp.where(live[..., None], beta, 0.0)
                count = jnp.clip(lengths - positions[:, 0], 0, n)
            with jax.named_scope(profiling.KDA_SCAN):
                o, state = kda_chunked(q, k, v, g, beta, state)
            with jax.named_scope(profiling.KDA_CONV):
                tail = jnp.stack([jax.lax.dynamic_slice_in_dim(
                    seq[i], count[i], taps - 1, axis=0)
                    for i in range(bsz)])
            return (state, tail), after(o, x)

        empty = (jnp.zeros((bsz, h, d, d), F32),
                 jnp.zeros((bsz, taps - 1, 3 * inner), cfg.dtype))
        if positions is None:
            positions = jnp.broadcast_to(jnp.arange(x.shape[1])[None],
                                         x.shape[:2])
        kept, out = _over_rows_carrying(block, rows, empty, x, positions)
        return (out, kept) if return_kv else out


def kda_plan(cfg: TransformerConfig) -> dict:
    """What the "kda" layers of ``cfg`` keep and run, from the configuration
    alone (the benchmark's ``kda:`` line)."""
    kinds = cfg.layer_kinds
    inner = cfg.kda_heads * cfg.kda_head_dim
    return {"layers": {kind: kinds.count(kind) for kind in sorted(set(kinds))},
            "chunk": CHUNK,
            "state_bytes_per_layer_and_slot":
                4 * cfg.kda_heads * cfg.kda_head_dim ** 2,
            "conv_tail_bytes_per_layer_and_slot":
                (cfg.kda_conv_width - 1) * 3 * inner
                * jnp.dtype(cfg.dtype).itemsize,
            "form": {"prefill": "chunked", "decode": "step"},
            # what the chunked form runs as at these widths: "kernel", "xla"
            "scan": scan_form(cfg.kda_heads, cfg.kda_head_dim,
                              cfg.kda_head_dim)}
