"""The Mamba-2 sequence mixer (arXiv:2405.21060), the ``"mamba"`` entry of
``TransformerConfig.layer_types``: one input projection into a gate ``z``, the
convolved stream ``xBC`` and a step size per head; a causal depthwise
convolution with bias and silu over ``xBC`` (``ops/causal_conv.py``: read
from the projection's output where it lies, x, B and C written apart as the
scan reads them; two Pallas kernels where the widths meet their rule, ``jnp``
ops elsewhere, by shape alone: ``ssm_plan(...)["conv"]`` says which); the
state-space recurrence per head (``ops/ssd_scan.py``: chunked, as matrix
products; two Pallas kernels where the widths meet their tiling rule, XLA's
ops elsewhere, by shape alone: ``ssm_plan(...)["scan"]`` says which); the gate
and an RMSNorm over all inner channels; the output projection.

Parameters, all the layer's own (transformers' names in brackets, for
``MambaMixer`` of Bamba / GraniteMoeHybrid):

    in_proj/kernel  [E, 2 I + 2 G N + H]   columns z | x | B | C | dt
                                           (``in_proj.weight`` transposed)
    conv_kernel     [K, I + 2 G N]         tap k multiplies position t-(K-1)+k
                                           (``conv1d.weight[c, 0, k]``)
    conv_bias       [I + 2 G N]            (``conv1d.bias``)
    dt_bias, A_log, D   [H]
    norm/scale      [I]                    (``norm.weight``)
    out_proj/kernel [I, E]

with I = heads x head size.  Everything the layer does is under one of four
scopes (``utils/profiling.py``: ``hvd_ssm_proj`` / ``_conv`` / ``_scan`` /
``_gate``), which backward and recomputed ops keep, and the kernels with
them (``hvd_causal_conv_fwd`` / ``_bwd`` under ``hvd_ssm_conv``,
``hvd_ssd_fwd`` / ``hvd_ssd_bwd`` under ``hvd_ssm_scan``).

Not supported yet: decode through the layer (it would carry the conv's last
K-1 inputs and the state [H, P, N] in a cache of their own), and a sequence
sharded over chips (the scan's hand-over would cross them).
"""

from __future__ import annotations

import math

import flax.linen as nn
import jax
import jax.numpy as jnp

from horovod_tpu.models.transformer import RMSNorm, TransformerConfig
from horovod_tpu.ops.causal_conv import causal_conv_silu, conv_form
from horovod_tpu.ops.ssd_scan import (carried_state_bytes, scan_form,
                                      ssd_scan)
from horovod_tpu.utils import profiling


def _uniform(scale):
    def init(key, shape, dtype=jnp.float32):
        return jax.random.uniform(key, shape, dtype, -scale, scale)
    return init


def _a_log_init(key, shape, dtype=jnp.float32):
    """A = exp(A_log) uniform in [1, 16], the paper's."""
    return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0)
                   ).astype(dtype)


def _dt_bias_init(key, shape, dtype=jnp.float32):
    """softplus(dt_bias) log-uniform in [0.001, 0.1], the paper's."""
    dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32,
                                    math.log(1e-3), math.log(1e-1)))
    return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)


class Mamba2Mixer(nn.Module):
    cfg: TransformerConfig

    @nn.compact
    def __call__(self, x, positions=None):
        cfg = self.cfg
        if cfg.context_axis is not None:
            raise NotImplementedError(
                "context parallelism across a scan is not supported yet: a "
                "mamba layer needs its whole sequence on one chip")
        h, p = cfg.mamba_heads, cfg.mamba_head_dim
        g, n = cfg.mamba_groups, cfg.mamba_state_dim
        inner, conv_dim = h * p, h * p + 2 * g * n
        dense = lambda name, width: nn.Dense(  # noqa: E731
            width, use_bias=False, dtype=cfg.dtype,
            param_dtype=cfg.param_dtype, name=name)
        own = lambda name, init, shape: self.param(  # noqa: E731
            name, init, shape, cfg.param_dtype)
        k = cfg.mamba_conv_width
        conv_kernel = own("conv_kernel", _uniform(k ** -0.5), (k, conv_dim))
        conv_bias = own("conv_bias", _uniform(k ** -0.5), (conv_dim,))
        dt_bias = own("dt_bias", _dt_bias_init, (h,))
        a_log = own("A_log", _a_log_init, (h,))
        d_skip = own("D", nn.initializers.ones, (h,))

        with jax.named_scope(profiling.SSM_PROJ):
            stream = dense("in_proj", inner + conv_dim + h)(x)
            z, dt = stream[..., :inner], stream[..., inner + conv_dim:]
        with jax.named_scope(profiling.SSM_CONV):
            xs, b, c = causal_conv_silu(stream, conv_kernel, conv_bias, inner,
                                        _conv_parts(cfg))
        with jax.named_scope(profiling.SSM_SCAN):
            bsz, s = x.shape[:2]
            y = ssd_scan(
                xs.reshape(bsz, s, h, p),
                jax.nn.softplus(dt.astype(jnp.float32)
                                + dt_bias.astype(jnp.float32)),
                -jnp.exp(a_log.astype(jnp.float32)),
                b.reshape(bsz, s, g, n), c.reshape(bsz, s, g, n), d_skip,
                cfg.mamba_chunk).reshape(bsz, s, inner)
        with jax.named_scope(profiling.SSM_GATE):
            # the gate first, then the norm over all inner channels as one
            # group (MambaRMSNormGated with norm_before_gate false)
            y = RMSNorm(dtype=cfg.dtype, param_dtype=cfg.param_dtype,
                        epsilon=cfg.norm_eps, name="norm")(y * nn.silu(z))
        with jax.named_scope(profiling.SSM_PROJ):
            return dense("out_proj", cfg.embed_dim)(y)


def _conv_parts(cfg: TransformerConfig) -> tuple:
    """The widths of x, B and C, side by side in the convolved stream."""
    gn = cfg.mamba_groups * cfg.mamba_state_dim
    return (cfg.mamba_heads * cfg.mamba_head_dim, gn, gn)


def ssm_plan(cfg: TransformerConfig, seq_len: int) -> dict:
    """What the recurrent layers of ``cfg`` do with a sequence of ``seq_len``
    tokens, from the configuration alone (the benchmark's ``ssm:`` line)."""
    kinds = cfg.layer_kinds
    return {"layers": {kind: kinds.count(kind) for kind in sorted(set(kinds))},
            "chunk": cfg.mamba_chunk,
            "chunks_per_sequence": -(-seq_len // min(cfg.mamba_chunk,
                                                     seq_len)),
            "carried_state_bytes_per_layer_and_sequence": carried_state_bytes(
                cfg.mamba_heads, cfg.mamba_head_dim, cfg.mamba_state_dim),
            "scan": scan_form(seq_len, cfg.mamba_chunk, cfg.mamba_heads,
                              cfg.mamba_groups, cfg.mamba_head_dim,
                              cfg.mamba_state_dim),
            "conv": conv_form(seq_len, cfg.mamba_conv_width,
                              cfg.mamba_heads * cfg.mamba_head_dim,
                              _conv_parts(cfg))}
