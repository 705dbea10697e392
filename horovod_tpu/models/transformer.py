"""Decoder-only Transformer — the long-context flagship.

The reference has no attention code at all (SURVEY §2.9: it predates the
technique and scales batch, never sequence).  The task brief makes
long-context first-class, so this model is built for it from the start: the
attention implementation is *pluggable* — dense causal attention by default,
or ring attention over a sequence-parallel mesh axis
(parallel/ring_attention.py) when the sequence dimension is sharded.

TPU-first choices: bf16 compute / f32 params, RMSNorm (one fused rsqrt, no
mean subtraction), rotary position embeddings computed in f32, GLU MLP with
MXU-aligned widths, all shapes static.
"""

from __future__ import annotations

import dataclasses
import functools
import importlib
import math
from typing import Any, Callable

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.experimental.layout import Layout, with_layout_constraint

from horovod_tpu.ops.flash_attention import repeat_kv_heads
from horovod_tpu.utils import profiling


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 32000
    num_layers: int = 12
    num_heads: int = 8
    head_dim: int = 64
    embed_dim: int = 512
    # width of the dense GLU MLP; of ONE expert too when num_experts > 0,
    # unless moe_mlp_dim gives the experts a width of their own
    mlp_dim: int = 2048
    max_seq_len: int = 2048
    dtype: Any = jnp.bfloat16
    # dtype the parameters are STORED in.  float32 (default) casts per use;
    # jnp.bfloat16 makes params bf16-resident — pair with
    # hvd.master_weights(...) so optimizer math keeps an f32 master copy.
    param_dtype: Any = jnp.float32
    # attention_fn(q, k, v, causal) -> out; shapes [B, S, H, D].  None = dense
    # causal attention.  parallel/ring_attention.py provides a drop-in for
    # sequence-sharded q/k/v.  A prefill that says where its prompts end
    # (Transformer(..., return_kv=True, lengths=)) also passes q_len and
    # k_len by name: hvd.make_flash_attention()'s function takes them.
    attention_fn: Callable | None = None
    # Base of the rotary embedding's frequencies.
    rope_theta: float = 10000.0
    # RMSNorm's epsilon, every norm of the model.
    norm_eps: float = 1e-6
    # RMSNorm (with a scale) on q and k before the rotary embedding.  True:
    # over the whole projection, all heads as one, a scale of num_heads *
    # head_dim (OLMoE's q_norm / k_norm).  "head": over each head's head_dim
    # channels, one scale of head_dim shared by the heads (Qwen3's).
    qk_norm: bool | str = False
    # Sparse feed-forward with every expert on each device (models/moe.py):
    # num_experts GLU experts of width mlp_dim replace the dense MLP (0 = the
    # dense MLP), a token visits its experts_per_token most probable ones,
    # nothing is dropped.  norm_topk_prob divides a token's gate weights by
    # their sum.  The layer sows a load-balancing loss and a router z-loss;
    # models.moe_aux_loss(cfg, collections) weights them with the two
    # coefficients for the user's loss to add (docs/parallelism.md).
    num_experts: int = 0
    experts_per_token: int = 1
    norm_topk_prob: bool = False
    moe_load_balance_coef: float = 0.01
    moe_router_z_coef: float = 0.001
    # Switch-MoE feed-forward, the other layout: set to a bound mesh axis
    # name (e.g. "ep") to replace the dense MLP with one expert per device
    # on that axis (top-1, capacity-bounded).  Requires calling inside
    # shard_map; not combined with num_experts.
    moe_axis: str | None = None
    moe_capacity_factor: float = 2.0
    # dtype of the returned logits.  The [B, S, vocab] buffer dominates HBM
    # traffic at large vocab; bfloat16 halves it — upcast inside your loss
    # (the cast fuses into the softmax chain, nothing f32 is materialized).
    logits_dtype: Any = jnp.float32
    # Rematerialize each block in the backward pass (jax.checkpoint):
    # activation memory drops from O(L) layer working sets to one layer +
    # L boundary tensors — the FLOPs-for-HBM trade long-context training
    # needs (S=32K training OOMs 15.75G HBM without it; fits with it).
    # With a context_plan set, the plan's remat decision wins (ring
    # sharding shrinks per-chip activations 1/width, typically dropping
    # full-layer remat — the ~17 MFU points BENCH r5 measured it costing).
    remat: bool = False
    # Context-parallel mesh axis: set (with context_plan) to route
    # attention through the planner-decided ring/zigzag flash path and
    # derive per-shard positions from the layout.  Call inside shard_map
    # over this axis with the sequence dimension sharded; explicit
    # attention_fn/positions win when given.
    context_axis: str | None = None
    # The ContextPlan (ops/schedule_plan.plan_context) that decided the
    # layout, kernel tiles, and remat policy for this model.
    context_plan: Any = None
    # The sequence mixer of each layer, by name: "attention" (Attention),
    # "mamba" (models/mamba.py: Mamba-2), "kda" (models/kda.py) or another
    # key of MIXERS.  None is "attention" num_layers times; otherwise one
    # entry a layer.  A layer type owns its parameters
    # and its sizes below; every layer is followed by the same feed-forward.
    layer_types: tuple | None = None
    # Grouped-query attention: K and V are projected at num_kv_heads heads
    # (None = num_heads) and query head j reads KV head j // group.
    num_kv_heads: int | None = None
    # False: attention sees no positions at all ("nope").
    rotary: bool = True
    # The softmax scale; None is head_dim ** -0.5.
    attention_scale: float | None = None
    # The output head is the embedding transposed: no lm_head parameter.
    tie_embeddings: bool = False
    # h0 = embedding_multiplier * embed(tokens); a layer adds
    # residual_multiplier times what its mixer and its feed-forward give;
    # the logits are divided by logits_scaling.  At 1 none of them is an op.
    embedding_multiplier: float = 1.0
    residual_multiplier: float = 1.0
    logits_scaling: float = 1.0
    # "layer": every norm is a LayerNorm (the mean taken out, a scale and no
    # bias) in place of RMSNorm; norm_eps is its epsilon.
    norm: str = "rms"
    # One norm a layer: the mixer and the feed-forward both read norm(x) and
    # both are added to the residual (x + mixer(h) + ff(h)).  False is the
    # sequential block with a norm before each.
    parallel_block: bool = False
    # "sliding_attention" layers see the last sliding_window positions
    # (query i sees keys i - sliding_window < j <= i) and are rotated;
    # "full_attention" layers see every earlier position and none at all
    # ("nope").  "attention" layers follow ``rotary`` and have no window.
    sliding_window: int | None = None
    # The rotary pairs: False rotates (i, i + d/2) (rotate_half), True the
    # adjacent pair (2i, 2i + 1) ("rope_gptj").
    rope_interleaved: bool = False
    # Sparse feed-forward, further (models/moe.py): how a token's experts
    # are scored ("softmax" over all experts, or "sigmoid" of each logit);
    # num_shared_experts experts of width mlp_dim every token visits, their
    # mean added to the routed sum; experts_held = (lo, hi) says this chip
    # holds routed experts lo..hi-1 of num_experts (expert parallelism's
    # share: the router keeps all num_experts outputs, the layer computes
    # what its own experts give).  None holds them all.
    moe_selection: str = "softmax"
    num_shared_experts: int = 0
    experts_held: tuple | None = None
    # The width of one routed (and of one shared) expert where it is not
    # mlp_dim; the first first_dense_layers layers of a sparse model keep the
    # dense GLU MLP of width mlp_dim; a token's gate weights, after
    # norm_topk_prob, are multiplied by moe_routed_scale.
    moe_mlp_dim: int | None = None
    first_dense_layers: int = 0
    moe_routed_scale: float = 1.0
    # Latent attention ("latent_attention" layers, :class:`LatentAttention`):
    # q through a norm at q_lora_rank, K and V through one at kv_lora_rank
    # beside one rotary key of qk_rope_head_dim for all heads; a head's query
    # and key are qk_nope_head_dim + qk_rope_head_dim wide, its value
    # v_head_dim.  head_dim is not read by such a layer.
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # YaRN on the rotary frequencies (:func:`yarn_frequencies`): (factor,
    # original positions, beta_fast, beta_slow, mscale, mscale_all_dim).
    # Read by latent attention, whose softmax scale it also sets.
    rope_yarn: tuple | None = None
    # A forward pass without a cache (training, a serving prefill) over more
    # positions than this runs each layer's feed-forward, with its norm, a
    # chunk of this many positions at a time: the rows to and from the
    # experts and a wide dense layer's gate and up are then a chunk's, not
    # the sequence's.  Attention sees the whole sequence.  None: in one piece.
    # (A prefill told its prompts' lengths runs a dense feed-forward a row
    # block at a time up to the prompt's end instead: Block, _over_rows.)
    feed_forward_chunk: int | None = None
    # EVA attention ("eva_attention" layers, :class:`EvaAttention`): a query
    # sees the positions of its own window of eva_window exactly and, for
    # every window that is wholly behind it, one learned summary (k̄, v̄) a
    # chunk of eva_chunk positions, under one softmax.  Its cache is a ring
    # of eva_window rows and one row a chunk (:func:`init_kv_cache`).
    eva_window: int = 0
    eva_chunk: int = 0
    # The one lm_head gives num_pred_heads x vocab_size outputs: head i
    # (columns i * vocab_size ...) predicts the token at t + 1 + i.
    num_pred_heads: int = 1
    # A norm's weight is norm_offset + its parameter (1.0: the "unit
    # offset", the parameter then starts at 0).
    norm_offset: float = 0.0
    # dtype of the residual stream and of its adds; the norms take their
    # statistics on it and hand the compute dtype on.  None: the compute
    # dtype, the stream every other field of this class describes.
    residual_dtype: Any = None
    # Mamba-2 sizes ("mamba" layers): heads x head size inner channels,
    # a state of state_dim a channel, B and C shared by heads / groups heads,
    # the causal convolution's taps, the scan's chunk.
    mamba_heads: int = 0
    mamba_head_dim: int = 64
    mamba_state_dim: int = 128
    mamba_groups: int = 1
    mamba_conv_width: int = 4
    mamba_chunk: int = 256
    # Latent attention, further: q_lora_rank 0 projects the queries straight
    # from the stream (no down-projection, no q norm); latent_qk_norm puts an
    # RMSNorm over each head's whole query and one over the rotary key, both
    # before the rotation (the nope part of a key comes from a latent that is
    # normed already); attention_gate "head_wise" multiplies each head's
    # output by the sigmoid of one more projection of the layer's input.
    latent_qk_norm: bool = False
    attention_gate: str | None = None
    # Kimi Delta Attention ("kda" layers, models/kda.py): heads x head size
    # for keys and values alike, the taps of the causal convolution on q, k
    # and v, the lower bound of a step's log decay.  Its cache is a state [heads, head size, head size] float32 and the
    # convolution's last taps - 1 inputs a slot, nothing a position.
    kda_heads: int = 0
    kda_head_dim: int = 128
    kda_conv_width: int = 4
    kda_lower_bound: float = -5.0
    # Sparse feed-forward, the routing of the DeepSeek-V3 family
    # ("noaux_tc"; models/moe.py): moe_expert_bias adds a bias an expert
    # (parameter ``expert_bias``) to the scores that PICK, never to the ones
    # that weigh; moe_groups > 0 limits the picks to the moe_topk_groups
    # groups of num_experts / moe_groups experts whose two best biased
    # scores sum highest.
    moe_expert_bias: bool = False
    moe_groups: int = 0
    moe_topk_groups: int = 0
    # Compressed convolutional attention ("cca" layers, models/cca.py):
    # queries and keys at num_heads / num_kv_heads heads of head_dim, a
    # latent narrower than the stream, through a depthwise and then a
    # grouped causal convolution of cca_taps[0] and cca_taps[1] positions;
    # rotary on the first rotary_fraction of a head's channels (read by
    # "cca" layers alone).  Its cache is K and V rows a position AND, a
    # slot, a tail: the convolutions' and the value shift's last inputs.
    cca_taps: tuple = (2, 2)
    rotary_fraction: float = 1.0
    # Sparse feed-forward, a router that is an MLP with a state (models/
    # moe.py): moe_router_dim > 0 replaces the router's one matrix by r =
    # x W_d + b_d (that wide) plus, from the model's second sparse layer on,
    # a learned vector times the r of the layer before (the state a layer
    # hands the next beside the stream), and the experts' logits by a
    # three-layer GELU MLP on norm(r), all in float32.
    moe_router_dim: int = 0
    # The residual merge, scaled: x <- (x + b_r) * s_r + (f + b_f) * s_f for
    # each sublayer's output f, four learned vectors a sublayer (scales from
    # 1, biases from 0).  Sequential blocks.
    residual_scaling: bool = False
    # A block-causal mask ("attention" layers): position i sees position j
    # iff j < (i // attention_block + 1) * attention_block, every earlier
    # block and the whole of its own (a model that generates by diffusion
    # over blocks; docs/inference.md).  None: the causal mask.
    # mask_token_id is the id such a model reads at a position not yet made
    # final; a serving backend never samples it.
    attention_block: int | None = None
    mask_token_id: int | None = None
    # Manifold-constrained hyper-connections (models/hyper.py): the residual
    # stream of a position is hyper_streams rows of embed_dim ([B, S, n, C]
    # from the embedding, copied into every row, to the rows' sum before the
    # final norm) and each sublayer reads one mix of the rows and writes into
    # another, under coefficients made from the stream: sigmoids, and for
    # the rows' own mix exp of logits clipped to hyper_res_clamp (lo, hi),
    # then columns and rows divided by their sums (+ hyper_eps, which is
    # also the flat norm's) hyper_sinkhorn_iters times.  0: one vector a
    # position, the stream every other field of this class describes.
    hyper_streams: int = 0
    hyper_sinkhorn_iters: int = 20
    hyper_eps: float = 1e-6
    hyper_res_clamp: tuple = (-30.0, 30.0)

    @classmethod
    def from_dict(cls, fields: dict) -> "TransformerConfig":
        """A configuration from plain data (a JSON file's object): lists
        become tuples and the three dtypes may be names ("bfloat16").  A key
        that is no field is an error that names it."""
        known = {f.name for f in dataclasses.fields(cls)}
        unknown = sorted(set(fields) - known)
        if unknown:
            raise ValueError(f"TransformerConfig has no field {unknown}")
        out = {k: tuple(v) if isinstance(v, list) else v
               for k, v in fields.items()}
        for k in ("dtype", "param_dtype", "logits_dtype", "residual_dtype"):
            if isinstance(out.get(k), str):
                out[k] = jnp.dtype(out[k]).type
        return cls(**out)

    @property
    def layer_kinds(self) -> tuple:
        """One mixer name a layer."""
        if self.layer_types is None:
            return ("attention",) * self.num_layers
        if len(self.layer_types) != self.num_layers:
            raise ValueError(f"layer_types names {len(self.layer_types)} "
                             f"layers, num_layers is {self.num_layers}")
        return tuple(self.layer_types)

    @property
    def kv_heads(self) -> int:
        return self.num_kv_heads or self.num_heads

    @property
    def latent(self) -> bool:
        """Every layer is latent attention: the cache is one of latents."""
        kinds = set(self.layer_kinds)
        others = kinds - {"latent_attention", "kda"}
        if "latent_attention" in kinds and others:
            raise NotImplementedError(
                f"latent attention beside {sorted(others)}"
                f" layers: the cache pool has one shape for all layers")
        return kinds == {"latent_attention"}

    @property
    def cache_layout(self) -> tuple | None:
        """For a model with "kda" or "cca" layers: ``(cache kind, index
        among the layers of that kind)`` a layer.  Its pool is not two
        like-shaped arrays but two trees with an entry a kind
        (:func:`init_kv_cache`): a state a slot for the "kda" layers beside
        a row a position for the others, each stacked over ITS layers alone.
        A "cca" layer is of two kinds at once (:data:`CACHE_PARTS`), rows a
        position and a tail a slot, and every layer of its model is one.
        None for every other model: one kind, two arrays, indexed by the
        layer."""
        kinds = self.layer_kinds
        if "cca" in kinds:
            if set(kinds) != {"cca"}:
                raise NotImplementedError(
                    f"cca layers beside {sorted(set(kinds) - {'cca'})}: a "
                    f"pool of rows and tails is built for a model whose "
                    f"every layer is cca; no configuration mixes them")
            return tuple(("cca", i) for i in range(len(kinds)))
        if "kda" not in kinds:
            return None
        cached = {CACHE_KINDS.get(kind) for kind in kinds}
        if None in cached or "eva" in cached or len(cached) > 2:
            raise NotImplementedError(
                f"kda layers beside {sorted(set(kinds) - {'kda'})}: a pool of "
                f"two kinds holds kda states beside keys and values or "
                f"beside latents")
        seen: dict = {}
        layout = []
        for kind in kinds:
            kind = CACHE_KINDS[kind]
            layout.append((kind, seen.get(kind, 0)))
            seen[kind] = layout[-1][1] + 1
        return tuple(layout)

    @property
    def eva(self) -> bool:
        """Every layer is EVA attention: the cache is a ring and summaries."""
        kinds = set(self.layer_kinds)
        if "eva_attention" in kinds and len(kinds) > 1:
            raise NotImplementedError(
                f"EVA attention beside {sorted(kinds - {'eva_attention'})} "
                f"layers: the cache pool has one shape for all layers")
        return kinds == {"eva_attention"}


# what a layer type keeps in the cache pool; a type that is not here (a
# "mamba" layer) serves from no cache
CACHE_KINDS = {"attention": "kv", "sliding_attention": "kv",
               "full_attention": "kv", "latent_attention": "latent",
               "eva_attention": "eva", "kda": "kda", "cca": "cca"}
# the entries of the pool's two trees that make up a layer's cache where
# they are more than the kind's own one
CACHE_PARTS = {"cca": ("cca", "cca_tail")}


def _own_cache(tree: dict, kind: str):
    """A layer's view of one tree of a pool of kinds: its kind's array, or
    for a kind of several parts (:data:`CACHE_PARTS`) a dict of them."""
    if kind not in CACHE_PARTS:
        return tree[kind]
    return {part: tree[part] for part in CACHE_PARTS[kind]}


def _with_cache(tree: dict, kind: str, new) -> dict:
    """``tree`` with a layer's view of it (:func:`_own_cache`) replaced."""
    return {**tree, **(new if kind in CACHE_PARTS else {kind: new})}


def _norm_scale(norm, width: int):
    """A norm's ``scale`` parameter: it starts at 1, or where the norm's
    weight is ``offset`` + the parameter at 1 - offset."""
    start = nn.initializers.constant(1.0 - norm.offset) if norm.offset \
        else nn.initializers.ones
    return norm.param("scale", start, (width,), norm.param_dtype)


def _norm_weight(norm, scale):
    """A norm's float32 weight: the parameter, plus ``offset`` if any."""
    scale = scale.astype(jnp.float32)
    return scale + norm.offset if norm.offset else scale


class RMSNorm(nn.Module):
    """RMSNorm over the last axis with one ``scale`` vector (``nn.RMSNorm``'s
    parameter structure): statistics in f32, output in ``dtype``.  Plain
    ``jnp`` on purpose -- XLA fuses the norm with its neighbours (residual
    add, casts, matmul epilogues), which a kernel's boundary would undo."""

    dtype: Any = jnp.float32
    param_dtype: Any = jnp.float32
    epsilon: float = 1e-6
    offset: float = 0.0

    @nn.compact
    def __call__(self, x):
        scale = _norm_scale(self, x.shape[-1])
        x = x.astype(self.dtype)
        xf = x.astype(jnp.float32)
        inv = jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True)
                            + self.epsilon)
        return (xf * inv * _norm_weight(self, scale)).astype(x.dtype)


class LayerNorm(nn.Module):
    """LayerNorm over the last axis with one ``scale`` vector and no bias:
    the mean taken out, then :class:`RMSNorm`'s arithmetic on what is left
    (statistics in f32, output in ``dtype``)."""

    dtype: Any = jnp.float32
    param_dtype: Any = jnp.float32
    epsilon: float = 1e-5
    offset: float = 0.0

    @nn.compact
    def __call__(self, x):
        scale = _norm_scale(self, x.shape[-1])
        x = x.astype(self.dtype)
        xf = x.astype(jnp.float32)
        xf = xf - jnp.mean(xf, axis=-1, keepdims=True)
        inv = jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True)
                            + self.epsilon)
        return (xf * inv * _norm_weight(self, scale)).astype(x.dtype)


NORMS = {"rms": RMSNorm, "layer": LayerNorm}


def _as_is(module):
    return module


@functools.partial(jax.jit, static_argnums=(0, 1))
def _applied(free, told, variables, *args, **kwargs):
    return free.apply(variables, *args, **dict(told), **kwargs)


def _unbound(module, nameless: bool = False, **told):
    """A bound flax ``module`` whose parameters are made, as a function of
    its inputs alone (and of ``told``, what else it is always called with):
    for the body of a ``lax`` loop, where flax lets no bound module be
    called.  The same parameters under the same names.  Applied under one
    ``jax.jit`` with the module static, so that a program's layers (alike
    but for their parameters, and ``nameless`` for their names) share one
    tracing of it: a served program is traced and lowered on every start
    (PERF.md section 6, PR 41 and PR 47)."""
    free = module.clone(parent=None, **({"name": None} if nameless else {}))
    return functools.partial(_applied, free, tuple(sorted(told.items())),
                             module.variables)


def make_norm(cfg: "TransformerConfig", name: str, made=_as_is):
    """The model's norm (``cfg.norm``) under ``name``.  Over a residual
    stream of its own dtype (``cfg.residual_dtype``) the statistics are
    taken in that dtype and the result handed on in the compute dtype.
    ``made`` is what the module is called through (:func:`_unbound`, for a
    norm inside a loop's body)."""
    try:
        cls = NORMS[cfg.norm]
    except KeyError:
        raise ValueError(f"norm {cfg.norm!r}; models/transformer.py has "
                         f"{sorted(NORMS)}") from None
    told = {"offset": cfg.norm_offset} if cfg.norm_offset else {}
    norm = made(cls(dtype=cfg.residual_dtype or cfg.dtype,
                    param_dtype=cfg.param_dtype, epsilon=cfg.norm_eps,
                    name=name, **told))
    if cfg.residual_dtype is None:
        return norm
    return lambda x: norm(x).astype(cfg.dtype)


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention factor: 0.1 mscale ln(factor) + 1 past factor 1."""
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def yarn_frequencies(d: int, theta: float, yarn: tuple):
    """([d/2] float32 rotary frequencies, what cos and sin are multiplied
    by) under YaRN, ``yarn`` as ``TransformerConfig.rope_yarn``.  Pair i's
    frequency is theta^(-2i/d) where the pair turns more than beta_fast
    times over the original positions, that over ``factor`` where it turns
    fewer than beta_slow times, and between the two correction dimensions
    their blend by a linear ramp."""
    factor, original, beta_fast, beta_slow, mscale, mscale_all_dim = yarn

    def correction_dim(turns):      # the pair that turns this many times
        return d * math.log(original / (turns * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(correction_dim(beta_fast)), 0)
    high = min(math.ceil(correction_dim(beta_slow)), d - 1)
    ramp = jnp.clip((jnp.arange(d // 2, dtype=jnp.float32) - low)
                    / max(high - low, 1e-3), 0.0, 1.0)
    freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    return (freq / factor * ramp + freq * (1.0 - ramp),
            yarn_mscale(factor, mscale) / yarn_mscale(factor, mscale_all_dim))


def rope(x, positions, theta: float, interleaved: bool = False,
         yarn: tuple | None = None):
    """Rotary embeddings; x: [B, S, H, D], positions: [B, S] (f32 math).
    Pair i is (x[i], x[i + D/2]), or with ``interleaved`` the adjacent
    (x[2i], x[2i + 1]); the same angle either way.  ``yarn``
    (``TransformerConfig.rope_yarn``) stretches the frequencies
    (:func:`yarn_frequencies`)."""
    d = x.shape[-1]
    freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    amplitude = 1.0
    if yarn is not None:
        freq, amplitude = yarn_frequencies(d, theta, yarn)
    angles = positions[..., None].astype(jnp.float32) * freq  # [B, S, d/2]
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    if amplitude != 1.0:
        cos, sin = cos * amplitude, sin * amplitude
    if interleaved:
        xf = x.astype(jnp.float32).reshape(*x.shape[:-1], d // 2, 2)
        x1, x2 = xf[..., 0], xf[..., 1]
        out = jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
        return out.reshape(x.shape).astype(x.dtype)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def dense_causal_attention(q, k, v, causal: bool = True,
                           scale: float | None = None,
                           window: int | None = None,
                           block: int | None = None):
    """Reference attention: one softmax(QKᵀ)V, causal-masked. [B, S, H, D];
    k and v may have fewer heads (grouped-query).  With ``window`` (causal
    only) query i sees keys i - window < j <= i.  With ``block`` (causal
    only, no window) the mask is block-causal: query i sees every key of
    its own block of ``block`` positions and of the blocks before it."""
    scale = q.shape[-1] ** -0.5 if scale is None else scale
    k, v = repeat_kv_heads(k, q.shape[2]), repeat_kv_heads(v, q.shape[2])
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    if window is not None and not causal:
        raise ValueError("a sliding window is a causal band: causal=True")
    if block is not None and (window is not None or not causal):
        raise ValueError("a block-causal mask is causal and has no window")
    if block is not None:
        s_q, s_k = logits.shape[-2], logits.shape[-1]
        q_pos = jnp.arange(s_q)[:, None] + (s_k - s_q)
        mask = jnp.arange(s_k)[None, :] < (q_pos // block + 1) * block
        logits = jnp.where(mask, logits, -1e30)
    elif causal:
        s_q, s_k = logits.shape[-2], logits.shape[-1]
        mask = jnp.tril(jnp.ones((s_q, s_k), dtype=bool), k=s_k - s_q)
        if window is not None:
            mask &= ~jnp.tril(jnp.ones((s_q, s_k), dtype=bool),
                              k=s_k - s_q - window)
        logits = jnp.where(mask, logits, -1e30)
    probs = nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def init_kv_cache(cfg: TransformerConfig, num_slots: int,
                  max_len: int | None = None):
    """Preallocated per-slot K/V cache for incremental decode
    (docs/inference.md "Serving loop"): two arrays in the compute dtype
    whose shape the model gives: keys and values ``[L, slots, S, H, D]``
    (for a block model, ``cfg.attention_block``, the same bytes as ROWS,
    ``[L, slots, S, H D]``: its every cache call brings block x heads query
    rows a slot, and the products read a layer's view as it lies,
    :func:`rows_decode_attention`; :func:`kv_pool_form` names the form),
    or for a model of latent attention the latents ``[L, slots, S,
    kv_lora_rank]`` and the one rotary key of all heads ``[L, slots, S,
    qk_rope_head_dim]`` (what a cache call writes and reads as they lie;
    K and V are never stored), or for a model of EVA attention K-side and
    V-side rows ``[L, slots, eva_window + S / eva_chunk, H, D]``: a ring of
    the current window's exact keys and values, then one summary a chunk.
    A model with "kda" layers (``cfg.cache_layout``) gets two TREES in
    their place, an entry a cache kind: ``{"kda": states [Lk, slots, H, D,
    D] float32, "latent": latents [Ll, slots, S, rank]}`` and ``{"kda": the
    convolutions' last inputs [Lk, slots, taps - 1, 3 H D], "latent":
    rotary keys [Ll, slots, S, rope]}`` (``"kv"`` keys and values where its
    other layers are plain attention).  So does one with "cca" layers
    (models/cca.py), each of which keeps two kinds: ``{"cca": keys [Lc,
    slots, S, KV D], "cca_tail": the two convolutions' last inputs [Lc,
    slots, taps0 - 1 + taps1 - 1, (H + KV) D] float32}`` and ``{"cca":
    values, "cca_tail": the value shift's last input [Lc, slots, 1, KV D /
    2] float32}``.
    One slot is one serving sequence — the
    continuous-batching scheduler (serving/engine.py) admits a request
    into a free slot (prefill writes positions ``0..len``) and decode
    appends one position per step, so the buffer is allocated once and
    the jitted programs never see a shape change.  A cache call
    (``Transformer.__call__(kv_cache=...)``) hands these two arrays through
    its layers whole and writes the new positions into them
    (:func:`write_kv_block`); a caller that donates them to its jitted
    program (``donate_argnums``) has them updated where they lie, one that
    does not pays a copy of both a call."""
    layout = cfg.cache_layout
    lead = (num_slots, max_len or cfg.max_seq_len)
    if "cca" in cfg.layer_kinds:
        # every layer is cca: K and V rows a position, a row its KV heads
        # side by side (a decode step's products read it as it lies,
        # rows_decode_attention), and a slot's tail in float32 (a
        # decode step convolves over it what a prefill convolved over the
        # positions themselves)
        rows = (len(layout),) + lead + (cfg.kv_heads * cfg.head_dim,)
        return ({"cca": jnp.zeros(rows, cfg.dtype),
                 "cca_tail": jnp.zeros(
                     (len(layout), num_slots, sum(cfg.cca_taps) - 2,
                      (cfg.num_heads + cfg.kv_heads) * cfg.head_dim),
                     jnp.float32)},
                {"cca": jnp.zeros(rows, cfg.dtype),
                 "cca_tail": jnp.zeros(
                     (len(layout), num_slots, 1,
                      cfg.kv_heads * cfg.head_dim // 2), jnp.float32)})
    if layout is not None:
        # a model with "kda" layers: two trees with an entry a cache kind,
        # each stacked over the layers of that kind alone.  The first holds
        # each kda layer's state a slot (float32: it is summed into over
        # every position) and the second the last inputs of its causal
        # convolution; beside them the other layers' rows a position.
        count = lambda kind: sum(k == kind for k, _ in layout)  # noqa: E731
        inner = cfg.kda_heads * cfg.kda_head_dim
        first = {"kda": jnp.zeros(
            (count("kda"), num_slots, cfg.kda_heads, cfg.kda_head_dim,
             cfg.kda_head_dim), jnp.float32)}
        second = {"kda": jnp.zeros(
            (count("kda"), num_slots, cfg.kda_conv_width - 1, 3 * inner),
            cfg.dtype)}
        if count("latent"):
            first["latent"] = jnp.zeros(
                (count("latent"),) + lead + (cfg.kv_lora_rank,), cfg.dtype)
            second["latent"] = jnp.zeros(
                (count("latent"),) + lead + (cfg.qk_rope_head_dim,),
                cfg.dtype)
        if count("kv"):
            shape = (count("kv"),) + lead + (cfg.kv_heads, cfg.head_dim)
            first["kv"] = jnp.zeros(shape, cfg.dtype)
            second["kv"] = jnp.zeros(shape, cfg.dtype)
        return first, second
    if cfg.eva:
        # a slot holds, a layer, a ring of the window's exact rows and then
        # one summary row a chunk of the positions it may reach: not a row
        # a position (:class:`EvaAttention`)
        rows = cfg.eva_window + -(-(max_len or cfg.max_seq_len)
                                  // cfg.eva_chunk)
        shape = (cfg.num_layers, num_slots, rows, cfg.kv_heads, cfg.head_dim)
        return jnp.zeros(shape, cfg.dtype), jnp.zeros(shape, cfg.dtype)
    lead = (cfg.num_layers, num_slots, max_len or cfg.max_seq_len)
    if cfg.latent:
        return (jnp.zeros(lead + (cfg.kv_lora_rank,), cfg.dtype),
                jnp.zeros(lead + (cfg.qk_rope_head_dim,), cfg.dtype))
    if cfg.attention_block:
        # a block model's pass brings block x heads query rows a slot: its
        # pool is rows, a row its KV heads side by side, and every cache
        # call reads a layer's view as it lies (:func:`rows_decode_attention`)
        shape = lead + (cfg.kv_heads * cfg.head_dim,)
    else:
        shape = lead + (cfg.kv_heads, cfg.head_dim)
    return jnp.zeros(shape, cfg.dtype), jnp.zeros(shape, cfg.dtype)


def kv_pool_form(cfg: TransformerConfig) -> str:
    """How a cached position lies in :func:`init_kv_cache`'s pool: ``"rows"``
    where its keys and values are one row of KV D channels that a cache call
    reads as it lies (:func:`rows_decode_attention`: a block model's
    attention layers, "cca" layers), ``"heads"`` where they are ``[KV, D]``
    and read through the grouped products
    (:func:`cached_decode_attention`), ``"latents"`` for a model that caches
    no keys or values at all."""
    kept = {CACHE_KINDS.get(kind) for kind in cfg.layer_kinds}
    if "cca" in kept or (cfg.attention_block and "kv" in kept):
        return "rows"
    return "heads" if kept & {"kv", "eva"} else "latents"


def init_kv_pages(cfg: TransformerConfig, num_pages: int, page_size: int):
    """Content-addressed KV page pool for the shared-prefix cache
    (serving/prefix_cache.py): two ``[L, pages, page_size, H, D]`` arrays.
    Unlike :func:`init_kv_cache`, positions are not owned by a slot — a
    slot is a row of page ids (its page table) and a page holding a
    shared prompt-prefix chunk can appear in many slots' rows at once.
    Page 0 is the scratch page inactive slots point at."""
    if cfg.attention_block:
        raise NotImplementedError(
            "a paged pool for a model of a block-causal mask "
            "(attention_block; init_kv_pages, PagedTransformerBackend, the "
            "prefix cache) is not built: a block is overwritten where it "
            "lies until its commit, so a shared page would need the block's "
            "boundary and its commit; such a model serves from "
            "init_kv_cache's pool")
    if "cca" in cfg.layer_kinds:
        raise NotImplementedError(
            "a paged pool beside a cca layer's tail (init_kv_pages, "
            "PagedTransformerBackend, the prefix cache) is not built: its "
            "rows could lie in pages, but a shared prefix would need the "
            "convolutions' and the value shift's tail at its end; a model "
            "with cca layers serves from init_kv_cache's pool")
    if "kda" in cfg.layer_kinds:
        raise NotImplementedError(
            "a paged pool beside a kda layer's recurrent state "
            "(init_kv_pages, PagedTransformerBackend, the prefix cache) is "
            "not built: a page of positions is no unit of a state, and a "
            "shared prefix would need the state at its end; a model with "
            "kda layers serves from init_kv_cache's pool")
    if cfg.latent:
        raise NotImplementedError(
            "a paged pool of latents (init_kv_pages, "
            "PagedTransformerBackend) is not built: latent attention "
            "serves from init_kv_cache's dense pool")
    if cfg.eva:
        raise NotImplementedError(
            "a paged pool of EVA attention's ring and summaries "
            "(init_kv_pages, PagedTransformerBackend) is not built: a page "
            "of positions is no unit of that cache; it serves from "
            "init_kv_cache's pool")
    shape = (cfg.num_layers, num_pages, page_size, cfg.kv_heads,
             cfg.head_dim)
    return jnp.zeros(shape, cfg.dtype), jnp.zeros(shape, cfg.dtype)


def write_kv_block(pool, block, layer: int, lengths):
    """``pool`` [L, B, S, ...] with ``block`` [B, S_q, ...] written at
    ``(layer, b, lengths[b])`` (``...`` is H, D for keys and values, one
    width for latents): one ``dynamic_update_slice`` a slot, each of the
    block's own bytes.  Nothing else of the pool is read or written, so
    where the caller donated the pool XLA updates it where it lies.

    The pool keeps the layout it came in.  Left to itself XLA:TPU lays a
    grouped-query pool out [L, B, H, S, D] for the attention products and
    copies all of it in and out again every call, more than the stack of
    updated slices this replaces (19.3 ms a decode step of
    ``cmdaplus-code8k-open``'s program against 19.4 before and 16.0 with
    the layout held; PERF.md section 6, PR 38).

    The sequel (PR 62): held row-major, a ``[.., S, KV, D]`` pool still has
    each layer's VIEW copied out head-major before the grouped products
    (:func:`cached_decode_attention`; 12 copies of 264 MB a pass of
    ``sdar30b-chat4k-open``, 9.6 of 29.4 ms).  A pool of ROWS ``[.., S, KV
    D]`` (:func:`init_kv_cache`, a block model's; a "cca" layer's) is
    written here just the same, a block reshaped to rows for free, and read
    by :func:`rows_decode_attention` with nothing copied."""
    for b in range(block.shape[0]):
        pool = jax.lax.dynamic_update_slice(
            pool, block[b][None, None],
            (layer, b, lengths[b]) + (0,) * (pool.ndim - 3))
    return with_layout_constraint(
        pool, Layout(major_to_minor=tuple(range(pool.ndim))))


def _cache_mask(lengths, s_q: int, s: int, window, block):
    """Which of a slot's ``s`` cached positions each of a cache call's
    ``s_q`` query rows sees, [B, 1, S_q, S]: row ``i`` sits at position
    ``lengths[b] + i`` and sees the cache up to its own position; with
    ``window`` positions p - window < j <= p alone; with ``block`` up to the
    end of its own block of ``block`` positions (the block-causal mask: the
    rows of one block see each other whole)."""
    qpos = lengths[:, None] + jnp.arange(s_q)[None, :]         # [B, S_q]
    if block is not None:
        if window is not None:
            raise ValueError("a block-causal mask has no window")
        qpos = (qpos // block + 1) * block - 1      # its block's last position
    mask = (jnp.arange(s)[None, None, :]
            <= qpos[:, :, None])[:, None, :, :]                # [B,1,S_q,S]
    if window is not None:
        mask &= (jnp.arange(s)[None, None, :]
                 > qpos[:, :, None] - window)[:, None, :, :]
    return mask


def as_pool_rows(block, pool):
    """A prefill's cache block ``[.., S, KV, D]`` as ``pool`` holds a
    position: itself where the ranks agree, rows ``[.., S, KV D]`` for a
    pool of rows (:func:`init_kv_cache`, a block model's), a free reshape."""
    if block.ndim == pool.ndim:
        return block
    return block.reshape(block.shape[:pool.ndim - 1] + (-1,))


def cached_decode_attention(q, k_cache, v_cache, lengths,
                            scale: float | None = None,
                            window: int | None = None,
                            block: int | None = None):
    """Block attention over a per-slot KV cache.

    ``q``: [B, S_q, H, D] — the block of positions being decoded per
    slot: one position for plain decode, the speculative draft window
    for batched verification, or a prompt suffix for prefix-attached
    prefill.  ``k_cache``/``v_cache``: [B, S, H, D] with query row ``i``
    sitting at position ``lengths[b] + i`` (``lengths[b]`` is the first
    position of the block, just written), everything past each row's own
    position masked causally.  Same f32-softmax/-1e30-mask arithmetic as
    :func:`dense_causal_attention`, so an incrementally decoded position
    matches the full forward pass.  The caches may hold fewer heads than
    ``q`` (grouped-query).  With ``window`` a row at position p sees cache
    positions p - window < j <= p alone.  With ``block`` a row sees the
    cache up to the end of its own block of ``block`` positions (the
    block-causal mask: the rows of one block see each other whole)."""
    scale = q.shape[-1] ** -0.5 if scale is None else scale
    mask = _cache_mask(lengths, q.shape[1], k_cache.shape[1], window, block)
    # The query heads of a group ride one axis beside their KV head, so a
    # grouped-query cache is read once as it lies.  Repeated to the query
    # heads it would be written and read again every step, group times the
    # cache's bytes (16 x at 128 query to 8 KV heads).
    heads, kv_heads = q.shape[2], k_cache.shape[2]
    if heads % kv_heads:
        raise ValueError(f"{kv_heads} KV heads do not divide {heads} "
                         f"query heads")
    grouped = q.reshape(*q.shape[:2], kv_heads, heads // kv_heads,
                        q.shape[-1])
    logits = jnp.einsum("bqhgd,bkhd->bhgqk", grouped, k_cache).astype(
        jnp.float32) * scale
    logits = jnp.where(mask[:, :, None], logits, -1e30)
    probs = nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bhgqk,bkhd->bqhgd", probs, v_cache).reshape(q.shape)


def rows_decode_attention(q, k_rows, v_rows, lengths,
                          scale: float | None = None,
                          window: int | None = None,
                          block: int | None = None):
    """:func:`cached_decode_attention` over a slot's cached rows AS THEY
    LIE: ``q`` [B, S_q, H, D] at positions ``lengths[b] + i``, ``k_rows`` /
    ``v_rows`` [B, S, KV D], a row its KV heads side by side.  A query head's
    vector is laid into its KV head's D channels of a row-wide vector of
    zeros, so the scores of all heads are ONE product over the row's whole
    width and the weighted sum one more, whose output keeps each head's own
    D channels: KV times the operations of the grouped products (a cache
    call is bound by the rows' bytes), the same numbers, and no operand
    re-laid head-major: a pool kept ``[.., S, KV, D]`` and read through a
    product batched over the KV heads has each layer's view copied out
    first (PERF.md section 6, PR 52 and PR 62).  The same mask
    (:func:`_cache_mask`: ``window``, ``block``) and arithmetic: float32
    scores and softmax, -1e30 behind the mask."""
    b, s_q, h, d = q.shape
    s, kv = k_rows.shape[1], k_rows.shape[2] // d
    scale = d ** -0.5 if scale is None else scale
    own = jnp.eye(kv, dtype=q.dtype)        # [a query's KV head, a slot]
    wide = jnp.einsum("bqhgd,hj->bqhgjd", q.reshape(b, s_q, kv, h // kv, d),
                      own).reshape(b, s_q, h, kv * d)
    mask = _cache_mask(lengths, s_q, s, window, block)
    logits = jnp.einsum("bqnf,bkf->bnqk", wide, k_rows).astype(
        jnp.float32) * scale
    probs = nn.softmax(jnp.where(mask, logits, -1e30), axis=-1).astype(
        q.dtype)
    out = jnp.einsum("bnqk,bkf->bqnf", probs, v_rows)   # [B, S_q, H, KV D]
    return jnp.einsum("bqhgjd,hj->bqhgd",
                      out.reshape(b, s_q, kv, h // kv, kv, d),
                      own.astype(out.dtype)).reshape(b, s_q, h, d)


class Attention(nn.Module):
    """Causal self-attention.  ``layer_type`` decides positions and mask:
    "attention" follows ``cfg.rotary`` and sees every earlier position;
    "sliding_attention" is rotated and sees ``cfg.sliding_window``
    positions; "full_attention" is not rotated and sees them all."""

    cfg: TransformerConfig
    layer_type: str = "attention"

    @nn.compact
    def __call__(self, x, positions, cache=None, return_kv=False,
                 lengths=None):
        cfg = self.cfg
        rotary = {"attention": cfg.rotary, "sliding_attention": True,
                  "full_attention": False}[self.layer_type]
        window = None
        if self.layer_type == "sliding_attention":
            if not cfg.sliding_window:
                raise ValueError("a sliding_attention layer needs "
                                 "TransformerConfig.sliding_window")
            window = int(cfg.sliding_window)
        # a served prefill that said where its prompt ends runs the two
        # position-wise sides of the layer over the prompt's row blocks
        rows, made = _prompt_rows(x, cache, return_kv, lengths)
        proj = {name: made(nn.DenseGeneral(
            (heads, cfg.head_dim), use_bias=False, dtype=cfg.dtype,
            param_dtype=cfg.param_dtype, name=name))
            for name, heads in (("q", cfg.num_heads), ("k", cfg.kv_heads),
                                ("v", cfg.kv_heads))}
        if cfg.qk_norm not in (False, True, "head"):
            raise ValueError(f"qk_norm is False, True (the whole projection) "
                             f"or \"head\"; got {cfg.qk_norm!r}")
        # True: over the whole projection, all heads as one; "head": over
        # each head's own channels, one scale for all heads
        qk_norm = {name: made(RMSNorm(
            dtype=cfg.dtype, param_dtype=cfg.param_dtype,
            epsilon=cfg.norm_eps, name=f"{name}_norm"))
            for name in ("q", "k")} if cfg.qk_norm else {}

        def heads(x, positions):
            def rotated(name):
                y = proj[name](x)
                if cfg.qk_norm == "head":
                    y = qk_norm[name](y)
                elif cfg.qk_norm:
                    y = qk_norm[name](
                        y.reshape(*y.shape[:-2], -1)).reshape(y.shape)
                if not rotary:
                    return y
                return rope(y, positions, cfg.rope_theta,
                            interleaved=cfg.rope_interleaved)

            return rotated("q"), rotated("k"), proj["v"](x)

        q, k, v = _over_rows(heads, rows, x, positions)
        # a caller's softmax scale and a layer's window go to the attention
        # function by name; without them the call is what it always was
        told = ({} if cfg.attention_scale is None
                else {"scale": cfg.attention_scale})
        if window is not None:
            told["window"] = window
        if cfg.attention_block:
            if self.layer_type != "attention":
                raise NotImplementedError(
                    f"attention_block beside a {self.layer_type} layer: the "
                    f"block-causal mask is built for \"attention\" layers")
            told["block"] = int(cfg.attention_block)
        o_proj = made(nn.DenseGeneral(
            cfg.embed_dim, axis=(-2, -1), use_bias=False, dtype=cfg.dtype,
            param_dtype=cfg.param_dtype, name="o"))
        if cache is not None:
            # Incremental decode: x is [B, S_q, E]; write the block's K/V
            # into the whole pool at (this layer, slot, its current length),
            # attend over this layer's view of the pool, hand the pool on.
            # K/V at a position depend only on that position's token and
            # rotary phase, so cached entries match what a full forward pass
            # would compute there.
            # The pool it was handed says how: rows [L, B, S, KV D] (a block
            # model's, init_kv_cache) are written as rows, a free reshape,
            # and read as they lie; [L, B, S, KV, D] (every plain decode, the
            # paged backend's gathered arrays) through the grouped products.
            k_pool, v_pool, lengths, layer = cache
            attend = cached_decode_attention
            if k_pool.ndim == 4:
                attend = rows_decode_attention
                k, v = (y.reshape(*y.shape[:2], -1) for y in (k, v))
            k_pool = write_kv_block(k_pool, k, layer, lengths)
            v_pool = write_kv_block(v_pool, v, layer, lengths)
            out = attend(q, k_pool[layer], v_pool[layer], lengths, **told)
            return o_proj(out), (k_pool, v_pool)
        attn = cfg.attention_fn
        if attn is None and cfg.context_axis and cfg.context_plan is not None:
            if cfg.kv_heads != cfg.num_heads or told:
                raise NotImplementedError(
                    "ring / zigzag attention over a context axis takes as "
                    "many KV heads as query heads, the d^-1/2 scale and no "
                    "sliding window")
            from horovod_tpu.parallel.context import context_attention_fn

            attn = context_attention_fn(cfg.context_axis, cfg.context_plan)
        told.update(_prompt_end(cfg, lengths))
        attn = attn or dense_causal_attention
        out = _over_rows(o_proj, rows, attn(q, k, v, causal=True, **told))
        return (out, (k, v)) if return_kv else out


def _prompt_end(cfg: TransformerConfig, lengths) -> dict:
    """What a prefill that said where its prompts end (``lengths`` [B]
    beside ``return_kv``) tells the model's own attention function: that
    many rows and keys count (``ops/flash_attention`` then runs no tile of
    the bucket's padding).  Nothing for the dense default, which computes
    whole arrays whatever it is told."""
    if lengths is None or cfg.attention_fn is None:
        return {}
    end = jnp.max(lengths)      # one bound a call: the longest row's
    if cfg.attention_block:
        # a row sees its block whole: the keys count to the block's end
        end = -(-end // cfg.attention_block) * cfg.attention_block
    return {"q_len": end, "k_len": end}


class LatentAttention(nn.Module):
    """Causal latent attention (MLA), two forms of one parameter tree.

    Down: ``c_q = norm(x W_DQ)`` [q_lora_rank]; ``x W_DKV`` is a latent
    ``c_kv = norm(.)`` [kv_lora_rank] beside ONE rotary key ``k_rope``
    [qk_rope_head_dim] that all heads share.  A head's query is ``c_q
    W_UQ`` = (nope | rope, the rope part rotated); ``W_UKV`` = [W_UK | W_UV]
    by head takes the latent to a head's key (nope) and value.

    *Expanded* (no cache: training, a serving prefill): K = [c_kv W_UK |
    k_rope for every head] and V = c_kv W_UV are built for the whole
    sequence and go to the attention function with keys wider than values
    (``ops/flash_attention``'s forward takes that).  ``return_kv`` hands
    back (c_kv, k_rope): the cache holds those, never K and V.

    *Absorbed* (a cache call: decode, a verify window): the query is carried
    into the latent space, ``q_lat = q_nope W_UK^T`` [H, kv_lora_rank], the
    scores are ``q_lat c_kv^T + q_rope k_rope^T`` over the cached latents as
    they lie (one "KV head" read once for all heads), the weighted sum is
    taken over the latents too and leaves through ``W_UV``.  The same
    numbers: (q_nope W_UK^T) c_kv^T = q_nope (c_kv W_UK)^T.

    The softmax scale is (nope + rope)^-1/2, times YaRN's mscale squared
    where ``rope_yarn`` is set (``attention_scale`` wins when given).

    Further, each by a field of its own: ``q_lora_rank`` 0 projects the
    queries straight from ``x`` (``q_up`` alone); ``latent_qk_norm`` puts an
    RMSNorm over each head's whole query and one over the rotary key before
    the rotation (both forms see the same normed numbers: the cache holds the
    normed, rotated key); ``attention_gate`` "head_wise" multiplies head h's
    output, in either form, by ``sigmoid(x w_gate,h)`` before ``W_O``."""

    cfg: TransformerConfig

    @nn.compact
    def __call__(self, x, positions, cache=None, return_kv=False,
                 lengths=None):
        cfg = self.cfg
        h, r = cfg.num_heads, cfg.kv_lora_rank
        nope, rot, d_v = (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                          cfg.v_head_dim)
        if not (r and nope and rot and d_v):
            raise ValueError(
                "a latent_attention layer needs TransformerConfig's "
                "kv_lora_rank, qk_nope_head_dim, qk_rope_head_dim and "
                "v_head_dim (q_lora_rank 0: the queries straight from the "
                "stream)")
        if cfg.attention_gate not in (None, "head_wise"):
            raise ValueError(f"attention_gate {cfg.attention_gate!r}; "
                             f"latent attention has 'head_wise'")
        # as Attention: a served prefill's two position-wise sides run over
        # the prompt's row blocks
        rows, made = _prompt_rows(x, cache, return_kv, lengths)
        dense = lambda *a, **kw: made(nn.DenseGeneral(  # noqa: E731
            *a, use_bias=False, dtype=cfg.dtype,
            param_dtype=cfg.param_dtype, **kw))
        norm = lambda name: made(RMSNorm(  # noqa: E731
            dtype=cfg.dtype, param_dtype=cfg.param_dtype,
            epsilon=cfg.norm_eps, name=name))
        turn = functools.partial(rope, theta=cfg.rope_theta,
                                 interleaved=cfg.rope_interleaved,
                                 yarn=cfg.rope_yarn)
        scale = cfg.attention_scale
        if scale is None:
            scale = (nope + rot) ** -0.5
            if cfg.rope_yarn is not None:
                scale *= yarn_mscale(cfg.rope_yarn[0], cfg.rope_yarn[5]) ** 2
        w_ukv = self.param(
            "kv_up", nn.initializers.lecun_normal(in_axis=0,
                                                  out_axis=(1, 2)),
            (r, h, nope + d_v), cfg.param_dtype).astype(cfg.dtype)
        o_proj = dense(cfg.embed_dim, axis=(-2, -1), name="o")
        if cfg.q_lora_rank:
            q_down = dense(cfg.q_lora_rank, name="q_down")
            q_norm = norm("q_norm")
        kv_down, kv_norm = dense(r + rot, name="kv_down"), norm("kv_norm")
        q_up = dense((h, nope + rot), name="q_up")
        if cfg.latent_qk_norm:
            # over a head's whole query and over the one rotary key, before
            # the rotation; one weight for all heads
            q_head_norm, k_rope_norm = norm("q_head_norm"), norm("k_rope_norm")
        if cfg.attention_gate:
            gate_proj = dense(h, name="gate")

        def down_and_up(x, positions):
            with jax.named_scope(profiling.MLA_DOWN):
                c_q = q_norm(q_down(x)) if cfg.q_lora_rank else x
                down = kv_down(x)
                c_kv = kv_norm(down[..., :r])
                k_rope = down[..., None, r:]
                if cfg.latent_qk_norm:
                    k_rope = k_rope_norm(k_rope)
                k_rope = turn(k_rope, positions)[..., 0, :]
            with jax.named_scope(profiling.MLA_UP):
                q = q_up(c_q)
                if cfg.latent_qk_norm:
                    q = q_head_norm(q)
                return (c_kv, k_rope, q[..., :nope],
                        turn(q[..., nope:], positions))

        def gated(out, x):
            """A head's output times the sigmoid of its gate's logit."""
            if not cfg.attention_gate:
                return out
            gate = jax.nn.sigmoid(gate_proj(x).astype(jnp.float32))
            return (out * gate[..., None]).astype(out.dtype)

        def expanded(c_kv, k_rope, q_nope, q_rope):
            with jax.named_scope(profiling.MLA_UP):
                kv = jnp.einsum("bsr,rhd->bshd", c_kv, w_ukv)
                k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(
                    k_rope[:, :, None, :], kv.shape[:3] + (rot,))], axis=-1)
                return (jnp.concatenate([q_nope, q_rope], axis=-1), k,
                        kv[..., nope:])

        if cache is not None:
            c_kv, k_rope, q_nope, q_rope = down_and_up(x, positions)
            latents, rope_keys, lengths, layer = cache
            with jax.named_scope(profiling.MLA_DOWN):
                latents = write_kv_block(latents, c_kv, layer, lengths)
                rope_keys = write_kv_block(rope_keys, k_rope, layer, lengths)
            with jax.named_scope(profiling.MLA_ABSORB):
                q_lat = jnp.einsum("bqhd,rhd->bqhr", q_nope,
                                   w_ukv[..., :nope])
            with jax.named_scope(profiling.MLA_ATTN):
                o_lat = absorbed_decode_attention(
                    q_lat, q_rope, latents[layer], rope_keys[layer],
                    lengths, scale)
            with jax.named_scope(profiling.MLA_ABSORB):
                out = jnp.einsum("bqhr,rhd->bqhd", o_lat, w_ukv[..., nope:])
            return o_proj(gated(out, x)), (latents, rope_keys)

        if cfg.context_axis and cfg.context_plan is not None:
            raise NotImplementedError(
                "ring / zigzag attention over a context axis takes values "
                "as wide as the keys; latent attention's are not")

        def heads(x, positions):
            c_kv, k_rope, q_nope, q_rope = down_and_up(x, positions)
            return (*expanded(c_kv, k_rope, q_nope, q_rope), c_kv, k_rope)

        q, k, v, c_kv, k_rope = _over_rows(heads, rows, x, positions)
        attn = cfg.attention_fn or dense_causal_attention
        out = attn(q, k, v, causal=True, scale=scale,
                   **_prompt_end(cfg, lengths))
        if cfg.attention_gate:
            out = _over_rows(lambda out, x: o_proj(gated(out, x)), rows,
                             out, x)
        else:
            out = _over_rows(o_proj, rows, out)
        return (out, (c_kv, k_rope)) if return_kv else out


def absorbed_decode_attention(q_lat, q_rope, latents, rope_keys, lengths,
                              scale: float):
    """Block attention over a per-slot cache of latents, the query already
    in the latent space: ``q_lat`` [B, S_q, H, R], ``q_rope`` [B, S_q, H,
    Dr], ``latents`` [B, S, R], ``rope_keys`` [B, S, Dr]; query row ``i`` of
    slot ``b`` sits at position ``lengths[b] + i``.  Returns the weighted
    sum of the latents [B, S_q, H, R].  :func:`cached_decode_attention`'s
    arithmetic: float32 scores and softmax, -1e30 behind the mask."""
    s, s_q = latents.shape[1], q_lat.shape[1]
    qpos = lengths[:, None] + jnp.arange(s_q)[None, :]         # [B, S_q]
    mask = (jnp.arange(s)[None, None, :]
            <= qpos[:, :, None])[:, None, :, :]                # [B,1,S_q,S]
    logits = (jnp.einsum("bqhr,bkr->bhqk", q_lat, latents,
                         preferred_element_type=jnp.float32)
              + jnp.einsum("bqhd,bkd->bhqk", q_rope, rope_keys,
                           preferred_element_type=jnp.float32)) * scale
    logits = jnp.where(mask, logits, -1e30)
    probs = nn.softmax(logits, axis=-1).astype(q_lat.dtype)
    return jnp.einsum("bhqk,bkr->bqhr", probs, latents)

# A pass without a cache runs EVA attention densely, one mask over its
# [1, H, S, S + S / chunk] float32 logits, while those fit this many bytes,
# and as two partial attentions merged by their log-sum-exp past that
# (:func:`eva_attention_form`).  TransformerBackend's crossover for the
# dense and the flash form of causal attention (serving/engine.py,
# FLASH_PREFILL_LOGITS_BYTES: read on a v5e, PERF.md section 5, PR 41).
EVA_DENSE_LOGITS_BYTES = 96 * 2 ** 20


def eva_attention_form(cfg: TransformerConfig, s: int) -> str:
    """``"dense"`` or ``"merged"``: how a pass without a cache over ``s``
    positions runs EVA attention, from its shape alone.  The merged form
    takes whole windows."""
    logits_bytes = 4 * cfg.num_heads * s * (s + s // cfg.eva_chunk)
    return ("merged" if s % cfg.eva_window == 0
            and logits_bytes > EVA_DENSE_LOGITS_BYTES else "dense")


def eva_chunk_summaries(k, v, phi, mu, chunk: int, scale: float,
                        rows_at_a_time: int | None = None):
    """One summary a whole chunk, from the chunk's own rows alone: ``k``,
    ``v`` [B, n * chunk, H, D] -> (k̄, v̄) [B, n, H, D] in their dtypes, with
    a = softmax over the chunk's rows m of (scale phi_h . k_m), k̄ = sum a_m
    k_m + mu_h, v̄ = sum a_m v_m; the softmax and the sums in float32.  A
    sequence of several times ``rows_at_a_time`` rows (whole chunks) is
    summarised that many rows at a time, the same numbers: its float32
    intermediates are then a piece's and not the sequence's."""
    b, s, h, d = k.shape
    if rows_at_a_time and s > rows_at_a_time and s % rows_at_a_time == 0:
        pieces = lambda x: jnp.moveaxis(  # noqa: E731
            x.reshape(b, s // rows_at_a_time, rows_at_a_time, h, d), 1, 0)
        kbar, vbar = jax.lax.map(
            lambda kv: eva_chunk_summaries(*kv, phi, mu, chunk, scale),
            (pieces(k), pieces(v)))
        whole = lambda x: jnp.moveaxis(x, 0, 1).reshape(  # noqa: E731
            b, s // chunk, h, d)
        return whole(kbar), whole(vbar)
    kc = k.reshape(b, s // chunk, chunk, h, d)
    vc = v.reshape(b, s // chunk, chunk, h, d)
    a = nn.softmax(jnp.einsum(
        "bnchd,hd->bnch", kc, phi.astype(k.dtype),
        preferred_element_type=jnp.float32) * scale, axis=2)
    # the weights go to the rows' dtype and the sums are taken in float32
    mean = lambda rows: jnp.einsum(  # noqa: E731
        "bnch,bnchd->bnhd", a.astype(rows.dtype), rows,
        preferred_element_type=jnp.float32)
    kbar, vbar = mean(kc) + mu.astype(jnp.float32), mean(vc)
    return kbar.astype(k.dtype), vbar.astype(v.dtype)


def eva_dense_attention(q, k, v, kbar, vbar, window: int, chunk: int,
                        scale: float):
    """EVA attention over a whole sequence, one mask: ``q``, ``k``, ``v``
    [B, S, H, D], ``kbar``, ``vbar`` [B, n, H, D] (n >= the chunks of the
    completed windows).  Query t sees keys m with t's window's start <= m <=
    t and summaries j < (window / chunk) * (t // window), under one float32
    softmax (:func:`dense_causal_attention`'s arithmetic)."""
    s, n = q.shape[1], kbar.shape[1]
    t = jnp.arange(s)[:, None]
    m = jnp.arange(s)[None, :]
    mask = jnp.concatenate(
        [(m <= t) & (m // window == t // window),
         jnp.arange(n)[None, :] < (window // chunk) * (t // window)], axis=1)
    logits = jnp.concatenate(
        [jnp.einsum("bqhd,bkhd->bhqk", q, k),
         jnp.einsum("bqhd,bkhd->bhqk", q, kbar)], axis=-1).astype(
        jnp.float32) * scale
    probs = nn.softmax(jnp.where(mask, logits, -1e30), axis=-1).astype(
        q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs[..., :s], v) \
        + jnp.einsum("bhqk,bkhd->bqhd", probs[..., s:], vbar)


@functools.partial(jax.jit, static_argnames=("window", "chunk"))
def eva_merged_attention(q, k, v, kbar, vbar, window: int, chunk: int,
                         length=None):
    """:func:`eva_dense_attention`'s numbers (at the d^-1/2 scale) without
    its logits: a window at a time, the causal triangle of the window's own
    keys and the rectangle of the summaries of the windows behind it, each a
    partial attention of the flash forward kernel
    (``ops/flash_attention.flash_attention_with_lse``; the rectangle over
    all the summaries with the kernel's valid length at the window's
    count, where its sweep ends), merged by their log-sum-exp.  ``S`` is
    whole windows.  ``length`` (a traced scalar may be given) is where the
    prompt ends in a padded sequence: window ``w`` then tells both kernels
    that ``clip(length - w * window, 0, window)`` of its rows count, a
    window wholly past the prompt runs neither, and the rows past the
    prompt come out 0.  Jitted, so that a program's layers share one
    tracing of the two kernels (PERF.md section 6, PR 41).  Forward
    only."""
    from horovod_tpu.ops.flash_attention import flash_attention_with_lse

    b, s, h, d = q.shape
    nw = s // window
    if nw * window != s:
        raise ValueError(f"the merged form takes whole windows of {window} "
                         f"positions; the sequence has {s}")
    by_window = lambda x: jnp.moveaxis(  # noqa: E731
        x.reshape(b, nw, window, h, d), 1, 0)

    def one(args):
        w, qw, kw, vw = args
        # how many of this window's rows (and own keys) are the prompt's
        count = None if length is None \
            else jnp.clip(length - w * window, 0, window)
        own, own_lse = flash_attention_with_lse(
            qw, kw, vw, causal=True, q_len=count, k_len=count)
        if nw == 1:
            return own.astype(q.dtype)
        # the summaries of windows 0 .. w - 1: the first w * window / chunk
        past, past_lse = flash_attention_with_lse(
            qw, kbar, vbar, causal=False, q_len=count,
            k_len=w * (window // chunk))
        lse = jnp.logaddexp(own_lse, past_lse)
        return (own * jnp.exp(own_lse - lse)[..., None]
                + past * jnp.exp(past_lse - lse)[..., None]).astype(q.dtype)

    out = jax.lax.map(one, (jnp.arange(nw), by_window(q), by_window(k),
                            by_window(v)))
    return jnp.moveaxis(out, 0, 1).reshape(b, s, h, d)


def eva_decode_attention(q, k_rows, v_rows, lengths, window: int,
                         chunk: int, scale: float):
    """One position a slot over its ring and summaries: ``q`` [B, 1, H, D]
    at position ``lengths[b]``, ``k_rows`` / ``v_rows`` [B, window + n, H,
    D] (ring row r holds the position of the current window with m mod
    window = r; row window + j chunk j's summary).  Seen: ring rows 0 ..
    lengths mod window and summaries j < (window / chunk) * (lengths //
    window); a ring row left from the window before and a summary of the
    current window (or of a slot's previous occupant) lie behind the mask.
    :func:`cached_decode_attention`'s arithmetic."""
    t = lengths[:, None]
    r = jnp.arange(k_rows.shape[1])[None, :]
    mask = jnp.where(r < window, r <= t % window,
                     r - window < (window // chunk) * (t // window))
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k_rows).astype(
        jnp.float32) * scale
    probs = nn.softmax(jnp.where(mask[:, None, None, :], logits, -1e30),
                       axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v_rows)


class EvaAttention(nn.Module):
    """EVA attention (Zheng et al., "Efficient Attention via Control
    Variates", as the EvaByte family runs it): per head, exact softmax terms
    for the keys of the query's own window (``eva_window`` positions, causal)
    and one summary (k̄_j, v̄_j) a chunk of ``eva_chunk`` positions for every
    window wholly behind the query, under ONE normaliser.  A chunk's summary
    is a softmax-weighted mean of its own rotated keys and of its values,
    the weights from a learned per-head vector ``phi`` and k̄ shifted by a
    learned ``mu`` (:func:`eva_chunk_summaries`).  One parameter tree, two
    forms:

    *Without a cache* (training, a serving prefill): the summaries of every
    whole chunk (scope ``hvd_eva_summary``), then attention (scope
    ``hvd_eva_attn``) densely with one mask or as merged partials, by the
    sequence's shape (:func:`eva_attention_form`).  ``return_kv`` hands back
    what the cache holds, K-side and V-side ``[B, eva_window + S //
    eva_chunk, H, D]``: the rows of the window that position ``lengths[b]``
    (default S) falls in, laid out as the ring, then the summaries.

    *With a cache* (one position a slot): the new key and value go to ring
    row ``t mod eva_window``; the summary of the chunk t is in is taken from
    the ring's rows of that chunk and written at its row every step (whole,
    so right, at the chunk's last position; nothing sees it before its
    window is complete); attention over the ring rows up to t's and the
    summaries of the completed windows.  Stale rows are masked, never
    cleared.  A block of more than one position (verify, suffix prefill) is
    refused by name."""

    cfg: TransformerConfig

    @nn.compact
    def __call__(self, x, positions, cache=None, return_kv=False,
                 lengths=None):
        cfg = self.cfg
        w, c, h, d = (cfg.eva_window, cfg.eva_chunk, cfg.num_heads,
                      cfg.head_dim)
        if not (w and c) or w % c or cfg.kv_heads != h:
            raise ValueError(
                "an eva_attention layer needs TransformerConfig's eva_window "
                "a multiple of eva_chunk, and as many KV heads as heads")
        scale = d ** -0.5 if cfg.attention_scale is None \
            else cfg.attention_scale
        # as Attention: a served prefill's position-wise sides run over the
        # prompt's row blocks, and its summaries over the prompt's windows
        rows, made = _prompt_rows(x, cache, return_kv, lengths)
        proj = {name: made(nn.DenseGeneral(
            (h, d), use_bias=False, dtype=cfg.dtype,
            param_dtype=cfg.param_dtype, name=name)) for name in "qkv"}
        q, k, v = _over_rows(
            lambda x, positions: (
                rope(proj["q"](x), positions, cfg.rope_theta),
                rope(proj["k"](x), positions, cfg.rope_theta), proj["v"](x)),
            rows, x, positions)
        vector = lambda name: self.param(  # noqa: E731
            name, nn.initializers.normal(0.02), (h, d), cfg.param_dtype)
        phi, mu = vector("phi"), vector("mu")
        o_proj = made(nn.DenseGeneral(
            cfg.embed_dim, axis=(-2, -1), use_bias=False, dtype=cfg.dtype,
            param_dtype=cfg.param_dtype, name="o"))
        if cache is not None:
            if x.shape[1] != 1:
                raise NotImplementedError(
                    "eva_attention decodes one position a cache call: a "
                    "block of more (speculative verify, a prefix-attached "
                    "suffix prefill) through the ring and summaries is not "
                    "built")
            k_pool, v_pool, lengths, layer = cache
            row = lengths % w
            k_pool = write_kv_block(k_pool, k, layer, row)
            v_pool = write_kv_block(v_pool, v, layer, row)
            with jax.named_scope(profiling.EVA_SUMMARY):
                first = row - row % c       # of the chunk t is in, in the ring
                rows = lambda pool: jnp.stack([  # noqa: E731
                    jax.lax.dynamic_slice(
                        pool, (layer, b, first[b], 0, 0), (1, 1, c, h, d)
                    )[0, 0] for b in range(x.shape[0])])
                kbar, vbar = eva_chunk_summaries(rows(k_pool), rows(v_pool),
                                                 phi, mu, c, scale)
                k_pool = write_kv_block(k_pool, kbar, layer, w + lengths // c)
                v_pool = write_kv_block(v_pool, vbar, layer, w + lengths // c)
            with jax.named_scope(profiling.EVA_ATTN):
                out = eva_decode_attention(q, k_pool[layer], v_pool[layer],
                                           lengths, w, c, scale)
            return o_proj(out), (k_pool, v_pool)

        if cfg.context_axis and cfg.context_plan is not None:
            raise NotImplementedError(
                "ring / zigzag attention over a context axis is causal "
                "attention's; eva_attention's windows and summaries are not "
                "sharded over one")
        s = x.shape[1]
        with jax.named_scope(profiling.EVA_SUMMARY):
            if rows is not None and s % w == 0:
                kbar, vbar = _over_rows(
                    lambda k, v: eva_chunk_summaries(k, v, phi, mu, c, scale),
                    rows, k, v, block=w)
            else:
                whole = s - s % c
                kbar, vbar = eva_chunk_summaries(
                    k[:, :whole], v[:, :whole], phi, mu, c, scale,
                    rows_at_a_time=w)
        with jax.named_scope(profiling.EVA_ATTN):
            if eva_attention_form(cfg, s) == "merged" \
                    and cfg.attention_scale is None:
                out = eva_merged_attention(
                    q, k, v, kbar, vbar, window=w, chunk=c,
                    length=None if lengths is None else jnp.max(lengths))
            else:
                out = eva_dense_attention(q, k, v, kbar, vbar, w, c, scale)
        if not return_kv:
            return o_proj(out)
        # the ring as a decode step at position lengths[b] expects it: the
        # rows of the window that position is in (for a prompt that ends on
        # a window's last position nothing of the ring is seen again)
        at = jnp.full((x.shape[0],), s) if lengths is None else lengths
        start = (at // w) * w
        pad = -s % w
        ring = lambda y: jnp.stack([  # noqa: E731
            jax.lax.dynamic_slice_in_dim(
                jnp.pad(y[b], ((0, pad), (0, 0), (0, 0))) if pad else y[b],
                start[b], w, axis=0) for b in range(x.shape[0])])
        return _over_rows(o_proj, rows, out), (
            jnp.concatenate([ring(k), kbar], axis=1),
            jnp.concatenate([ring(v), vbar], axis=1))


class MLP(nn.Module):
    cfg: TransformerConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        gate = nn.Dense(cfg.mlp_dim, use_bias=False, dtype=cfg.dtype,
                        param_dtype=cfg.param_dtype, name="gate")(x)
        up = nn.Dense(cfg.mlp_dim, use_bias=False, dtype=cfg.dtype,
                      param_dtype=cfg.param_dtype, name="up")(x)
        return nn.Dense(cfg.embed_dim, use_bias=False, dtype=cfg.dtype,
                        param_dtype=cfg.param_dtype,
                        name="down")(nn.silu(gate) * up)


# layer type -> (module of the mixer's class, the class, its name in a layer,
# what else its constructor is told).  A mixer is
# ``Mixer(cfg, name=...)(x, positions)``; one that can serve from a cache
# also takes ``cache`` / ``return_kv`` and then returns (out, kv).
_ATTENTION = ("horovod_tpu.models.transformer", "Attention", "attn")
MIXERS = {
    "attention": _ATTENTION + ({},),
    "sliding_attention": _ATTENTION + ({"layer_type": "sliding_attention"},),
    "full_attention": _ATTENTION + ({"layer_type": "full_attention"},),
    "mamba": ("horovod_tpu.models.mamba", "Mamba2Mixer", "mamba", {}),
    "latent_attention": ("horovod_tpu.models.transformer",
                         "LatentAttention", "attn", {}),
    "eva_attention": ("horovod_tpu.models.transformer", "EvaAttention",
                      "attn", {}),
    "kda": ("horovod_tpu.models.kda", "KDAMixer", "kda", {}),
    "cca": ("horovod_tpu.models.cca", "CCAMixer", "cca", {}),
}
CACHED_MIXERS = ("attention", "sliding_attention", "full_attention",
                 "latent_attention", "eva_attention", "kda", "cca")


def _scaled(x, multiplier: float):
    """x * multiplier, the product in float32 (0.22 is no bf16 number);
    at 1 no op at all."""
    if multiplier == 1.0:
        return x
    return (x.astype(jnp.float32) * multiplier).astype(x.dtype)


def _feed_forward(cfg: TransformerConfig, dense: bool = False,
                  made=_as_is):
    """The layer's feed-forward as ``ff(y, valid)`` on its normed input: the
    dense GLU MLP (always, in a layer that is ``dense``: one of a sparse
    model's ``first_dense_layers``) or one of the two sparse layouts.  Made
    inside :class:`Block`'s compact call, once a layer, by a function, so
    that flax adds no method's name to the module path; ``ff`` may then be
    called a chunk of the sequence at a time.  The dense MLP is called
    through ``made`` (:func:`_unbound`, inside a loop's body).  Where the
    router carries a state down the layers (``cfg.moe_router_dim``) it is
    ``ff(y, valid, *state)`` -> ``(out, state)``: ``state`` the layer
    before's, nothing for the first; a dense layer hands on what it got."""
    if cfg.num_experts > 0 and not dense:
        from horovod_tpu.models.moe import MoEMLP

        if cfg.moe_axis is not None:
            raise ValueError("num_experts (every expert on each device) "
                             "and moe_axis (one expert a device) are two "
                             "layouts; set one")
        moe = MoEMLP(embed_dim=cfg.embed_dim,
                     mlp_dim=cfg.moe_mlp_dim or cfg.mlp_dim,
                     axis_name=None, dtype=cfg.dtype,
                     num_experts=cfg.num_experts,
                     experts_per_token=cfg.experts_per_token,
                     norm_topk_prob=cfg.norm_topk_prob,
                     selection=cfg.moe_selection,
                     num_shared_experts=cfg.num_shared_experts,
                     experts_held=cfg.experts_held,
                     routed_scale=cfg.moe_routed_scale,
                     expert_bias=cfg.moe_expert_bias,
                     groups=cfg.moe_groups,
                     topk_groups=cfg.moe_topk_groups,
                     router_dim=cfg.moe_router_dim, norm_eps=cfg.norm_eps,
                     param_dtype=cfg.param_dtype, name="moe_mlp")
        if cfg.moe_router_dim:
            return lambda y, valid, *state: moe(
                y, valid=valid, router_state=state[0] if state else None)
        return lambda y, valid: moe(y, valid=valid)
    if cfg.moe_axis is not None:
        from horovod_tpu.models.moe import MoEMLP

        # Residual carries over-capacity (dropped) tokens unchanged.
        moe = MoEMLP(embed_dim=cfg.embed_dim, mlp_dim=cfg.mlp_dim,
                     axis_name=cfg.moe_axis,
                     capacity_factor=cfg.moe_capacity_factor,
                     dtype=cfg.dtype, name="moe_mlp")
        return lambda y, valid: moe(y)
    mlp = made(MLP(cfg, name="mlp"))
    if cfg.moe_router_dim:
        return lambda y, valid, *state: (mlp(y), state[0] if state else None)
    return lambda y, valid: mlp(y)


def _feed_forward_over(cfg: "TransformerConfig", dense_ff: bool, rows, made):
    """(the block's feed-forward, the rows it runs over, its chunk) in a
    pass whose position-wise work runs over ``rows`` through ``made``
    (:func:`_prompt_rows`).  A dense feed-forward runs over the prompt's row
    blocks where there are any, the loop's blocks in the chunks' place.  A
    sparse one keeps its lines and its chunks: its routed experts walk the
    whole chunk's pairs, and its position-wise parts stop at the last
    position that holds a token by themselves (models/moe.py)."""
    if cfg.moe_axis or (cfg.num_experts and not dense_ff):
        rows, made = None, _as_is
    return (_feed_forward(cfg, dense_ff, made), rows,
            cfg.feed_forward_chunk if rows is None else None)


def _in_chunks(fn, chunk: int | None, x, valid, *more):
    """``fn(x, valid, *more)`` over [B, S, ...] (``more``: further arrays a
    position), ``chunk`` positions at a time where the sequence is longer
    (``fn`` is position-wise: the same numbers, whatever tree it gives)."""
    s = x.shape[1]
    if not chunk or s <= chunk:
        return fn(x, valid, *more)
    return jax.tree.map(
        lambda *ys: jnp.concatenate(ys, axis=1),
        *[fn(x[:, a:a + chunk],
             None if valid is None else valid[:, a:a + chunk],
             *(m[:, a:a + chunk] for m in more))
          for a in range(0, s, chunk)])


# Rows in a block of a served prefill's position-wise work: the flash
# forward's q block, the unit ``attn_rows`` counts in (ops/flash_attention).
ROW_BLOCK = 1024


def row_blocks(s: int) -> int:
    """In how many row blocks a served prefill's position-wise work over
    ``s`` positions may run (:func:`_over_rows`); 0 where it runs in one
    piece.  More than two whole blocks: a bucket of two, on a ladder that
    doubles, holds only prompts that reach into its second, so a loop there
    would skip nothing, and a block's result is copied into the loop's
    buffer where the whole call's is written once (read on a v5e, unloaded,
    the 2048 bucket whole against looped: deepseek-coder-1.3b 41.0 / 45.8
    ms, EvaByte 94.8 / 97.6, A.X-K1 69.2 / 70.8, command-a-plus 76.6 /
    77.1; PERF.md section 6, PR 47)."""
    return s // ROW_BLOCK if s > 2 * ROW_BLOCK and s % ROW_BLOCK == 0 else 0


def _prompt_rows(x, cache, return_kv: bool, lengths):
    """(rows, made) for a pass over ``x`` [B, S, ...].  In a served prefill
    of several row blocks (:func:`row_blocks`; a pass without a cache whose
    caller said where its prompts end, ``lengths`` beside ``return_kv``)
    ``rows`` is how many leading positions hold a prompt (the longest
    row's, a traced scalar), for :func:`_over_rows`, and ``made`` is
    :func:`_unbound`, what a submodule is called through inside that loop.
    For every other pass
    (training, evaluation, a cache call, ``model.init``, a short bucket)
    ``rows`` is None and ``made`` hands the module back: the position-wise
    work runs over the whole sequence in the lines it always had."""
    if cache is not None or not return_kv or lengths is None \
            or not row_blocks(x.shape[1]):
        return None, _as_is
    return jnp.max(lengths), _unbound


def _over_rows(fn, rows, *xs, block: int = ROW_BLOCK):
    """``fn(*xs)`` for a position-wise ``fn`` over arrays [B, S, ...], where
    ``rows`` (a traced scalar; None: in one piece, as written) says how many
    leading positions count: ONE body in a loop of ``ceil(rows / block)``
    trips, each over ``block`` positions (the block the count ends in runs
    whole) written into buffers of the sequence's extent that start as
    zeros.  Positions ``< rows`` get the numbers the whole call would give
    them; those of the blocks never visited stay exactly 0, whatever lies
    in ``xs`` there.  ``fn`` may give fewer rows than it takes (one a chunk)
    and any tree of arrays.

    The buffers are ``jnp.zeros`` and the loop a ``fori_loop`` on purpose.
    Left uninitialised (``lax.empty``) with a zero block written past the
    count, every block written once, XLA:TPU fixed their layout and put two
    copies of the bucket's rows a layer in front of EVA attention's kernels:
    ``evabyte-code32k-open``'s 32768 bucket 1678 -> 1714 ms and its 2048
    bucket 97.6 -> 106.9 (PERF.md section 6, PR 47)."""
    if rows is None:
        return fn(*xs)
    blocks = xs[0].shape[1] // block
    piece = lambda x, i: jax.lax.dynamic_slice_in_dim(  # noqa: E731
        x, i * block, block, axis=1)
    one = jax.eval_shape(fn, *(jax.ShapeDtypeStruct(
        x.shape[:1] + (block,) + x.shape[2:], x.dtype) for x in xs))

    def body(i, out):
        return jax.tree.map(
            lambda whole, part: jax.lax.dynamic_update_slice_in_dim(
                whole, part, i * part.shape[1], axis=1),
            out, fn(*(piece(x, i) for x in xs)))

    return jax.lax.fori_loop(
        0, (rows + block - 1) // block, body, jax.tree.map(
            lambda a: jnp.zeros(
                (a.shape[0], blocks * a.shape[1]) + a.shape[2:], a.dtype),
            one))


def _over_rows_carrying(fn, rows, carry, *xs, block: int = ROW_BLOCK):
    """:func:`_over_rows` for a ``fn`` that is position-wise but for a
    state it hands from block to block (a recurrent mixer):
    ``fn(carry, *xs) -> (carry, out)`` over arrays [B, S, ...], a row block
    at a time in order, up to the block ``rows`` ends in.  Returns (the
    carry after the last block visited, out [B, S, ...] with the rows of
    the blocks never visited 0).  ``rows`` None: one call over the whole
    sequence."""
    if rows is None:
        return fn(carry, *xs)
    blocks = xs[0].shape[1] // block
    piece = lambda x, i: jax.lax.dynamic_slice_in_dim(  # noqa: E731
        x, i * block, block, axis=1)
    _, one = jax.eval_shape(fn, carry, *(jax.ShapeDtypeStruct(
        x.shape[:1] + (block,) + x.shape[2:], x.dtype) for x in xs))

    def body(i, state):
        carry, out = state
        carry, part = fn(carry, *(piece(x, i) for x in xs))
        return carry, jax.tree.map(
            lambda whole, part: jax.lax.dynamic_update_slice_in_dim(
                whole, part, i * part.shape[1], axis=1), out, part)

    return jax.lax.fori_loop(
        0, (rows + block - 1) // block, body, (carry, jax.tree.map(
            lambda a: jnp.zeros(
                (a.shape[0], blocks * a.shape[1]) + a.shape[2:], a.dtype),
            one)))


# what a stream of several rows (hyper_streams) is not built beside, and why
HYPER_REFUSED = {
    "parallel_block": "the rows are mixed around each of two sublayers in "
                      "turn, and a parallel block has one",
    "residual_scaling": "the scaled residual merge is the sequential "
                        "block's one-row stream's; the rows' mixes stand in "
                        "its place",
    "moe_router_dim": "a router state handed down the layers rides beside "
                      "a one-row stream",
    "attention_block": "a block-diffusion model's passes are built for a "
                       "one-row stream"}


def _hyper_block(block, mixer, name: str, x, positions, cache, return_kv,
                 valid, lengths):
    """:class:`Block`'s call for a stream of several rows
    (``cfg.hyper_streams``; models/hyper.py): ``x`` is [B, S, n, C], each
    sublayer reads ``norm(h)``, h one mix of the rows, and what it gives
    is written into another mix of them.  The coefficients and the mixes
    are position-wise: in a served prefill of several row blocks they run
    over the prompt's own blocks (:func:`_over_rows`), as a dense
    feed-forward does; a sparse one keeps its chunks.  Called inside
    ``Block``'s compact call, so the modules made here are the block's."""
    from horovod_tpu.models import hyper

    cfg = block.cfg
    rows, made = _prompt_rows(x, cache, return_kv, lengths)

    def entering(side: str):
        """x -> (the sublayer's normed input, its coefficients, the column
        error): the sublayer's own hyper-connection and its norm."""
        coefficients = made(hyper.HyperConnection(
            cfg.hyper_streams, cfg.hyper_sinkhorn_iters, cfg.hyper_eps,
            tuple(cfg.hyper_res_clamp), cfg.param_dtype, name=f"{side}_hc"))
        norm = make_norm(cfg, f"{side}_norm", made)

        def enter(x):
            coef, err = coefficients(x)
            return norm(hyper.pre_mix(coef, x)), coef, err

        return enter

    def leave(x, f, coef):
        return hyper.post_mix(coef, x, _scaled(f, cfg.residual_multiplier))

    y, coef, err = _over_rows(entering(name), rows, x)
    kv = None
    if cache is not None or return_kv:
        mixed, kv = mixer(y, positions, cache=cache, return_kv=return_kv,
                          **({} if lengths is None else {"lengths": lengths}))
    else:
        mixed = mixer(y, positions)
    x = _over_rows(leave, rows, x, mixed, coef)
    y, coef, err_ff = _over_rows(entering("mlp"), rows, x)
    ff, ff_rows, chunk = _feed_forward_over(cfg, block.dense_ff, rows, made)
    f = _over_rows(lambda y: _in_chunks(ff, chunk, y, valid), ff_rows, y)
    out = _over_rows(leave, rows, x, f, coef)
    if not block.is_initializing():
        block.sow(hyper.MHC_STATS, "col_sum_err",
                  jnp.maximum(jnp.max(err), jnp.max(err_ff)))
    if cache is not None or return_kv:
        return out, kv
    return out


class Block(nn.Module):
    cfg: TransformerConfig
    layer_type: str = "attention"
    # the dense GLU MLP in a sparse model (one of its first_dense_layers)
    dense_ff: bool = False

    @nn.compact
    def __call__(self, x, positions, cache=None, return_kv=False,
                 valid=None, lengths=None, router_state=None):
        """Where the router carries a state down the layers
        (``cfg.moe_router_dim``) the stream this returns is the pair ``(x,
        the state for the next layer)`` and ``router_state`` is the layer
        before's (None: the first sparse layer)."""
        cfg = self.cfg
        norm = functools.partial(make_norm, cfg)
        try:
            module, cls, name, told = MIXERS[self.layer_type]
        except KeyError:
            raise ValueError(f"layer type {self.layer_type!r}; "
                             f"models/transformer.py has {sorted(MIXERS)}"
                             ) from None
        mixer = getattr(importlib.import_module(module), cls)(
            cfg, name=name, **told)
        if cfg.hyper_streams:
            return _hyper_block(self, mixer, name, x, positions, cache,
                                return_kv, valid, lengths)
        y = norm(f"{name}_norm")(x)
        kv = None
        if cache is not None or return_kv:
            # where a prefill's prompts end is told by name, only by a
            # caller that says it: the mixer's kernels then stop there, and
            # one whose cache is not a row a position lays it out for it
            mixed, kv = mixer(y, positions, cache=cache, return_kv=return_kv,
                              **({} if lengths is None
                                 else {"lengths": lengths}))
        else:
            mixed = mixer(y, positions)
        # A served prefill that said where its prompt ends (the mixers' rule)
        # runs what follows the mixer over the prompt's row blocks, the
        # loop's blocks in the chunks' place.
        rows, made = _prompt_rows(x, cache, return_kv, lengths)
        if cfg.parallel_block and rows is not None:
            # ... and after the mixer: XLA would else run a sparse
            # feed-forward first, with the mixer's loops' buffers and the
            # attention's output waiting beside its own
            mixed, y = jax.lax.optimization_barrier((mixed, y))
        ff, rows, chunk = _feed_forward_over(cfg, self.dense_ff, rows, made)
        if rows is None:
            made = _as_is
        carrying = bool(cfg.moe_router_dim)
        if cfg.parallel_block:
            if carrying or cfg.residual_scaling:
                raise NotImplementedError(
                    "parallel_block beside moe_router_dim or "
                    "residual_scaling: a router state handed down the "
                    "layers and the scaled residual merge are the "
                    "sequential block's")
            # one norm a layer: the feed-forward reads what the mixer read
            # and both are added to the residual
            out = _over_rows(lambda x, mixed, y: x + _scaled(
                mixed + _in_chunks(ff, chunk, y, valid),
                cfg.residual_multiplier), rows, x, mixed, y)
        else:
            # the two sublayers' merges, each with four vectors of its own
            # where scaled; a router's state rides beside x through the row
            # blocks and the chunks
            vector = lambda name, init: self.param(  # noqa: E731
                name, init, (cfg.embed_dim,), cfg.param_dtype
            ).astype(jnp.float32)
            scaled = {side: tuple(
                vector(f"{side}_{part}", nn.initializers.zeros
                       if part.endswith("bias") else nn.initializers.ones)
                for part in ("residual_bias", "residual_scale",
                             "branch_bias", "branch_scale"))
                for side in (name, "mlp")} if cfg.residual_scaling else {}
            mlp_norm = make_norm(cfg, "mlp_norm", made)

            def merged(side, x, f):
                if not scaled:
                    return x + _scaled(f, cfg.residual_multiplier)
                b_r, s_r, b_f, s_f = scaled[side]
                return ((x.astype(jnp.float32) + b_r) * s_r
                        + (f.astype(jnp.float32) + b_f) * s_f).astype(x.dtype)

            def rest(x, mixed, *state):
                x = merged(name, x, mixed)
                f = _in_chunks(
                    lambda x, valid, *state: ff(mlp_norm(x), valid, *state),
                    chunk, x, valid, *state)
                if not carrying:
                    return merged("mlp", x, f)
                f, handed = f
                return (merged("mlp", x, f),) + (
                    () if handed is None else (handed,))

            state = () if router_state is None else (router_state,)
            out = _over_rows(rest, rows, x, mixed, *state)
            if carrying:
                out = (out[0], out[1] if len(out) > 1 else None)
        if cache is not None or return_kv:
            return out, kv
        return out


class Transformer(nn.Module):
    """Token ids [B, S] → logits [B, S, vocab].

    ``position_offset`` shifts positions for sequence-parallel shards so each
    shard computes RoPE/causal masks at its global coordinates.  For
    non-contiguous layouts (zigzag ring attention), pass explicit
    ``positions`` ([S] or [B, S] global coordinates) instead — e.g.
    ``parallel.zigzag_positions(s_local, axis)``.  With
    ``cfg.context_axis`` + ``cfg.context_plan`` set, positions, the
    attention path, and the remat policy all derive from the plan (see
    ``parallel/context.py``); explicit arguments still win.

    Serving (docs/inference.md "Serving loop"):

    * ``return_kv=True`` — a prefill pass: also return the per-layer
      rotary-embedded K and raw V as two stacked ``[L, B, S, H, D]``
      arrays, for writing into a slot of an :func:`init_kv_cache` buffer.
      ``lengths`` ([B]) beside it says where each row's prompt ends in a
      padded sequence: ``cfg.attention_fn``, where the model has one, is
      then called with ``q_len`` and ``k_len`` (the longest row's), and
      ``ops/flash_attention`` runs no tile of the padding; the rows past
      a prompt's end come out of the kernel 0, not what the padding would
      attend to.  The dense default is told nothing and computes it all.
      Over several row blocks (:func:`row_blocks`) the position-wise
      work of every layer also stops at the block the longest
      prompt ends in (:func:`_over_rows`): the rows of the blocks past it
      are 0 in the streams and in the returned blocks, the rows below the
      lengths what they are without ``lengths``, to the bit.
    * ``kv_cache=(k, v)`` + ``lengths`` — one incremental decode step:
      ``tokens`` is ``[B, 1]`` (the last sampled token per slot),
      ``lengths`` ``[B]`` the position each slot is decoding at; returns
      ``(logits [B, vocab], (k, v))`` with the caches advanced in place:
      the two whole ``[L, B, S, H, D]`` arrays go through the layers, layer
      ``i`` writes its block at ``(i, b, lengths[b])``
      (:func:`write_kv_block`), attends over its own view ``k[i]`` and
      hands the arrays on.  No layer's slice is rebuilt and nothing is
      stacked, so a jitted caller that DONATES ``k`` and ``v`` gets them
      back in the same memory with the block's rows written; without the
      donation XLA copies both arrays once a call.
      The decode program's shapes are fixed by the slot count, so the
      jitted step never recompiles as sequences come and go.
    * ``valid`` ([B, S] bool; a sparse model, ``num_experts`` > 0): which
      positions hold a token.  A prefill bucket's padding and a slot with
      no request are routed to no expert (models/moe.py).
    * ``kv_into=(k_pool, v_pool, slot)`` beside ``return_kv`` (one row of
      tokens): each layer's block is written into the two pools at ``(layer,
      slot)`` as soon as the layer has run, and the pools are what is
      returned in place of the stacked blocks.  For a model whose block a
      layer is a slot's whole extent (EVA attention: 16 layers' rings and
      summaries stacked would be a second slot beside the pool).
    * ``logits_at`` ([B] positions; not with a cache): the final norm and
      the head run on that one position of each row and the logits are
      ``[B, vocab]``: a prefill needs its prompt's last position alone.

    A model whose layers are "latent_attention" keeps latents in the cache
    (``return_kv`` and ``kv_cache`` are then :func:`init_kv_cache`'s two
    latent arrays, ``[L, B, S, kv_lora_rank]`` and ``[L, B, S,
    qk_rope_head_dim]``): :class:`LatentAttention`.  One whose layers are
    "eva_attention" keeps a ring of its window's keys and values and one
    summary a chunk (``[L, B, eva_window + S / eva_chunk, H, D]`` twice;
    ``lengths`` beside ``return_kv`` says where each row's prompt ends, so
    that the ring is the one a decode step there expects):
    :class:`EvaAttention`.  One with "kda" layers (``models/kda.py``) keeps
    a recurrent state and a convolution tail a slot for each of them beside
    its other layers' rows: ``kv_cache``, ``kv_into`` and what ``return_kv``
    hands back are then two TREES with an entry a cache kind
    (:func:`init_kv_cache`, ``cfg.cache_layout``), a layer given its own
    kind's two arrays at its index among that kind's layers.  With
    ``num_pred_heads`` > 1 the logits' last axis is ``num_pred_heads *
    vocab_size`` wide, head 0 (the next token) first.

    The stream between layers is one vector a position in every call form
    above: ``[B, S, C]`` in a pass without a cache (``[B, S, C]`` with the
    rows past the longest prompt's block 0 in a prefill that said its
    ``lengths``), ``[B, S_q, C]`` in a cache call, beside a router's state
    where ``moe_router_dim`` carries one.  With ``cfg.hyper_streams`` = n >
    0 (models/hyper.py) it is n ROWS a position, ``[B, S, n, C]`` and ``[B,
    S_q, n, C]`` (a decode step: ``[slots, 1, n, C]``): the embedding copied
    into every row, two hyper-connections a layer mixing the rows around
    its sublayers (:func:`_hyper_block`), the rows summed before the final
    norm, after ``logits_at``'s pick where that is given.  The cache is the
    mixers' and knows nothing of it.
    """

    cfg: TransformerConfig

    @nn.compact
    def __call__(self, tokens, position_offset=0, positions=None,
                 kv_cache=None, lengths=None, return_kv=False, valid=None,
                 logits_at=None, kv_into=None):
        cfg = self.cfg
        if logits_at is not None and kv_cache is not None:
            raise ValueError("logits_at picks a position of a pass without "
                             "a cache; a cache call's block is its own")
        if valid is not None and cfg.num_experts == 0:
            raise ValueError("valid says which positions a sparse "
                             "feed-forward routes: num_experts is 0")
        # only a caller that says which positions hold a token tells a layer
        told = {} if valid is None else {"valid": valid}
        decode = kv_cache is not None
        kinds = cfg.layer_kinds
        if cfg.attention_block and set(kinds) != {"attention"}:
            raise NotImplementedError(
                f"attention_block beside {sorted(set(kinds) - {'attention'})}"
                f" layers: the block-causal mask is built for a model whose "
                f"every layer is \"attention\"")
        if cfg.hyper_streams:
            for field, why in HYPER_REFUSED.items():
                if getattr(cfg, field):
                    raise NotImplementedError(
                        f"hyper_streams beside {field}: {why}")
        if (decode or return_kv) and set(kinds) - set(CACHED_MIXERS):
            raise NotImplementedError(
                f"decode and serving through a recurrent layer are not "
                f"supported yet: layer_types holds "
                f"{sorted(set(kinds) - set(CACHED_MIXERS))}, and kv_cache / "
                f"return_kv serve {list(CACHED_MIXERS)} layers only")
        embed = nn.Embed(cfg.vocab_size, cfg.embed_dim, dtype=cfg.dtype,
                         param_dtype=cfg.param_dtype, name="embed")
        x = _scaled(embed(tokens), cfg.embedding_multiplier)
        if cfg.residual_dtype is not None:
            x = x.astype(cfg.residual_dtype)
        if cfg.hyper_streams:
            from horovod_tpu.models import hyper

            x = hyper.spread(x, cfg.hyper_streams)
        if decode:
            # Block row i of a cache call decodes position lengths + i:
            # S=1 is plain decode, S>1 is a speculative verify window or a
            # prefix-attached prompt-suffix prefill.
            positions = (jnp.asarray(lengths)[:, None]
                         + jnp.arange(tokens.shape[1])[None, :])
        if positions is None and cfg.context_axis and \
                cfg.context_plan is not None:
            from horovod_tpu.parallel.context import context_positions

            positions = context_positions(cfg.context_axis,
                                          tokens.shape[1], cfg.context_plan)
        if positions is None:
            positions = (jnp.arange(tokens.shape[1])[None, :]
                         + jnp.asarray(position_offset))
        elif positions.ndim == 1:
            positions = positions[None, :]
        positions = jnp.broadcast_to(positions, tokens.shape)
        remat_on = (cfg.remat if cfg.context_plan is None
                    else cfg.context_plan.remat) and not decode \
            and not return_kv
        block_cls = nn.remat(Block) if remat_on else Block
        kvs = []
        # a model with "kda" layers: the pool is two trees with an entry a
        # cache kind, and a layer is one of ITS kind's (cfg.cache_layout)
        layout = cfg.cache_layout if decode or return_kv else None
        # where each row's prompt ends, beside return_kv: a mixer whose
        # attention is a kernel stops it there, and EVA attention also lays
        # its cache block out for a decode step there
        ends = {"lengths": jnp.asarray(lengths)} \
            if return_kv and lengths is not None else {}
        # a router that carries a state down the layers: a layer's stream is
        # then (x, the state), and the next layer is told the state
        carrying = bool(cfg.moe_router_dim)
        for i, kind in enumerate(kinds):
            block = block_cls(cfg, kind, i < cfg.first_dense_layers,
                              name=f"layer_{i}")
            if carrying and i:
                x, told["router_state"] = x
            if decode and layout is not None:
                # ... of two kinds: the layer's own kind's two arrays go
                # through it, at its index among that kind's layers (a
                # kind of several parts: a dict of them)
                kind, at = layout[i]
                x, (one, two) = block(
                    x, positions, cache=(_own_cache(kv_cache[0], kind),
                                         _own_cache(kv_cache[1], kind),
                                         lengths, at),
                    **told)
                kv_cache = (_with_cache(kv_cache[0], kind, one),
                            _with_cache(kv_cache[1], kind, two))
            elif decode:
                # the whole pool goes through every layer: layer i writes
                # its block into it and reads its own view of it
                x, kv_cache = block(
                    x, positions, cache=(*kv_cache, lengths, i), **told)
            elif return_kv:
                if ends and cfg.num_experts == 0 \
                        and row_blocks(tokens.shape[1]):
                    # a layer of loops is traced once a kind of layer, not
                    # once a layer (a sparse layer sows, and is called as
                    # it lies)
                    with jax.named_scope(block.name):
                        x, kv = _unbound(block, nameless=True,
                                         return_kv=True)(
                            x, positions, **ends)
                else:
                    x, kv = block(x, positions, return_kv=True, **told,
                                  **ends)
                if kv_into is None:
                    kvs.append(kv)
                    continue
                # the layer's block goes into the pool before the next
                # layer runs (the barrier holds XLA to that order), so no
                # layer's block outlives its layer
                *pools, slot = kv_into
                kind, at = (None, i) if layout is None else layout[i]
                own = pools if layout is None else [_own_cache(p, kind)
                                                    for p in pools]
                own = [jax.tree.map(
                    lambda pool, block_: jax.lax.dynamic_update_slice(
                        pool, as_pool_rows(block_[None], pool).astype(
                            pool.dtype),
                        (at, slot) + (0,) * (pool.ndim - 2)), pool, block_)
                    for pool, block_ in zip(own, kv)]
                x, own = jax.lax.optimization_barrier((x, own))
                pools = own if layout is None else [
                    _with_cache(p, kind, one) for p, one in zip(pools, own)]
                kv_into = (*pools, slot)
            else:
                x = block(x, positions, **told)
        if carrying:
            x, _ = x
        if cfg.hyper_streams:
            # the rows' sum, of logits_at's one position where it is given
            if logits_at is not None:
                x = jnp.take_along_axis(x, jnp.asarray(
                    logits_at)[:, None, None, None], axis=1)[:, 0]
            x = hyper.gathered(x)
        elif logits_at is not None:
            x = jnp.take_along_axis(
                x, jnp.asarray(logits_at)[:, None, None], axis=1)[:, 0]
        x = make_norm(cfg, "final_norm")(x)
        # Head matmul in the compute dtype (bf16 hits the MXU at full rate;
        # f32 params, XLA accumulates in f32); logits upcast for the loss —
        # the standard LLM-trainer convention.  The f32 head matmul this
        # replaces was ~15% of step time (round 3's chip profile).
        if cfg.tie_embeddings:
            logits = embed.attend(x)
        else:
            logits = nn.Dense(cfg.vocab_size * cfg.num_pred_heads,
                              use_bias=False, dtype=cfg.dtype,
                              param_dtype=cfg.param_dtype, name="lm_head")(x)
        logits = _scaled(logits, 1.0 / cfg.logits_scaling).astype(
            cfg.logits_dtype)
        if decode:
            if tokens.shape[1] == 1:
                return logits[:, 0], kv_cache
            # Multi-token cache call (speculative verify / suffix
            # prefill): the caller needs every block position's logits.
            return logits, kv_cache
        if return_kv and kv_into is not None:
            return logits, tuple(kv_into[:2])
        if return_kv and layout is not None:
            # an entry a cache kind, each stacked over its own layers
            return logits, tuple(
                {part: jnp.stack([
                    kv[side][part] if kind in CACHE_PARTS else kv[side]
                    for kv, (k, _) in zip(kvs, layout) if k == kind])
                 for kind in dict(layout)
                 for part in CACHE_PARTS.get(kind, (kind,))}
                for side in (0, 1))
        if return_kv:
            return logits, (jnp.stack([kv[0] for kv in kvs]),
                            jnp.stack([kv[1] for kv in kvs]))
        return logits
