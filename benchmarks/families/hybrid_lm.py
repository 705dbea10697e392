"""Family ``hybrid_lm``: a Mamba-2 / attention hybrid decoder
(granite-4.0-h-micro's kind) trained through the path a user takes --
``hvd.DistributedOptimizer(optax...)`` inside
``jax.jit(hvd.shard(step), donate...)``, ``models/transformer.py`` with
``layer_types`` (``models/mamba.py`` and ``ops/ssd_scan.py`` for the ``mamba``
layers; grouped-query attention without positions through the flash kernels
for the ``attention`` ones), a tied head, the four multipliers.

The configuration file holds Hugging Face's keys; this module maps them onto
``TransformerConfig`` and refuses what the program cannot express.
"""

from __future__ import annotations

import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import PartitionSpec as P

import horovod_tpu as hvd
from horovod_tpu.models import Transformer, TransformerConfig

from benchmarks import compare, flops_ssm, streams
from benchmarks.built import Built
from benchmarks.reference import hybrid_lm as reference

# Tolerances of the reference comparison, and the reason for each.  The
# program multiplies in bf16 (8 bits of mantissa, 2**-8 = 0.4% a rounding)
# with f32 accumulation and keeps bf16 logits; the reference is f32 at
# "highest" and computes the recurrence another way (the whole sequence's
# lower-triangular form for the gradients, one position after the other for
# the long-context logits; the program works in chunks of 256).  Each bound
# stands between two readings of the chip at the published widths (PERF.md,
# PR 33): the largest the program gave over its seeds, and what the
# reference itself gives with every product's operands rounded to
# float8_e4m3fn, the nearest precision below bf16, which has to fail.
#
# GRAD_TOL: the worst gradient leaf reads 3.07 to 4.29% over 8 seeds, always
#   a ``dt_bias`` leaf (64 numbers that sum every position's sensitivity to
#   its step size through the decays' exponentials; the reference with bf16
#   operands alone reads 2.28% there), the median leaf 1.9 to 2.1%.  With
#   float8 operands every leaf is lost: worst 101%, median 100%.  10% leaves
#   the program twice its largest reading and float8 ten times outside.
#   residual_multiplier 0.22 damps what one layer adds to the loss, so a
#   dropped term in one mixer barely moves the loss: the leaves, each judged
#   alone (every A_log, D, dt_bias, conv and norm leaf among them), are what
#   catches it (a dropped D skip or conv bias reads 100% in its own leaf).
# LOGITS_TOL: the last 256 of 8192 positions read 1.60 to 1.63%; float8 reads
#   13.1%.  3%, the accepted families' bound, stands between.
# LOSS_TOL: precision hardly moves this number (5e-7 to 1.5e-5 read; float8
#   reads 1.8e-5), so it takes the accepted families' 1e-3, sixty times the
#   largest reading: it guards the loss's terms and scalings, not precision.
LOSS_TOL = 1e-3
GRAD_TOL = 0.10
LOGITS_TOL = 0.03

HYBRID_FIELDS = ("layer_types", "num_kv_heads", "rotary", "attention_scale",
                 "tie_embeddings", "embedding_multiplier",
                 "residual_multiplier", "logits_scaling", "mamba_heads")


def model_config(cfg: dict, traffic: dict) -> TransformerConfig:
    have = {f.name for f in dataclasses.fields(TransformerConfig)}
    if not set(HYBRID_FIELDS) <= have:
        raise SystemExit(
            "hybrid_lm: this program's TransformerConfig cannot express a "
            f"hybrid decoder (no {sorted(set(HYBRID_FIELDS) - have)})")
    for key, want in (
            ("tie_word_embeddings", True), ("position_embedding_type", "nope"),
            ("attention_bias", False), ("hidden_act", "silu"),
            ("normalization_function", "rmsnorm"), ("num_local_experts", 0),
            ("mamba_conv_bias", True), ("mamba_proj_bias", False)):
        if cfg.get(key, want) != want:
            raise ValueError(f"families/hybrid_lm.py cannot express "
                             f"{key}={cfg[key]!r}")
    if cfg["shared_intermediate_size"] != cfg["intermediate_size"]:
        raise ValueError("one feed-forward width a layer: "
                         "shared_intermediate_size != intermediate_size")
    if len(cfg["layer_types"]) != cfg["num_hidden_layers"]:
        raise ValueError("layer_types does not name num_hidden_layers layers")
    e, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    if cfg["mamba_n_heads"] * cfg["mamba_d_head"] != cfg["mamba_expand"] * e:
        raise ValueError("mamba_n_heads x mamba_d_head != mamba_expand x "
                         "hidden_size")
    return TransformerConfig(
        vocab_size=cfg["vocab_size"], num_layers=cfg["num_hidden_layers"],
        layer_types=tuple(cfg["layer_types"]),
        num_heads=heads, num_kv_heads=cfg["num_key_value_heads"],
        head_dim=e // heads, embed_dim=e,
        mlp_dim=cfg["shared_intermediate_size"],
        max_seq_len=cfg["max_position_embeddings"], rotary=False,
        attention_scale=float(cfg["attention_multiplier"]),
        tie_embeddings=True,
        embedding_multiplier=float(cfg["embedding_multiplier"]),
        residual_multiplier=float(cfg["residual_multiplier"]),
        logits_scaling=float(cfg["logits_scaling"]),
        norm_eps=cfg["rms_norm_eps"],
        mamba_heads=cfg["mamba_n_heads"], mamba_head_dim=cfg["mamba_d_head"],
        mamba_state_dim=cfg["mamba_d_state"],
        mamba_groups=cfg["mamba_n_groups"],
        mamba_conv_width=cfg["mamba_d_conv"],
        mamba_chunk=cfg["mamba_chunk_size"],
        dtype=jnp.bfloat16, logits_dtype=jnp.bfloat16,
        remat=bool(traffic["remat"]),
        attention_fn=hvd.make_flash_attention())


def to_reference(tree: dict, cfg: dict) -> dict:
    """The program's parameter (or gradient) tree in the reference's
    layout: reshapes only, so it serves gradients as it serves weights.  The
    program keeps gate and up apart; the checkpoint's ``input_linear`` is
    the two stacked, gate first."""
    p = tree["params"]
    e = cfg["hidden_size"]
    layers = []
    for i, kind in enumerate(cfg["layer_types"]):
        lay = p[f"layer_{i}"]
        m = lay["mlp"]
        out = {"post_attention_layernorm": lay["mlp_norm"]["scale"],
               "gate_proj": m["gate"]["kernel"], "up_proj": m["up"]["kernel"],
               "down_proj": m["down"]["kernel"]}
        if kind == "attention":
            a = lay["attn"]
            out.update({
                "input_layernorm": lay["attn_norm"]["scale"],
                "q_proj": a["q"]["kernel"].reshape(e, -1),
                "k_proj": a["k"]["kernel"].reshape(e, -1),
                "v_proj": a["v"]["kernel"].reshape(e, -1),
                "o_proj": a["o"]["kernel"].reshape(-1, e)})
        else:
            s = lay["mamba"]
            out.update({
                "input_layernorm": lay["mamba_norm"]["scale"],
                "in_proj": s["in_proj"]["kernel"],
                "conv_weight": s["conv_kernel"], "conv_bias": s["conv_bias"],
                "dt_bias": s["dt_bias"], "A_log": s["A_log"], "D": s["D"],
                "mamba_norm": s["norm"]["scale"],
                "out_proj": s["out_proj"]["kernel"]})
        layers.append(out)
    return {"embed_tokens": p["embed"]["embedding"], "layers": layers,
            "norm": p["final_norm"]["scale"]}


def build(cfg: dict, traffic: dict, chips: int, seed: int) -> Built:
    seq, per_chip = int(traffic["seq_len"]), int(traffic["per_chip"])
    mcfg = model_config(cfg, traffic)   # leaves, on a program without mamba
    from horovod_tpu.models.mamba import ssm_plan

    print(f"ssm: {json.dumps(ssm_plan(mcfg, seq))}")
    model = Transformer(mcfg)
    replicated = hvd.replicated_sharding()

    def loss_fn(params, tokens):
        logits = model.apply(params, tokens)
        return hvd.softmax_cross_entropy(logits[:, :-1], tokens[:, 1:]).mean()

    o = dict(traffic["optimizer"])
    opt = hvd.DistributedOptimizer(getattr(optax, o.pop("name"))(**o))

    def step_with(opt, state, tokens):
        """One optimizer step through ``opt``.  The timed step and the
        comparison's are both this function; they differ in ``opt`` alone."""
        params, opt_state = state
        loss, grads = jax.value_and_grad(loss_fn)(params, tokens)
        updates, opt_state = opt.update(grads, opt_state, params)
        # the mean over every chip's sequences, not this chip's own
        return (optax.apply_updates(params, updates), opt_state), \
            hvd.allreduce(loss), updates

    def train_step(state, tokens):
        return step_with(opt, state, tokens)[:2]

    step = jax.jit(
        hvd.shard(train_step, in_specs=(P(), hvd.batch_spec(2)),
                  out_specs=(P(), P())),
        donate_argnums=(0,))

    def init_model():
        key = jax.random.fold_in(jax.random.PRNGKey(seed >> 31),
                                 seed & 0x7FFFFFFF)
        return jax.jit(model.init, out_shardings=replicated)(
            key, jnp.zeros((1, 128), jnp.int32))

    def init_train(params):
        return params, jax.jit(opt.init, out_shardings=replicated)(params)

    pool = streams.make_pool(traffic["stream"], seed, per_chip * chips,
                             seq_len=seq, vocab=cfg["vocab_size"])

    def compare_with_reference(params) -> list[dict]:
        return _compare(cfg, traffic, params, step_with, model, pool, chips)

    tokens_a_chip = per_chip * seq
    remat = bool(traffic["remat"])
    return Built(
        init_model=init_model, init_train=init_train, step=step, pool=pool,
        batch_shardings=(hvd.data_sharding(2),),
        units_per_call=tokens_a_chip * chips, steps_per_call=1,
        flops_per_unit=flops_ssm.hybrid_lm_train_flops_per_token(cfg, seq),
        compare=compare_with_reference,
        flash_calls=flops_ssm.flash_calls(cfg, per_chip, seq),
        notes={"head_share_of_flops":
               flops_ssm.hybrid_lm_head_share(cfg, seq),
               "ssd_scan_flops_per_step_a_chip":
               flops_ssm.ssd_scan_step_flops(cfg, tokens_a_chip, remat),
               "ssd_scan_bytes_per_step_a_chip":
               flops_ssm.ssd_scan_step_bytes(cfg, tokens_a_chip, remat)})


def _compare(cfg, traffic, params, step_with, model, pool, chips
             ) -> list[dict]:
    """Loss and gradients on one sequence of ``compare_seq_len`` tokens a
    chip (several of the program's chunks, so the state's hand-over is
    compared; the reference uses no chunks), against the reference's mean
    over the same sequences.  The program's side is the timed step's own
    function, ``step_with``, under the same ``hvd.shard``, with
    ``hvd.DistributedOptimizer(optax.sgd(1.0))`` in the optimizer's place:
    plain SGD at rate 1 makes the update the negated gradient as
    ``DistributedOptimizer`` averaged it over the chips.  At a longer
    context also the logits of the last ``compare_last`` positions against
    the whole context, the reference by the sequential recurrence."""
    seq, last = int(traffic["seq_len"]), int(traffic["compare_last"])
    n = min(seq, int(traffic["compare_seq_len"]))
    tokens = np.ascontiguousarray(pool[0][0][:chips, :n])
    probe = hvd.DistributedOptimizer(optax.sgd(1.0))

    def grads_fn(p, t):
        _, loss, updates = step_with(probe, (p, probe.init(p)), t)
        return loss, to_reference(jax.tree.map(jnp.negative, updates), cfg)

    loss, grads = jax.jit(hvd.shard(
        grads_fn, in_specs=(P(), hvd.batch_spec(2)),
        out_specs=(P(), P())))(params, tokens)
    ref_loss, ref_grads = compare.mean_over(
        jax.jit(lambda p, row: reference.loss_and_grads(
            to_reference(p, cfg), row, cfg)),
        [(params, row) for row in tokens])
    ref_loss = float(ref_loss)
    checks = [
        compare.check("loss", abs(float(loss) - ref_loss) / abs(ref_loss),
                      LOSS_TOL),
        compare.check_tree("grads_from_distributed_optimizer", grads,
                           ref_grads, GRAD_TOL)]
    del grads, ref_grads
    if seq > n:
        row = np.ascontiguousarray(pool[0][0][:1])
        got = jax.jit(lambda p, t: model.apply(p, t)[0, -last:])(params, row)
        want = jax.jit(lambda p, t: reference.logits_last(
            to_reference(p, cfg), t, cfg, last=last, query_block=1024))(
            params, row[0])
        checks.append(compare.check(
            f"logits_last{last}_of_{seq}",
            compare.relative_l2(got, want), LOGITS_TOL))
    return checks
