"""Family ``decoder_lm``: a Llama-style decoder trained through the path a
user takes -- ``hvd.DistributedOptimizer(optax...)`` inside
``jax.jit(hvd.shard(step), donate...)``, ``models/transformer.py`` with the
flash kernels of ``ops/flash_attention.py`` at the library's default tiles.

The configuration file holds Hugging Face's keys; this module maps them onto
``TransformerConfig`` and refuses what the program cannot express.
"""

from __future__ import annotations


import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import PartitionSpec as P

import horovod_tpu as hvd
from horovod_tpu.models import Transformer, TransformerConfig

from benchmarks import compare, flops, streams
from benchmarks.built import Built
from benchmarks.reference import decoder_lm as reference

# Tolerances of the reference comparison.  The program multiplies in bf16
# (8 bits of mantissa, 2**-8 = 0.4% a rounding) with f32 accumulation and
# keeps bf16 logits; the reference is f32 at "highest".  Roundings compound
# over the layers as a random walk.  Measured on the chip at the published
# widths and six layers, 26 runs of 7 seeds (PERF.md, PR 23): the loss
# differs by 6e-6 to 2.2e-4 relative, the worst gradient leaf (a k or q
# projection) by 1.9-2.2%, the long-context logits by 1.1-1.3% of their
# norm.  The bounds leave a factor of two to five.  A product formed in an
# 8-bit float (2**-4 a rounding, sixteen times bf16's) or a dropped term
# lands far outside.
LOSS_TOL = 1e-3
GRAD_TOL = 0.05
LOGITS_TOL = 0.03


def model_config(cfg: dict, traffic: dict) -> TransformerConfig:
    heads = cfg["num_attention_heads"]
    if cfg["num_key_value_heads"] != heads:
        raise ValueError("decoder_lm builds multi-head attention: as many KV "
                         "heads as query heads (families/hybrid_lm.py "
                         "passes num_kv_heads); this configuration has not")
    if cfg["rms_norm_eps"] != 1e-6:
        raise ValueError("models/transformer.py fixes RMSNorm's eps at 1e-6")
    if cfg.get("tie_word_embeddings"):
        raise ValueError("models/transformer.py does not tie the head")
    return TransformerConfig(
        vocab_size=cfg["vocab_size"], num_layers=cfg["num_hidden_layers"],
        num_heads=heads, head_dim=cfg["hidden_size"] // heads,
        embed_dim=cfg["hidden_size"], mlp_dim=cfg["intermediate_size"],
        max_seq_len=cfg["max_position_embeddings"],
        rope_theta=float(cfg["rope_theta"]), dtype=jnp.bfloat16,
        logits_dtype=jnp.bfloat16, remat=bool(traffic["remat"]),
        attention_fn=hvd.make_flash_attention())


def to_reference(tree: dict, cfg: dict) -> dict:
    """The program's parameter (or gradient) tree in the reference's
    layout: reshapes only, so it serves gradients as it serves weights."""
    p = tree["params"]
    e = cfg["hidden_size"]
    layers = []
    for i in range(cfg["num_hidden_layers"]):
        lay = p[f"layer_{i}"]
        a, m = lay["attn"], lay["mlp"]
        layers.append({
            "input_layernorm": lay["attn_norm"]["scale"],
            "q_proj": a["q"]["kernel"].reshape(e, -1),
            "k_proj": a["k"]["kernel"].reshape(e, -1),
            "v_proj": a["v"]["kernel"].reshape(e, -1),
            "o_proj": a["o"]["kernel"].reshape(-1, e),
            "post_attention_layernorm": lay["mlp_norm"]["scale"],
            "gate_proj": m["gate"]["kernel"], "up_proj": m["up"]["kernel"],
            "down_proj": m["down"]["kernel"]})
    return {"embed_tokens": p["embed"]["embedding"], "layers": layers,
            "norm": p["final_norm"]["scale"],
            "lm_head": p["lm_head"]["kernel"]}


def build(cfg: dict, traffic: dict, chips: int, seed: int) -> Built:
    seq, per_chip = int(traffic["seq_len"]), int(traffic["per_chip"])
    mcfg = model_config(cfg, traffic)
    model = Transformer(mcfg)
    replicated = hvd.replicated_sharding()
    scaling = cfg.get("rope_scaling")
    if scaling and scaling["type"] != "linear":
        raise ValueError(f"only linear rope scaling can be passed to the "
                         f"program as positions, not {scaling}")
    rope_div = float(scaling["factor"]) if scaling else 1.0

    def apply(params, tokens):
        # Linear rope scaling is position / factor; the program takes
        # positions as an argument, so nothing in it is patched.
        positions = jnp.arange(tokens.shape[1], dtype=jnp.float32) / rope_div
        return model.apply(params, tokens, positions=positions)

    def loss_fn(params, tokens):
        logits = apply(params, tokens)
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
        return _compare(cfg, traffic, params, step_with, apply, pool, chips)

    heads, d = mcfg.num_heads, mcfg.head_dim
    return Built(
        init_model=init_model, init_train=init_train, step=step, pool=pool,
        batch_shardings=(hvd.data_sharding(2),),
        units_per_call=per_chip * chips * seq, steps_per_call=1,
        flops_per_unit=flops.decoder_lm_train_flops_per_token(cfg, seq),
        compare=compare_with_reference,
        flash_calls=[dict(b=per_chip, h=heads, s=seq, d=d, causal=True)]
        * mcfg.num_layers,
        notes={"head_share_of_flops": flops.decoder_lm_head_share(cfg, seq)})


def _compare(cfg, traffic, params, step_with, apply, pool, chips
             ) -> list[dict]:
    """Loss and gradients on one sequence of ``compare_seq_len`` tokens a chip
    (what the reference can hold the backward of), against the reference's
    mean over the same sequences.  The program's side is the timed step's
    own function, ``step_with``, under the same ``hvd.shard``, with
    ``hvd.DistributedOptimizer(optax.sgd(1.0))`` in the optimizer's place:
    plain SGD at rate 1 makes the update the negated gradient as
    ``DistributedOptimizer`` averaged it over the chips, so what is compared
    is what the step's optimizer is given.  At a longer context also the
    logits of the last ``compare_last`` positions against the whole
    context."""
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
        got = jax.jit(lambda p, t: apply(p, t)[0, -last:])(
            params, row)
        want = jax.jit(lambda p, t: reference.logits_last(
            to_reference(p, cfg), t, cfg, last=last, query_block=1024))(
            params, row[0])
        checks.append(compare.check(
            f"logits_last{last}_of_{seq}",
            compare.relative_l2(got, want), LOGITS_TOL))
    return checks
