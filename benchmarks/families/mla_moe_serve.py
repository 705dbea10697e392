"""Family ``mla_moe_serve``: an ``axk1`` decoder (SK Telecom's A.X-K1: latent
attention in every layer, one leading dense layer, then sigmoid-routed
experts with a routed scale beside one shared expert, an untied head) served
through the path a user takes -- ``horovod_tpu.serving.ServingEngine`` over
``TransformerBackend``, whose pool is the model's cache of latents, whose
prefill runs latent attention expanded through the flash forward kernel
(keys of 192, values of 128) and whose decode runs it absorbed over the
cached latents -- weights and compute in bfloat16, greedy tokens, no EOS.

The chip holds ONE CHIP'S SHARE of an expert-parallel stage, as
``families/cohere2_moe_serve.py`` does (this family takes that one's timing
wrapper, sampling of the finished requests and judgement of a token from
it): every head, the shared expert, the router's every output, the routed
experts the configuration's ``experts_held`` names, an eighth of the
vocabulary.  The reference is given the same share.

The configuration file holds Hugging Face's keys; this module maps them onto
``TransformerConfig`` and refuses what the program cannot express.  The
weights are the benchmark's own: drawn here from ``--seed``, a layer a
jitted call, in the type they are served in, handed to the program in its
layout and, drawn again after the window, to the plain reference in the
reference's.
"""

from __future__ import annotations

import functools
import json
import statistics
import time

import jax
import jax.numpy as jnp
import numpy as np

from horovod_tpu.models import Transformer, TransformerConfig
from horovod_tpu.models.transformer import init_kv_cache
from horovod_tpu.serving import ServingConfig, ServingEngine
from horovod_tpu.serving.engine import TransformerBackend

from benchmarks import compare, scopes
from benchmarks.families import cohere2_moe_serve as sparse
from benchmarks.reference import mla_moe_serve as reference

seed_key, layer_key = sparse.seed_key, sparse.layer_key

# The one number of the comparison, as families/cohere2_moe_serve.py has it:
# over a sample of the requests the window finished, the widest gap by which
# a served token's logit lies below the reference's best at its position, in
# units of that position's standard deviation over the vocabulary.  The
# reference is given the tokens and nothing else the program made; it routes
# every position by its own picks and computes latent attention expanded,
# where the program decoded absorbed from a bfloat16 cache of latents over
# up to 16896 positions.  Read on the chip at the cell's own size (PR 42,
# PERF.md section 6): sound runs 0.177-0.610 over 24 seeds (mean 0.31, two
# past 0.48; 1560-2340 served tokens a reading); the float8 control through
# this same comparison 4.06-4.52 over 3 seeds, not correct on any.  The
# limit is near the geometric middle (1.57): 2.5 times above the largest
# sound reading, 2.7 times below the smallest control.
GAP_LIMIT = 1.5


def model_config(cfg: dict, traffic: dict) -> TransformerConfig:
    refused = {
        "model_type": "axk1", "hidden_act": "silu", "attention_bias": False,
        "scoring_func": "sigmoid", "topk_method": "none",
        "tie_word_embeddings": False, "moe_layer_freq": 1,
        "num_key_value_heads": cfg.get("num_attention_heads")}
    wrong = {k: cfg.get(k) for k, v in refused.items() if cfg.get(k) != v}
    if cfg.get("rope_scaling", {}).get("type") != "yarn":
        wrong["rope_scaling.type"] = cfg.get("rope_scaling", {}).get("type")
    if wrong:
        raise ValueError(f"mla_moe_serve builds {refused} and yarn; the "
                         f"configuration says {wrong}")
    lo, hi = cfg["experts_held"]
    if hi - lo != cfg["n_routed_experts"]:
        raise ValueError("n_routed_experts counts the experts HELD "
                         "(experts_held); the published count is "
                         "n_routed_experts_published")
    y = cfg["rope_scaling"]
    layers = cfg["num_hidden_layers"]
    # a checkout before PR 42 has no such fields and says so (a TypeError)
    return TransformerConfig(
        vocab_size=cfg["vocab_size"], num_layers=layers,
        layer_types=("latent_attention",) * layers,
        num_heads=cfg["num_attention_heads"], embed_dim=cfg["hidden_size"],
        q_lora_rank=cfg["q_lora_rank"], kv_lora_rank=cfg["kv_lora_rank"],
        qk_nope_head_dim=cfg["qk_nope_head_dim"],
        qk_rope_head_dim=cfg["qk_rope_head_dim"],
        v_head_dim=cfg["v_head_dim"], rope_theta=float(cfg["rope_theta"]),
        rope_interleaved=True,
        rope_yarn=(y["factor"], y["original_max_position_embeddings"],
                   y["beta_fast"], y["beta_slow"], y["mscale"],
                   y["mscale_all_dim"]),
        norm_eps=float(cfg["rms_norm_eps"]), mlp_dim=cfg["intermediate_size"],
        first_dense_layers=cfg["first_k_dense_replace"],
        moe_mlp_dim=cfg["moe_intermediate_size"],
        num_experts=cfg["n_routed_experts_published"],
        experts_per_token=cfg["num_experts_per_tok"],
        norm_topk_prob=bool(cfg["norm_topk_prob"]), moe_selection="sigmoid",
        moe_routed_scale=float(cfg["routed_scaling_factor"]),
        num_shared_experts=cfg["n_shared_experts"], experts_held=(lo, hi),
        feed_forward_chunk=cfg.get("feed_forward_chunk"),
        max_seq_len=int(traffic["max_seq_len"]), dtype=jnp.bfloat16,
        param_dtype=jnp.bfloat16)


def draw_layer(cfg: dict, dense: bool, key) -> dict:
    """One layer's weights (a leading ``dense`` one, or a sparse one) in the
    reference's layout, bfloat16: normal with the ``assumed``
    initializer_range, the norms' scales at 1."""
    e, h = cfg["hidden_size"], cfg["num_attention_heads"]
    rq, rkv = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    nope, rot, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                     cfg["v_head_dim"])
    normal = sparse._normal(float(cfg["initializer_range"]))
    ones = lambda n: jnp.ones((n,), jnp.bfloat16)  # noqa: E731
    k = iter(jax.random.split(key, 12))
    w = {"input_layernorm": ones(e), "post_attention_layernorm": ones(e),
         "q_a_proj": normal(next(k), e, rq), "q_a_layernorm": ones(rq),
         "q_b_proj": normal(next(k), rq, h * (nope + rot)),
         "kv_a_proj_with_mqa": normal(next(k), e, rkv + rot),
         "kv_a_layernorm": ones(rkv),
         "kv_b_proj": normal(next(k), rkv, h * (nope + dv)),
         "o_proj": normal(next(k), h * dv, e)}
    if dense:
        f = cfg["intermediate_size"]
        w["mlp"] = {"gate_proj": normal(next(k), e, f),
                    "up_proj": normal(next(k), e, f),
                    "down_proj": normal(next(k), f, e)}
        return w
    held, f = cfg["n_routed_experts"], cfg["moe_intermediate_size"]
    nf = cfg["n_shared_experts"] * f
    w["router"] = normal(next(k), e, cfg["n_routed_experts_published"])
    w["experts"] = {"gate_proj": normal(next(k), held, e, f),
                    "up_proj": normal(next(k), held, e, f),
                    "down_proj": normal(next(k), held, f, e)}
    w["shared_experts"] = {"gate_proj": normal(next(k), e, nf),
                           "up_proj": normal(next(k), e, nf),
                           "down_proj": normal(next(k), nf, e)}
    return w


def layer_to_program(w: dict, cfg: dict) -> dict:
    """One layer as ``models/transformer.py`` lays it out: reshapes alone."""
    h = cfg["num_attention_heads"]
    kernel = lambda x, *shape: {  # noqa: E731
        "kernel": x.reshape(x.shape[0], *shape) if shape else x}
    out = {"attn_norm": {"scale": w["input_layernorm"]},
           "mlp_norm": {"scale": w["post_attention_layernorm"]},
           "attn": {"q_down": kernel(w["q_a_proj"]),
                    "q_norm": {"scale": w["q_a_layernorm"]},
                    "q_up": kernel(w["q_b_proj"], h, -1),
                    "kv_down": kernel(w["kv_a_proj_with_mqa"]),
                    "kv_norm": {"scale": w["kv_a_layernorm"]},
                    "kv_up": w["kv_b_proj"].reshape(
                        w["kv_b_proj"].shape[0], h, -1),
                    "o": {"kernel": w["o_proj"].reshape(
                        h, -1, w["o_proj"].shape[-1])}}}
    if "mlp" in w:
        out["mlp"] = {n: kernel(w["mlp"][f"{n}_proj"])
                      for n in ("gate", "up", "down")}
        return out
    ex, sh = w["experts"], w["shared_experts"]
    out["moe_mlp"] = {"router": w["router"], "gate": ex["gate_proj"],
                      "up": ex["up_proj"], "down": ex["down_proj"],
                      "shared_gate": sh["gate_proj"],
                      "shared_up": sh["up_proj"],
                      "shared_down": sh["down_proj"]}
    return out


def _drawn(cfg: dict, key, lay):
    """(embedding, head, [lay(layer's weights)], final norm's scale): a
    layer a jitted call, so that no layer lies on the chip in two layouts at
    once."""
    normal = sparse._normal(float(cfg["initializer_range"]))
    v, e = cfg["vocab_size"], cfg["hidden_size"]
    top = jax.random.split(jax.random.fold_in(key, 0))
    layer = jax.jit(lambda k, dense: lay(draw_layer(cfg, dense, k)),
                    static_argnums=1)
    return (jax.jit(lambda k: normal(k, v, e))(top[0]),
            jax.jit(lambda k: normal(k, e, v))(top[1]),
            [layer(layer_key(key, i), i < cfg["first_k_dense_replace"])
             for i in range(cfg["num_hidden_layers"])],
            jnp.ones((e,), jnp.bfloat16))


def draw(cfg: dict, key) -> dict:
    """The weights in the reference's layout (reference/mla_moe_serve.py)."""
    embedding, head, layers, norm = _drawn(cfg, key, lambda w: w)
    return {"embed_tokens": embedding, "lm_head": head, "layers": layers,
            "norm": norm}


def to_program(w: dict, cfg: dict) -> dict:
    return {"params": {
        "embed": {"embedding": w["embed_tokens"]},
        "lm_head": {"kernel": w["lm_head"]},
        "final_norm": {"scale": w["norm"]},
        **{f"layer_{i}": layer_to_program(layer, cfg)
           for i, layer in enumerate(w["layers"])}}}


def program_params(cfg: dict, key) -> dict:
    """The seed's weights in the program's layout."""
    embedding, head, layers, norm = _drawn(
        cfg, key, lambda w: layer_to_program(w, cfg))
    return {"params": {"embed": {"embedding": embedding},
                       "lm_head": {"kernel": head},
                       "final_norm": {"scale": norm},
                       **{f"layer_{i}": w for i, w in enumerate(layers)}}}


def serve(cfg: dict, traffic: dict, chips: int, seed: int
          ) -> sparse.ServedSparse:
    if chips != 1:
        raise ValueError("mla_moe_serve serves one data-parallel replica of "
                         "the expert-parallel group on one chip")
    mcfg = model_config(cfg, traffic)
    model = Transformer(mcfg)
    slots, max_len = int(traffic["num_slots"]), int(traffic["max_seq_len"])
    buckets = tuple(int(b) for b in traffic["prefill_buckets"])
    params = program_params(cfg, seed_key(seed))
    shapes = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                          params)
    n_params = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes))
    backend = TransformerBackend(model, params, mcfg, slots, max_len)
    del params
    timed = sparse.TimedSparse(backend)
    engine = ServingEngine(
        timed, ServingConfig(num_slots=slots, buckets=buckets,
                             max_seq_len=max_len, eos_id=None),
        clock=time.perf_counter)
    pool = jax.eval_shape(lambda: init_kv_cache(mcfg, slots, max_len))
    per_token = sum(int(np.prod(p.shape[3:])) * p.dtype.itemsize
                    for p in pool) * mcfg.num_layers
    notes: dict = {"flash_prefill": backend.flash_prefill}
    sparse_layers = cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]
    plan = {"experts": cfg["n_routed_experts_published"],
            "experts_held": cfg["n_routed_experts"],
            "held_from": cfg["experts_held"][0],
            "experts_per_token": cfg["num_experts_per_tok"],
            "shared_experts": cfg["n_shared_experts"],
            "selection": cfg["scoring_func"],
            "norm_topk_prob": cfg["norm_topk_prob"],
            "routed_scale": cfg["routed_scaling_factor"],
            "layers": {"dense": cfg["first_k_dense_replace"],
                       "sparse": sparse_layers}, "slots": slots}
    latent = {"cache": {"latent": mcfg.kv_lora_rank,
                        "rotary_key": mcfg.qk_rope_head_dim,
                        "bytes_per_token": per_token,
                        "pool_bytes": per_token * slots * max_len},
              "form": {"prefill": "expanded", "decode": "absorbed"},
              "prefill_attention": {b: backend.prefill_attention(b)
                                    for b in buckets},
              "feed_forward_chunk": mcfg.feed_forward_chunk,
              "prefill_chunks": {b: backend.prefill_chunks(b)
                                 for b in buckets}}

    def warm() -> None:
        def ids(n: int) -> list[int]:
            return [int(t) for t in np.arange(n) % cfg["vocab_size"]]

        for b in buckets:               # compiles each bucket, and decode
            engine.submit(ids(min(b, max_len - 4)), 3)
        engine.run_until_idle()
        # unloaded, on the programs now compiled: what the mix's two limits
        # were set from, read again in every run
        del timed.log[:]
        for b in buckets:
            engine.submit(ids(min(b, max_len - 4)), 2)
            engine.run_until_idle()
        notes["unloaded_prefill_ms_by_bucket"] = {
            e[3]: round(1e3 * (e[2] - e[1]), 3) for e in timed.log
            if e[0] == "prefill"}
        notes["unloaded_ttft_ms_longest_bucket"] = notes[
            "unloaded_prefill_ms_by_bucket"][buckets[-1]]
        for _ in range(slots):
            engine.submit(ids(buckets[0]), 10)
        engine.run_until_idle()
        full = [1e3 * (e[2] - e[1]) for e in timed.log
                if e[0] == "decode" and e[3] == slots]
        notes["unloaded_decode_ms_every_slot_full"] = statistics.median(full)

    def release() -> None:
        # of every call since the programs were built, warm-up and all
        print("moe: " + json.dumps({
            **plan, **backend.moe_counters,
            "held_pair_share_pct": 100.0 * backend.moe_counters["held_pairs"]
            / max(backend.moe_counters["pairs"], 1)}))
        print("mla: " + json.dumps(latent))
        backend.kk = backend.vv = backend.params = None

    def decode_scopes():
        i32 = jax.ShapeDtypeStruct((slots,), jnp.int32)
        return scopes.table_of(
            backend._decode.lower(shapes, *pool, i32, i32).compile())

    def prefill_scopes(bucket: int):
        padded = jax.ShapeDtypeStruct((1, bucket), jnp.int32)
        return scopes.table_of(
            backend._prefill.lower(shapes, *pool, padded, 1, 0).compile())

    return sparse.ServedSparse(
        engine=engine, warm=warm, release=release,
        compare=functools.partial(compare_served, cfg, traffic),
        vocab_size=cfg["vocab_size"], parameters=n_params, num_slots=slots,
        kv_bytes_per_token=per_token,
        program_names={"decode": "jit__decode_fn",
                       "prefill": "jit__prefill_fn"},
        decode_scopes=decode_scopes, notes=notes,
        prefill_scopes=prefill_scopes)


def reference_rows(cfg: dict, traffic: dict, weights, prompt, served,
                   operand_dtype=None):
    """The reference's logits [T, V] at the positions that predict the
    served tokens of one request, T = len(served)."""
    rows = int(traffic["arrivals"]["output_tokens"]["max"])
    max_len = int(traffic["max_seq_len"])
    seq = np.concatenate([prompt, served]).astype(np.int32)
    block = max(max_len // 128, 1)  # queries a block; the pads are multiples
    pad = next(p for p in (32 * block, 64 * block, 128 * block)
               if p >= max(len(seq), rows + 1))
    padded = np.zeros(pad, np.int32)
    padded[:len(seq)] = seq
    first = len(prompt) - 1             # the row that predicts served[0]
    start = min(first, pad - rows)
    logits = _rows(cfg, pad, rows, block, operand_dtype)(
        weights, padded, start)
    return logits[first - start:first - start + len(served)]


_ROWS_PROGRAMS: dict = {}


def _rows(cfg, pad, rows, block, operand_dtype):
    key = (pad, rows, block, operand_dtype, json.dumps(
        {k: v for k, v in cfg.items()
         if isinstance(v, (int, float, list))}, sort_keys=True))
    if key not in _ROWS_PROGRAMS:
        _ROWS_PROGRAMS[key] = jax.jit(
            lambda w, t, s: reference.logits_of_rows(
                w, t, cfg, tuple(cfg["experts_held"]), s, rows,
                query_block=block, operand_dtype=operand_dtype)[0])
    return _ROWS_PROGRAMS[key]


def compare_served(cfg, traffic, finished, seed, control=None) -> list[dict]:
    """The comparison of a run, as ``cohere2_moe_serve.compare_served``:
    ``control`` is None in every run of the benchmark (the tokens compared
    are the ones the window served); given an operand type
    (``benchmarks/control.py`` and the tests give ``jnp.float8_e4m3fn``, the
    step below the configuration's bfloat16), the reference computed with
    operands of that type stands in the program's place."""
    chosen = sparse.sample(finished, seed, int(traffic["compare_requests"]),
                           int(traffic["max_seq_len"]))
    weights = draw(cfg, seed_key(seed))
    widest, tokens = 0.0, 0
    for prompt, served in chosen:
        if control is None:
            judged = jnp.asarray(served, jnp.int32)
        else:
            judged = jnp.argmax(reference_rows(
                cfg, traffic, weights, prompt, served,
                operand_dtype=control), axis=-1).astype(jnp.int32)
        logits = reference_rows(cfg, traffic, weights, prompt, served)
        widest = max(widest, float(jnp.max(
            sparse.gaps_below_best(logits, judged))))
        tokens += len(served)
    # nothing finished is nothing shown: a reading no limit admits
    out = compare.check("served_token_gap_below_reference_best",
                        widest if chosen else 1e9, GAP_LIMIT)
    out["requests"], out["tokens"] = len(chosen), tokens
    out["longest"] = max((len(p) + len(s) for p, s in chosen), default=0)
    return [out]
