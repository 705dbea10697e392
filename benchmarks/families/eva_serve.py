"""Family ``eva_serve``: an ``evabyte`` decoder (EvaByte: a byte-level model,
EVA attention in every layer -- exact softmax terms inside a window of 2048
positions beside one learned summary a chunk of 16 for every window behind
it -- SwiGLU, RMSNorm with a unit offset, a float32 residual stream, a head
of eight byte predictions) served through the path a user takes:
``horovod_tpu.serving.ServingEngine`` over ``TransformerBackend``, whose
pool is the model's cache of a window ring and chunk summaries, whose
prefill runs the merged form (two partial attentions of the flash forward
kernel a window, joined by their log-sum-exp) and whose decode step writes a
ring row, closes a chunk every 16th position and attends over ring and
summaries -- weights and matmul operands in bfloat16, greedy bytes from head
0, no EOS.

The chip holds the first of two pipeline stages (16 of 32 layers) and, so
that a byte can be sampled, the final norm and the head.  The reference is
given the same.  Lengths are bytes: the traffic draws ids over the 320.

The configuration file holds Hugging Face's keys; this module maps them onto
``TransformerConfig`` and refuses what the program cannot express.  The
weights are the benchmark's own: drawn here from ``--seed``, a layer a
jitted call, in the type they are served in, handed to the program in its
layout and, drawn again after the window, to the plain reference in the
reference's.  Timing of a call, the sample of the finished requests and the
judgement of a token are ``families/cohere2_moe_serve.py``'s.
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

from benchmarks import compare, scopes, serving
from benchmarks.families import cohere2_moe_serve as shared
from benchmarks.reference import eva_serve as reference

seed_key, layer_key = shared.seed_key, shared.layer_key

# The one number of the comparison, as the other served families have it:
# over a sample of the requests the window finished, the widest gap by which
# a served byte's logit lies below the reference's best at its position, in
# units of that position's standard deviation over the 320 logits of head 0
# (the head the byte was sampled from).  The reference is given the bytes
# and nothing else the program made; it computes every position under one
# mask in float32, where the program prefilled through merged partial
# attentions and decoded from a bfloat16 ring and bfloat16 summaries.
# At init_std 0.01275 a position's logits spread by about 0.8 (unit-RMS
# hidden states times 4096 weights of 0.01275), less than half of what
# A.X-K1's 0.02 over 7168 gives, so that family's limit is not taken over:
# this one is set from its own two readings on the chip, at the cell's own
# size (PERF.md section 6, PR 44): sound runs 0.016-0.034 over 24 seeds
# (640-1290 served bytes a reading, the 31744-byte prompt among the eight
# requests); the float8 control through this same comparison 5.00-5.52 over
# 3 seeds, not correct on any.  The limit is at the geometric middle of the
# two ends (0.41): about twelve times above the largest sound reading and
# twelve times below the smallest control.
GAP_LIMIT = 0.4


def model_config(cfg: dict, traffic: dict) -> TransformerConfig:
    refused = {
        "model_type": "evabyte", "attention_class": "eva",
        "hidden_act": "silu", "attention_bias": False,
        "tie_word_embeddings": False, "rope_scaling": None,
        "norm_add_unit_offset": True, "fp32_skip_add": True,
        "fp32_logits": True,
        "num_key_value_heads": cfg.get("num_attention_heads")}
    wrong = {k: cfg.get(k) for k, v in refused.items() if cfg.get(k) != v}
    if wrong:
        raise ValueError(f"eva_serve builds {refused}; the configuration "
                         f"says {wrong}")
    heads, layers = cfg["num_attention_heads"], cfg["num_hidden_layers"]
    # a checkout before PR 44 has no such fields and says so (a TypeError)
    return TransformerConfig(
        vocab_size=cfg["vocab_size"], num_layers=layers,
        layer_types=("eva_attention",) * layers, num_heads=heads,
        head_dim=cfg["hidden_size"] // heads, embed_dim=cfg["hidden_size"],
        mlp_dim=cfg["intermediate_size"],
        eva_window=cfg["window_size"], eva_chunk=cfg["chunk_size"],
        num_pred_heads=cfg["num_pred_heads"], norm_offset=1.0,
        residual_dtype=jnp.float32, rope_theta=float(cfg["rope_theta"]),
        norm_eps=float(cfg["rms_norm_eps"]),
        feed_forward_chunk=cfg.get("feed_forward_chunk"),
        max_seq_len=int(traffic["max_seq_len"]), dtype=jnp.bfloat16,
        param_dtype=jnp.bfloat16)


def draw_layer(cfg: dict, key) -> dict:
    """One layer's weights in the reference's layout, bfloat16: normal at
    ``init_std``, phi and mu the same normal clamped to one ``init_std``,
    the norms' offsets 0."""
    e, f, h = (cfg["hidden_size"], cfg["intermediate_size"],
               cfg["num_attention_heads"])
    std = float(cfg["init_std"])
    normal = shared._normal(std)
    zeros = jnp.zeros((e,), jnp.bfloat16)
    clamped = lambda key: jnp.clip(  # noqa: E731
        normal(key, h, e // h), -std, std)
    k = iter(jax.random.split(key, 9))
    return {"input_layernorm": zeros, "post_attention_layernorm": zeros,
            "q_proj": normal(next(k), e, e), "k_proj": normal(next(k), e, e),
            "v_proj": normal(next(k), e, e), "o_proj": normal(next(k), e, e),
            "adaptive_phi": clamped(next(k)),
            "adaptive_mu_k": clamped(next(k)),
            "gate_proj": normal(next(k), e, f),
            "up_proj": normal(next(k), e, f),
            "down_proj": normal(next(k), f, e)}


def layer_to_program(w: dict, cfg: dict) -> dict:
    """One layer as ``models/transformer.py`` lays it out: reshapes alone."""
    h = cfg["num_attention_heads"]
    heads = lambda x: {"kernel": x.reshape(x.shape[0], h, -1)}  # noqa: E731
    return {"attn_norm": {"scale": w["input_layernorm"]},
            "mlp_norm": {"scale": w["post_attention_layernorm"]},
            "attn": {"q": heads(w["q_proj"]), "k": heads(w["k_proj"]),
                     "v": heads(w["v_proj"]),
                     "o": {"kernel": w["o_proj"].reshape(
                         h, -1, w["o_proj"].shape[-1])},
                     "phi": w["adaptive_phi"], "mu": w["adaptive_mu_k"]},
            "mlp": {n: {"kernel": w[f"{n}_proj"]}
                    for n in ("gate", "up", "down")}}


def _drawn(cfg: dict, key, lay):
    """(embedding, head, [lay(layer's weights)], final norm's offset): a
    layer a jitted call, so that no layer lies on the chip in two layouts at
    once."""
    normal = shared._normal(float(cfg["init_std"]))
    v, e = cfg["vocab_size"], cfg["hidden_size"]
    top = jax.random.split(jax.random.fold_in(key, 0))
    layer = jax.jit(lambda k: lay(draw_layer(cfg, k)))
    return (jax.jit(lambda k: normal(k, v, e))(top[0]),
            jax.jit(lambda k: normal(k, e, cfg["num_pred_heads"] * v))(
                top[1]),
            [layer(layer_key(key, i))
             for i in range(cfg["num_hidden_layers"])],
            jnp.zeros((e,), jnp.bfloat16))


def draw(cfg: dict, key) -> dict:
    """The weights in the reference's layout (reference/eva_serve.py)."""
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


class TimedEva(serving.Timed):
    """``serving.Timed`` for a backend whose cache is no row a position: a
    logged decode call also holds, as a sixth field, ``{"lengths": the live
    slots' lengths}`` (what each slot's ring and summaries hold follows
    from its length: ``benchmarks/flops_eva.py``)."""

    def decode(self, last_tokens, lengths):
        out = super().decode(last_tokens, lengths)
        self.log[-1] += ({"lengths": lengths[lengths > 0].tolist()},)
        return out


def serve(cfg: dict, traffic: dict, chips: int, seed: int
          ) -> shared.ServedSparse:
    if chips != 1:
        raise ValueError("eva_serve serves one pipeline stage on one chip")
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
    timed = TimedEva(backend)
    engine = ServingEngine(
        timed, ServingConfig(num_slots=slots, buckets=buckets,
                             max_seq_len=max_len, eos_id=None),
        clock=time.perf_counter)
    pool = jax.eval_shape(lambda: init_kv_cache(mcfg, slots, max_len))
    rows = pool[0].shape[2]
    slot_bytes = sum(int(np.prod(p.shape[2:])) * p.dtype.itemsize
                     for p in pool) * mcfg.num_layers
    notes: dict = {"flash_prefill": backend.flash_prefill}
    plan = {"window": mcfg.eva_window, "chunk": mcfg.eva_chunk,
            "rows_per_slot": rows,
            "rows_per_slot_dense": max_len, "bytes_per_slot": slot_bytes,
            "pool_bytes": slot_bytes * slots, "slots": slots,
            "pred_heads": mcfg.num_pred_heads,
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
        print("eva: " + json.dumps({**plan, **backend.eva_counters}))
        backend.kk = backend.vv = backend.params = None

    def decode_scopes():
        i32 = jax.ShapeDtypeStruct((slots,), jnp.int32)
        return scopes.table_of(
            backend._decode.lower(shapes, *pool, i32, i32).compile())

    def prefill_scopes(bucket: int):
        padded = jax.ShapeDtypeStruct((1, bucket), jnp.int32)
        return scopes.table_of(
            backend._prefill.lower(shapes, *pool, padded, 1, 0).compile())

    # (Served with prefill_scopes beside decode_scopes: serve_scopes.py)
    return shared.ServedSparse(
        engine=engine, warm=warm, release=release,
        compare=functools.partial(compare_served, cfg, traffic),
        vocab_size=cfg["vocab_size"], parameters=n_params, num_slots=slots,
        # what a slot reserves over the positions it may reach: the harness's
        # kv: line multiplies it by slots x max_seq_len, and so prints the
        # pool's true size; the line's LIVE numbers count a row a cached
        # position and are not this cache's (eva_cache_live_share.srv is)
        kv_bytes_per_token=slot_bytes // max_len,
        program_names={"decode": "jit__decode_fn",
                       "prefill": "jit__prefill_fn"},
        decode_scopes=decode_scopes, notes=notes,
        prefill_scopes=prefill_scopes)


def reference_rows(cfg: dict, traffic: dict, weights, prompt, served,
                   operand_dtype=None):
    """The reference's logits [T, P V] (every prediction head) at the
    positions that predict the served bytes of one request, T =
    len(served)."""
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
    """(weights, padded tokens, start) -> logits [rows, P V]: the reference
    with its layer compiled once and run a layer at a time (the whole
    forward of 32768 positions is no one program the chip's memory holds),
    the residual stream donated from layer to layer."""
    key = (pad, rows, block, operand_dtype, json.dumps(
        {k: v for k, v in cfg.items()
         if isinstance(v, (int, float, list))}, sort_keys=True))
    if key not in _ROWS_PROGRAMS:
        layer = jax.jit(lambda x, w: reference.layer(
            x, w, cfg, block, operand_dtype), donate_argnums=0)
        first = jax.jit(reference.embed)
        last = jax.jit(lambda x, w, s: reference.head_rows(
            x, w["norm"], w["lm_head"], cfg, s, rows, operand_dtype))

        def run(weights, tokens, start):
            x = first(weights, tokens)
            for w in weights["layers"]:
                x = layer(x, w)
            return last(x, weights, start)

        _ROWS_PROGRAMS[key] = run
    return _ROWS_PROGRAMS[key]


def compare_served(cfg, traffic, finished, seed, control=None) -> list[dict]:
    """The comparison of a run, as ``cohere2_moe_serve.compare_served``:
    ``control`` is None in every run of the benchmark (the bytes compared
    are the ones the window served); given an operand type
    (``benchmarks/control.py`` and the tests give ``jnp.float8_e4m3fn``, the
    step below the configuration's bfloat16), the reference computed with
    operands of that type stands in the program's place.  A byte is judged
    on head 0's logits, the head it was sampled from."""
    vocab = cfg["vocab_size"]
    chosen = shared.sample(finished, seed, int(traffic["compare_requests"]),
                           int(traffic["max_seq_len"]))
    weights = draw(cfg, seed_key(seed))
    widest, tokens = 0.0, 0
    for prompt, served in chosen:
        if control is None:
            judged = jnp.asarray(served, jnp.int32)
        else:
            judged = jnp.argmax(reference_rows(
                cfg, traffic, weights, prompt, served,
                operand_dtype=control)[:, :vocab], axis=-1).astype(jnp.int32)
        logits = reference_rows(cfg, traffic, weights, prompt,
                                served)[:, :vocab]
        widest = max(widest, float(jnp.max(
            shared.gaps_below_best(logits, judged))))
        tokens += len(served)
    # nothing finished is nothing shown: a reading no limit admits
    out = compare.check("served_token_gap_below_reference_best",
                        widest if chosen else 1e9, GAP_LIMIT)
    out["requests"], out["tokens"] = len(chosen), tokens
    out["longest"] = max((len(p) + len(s) for p, s in chosen), default=0)
    return [out]
