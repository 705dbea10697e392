"""Family ``cohere2_moe_serve``: a ``cohere2_moe`` decoder (CohereLabs'
command-a-plus: a parallel block of grouped-query attention, three
sliding-window layers to one full layer without positions, beside
sigmoid-routed experts and averaged shared ones) served through the path a
user takes -- ``horovod_tpu.serving.ServingEngine`` over
``TransformerBackend``, whose prefill runs the flash forward kernel for a
model of this many heads and whose decode runs ``cached_decode_attention``
with the window in its mask -- weights and compute in bfloat16, greedy
tokens, no EOS.

The chip holds ONE CHIP'S SHARE of an expert-parallel stage: every head,
the shared experts, the router's every output, and the routed experts the
configuration's ``experts_held`` names.  The layer routes over all the
published experts and computes what its own give (``models/moe.py``); the
reference is given the same share.

The configuration file holds Hugging Face's keys; this module maps them onto
``TransformerConfig`` and refuses what the program cannot express.  The
weights are the benchmark's own: drawn here from ``--seed``, a layer a
jitted call, in the type they are served in, handed to the program in its
layout and, drawn again after the window, to the plain reference in the
reference's.
"""

from __future__ import annotations

import dataclasses
import functools
import json
import statistics
import time
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from horovod_tpu.models import Transformer, TransformerConfig
from horovod_tpu.serving import ServingConfig, ServingEngine
from horovod_tpu.serving.engine import TransformerBackend

from benchmarks import compare, scopes, serving
from benchmarks.built import Served
from benchmarks.reference import cohere2_moe_serve as reference

# The one number of the comparison, as families/decoder_serve.py has it: over
# a sample of the requests the window finished, the widest gap by which a
# served token's logit lies below the reference's best at its position, in
# units of that position's standard deviation over the vocabulary.  The
# reference is given the tokens and nothing else the program made: it routes
# every position by its own picks.  A pick is a comparison that a rounding
# decides at a near-tie, the program scores from bfloat16 activations, and
# with an eighth of the experts held a flipped pick puts a whole expert on
# or off the chip: that is what widens a sound reading here past
# decoder_serve's.  Read on the chip at the cell's own size (PR 37, PERF.md
# section 6): sound runs 0.007-0.312 over 28 seeds (mean 0.09, three past
# 0.18); the float8 control through this same comparison 2.03-3.82 over 12
# seeds, not correct on any.  The limit is the geometric middle: 2.6 times
# above the largest sound reading, 2.5 times below the smallest control.
GAP_LIMIT = 0.8


def model_config(cfg: dict, traffic: dict) -> TransformerConfig:
    refused = {
        "model_type": "cohere2_moe", "hidden_act": "silu",
        "use_gated_activation": True, "attention_bias": False,
        "use_qk_norm": False, "first_k_dense_replace": 0, "rotary_pct": 1,
        "expert_selection_fn": "sigmoid",
        "shared_expert_combination_strategy": "average",
        "position_embedding_type": "rope_gptj", "tie_word_embeddings": True,
        "use_parallel_block": True, "rms_norm_eps": None}
    wrong = {k: cfg.get(k) for k, v in refused.items() if cfg.get(k) != v}
    if wrong:
        raise ValueError(f"cohere2_moe_serve builds {refused}; the "
                         f"configuration says {wrong}")
    lo, hi = cfg["experts_held"]
    if hi - lo != cfg["num_experts"]:
        raise ValueError("num_experts counts the experts HELD "
                         "(experts_held); the published count is "
                         "num_experts_published")
    return TransformerConfig(
        vocab_size=cfg["vocab_size"], num_layers=cfg["num_hidden_layers"],
        num_heads=cfg["num_attention_heads"], head_dim=cfg["head_dim"],
        num_kv_heads=cfg["num_key_value_heads"],
        embed_dim=cfg["hidden_size"], mlp_dim=cfg["intermediate_size"],
        max_seq_len=int(traffic["max_seq_len"]),
        rope_theta=float(cfg["rope_theta"]), rope_interleaved=True,
        norm="layer", norm_eps=float(cfg["layer_norm_eps"]),
        parallel_block=True, layer_types=tuple(cfg["layer_types"]),
        sliding_window=int(cfg["sliding_window"]), tie_embeddings=True,
        logits_scaling=1.0 / float(cfg["logit_scale"]),
        num_experts=cfg["num_experts_published"],
        experts_per_token=cfg["num_experts_per_tok"],
        norm_topk_prob=bool(cfg["norm_topk_prob"]), moe_selection="sigmoid",
        num_shared_experts=cfg["num_shared_experts"],
        experts_held=(lo, hi), dtype=jnp.bfloat16,
        param_dtype=jnp.bfloat16)


def seed_key(seed: int):
    # --seed may exceed 2**31
    return jax.random.fold_in(jax.random.PRNGKey(seed >> 31),
                              seed & 0x7FFFFFFF)


def _normal(std):
    def normal(key, *shape):
        return (std * jax.random.normal(key, shape, jnp.float32)
                ).astype(jnp.bfloat16)
    return normal


def draw_layer(cfg: dict, key) -> dict:
    """One layer's weights in the reference's layout, bfloat16: normal with
    the ``assumed`` initializer_range, the norm's scale at 1."""
    e, f = cfg["hidden_size"], cfg["intermediate_size"]
    h, kv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                cfg["head_dim"])
    held, n = cfg["num_experts"], cfg["num_shared_experts"]
    normal = _normal(float(cfg["initializer_range"]))
    k = iter(jax.random.split(key, 11))
    return {"input_layernorm": jnp.ones((e,), jnp.bfloat16),
            "q_proj": normal(next(k), e, h * d),
            "k_proj": normal(next(k), e, kv * d),
            "v_proj": normal(next(k), e, kv * d),
            "o_proj": normal(next(k), h * d, e),
            "router": normal(next(k), e, cfg["num_experts_published"]),
            "experts": {"gate_proj": normal(next(k), held, e, f),
                        "up_proj": normal(next(k), held, e, f),
                        "down_proj": normal(next(k), held, f, e)},
            "shared_experts": {"gate_proj": normal(next(k), n, e, f),
                               "up_proj": normal(next(k), n, e, f),
                               "down_proj": normal(next(k), n, f, e)}}


def draw_embedding(cfg: dict, key):
    return _normal(float(cfg["initializer_range"]))(
        key, cfg["vocab_size"], cfg["hidden_size"])


def layer_key(key, i: int):
    return jax.random.fold_in(key, i + 1)


def _drawn(cfg: dict, key, lay):
    """(embedding, [lay(layer's weights)], final norm's scale): a layer a
    jitted call, so that no layer lies on the chip in two layouts at once."""
    layer = jax.jit(lambda k: lay(draw_layer(cfg, k)))
    return (jax.jit(functools.partial(draw_embedding, cfg))(
                jax.random.fold_in(key, 0)),
            [layer(layer_key(key, i))
             for i in range(cfg["num_hidden_layers"])],
            jnp.ones((cfg["hidden_size"],), jnp.bfloat16))


def draw(cfg: dict, key) -> dict:
    """The weights in the reference's layout (reference/
    cohere2_moe_serve.py)."""
    embedding, layers, norm = _drawn(cfg, key, lambda w: w)
    return {"embed_tokens": embedding, "layers": layers, "norm": norm}


def layer_to_program(w: dict, cfg: dict) -> dict:
    """One layer as ``models/transformer.py`` lays it out: reshapes, and for
    the shared experts' gate and up one transposition ([n, E, F] side by
    side as [E, n F]: expert j owns columns j F .. (j + 1) F)."""
    e, f = cfg["hidden_size"], cfg["intermediate_size"]
    h, kv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                cfg["head_dim"])
    n = cfg["num_shared_experts"]
    ex, sh = w["experts"], w["shared_experts"]
    beside = lambda x: x.transpose(1, 0, 2).reshape(e, n * f)  # noqa: E731
    return {"attn_norm": {"scale": w["input_layernorm"]},
            "attn": {"q": {"kernel": w["q_proj"].reshape(e, h, d)},
                     "k": {"kernel": w["k_proj"].reshape(e, kv, d)},
                     "v": {"kernel": w["v_proj"].reshape(e, kv, d)},
                     "o": {"kernel": w["o_proj"].reshape(h, d, e)}},
            "moe_mlp": {"router": w["router"], "gate": ex["gate_proj"],
                        "up": ex["up_proj"], "down": ex["down_proj"],
                        "shared_gate": beside(sh["gate_proj"]),
                        "shared_up": beside(sh["up_proj"]),
                        "shared_down": sh["down_proj"].reshape(n * f, e)}}


def to_program(w: dict, cfg: dict) -> dict:
    params = {"embed": {"embedding": w["embed_tokens"]},
              "final_norm": {"scale": w["norm"]}}
    for i, layer in enumerate(w["layers"]):
        params[f"layer_{i}"] = layer_to_program(layer, cfg)
    return {"params": params}


def program_params(cfg: dict, key) -> dict:
    """The seed's weights in the program's layout."""
    embedding, layers, norm = _drawn(
        cfg, key, lambda w: layer_to_program(w, cfg))
    return {"params": {"embed": {"embedding": embedding},
                       "final_norm": {"scale": norm},
                       **{f"layer_{i}": w for i, w in enumerate(layers)}}}


class TimedSparse(serving.Timed):
    """``serving.Timed`` for a sparse backend: each logged call also holds,
    as a sixth field, what the program counted in it: ``{"pairs": [L][held]
    (token, expert) pairs each held expert was given, "lengths": the live
    slots' lengths of a decode step}``."""

    def prefill(self, padded, length, slot):
        out = super().prefill(padded, length, slot)
        self.log[-1] += ({"pairs": self.inner.last_expert_pairs.tolist()},)
        return out

    def decode(self, last_tokens, lengths):
        out = super().decode(last_tokens, lengths)
        self.log[-1] += ({"pairs": self.inner.last_expert_pairs.tolist(),
                          "lengths": lengths[lengths > 0].tolist()},)
        return out


@dataclasses.dataclass
class ServedSparse(Served):
    # bucket -> the prefill program's scope table, or None; asked of a
    # traced run on the chip alone (benchmarks/serve_scopes.py)
    prefill_scopes: Callable[[int], Any] = None


def serve(cfg: dict, traffic: dict, chips: int, seed: int) -> ServedSparse:
    if chips != 1:
        raise ValueError("cohere2_moe_serve serves one data-parallel replica "
                         "of the expert-parallel group on one chip")
    # a checkout before PR 37 has no such fields and says so (a TypeError)
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
    timed = TimedSparse(backend)
    engine = ServingEngine(
        timed, ServingConfig(num_slots=slots, buckets=buckets,
                             max_seq_len=max_len, eos_id=None),
        clock=time.perf_counter)
    notes: dict = {"flash_prefill": backend.flash_prefill}
    kinds = cfg["layer_types"]
    plan = {"experts": cfg["num_experts_published"],
            "experts_held": cfg["num_experts"],
            "held_from": cfg["experts_held"][0],
            "experts_per_token": cfg["num_experts_per_tok"],
            "shared_experts": cfg["num_shared_experts"],
            "selection": cfg["expert_selection_fn"],
            "norm_topk_prob": cfg["norm_topk_prob"],
            "layers": {kind: kinds.count(kind) for kind in sorted(set(kinds))},
            "sliding_window": cfg["sliding_window"],
            "parallel_block": cfg["use_parallel_block"], "slots": slots}

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
        backend.kk = backend.vv = backend.params = None

    kv = jax.ShapeDtypeStruct(
        (mcfg.num_layers, slots, max_len, mcfg.kv_heads, mcfg.head_dim),
        mcfg.dtype)

    def decode_scopes():
        i32 = jax.ShapeDtypeStruct((slots,), jnp.int32)
        return scopes.table_of(
            backend._decode.lower(shapes, kv, kv, i32, i32).compile())

    def prefill_scopes(bucket: int):
        padded = jax.ShapeDtypeStruct((1, bucket), jnp.int32)
        return scopes.table_of(
            backend._prefill.lower(shapes, kv, kv, padded, 1, 0).compile())

    return ServedSparse(
        engine=engine, warm=warm, release=release,
        compare=functools.partial(compare_served, cfg, traffic),
        vocab_size=cfg["vocab_size"],
        parameters=n_params, num_slots=slots,
        kv_bytes_per_token=(2 * mcfg.num_layers * mcfg.kv_heads
                            * mcfg.head_dim * 2),
        program_names={"decode": "jit__decode_fn",
                       "prefill": "jit__prefill_fn"},
        decode_scopes=decode_scopes, notes=notes,
        prefill_scopes=prefill_scopes)


def sample(finished: list[tuple], seed: int, how_many: int, window: int
           ) -> list[tuple]:
    """Of the requests the window finished: the longest (past the sliding
    window if any is), the one with most served tokens, the one with the
    shortest prompt, the longest among those that stay inside the window,
    and others drawn from the seed, ``how_many`` in all."""
    if not finished:
        return []
    total = lambda k: len(finished[k][0]) + len(finished[k][1])  # noqa: E731
    idx = range(len(finished))
    inside = [k for k in idx if total(k) <= window] or list(idx)
    picked = [max(idx, key=total), max(idx, key=lambda k: len(finished[k][1])),
              min(idx, key=lambda k: len(finished[k][0])),
              max(inside, key=total)]
    rng = np.random.default_rng(np.random.SeedSequence([abs(int(seed)), 3]))
    picked += [int(k) for k in rng.permutation(len(finished))]
    return [finished[k] for k in list(dict.fromkeys(picked))[:how_many]]


def reference_rows(cfg: dict, traffic: dict, weights, prompt, served,
                   operand_dtype=None):
    """The reference's logits [T, V] at the positions that predict the
    served tokens of one request, T = len(served)."""
    rows = int(traffic["arrivals"]["output_tokens"]["max"])
    max_len = int(traffic["max_seq_len"])
    seq = np.concatenate([prompt, served]).astype(np.int32)
    block = max_len // 32       # queries a block; the pads are multiples
    pad = next(p for p in (8 * block, 16 * block, 32 * block)
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


@jax.jit
def gaps_below_best(logits, tokens):
    """By how much each token's logit lies below its row's best, in units
    of the row's standard deviation over the vocabulary."""
    picked = jnp.take_along_axis(logits, tokens[:, None], axis=-1)[:, 0]
    return (jnp.max(logits, axis=-1) - picked) / jnp.std(logits, axis=-1)


def compare_served(cfg, traffic, finished, seed, control=None) -> list[dict]:
    """The comparison of a run.  ``control`` is None in every run of the
    benchmark: the tokens compared are the ones the window served.  Given an
    operand type (``benchmarks/control.py`` and the tests give
    ``jnp.float8_e4m3fn``, the step below the configuration's bfloat16), the
    reference computed with operands of that type stands in the program's
    place: at each position of the same prompts and tokens, the token IT
    puts first is judged as a served one is, by the same code and limit."""
    chosen = sample(finished, seed, int(traffic["compare_requests"]),
                    int(cfg["sliding_window"]))
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
        widest = max(widest, float(jnp.max(gaps_below_best(logits, judged))))
        tokens += len(served)
    # nothing finished is nothing shown: a reading no limit admits
    out = compare.check("served_token_gap_below_reference_best",
                        widest if chosen else 1e9, GAP_LIMIT)
    out["requests"], out["tokens"] = len(chosen), tokens
    out["longest"] = max((len(p) + len(s) for p, s in chosen), default=0)
    return [out]
