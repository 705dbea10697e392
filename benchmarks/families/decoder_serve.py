"""Family ``decoder_serve``: a Llama-style decoder served through the path a
user takes -- ``horovod_tpu.serving.ServingEngine`` over
``TransformerBackend`` (the backend ``python -m horovod_tpu.serving`` gives
when no prefix cache is asked for), ``models/transformer.py``'s
``return_kv`` prefill and ``kv_cache`` decode with
``cached_decode_attention``, weights and compute in bfloat16, greedy tokens,
no EOS.

The configuration file holds Hugging Face's keys; this module maps them onto
``TransformerConfig`` and refuses what the program cannot express.  The
weights are the benchmark's own: drawn here from ``--seed`` in one jitted
call, in the type they are served in, handed to the program in its layout
and, drawn again after the window, to the plain reference in the
reference's.  Nothing the program made reaches the reference.
"""

from __future__ import annotations

import functools
import statistics
import time

import jax
import jax.numpy as jnp
import numpy as np

from horovod_tpu.models import Transformer, TransformerConfig
from horovod_tpu.serving import ServingConfig, ServingEngine
from horovod_tpu.serving.engine import TransformerBackend

from benchmarks import compare, scopes, serving
from benchmarks.built import Served
from benchmarks.reference import decoder_serve as reference

# The one number of the comparison: over a sample of the requests the window
# finished, the widest gap by which a served token's logit lies below the
# reference's best at that position, in units of that position's standard
# deviation over the vocabulary (near 0.9 for these weights).  The program
# multiplies in bf16 and keeps bf16 logits, whose spacing at the top of a
# row is 2**-6 to 2**-5; the reference is f32 at "highest".  A token put
# first by a lower precision, or altered, or decoded against a wrong cache,
# lies further down.  Read on the chip at the cell's own size (PR 36): sound
# runs 0.022-0.079 over some 70 runs on 40 seeds of two traffics (one or two
# spacings of a bf16 logit above 4, 0.035 of a standard deviation each: ties
# the argmax settles by index); the float8 control, through this same
# comparison (``compare_served(..., control=...)``, benchmarks/control.py),
# 5.4-7.1 over ten seeds (its tokens are all but random), not correct on
# every one; a token picked at random would read about 4.  The limit stands
# 3.8 times above the largest sound reading and eighteen below the smallest
# control.
GAP_LIMIT = 0.3


def model_config(cfg: dict, traffic: dict) -> TransformerConfig:
    heads = cfg["num_attention_heads"]
    if cfg["rms_norm_eps"] != 1e-6:
        raise ValueError("models/transformer.py's default RMSNorm eps is "
                         "1e-6 and this family passes none")
    if cfg.get("tie_word_embeddings"):
        raise ValueError("decoder_serve builds an untied head")
    return TransformerConfig(
        vocab_size=cfg["vocab_size"], num_layers=cfg["num_hidden_layers"],
        num_heads=heads, head_dim=cfg["hidden_size"] // heads,
        num_kv_heads=cfg["num_key_value_heads"],
        embed_dim=cfg["hidden_size"], mlp_dim=cfg["intermediate_size"],
        max_seq_len=int(traffic["max_seq_len"]),
        rope_theta=float(cfg["rope_theta"]), dtype=jnp.bfloat16,
        param_dtype=jnp.bfloat16)


def seed_key(seed: int):
    # --seed may exceed 2**31
    return jax.random.fold_in(jax.random.PRNGKey(seed >> 31),
                              seed & 0x7FFFFFFF)


def draw(cfg: dict, key) -> dict:
    """The weights in the reference's layout (reference/decoder_serve.py),
    bfloat16: normal with the published ``initializer_range``, norms at 1."""
    e, i, v = cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]
    n, h, kv = (cfg["num_hidden_layers"], cfg["num_attention_heads"],
                cfg["num_key_value_heads"])
    d = e // h
    std = float(cfg["initializer_range"])
    keys = iter(jax.random.split(key, 9))

    def normal(*shape):
        return (std * jax.random.normal(next(keys), shape, jnp.float32)
                ).astype(jnp.bfloat16)

    ones = lambda *shape: jnp.ones(shape, jnp.bfloat16)  # noqa: E731
    return {"embed_tokens": normal(v, e),
            "layers": {"input_layernorm": ones(n, e),
                       "q_proj": normal(n, e, h * d),
                       "k_proj": normal(n, e, kv * d),
                       "v_proj": normal(n, e, kv * d),
                       "o_proj": normal(n, h * d, e),
                       "post_attention_layernorm": ones(n, e),
                       "gate_proj": normal(n, e, i),
                       "up_proj": normal(n, e, i),
                       "down_proj": normal(n, i, e)},
            "norm": ones(e), "lm_head": normal(e, v)}


def to_program(w: dict, cfg: dict) -> dict:
    """The same weights as ``models/transformer.py`` lays them out:
    slices and reshapes only."""
    e, h, kv = (cfg["hidden_size"], cfg["num_attention_heads"],
                cfg["num_key_value_heads"])
    d = e // h
    lay = w["layers"]
    params = {"embed": {"embedding": w["embed_tokens"]},
              "final_norm": {"scale": w["norm"]},
              "lm_head": {"kernel": w["lm_head"]}}
    for i in range(cfg["num_hidden_layers"]):
        params[f"layer_{i}"] = {
            "attn_norm": {"scale": lay["input_layernorm"][i]},
            "attn": {"q": {"kernel": lay["q_proj"][i].reshape(e, h, d)},
                     "k": {"kernel": lay["k_proj"][i].reshape(e, kv, d)},
                     "v": {"kernel": lay["v_proj"][i].reshape(e, kv, d)},
                     "o": {"kernel": lay["o_proj"][i].reshape(h, d, e)}},
            "mlp_norm": {"scale": lay["post_attention_layernorm"][i]},
            "mlp": {"gate": {"kernel": lay["gate_proj"][i]},
                    "up": {"kernel": lay["up_proj"][i]},
                    "down": {"kernel": lay["down_proj"][i]}}}
    return {"params": params}


def serve(cfg: dict, traffic: dict, chips: int, seed: int) -> Served:
    if chips != 1:
        raise ValueError("decoder_serve serves one replica on one chip")
    mcfg = model_config(cfg, traffic)
    model = Transformer(mcfg)
    slots, max_len = int(traffic["num_slots"]), int(traffic["max_seq_len"])
    buckets = tuple(int(b) for b in traffic["prefill_buckets"])
    make_params = jax.jit(lambda key: to_program(draw(cfg, key), cfg))
    params = make_params(seed_key(seed))
    shapes = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                          params)
    n_params = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes))
    backend = TransformerBackend(model, params, mcfg, slots, max_len)
    del params
    timed = serving.Timed(backend)
    engine = ServingEngine(
        timed, ServingConfig(num_slots=slots, buckets=buckets,
                             max_seq_len=max_len, eos_id=None),
        clock=time.perf_counter)
    notes: dict = {}

    def warm() -> None:
        def ids(n: int) -> list[int]:
            return [int(t) for t in np.arange(n) % cfg["vocab_size"]]

        for b in buckets:               # compiles each bucket, and decode
            engine.submit(ids(min(b, max_len - 4)), 3)
        engine.run_until_idle()
        # unloaded, on the programs now compiled: what the mix's two limits
        # were set from, read again in every run
        del timed.log[:]
        engine.submit(ids(min(buckets[-1], max_len - 4)), 2)
        engine.run_until_idle()
        notes["unloaded_ttft_ms_longest_bucket"] = 1e3 * (
            timed.log[0][2] - timed.log[0][1])
        for _ in range(slots):
            engine.submit(ids(buckets[0]), 10)
        engine.run_until_idle()
        full = [1e3 * (e[2] - e[1]) for e in timed.log
                if e[0] == "decode" and e[3] == slots]
        notes["unloaded_decode_ms_every_slot_full"] = statistics.median(full)

    def release() -> None:
        backend.kk = backend.vv = backend.params = None

    def decode_scopes():
        kv = jax.ShapeDtypeStruct(
            (mcfg.num_layers, slots, max_len, mcfg.kv_heads, mcfg.head_dim),
            mcfg.dtype)
        i32 = jax.ShapeDtypeStruct((slots,), jnp.int32)
        return scopes.table_of(
            backend._decode.lower(shapes, kv, kv, i32, i32).compile())

    return Served(
        engine=engine, warm=warm, release=release,
        compare=functools.partial(compare_served, cfg, traffic),
        vocab_size=cfg["vocab_size"],
        parameters=n_params, num_slots=slots,
        kv_bytes_per_token=(2 * mcfg.num_layers * mcfg.kv_heads
                            * mcfg.head_dim * 2),
        program_names={"decode": "jit__decode_fn",
                       "prefill": "jit__prefill_fn"},
        decode_scopes=decode_scopes, notes=notes)


def sample(finished: list[tuple], seed: int, how_many: int) -> list[tuple]:
    """Of the requests the window finished: the longest, the one with most
    served tokens, the one with the shortest prompt, and others drawn from
    the seed, ``how_many`` in all."""
    if not finished:
        return []
    total = lambda k: len(finished[k][0]) + len(finished[k][1])  # noqa: E731
    idx = range(len(finished))
    picked = [max(idx, key=total), max(idx, key=lambda k: len(finished[k][1])),
              min(idx, key=lambda k: len(finished[k][0]))]
    rng = np.random.default_rng(np.random.SeedSequence([abs(int(seed)), 3]))
    picked += [int(k) for k in rng.permutation(len(finished))]
    return [finished[k] for k in list(dict.fromkeys(picked))[:how_many]]


def reference_rows(cfg: dict, traffic: dict, weights, prompt, served,
                   operand_dtype=None):
    """The reference's logits at the positions that predict the served
    tokens of one request: (logits [T, V], where T = len(served))."""
    rows = int(traffic["arrivals"]["output_tokens"]["max"])
    max_len = int(traffic["max_seq_len"])
    seq = np.concatenate([prompt, served]).astype(np.int32)
    block = max_len // 4        # queries a block; the pads are multiples
    pad = next(p for p in (block, 2 * block, 4 * block)
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
    key = (pad, rows, block, operand_dtype, tuple(sorted(
        (k, v) for k, v in cfg.items() if isinstance(v, (int, float)))))
    if key not in _ROWS_PROGRAMS:
        _ROWS_PROGRAMS[key] = jax.jit(
            lambda w, t, s: reference.logits_of_rows(
                w, t, cfg, s, rows, query_block=block,
                operand_dtype=operand_dtype))
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
    chosen = sample(finished, seed, int(traffic["compare_requests"]))
    weights = jax.jit(lambda key: draw(cfg, key))(seed_key(seed))
    widest, tokens = 0.0, 0
    for prompt, served in chosen:
        logits = reference_rows(cfg, traffic, weights, prompt, served)
        judged = jnp.asarray(served, jnp.int32) if control is None else \
            jnp.argmax(reference_rows(cfg, traffic, weights, prompt, served,
                                      operand_dtype=control),
                       axis=-1).astype(jnp.int32)
        widest = max(widest, float(jnp.max(gaps_below_best(logits, judged))))
        tokens += len(served)
    # nothing finished is nothing shown: a reading no limit admits
    out = compare.check("served_token_gap_below_reference_best",
                        widest if chosen else 1e9, GAP_LIMIT)
    out["requests"], out["tokens"] = len(chosen), tokens
    return [out]
