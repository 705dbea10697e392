"""Family ``cca_moe_serve``: a ``zaya`` decoder (Zyphra's ZAYA1-8B:
compressed convolutional attention, CCA, in every layer -- queries, keys and
values in a latent half and an eighth as wide as the stream, two short
causal convolutions over the sequence, attention inside the latent -- then a
top-1 sparse feed-forward whose router is an MLP with a state carried down
the layers, every residual merge scaled, the head tied to the embedding)
served through the path a user takes --
``horovod_tpu.serving.ServingEngine`` over ``TransformerBackend``, whose
pool for this model is of TWO KINDS IN EVERY LAYER (K and V rows a position;
the convolutions' and the value shift's tail a slot, float32), whose prefill
runs the layer's position-wise parts over the prompt's own row blocks with
the tail handed across and attention through the flash forward kernel, and
whose decode step convolves over the tail and reads the slot's rows --
weights and compute in bfloat16, the router and the tail in float32, greedy
tokens, no EOS.

The chip holds the FIRST OF TWO PIPELINE STAGES whole: every head, every
expert, the whole vocabulary (and the tied head, the last stage's in the
deployment, so that a token can be sampled).  Nothing walks: a prefill's
bucket and a decode step's slots go through ``lax.ragged_dot`` over all 16
experts.  This family takes ``cohere2_moe_serve``'s timing wrapper, sampling
of the finished requests and judgement of a token.

The configuration file holds Hugging Face's keys; this module maps them onto
``TransformerConfig`` and refuses what the program cannot express.  The
weights are the benchmark's own: drawn here from ``--seed``, a layer a
jitted call, in the type they are served in, handed to the program in its
layout and, drawn again after the window a layer at a time, to the plain
reference in the reference's.
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
from benchmarks.reference import cca_moe_serve as reference

seed_key, layer_key = sparse.seed_key, sparse.layer_key
F32, BF16 = jnp.float32, jnp.bfloat16

# The one number of the comparison, as families/cohere2_moe_serve.py has it:
# over a sample of the requests the window finished, the widest gap by which
# a served token's logit lies below the reference's best at its position, in
# units of that position's standard deviation over the vocabulary.  The
# reference is given the tokens and nothing else the program made; it routes
# every position by its own picks in float32, where the program scored from
# bfloat16 activations, and with ONE expert a token a flipped pick at a
# near-tie swaps the token's whole expert.  Read on the chip at the cell's own
# size (PR 52, PERF.md section 6): sound runs 0.000-0.359 over 23 seeds
# (median 0.19; 5039-8172 served tokens a reading, the longest request
# 5508-10240 positions); the float8 control through this same comparison
# 7.40-8.80 over 4 seeds, not correct on any.  The limit was fixed by rule
# before the readings (between the largest sound reading and the smallest
# control reading, nearer the sound ones): 2.8 times above the largest sound
# reading, 7.4 times below the smallest control; their geometric middle is
# 1.63.
GAP_LIMIT = 1.0


def model_config(cfg: dict, traffic: dict) -> TransformerConfig:
    refused = {
        "model_type": "zaya", "hidden_act": "silu", "attention_bias": False,
        "lm_head_bias": False, "tie_word_embeddings": True,
        "sliding_window": None, "num_experts_per_tok": 1,
        "layer_types": ["hybrid"] * cfg["num_hidden_layers"]}
    wrong = {k: cfg.get(k) for k, v in refused.items() if cfg.get(k) != v}
    if wrong:
        raise ValueError(f"cca_moe_serve builds {refused}; the configuration "
                         f"says {wrong}")
    rope = cfg["rope_parameters"]["hybrid"]
    if rope["rope_type"] != "default" \
            or rope["partial_rotary_factor"] != cfg["partial_rotary_factor"]:
        raise ValueError(f"cca_moe_serve builds the default rotary embedding "
                         f"on partial_rotary_factor of a head; the "
                         f"configuration says {rope}")
    # (a checkout before PR 52 has no such fields and says so at once:
    # TransformerConfig.from_dict names the first it does not know)
    return TransformerConfig.from_dict(dict(
        vocab_size=cfg["vocab_size"], num_layers=cfg["num_hidden_layers"],
        layer_types=["cca"] * cfg["num_hidden_layers"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        embed_dim=cfg["hidden_size"], mlp_dim=cfg["moe_intermediate_size"],
        cca_taps=[cfg["cca_time0"], cfg["cca_time1"]],
        rotary_fraction=float(rope["partial_rotary_factor"]),
        rope_theta=float(rope["rope_theta"]),
        norm_eps=float(cfg["rms_norm_eps"]), num_experts=cfg["num_experts"],
        experts_per_token=cfg["num_experts_per_tok"],
        moe_selection="softmax", moe_expert_bias=True,
        moe_router_dim=cfg["router_hidden_size"], residual_scaling=True,
        tie_embeddings=True,
        feed_forward_chunk=cfg.get("feed_forward_chunk"),
        max_seq_len=int(traffic["max_seq_len"]), dtype="bfloat16",
        param_dtype="bfloat16"))


def _normal32(std):
    def normal(key, *shape):
        return std * jax.random.normal(key, shape, F32)
    return normal


MERGE = ("residual_bias", "residual_scale", "branch_bias", "branch_scale")


def draw_layer(cfg: dict, first: bool, key) -> dict:
    """One layer's weights in the reference's layout: the matrices bfloat16,
    normal at the ``assumed`` initializer_range; the convolutions and the
    router's MLP at their fan-in's scale; the router's own float32; as
    ``assumed.draw`` says.  ``first``: the layer has no state to decay."""
    e, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    h, kv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                cfg["head_dim"])
    k0, k1 = cfg["cca_time0"], cfg["cca_time1"]
    n, width = cfg["num_experts"], cfg["router_hidden_size"]
    c = (h + kv) * d
    std = float(cfg["initializer_range"])
    normal, small = sparse._normal(std), _normal32(std)
    ones = lambda m: jnp.ones((m,), BF16)  # noqa: E731
    k = iter(jax.random.split(key, 40))
    fan = lambda fan_in: sparse._normal(fan_in ** -0.5)  # noqa: E731
    # each column's mean taken out: a GELU's output has a mean, and through
    # fresh matrices that mean would decide the pick whatever the token
    centred = lambda w: w - w.mean(axis=0, keepdims=True)  # noqa: E731
    router = {
        "down": small(next(k), e, width), "down_bias": small(next(k), width),
        "norm": jnp.ones((width,), F32),
        "w1": _normal32(width ** -0.5)(next(k), width, width),
        "b1": small(next(k), width),
        "w2": centred(_normal32(width ** -0.5)(next(k), width, width)),
        "b2": small(next(k), width),
        "w3": centred(_normal32(
            float(cfg["router_logit_gain"]) * width ** -0.5)(
                next(k), width, n))}
    decay = jax.random.uniform(next(k), (width,), F32, 0.25, 0.75)
    if not first:
        router["decay"] = decay

    def merge_vectors():
        return {part: (normal(next(k), e) if part.endswith("bias")
                       else (1.0 + small(next(k), e)).astype(BF16))
                for part in MERGE}

    return {
        "input_layernorm": ones(e), "post_attention_layernorm": ones(e),
        "cca": {"q_proj": normal(next(k), e, h * d),
                "k_proj": normal(next(k), e, kv * d),
                "v_now_proj": normal(next(k), e, kv * d // 2),
                "v_prev_proj": normal(next(k), e, kv * d // 2),
                "conv0": fan(k0)(next(k), k0, c),
                "conv0_bias": normal(next(k), c),
                "conv1": fan(k1 * d)(next(k), h + kv, k1, d, d),
                "conv1_bias": normal(next(k), c),
                "k_scale": (1.0 + small(next(k), kv)).astype(BF16),
                "o_proj": normal(next(k), h * d, e)},
        "router": router,
        "expert_bias": _normal32(float(cfg["expert_bias_scale"]))(
            next(k), n),
        "experts": {"gate_proj": normal(next(k), n, e, f),
                    "up_proj": normal(next(k), n, e, f),
                    "down_proj": normal(next(k), n, f, e)},
        "merge": {"attn": merge_vectors(), "mlp": merge_vectors()}}


def layer_to_program(w: dict, cfg: dict) -> dict:
    """One layer as ``models/transformer.py`` lays it out: names alone."""
    m, ex = w["cca"], w["experts"]
    kernel = lambda x: {"kernel": x}  # noqa: E731
    out = {"cca_norm": {"scale": w["input_layernorm"]},
           "mlp_norm": {"scale": w["post_attention_layernorm"]},
           "cca": {"q": kernel(m["q_proj"]), "k": kernel(m["k_proj"]),
                   "v_now": kernel(m["v_now_proj"]),
                   "v_prev": kernel(m["v_prev_proj"]),
                   "o": kernel(m["o_proj"]),
                   **{name: m[name] for name in (
                       "conv0", "conv0_bias", "conv1", "conv1_bias",
                       "k_scale")}},
           "moe_mlp": {"gate": ex["gate_proj"], "up": ex["up_proj"],
                       "down": ex["down_proj"],
                       "expert_bias": w["expert_bias"],
                       **{f"router_{name}": x
                          for name, x in w["router"].items()}}}
    for side, name in (("attn", "cca"), ("mlp", "mlp")):
        for part in MERGE:
            out[f"{name}_{part}"] = w["merge"][side][part]
    return out


def _embedding(cfg: dict, key):
    return jax.jit(lambda k: sparse._normal(float(cfg["initializer_range"]))(
        k, cfg["vocab_size"], cfg["hidden_size"]))(jax.random.fold_in(key, 0))


@functools.lru_cache(maxsize=None)
def _layer_drawer(cfg_json: str, first: bool, program: bool):
    cfg = json.loads(cfg_json)
    lay = (lambda w: layer_to_program(w, cfg)) if program else (lambda w: w)
    return jax.jit(lambda k: lay(draw_layer(cfg, first, k)))


def _numbers(cfg: dict) -> dict:
    return {k: v for k, v in cfg.items()
            if isinstance(v, (int, float, list, bool, type(None)))
            or k == "rope_parameters"}


def drawn_layer(cfg: dict, key, local: int, program: bool = False) -> dict:
    """Layer ``local``'s weights of the seed ``key``: one jitted call, so
    that no layer lies on the chip in two layouts at once."""
    return _layer_drawer(json.dumps(_numbers(cfg), sort_keys=True),
                         local == 0, program)(layer_key(key, local))


def _final_norm(cfg: dict):
    return jnp.ones((cfg["hidden_size"],), BF16)


def draw(cfg: dict, key) -> dict:
    """The weights in the reference's layout, all layers at once (the tests'
    sizes; a run's comparison draws a layer at a time)."""
    return {"embed_tokens": _embedding(cfg, key),
            "layers": [drawn_layer(cfg, key, i)
                       for i in range(cfg["num_hidden_layers"])],
            "norm": _final_norm(cfg)}


def to_program(w: dict, cfg: dict) -> dict:
    return {"params": {
        "embed": {"embedding": w["embed_tokens"]},
        "final_norm": {"scale": w["norm"]},
        **{f"layer_{i}": layer_to_program(layer, cfg)
           for i, layer in enumerate(w["layers"])}}}


def program_params(cfg: dict, key) -> dict:
    """The seed's weights in the program's layout."""
    return {"params": {
        "embed": {"embedding": _embedding(cfg, key)},
        "final_norm": {"scale": _final_norm(cfg)},
        **{f"layer_{i}": drawn_layer(cfg, key, i, program=True)
           for i in range(cfg["num_hidden_layers"])}}}


def serve(cfg: dict, traffic: dict, chips: int, seed: int
          ) -> sparse.ServedSparse:
    if chips != 1:
        raise ValueError("cca_moe_serve serves one pipeline stage on one "
                         "chip")
    mcfg = model_config(cfg, traffic)
    from horovod_tpu.models.cca import cca_sizes

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
    size = lambda p: int(np.prod(p.shape)) * p.dtype.itemsize  # noqa: E731
    # a cached position's bytes (the rows) and a slot's (the tails)
    per_token = sum(size(side["cca"]) for side in pool) // (slots * max_len)
    per_slot = sum(size(side["cca_tail"]) for side in pool) // slots
    notes: dict = {"flash_prefill": backend.flash_prefill}
    layers = cfg["num_hidden_layers"]
    plan = {"experts": cfg["num_experts"], "experts_held": cfg["num_experts"],
            "held_from": 0,
            "experts_per_token": cfg["num_experts_per_tok"],
            "shared_experts": 0, "selection": "softmax",
            "router": {"mlp_width": cfg["router_hidden_size"],
                       "state_carried": True},
            "expert_bias": True, "layers": {"sparse": layers},
            "slots": slots}
    latent = {
        **cca_sizes(mcfg), "layers": layers,
        "cache": {"bytes_per_token": per_token,
                  "tail_bytes_per_slot": per_slot,
                  "pool_bytes": per_slot * slots
                  + per_token * slots * max_len},
        "prefill_by_bucket": {
            b: {"attention": backend.prefill_attention(b),
                "row_blocks": (backend.prefill_rows(b, b) or 0) // 1024,
                "feed_forward_chunks": backend.prefill_chunks(b)}
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
        print("cca: " + json.dumps(latent))
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
        # the rows' bytes a cached position; what a slot holds whatever its
        # length (the tails) is on the cca: line
        kv_bytes_per_token=per_token,
        program_names={"decode": "jit__decode_fn",
                       "prefill": "jit__prefill_fn"},
        decode_scopes=decode_scopes, notes=notes,
        prefill_scopes=prefill_scopes)


_PROGRAMS: dict = {}


def _program(name: str, make, *key):
    if (name, *key) not in _PROGRAMS:
        _PROGRAMS[name, *key] = make()
    return _PROGRAMS[name, *key]


def reference_streams(cfg: dict, traffic: dict, key, requests,
                      operand_dtype=None) -> list:
    """The reference's stream after the last layer, [pad, E] float32, of
    each of ``requests`` [(prompt, served), ...], with where the rows that
    predict its served tokens lie ``(first, start, n)``: a layer at a time
    over all of them (18.8 GB of float32 weights are no one program beside a
    10 000-position layer), each layer's weights drawn from ``key`` as the
    layer is reached and dropped after it, the streams and the router's
    states donated from layer to layer."""
    rows = int(traffic["arrivals"]["output_tokens"]["max"])
    max_len = int(traffic["max_seq_len"])
    block = max(max_len // 128, 1)  # queries a block; the pads are multiples
    numbers = json.dumps(_numbers(cfg), sort_keys=True)
    embedding = _embedding(cfg, key)
    streams, where = [], []
    for prompt, served in requests:
        seq = np.concatenate([prompt, served]).astype(np.int32)
        pad = next(p for p in (32 * block, 64 * block, 128 * block)
                   if p >= max(len(seq), rows + 1))
        padded = np.zeros(pad, np.int32)
        padded[:len(seq)] = seq
        first = len(prompt) - 1         # the row that predicts served[0]
        where.append((first, min(first, pad - rows), len(served)))
        streams.append((_program("embed", lambda: jax.jit(reference.embed))(
            embedding, padded), None))
    del embedding
    for local in range(cfg["num_hidden_layers"]):
        w = drawn_layer(cfg, key, local)
        for i, (x, state) in enumerate(streams):
            layer = _program(
                "layer", lambda: jax.jit(
                    lambda x, state, w: reference.layer(
                        x, state, w, cfg, block, operand_dtype)[:2],
                    donate_argnums=(0, 1)),
                numbers, x.shape[0], local == 0, operand_dtype)
            streams[i] = layer(x, state, w)
        del w
    return [(x, *at) for (x, _), at in zip(streams, where)]


def _logits(cfg: dict, traffic: dict, embedding, stream, operand_dtype=None):
    """The reference's logits [T, V] at the positions that predict one
    request's served tokens, from its final stream."""
    rows = int(traffic["arrivals"]["output_tokens"]["max"])
    x, first, start, n = stream
    last = _program(
        "head", lambda: jax.jit(
            lambda x, norm, embedding, s: reference.head_rows(
                x, norm, embedding, cfg, s, rows, operand_dtype)),
        json.dumps(_numbers(cfg), sort_keys=True), x.shape[0], operand_dtype)
    return last(x, _final_norm(cfg), embedding, start)[
        first - start:first - start + n]


def compare_served(cfg, traffic, finished, seed, control=None) -> list[dict]:
    """The comparison of a run, as ``cohere2_moe_serve.compare_served``:
    ``control`` is None in every run of the benchmark (the tokens compared
    are the ones the window served); given an operand type
    (``benchmarks/control.py`` and the tests give ``jnp.float8_e4m3fn``, the
    step below the configuration's bfloat16), the reference computed with
    operands of that type stands in the program's place.  A request's
    logits (2048 rows of 262 272) are made, judged and dropped before the
    next request's."""
    chosen = sparse.sample(finished, seed, int(traffic["compare_requests"]),
                           int(traffic["max_seq_len"]))
    key = seed_key(seed)
    every = reference_streams(cfg, traffic, key, chosen)
    stood_in = None if control is None else reference_streams(
        cfg, traffic, key, chosen, operand_dtype=control)
    embedding = _embedding(cfg, key)
    gaps = []
    for i, (_, served) in enumerate(chosen):
        if stood_in is None:
            judged = jnp.asarray(served, jnp.int32)
        else:
            judged = jnp.argmax(_logits(cfg, traffic, embedding, stood_in[i],
                                        control), axis=-1).astype(jnp.int32)
        gaps.append(np.asarray(sparse.gaps_below_best(
            _logits(cfg, traffic, embedding, every[i]), judged)))
        every[i] = None
    widest = max((float(g.max()) for g in gaps), default=0.0)
    # nothing finished is nothing shown: a reading no limit admits
    out = compare.check("served_token_gap_below_reference_best",
                        widest if chosen else 1e9, GAP_LIMIT)
    out["requests"] = len(chosen)
    out["tokens"] = sum(len(served) for _, served in chosen)
    out["longest"] = max((len(p) + len(s) for p, s in chosen), default=0)
    # where the widest gap lies: (its request's prompt length, the served
    # token's index), and every request's own widest beside its lengths
    out["by_request"] = [[len(p), len(s), round(float(g.max()), 4),
                          int(g.argmax())]
                         for (p, s), g in zip(chosen, gaps)]
    return [out]
