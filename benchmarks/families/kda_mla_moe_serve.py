"""Family ``kda_mla_moe_serve``: a ``bailing_hybrid`` decoder (inclusionAI's
Ling-3.0-flash: Kimi Delta Attention in five layers of six and latent
attention in the sixth, leading dense layers, then sigmoid-routed experts
picked by a bias-corrected, group-limited top-k beside one shared expert, an
untied head) served through the path a user takes --
``horovod_tpu.serving.ServingEngine`` over ``TransformerBackend``, whose pool
for this model is of TWO KINDS (a float32 state and a convolution tail a
slot for each KDA layer; latents and one rotary key a position for the
latent layer), whose prefill runs the delta rule chunked over the prompt's
own row blocks and latent attention expanded through the flash forward
kernel, and whose decode runs one recurrence step a slot and latent
attention absorbed -- weights and compute in bfloat16, greedy tokens, no
EOS.

The chip holds ONE CHIP'S SHARE of an expert-parallel stage, as
``families/mla_moe_serve.py`` does (this family takes
``cohere2_moe_serve``'s timing wrapper, sampling of the finished requests
and judgement of a token, as that one does): every head of both mixers, the
shared expert, the router's every output and bias, the routed experts the
configuration's ``experts_held`` names, a quarter of the vocabulary; of the
published layers those of ``layers_held``.  The reference is given the same
share.

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
from benchmarks.reference import kda_mla_moe_serve as reference

seed_key, layer_key = sparse.seed_key, sparse.layer_key

# The one number of the comparison, as families/cohere2_moe_serve.py has it:
# over a sample of the requests the window finished, the widest gap by which
# a served token's logit lies below the reference's best at its position, in
# units of that position's standard deviation over the vocabulary.  The
# reference is given the tokens and nothing else the program made; it routes
# every position by its own picks, runs the delta rule a position at a time
# in float32 and latent attention expanded, where the program ran the rule
# chunked from bfloat16 projections, carried its state over up to 32 row
# blocks and 512 decode steps, and decoded absorbed from a bfloat16 cache of
# latents.  Read on the chip at the cell's own size (PR 49, PERF.md section
# 6): sound runs 0.462-1.113 over 17 seeds (median 0.58, two past 0.9;
# 1076-2401 served tokens a reading, the longest request 29322-32974
# positions): a flipped pick at a near-tie puts a whole expert on or off the
# chip, as in the two sparse cells before, and here the stream also feeds a
# recurrence; the float8 control through this same comparison 2.553-2.744
# over 3 seeds, not correct on any.  The limit is the geometric middle
# (1.69): 1.5 times above the largest sound reading, 1.5 times below the
# smallest control.
GAP_LIMIT = 1.7


def _kinds(cfg: dict) -> list[tuple[str, bool]]:
    """(mixer, dense feed-forward?) of each layer held, by its published
    index (the reference's own rule)."""
    lo, hi = cfg["layers_held"]
    if hi - lo != cfg["num_hidden_layers"]:
        raise ValueError("num_hidden_layers counts the layers HELD "
                         "(layers_held); the published count is "
                         "num_hidden_layers_published")
    return [reference.layer_kind(cfg, local) for local in range(hi - lo)]


def model_config(cfg: dict, traffic: dict) -> TransformerConfig:
    refused = {
        "model_type": "bailing_hybrid", "hidden_act": "silu",
        "score_function": "sigmoid", "topk_method": "noaux_tc",
        "tie_word_embeddings": False, "q_lora_rank": None,
        "rope_scaling": None, "rope_interleave": True, "use_qk_norm": True,
        "gated_attention_proj_granularity_type": "head_wise",
        "linear_silu": True, "kda_safe_gate": True, "no_kda_lora": True,
        "use_kda_lora": False, "use_bias": False, "use_qkv_bias": False,
        "group_norm_size": 1, "value_norm": False, "up_proj_norm": False,
        "use_nGPT": False, "scale_router_input": False,
        "moe_router_enable_expert_bias": True,
        "num_kv_heads_for_linear_attn": 0,
        "num_key_value_heads": cfg.get("num_attention_heads"),
        "moe_shared_expert_intermediate_size":
            cfg.get("moe_intermediate_size"),
        "rotary_dim": cfg.get("qk_rope_head_dim")}
    wrong = {k: cfg.get(k) for k, v in refused.items() if cfg.get(k) != v}
    lo, hi = cfg["layers_held"]
    for key in ("expert_swiglu_limit_list", "share_expert_swiglu_limit_list"):
        if any(cfg[key][lo:hi]):
            wrong[key] = cfg[key][lo:hi]    # a clamp this family writes not
    if wrong:
        raise ValueError(f"kda_mla_moe_serve builds {refused} and no swiglu "
                         f"clamp; the configuration says {wrong}")
    first, last = cfg["experts_held"]
    if last - first != cfg["num_experts"]:
        raise ValueError("num_experts counts the experts HELD "
                         "(experts_held); the published count is "
                         "num_experts_published")
    kinds = _kinds(cfg)
    dense = [d for _, d in kinds]
    if dense != sorted(dense, reverse=True):
        raise ValueError("the dense layers held are the leading ones")
    # (a checkout before PR 49 has neither these fields nor models/kda.py
    # and says so at once: serve() fails on its first import)
    return TransformerConfig(
        vocab_size=cfg["vocab_size"], num_layers=len(kinds),
        layer_types=tuple("latent_attention" if kind == "mla" else "kda"
                          for kind, _ in kinds),
        num_heads=cfg["num_attention_heads"], embed_dim=cfg["hidden_size"],
        q_lora_rank=0, kv_lora_rank=cfg["kv_lora_rank"],
        qk_nope_head_dim=cfg["qk_nope_head_dim"],
        qk_rope_head_dim=cfg["qk_rope_head_dim"],
        v_head_dim=cfg["v_head_dim"], rope_theta=float(cfg["rope_theta"]),
        rope_interleaved=True, latent_qk_norm=True,
        attention_gate="head_wise",
        kda_heads=cfg["num_attention_heads"], kda_head_dim=cfg["head_dim"],
        kda_conv_width=cfg["short_conv_kernel_size"],
        kda_lower_bound=float(cfg["kda_lower_bound"]),
        norm_eps=float(cfg["rms_norm_eps"]), mlp_dim=cfg["intermediate_size"],
        first_dense_layers=sum(dense),
        moe_mlp_dim=cfg["moe_intermediate_size"],
        num_experts=cfg["num_experts_published"],
        experts_per_token=cfg["num_experts_per_tok"],
        norm_topk_prob=bool(cfg["norm_topk_prob"]), moe_selection="sigmoid",
        moe_routed_scale=float(cfg["routed_scaling_factor"]),
        num_shared_experts=cfg["num_shared_experts"],
        experts_held=(first, last), moe_expert_bias=True,
        moe_groups=cfg["n_group"], moe_topk_groups=cfg["topk_group"],
        feed_forward_chunk=cfg.get("feed_forward_chunk"),
        max_seq_len=int(traffic["max_seq_len"]), dtype=jnp.bfloat16,
        param_dtype=jnp.bfloat16)


def draw_layer(cfg: dict, kind: str, dense: bool, key) -> dict:
    """One layer's weights in the reference's layout, bfloat16: matrices
    normal with the ``assumed`` initializer_range, the norms' scales at 1,
    and the KDA layer's own as ``assumed.kda_draw`` says."""
    e, h = cfg["hidden_size"], cfg["num_attention_heads"]
    normal = sparse._normal(float(cfg["initializer_range"]))
    ones = lambda n: jnp.ones((n,), jnp.bfloat16)  # noqa: E731
    k = iter(jax.random.split(key, 24))
    w = {"input_layernorm": ones(e), "post_attention_layernorm": ones(e)}
    if kind == "kda":
        d, taps = cfg["head_dim"], cfg["short_conv_kernel_size"]
        tap = sparse._normal(float(cfg["kda_conv_init_std"]))
        w["kda"] = {
            **{f"{n}_proj": normal(next(k), e, h * d) for n in "qkvfg"},
            **{f"{n}_conv": tap(next(k), taps, h * d) for n in "qkv"},
            "b_proj": normal(next(k), e, h),
            # a decay rate of 1 to 16 a head, the Mamba-2 family's draw
            "A_log": jnp.log(jax.random.uniform(
                next(k), (h,), jnp.float32, 1.0, 16.0)).astype(jnp.bfloat16),
            "dt_bias": sparse._normal(1.0)(next(k), h * d),
            "o_norm": ones(d), "o_proj": normal(next(k), h * d, e)}
    else:
        nope, rot, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                         cfg["v_head_dim"])
        rank = cfg["kv_lora_rank"]
        w["mla"] = {
            "q_proj": normal(next(k), e, h * (nope + rot)),
            "q_norm": ones(nope + rot),
            "kv_a_proj_with_mqa": normal(next(k), e, rank + rot),
            "kv_a_layernorm": ones(rank), "k_rope_norm": ones(rot),
            "kv_b_proj": normal(next(k), rank, h * (nope + dv)),
            "gate_proj": normal(next(k), e, h),
            "o_proj": normal(next(k), h * dv, e)}
    if dense:
        f = cfg["intermediate_size"]
        w["mlp"] = {"gate_proj": normal(next(k), e, f),
                    "up_proj": normal(next(k), e, f),
                    "down_proj": normal(next(k), f, e)}
        return w
    held, f = cfg["num_experts"], cfg["moe_intermediate_size"]
    nf = cfg["num_shared_experts"] * cfg["moe_shared_expert_intermediate_size"]
    n = cfg["num_experts_published"]
    w["router"] = normal(next(k), e, n)
    w["expert_bias"] = sparse._normal(float(cfg["expert_bias_scale"]))(
        next(k), n)
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
    out = {"mlp_norm": {"scale": w["post_attention_layernorm"]}}
    if "kda" in w:
        m = w["kda"]
        out["kda_norm"] = {"scale": w["input_layernorm"]}
        out["kda"] = {
            **{n: kernel(m[f"{n}_proj"]) for n in "qkvfgbo"},
            **{f"{n}_conv": m[f"{n}_conv"] for n in "qkv"},
            "A_log": m["A_log"], "dt_bias": m["dt_bias"],
            "o_norm": {"scale": m["o_norm"]}}
    else:
        m = w["mla"]
        out["attn_norm"] = {"scale": w["input_layernorm"]}
        out["attn"] = {
            "q_up": kernel(m["q_proj"], h, -1),
            "q_head_norm": {"scale": m["q_norm"]},
            "kv_down": kernel(m["kv_a_proj_with_mqa"]),
            "kv_norm": {"scale": m["kv_a_layernorm"]},
            "k_rope_norm": {"scale": m["k_rope_norm"]},
            "kv_up": m["kv_b_proj"].reshape(m["kv_b_proj"].shape[0], h, -1),
            "gate": kernel(m["gate_proj"]),
            "o": {"kernel": m["o_proj"].reshape(h, -1,
                                                m["o_proj"].shape[-1])}}
    if "mlp" in w:
        out["mlp"] = {n: kernel(w["mlp"][f"{n}_proj"])
                      for n in ("gate", "up", "down")}
        return out
    ex, sh = w["experts"], w["shared_experts"]
    out["moe_mlp"] = {"router": w["router"], "expert_bias": w["expert_bias"],
                      "gate": ex["gate_proj"], "up": ex["up_proj"],
                      "down": ex["down_proj"], "shared_gate": sh["gate_proj"],
                      "shared_up": sh["up_proj"],
                      "shared_down": sh["down_proj"]}
    return out


def _top(cfg: dict, key):
    """(embedding, head), a jitted call each."""
    normal = sparse._normal(float(cfg["initializer_range"]))
    v, e = cfg["vocab_size"], cfg["hidden_size"]
    top = jax.random.split(jax.random.fold_in(key, 0))
    return (jax.jit(lambda k: normal(k, v, e))(top[0]),
            jax.jit(lambda k: normal(k, e, v))(top[1]))


@functools.lru_cache(maxsize=None)
def _layer_drawer(cfg_json: str, kind: str, dense: bool, program: bool):
    cfg = json.loads(cfg_json)
    lay = (lambda w: layer_to_program(w, cfg)) if program else (lambda w: w)
    return jax.jit(lambda k: lay(draw_layer(cfg, kind, dense, k)))


def drawn_layer(cfg: dict, key, local: int, program: bool = False) -> dict:
    """Layer ``local``'s weights of the seed ``key``: one jitted call, so
    that no layer lies on the chip in two layouts at once."""
    kind, dense = _kinds(cfg)[local]
    return _layer_drawer(json.dumps(_numbers(cfg), sort_keys=True), kind,
                         dense, program)(layer_key(key, local))


def _numbers(cfg: dict) -> dict:
    return {k: v for k, v in cfg.items()
            if isinstance(v, (int, float, list, bool, type(None)))}


def draw(cfg: dict, key) -> dict:
    """The weights in the reference's layout, all layers at once (the tests'
    sizes; a run's comparison draws a layer at a time)."""
    embedding, head = _top(cfg, key)
    return {"embed_tokens": embedding, "lm_head": head,
            "layers": [drawn_layer(cfg, key, i)
                       for i in range(cfg["num_hidden_layers"])],
            "norm": jnp.ones((cfg["hidden_size"],), jnp.bfloat16)}


def to_program(w: dict, cfg: dict) -> dict:
    return {"params": {
        "embed": {"embedding": w["embed_tokens"]},
        "lm_head": {"kernel": w["lm_head"]},
        "final_norm": {"scale": w["norm"]},
        **{f"layer_{i}": layer_to_program(layer, cfg)
           for i, layer in enumerate(w["layers"])}}}


def program_params(cfg: dict, key) -> dict:
    """The seed's weights in the program's layout."""
    embedding, head = _top(cfg, key)
    return {"params": {
        "embed": {"embedding": embedding}, "lm_head": {"kernel": head},
        "final_norm": {"scale": jnp.ones((cfg["hidden_size"],),
                                         jnp.bfloat16)},
        **{f"layer_{i}": drawn_layer(cfg, key, i, program=True)
           for i in range(cfg["num_hidden_layers"])}}}


def serve(cfg: dict, traffic: dict, chips: int, seed: int
          ) -> sparse.ServedSparse:
    if chips != 1:
        raise ValueError("kda_mla_moe_serve serves one data-parallel replica "
                         "of the expert-parallel group on one chip")
    from horovod_tpu.models.kda import kda_plan

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
    size = lambda p: int(np.prod(p.shape)) * p.dtype.itemsize  # noqa: E731
    # a cached position's bytes (the latent layers' rows) and a slot's
    # (the kda layers' states and tails)
    per_token = sum(size(side["latent"]) for side in pool) \
        // (slots * max_len)
    per_slot = sum(size(side["kda"]) for side in pool) // slots
    notes: dict = {"flash_prefill": backend.flash_prefill}
    kinds = _kinds(cfg)
    plan = {"experts": cfg["num_experts_published"],
            "experts_held": cfg["num_experts"],
            "held_from": cfg["experts_held"][0],
            "experts_per_token": cfg["num_experts_per_tok"],
            "shared_experts": cfg["num_shared_experts"],
            "selection": cfg["score_function"],
            "expert_bias": True, "groups": cfg["n_group"],
            "groups_kept": cfg["topk_group"],
            "norm_topk_prob": cfg["norm_topk_prob"],
            "routed_scale": cfg["routed_scaling_factor"],
            "layers": {"dense": sum(d for _, d in kinds),
                       "sparse": sum(not d for _, d in kinds)},
            "slots": slots}
    recurrent = {
        **kda_plan(mcfg),
        "cache": {"state_bytes_per_slot": per_slot,
                  "bytes_per_token": per_token,
                  "pool_bytes": per_slot * slots
                  + per_token * slots * max_len},
        "prefill_by_bucket": {
            b: {"kda_blocks": backend.kda_blocks(b, b),
                "latent": backend.prefill_attention(b),
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
        print("kda: " + json.dumps({**recurrent, **backend.kda_counters}))
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
        # the latent layer's bytes a cached position; what a slot holds
        # whatever its length (the kda layers' states) is on the kda: line,
        # and the harness's kv: line, which knows rows alone, leaves it out
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


def reference_rows(cfg: dict, traffic: dict, key, requests,
                   operand_dtype=None) -> list:
    """The reference's logits [T, V] at the positions that predict the
    served tokens of each of ``requests`` [(prompt, served), ...], T =
    len(served): a layer at a time over all of them (the whole forward of
    33 000 positions is no one program the chip's memory holds beside 10 GB
    of weights), each layer's weights drawn from ``key`` as the layer is
    reached and dropped after it, the streams donated from layer to
    layer."""
    rows = int(traffic["arrivals"]["output_tokens"]["max"])
    max_len = int(traffic["max_seq_len"])
    block = max(max_len // 128, 1)  # queries a block; the pads are multiples
    numbers = json.dumps(_numbers(cfg), sort_keys=True)
    held = tuple(cfg["experts_held"])
    embedding, head = _top(cfg, key)
    streams, where = [], []
    for prompt, served in requests:
        seq = np.concatenate([prompt, served]).astype(np.int32)
        pad = next(p for p in (32 * block, 64 * block, 128 * block)
                   if p >= max(len(seq), rows + 1))
        padded = np.zeros(pad, np.int32)
        padded[:len(seq)] = seq
        first = len(prompt) - 1         # the row that predicts served[0]
        where.append((first, min(first, pad - rows), len(served)))
        streams.append(_program("embed", lambda: jax.jit(reference.embed))(
            embedding, padded))
    for local in range(cfg["num_hidden_layers"]):
        w = drawn_layer(cfg, key, local)
        for i, x in enumerate(streams):
            layer = _program(
                "layer", lambda: jax.jit(lambda x, w: reference.layer(
                    x, w, cfg, local, held, block, operand_dtype)[0],
                    donate_argnums=0),
                numbers, x.shape[0], _kinds(cfg)[local], operand_dtype)
            streams[i] = layer(x, w)
        del w
    norm = jnp.ones((cfg["hidden_size"],), jnp.bfloat16)
    out = []
    for x, (first, start, n) in zip(streams, where):
        last = _program(
            "head", lambda: jax.jit(lambda x, norm, head, s:
                                    reference.head_rows(
                                        x, norm, head, cfg, s, rows,
                                        operand_dtype)),
            numbers, x.shape[0], operand_dtype)
        out.append(last(x, norm, head, start)[first - start:
                                              first - start + n])
    return out


def compare_served(cfg, traffic, finished, seed, control=None) -> list[dict]:
    """The comparison of a run, as ``cohere2_moe_serve.compare_served``:
    ``control`` is None in every run of the benchmark (the tokens compared
    are the ones the window served); given an operand type
    (``benchmarks/control.py`` and the tests give ``jnp.float8_e4m3fn``, the
    step below the configuration's bfloat16), the reference computed with
    operands of that type stands in the program's place."""
    chosen = sparse.sample(finished, seed, int(traffic["compare_requests"]),
                           int(traffic["max_seq_len"]))
    key = seed_key(seed)
    every = reference_rows(cfg, traffic, key, chosen)
    if control is None:
        judged = [jnp.asarray(served, jnp.int32) for _, served in chosen]
    else:
        judged = [jnp.argmax(logits, axis=-1).astype(jnp.int32)
                  for logits in reference_rows(cfg, traffic, key, chosen,
                                               operand_dtype=control)]
    gaps = [np.asarray(sparse.gaps_below_best(logits, tokens))
            for logits, tokens in zip(every, judged)]
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
