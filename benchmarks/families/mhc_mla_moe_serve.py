"""Family ``mhc_mla_moe_serve``: a ``xing4_0`` decoder (XingChen-AGI's
Xing4.0-29B-A4B: a residual stream of four rows a position, mixed around
every sublayer by manifold-constrained hyper-connections -- coefficients
made from the stream itself, the rows' own mix Sinkhorn-normalised -- around
latent attention in every layer, two leading dense layers, then top-4 of 64
sigmoid-routed experts picked by a bias-corrected score beside one shared
expert, an untied head) served through the path a user takes --
``horovod_tpu.serving.ServingEngine`` over ``TransformerBackend``, whose
pool is ``mla_moe_serve``'s cache of latents unchanged, whose prefill
buckets and decode program carry a stream ``[.., 4, hidden]`` between
sublayers, whose prefill runs latent attention expanded through the flash
forward kernel and whose decode runs it absorbed -- weights and compute in
bfloat16, the hyper-connections' coefficients, the router and the softmax
in float32, greedy tokens, no EOS.

The chip holds the FIRST OF FIVE PIPELINE STAGES whole: layers 0-7 as
published (both dense layers and six sparse ones), every head, every
expert, the whole vocabulary (and the head, the last stage's in the
deployment, so that a token can be sampled).  A step's (token, expert)
pairs are tokens x 4 whatever the router picks.  This family takes
``cohere2_moe_serve``'s timing wrapper, sampling of the finished requests
and judgement of a token, and ``mla_moe_serve``'s attention weights' layout.

The configuration file holds Hugging Face's keys; this module maps them onto
``TransformerConfig`` and refuses what the program cannot express.  The
weights are the benchmark's own: drawn here from ``--seed``, a layer a
jitted call, in the type they are served in, handed to the program in its
layout and, drawn again after the window a layer at a time, to the plain
reference in the reference's.

The family prints an ``mhc:`` line beside ``moe:`` and ``mla:``: the largest
column error Sinkhorn left and the bytes the stream's mixes cannot avoid
moving (``benchmarks/flops_mhc.py``).  What the new residual path cost on
the device, by program, from the four ``hvd_mhc_*`` scopes
(``benchmarks/serve_scopes.py``), is :func:`mhc_line`'s for a caller that
has the traced run.  ``BENCHMARK.json`` holds 128 of 128 ``per_layer``
entries, so no checked metric reads ``models/hyper.py`` yet (PERF.md
section 7).
"""

from __future__ import annotations

import functools
import json
import math
import statistics
import time

import jax
import jax.numpy as jnp
import numpy as np

from horovod_tpu.models import Transformer, TransformerConfig
from horovod_tpu.models.transformer import init_kv_cache
from horovod_tpu.serving import ServingConfig, ServingEngine
from horovod_tpu.serving.engine import TransformerBackend
from horovod_tpu.utils import profiling

from benchmarks import compare, flops_mhc, scopes, serve_scopes
from benchmarks.families import cohere2_moe_serve as sparse
from benchmarks.families import mla_moe_serve as mla
from benchmarks.reference import mhc_mla_moe_serve as reference

seed_key, layer_key = sparse.seed_key, sparse.layer_key
BF16 = jnp.bfloat16

# The comparison, as families/cohere2_moe_serve.py has it but for its
# statistic: over a sample of the requests the window finished, the gap by
# which a served token's logit lies below the reference's best at its
# position, in units of that position's standard deviation over the
# vocabulary.  The reference is given the tokens and nothing else the program
# made; it routes every position by its own picks and makes every sublayer's
# coefficients from its own float32 stream, where the program's stream is
# bfloat16 between sublayers.  The other served families judge the WIDEST
# such gap.  Here a sound run's widest reads 1.33-3.10 over 33 seeds
# (A.X-K1: 0.18-0.61) where the float8 control's reads 4.83-7.24 over six: no
# limit stands 1.5 times above the one and at half the other (the reference
# itself with bfloat16 operands, the program's precision and none of its
# code, reads the program's gaps: 1.89 beside 2.00 on one seed; the traffic
# file).  Two numbers of the
# same gaps are held instead, each as a share of its limit, the larger of
# the two against 1 (PR 56's lesson: a comparison's statistic is part of its
# limit).  The MEAN over all the tokens compared separates the precisions:
# sound 0.024-0.052, the control 1.50-1.69; it would let one token in twenty
# be wrong.  The share of the tokens compared that lie FAR below the best is
# what a few wrong tokens move: a token that is not this position's (a slot
# serving another sequence's, a sampling fault) reads what one drawn at
# random does, the expected best of a vocabulary of standard normals (4.34
# for 131 072), and far is :data:`FAR_OF_RANDOM` of that (3.47).  No token of
# 33 sound runs (over 60 000 compared) lies there and 3.8% of the control's
# do; three in a thousand may, so six wrong tokens of 1700 are past it.
# The readings of both are in benchmarks/traffic/chat4k-open-xing4.json
# ("compare").
MEAN_GAP_LIMIT = 0.25
FAR_OF_RANDOM = 0.8
FAR_SHARE_LIMIT = 0.003


def random_gap(vocab: int) -> float:
    """The expected best of ``vocab`` standard normals, to second order:
    the gap a token drawn at random reads."""
    a = math.sqrt(2.0 * math.log(vocab))
    return a - (math.log(math.log(vocab)) + math.log(4.0 * math.pi)) / (2 * a)


def model_config(cfg: dict, traffic: dict) -> TransformerConfig:
    refused = {
        "model_type": "xing4_0", "hidden_act": "silu",
        "attention_bias": False, "scoring_func": "sigmoid",
        "topk_method": "noaux_tc", "n_group": 1, "topk_group": 1,
        "tie_word_embeddings": False, "moe_layer_freq": 1, "ep_size": 1,
        "num_key_value_heads": cfg.get("num_attention_heads")}
    wrong = {k: cfg.get(k) for k, v in refused.items() if cfg.get(k) != v}
    if cfg.get("rope_scaling", {}).get("type") != "yarn":
        wrong["rope_scaling.type"] = cfg.get("rope_scaling", {}).get("type")
    if wrong:
        raise ValueError(f"mhc_mla_moe_serve builds {refused} and yarn; the "
                         f"configuration says {wrong}")
    y = cfg["rope_scaling"]
    layers = cfg["num_hidden_layers"]
    # a checkout before PR 59 has no hyper_* fields and says so (a TypeError)
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
        num_experts=cfg["n_routed_experts"],
        experts_per_token=cfg["num_experts_per_tok"],
        norm_topk_prob=bool(cfg["norm_topk_prob"]), moe_selection="sigmoid",
        moe_routed_scale=float(cfg["routed_scaling_factor"]),
        moe_expert_bias=True, num_shared_experts=cfg["n_shared_experts"],
        hyper_streams=cfg["hc_mult"],
        hyper_sinkhorn_iters=cfg["hc_sinkhorn_iters"],
        hyper_eps=float(cfg["hc_eps"]),
        hyper_res_clamp=(float(cfg["mhc_h_res_clamp_min"]),
                         float(cfg["mhc_h_res_clamp_max"])),
        feed_forward_chunk=cfg.get("feed_forward_chunk"),
        max_seq_len=int(traffic["max_seq_len"]), dtype=BF16,
        param_dtype=BF16)


def held(cfg: dict) -> tuple:
    return (0, cfg["n_routed_experts"])


def draw_hyper(cfg: dict, key) -> dict:
    """One sublayer's hyper-connection as ``assumed.hyper_draw`` says: the
    three phi normal at initializer_range, alpha 1, the biases 0."""
    n, e = cfg["hc_mult"], cfg["hidden_size"]
    normal = sparse._normal(float(cfg["initializer_range"]))
    k = jax.random.split(key, 3)
    return {"phi_pre": normal(k[0], n * e, n),
            "phi_post": normal(k[1], n * e, n),
            "phi_res": normal(k[2], n * e, n * n),
            "b_pre": jnp.zeros((n,), BF16), "b_post": jnp.zeros((n,), BF16),
            "b_res": jnp.zeros((n, n), BF16), "alpha": jnp.ones((3,), BF16)}


def draw_layer(cfg: dict, dense: bool, key) -> dict:
    """One layer's weights (a leading ``dense`` one, or a sparse one) in the
    reference's layout, bfloat16: ``mla_moe_serve``'s attention and
    feed-forward draws (matrices normal at initializer_range, the norms'
    scales at 1) beside the two hyper-connections and, sparse, the routing
    bias (normal at expert_bias_scale)."""
    k_attn, k_a, k_m, k_bias = jax.random.split(key, 4)
    w = mla.draw_layer({**cfg, "n_routed_experts_published":
                        cfg["n_routed_experts"]}, dense, k_attn)
    w["attn_hc"], w["mlp_hc"] = draw_hyper(cfg, k_a), draw_hyper(cfg, k_m)
    if not dense:
        w["e_score_correction_bias"] = sparse._normal(
            float(cfg["expert_bias_scale"]))(k_bias, cfg["n_routed_experts"])
    return w


def hyper_to_program(w: dict, cfg: dict) -> dict:
    """models/hyper.py's layout: the three phi side by side (pre | post |
    res) as [n, C, n (n + 2)], the biases likewise."""
    n, e = cfg["hc_mult"], cfg["hidden_size"]
    return {"phi": jnp.concatenate([w["phi_pre"], w["phi_post"],
                                    w["phi_res"]], axis=1).reshape(n, e, -1),
            "bias": jnp.concatenate([w["b_pre"], w["b_post"],
                                     w["b_res"].reshape(-1)]),
            "alpha": w["alpha"]}


def layer_to_program(w: dict, cfg: dict) -> dict:
    """One layer as ``models/transformer.py`` lays it out: reshapes and
    concatenations alone."""
    out = mla.layer_to_program(w, cfg)
    out["attn_hc"] = hyper_to_program(w["attn_hc"], cfg)
    out["mlp_hc"] = hyper_to_program(w["mlp_hc"], cfg)
    if "moe_mlp" in out:
        out["moe_mlp"]["expert_bias"] = w["e_score_correction_bias"]
    return out


def _numbers(cfg: dict) -> dict:
    return {k: v for k, v in cfg.items()
            if isinstance(v, (int, float, list, bool, type(None)))
            or k == "rope_scaling"}


@functools.lru_cache(maxsize=None)
def _layer_drawer(cfg_json: str, dense: bool, program: bool):
    cfg = json.loads(cfg_json)
    lay = (lambda w: layer_to_program(w, cfg)) if program else (lambda w: w)
    return jax.jit(lambda k: lay(draw_layer(cfg, dense, k)))


def drawn_layer(cfg: dict, key, local: int, program: bool = False) -> dict:
    """Layer ``local``'s weights of the seed ``key``: one jitted call, so
    that no layer lies on the chip in two layouts at once."""
    return _layer_drawer(json.dumps(_numbers(cfg), sort_keys=True),
                         local < cfg["first_k_dense_replace"],
                         program)(layer_key(key, local))


def _top(cfg: dict, key):
    """(embedding, head), a jitted call each."""
    normal = sparse._normal(float(cfg["initializer_range"]))
    v, e = cfg["vocab_size"], cfg["hidden_size"]
    top = jax.random.split(jax.random.fold_in(key, 0))
    return (jax.jit(lambda k: normal(k, v, e))(top[0]),
            jax.jit(lambda k: normal(k, e, v))(top[1]))


def _final_norm(cfg: dict):
    return jnp.ones((cfg["hidden_size"],), BF16)


def draw(cfg: dict, key) -> dict:
    """The weights in the reference's layout, all layers at once (the tests'
    sizes; a run's comparison draws a layer at a time)."""
    embedding, head = _top(cfg, key)
    return {"embed_tokens": embedding, "lm_head": head,
            "layers": [drawn_layer(cfg, key, i)
                       for i in range(cfg["num_hidden_layers"])],
            "norm": _final_norm(cfg)}


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
        "final_norm": {"scale": _final_norm(cfg)},
        **{f"layer_{i}": drawn_layer(cfg, key, i, program=True)
           for i in range(cfg["num_hidden_layers"])}}}


# Where XLA:TPU puts the mixes in a prefill program (read in the programs'
# text, PR 59): the rows a sublayer reads are mixed inside its NORM's fusion,
# and the rows it writes inside the EPILOGUE of the projection that ends it
# (the attention's o, the dense down, the shared expert's down), often with
# the next sublayer's flat norm in the same fusion.  Those fusions are filed
# under these module names, not under an ``hvd_mhc_*`` scope, and their time
# holds the projections' own products: it is printed beside the scopes' and
# divided into nothing.
MIX_HOSTS = ("attn_norm", "mlp_norm", "o", "down", profiling.MOE_SHARED)


def mhc_line(cfg: dict, run, col_sum_err: float, prompt_tokens=()) -> dict:
    """The ``mhc:`` line.  Counters where ``run`` is None (what
    ``release()`` prints: ``benchmarks/serving.py`` hands it nothing): the
    largest column error Sinkhorn left and the bytes the stream's mixes
    cannot avoid moving over the prefills logged
    (``flops_mhc.prefill_bytes`` at the prompts' own lengths).  Given a
    traced ``ServeRun`` (a reader under ``benchmarks/metrics/`` has one),
    also the device time under the four ``hvd_mhc_*`` scopes by program and
    under :data:`MIX_HOSTS`, and the needed bytes of the traced prefills.
    No share of a roofline: as long as XLA fuses the mixes into their
    neighbours' fusions the scopes' time leaves work out (the share read
    105%), and the neighbours' holds work that is not the stream's."""
    out = {"streams": cfg["hc_mult"], "sinkhorn_iters":
           cfg["hc_sinkhorn_iters"], "col_sum_err": col_sum_err}
    if prompt_tokens:
        out["needed_bytes_prefill"] = flops_mhc.prefill_bytes(
            cfg, prompt_tokens)
        out["prompt_tokens"] = int(sum(prompt_tokens))
    joined = serve_scopes.of(run) if run is not None else None
    if joined is None:
        return out
    under = lambda program, names: sum(  # noqa: E731
        joined.under(program, name) for name in names)
    prefills = serve_scopes.traced(run, "prefill")
    tokens = sum(e[4] for e in prefills)
    if joined.calls["prefill"] and tokens:
        out["prefill_ms_per_ktoken"] = 1e3 * under(
            "prefill", profiling.MHC_SCOPES) / (tokens / 1e3)
        out["prefill_ms_by_scope"] = {
            name: 1e3 * joined.under("prefill", name)
            for name in profiling.MHC_SCOPES + MIX_HOSTS}
        out["needed_bytes_prefill"] = flops_mhc.prefill_bytes(
            cfg, [e[4] for e in prefills])
        out["prompt_tokens"] = tokens
    if joined.calls["decode"]:
        steps = joined.calls["decode"]
        out["decode_ms"] = 1e3 * under("decode", profiling.MHC_SCOPES) / steps
        out["sinkhorn_decode_ms"] = 1e3 * under(
            "decode", (profiling.MHC_SINKHORN,)) / steps
    out["joined_share"] = joined.joined_share
    return out


def serve(cfg: dict, traffic: dict, chips: int, seed: int
          ) -> sparse.ServedSparse:
    if chips != 1:
        raise ValueError("mhc_mla_moe_serve serves one pipeline stage on "
                         "one chip")
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
    dense_layers = cfg["first_k_dense_replace"]
    sparse_layers = cfg["num_hidden_layers"] - dense_layers
    experts = cfg["n_routed_experts"]
    expert_bytes = 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"] * 2
    plan = {"experts": experts, "experts_held": experts, "held_from": 0,
            "experts_per_token": cfg["num_experts_per_tok"],
            "shared_experts": cfg["n_shared_experts"],
            "selection": cfg["scoring_func"], "expert_bias": True,
            "norm_topk_prob": cfg["norm_topk_prob"],
            "routed_scale": cfg["routed_scaling_factor"],
            "layers": {"dense": dense_layers, "sparse": sparse_layers},
            "slots": slots, "expert_bytes": expert_bytes}
    latent = {"cache": {"latent": mcfg.kv_lora_rank,
                        "rotary_key": mcfg.qk_rope_head_dim,
                        "bytes_per_token": per_token,
                        "pool_bytes": per_token * slots * max_len},
              "form": {"prefill": "expanded", "decode": "absorbed"},
              "prefill_attention": {b: backend.prefill_attention(b)
                                    for b in buckets},
              "row_blocks": {b: (backend.prefill_rows(b, b) or 0) // 1024
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
        c = backend.moe_counters
        decodes = [e for e in timed.log if e[0] == "decode" and len(e) > 5]
        touched = [sum(1 for layer in e[5]["pairs"] for n in layer if n)
                   for e in decodes]
        print("moe: " + json.dumps({
            **plan, **c,
            "held_pair_share_pct": 100.0 * c["held_pairs"]
            / max(c["pairs"], 1),
            # of the decode steps logged: the share of (layer, expert)
            # weights a step's live slots picked, and the bytes that is
            "experts_touched_share_pct": 100.0 * statistics.mean(touched)
            / (sparse_layers * experts) if touched else None,
            "expert_bytes_touched_a_step": statistics.mean(touched)
            * expert_bytes if touched else None}))
        print("mla: " + json.dumps(latent))
        # counters: benchmarks/serving.py hands a family's release nothing,
        # so the scopes' device time waits for a reader that is handed the
        # traced run (PERF.md section 7)
        print("mhc: " + json.dumps(mhc_line(
            cfg, None, backend.mhc_col_sum_err,
            [e[4] for e in timed.log if e[0] == "prefill"])))
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


_PROGRAMS: dict = {}


def _program(name: str, make, *key):
    if (name, *key) not in _PROGRAMS:
        _PROGRAMS[name, *key] = make()
    return _PROGRAMS[name, *key]


def reference_streams(cfg: dict, traffic: dict, key, requests,
                      operand_dtype=None) -> list:
    """The reference's stream after the last layer, [pad, n, E] float32, of
    each of ``requests`` [(prompt, served), ...], with where the rows that
    predict its served tokens lie ``(first, start, n)``: a layer at a time
    over all of them (22.7 GB of float32 weights are no one program), each
    layer's weights drawn from ``key`` as the layer is reached and dropped
    after it, the streams donated from layer to layer."""
    rows = int(traffic["arrivals"]["output_tokens"]["max"])
    max_len = int(traffic["max_seq_len"])
    block = max(max_len // 128, 1)  # queries a block; the pads are multiples
    numbers = json.dumps(_numbers(cfg), sort_keys=True)
    embedding = _top(cfg, key)[0]
    streams, where = [], []
    for prompt, served in requests:
        seq = np.concatenate([prompt, served]).astype(np.int32)
        pad = next(p for p in (32 * block, 64 * block, 128 * block)
                   if p >= max(len(seq), rows + 1))
        padded = np.zeros(pad, np.int32)
        padded[:len(seq)] = seq
        first = len(prompt) - 1         # the row that predicts served[0]
        where.append((first, min(first, pad - rows), len(served)))
        streams.append(_program(
            "embed", lambda: jax.jit(lambda e, t: reference.embed(e, t, cfg)),
            numbers)(embedding, padded))
    del embedding
    for local in range(cfg["num_hidden_layers"]):
        w = drawn_layer(cfg, key, local)
        for i, x in enumerate(streams):
            layer = _program(
                "layer", lambda: jax.jit(lambda x, w: reference.layer(
                    x, w, cfg, local, held(cfg), block, operand_dtype)[0],
                    donate_argnums=0),
                numbers, x.shape[0], local < cfg["first_k_dense_replace"],
                operand_dtype)
            streams[i] = layer(x, w)
        del w
    return [(x, *at) for x, at in zip(streams, where)]


def _logits(cfg: dict, traffic: dict, head, stream, operand_dtype=None):
    """The reference's logits [T, V] at the positions that predict one
    request's served tokens, from its final stream."""
    rows = int(traffic["arrivals"]["output_tokens"]["max"])
    x, first, start, n = stream
    last = _program(
        "head", lambda: jax.jit(
            lambda x, norm, head, s: reference.head_rows(
                x, norm, head, cfg, s, rows, operand_dtype)),
        json.dumps(_numbers(cfg), sort_keys=True), x.shape[0], operand_dtype)
    return last(x, _final_norm(cfg), head, start)[
        first - start:first - start + n]


def judge(gaps: list, vocab: int) -> dict:
    """The check of a run from each compared request's gaps: the mean gap
    and the share of gaps that lie far below the best, each as a share of
    its limit, the larger against 1; both readings, the 99th percentile and
    the widest beside it."""
    if not gaps:    # nothing finished is nothing shown: no limit admits it
        return compare.check("served_token_gap_share_of_limit", 1e9, 1.0)
    every = np.concatenate(gaps)
    far_gap = FAR_OF_RANDOM * random_gap(vocab)
    mean, far = float(every.mean()), float((every > far_gap).mean())
    out = compare.check("served_token_gap_share_of_limit",
                        max(mean / MEAN_GAP_LIMIT, far / FAR_SHARE_LIMIT), 1.0)
    out.update(mean=mean, mean_limit=MEAN_GAP_LIMIT, far_gap=far_gap,
               far_share=far, far_limit=FAR_SHARE_LIMIT,
               far_tokens=int((every > far_gap).sum()),
               over_1_share=float((every > 1.0).mean()),
               p99=float(np.percentile(every, 99)), widest=float(every.max()))
    return out


def compare_served(cfg, traffic, finished, seed, control=None) -> list[dict]:
    """The comparison of a run, as ``cohere2_moe_serve.compare_served`` with
    another statistic (:func:`judge`, :data:`MEAN_GAP_LIMIT`,
    :data:`FAR_SHARE_LIMIT`): over a sample of the requests the window
    finished, the gaps by which a served token's logit lies below the
    reference's best at its position.  ``control`` is None in every run of
    the benchmark (the tokens compared are the ones the window served); given
    an operand type
    (``benchmarks/control.py`` and the tests give ``jnp.float8_e4m3fn``, the
    step below the configuration's bfloat16), the reference computed with
    operands of that type stands in the program's place.  A request's
    logits (1024 rows of 131 072) are made, judged and dropped before the
    next request's."""
    chosen = sparse.sample(finished, seed, int(traffic["compare_requests"]),
                           int(traffic["max_seq_len"]))
    key = seed_key(seed)
    every = reference_streams(cfg, traffic, key, chosen)
    stood_in = None if control is None else reference_streams(
        cfg, traffic, key, chosen, operand_dtype=control)
    head = _top(cfg, key)[1]
    gaps = []
    for i, (_, served) in enumerate(chosen):
        if stood_in is None:
            judged = jnp.asarray(served, jnp.int32)
        else:
            judged = jnp.argmax(_logits(cfg, traffic, head, stood_in[i],
                                        control), axis=-1).astype(jnp.int32)
        gaps.append(np.asarray(sparse.gaps_below_best(
            _logits(cfg, traffic, head, every[i]), judged)))
        every[i] = None
    out = judge(gaps, cfg["vocab_size"])
    out["requests"] = len(chosen)
    out["tokens"] = sum(len(served) for _, served in chosen)
    out["longest"] = max((len(p) + len(s) for p, s in chosen), default=0)
    # where each request's widest gap lies: (its prompt's length, its served
    # tokens, the gap, the served token's index)
    out["by_request"] = [[len(p), len(s), round(float(g.max()), 4),
                          int(g.argmax())]
                         for (p, s), g in zip(chosen, gaps)]
    return [out]
