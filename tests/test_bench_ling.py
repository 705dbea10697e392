"""The benchmark's side of the Ling-3.0-flash configuration (PR 49): the
manifest's entries for ``Ling-3.0-flash`` and ``ling3f-longdoc32k-open``
(every published key, ``reduced``, the deployment, the traffic's parameters),
the counts of ``benchmarks/flops_kda.py``, the new readers on a hand-made
run, and a ``--rehearse-on-cpu`` walk of a tiny cell of the family through
``benchmarks/serving.py``, its files found by name: ``correct`` true as
served, false with a served token altered, and the float8 control, through
the run's own comparison, past the limit.  Here, and not under
``benchmarks/tests``, so that the tier-1 run holds them."""

import json
import os
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import flops_kda  # noqa: E402
from benchmarks.run import load_cell, load_module  # noqa: E402

from _rehearse import (assert_the_altered_record_is_not_correct,  # noqa: E402
                       walk)

CELL = "ling3f-longdoc32k-open"
TINY = {"family": "kda_mla_moe_serve", "model_type": "bailing_hybrid",
        "hidden_act": "silu", "hidden_size": 32, "intermediate_size": 48,
        "head_dim": 8, "num_attention_heads": 4, "num_key_value_heads": 4,
        "kda_lower_bound": -5, "kda_safe_gate": True, "linear_silu": True,
        "no_kda_lora": True, "use_kda_lora": False,
        "short_conv_kernel_size": 4, "group_norm_size": 1,
        "num_kv_heads_for_linear_attn": 0, "layer_group_size": 3,
        "kv_lora_rank": 8, "q_lora_rank": None, "qk_nope_head_dim": 8,
        "qk_rope_head_dim": 4, "rotary_dim": 4, "v_head_dim": 6,
        "rope_theta": 10000, "rope_scaling": None, "rope_interleave": True,
        "use_qk_norm": True,
        "gated_attention_proj_granularity_type": "head_wise",
        "first_k_dense_replace": 2, "moe_intermediate_size": 16,
        "moe_shared_expert_intermediate_size": 16, "num_shared_experts": 1,
        "num_experts": 4, "num_experts_published": 16,
        "experts_held": [4, 8], "num_experts_per_tok": 4, "n_group": 4,
        "topk_group": 2, "norm_topk_prob": True,
        "routed_scaling_factor": 2.5, "score_function": "sigmoid",
        "topk_method": "noaux_tc", "moe_router_enable_expert_bias": True,
        "scale_router_input": False, "use_bias": False,
        "use_qkv_bias": False, "use_nGPT": False, "value_norm": False,
        "up_proj_norm": False, "tie_word_embeddings": False,
        "rms_norm_eps": 1e-6, "num_hidden_layers": 4,
        "layers_held": [1, 5], "vocab_size": 256,
        "expert_swiglu_limit_list": [0] * 6,
        "share_expert_swiglu_limit_list": [0] * 6,
        "initializer_range": 0.5, "kda_conv_init_std": 0.3,
        "expert_bias_scale": 0.05, "feed_forward_chunk": 32}
TRAFFIC = {"why": "rehearsal", "unit": "tokens", "rate": 6.0,
           "lead_in_s": 0.5, "drain_s": 30, "num_slots": 3,
           "max_seq_len": 128, "prefill_buckets": [16, 32, 64],
           "arrivals": {"kind": "poisson_lognormal", "schedule_seed": 7,
                        "prompt_tokens": {"median": 24, "sigma": 0.6,
                                          "min": 8, "max": 64},
                        "output_tokens": {"median": 6, "sigma": 0.5,
                                          "min": 3, "max": 16}},
           "stream": {"kind": "markov_zipf_tokens", "zipf_a": 0.0,
                      "follow_prob": 0.5, "max_run": 8},
           "ttft_limit_ms": 1000.0, "tpot_limit_ms": 500.0,
           "compare_requests": 4}
NEW = {"kda_prefill_ms_per_ktoken.srv", "kda_decode_ms.srv",
       "kdamla_prefill_ms_per_ktoken.srv", "kdamla_decode_ms.srv",
       "kda_scan_roofline.srv", "kda_decode_state_roofline.srv",
       "kdamla_prefill_attn_roofline.srv", "kda_moe_held_pair_share.srv"}


def manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def catalog_entry():
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        return None
    with open(path) as f:
        rows = [json.loads(line) for line in f if line.strip()]
    return next(r for r in rows if r["name"] == "Ling-3.0-flash")


def test_the_manifest_holds_the_cell_and_its_metrics():
    m = manifest()
    # the eleventh cell and the ninth configuration (later PRs append theirs)
    cell = m["workloads"][10]
    assert (cell["name"], cell["config"], cell["traffic"], cell["chips"]) \
        == (CELL, "Ling-3.0-flash", "longdoc32k-open", 1)
    assert sum(c["chips"] == 4 for c in m["workloads"]) == 1
    entry = m["configs"][8]
    assert entry["name"] == "Ling-3.0-flash"
    assert entry["reduced"] == ["num_hidden_layers", "num_experts",
                                "vocab_size"]
    assert entry["source"] == ("https://huggingface.co/inclusionAI/"
                               "Ling-3.0-flash/blob/main/config.json")
    for e in m["configs"] + m["workloads"]:
        assert len(e["why"]) <= 200, e["name"]
    with open(os.path.join(ROOT, entry["file"])) as f:
        cfg = json.load(f)
    # every published width, and what routes
    widths = {
        "hidden_size": 2560, "num_attention_heads": 32, "head_dim": 128,
        "short_conv_kernel_size": 4, "kda_lower_bound": -5,
        "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "v_head_dim": 128,
        "kv_lora_rank": 512, "q_lora_rank": None,
        "moe_intermediate_size": 768,
        "moe_shared_expert_intermediate_size": 768,
        "num_experts_per_tok": 8, "n_group": 8, "topk_group": 4,
        "routed_scaling_factor": 2.5, "intermediate_size": 6144,
        "layer_group_size": 6, "first_k_dense_replace": 2,
        "topk_method": "noaux_tc", "moe_router_enable_expert_bias": True,
        "model_type": "bailing_hybrid", "rope_theta": 6000000}
    assert {k: cfg[k] for k in widths} == widths
    catalog = catalog_entry()
    if catalog is not None:     # the guide's row, where it can be read
        assert entry["source"] == catalog["source_url"]
        published = {k: v for k, v in catalog["config"].items()
                     if k not in entry["reduced"]}
        assert {k: cfg[k] for k in published} == published
        assert (catalog["config"]["num_hidden_layers"],
                catalog["config"]["num_experts"],
                catalog["config"]["vocab_size"]) == (
            cfg["num_hidden_layers_published"],
            cfg["num_experts_published"], cfg["vocab_size_published"])
    assert cfg["num_hidden_layers"] == 7 and cfg["layers_held"] == [1, 8]
    assert cfg["vocab_size"] == 157184 // 4
    # the experts HELD; the router's width and the published count beside it
    assert cfg["num_experts"] == 128 and cfg["experts_held"] == [0, 128]
    assert cfg["num_experts_published"] == cfg["router_width"] == 512
    assert list(cfg["reduced"]) == entry["reduced"]
    for said in ("28 chips", "4 chips share each layer",
                 "7 pipeline stages", "128 a chip", "5231.8 M in all",
                 "10.46 GB", "1152 bytes a token", "2.17 MB a slot",
                 "A QUARTER OF THE PAIRS", "deviceless compile"):
        assert said in cfg["deployment"], said
    assert {"kda_gate", "qk_norm_place", "expert_bias", "initializer_range",
            "serving_dtypes", "lengths_sigma"} <= set(cfg["assumed"])
    assert {"mtp", "head", "context", "exchange"} <= set(cfg["departures"])
    # the layers held, by the published rule: K K K K M K K, the first dense
    assert flops_kda.layers(cfg) == {"kda": 6, "mla": 1, "sparse": 6}
    family = load_module("families", "kda_mla_moe_serve")
    assert family._kinds(cfg) == [("kda", True)] + [("kda", False)] * 3 \
        + [("mla", False)] + [("kda", False)] * 2
    # the count the build: line will read, from the keys alone
    e, h, d = cfg["hidden_size"], 32, 128
    kda = 6 * e * h * d + 3 * 4 * h * d + e * h + h * d + h + d
    mla = (e * h * 192 + 192 + e * 576 + 512 + 64 + 512 * h * 256 + e * h
           + h * 128 * e)
    expert = 3 * e * 768
    sparse = expert + e * 512 + 512 + 128 * expert
    total = (6 * kda + mla + 7 * 2 * e + 3 * e * 6144 + 6 * sparse
             + 2 * cfg["vocab_size"] * e + e)
    assert round(total / 1e6, 1) == 5231.8
    assert round(kda / 1e6, 1) == 63.0 and round(mla / 1e6, 1) == 32.0

    *_, traffic = load_cell(os.path.join(ROOT, "BENCHMARK.json"), CELL)
    arrivals = traffic["arrivals"]
    assert arrivals["kind"] == "poisson_lognormal"
    assert arrivals["prompt_tokens"] == {
        "median": 5560, "sigma": 0.789, "min": 64, "max": 32768}
    assert arrivals["output_tokens"] == {
        "median": 84, "sigma": 1.239, "min": 1, "max": 512}
    assert "Mooncake" in arrivals["source"] and "recalled" in \
        arrivals["source"]
    assert traffic["prefill_buckets"] == [1024, 2048, 4096, 8192, 16384,
                                          32768]
    assert (traffic["max_seq_len"], traffic["lead_in_s"],
            traffic["compare_requests"]) == (33280, 5, 8)
    assert traffic["num_slots"] in (16, 12, 8)
    assert traffic["drain_s"] > 0 and traffic["stream"]["zipf_a"] == 0
    knee = traffic["knee"]["rate_per_s"]
    share = traffic["knee"]["share_of_capacity"]
    assert share in (0.7, 0.8)          # the issue's rate, or its fallback
    assert (share - 0.01) * knee <= traffic["rate"] <= (share + 0.01) * knee
    assert len(traffic["knee"]["below_capacity"]) >= 3
    unloaded = traffic["knee"]["unloaded"]
    assert traffic["ttft_limit_ms"] == pytest.approx(
        5 * unloaded["ttft_ms_32768_token_prompt"], rel=0.02)
    assert traffic["tpot_limit_ms"] == pytest.approx(
        3 * unloaded["decode_step_ms_every_slot_full"], rel=0.02)
    # the pool: one latent layer's rows, six KDA layers' states and tails
    slots = traffic["num_slots"]
    assert 16 * 33280 * (512 + 64) * 2 == 613416960
    assert 6 * (32 * 128 * 128 * 4 + 3 * 3 * 4096 * 2) == 13025280
    assert slots * 13025280 <= 208404480

    reported = {e["name"] for g in ("end_to_end", "per_layer")
                for e in m[g]
                if "workloads" not in e or CELL in e["workloads"]}
    assert NEW | {"ttft_ms_mean", "peak_hbm", "setup_s", "hbm_in_use",
                  "hbm_reserved", "device_idle.srv", "prefill_share.srv",
                  "decode_step_ms.srv", "prefill_ms_per_ktoken.srv",
                  "queue_ms_p95.srv", "moe_decode_ms.srv",
                  "moe_prefill_ms_per_ktoken.srv",
                  "idle_named_share.srv"} <= reported
    # readers that count a latent layer for every layer, that take every
    # layer's attn path or that assume a row a position are not this cell's
    assert not {"mla_prefill_attn_roofline.srv",
                "mla_decode_attn_roofline.srv", "mla_decode_ms.srv",
                "mla_prefill_ms_per_ktoken.srv", "kv_live_share.srv",
                "kv_live_peak_share.srv", "mla_moe_held_pair_share.srv",
                "moe_held_pair_share.srv", "tokens_per_s", "flash_ms"} \
        & reported
    names = [e["name"] for e in m["per_layer"]]
    at = names.index("kda_prefill_ms_per_ktoken.srv")
    assert set(names[at:at + 8]) == NEW     # appended, together
    layers = {e["layer"] for e in m["per_layer"][:at]} | {
        "models (models/kda.py)"}
    for e in m["per_layer"][at:at + 8]:
        assert e["workloads"] == [CELL] and e["moves"] == "ttft_ms_mean"
        assert e["layer"] in layers
        assert os.path.exists(os.path.join(
            ROOT, "benchmarks", "metrics", e["name"].split(".")[0] + ".py"))
        if "roofline" in e["name"]:
            assert e["unit"] == "%" and e["better"] == "higher"
    # every list the cell was appended to held it last (a later cell's name
    # may follow it)
    later = {c["name"] for c in m["workloads"][11:]}
    for g in ("end_to_end", "per_layer"):
        for e in m[g]:
            if CELL in e.get("workloads", ()):
                assert [w for w in e["workloads"] if w not in later][-1] \
                    == CELL, e["name"]


def test_the_schedule_is_typical_of_its_long_run():
    """As tests/test_bench_axk1.py holds longdoc16k-open: the first 35 s
    (lead-in and window) within 5% of the long run, at the file's rate and
    over the band the seed was chosen for before the capacity was read."""
    from benchmarks import arrivals

    *_, traffic = load_cell(os.path.join(ROOT, "BENCHMARK.json"), CELL)
    for name, ratio in arrivals.typical(traffic, 35.0).items():
        assert abs(ratio - 1.0) <= 0.05, (name, ratio)
    lo, hi = traffic["arrivals"]["schedule_seed_band"]
    assert lo <= traffic["rate"] <= hi
    rate = lo
    while rate <= hi + 1e-9:
        for name, ratio in arrivals.typical(
                dict(traffic, rate=round(rate, 2)), 35.0).items():
            assert abs(ratio - 1.0) <= 0.05, (rate, name, ratio)
        rate += 0.01
    sched = arrivals.schedule(traffic, 35.0)
    assert len(arrivals.schedule(traffic, 35.0)) \
        - len(arrivals.schedule(traffic, 5.0)) >= 40    # the window's count
    assert (sched.prompt_len > 16384).sum() >= 3    # the longest bucket works
    assert sched.prompt_len.max() <= 32768
    mean = arrivals.long_run(traffic)
    assert 7000 < mean["mean_prompt_tokens"] < 7600
    assert 135 < mean["mean_output_tokens"] < 155


CFG = {"num_attention_heads": 2, "head_dim": 4, "qk_nope_head_dim": 4,
       "qk_rope_head_dim": 2, "v_head_dim": 3, "layers_held": [1, 8],
       "layer_group_size": 6, "first_k_dense_replace": 2,
       "num_experts_per_tok": 4}


def test_counts_of_the_recurrence_and_of_the_share():
    assert flops_kda.layers(CFG) == {"kda": 6, "mla": 1, "sparse": 6}
    # 7 D^2 a head a position a kda layer: 10 positions, 2 heads of 4
    assert flops_kda.scan_flops(CFG, 10) == 7.0 * 2 * 16 * 10 * 6
    # Ling's own: 7 x 32 x 128^2 = 3.67 M operations a position a layer
    assert 7 * 32 * 128 ** 2 == 3670016
    # q, k, v, g in and o out at 2 bytes, the float32 state once a prompt
    assert flops_kda.scan_bytes(CFG, [3, 7]) == 6 * (
        5 * 8 * 2 * 10 + 4 * 32 * 2)
    # a live slot's state read and written once a layer: 2 x 4 x 32 values
    assert flops_kda.decode_state_bytes(CFG, [2, 1]) == 2 * 4 * 32 * 6 * 3
    assert 2 * 4 * 32 * 128 * 128 * 6 == 25165824      # Ling's, a slot
    # ONE latent layer's triangle of 6 positions: 21 pairs, 2 H (6 + 3)
    assert flops_kda.latent_prefill_flops(CFG, [6]) == 2.0 * 2 * 9 * 21
    assert 2 * 32 * (128 + 64 + 128) == 20480


def test_the_new_readers_read_a_hand_made_run_and_nothing_without_it():
    from benchmarks import serve_scopes
    from horovod_tpu.utils import profiling

    reader = lambda stem: load_module("metrics", stem)  # noqa: E731
    stems = sorted(n.split(".")[0] for n in NEW)
    training = types.SimpleNamespace(trace=None, peaks=None)
    for stem in stems:
        assert reader(stem).read(training) is None, stem
    pairs = [[0, 2, 0, 1], [1, 0, 0, 0]]
    decode = ("decode", 1.0, 1.1, 2, 12, {"pairs": pairs, "lengths": [3, 9]})
    prefill = ("prefill", 1.2, 1.3, 16, 6, {"pairs": pairs})
    lay = "Transformer/layer_N"
    joined = serve_scopes.Joined(
        calls={"decode": 1, "prefill": 1},
        module_s={"decode": {f"{lay}/kda/{profiling.KDA_SCAN}": 4e-3,
                             f"{lay}/kda/{profiling.KDA_PROJ}/q": 1e-3,
                             f"{lay}/attn/{profiling.MLA_ATTN}": 2e-3,
                             f"{lay}/moe_mlp/hvd_moe_shared": 2e-3},
                  "prefill": {f"{lay}/kda/{profiling.KDA_SCAN}": 4e-3,
                              f"{lay}/kda/{profiling.KDA_CONV}": 5e-3,
                              f"{lay}/attn/{profiling.MLA_UP}": 1e-3,
                              f"{lay}/mlp/up": 9e-3}},
        kernel_s={"decode": {}, "prefill": {"hvd_flash_fwd": 2e-3}},
        pathless_s={"decode": {}, "prefill": {"hvd_flash_fwd": 2e-3}},
        joined_share=1.0)
    run = types.SimpleNamespace(
        records=[], config=CFG, peaks={"hbm_bytes_per_s": 1e6,
                                       "bf16_flops_per_s": 1e9},
        traced_steps_log=[decode, prefill], steps=[decode, prefill],
        inside=lambda t: True, built=types.SimpleNamespace(num_slots=2),
        trace=types.SimpleNamespace(program_calls={"decode": 1}),
        _serve_scopes=joined)
    assert reader("kda_decode_ms").read(run) == pytest.approx(5.0)
    assert reader("kdamla_decode_ms").read(run) == pytest.approx(2.0)
    assert reader("kda_prefill_ms_per_ktoken").read(run) == pytest.approx(
        9.0 / 0.006)
    # 1 ms under attn + 2 ms of the pathless kernel, a 6-token prompt
    assert reader("kdamla_prefill_ms_per_ktoken").read(run) == \
        pytest.approx(3.0 / 0.006)
    # bytes bind: 6 layers x (5 x 8 x 2 x 6 + 4 x 32) bytes / 1e6
    assert reader("kda_scan_roofline").read(run) == pytest.approx(
        100 * flops_kda.scan_bytes(CFG, [6]) / 1e6 / 4e-3)
    assert reader("kda_decode_state_roofline").read(run) == pytest.approx(
        100 * flops_kda.decode_state_bytes(CFG, [2]) / 1e6 / 4e-3)
    assert reader("kdamla_prefill_attn_roofline").read(run) == \
        pytest.approx(100 * flops_kda.latent_prefill_flops(CFG, [6]) / 1e9
                      / 2e-3)
    # 4 pairs held a call of (6 prompt positions + 2 live slots) x 6 x 4
    assert reader("kda_moe_held_pair_share").read(run) == pytest.approx(
        100 * 8 / (8 * 24))
    # a program that names no kda scope (the parent's): nothing, no raise
    joined.module_s = {"decode": {f"{lay}/attn/o": 1e-3},
                       "prefill": {f"{lay}/mlp/up": 9e-3}}
    for stem in ("kda_decode_ms", "kda_prefill_ms_per_ktoken",
                 "kda_scan_roofline", "kda_decode_state_roofline"):
        assert reader(stem).read(run) is None, stem


@pytest.fixture(scope="module")
def walked(tmp_path_factory):
    """The file's one walk of the tiny cell (``tests/_rehearse.py``)."""
    return walk(tmp_path_factory.mktemp("walk"), "tiny-ling", TINY, TRAFFIC,
                CELL)


def test_a_tiny_cell_walks_serving_py_on_the_cpu(walked):
    result, _, stdout = walked
    assert result["correct"], stdout[-3000:]
    assert result["failed"] == 0 and result["attempted"] >= 5
    names = set(result["metrics"])
    assert "kda_moe_held_pair_share.srv" in names   # the program's counter
    assert 5.0 < result["metrics"]["kda_moe_held_pair_share.srv"]["value"] \
        < 60.0
    # device metrics are never made up from a CPU trace
    assert not (NEW - {"kda_moe_held_pair_share.srv"}) & names
    assert "device_idle.srv" not in names
    assert "family=kda_mla_moe_serve" in stdout
    moe = json.loads(stdout.split("moe: ")[1].splitlines()[0])
    assert (moe["experts"], moe["experts_held"], moe["held_from"]) == (
        16, 4, 4)
    assert moe["layers"] == {"dense": 1, "sparse": 3}
    assert (moe["groups"], moe["groups_kept"], moe["expert_bias"]) == (
        4, 2, True)
    assert 0 < moe["held_pairs"] < moe["pairs"]
    kda = json.loads(stdout.split("kda: ")[1].splitlines()[0])
    # published layers 1-4 of a period of 3: K M K K
    assert kda["layers"] == {"kda": 3, "latent_attention": 1}
    # a state of 4 x 8 x 8 float32 and a tail of 3 x 3 x 32 bfloat16 a layer
    assert kda["state_bytes_per_layer_and_slot"] == 1024
    assert kda["conv_tail_bytes_per_layer_and_slot"] == 576
    assert kda["cache"] == {"state_bytes_per_slot": 3 * 1600,
                            "bytes_per_token": (8 + 4) * 2,
                            "pool_bytes": 3 * 4800 + 24 * 3 * 128}
    assert kda["form"] == {"prefill": "chunked", "decode": "step"}
    # the tiny model's widths (heads of 8) are off the kernel's rule
    assert kda["scan"] == "xla"
    assert kda["prefill_by_bucket"]["64"] == {
        "kda_blocks": 1, "latent": "dense", "feed_forward_chunks": 2}
    assert kda["kda_blocks"] > 0 and kda["state_slots"] > 0
    assert "kv: bytes_per_token=24 " in stdout
    gap, limit = result["compared"]["served_token_gap_below_reference_best"]
    assert gap < limit
    checks = json.loads(stdout.split("checks=")[1].splitlines()[0])
    assert checks[0]["requests"] == 4 and checks[0]["longest"] > 32


def test_an_altered_served_token_is_not_correct(walked):
    assert_the_altered_record_is_not_correct(walked)


@pytest.fixture(scope="module")
def control_family():
    """The family loaded once for the control's three seeds: its reference's
    programs (``_PROGRAMS``: a layer, the head, a dtype each) are keyed on
    shapes and numbers and not on the seed, whose weights are arguments, so
    the second and third seeds compile nothing."""
    return load_module("families", "kda_mla_moe_serve")


@pytest.mark.parametrize("seed", [1, 2, 2**31 + 6])
def test_the_float8_control_fails_the_comparison(seed, control_family):
    """The reference with float8 operands put in the program's place and
    judged by the run's own comparison and limit is not correct; the
    reference's own first choices, judged the same way, are (gap 0).  The
    toy is given 12 layers: float8's error compounds with depth."""
    import jax.numpy as jnp
    import numpy as np

    family = control_family
    cfg = dict(TINY, num_hidden_layers=12, layers_held=[1, 13],
               expert_swiglu_limit_list=[0] * 13,
               share_expert_swiglu_limit_list=[0] * 13)
    traffic = dict(TRAFFIC, compare_requests=8)
    rng = np.random.default_rng(seed % 2**31)
    finished = [(rng.integers(0, 256, n), rng.integers(0, 256, 16))
                for n in (20, 31, 40, 47, 56, 64, 80, 96)]
    control, = family.compare_served(cfg, traffic, finished, seed,
                                     control=jnp.float8_e4m3fn)
    assert not control["ok"] and control["error"] > family.GAP_LIMIT
    assert control["tokens"] == 8 * 16 and control["longest"] == 112
    exact, = family.compare_served(cfg, traffic, finished, seed,
                                   control=jnp.float32)
    assert exact["ok"] and exact["error"] < 1e-3
