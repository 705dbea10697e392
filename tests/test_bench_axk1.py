"""The benchmark's side of the A.X-K1 configuration (PR 42): the manifest's
entries for ``A.X-K1`` and ``axk1-longdoc16k-open`` (every published key,
``reduced``, the deployment, the traffic's parameters), the counts of
``benchmarks/flops_mla.py``, the new readers on a hand-made run, and a
``--rehearse-on-cpu`` walk of a tiny cell of the family through
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

from benchmarks import flops_mla  # noqa: E402
from benchmarks.run import load_cell, load_module  # noqa: E402

from _rehearse import (assert_the_altered_record_is_not_correct,  # noqa: E402
                       walk)

CELL = "axk1-longdoc16k-open"
TINY = {"family": "mla_moe_serve", "model_type": "axk1",
        "attention_bias": False, "first_k_dense_replace": 1,
        "hidden_act": "silu", "hidden_size": 32, "intermediate_size": 48,
        "kv_lora_rank": 8, "moe_intermediate_size": 16, "moe_layer_freq": 1,
        "n_group": 8, "n_routed_experts": 4,
        "n_routed_experts_published": 16, "experts_held": [4, 8],
        "n_shared_experts": 1, "norm_topk_prob": True,
        "num_attention_heads": 4, "num_experts_per_tok": 4,
        "num_hidden_layers": 3, "num_key_value_heads": 4, "q_lora_rank": 16,
        "qk_nope_head_dim": 8, "qk_rope_head_dim": 4, "rms_norm_eps": 1e-6,
        "rope_theta": 10000,
        "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 32,
                         "mscale": 1, "mscale_all_dim": 1,
                         "original_max_position_embeddings": 16,
                         "type": "yarn"},
        "routed_scaling_factor": 2.5, "scoring_func": "sigmoid",
        "tie_word_embeddings": False, "topk_group": 4, "topk_method": "none",
        "v_head_dim": 6, "vocab_size": 256, "initializer_range": 0.5,
        "feed_forward_chunk": 32}
TRAFFIC = {"why": "rehearsal", "unit": "tokens", "rate": 6.0,
           "lead_in_s": 0.5, "drain_s": 20, "num_slots": 3,
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
NEW = {"mla_decode_ms.srv", "mla_prefill_ms_per_ktoken.srv",
       "mla_prefill_attn_roofline.srv", "mla_decode_attn_roofline.srv",
       "mla_moe_held_pair_share.srv"}


def manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def catalog_entry():
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        return None
    with open(path) as f:
        rows = [json.loads(line) for line in f if line.strip()]
    return next(r for r in rows if r["name"] == "A.X-K1")


def test_the_manifest_holds_the_cell_and_its_metrics():
    m = manifest()
    # the ninth cell and the seventh configuration (later PRs append theirs)
    cell = m["workloads"][8]
    assert (cell["name"], cell["config"], cell["traffic"], cell["chips"]) \
        == (CELL, "A.X-K1", "longdoc16k-open", 1)
    assert len(m["workloads"]) >= 9 and len(m["configs"]) >= 7
    assert sum(c["chips"] == 4 for c in m["workloads"]) == 1
    entry = m["configs"][6]
    assert entry["name"] == "A.X-K1"
    assert entry["reduced"] == ["num_hidden_layers", "n_routed_experts",
                                "vocab_size"]
    assert entry["source"] == \
        "https://huggingface.co/skt/A.X-K1/blob/main/config.json"
    for e in m["configs"] + m["workloads"]:
        assert len(e["why"]) <= 200, e["name"]
    with open(os.path.join(ROOT, entry["file"])) as f:
        cfg = json.load(f)
    published = {
        "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 1,
        "hidden_act": "silu", "hidden_size": 7168,
        "intermediate_size": 18432, "kv_lora_rank": 512,
        "max_position_embeddings": 131072, "model_type": "axk1",
        "moe_intermediate_size": 2048, "moe_layer_freq": 1, "n_group": 8,
        "n_shared_experts": 1, "norm_topk_prob": True,
        "num_attention_heads": 64, "num_experts_per_tok": 8,
        "num_key_value_heads": 64, "q_lora_rank": 1536,
        "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
        "rms_norm_eps": 1e-06, "rope_theta": 10000,
        "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 32,
                         "mscale": 1, "mscale_all_dim": 1,
                         "original_max_position_embeddings": 4096,
                         "type": "yarn"},
        "routed_scaling_factor": 2.5, "scoring_func": "sigmoid",
        "seq_aux": True, "tie_word_embeddings": False, "topk_group": 4,
        "topk_method": "none", "v_head_dim": 128}
    assert {k: cfg[k] for k in published} == published
    catalog = catalog_entry()
    if catalog is not None:     # the guide's row, where it can be read
        assert entry["source"] == catalog["source_url"]
        assert {k: v for k, v in catalog["config"].items()
                if k not in entry["reduced"]} == published
        assert (catalog["config"]["num_hidden_layers"],
                catalog["config"]["n_routed_experts"],
                catalog["config"]["vocab_size"]) == (
            cfg["num_hidden_layers_published"],
            cfg["n_routed_experts_published"], cfg["vocab_size_published"])
    assert cfg["num_hidden_layers"] == 6 and cfg["vocab_size"] == 163840 // 8
    # the experts HELD; the router's width and the published count beside it
    assert cfg["n_routed_experts"] == 12 and cfg["experts_held"] == [0, 12]
    assert cfg["n_routed_experts_published"] == cfg["router_width"] == 192
    assert list(cfg["reduced"]) == entry["reduced"]
    for said in ("192 chips", "16 chips share each layer",
                 "12 pipeline stages", "12 a chip", "4166.3 M parameters",
                 "8.33 GB", "6912 a token", "A SIXTEENTH OF THE PAIRS"):
        assert said in cfg["deployment"], said
    assert {"topk_method", "rotary_pairing", "initializer_range",
            "serving_dtypes", "lengths_sigma"} <= set(cfg["assumed"])
    assert {"head", "context", "exchange"} <= set(cfg["departures"])
    # the count the build: line will read, from the keys alone
    h, e = cfg["num_attention_heads"], cfg["hidden_size"]
    attn = (e * cfg["q_lora_rank"] + cfg["q_lora_rank"]
            + cfg["q_lora_rank"] * h * 192 + e * 576 + 512
            + 512 * h * 256 + h * 128 * e)
    expert = 3 * e * cfg["moe_intermediate_size"]
    sparse = attn + expert + e * 192 + 2 * e + 12 * expert
    dense = attn + 3 * e * cfg["intermediate_size"] + 2 * e
    total = dense + 5 * sparse + 2 * cfg["vocab_size"] * e + e
    assert round(total / 1e6, 1) == 4166.3

    *_, traffic = load_cell(os.path.join(ROOT, "BENCHMARK.json"), CELL)
    arrivals = traffic["arrivals"]
    assert arrivals["kind"] == "poisson_lognormal"
    assert arrivals["prompt_tokens"] == {
        "median": 5560, "sigma": 0.789, "min": 64, "max": 16384}
    assert arrivals["output_tokens"] == {
        "median": 84, "sigma": 1.239, "min": 1, "max": 512}
    assert "Mooncake" in arrivals["source"] and "recalled" in \
        arrivals["source"]
    assert traffic["prefill_buckets"] == [1024, 2048, 4096, 8192, 16384]
    assert (traffic["max_seq_len"], traffic["num_slots"],
            traffic["lead_in_s"], traffic["compare_requests"]) == (
        16896, 16, 5, 10)
    assert traffic["drain_s"] > 0 and traffic["stream"]["zipf_a"] == 0
    knee = traffic["knee"]["rate_per_s"]
    share = traffic["knee"]["share_of_capacity"]
    assert share in (0.7, 0.6)          # the issue's rate, or its fallback
    assert (share - 0.01) * knee <= traffic["rate"] <= (share + 0.01) * knee
    assert len(traffic["knee"]["below_capacity"]) >= 4
    unloaded = traffic["knee"]["unloaded"]
    assert traffic["ttft_limit_ms"] == pytest.approx(
        5 * unloaded["ttft_ms_16384_token_prompt"], rel=0.02)
    assert traffic["tpot_limit_ms"] == pytest.approx(
        3 * unloaded["decode_step_ms_every_slot_full"], rel=0.02)
    # the latent pool: 6 layers x 16 slots x 16896 positions x 1152 bytes
    assert 6 * 16 * 16896 * (512 + 64) * 2 == 1868562432

    reported = {e["name"] for g in ("end_to_end", "per_layer")
                for e in m[g]
                if "workloads" not in e or CELL in e["workloads"]}
    assert NEW | {"ttft_ms_mean", "peak_hbm", "setup_s", "hbm_in_use",
                  "hbm_reserved", "device_idle.srv", "prefill_share.srv",
                  "decode_step_ms.srv", "kv_live_share.srv",
                  "moe_decode_ms.srv", "moe_prefill_ms_per_ktoken.srv",
                  "idle_named_share.srv"} <= reported
    # readers that count from another family's keys are not this cell's
    assert not {"moe_decode_roofline.srv", "moe_held_pair_share.srv",
                "prefill_attn_roofline.srv", "decode_attn_roofline.srv",
                "decode_attn_window_roofline.srv", "tokens_per_s", "moe_ms",
                "flash_ms"} & reported
    names = [e["name"] for e in m["per_layer"]]
    at = names.index("mla_decode_ms.srv")
    assert set(names[at:at + 5]) == NEW     # appended, together
    layers = {e["layer"] for e in m["per_layer"][:at]}
    for e in m["per_layer"][at:at + 5]:
        # (a later latent-attention cell's name may follow: PR 59)
        assert e["workloads"][0] == CELL and e["moves"] == "ttft_ms_mean"
        assert e["layer"] in layers     # a layer the benchmark names already
        assert os.path.exists(os.path.join(
            ROOT, "benchmarks", "metrics", e["name"].split(".")[0] + ".py"))
        if "roofline" in e["name"]:
            assert e["unit"] == "%" and e["better"] == "higher"
    # every list the cell was appended to held it last (a later cell's name
    # may follow it)
    later = {c["name"] for c in m["workloads"][9:]}
    for g in ("end_to_end", "per_layer"):
        for e in m[g]:
            if CELL in e.get("workloads", ()):
                assert [w for w in e["workloads"] if w not in later][-1] \
                    == CELL, e["name"]


def test_the_schedule_is_typical_of_its_long_run():
    """As tests/test_bench_cohere2.py holds code8k-open: the first 35 s
    (lead-in and window) within 5% of the long run, at the file's rate and
    over the band the seed was chosen for before the capacity was read."""
    from benchmarks import arrivals

    *_, traffic = load_cell(os.path.join(ROOT, "BENCHMARK.json"), CELL)
    for name, ratio in arrivals.typical(traffic, 35.0).items():
        assert abs(ratio - 1.0) <= 0.05, (name, ratio)
    for rate in (0.98, 1.0, 1.03, 1.06, 1.09, 1.12):
        for name, ratio in arrivals.typical(
                dict(traffic, rate=rate), 35.0).items():
            assert abs(ratio - 1.0) <= 0.05, (rate, name, ratio)
    sched = arrivals.schedule(traffic, 35.0)
    assert (sched.prompt_len > 8192).sum() >= 5     # the longest bucket works
    assert sched.prompt_len.max() <= 16384
    mean = arrivals.long_run(traffic)
    assert 6700 < mean["mean_prompt_tokens"] < 7100
    assert 135 < mean["mean_output_tokens"] < 155


CFG = {"num_attention_heads": 2, "qk_nope_head_dim": 4,
       "qk_rope_head_dim": 2, "v_head_dim": 3, "kv_lora_rank": 8,
       "num_hidden_layers": 3, "first_k_dense_replace": 1,
       "hidden_size": 8, "moe_intermediate_size": 16,
       "intermediate_size": 64, "n_shared_experts": 1,
       "n_routed_experts_published": 32, "num_experts_per_tok": 4}


def test_counts_of_the_two_forms_and_of_the_share():
    # a triangle of 6 positions: 21 pairs, 2 H (6 + 3) a pair, 3 layers
    assert flops_mla.prefill_attention_flops(CFG, [6]) == \
        2.0 * 2 * (4 + 2 + 3) * 21 * 3
    # A.X-K1's own: 40960 operations a pair a layer
    assert 2 * 64 * (128 + 64 + 128) == 40960
    # two live slots of 3 and 9 cached tokens, one step, 3 layers
    assert flops_mla.decode_attention_bytes(CFG, [[3, 9]]) == \
        (8 + 2) * 2 * 3 * 12
    assert flops_mla.decode_attention_flops(CFG, [[3, 9]]) == \
        2.0 * 2 * (8 + 2 + 8) * 3 * 12
    # A.X-K1's: 1152 bytes and 64 x 2 x (576 + 512) operations a token a
    # layer, 121 operations a byte
    assert (512 + 64) * 2 == 1152
    assert 64 * 2 * (576 + 512) / 1152 == pytest.approx(120.9, abs=0.1)
    assert flops_mla.sparse_layers(CFG) == 2


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
        module_s={"decode": {f"{lay}/attn/{profiling.MLA_ATTN}": 4e-3,
                             f"{lay}/attn/{profiling.MLA_DOWN}/q_down": 1e-3,
                             f"{lay}/attn/o": 1e-3,
                             f"{lay}/moe_mlp/hvd_moe_shared": 2e-3},
                  "prefill": {f"{lay}/attn/{profiling.MLA_UP}": 4e-3,
                              f"{lay}/mlp/up": 9e-3}},
        kernel_s={"decode": {"hvd_moe_experts": 3e-3},
                  "prefill": {"hvd_flash_fwd": 2e-3}},
        pathless_s={"decode": {"hvd_moe_experts": 3e-3},
                    "prefill": {"hvd_flash_fwd": 2e-3}},
        joined_share=1.0)
    run = types.SimpleNamespace(
        records=[], config=CFG, peaks={"hbm_bytes_per_s": 1e6,
                                       "bf16_flops_per_s": 1e9},
        traced_steps_log=[decode, prefill], steps=[decode, prefill],
        inside=lambda t: True, built=types.SimpleNamespace(num_slots=2),
        trace=types.SimpleNamespace(program_calls={"decode": 1}),
        _serve_scopes=joined)
    # everything under attn: 4 + 1 + 1 ms a step
    assert reader("mla_decode_ms").read(run) == pytest.approx(6.0)
    # (4 ms under attn + 2 ms of the pathless kernel) a 6-token prompt
    assert reader("mla_prefill_ms_per_ktoken").read(run) == pytest.approx(
        6.0 / 0.006)
    assert reader("mla_prefill_attn_roofline").read(run) == pytest.approx(
        100 * flops_mla.prefill_attention_flops(CFG, [6]) / 1e9 / 2e-3)
    # bytes bind here: 720 bytes / 1e6 against 2592 operations / 1e9
    assert reader("mla_decode_attn_roofline").read(run) == pytest.approx(
        100 * flops_mla.decode_attention_bytes(CFG, [[3, 9]]) / 1e6 / 4e-3)
    # 4 pairs held a call of (6 prompt positions + 2 live slots) x 2 x 4
    assert reader("mla_moe_held_pair_share").read(run) == pytest.approx(
        100 * 8 / (8 * 8))
    # a family that counts nothing (decoder_serve's five-field log)
    run.traced_steps_log = run.steps = [decode[:5], prefill[:5]]
    for stem in ("mla_decode_attn_roofline", "mla_moe_held_pair_share"):
        assert reader(stem).read(run) is None, stem


@pytest.fixture(scope="module")
def walked(tmp_path_factory):
    """The file's one walk of the tiny cell (``tests/_rehearse.py``)."""
    return walk(tmp_path_factory.mktemp("walk"), "tiny-axk1", TINY, TRAFFIC,
                CELL)


def test_a_tiny_cell_walks_serving_py_on_the_cpu(walked):
    result, _, stdout = walked
    assert result["correct"], stdout[-3000:]
    assert result["failed"] == 0 and result["attempted"] >= 5
    names = set(result["metrics"])
    assert "mla_moe_held_pair_share.srv" in names   # the program's counter
    assert 5.0 < result["metrics"]["mla_moe_held_pair_share.srv"]["value"] \
        < 60.0
    # device metrics are never made up from a CPU trace
    assert not (NEW - {"mla_moe_held_pair_share.srv"}) & names
    assert "device_idle.srv" not in names
    assert "family=mla_moe_serve" in stdout
    moe = json.loads(stdout.split("moe: ")[1].splitlines()[0])
    assert (moe["experts"], moe["experts_held"], moe["held_from"]) == (
        16, 4, 4)
    assert moe["layers"] == {"dense": 1, "sparse": 2}
    assert moe["routed_scale"] == 2.5 and moe["slots"] == 3
    assert 0 < moe["held_pairs"] < moe["pairs"]
    mla = json.loads(stdout.split("mla: ")[1].splitlines()[0])
    # 3 layers x (8 latent + 4 rotary-key values) x 2 bytes
    assert mla["cache"] == {"latent": 8, "rotary_key": 4,
                            "bytes_per_token": 72,
                            "pool_bytes": 72 * 3 * 128}
    assert mla["form"] == {"prefill": "expanded", "decode": "absorbed"}
    assert mla["feed_forward_chunk"] == 32
    assert mla["prefill_chunks"] == {"16": 1, "32": 1, "64": 2}
    assert "kv: bytes_per_token=72 " in stdout
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
    return load_module("families", "mla_moe_serve")


@pytest.mark.parametrize("seed", [1, 2, 2**31 + 6])
def test_the_float8_control_fails_the_comparison(seed, control_family):
    """The reference with float8 operands put in the program's place and
    judged by the run's own comparison and limit is not correct; the
    reference's own first choices, judged the same way, are (gap 0).  The
    toy is given 12 layers: float8's error compounds with depth."""
    import jax.numpy as jnp
    import numpy as np

    family = control_family
    cfg = dict(TINY, num_hidden_layers=12)
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
