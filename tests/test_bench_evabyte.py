"""The benchmark's side of the EvaByte configuration (PR 44): the manifest's
entries for ``EvaByte`` and ``evabyte-code32k-open`` (every published key,
``reduced``, the deployment, the traffic's parameters), the counts of
``benchmarks/flops_eva.py`` against a brute-force count of E_t and R_t, the
new readers on a hand-made run, and a ``--rehearse-on-cpu`` walk of a tiny
cell of the family through ``benchmarks/serving.py``, its files found by
name: ``correct`` true as served, false with a served byte altered, and the
float8 control, through the run's own comparison, past the limit.  Here, and
not under ``benchmarks/tests``, so that the tier-1 run holds them."""

import json
import os
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import flops_eva  # noqa: E402
from benchmarks.run import load_cell, load_module  # noqa: E402

from _rehearse import (assert_the_altered_record_is_not_correct,  # noqa: E402
                       walk)

CELL = "evabyte-code32k-open"
TINY = {"family": "eva_serve", "model_type": "evabyte",
        "attention_class": "eva", "attention_bias": False,
        "hidden_act": "silu", "hidden_size": 32, "intermediate_size": 48,
        "num_attention_heads": 2, "num_key_value_heads": 2,
        "num_hidden_layers": 3, "num_pred_heads": 3, "vocab_size": 40,
        "window_size": 32, "chunk_size": 4, "rms_norm_eps": 1e-5,
        "rope_theta": 100000, "rope_scaling": None,
        "norm_add_unit_offset": True, "fp32_skip_add": True,
        "fp32_logits": True, "tie_word_embeddings": False, "init_std": 0.4,
        "feed_forward_chunk": 32}
TRAFFIC = {"why": "rehearsal", "unit": "tokens", "rate": 6.0,
           "lead_in_s": 0.5, "drain_s": 20, "num_slots": 3,
           "max_seq_len": 128, "prefill_buckets": [32, 64],
           "arrivals": {"kind": "poisson_lognormal", "schedule_seed": 7,
                        "prompt_tokens": {"median": 30, "sigma": 0.6,
                                          "min": 8, "max": 64},
                        "output_tokens": {"median": 12, "sigma": 0.8,
                                          "min": 3, "max": 48}},
           "stream": {"kind": "markov_zipf_tokens", "zipf_a": 0.0,
                      "follow_prob": 0.5, "max_run": 8},
           "ttft_limit_ms": 1000.0, "tpot_limit_ms": 500.0,
           "compare_requests": 4}
NEW = {"eva_decode_ms.srv", "eva_prefill_ms_per_ktoken.srv",
       "eva_prefill_attn_roofline.srv", "eva_decode_attn_roofline.srv",
       "eva_cache_live_share.srv"}


def manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def catalog_entry():
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        return None
    with open(path) as f:
        rows = [json.loads(line) for line in f if line.strip()]
    return next(r for r in rows if r["name"] == "EvaByte")


PUBLISHED = {
    "attention_bias": False, "attention_class": "eva", "chunk_size": 16,
    "fp32_ln": False, "fp32_logits": True, "fp32_skip_add": True,
    "hidden_act": "silu", "hidden_size": 4096, "init_cutoff_factor": None,
    "init_fn": "v2", "init_std": 0.01275, "intermediate_size": 11008,
    "lazy_init": True, "max_position_embeddings": 32768,
    "max_seq_length": 32768, "mixedp_attn": True, "model_type": "evabyte",
    "norm_add_unit_offset": True, "num_attention_heads": 32,
    "num_chunks": None, "num_key_value_heads": 32, "num_pred_heads": 8,
    "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 100000,
    "tie_word_embeddings": False, "vocab_size": 320, "window_size": 2048}


def test_the_manifest_holds_the_configuration():
    m = manifest()
    entry = m["configs"][7]      # the eighth (later PRs append theirs)
    assert len(m["configs"]) >= 8 and entry["name"] == "EvaByte"
    assert entry["reduced"] == ["num_hidden_layers"]
    assert entry["source"] == \
        "https://huggingface.co/EvaByte/EvaByte/blob/main/config.json"
    for e in m["configs"] + m["workloads"]:
        assert len(e["why"]) <= 200, e["name"]
    with open(os.path.join(ROOT, entry["file"])) as f:
        cfg = json.load(f)
    assert {k: cfg[k] for k in PUBLISHED} == PUBLISHED
    catalog = catalog_entry()
    if catalog is not None:     # the guide's row, where it can be read
        assert entry["source"] == catalog["source_url"]
        assert {k: v for k, v in catalog["config"].items()
                if k not in entry["reduced"]} == PUBLISHED
        assert catalog["config"]["num_hidden_layers"] == \
            cfg["num_hidden_layers_published"] == 32
    # no width, head count, vocabulary row or prediction head is cut
    assert cfg["num_hidden_layers"] == 16 and cfg["family"] == "eva_serve"
    assert list(cfg["reduced"]) == entry["reduced"]
    for said in ("two pipeline stages of 16 layers", "first stage",
                 "202 391 552", "3250.1 M parameters", "6.50 GB",
                 "2048 + 32768 / 16 = 4096 rows", "1.07 GB a slot",
                 "about twice a deployment's"):
        assert said in cfg["deployment"], said
    assumed = list(cfg["assumed"])
    assert assumed[:2] == ["attention_summary", "attention_visibility"]
    assert cfg["assumed"]["attention_summary"].startswith(
        "the catalog's config gives `attention_class`, `window_size`, "
        "`chunk_size` and no more; recalled from the family's published "
        "modeling code and EVA's equations, no network")
    assert {"initializer", "serving_dtypes", "rotary"} <= set(assumed)
    assert {"head", "sampling", "stage_exchange"} <= set(cfg["departures"])
    # the count the build: line will read, from the keys alone
    e, f, h = (cfg["hidden_size"], cfg["intermediate_size"],
               cfg["num_attention_heads"])
    layer = 4 * e * e + 3 * e * f + 2 * h * (e // h) + 2 * e
    assert layer == 202391552
    total = (cfg["num_hidden_layers"] * layer + cfg["vocab_size"] * e
             + e * cfg["num_pred_heads"] * cfg["vocab_size"] + e)
    assert round(total / 1e6, 1) == 3250.1


def test_the_manifest_holds_the_cell_and_its_metrics():
    m = manifest()
    cell = m["workloads"][9]     # the tenth (later PRs append theirs)
    assert (cell["name"], cell["config"], cell["traffic"], cell["chips"]) \
        == (CELL, "EvaByte", "codebytes32k-open", 1)
    assert len(m["workloads"]) >= 10
    assert sum(c["chips"] == 4 for c in m["workloads"]) == 1
    assert "16 of 32 layers" in cell["why"]

    *_, traffic = load_cell(os.path.join(ROOT, "BENCHMARK.json"), CELL)
    arrivals = traffic["arrivals"]
    assert arrivals["kind"] == "poisson_lognormal"
    assert arrivals["prompt_tokens"] == {
        "median": 6000, "sigma": 0.789, "min": 256, "max": 31744}
    assert arrivals["output_tokens"] == {
        "median": 52, "sigma": 1.239, "min": 1, "max": 1024}
    assert "4 bytes a token" in arrivals["source"]
    assert traffic["prefill_buckets"] == [2048, 4096, 8192, 16384, 32768]
    assert (traffic["max_seq_len"], traffic["lead_in_s"]) == (32768, 5)
    assert traffic["num_slots"] in (6, 5)       # the issue's, or its fallback
    assert traffic["drain_s"] > 0 and traffic["stream"]["zipf_a"] == 0
    knee = traffic["knee"]["rate_per_s"]
    share = traffic["knee"]["share_of_capacity"]
    assert share in (0.7, 0.8)          # the issue's rate, or its fallback
    assert (share - 0.01) * knee <= traffic["rate"] <= (share + 0.01) * knee
    unloaded = traffic["knee"]["unloaded"]
    assert traffic["ttft_limit_ms"] == pytest.approx(
        5 * unloaded["ttft_ms_32768_byte_prompt"], rel=0.02)
    assert traffic["tpot_limit_ms"] == pytest.approx(
        3 * unloaded["decode_step_ms_every_slot_full"], rel=0.02)
    # the pool: 16 layers x slots x (2048 + 2048) rows x [32, 128] x K, V
    assert 16 * 6 * 4096 * 32 * 128 * 2 * 2 == 6442450944

    reported = {e["name"] for g in ("end_to_end", "per_layer")
                for e in m[g]
                if "workloads" not in e or CELL in e["workloads"]}
    assert NEW | {"ttft_ms_mean", "peak_hbm", "setup_s", "hbm_in_use",
                  "hbm_reserved", "device_idle.srv", "prefill_share.srv",
                  "decode_step_ms.srv", "prefill_ms_per_ktoken.srv",
                  "idle_named_share.srv"} <= reported
    # readers that divide by num_slots x max_seq_len, or count from another
    # family's keys, are not this cell's
    assert not {"kv_live_share.srv", "kv_live_peak_share.srv",
                "moe_decode_ms.srv", "mla_decode_ms.srv",
                "prefill_attn_roofline.srv", "decode_attn_roofline.srv",
                "tokens_per_s", "flash_ms"} & reported
    names = [e["name"] for e in m["per_layer"]]
    at = names.index("eva_decode_ms.srv")
    assert set(names[at:at + 5]) == NEW     # appended, together
    assert at > names.index("mla_moe_held_pair_share.srv")
    layers = {e["layer"] for e in m["per_layer"][:at]}
    for e in m["per_layer"][at:at + 5]:
        assert e["workloads"] == [CELL] and e["moves"] == "ttft_ms_mean"
        assert e["layer"] in layers     # a layer the benchmark names already
        assert os.path.exists(os.path.join(
            ROOT, "benchmarks", "metrics", e["name"].split(".")[0] + ".py"))
        if "roofline" in e["name"]:
            assert e["unit"] == "%" and e["better"] == "higher"
    # every list the cell was appended to held it last (a later cell's name
    # may follow it)
    later = {c["name"] for c in m["workloads"][10:]}
    for g in ("end_to_end", "per_layer"):
        for e in m[g]:
            if CELL in e.get("workloads", ()):
                assert [w for w in e["workloads"] if w not in later][-1] \
                    == CELL, e["name"]


def test_the_schedule_holds_its_rate():
    """As tests/test_bench_axk1.py holds longdoc16k-open: the first 35 s
    (lead-in and window) within 5% of the long run at the file's rate; over
    the band the seed was chosen for, before the capacity was read, within
    the 8.1% the file states."""
    from benchmarks import arrivals

    *_, traffic = load_cell(os.path.join(ROOT, "BENCHMARK.json"), CELL)
    for name, ratio in arrivals.typical(traffic, 35.0).items():
        assert abs(ratio - 1.0) <= 0.05, (name, ratio)
    for rate in (0.63, 0.66, 0.70, 0.74, 0.77):
        for name, ratio in arrivals.typical(
                dict(traffic, rate=rate), 35.0).items():
            assert abs(ratio - 1.0) <= 0.082, (rate, name, ratio)
    sched = arrivals.schedule(traffic, 35.0)
    counted = sched.due_s >= 5.0
    assert counted.sum() >= 20                      # the issue's floor
    assert (sched.prompt_len[counted] > 16384).sum() >= 1
    assert (sched.prompt_len > 2048).mean() > 0.8   # most cross a window
    assert 256 <= sched.prompt_len.min() and sched.prompt_len.max() <= 31744
    mean = arrivals.long_run(traffic)
    assert 7700 < mean["mean_prompt_tokens"] < 8100
    assert 100 < mean["mean_output_tokens"] < 120


CFG = {"window_size": 8, "chunk_size": 2, "hidden_size": 6,
       "num_attention_heads": 2, "num_hidden_layers": 3}


def brute(n):
    """|E_t| and |R_t| summed over a prompt, from the definitions."""
    w, c = CFG["window_size"], CFG["chunk_size"]
    exact = sum(1 for t in range(n) for m in range(n)
                if w * (t // w) <= m <= t)
    summarised = sum(1 for t in range(n) for j in range(n // c + 1)
                     if j < (w // c) * (t // w))
    return exact, summarised


@pytest.mark.parametrize("n", [1, 5, 8, 9, 16, 23, 24, 41])
def test_the_pair_counts_are_the_brute_force_counts(n):
    assert flops_eva.prefill_pairs(CFG, n) == brute(n)
    assert flops_eva.prefill_attention_flops(CFG, [n, 3]) == \
        4.0 * 6 * (sum(brute(n)) + sum(brute(3))) * 3
    # the last query's own sets
    w, c = CFG["window_size"], CFG["chunk_size"]
    t = n - 1
    assert flops_eva.seen_rows(CFG, t) == (
        sum(1 for m in range(n) if w * (t // w) <= m <= t),
        sum(1 for j in range(n) if j < (w // c) * (t // w)))


def test_counts_of_a_decode_step_and_of_a_slot():
    # two live slots whose engine lengths are 4 and 21 (queries at 3 and
    # 20): 4 ring rows; 5 ring rows + 8 summaries; K and V, 6 wide, bf16
    assert flops_eva.decode_attention_bytes(CFG, [[4, 21]]) == \
        2 * 6 * 2 * (4 + 5 + 8) * 3
    assert flops_eva.held_rows(CFG, 21) == 5 + 10
    assert flops_eva.reserved_rows(CFG, 64) == 8 + 32
    # EvaByte's own: 16384 operations a pair a layer; a slot's rows
    real = {"window_size": 2048, "chunk_size": 16, "hidden_size": 4096,
            "num_hidden_layers": 16}
    assert flops_eva._per_pair(real) == 4 * 32 * 128
    assert flops_eva.reserved_rows(real, 32768) == 4096
    exact, summarised = flops_eva.prefill_pairs(real, 32768)
    assert exact == 16 * 2048 * 2049 // 2
    assert summarised == 2048 * 128 * 120
    # ISSUE 44's 1.07 TF of attention a layer, half of it over summaries
    assert 16384 * (exact + summarised) == pytest.approx(1.07e12, rel=0.01)


def test_the_new_readers_read_a_hand_made_run_and_nothing_without_it():
    from benchmarks import serve_scopes
    from horovod_tpu.utils import profiling

    reader = lambda stem: load_module("metrics", stem)  # noqa: E731
    stems = sorted(n.split(".")[0] for n in NEW)
    training = types.SimpleNamespace(trace=None, peaks=None)
    for stem in stems:
        assert reader(stem).read(training) is None, stem
    decode = ("decode", 1.0, 1.1, 2, 25, {"lengths": [4, 21]})
    prefill = ("prefill", 1.2, 1.3, 16, 9)
    lay = "Transformer/layer_N"
    joined = serve_scopes.Joined(
        calls={"decode": 1, "prefill": 1},
        module_s={"decode": {f"{lay}/attn/{profiling.EVA_ATTN}": 4e-3,
                             f"{lay}/attn/{profiling.EVA_SUMMARY}": 1e-3,
                             f"{lay}/attn/o": 1e-3, f"{lay}/mlp/up": 7e-3},
                  "prefill": {f"{lay}/attn/{profiling.EVA_ATTN}": 3e-3,
                              f"{lay}/attn/q": 1e-3,
                              f"{lay}/mlp/up": 9e-3}},
        kernel_s={"decode": {}, "prefill": {"hvd_flash_fwd": 2e-3}},
        pathless_s={"decode": {}, "prefill": {"hvd_flash_fwd": 2e-3}},
        joined_share=1.0)
    run = types.SimpleNamespace(
        records=[], config=CFG, traffic={"max_seq_len": 64},
        peaks={"hbm_bytes_per_s": 1e6, "bf16_flops_per_s": 1e9},
        traced_steps_log=[decode, prefill], steps=[decode, prefill],
        steps_in_window=lambda kind: [decode] if kind == "decode" else [],
        built=types.SimpleNamespace(num_slots=2), _serve_scopes=joined)
    # everything under attn: 4 + 1 + 1 ms a step
    assert reader("eva_decode_ms").read(run) == pytest.approx(6.0)
    # (3 + 1 ms under attn + 2 ms of the pathless kernel) a 9-byte prompt
    assert reader("eva_prefill_ms_per_ktoken").read(run) == pytest.approx(
        6.0 / 0.009)
    # under hvd_eva_attn, with the kernel: 5 ms
    assert reader("eva_prefill_attn_roofline").read(run) == pytest.approx(
        100 * flops_eva.prefill_attention_flops(CFG, [9]) / 1e9 / 5e-3)
    assert reader("eva_decode_attn_roofline").read(run) == pytest.approx(
        100 * flops_eva.decode_attention_bytes(CFG, [[4, 21]]) / 1e6 / 4e-3)
    # 3 and 20 positions cached: 3 + 1 and 4 + 10 rows of 2 x (8 + 32)
    assert reader("eva_cache_live_share").read(run) == pytest.approx(
        100 * (4 + 14) / 80)
    # a family that counts nothing (decoder_serve's five-field log)
    run.traced_steps_log = run.steps = [decode[:5], prefill]
    run.steps_in_window = lambda kind: [decode[:5]]
    for stem in ("eva_decode_attn_roofline", "eva_cache_live_share"):
        assert reader(stem).read(run) is None, stem


@pytest.fixture(scope="module")
def walked(tmp_path_factory):
    """The file's one walk of the tiny cell (``tests/_rehearse.py``)."""
    return walk(tmp_path_factory.mktemp("walk"), "tiny-eva", TINY, TRAFFIC,
                CELL, alter=(17, 40))


def test_a_tiny_cell_walks_serving_py_on_the_cpu(walked):
    result, _, stdout = walked
    assert result["correct"], stdout[-3000:]
    assert result["failed"] == 0 and result["attempted"] >= 5
    names = set(result["metrics"])
    assert "eva_cache_live_share.srv" in names      # the program's counter
    assert 1.0 < result["metrics"]["eva_cache_live_share.srv"]["value"] < 90.0
    # device metrics are never made up from a CPU trace
    assert not (NEW - {"eva_cache_live_share.srv"}) & names
    assert "device_idle.srv" not in names
    assert "family=eva_serve" in stdout
    eva = json.loads(stdout.split("eva: ")[1].splitlines()[0])
    # a ring of 32 rows and 128 / 4 summaries, not 128 rows
    assert (eva["window"], eva["chunk"], eva["rows_per_slot"],
            eva["rows_per_slot_dense"]) == (32, 4, 64, 128)
    # 3 layers x 64 rows x [2, 16] x K and V x 2 bytes
    assert eva["bytes_per_slot"] == 3 * 64 * 32 * 2 * 2
    assert eva["pool_bytes"] == 3 * eva["bytes_per_slot"]
    assert eva["pred_heads"] == 3
    assert eva["prefill_attention"] == {"32": "dense", "64": "dense"}
    assert eva["prefill_chunks"] == {"32": 1, "64": 2}
    assert eva["windows"] > 0 and eva["summaries"] > 0
    assert eva["chunks_closed"] > 0 and eva["rollovers"] > 0
    gap, limit = result["compared"]["served_token_gap_below_reference_best"]
    assert gap < limit
    checks = json.loads(stdout.split("checks=")[1].splitlines()[0])
    assert checks[0]["requests"] == 4 and checks[0]["longest"] > 32


def test_an_altered_served_byte_is_not_correct(walked):
    assert_the_altered_record_is_not_correct(walked)


@pytest.fixture(scope="module")
def control_family():
    """The family loaded once for the control's three seeds: its reference's
    programs (``_PROGRAMS``: a layer, the head, a dtype each) are keyed on
    shapes and numbers and not on the seed, whose weights are arguments, so
    the second and third seeds compile nothing."""
    return load_module("families", "eva_serve")


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
    finished = [(rng.integers(0, 40, n), rng.integers(0, 40, 16))
                for n in (20, 31, 40, 47, 56, 64, 80, 96)]
    control, = family.compare_served(cfg, traffic, finished, seed,
                                     control=jnp.float8_e4m3fn)
    assert not control["ok"] and control["error"] > family.GAP_LIMIT
    assert control["tokens"] == 8 * 16 and control["longest"] == 112
    exact, = family.compare_served(cfg, traffic, finished, seed,
                                   control=jnp.float32)
    assert exact["ok"] and exact["error"] < 1e-3
