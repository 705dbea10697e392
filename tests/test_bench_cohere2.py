"""The benchmark's side of the cohere2_moe configuration (PR 37): the
manifest's entries for ``command-a-plus-05-2026`` and
``cmdaplus-code8k-open`` (the published keys, ``reduced``, the traffic's
parameters), the counts of ``benchmarks/flops_cohere2.py``, the new readers
on a hand-made run, and a ``--rehearse-on-cpu`` walk of a tiny cell of the
family through ``benchmarks/serving.py``: ``correct`` true as served, false
with a served token altered.  Here, and not under ``benchmarks/tests``, so
that the tier-1 run holds them."""

import json
import os
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import flops_cohere2  # noqa: E402
from benchmarks.run import load_module  # noqa: E402

from _rehearse import (assert_the_altered_record_is_not_correct,  # noqa: E402
                       walk)

CELL = "cmdaplus-code8k-open"
TINY = {"family": "cohere2_moe_serve", "model_type": "cohere2_moe",
        "attention_bias": False, "expert_selection_fn": "sigmoid",
        "first_k_dense_replace": 0, "head_dim": 8, "hidden_act": "silu",
        "hidden_size": 32, "intermediate_size": 16, "layer_norm_eps": 1e-05,
        "layer_types": ["sliding_attention", "sliding_attention",
                        "sliding_attention", "full_attention"],
        "logit_scale": 1, "norm_topk_prob": True, "num_attention_heads": 16,
        "num_experts": 4, "num_experts_published": 16,
        "experts_held": [4, 8], "num_experts_per_tok": 4,
        "num_hidden_layers": 4, "num_key_value_heads": 1,
        "num_shared_experts": 2, "position_embedding_type": "rope_gptj",
        "rms_norm_eps": None, "rope_theta": 50000, "rotary_pct": 1,
        "shared_expert_combination_strategy": "average",
        "sliding_window": 24, "tie_word_embeddings": True,
        "use_gated_activation": True, "use_parallel_block": True,
        "use_qk_norm": False, "vocab_size": 256, "initializer_range": 0.5}
TRAFFIC = {"why": "rehearsal", "unit": "tokens", "rate": 6.0,
           "lead_in_s": 0.5, "drain_s": 20, "num_slots": 3,
           "max_seq_len": 96, "prefill_buckets": [16, 32, 64],
           "arrivals": {"kind": "poisson_lognormal", "schedule_seed": 7,
                        "prompt_tokens": {"median": 24, "sigma": 0.6,
                                          "min": 8, "max": 64},
                        "output_tokens": {"median": 6, "sigma": 0.5,
                                          "min": 3, "max": 16}},
           "stream": {"kind": "markov_zipf_tokens", "zipf_a": 1.1,
                      "follow_prob": 0.5, "max_run": 8},
           "ttft_limit_ms": 1000.0, "tpot_limit_ms": 500.0,
           "compare_requests": 4}


def manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_the_manifest_holds_the_cell_and_its_metrics():
    m = manifest()
    cell = next(c for c in m["workloads"] if c["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "command-a-plus-05-2026", "code8k-open", 1)
    assert len(m["workloads"]) >= 9     # later PRs append theirs
    assert sum(c["chips"] == 4 for c in m["workloads"]) == 1
    entry = next(c for c in m["configs"] if c["name"] == cell["config"])
    assert entry["reduced"] == ["num_hidden_layers", "layer_types",
                                "num_experts", "vocab_size"]
    assert entry["source"] == ("https://huggingface.co/CohereLabs/"
                               "command-a-plus-05-2026/blob/main/config.json")
    with open(os.path.join(ROOT, entry["file"])) as f:
        cfg = json.load(f)
    published = {
        "attention_bias": False, "expert_selection_fn": "sigmoid",
        "first_k_dense_replace": 0, "head_dim": 128, "hidden_act": "silu",
        "hidden_size": 4096, "intermediate_size": 4096,
        "layer_norm_eps": 1e-05, "layer_switch": 4, "logit_scale": 1,
        "max_position_embeddings": 200000, "model_type": "cohere2_moe",
        "norm_topk_prob": True, "num_attention_heads": 128,
        "num_experts_per_tok": 8, "num_key_value_heads": 8,
        "num_shared_experts": 4, "position_embedding_type": "rope_gptj",
        "prefix_dense_intermediate_size": 16384, "rms_norm_eps": None,
        "rope_theta": 50000, "rotary_pct": 1,
        "shared_expert_combination_strategy": "average",
        "sliding_window": 4096, "tie_word_embeddings": True,
        "use_gated_activation": True, "use_parallel_block": True,
        "use_qk_norm": False}
    assert {k: cfg[k] for k in published} == published
    assert cfg["num_hidden_layers"] == 4 and cfg["vocab_size"] == 262144 // 8
    assert cfg["layer_types"] == ["sliding_attention"] * 3 + ["full_attention"]
    # the experts HELD; the router's width and the published count beside it
    assert cfg["num_experts"] == 16 and cfg["experts_held"] == [0, 16]
    assert cfg["num_experts_published"] == cfg["router_width"] == 128
    assert list(cfg["reduced"]) == entry["reduced"]
    for said in ("8 chips share each layer", "data-parallel attention",
                 "16 a chip", "32768 rows a chip", "4 layers a chip",
                 "AN EIGHTH OF THE PAIRS"):
        assert said in cfg["deployment"], said
    assert {"shared_expert_combination_strategy", "norm",
            "full_attention_layers", "intermediate_size",
            "serving_dtypes"} <= set(cfg["assumed"])
    assert {"vision_tower", "context", "cache_pool"} <= set(cfg["departures"])
    from benchmarks.run import load_cell

    *_, traffic = load_cell(os.path.join(ROOT, "BENCHMARK.json"), CELL)
    assert traffic["arrivals"]["kind"] == "poisson_lognormal"
    assert traffic["arrivals"]["prompt_tokens"] == {
        "median": 1500, "sigma": 0.789, "min": 16, "max": 8192}
    assert traffic["arrivals"]["output_tokens"] == {
        "median": 13, "sigma": 1.239, "min": 1, "max": 256}
    assert traffic["prefill_buckets"] == [512, 1024, 2048, 4096, 8192]
    assert (traffic["max_seq_len"], traffic["num_slots"],
            traffic["lead_in_s"], traffic["compare_requests"]) == (
        8448, 8, 5, 10)
    assert traffic["drain_s"] > 0
    knee = traffic["knee"]["rate_per_s"]
    # ISSUE 37's fallback: 0.7 of what the cell's own schedule sustains
    assert 0.69 * knee <= traffic["rate"] <= 0.71 * knee
    # ids drawn uniformly, so that no few ids decide a seed's routing
    assert traffic["stream"]["zipf_a"] == 0
    unloaded = traffic["knee"]["unloaded"]
    assert traffic["ttft_limit_ms"] == pytest.approx(
        5 * unloaded["ttft_ms_8192_token_prompt"], rel=0.02)
    assert traffic["tpot_limit_ms"] == pytest.approx(
        3 * unloaded["decode_step_ms_every_slot_full"], rel=0.02)
    reported = {e["name"] for g in ("end_to_end", "per_layer")
                for e in m[g]
                if "workloads" not in e or CELL in e["workloads"]}
    new = {"moe_decode_ms.srv", "moe_prefill_ms_per_ktoken.srv",
           "moe_decode_roofline.srv", "prefill_attn_roofline.srv",
           "decode_attn_window_roofline.srv", "moe_held_pair_share.srv"}
    assert new | {"ttft_ms_mean", "peak_hbm", "setup_s", "hbm_in_use",
                  "device_idle.srv", "prefill_share.srv",
                  "decode_step_ms.srv", "kv_live_share.srv"} <= reported
    assert "decode_attn_roofline.srv" not in reported
    assert not {"tokens_per_s", "moe_ms", "flash_ms"} & reported
    for e in m["per_layer"]:
        if e["name"] in new:
            # (a later sparse cell reads the readers that ask nothing of a
            # configuration's keys too: appended behind this one)
            assert e["workloads"][0] == CELL and e["moves"] == "ttft_ms_mean"


def test_the_schedule_is_typical_of_its_long_run():
    """As benchmarks/tests/test_arrivals.py holds code-open-0.8knee: the
    first 35 s (lead-in and window) within 5% of the long run."""
    from benchmarks import arrivals
    from benchmarks.run import load_cell

    *_, traffic = load_cell(os.path.join(ROOT, "BENCHMARK.json"), CELL)
    for name, ratio in arrivals.typical(traffic, 35.0).items():
        assert abs(ratio - 1.0) <= 0.05, (name, ratio)
    # the seed was fixed before the capacity was read: typical at any rate
    # that 0.7 of a plausible capacity could have given
    for rate in (3.7, 3.8, 3.9, 4.0, 4.1):
        for name, ratio in arrivals.typical(
                dict(traffic, rate=rate), 35.0).items():
            assert abs(ratio - 1.0) <= 0.05, (rate, name, ratio)
    sched = arrivals.schedule(traffic, 35.0)
    assert (sched.prompt_len > 4096).any()      # the band is worked
    assert sched.prompt_len.max() <= 8192


def test_counts_of_the_band_and_of_the_share():
    cfg = {"num_attention_heads": 2, "head_dim": 4, "sliding_window": 4,
           "layer_types": ["sliding_attention", "full_attention"],
           "num_key_value_heads": 1, "hidden_size": 8,
           "intermediate_size": 16, "num_shared_experts": 2,
           "num_experts_published": 32}
    assert flops_cohere2.seen_positions(3, 4) == 6       # still a triangle
    assert flops_cohere2.seen_positions(6, 4) == 10 + 2 * 4
    assert flops_cohere2.seen_positions(6, None) == 21
    brute = sum(1 for i in range(6) for j in range(6) if i - 4 < j <= i)
    assert flops_cohere2.seen_positions(6, 4) == brute
    assert flops_cohere2.prefill_attention_flops(cfg, [6]) == \
        4.0 * 2 * 4 * (18 + 21)
    # two live slots of 3 and 9 cached positions, one step
    assert flops_cohere2.decode_attention_window_bytes(cfg, [[3, 9]]) == \
        2 * 1 * 4 * 2 * ((3 + 4) + (3 + 9))
    expert = 3 * 8 * 16 * 2
    step = [[0, 2, 0, 1], [0, 0, 0, 0]]      # [L][held]: 2 experts touched
    assert flops_cohere2.moe_decode_bytes(cfg, [step]) == \
        2 * (2 * expert + 8 * 32 * 2) + 2 * expert


def test_the_new_readers_read_a_hand_made_run_and_nothing_without_it():
    from benchmarks import serve_scopes

    reader = lambda stem: load_module("metrics", stem)  # noqa: E731
    stems = ("moe_decode_ms", "moe_prefill_ms_per_ktoken",
             "moe_decode_roofline", "prefill_attn_roofline",
             "decode_attn_window_roofline", "moe_held_pair_share")
    training = types.SimpleNamespace(trace=None, peaks=None)
    for stem in stems:
        assert reader(stem).read(training) is None, stem
    cfg = {"num_attention_heads": 2, "head_dim": 4, "sliding_window": 4,
           "layer_types": ["sliding_attention", "full_attention"],
           "num_key_value_heads": 1, "hidden_size": 8,
           "intermediate_size": 16, "num_shared_experts": 2,
           "num_experts_published": 32, "num_hidden_layers": 2,
           "num_experts_per_tok": 4}
    pairs = [[0, 2, 0, 1], [1, 0, 0, 0]]
    decode = ("decode", 1.0, 1.1, 2, 12, {"pairs": pairs, "lengths": [3, 9]})
    prefill = ("prefill", 1.2, 1.3, 16, 6, {"pairs": pairs})
    lay = "Transformer/layer_N/moe_mlp"
    joined = serve_scopes.Joined(
        calls={"decode": 1, "prefill": 1},
        module_s={"decode": {f"{lay}/hvd_moe_route": 1e-3,
                             f"{lay}/hvd_moe_shared": 2e-3,
                             "Transformer/layer_N/attn": 5e-3},
                  "prefill": {f"{lay}/hvd_moe_combine": 4e-3}},
        kernel_s={"decode": {"hvd_moe_experts": 3e-3},
                  "prefill": {"hvd_moe_experts": 6e-3,
                              "hvd_flash_fwd": 1e-3}},
        pathless_s={"decode": {"hvd_moe_experts": 3e-3},
                    "prefill": {"hvd_moe_experts": 6e-3}},
        joined_share=1.0)
    run = types.SimpleNamespace(
        records=[], config=cfg, peaks={"hbm_bytes_per_s": 1e6,
                                       "bf16_flops_per_s": 1e9},
        traced_steps_log=[decode, prefill], steps=[decode, prefill],
        inside=lambda t: True, built=types.SimpleNamespace(num_slots=2),
        trace=types.SimpleNamespace(decode_attn_s=4e-3,
                                    program_calls={"decode": 1}),
        _serve_scopes=joined)
    assert reader("moe_decode_ms").read(run) == pytest.approx(6.0)
    assert reader("moe_prefill_ms_per_ktoken").read(run) == pytest.approx(
        10.0 / 0.006)
    least = flops_cohere2.moe_decode_bytes(cfg, [pairs]) / 1e6
    assert reader("moe_decode_roofline").read(run) == pytest.approx(
        100 * least / 6e-3)
    assert reader("prefill_attn_roofline").read(run) == pytest.approx(
        100 * flops_cohere2.prefill_attention_flops(cfg, [6]) / 1e9 / 1e-3)
    assert reader("decode_attn_window_roofline").read(run) == pytest.approx(
        100 * flops_cohere2.decode_attention_window_bytes(cfg, [[3, 9]])
        / 1e6 / 4e-3)
    # 4 pairs held a call of (6 prompt positions + 2 live slots) x 2 x 4
    assert reader("moe_held_pair_share").read(run) == pytest.approx(
        100 * 8 / (8 * 8))
    # a family that counts nothing (decoder_serve's five-field log)
    run.traced_steps_log = run.steps = [decode[:5], prefill[:5]]
    for stem in ("moe_decode_roofline", "decode_attn_window_roofline",
                 "moe_held_pair_share"):
        assert reader(stem).read(run) is None, stem


@pytest.fixture(scope="module")
def walked(tmp_path_factory):
    """The file's one walk of the tiny cell (``tests/_rehearse.py``)."""
    return walk(tmp_path_factory.mktemp("walk"), "tiny-cohere2", TINY, TRAFFIC,
                CELL)


def test_a_tiny_cell_walks_serving_py_on_the_cpu(walked):
    result, _, stdout = walked
    assert result["correct"], stdout[-3000:]
    assert result["failed"] == 0 and result["attempted"] >= 5
    names = set(result["metrics"])
    assert "moe_held_pair_share.srv" in names      # the program's counter
    assert 5.0 < result["metrics"]["moe_held_pair_share.srv"]["value"] < 60.0
    # device metrics are never made up from a CPU trace
    assert not {"moe_decode_ms.srv", "moe_decode_roofline.srv",
                "prefill_attn_roofline.srv", "device_idle.srv",
                "decode_attn_window_roofline.srv"} & names
    assert "family=cohere2_moe_serve" in stdout and "moe: {" in stdout
    moe = json.loads(stdout.split("moe: ")[1].splitlines()[0])
    assert (moe["experts"], moe["experts_held"], moe["held_from"]) == (
        16, 4, 4)
    assert moe["layers"] == {"full_attention": 1, "sliding_attention": 3}
    assert moe["sliding_window"] == 24 and moe["slots"] == 3
    assert 0 < moe["held_pairs"] < moe["pairs"]
    gap, limit = result["compared"]["served_token_gap_below_reference_best"]
    assert gap < limit
    checks = json.loads(stdout.split("checks=")[1].splitlines()[0])
    # requests past the 24-token window were served and compared
    assert checks[0]["longest"] > 24


def test_an_altered_served_token_is_not_correct(walked):
    assert_the_altered_record_is_not_correct(walked)


@pytest.mark.parametrize("altered", [False, True])
def test_measure_says_of_a_run_what_its_comparison_says(
        altered, tmp_path, monkeypatch):
    """The walk above hands ``compare`` an altered record; this is the rest
    of the line, in this process and with no model: ``serving.measure``
    drives an engine over the token automaton, the ENGINE serves one wrong
    token, and the harness's own sample of finished requests, judged by a
    comparison that replays the automaton, makes the run not ``correct``
    and puts the gap beside its limit in ``compared``."""
    import argparse

    import numpy as np

    from benchmarks import serving
    from benchmarks.built import Served
    from horovod_tpu.serving.engine import (ServingConfig, ServingEngine,
                                            StubBackend)

    backend = StubBackend(3)

    def replayed(prompt) -> list[int]:
        padded = np.asarray(prompt)[None]
        return [backend.prefill(padded, padded.shape[1], 0)[0]]

    def compare(finished, seed):
        gap = 0
        for prompt, tokens in finished:
            want = replayed(prompt)
            while len(want) < len(tokens):
                want.append(backend._next_tok(want[-1],
                                              len(prompt) + len(want)))
            gap += sum(int(a != b) for a, b in zip(want, tokens))
        return [{"name": "served_token_gap_below_reference_best",
                 "error": float(gap), "tolerance": 0.5, "ok": gap < 0.5,
                 "requests": len(finished)}]

    def serve(config, traffic, chips, seed):
        engine = ServingEngine(
            serving.Timed(backend),
            ServingConfig(num_slots=3, buckets=(16, 32, 64), max_seq_len=96,
                          eos_id=None), clock=serving.clock)
        return Served(
            engine=engine, warm=lambda: None, release=lambda: None,
            compare=compare, vocab_size=256, parameters=0, num_slots=3,
            kv_bytes_per_token=0, program_names={},
            decode_scopes=lambda: None)

    if altered:
        take = ServingEngine._take_token

        def wrong(self, req, slot, token, *a, **k):
            if len(req.tokens) == 2:
                token = (token + 101) % 256
            return take(self, req, slot, token, *a, **k)

        monkeypatch.setattr(ServingEngine, "_take_token", wrong)
    harness = types.SimpleNamespace(
        args=argparse.Namespace(seed=7, seconds=1.0, trace=0,
                                out=str(tmp_path)),
        cell={"name": "stub-1"}, config={"family": "stub"},
        traffic=dict(TRAFFIC, rate=20.0, drain_s=5), chips=1,
        family=types.SimpleNamespace(serve=serve), peaks=None,
        compile_events=[], devices=[None],
        dev=types.SimpleNamespace(platform="cpu", device_kind="cpu"))
    outcome = serving.measure(harness)
    assert outcome.attempted >= 5 and outcome.failed == 0
    gap, limit = outcome.compared["served_token_gap_below_reference_best"]
    assert outcome.correct is not altered
    assert (gap > limit) is altered
    assert outcome.compared["rejected"] == [0, 0]
