"""The benchmark's side of the SDAR-30B-A3B-Chat configuration (PR 56): the
manifest's entries for ``SDAR-30B-A3B-Chat`` and ``sdar30b-chat4k-open``
(every published key against the catalog's row, ``reduced``, the
deployment, the traffic's parameters), the schedule against its long run,
the counts of ``benchmarks/flops_sdar.py``, the files found by name, the new
readers on hand-made spans, and what a checkout before this PR says of the
file.  Here, and not under ``benchmarks/tests``, so that the tier-1 run
holds them; the model, the engine and the run's comparison on a small model
are ``tests/test_block_diffusion.py``'s."""

import dataclasses
import json
import os
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import flops_sdar  # noqa: E402
from benchmarks.run import load_cell, load_module  # noqa: E402

CELL = "sdar30b-chat4k-open"
# (four: the manifest may hold 128 per-layer metrics and held 124; ISSUE 56's
# fifth, the commit passes' share, is denoise_tokens_per_pass's rest)
NEW = {"denoise_tokens_per_pass.srv", "denoise_first_token_passes.srv",
       "moe_block_decode_roofline.srv", "sdar_prefill_attn_roofline.srv"}
# what the cell does not join, and why (PERF.md section 7 B4 / B7)
LEFT_OUT = {"queue_ms_p95.srv", "tpot_ms_p50.srv", "tpot_ms_p95.srv",
            "moe_top1_decode_roofline.srv", "prefill_attn_roofline.srv"}
NEW_FIELDS = {"attention_block", "mask_token_id"}


def manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def catalog_entry():
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        return None
    with open(path) as f:
        rows = [json.loads(line) for line in f if line.strip()]
    return next(r for r in rows if r["name"] == "SDAR-30B-A3B-Chat")


def test_the_manifest_holds_the_cell_and_its_metrics():
    m = manifest()
    # the thirteenth cell and the eleventh configuration
    cell = m["workloads"][12]
    assert (cell["name"], cell["config"], cell["traffic"], cell["chips"]) \
        == (CELL, "SDAR-30B-A3B-Chat", "chat4k-open", 1)
    assert sum(c["chips"] == 4 for c in m["workloads"]) == 1
    entry = m["configs"][10]
    assert entry["name"] == "SDAR-30B-A3B-Chat"
    assert entry["reduced"] == ["num_hidden_layers"]
    for e in m["configs"] + m["workloads"]:
        assert len(e["why"]) <= 200, e["name"]
    # the why names the product form the kept rule runs in a pass
    assert "hvd_moe_grouped" in cell["why"] or "ragged_dot" in cell["why"]
    with open(os.path.join(ROOT, entry["file"])) as f:
        cfg = json.load(f)
    widths = {
        "hidden_size": 2048, "num_attention_heads": 32,
        "num_key_value_heads": 4, "head_dim": 128, "num_experts": 128,
        "moe_intermediate_size": 768, "num_experts_per_tok": 8,
        "vocab_size": 151936, "tie_word_embeddings": False,
        "norm_topk_prob": True, "rms_norm_eps": 1e-6, "rope_theta": 1000000,
        "model_type": "sdar_moe", "max_position_embeddings": 32768}
    assert {k: cfg[k] for k in widths} == widths
    catalog = catalog_entry()
    if catalog is not None:     # the guide's row, where it can be read
        assert entry["source"] == catalog["source_url"] == cfg["source"]
        differ = {k for k, v in catalog["config"].items() if cfg.get(k) != v}
        assert differ == {"num_hidden_layers"}
        assert catalog["config"]["num_hidden_layers"] == \
            cfg["num_hidden_layers_published"] == 48
    assert cfg["num_hidden_layers"] == 6
    assert list(cfg["reduced"]) == entry["reduced"]
    assert "first of eight pipeline stages" in cfg["reduced"][
        "num_hidden_layers"]
    for said in ("eight pipeline stages of 6 layers", "4361 M", "8.72 GB",
                 "2048 bytes a position a layer", "3.17 GB"):
        assert said in cfg["deployment"], said
    assert {"qk_norm", "block_length", "denoising_steps", "remasking",
            "confidence_threshold", "mask_token_id", "no_shift",
            "initializer_range", "draw"} <= set(cfg["assumed"])
    assert set(cfg["departures"]) == {"head", "context", "pipeline",
                                      "mask_excluded"}
    assert cfg["generation"] == {
        "block_length": 4, "denoising_steps": 4,
        "remasking": "low_confidence_static", "confidence_threshold": 0.9,
        "temperature": 1.0, "mask_token_id": 151669}
    # the shared metrics the cell joins, and the five it brings
    joined = {e["name"] for g in ("end_to_end", "per_layer") for e in m[g]
              if CELL in e.get("workloads", ())}
    assert NEW <= joined and not LEFT_OUT & joined
    assert {"ttft_ms_mean", "engine_queue_ms_p95.srv", "goodput_share.srv",
            "decode_attn_roofline.srv", "moe_decode_ms.srv",
            "moe_prefill_ms_per_ktoken.srv", "kv_live_share.srv",
            "moe_experts_touched_share.srv", "idle_named_share.srv",
            "sched_self_ms.srv", "decode_step_ms.srv",
            "setup_warm_s"} <= joined
    for e in m["per_layer"]:
        if e["name"] in NEW:
            assert e["moves"] == "ttft_ms_mean" and e["workloads"] == [CELL]
    # appended: nothing before the new entries moved
    assert [e["name"] for e in m["per_layer"]][-4:] == [
        "denoise_tokens_per_pass.srv", "denoise_first_token_passes.srv",
        "moe_block_decode_roofline.srv", "sdar_prefill_attn_roofline.srv"]
    assert len(m["per_layer"]) <= 128       # the manifest's own limit


def test_the_traffic_file_says_where_its_numbers_come_from():
    *_, cfg, traffic = load_cell(os.path.join(ROOT, "BENCHMARK.json"), CELL)
    assert traffic["arrivals"]["prompt_tokens"] == {
        "median": 1020, "sigma": 0.499, "min": 16, "max": 4096}
    assert traffic["arrivals"]["output_tokens"] == {
        "median": 129, "sigma": 0.992, "min": 1, "max": 1024}
    assert "recalled" in traffic["arrivals"]["source"]
    assert (traffic["num_slots"], traffic["max_seq_len"],
            traffic["prefill_buckets"]) == (48, 5376, [512, 1024, 2048, 4096])
    assert traffic["num_slots"] * 4 * cfg["num_experts_per_tok"] % 128 == 0
    assert (traffic["lead_in_s"], traffic["drain_s"],
            traffic["compare_requests"]) == (10, 60, 8)
    assert traffic["generation"] == {"remasking": "low_confidence_static",
                                     "denoising_steps": 4}
    assert traffic["stream"]["zipf_a"] == 0.0
    knee = traffic["knee"]
    assert knee["share_of_capacity"] in (0.7, 0.8)
    assert traffic["rate"] == pytest.approx(
        knee["share_of_capacity"] * knee["rate_per_s"], rel=0.01)
    assert len(knee["below_capacity"]) == 4 and knee["overload"]["sent"] > 0
    assert "rule" in knee
    unloaded = knee["unloaded"]
    assert traffic["ttft_limit_ms"] == pytest.approx(
        5 * unloaded["ttft_ms_4096_token_prompt"], rel=0.01)
    assert traffic["tpot_limit_ms"] == pytest.approx(
        3 * unloaded["decode_step_ms_every_slot_full"], rel=0.01)


def test_the_schedule_is_typical_of_its_long_run():
    """By longdoc32k-open's rule: the first 40 s (lead-in and window) within
    5% of the long run, at the file's rate and over the band the seed was
    chosen for before the capacity was read."""
    from benchmarks import arrivals

    *_, traffic = load_cell(os.path.join(ROOT, "BENCHMARK.json"), CELL)
    for name, ratio in arrivals.typical(traffic, 40.0).items():
        assert abs(ratio - 1.0) <= 0.05, (name, ratio)
    lo, hi = traffic["arrivals"]["schedule_seed_band"]
    assert lo <= traffic["rate"] <= hi
    rate = lo
    while rate <= hi + 1e-9:
        for name, ratio in arrivals.typical(
                dict(traffic, rate=round(rate, 2)), 40.0).items():
            assert abs(ratio - 1.0) <= 0.05, (rate, name, ratio)
        rate += 0.05
    sched = arrivals.schedule(traffic, 40.0)
    # the window's count: ISSUE 56's floor
    assert len(sched) - len(arrivals.schedule(traffic, 10.0)) >= 40
    assert sched.prompt_len.max() <= 4096 and sched.output_len.max() <= 1024
    mean = arrivals.long_run(traffic)
    assert 1140 < mean["mean_prompt_tokens"] < 1190
    assert 195 < mean["mean_output_tokens"] < 215


def test_the_family_its_reference_and_its_readers_are_found_by_name():
    *_, cfg, traffic = load_cell(os.path.join(ROOT, "BENCHMARK.json"), CELL)
    family = load_module("families", cfg["family"])
    assert hasattr(family, "serve") and not hasattr(family, "build")
    assert family.reference.__name__ == "benchmarks.reference.sdar_moe_serve"
    with open(os.path.join(ROOT, "benchmarks", "reference",
                           "sdar_moe_serve.py")) as f:
        text = f.read()
    assert "horovod_tpu" not in text
    assert 'default_matmul_precision("highest")' in text
    assert traffic["why"].startswith("chat traffic")
    for name in NEW:
        assert callable(load_module("metrics", name.split(".")[0]).read)
    mcfg = family.model_config(cfg, traffic)
    assert (mcfg.qk_norm, mcfg.attention_block, mcfg.mask_token_id,
            mcfg.num_experts, mcfg.experts_per_token, mcfg.max_seq_len) == (
        "head", 4, 151669, 128, 8, 5376)
    scfg = family.serving_config(cfg, traffic)
    assert (scfg.denoise_steps, scfg.unmask_rule,
            scfg.confidence_threshold) == (4, "low_confidence_static", 0.9)
    # a traffic mix may ask for the other rule
    other = family.serving_config(cfg, dict(traffic, generation={
        "remasking": "low_confidence_dynamic"}))
    assert other.unmask_rule == "low_confidence_dynamic"


def test_the_parameters_of_the_built_tree():
    """4361 M parameters from the shapes of the drawn tree, nothing made."""
    import jax
    import numpy as np

    *_, cfg, traffic = load_cell(os.path.join(ROOT, "BENCHMARK.json"), CELL)
    family = load_module("families", cfg["family"])
    shapes = jax.eval_shape(
        lambda: family.program_params(cfg, family.seed_key(1)))
    n = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes))
    assert round(n / 1e6) == 4361
    layer = sum(int(np.prod(x.shape))
                for x in jax.tree.leaves(shapes["params"]["layer_0"]))
    assert round(layer / 1e6, 1) == 623.1
    assert shapes["params"]["layer_0"]["attn"]["q_norm"]["scale"].shape \
        == (128,)
    assert shapes["params"]["lm_head"]["kernel"].shape == (2048, 151936)


CFG = {"num_attention_heads": 32, "head_dim": 128, "hidden_size": 2048,
       "moe_intermediate_size": 768, "num_hidden_layers": 6,
       "generation": {"block_length": 4}}


def test_counts_of_the_block_causal_triangle_and_of_the_picks():
    # block 4: positions 0-3 see 4 keys each, 4-7 see 8
    assert flops_sdar.seen_positions(4, 4) == 16
    assert flops_sdar.seen_positions(8, 4) == 48
    assert flops_sdar.seen_positions(6, 4) == 16 + 2 * 6
    brute = sum(min((i // 4 + 1) * 4, 1156) for i in range(1156))
    assert flops_sdar.seen_positions(1156, 4) == brute
    assert flops_sdar.prefill_attention_flops(CFG, [8, 4]) == \
        4.0 * 32 * 128 * 6 * (48 + 16)
    # an expert's three matrices: 9.437 MB
    assert flops_sdar.expert_bytes(CFG) == 9437184
    assert flops_sdar.block_decode_bytes(CFG, 700) == 700 * 9437184


def test_the_new_readers_read_hand_made_spans_and_nothing_without_them(
        monkeypatch):
    from horovod_tpu.utils import profiling

    tokens = load_module("metrics", "denoise_tokens_per_pass")
    first = load_module("metrics", "denoise_first_token_passes")
    record = lambda name, start, rid=None, **f: profiling.Record(  # noqa: E731
        name, start, start + 0.01, 0, 0, rid, f)
    records = [
        record(profiling.SRV_STEP, 0.9, queued=0, made_final=3, handed=2),
        record(profiling.SRV_DECODE, 1.0, slots=4, commits=1, block=4,
               masked_in=9),
        record(profiling.SRV_STEP, 1.9, queued=1, made_final=5, handed=7),
        record(profiling.SRV_DECODE, 2.0, slots=6, commits=1, block=4,
               masked_in=11),
        record(profiling.SRV_STEP, 8.9, queued=0, made_final=0, handed=0),
        record(profiling.SRV_DECODE, 9.0, slots=6, commits=6, block=4,
               masked_in=0),                            # outside the window
        record(profiling.SRV_STEP, 1.4, queued=0),
        record(profiling.SRV_DECODE, 1.5, slots=3),     # a token a step
        record(profiling.SRV_REQUEST, 0.5, rid=7, passes=9,
               first_token_passes=1),
        record(profiling.SRV_REQUEST, 0.6, rid=8, passes=9,
               first_token_passes=4),
        record(profiling.SRV_REQUEST, 0.7, rid=9, passes=9,
               first_token_passes=3)]                   # not counted
    monkeypatch.setattr(profiling, "spans", lambda: records)
    run = types.SimpleNamespace(
        records=[], inside=lambda t: 0.0 <= t < 5.0,
        counted=[types.SimpleNamespace(request=types.SimpleNamespace(rid=r))
                 for r in (7, 8)])
    assert tokens.read(run) == pytest.approx(8 / 10)
    assert tokens.passes(run) == {"live": 10, "commits": 2, "made_final": 8}
    assert first.read(run) == pytest.approx(2.5)
    # a program that writes no such fields (the parent's): nothing, no raise
    monkeypatch.setattr(profiling, "spans", lambda: [
        record(profiling.SRV_STEP, 0.9, queued=0),
        record(profiling.SRV_DECODE, 1.0, slots=4),
        record(profiling.SRV_REQUEST, 0.5, rid=7, tokens=3)])
    assert tokens.read(run) is None
    assert first.read(run) is None
    # a training run, an untraced run: the device readers give nothing
    train = types.SimpleNamespace(peaks=None)
    for stem in ("moe_block_decode_roofline", "sdar_prefill_attn_roofline"):
        assert load_module("metrics", stem).read(train) is None
    assert tokens.read(train) is None and first.read(train) is None


def test_a_checkout_before_this_pr_refuses_the_file_at_once(monkeypatch):
    """The parent tree given the new files: ``TransformerConfig.from_dict``
    names the fields it does not know, before a weight is drawn."""
    from horovod_tpu.models import TransformerConfig

    *_, cfg, traffic = load_cell(os.path.join(ROOT, "BENCHMARK.json"), CELL)
    family = load_module("families", "sdar_moe_serve")
    parent = dataclasses.make_dataclass(
        "TransformerConfig",
        [(f.name, f.type, f) for f in dataclasses.fields(TransformerConfig)
         if f.name not in NEW_FIELDS], frozen=True,
        namespace={"from_dict": classmethod(
            TransformerConfig.from_dict.__func__)})
    monkeypatch.setattr(family, "TransformerConfig", parent)
    with pytest.raises(ValueError, match=r"no field \['attention_block', "
                       r"'mask_token_id'\]"):
        family.model_config(cfg, traffic)
