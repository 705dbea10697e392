"""The benchmark's side of the ZAYA1-8B configuration (PR 52): the
manifest's entries for ``ZAYA1-8B`` and ``zaya1-reason8k-open`` (every
published key against the catalog's row, ``reduced``, the deployment, the
traffic's parameters), the parameter count from the built tree, the counts
of ``benchmarks/flops_cca.py``, the new readers on a hand-made run, what a
checkout before this PR says of the file, and a ``--rehearse-on-cpu`` walk of
a tiny cell of the family through ``benchmarks/serving.py``, its files found
by name: ``correct`` true as served, false with a served token altered, and
the float8 control, through the run's own comparison, past the limit.  Here,
and not under ``benchmarks/tests``, so that the tier-1 run holds them."""

import dataclasses
import json
import os
import sys
import time
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import flops_cca  # noqa: E402
from benchmarks.run import load_cell, load_module  # noqa: E402

from _rehearse import (assert_the_altered_record_is_not_correct,  # noqa: E402
                       walk)

CELL = "zaya1-reason8k-open"
TINY = {"family": "cca_moe_serve", "model_type": "zaya", "hidden_act": "silu",
        "attention_bias": False, "lm_head_bias": False, "hidden_size": 32,
        "head_dim": 8, "num_attention_heads": 4, "num_key_value_heads": 2,
        "cca_time0": 2, "cca_time1": 2, "partial_rotary_factor": 0.5,
        "rope_parameters": {
            "hybrid": {"partial_rotary_factor": 0.5, "rope_theta": 5000000,
                       "rope_type": "default"}, "rope_type": "default"},
        "router_hidden_size": 16, "moe_intermediate_size": 24,
        "num_experts": 4, "num_experts_per_tok": 1, "rms_norm_eps": 1e-5,
        "sliding_window": None, "tie_word_embeddings": True,
        "num_hidden_layers": 3, "layer_types": ["hybrid"] * 3,
        "vocab_size": 256, "initializer_range": 0.5,
        "expert_bias_scale": 0.02, "router_logit_gain": 3.0,
        "feed_forward_chunk": 64}
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
NEW = {"cca_prefill_ms_per_ktoken.srv", "cca_decode_ms.srv",
       "cca_prefill_attn_roofline.srv", "cca_decode_attn_roofline.srv",
       "moe_top1_decode_roofline.srv", "moe_experts_touched_share.srv"}
NEW_FIELDS = {"cca_taps", "rotary_fraction", "moe_router_dim",
              "residual_scaling"}


def manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def catalog_entry():
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        return None
    with open(path) as f:
        rows = [json.loads(line) for line in f if line.strip()]
    return next(r for r in rows if r["name"] == "ZAYA1-8B")


def test_the_manifest_holds_the_cell_and_its_metrics():
    m = manifest()
    # the twelfth cell and the tenth configuration (later PRs append theirs)
    cell = m["workloads"][11]
    assert (cell["name"], cell["config"], cell["traffic"], cell["chips"]) \
        == (CELL, "ZAYA1-8B", "reason8k-open", 1)
    assert sum(c["chips"] == 4 for c in m["workloads"]) == 1
    entry = m["configs"][9]
    assert entry["name"] == "ZAYA1-8B"
    assert entry["reduced"] == ["num_hidden_layers"]
    assert entry["source"] == ("https://huggingface.co/Zyphra/ZAYA1-8B/"
                               "blob/main/config.json")
    for e in m["configs"] + m["workloads"]:
        assert len(e["why"]) <= 200, e["name"]
    with open(os.path.join(ROOT, entry["file"])) as f:
        cfg = json.load(f)
    # every published width, and what routes
    widths = {
        "hidden_size": 2048, "num_attention_heads": 8,
        "num_key_value_heads": 2, "head_dim": 128, "cca_time0": 2,
        "cca_time1": 2, "partial_rotary_factor": 0.5,
        "router_hidden_size": 256, "num_experts": 16,
        "moe_intermediate_size": 2048, "num_experts_per_tok": 1,
        "vocab_size": 262272, "tie_word_embeddings": True,
        "rms_norm_eps": 1e-5, "model_type": "zaya",
        "max_position_embeddings": 131072}
    assert {k: cfg[k] for k in widths} == widths
    assert cfg["rope_parameters"]["hybrid"]["rope_theta"] == 5000000
    catalog = catalog_entry()
    if catalog is not None:     # the guide's row, where it can be read
        assert entry["source"] == catalog["source_url"] == cfg["source"]
        # key by key: num_hidden_layers the one difference (layer_types is
        # its list, cut with it), and it is in ``reduced``
        differ = {k for k, v in catalog["config"].items() if cfg.get(k) != v}
        assert differ == {"num_hidden_layers", "layer_types"}
        assert catalog["config"]["num_hidden_layers"] == \
            cfg["num_hidden_layers_published"] == 40
        assert catalog["config"]["layer_types"] == ["hybrid"] * 40
    assert cfg["num_hidden_layers"] == 20
    assert cfg["layer_types"] == ["hybrid"] * 20
    assert list(cfg["reduced"]) == entry["reduced"]
    assert "first of two pipeline stages" in cfg["reduced"][
        "num_hidden_layers"]
    for said in ("two pipeline stages of 20 layers", "4689 M", "9.38 GB",
                 "1024 bytes a position a layer", "2688 float32 values",
                 "24 slots of 10496", "about twice a deployment's"):
        assert said in cfg["deployment"], said
    assert {"value_shift_layout", "key_scale", "router_depth_averaging",
            "router_mlp", "residual_merge", "initializer_range", "draw",
            "convolutions", "rotary"} <= set(cfg["assumed"])
    assert {"mod", "head", "context", "pipeline"} <= set(cfg["departures"])
    assert "zaya_use_mod" in cfg["departures"]["mod"]

    *_, traffic = load_cell(os.path.join(ROOT, "BENCHMARK.json"), CELL)
    arrivals = traffic["arrivals"]
    assert arrivals["kind"] == "poisson_lognormal"
    assert arrivals["prompt_tokens"] == {
        "median": 2120, "sigma": 0.789, "min": 64, "max": 8192}
    assert arrivals["output_tokens"] == {
        "median": 480, "sigma": 1.239, "min": 1, "max": 2048}
    assert "open-infra-index" in arrivals["source"] and "Recalled" in \
        arrivals["source"]
    assert traffic["prefill_buckets"] == [1024, 2048, 4096, 8192]
    assert (traffic["max_seq_len"], traffic["lead_in_s"],
            traffic["drain_s"], traffic["compare_requests"]) == (
        10496, 15, 60, 8)
    assert traffic["num_slots"] in (24, 16)
    assert traffic["stream"]["zipf_a"] == 0 and traffic["unit"] == "tokens"
    knee = traffic["knee"]["rate_per_s"]
    share = traffic["knee"]["share_of_capacity"]
    assert share in (0.7, 0.8)          # the issue's rate, or its count rule's
    assert (share - 0.01) * knee <= traffic["rate"] <= (share + 0.01) * knee
    assert len(traffic["knee"]["below_capacity"]) >= 3
    unloaded = traffic["knee"]["unloaded"]
    assert traffic["ttft_limit_ms"] == pytest.approx(
        5 * unloaded["ttft_ms_8192_token_prompt"], rel=0.02)
    assert traffic["tpot_limit_ms"] == pytest.approx(
        3 * unloaded["decode_step_ms_every_slot_full"], rel=0.02)
    # the pool: 20 layers' rows and tails
    assert 20 * 2 * 2 * 128 * 2 == 20480
    assert 24 * 10496 * 20480 == 5158993920
    assert 20 * 2688 * 4 * 24 == 5160960

    reported = {e["name"] for g in ("end_to_end", "per_layer")
                for e in m[g]
                if "workloads" not in e or CELL in e["workloads"]}
    assert NEW | {"ttft_ms_mean", "peak_hbm", "setup_s", "hbm_in_use",
                  "hbm_reserved", "device_idle.srv", "prefill_share.srv",
                  "decode_step_ms.srv", "tpot_ms_p50.srv",
                  "prefill_ms_per_ktoken.srv", "queue_ms_p95.srv",
                  "moe_decode_ms.srv", "moe_prefill_ms_per_ktoken.srv",
                  "kv_live_share.srv", "kv_live_peak_share.srv",
                  "decode_fetch_ms.srv", "idle_named_share.srv"} <= reported
    # readers that count another family's keys or another mixer's path
    assert not {"moe_decode_roofline.srv", "moe_held_pair_share.srv",
                "decode_attn_roofline.srv", "kda_decode_ms.srv",
                "mla_decode_ms.srv", "tokens_per_s", "flash_ms"} & reported
    names = [e["name"] for e in m["per_layer"]]
    at = names.index("cca_prefill_ms_per_ktoken.srv")
    assert set(names[at:at + 6]) == NEW     # appended, together
    layers = {e["layer"] for e in m["per_layer"][:at]} | {
        "models (models/cca.py)"}
    for e in m["per_layer"][at:at + 6]:
        # (a later cell whose program writes the same counter appends
        # itself: PR 56 joined moe_experts_touched_share.srv)
        assert e["workloads"][0] == CELL and e["moves"] == "ttft_ms_mean"
        assert e["workloads"] == [CELL] or e["name"] == \
            "moe_experts_touched_share.srv"
        assert e["layer"] in layers
        assert os.path.exists(os.path.join(
            ROOT, "benchmarks", "metrics", e["name"].split(".")[0] + ".py"))
        if "roofline" in e["name"]:
            assert e["unit"] == "%" and e["better"] == "higher"
    # every list the cell was appended to holds it last (a later cell's name
    # may follow it)
    later = {c["name"] for c in m["workloads"][12:]}
    for g in ("end_to_end", "per_layer"):
        for e in m[g]:
            if CELL in e.get("workloads", ()):
                assert [w for w in e["workloads"] if w not in later][-1] \
                    == CELL, e["name"]


def test_the_parameters_of_the_built_tree():
    """207.6 M a layer and 4689 M in all, from the shapes of the tree the
    family hands the program (nothing is drawn)."""
    import jax
    import numpy as np

    *_, cfg, traffic = load_cell(os.path.join(ROOT, "BENCHMARK.json"), CELL)
    family = load_module("families", "cca_moe_serve")
    shapes = jax.eval_shape(
        lambda: family.program_params(cfg, family.seed_key(1)))["params"]
    count = lambda tree: sum(  # noqa: E731
        int(np.prod(x.shape)) for x in jax.tree.leaves(tree))
    assert round(count(shapes["layer_1"]) / 1e6, 1) == 207.6
    # the first layer has no state to decay: 256 values fewer
    assert count(shapes["layer_1"]) - count(shapes["layer_0"]) == 256
    assert round(count(shapes["layer_1"]["cca"]) / 1e6, 2) == 5.58
    router = {k: v for k, v in shapes["layer_1"]["moe_mlp"].items()
              if k.startswith("router_")}
    assert round(count(router) / 1e6, 2) == 0.66
    assert count(shapes["layer_1"]["moe_mlp"]["gate"]) * 3 == 201326592
    assert count(shapes["embed"]) == 262272 * 2048
    assert "lm_head" not in shapes      # tied
    assert round(count(shapes) / 1e6) == 4689
    # the program's own tree has the same leaves
    from horovod_tpu.models import Transformer

    mcfg = family.model_config(cfg, dict(traffic, max_seq_len=64))
    thin = dataclasses.replace(mcfg, num_layers=2, layer_types=("cca",) * 2,
                               vocab_size=64)
    made = jax.eval_shape(
        lambda: Transformer(thin).init(
            jax.random.PRNGKey(0), np.zeros((1, 8), np.int32)))["params"]
    for i in (0, 1):
        assert jax.tree.map(lambda x: x.shape, made[f"layer_{i}"]) == \
            jax.tree.map(lambda x: x.shape, shapes[f"layer_{i}"])


def test_the_schedule_is_typical_of_its_long_run():
    """By longdoc32k-open's rule: the first 45 s (lead-in and window) within
    5% of the long run, at the file's rate and over the band the seed was
    chosen for before the capacity was read."""
    from benchmarks import arrivals

    *_, traffic = load_cell(os.path.join(ROOT, "BENCHMARK.json"), CELL)
    for name, ratio in arrivals.typical(traffic, 45.0).items():
        assert abs(ratio - 1.0) <= 0.05, (name, ratio)
    # the seed was chosen for a band around 0.8 of capacity, the share
    # ISSUE 52's count rule takes, and the rate kept lies inside it
    lo, hi = traffic["arrivals"]["schedule_seed_band"]
    assert traffic["knee"]["share_of_capacity"] == 0.8
    assert lo <= traffic["rate"] <= hi
    rate = lo
    while rate <= hi + 1e-9:
        for name, ratio in arrivals.typical(
                dict(traffic, rate=round(rate, 2)), 45.0).items():
            assert abs(ratio - 1.0) <= 0.05, (rate, name, ratio)
        rate += 0.01
    sched = arrivals.schedule(traffic, 45.0)
    # the window's count: ISSUE 52 asked for 40 at a capacity of 1.5-1.9;
    # capacity read 1.375, and at 0.8 of it the window is sent 35
    assert len(sched) - len(arrivals.schedule(traffic, 15.0)) >= 33
    assert sched.prompt_len.max() <= 8192 and sched.output_len.max() <= 2048
    mean = arrivals.long_run(traffic)
    assert 2600 < mean["mean_prompt_tokens"] < 2800
    assert 700 < mean["mean_output_tokens"] < 780
    # 3.7 input tokens an output token (the source: 3.6)
    assert 3.5 < mean["mean_prompt_tokens"] / mean["mean_output_tokens"] < 3.9


def test_the_family_its_reference_and_its_readers_are_found_by_name():
    *_, cfg, traffic = load_cell(os.path.join(ROOT, "BENCHMARK.json"), CELL)
    family = load_module("families", cfg["family"])
    assert hasattr(family, "serve") and not hasattr(family, "build")
    assert family.reference.__name__ == "benchmarks.reference.cca_moe_serve"
    with open(os.path.join(ROOT, "benchmarks", "reference",
                           "cca_moe_serve.py")) as f:
        assert "horovod_tpu" not in f.read().replace(
            "families/cca_moe_serve.py", "")
    assert traffic["why"].startswith("reasoning traffic")
    for name in NEW:
        assert callable(load_module("metrics", name.split(".")[0]).read)


CFG = {"num_attention_heads": 8, "num_key_value_heads": 2, "head_dim": 128,
       "hidden_size": 2048, "moe_intermediate_size": 2048,
       "num_experts": 16, "num_hidden_layers": 20}


def test_counts_of_the_latent_attention_and_of_the_picks():
    # 4 x 1024 FLOPs a (query, key) pair a layer: a triangle of 3 is 6 pairs
    assert flops_cca.prefill_attention_flops(CFG, [3]) == 4 * 1024 * 6 * 20
    assert flops_cca.prefill_attention_flops(CFG, [3, 1]) == \
        4 * 1024 * 7 * 20
    # 1024 bytes a cached position a layer
    assert flops_cca.cached_bytes_per_token(CFG) == 1024
    assert flops_cca.decode_attention_bytes(CFG, [100, 50]) == \
        1024 * 20 * 150
    # an expert's three matrices: 25.17 MB
    assert flops_cca.expert_bytes(CFG) == 25165824
    assert flops_cca.top1_decode_bytes(CFG, 7) == 7 * 25165824


def test_the_new_readers_read_a_hand_made_run_and_nothing_without_it(
        monkeypatch):
    from benchmarks import serve_scopes
    from horovod_tpu.utils import profiling

    reader = lambda stem: load_module("metrics", stem)  # noqa: E731
    stems = sorted(n.split(".")[0] for n in NEW)
    training = types.SimpleNamespace(trace=None, peaks=None)
    for stem in stems:
        assert reader(stem).read(training) is None, stem
    pairs = [[0, 2, 0, 1]] * 20
    decode = ("decode", 1.0, 1.1, 2, 12, {"pairs": pairs, "lengths": [3, 9]})
    dense = ("prefill", 1.2, 1.3, 1024, 600, {"pairs": pairs})
    flash = ("prefill", 1.4, 1.5, 2048, 1500, {"pairs": pairs})
    lay = "Transformer/layer_N"
    joined = serve_scopes.Joined(
        calls={"decode": 1, "prefill": 2},
        module_s={"decode": {f"{lay}/cca/{profiling.CCA_ATTN}": 4e-3,
                             f"{lay}/cca/{profiling.CCA_PROJ}/q": 1e-3,
                             f"{lay}/moe_mlp/{profiling.MOE_EXPERTS}": 3e-3,
                             f"{lay}/moe_mlp/{profiling.MOE_ROUTE}": 2e-3},
                  "prefill": {f"{lay}/cca/{profiling.CCA_CONV}": 5e-3,
                              f"{lay}/cca/{profiling.CCA_OUT}/o": 1e-3,
                              f"{lay}/moe_mlp/{profiling.MOE_ROUTE}": 9e-3}},
        kernel_s={"decode": {profiling.MOE_EXPERTS: 5e-3},
                  "prefill": {"hvd_flash_fwd": 2e-3}},
        pathless_s={"decode": {profiling.MOE_EXPERTS: 5e-3},
                    "prefill": {"hvd_flash_fwd": 2e-3}},
        joined_share=1.0)
    backend = types.SimpleNamespace(
        prefill_attention=lambda b: "flash" if b > 1024 else "dense")
    span = lambda start, **fields: types.SimpleNamespace(  # noqa: E731
        name=profiling.SRV_DECODE, start=start, fields=fields)
    ring = [span(5.0, experts_touched=40, slots=2),
            span(6.0, experts_touched=24, slots=1),
            span(20.0, experts_touched=30, slots=2)]    # the traced stretch
    monkeypatch.setattr(profiling, "spans", lambda: ring)
    run = types.SimpleNamespace(
        records=[], config=CFG, peaks={"hbm_bytes_per_s": 1e9,
                                       "bf16_flops_per_s": 1e12},
        traced_steps_log=[decode, dense, flash], steps=[decode, dense, flash],
        inside=lambda t: t < 10.0, end_t=10.0,
        built=types.SimpleNamespace(
            num_slots=2, engine=types.SimpleNamespace(backend=backend)),
        trace=types.SimpleNamespace(program_calls={"decode": 1}),
        _serve_scopes=joined)
    assert reader("cca_decode_ms").read(run) == pytest.approx(5.0)
    # 6 ms under cca + 2 ms of the pathless kernel, 2100 prompt tokens
    assert reader("cca_prefill_ms_per_ktoken").read(run) == pytest.approx(
        8.0 / 2.1)
    # the flash bucket's prompt alone, at its own length, over the kernel
    assert reader("cca_prefill_attn_roofline").read(run) == pytest.approx(
        100 * flops_cca.prefill_attention_flops(CFG, [1500]) / 1e12 / 2e-3)
    assert reader("cca_decode_attn_roofline").read(run) == pytest.approx(
        100 * flops_cca.decode_attention_bytes(CFG, [12]) / 1e9 / 4e-3)
    # the window's two steps: 64 of 2 x 16 x 20
    assert reader("moe_experts_touched_share").read(run) == pytest.approx(
        100 * 64 / 640)
    # the traced step's 30 experts over 3 ms under the scope + 5 ms pathless
    assert reader("moe_top1_decode_roofline").read(run) == pytest.approx(
        100 * 30 * 25165824 / 1e9 / 8e-3)
    # a program that names no cca scope and counts no experts: nothing
    joined.module_s = {"decode": {f"{lay}/attn/o": 1e-3},
                       "prefill": {f"{lay}/mlp/up": 9e-3}}
    joined.kernel_s = joined.pathless_s = {"decode": {}, "prefill": {}}
    monkeypatch.setattr(profiling, "spans", lambda: [span(5.0, slots=2)])
    for stem in stems:
        assert reader(stem).read(run) is None, stem


def test_a_checkout_before_this_pr_refuses_the_file_at_once(monkeypatch):
    """The parent tree given the new files: ``TransformerConfig.from_dict``
    names the fields it does not know, before a weight is drawn."""
    from horovod_tpu.models import TransformerConfig

    *_, cfg, traffic = load_cell(os.path.join(ROOT, "BENCHMARK.json"), CELL)
    family = load_module("families", "cca_moe_serve")
    assert family.model_config(cfg, traffic).cca_taps == (2, 2)
    parent = dataclasses.make_dataclass(
        "TransformerConfig",
        [(f.name, f.type, f) for f in dataclasses.fields(TransformerConfig)
         if f.name not in NEW_FIELDS], frozen=True,
        namespace={"from_dict": classmethod(
            TransformerConfig.from_dict.__func__)})
    monkeypatch.setattr(family, "TransformerConfig", parent)
    with pytest.raises(ValueError, match=r"no field \['cca_taps', "
                       r"'moe_router_dim', 'residual_scaling', "
                       r"'rotary_fraction'\]"):
        family.model_config(cfg, traffic)


@pytest.fixture(scope="module")
def walked(tmp_path_factory):
    """The file's one walk of the tiny cell (``tests/_rehearse.py``)."""
    return walk(tmp_path_factory.mktemp("walk"), "tiny-zaya", TINY, TRAFFIC,
                CELL)


def test_a_tiny_cell_walks_serving_py_on_the_cpu(walked):
    result, _, stdout = walked
    assert result["correct"], stdout[-3000:]
    assert result["failed"] == 0 and result["attempted"] >= 5
    names = set(result["metrics"])
    # the program's counter; device metrics are never made up from a CPU trace
    assert "moe_experts_touched_share.srv" in names
    assert 25.0 < result["metrics"]["moe_experts_touched_share.srv"][
        "value"] <= 75.0        # 1-3 live slots over 4 experts
    assert not (NEW - {"moe_experts_touched_share.srv"}) & names
    assert "device_idle.srv" not in names
    assert "kv_live_share.srv" in names
    assert "family=cca_moe_serve" in stdout
    moe = json.loads(stdout.split("moe: ")[1].splitlines()[0])
    assert (moe["experts"], moe["experts_held"], moe["experts_per_token"]) \
        == (4, 4, 1)
    assert moe["router"] == {"mlp_width": 16, "state_carried": True}
    # every expert held: every routed pair is held; its buckets of 16 to 64
    # rows are under a bucket's (512 pairs): ragged_dot's, no tile counted
    assert moe["held_pairs"] == moe["pairs"] > 0 and moe["tile_rows"] == 0
    cca = json.loads(stdout.split("cca: ")[1].splitlines()[0])
    assert (cca["heads"], cca["kv_heads"], cca["head_dim"], cca["taps"]) \
        == (4, 2, 8, [2, 2])
    assert (cca["query_width"], cca["key_width"], cca["channels"]) == (
        32, 16, 48)
    # K and V rows of 2 x 8 in bfloat16; a tail of 2 x 48 + 8 float32 values
    assert cca["bytes_per_token_and_layer"] == 64
    assert cca["tail_bytes_per_layer_and_slot"] == 416
    assert cca["cache"] == {"bytes_per_token": 192,
                            "tail_bytes_per_slot": 1248,
                            "pool_bytes": 3 * 1248 + 192 * 3 * 128}
    assert cca["prefill_by_bucket"]["64"] == {
        "attention": "dense", "row_blocks": 0, "feed_forward_chunks": 1}
    assert "kv: bytes_per_token=192 " in stdout
    gap, limit = result["compared"]["served_token_gap_below_reference_best"]
    assert gap < limit
    checks = json.loads(stdout.split("checks=")[1].splitlines()[0])
    assert checks[0]["requests"] == 4 and checks[0]["longest"] > 32
    # no logits came to the host: a fetch is the pair counts alone
    host = json.loads(stdout.split("serve_host: ")[1].splitlines()[0])
    assert host["decode_calls"] > 0


def test_a_tiny_cells_prefills_run_the_grouped_kernel_and_say_so(capsys):
    """The tiny cell given a 512 bucket, its feed-forward in one chunk (PR
    53: over a served bucket's rows a prefill whose layers hold every expert
    multiplies them in ``hvd_moe_grouped``'s tiles): its ``hvd_srv_prefill``
    spans carry ``moe_tile_rows``, whole tiles that hold every live pair,
    the 64 bucket's and every decode span none, and the ``moe:`` line sums
    them."""
    from horovod_tpu.utils import profiling

    family = load_module("families", "cca_moe_serve")
    traffic = dict(TRAFFIC, prefill_buckets=[64, 512], max_seq_len=640)
    began = time.perf_counter()     # the ring is the process's: this test's
    served = family.serve(dict(TINY, feed_forward_chunk=512), traffic, 1, 5)
    served.warm()           # every bucket twice, then every slot decoding
    calls = {name: [r.fields for r in profiling.spans()
                    if r.name == name and r.start >= began]
             for name in (profiling.SRV_PREFILL, profiling.SRV_DECODE)}
    long = [f for f in calls[profiling.SRV_PREFILL] if f["bucket"] == 512]
    short = [f for f in calls[profiling.SRV_PREFILL] if f["bucket"] < 512]
    assert len(long) == 2 and len(short) >= 2 and calls[profiling.SRV_DECODE]
    for f in long:          # 3 layers, 4 experts, tiles of 128
        assert f["moe_held"] == 3 * f["length"] and f["moe_rows"] == 3 * 512
        assert f["moe_tile_rows"] % 128 == 0
        assert f["moe_held"] <= f["moe_tile_rows"] <= 3 * (512 + 4 * 128)
    assert not any("moe_tile_rows" in f
                   for f in short + calls[profiling.SRV_DECODE])
    # (the summary is over the process's ring: every engine's, those of the
    # tests that ran before this one in its worker too)
    summary = served.engine.span_summary()
    assert summary[profiling.SRV_PREFILL]["moe"]["tile_rows"] \
        >= sum(f["moe_tile_rows"] for f in long)
    served.release()
    moe = json.loads(capsys.readouterr().out.split("moe: ")[1].splitlines()[0])
    assert moe["tile_rows"] == sum(f["moe_tile_rows"] for f in long)
    assert moe["held_pairs"] == moe["pairs"] > 2 * 3 * 500


def test_an_altered_served_token_is_not_correct(walked):
    assert_the_altered_record_is_not_correct(walked)


@pytest.fixture(scope="module")
def control_family():
    """The family loaded once for the control's three seeds: its reference's
    programs (``_PROGRAMS``: a layer, the head, a dtype each) are keyed on
    shapes and numbers and not on the seed, whose weights are arguments, so
    the second and third seeds compile nothing."""
    return load_module("families", "cca_moe_serve")


@pytest.mark.parametrize("seed", [1, 2, 2**31 + 6])
def test_the_float8_control_fails_the_comparison(seed, control_family):
    """The reference with float8 operands put in the program's place and
    judged by the run's own comparison and limit is not correct; the
    reference's own first choices, judged the same way, are (gap 0).  The
    toy is given 12 layers: float8's error compounds with depth."""
    import jax.numpy as jnp
    import numpy as np

    family = control_family
    cfg = dict(TINY, num_hidden_layers=12, layer_types=["hybrid"] * 12)
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
