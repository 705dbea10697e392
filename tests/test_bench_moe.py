"""The benchmark's side of the sparse decoder (PR 26): ``families/moe_lm.py``
through ``hvd.shard`` + ``DistributedOptimizer(optax.sgd(1.0))`` against
``reference/moe_lm.py`` at a tiny size in float32 (the functions the chip
compares at published widths), the ``moe_*`` readers on hand-made joins, the
FLOP counts, and a ``--rehearse-on-cpu`` walk of a tiny ``moe_lm`` cell.
Here, and not under ``benchmarks/tests``, so that the tier-1 run holds them."""

import dataclasses
import importlib.util
import json
import os
import subprocess
import sys
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import compare, flops, flops_moe, scopes  # noqa: E402
from benchmarks.run import load_module  # noqa: E402
from horovod_tpu.utils import profiling  # noqa: E402

TINY = {"family": "moe_lm", "hidden_size": 64, "intermediate_size": 32,
        "num_attention_heads": 4, "num_key_value_heads": 4,
        "num_hidden_layers": 2, "max_position_embeddings": 512,
        "rms_norm_eps": 1e-05, "rope_theta": 10000, "rope_scaling": None,
        "tie_word_embeddings": False, "vocab_size": 256, "num_experts": 8,
        "num_experts_per_tok": 2, "norm_topk_prob": False,
        "assumed": {"router_aux_loss_coef": 0.01,
                    "router_z_loss_coef": 0.001}}
TRAFFIC = {"why": "rehearsal", "unit": "tokens", "seq_len": 256,
           "per_chip": 2, "remat": False,
           "optimizer": {"name": "adamw", "learning_rate": 0.003},
           "stream": {"kind": "markov_zipf_tokens", "pool_batches": 4,
                      "zipf_a": 1.1, "follow_prob": 0.5, "max_run": 8},
           "expect_loss_to_fall": True, "compare_seq_len": 128,
           "compare_last": 32}


@pytest.fixture(scope="module")
def hvd():
    import horovod_tpu as hvd

    hvd.init()
    yield hvd
    hvd.shutdown()


@pytest.fixture(scope="module")
def family():
    return load_module("families", "moe_lm")


def test_the_family_agrees_with_the_reference_in_float32(hvd, family,
                                                         monkeypatch):
    """What the chip compares at published widths in bf16, compared here in
    float32 with dense attention, where the two sides differ by summation
    order alone: loss, every gradient leaf as ``DistributedOptimizer`` hands
    it on (one sequence a device, averaged), the routing, and the logits of
    the last positions against the reference in blocks."""
    bf16_config = family.model_config

    def f32_config(cfg, traffic):
        return dataclasses.replace(bf16_config(cfg, traffic),
                                   dtype=jnp.float32,
                                   logits_dtype=jnp.float32,
                                   attention_fn=None)

    monkeypatch.setattr(family, "model_config", f32_config)
    chips = hvd.num_chips()
    built = family.build(TINY, TRAFFIC, chips, 2**31 + 11)
    with jax.default_matmul_precision("highest"):
        checks = {c["name"]: c for c in built.compare(built.init_model())}
    assert set(checks) == {
        "routing_picks_not_among_the_references",
        "routing_disagreement_log_prob_gap", "loss",
        "grads_from_distributed_optimizer",
        "grads_with_ties_settled_the_programs_way", "logits_last32_of_256"}
    assert checks["routing_picks_not_among_the_references"]["error"] == 0.0
    assert checks["routing_disagreement_log_prob_gap"]["error"] == 0.0
    assert checks["loss"]["error"] < 1e-5
    assert checks["grads_from_distributed_optimizer"]["error"] < 1e-4, checks
    assert checks["grads_with_ties_settled_the_programs_way"]["error"] < 1e-4
    assert checks["logits_last32_of_256"]["error"] < 1e-5
    load = built.notes["expert_load"]
    assert load["pairs"] == 2 * 256 * 2 and load["max_over_mean"] >= 1.0
    assert built.notes["pairs_per_step_a_chip"] == 2 * 256 * 2
    assert built.flash_calls == [dict(b=2, h=4, s=256, d=16, causal=True)] * 2


def test_a_step_through_the_grouped_kernels_passes_the_familys_comparison(
        hvd, family):
    """The small OLMoE configuration as the chip runs it (bfloat16, the
    family's own tolerances), a sequence of 256 tokens a device: 512 pairs a
    layer, so the expert products and their backward are ``hvd_moe_grouped``'s
    (PR 55; interpreted here) and no ``ragged_dot`` is left in the step.
    The compiled step's scope table files what the kernels do, forward and
    backward, under the layer's module path inside ``hvd_moe_experts``,
    where ``moe_experts_ms`` reads it."""
    chips = hvd.num_chips()
    traffic = {**TRAFFIC, "per_chip": 1, "compare_seq_len": 256}
    built = family.build(TINY, traffic, chips, 2**31 + 13)
    params = built.init_model()
    checks = {c["name"]: c for c in built.compare(params)}
    # (a model 64 wide rounds its router's input too coarsely in bfloat16
    # for the tie check, kernels or none: PR 54's tree reads the same 1.108)
    gap = checks.pop("routing_disagreement_log_prob_gap")
    assert len(checks) == 4 and all(c["ok"] for c in checks.values()), checks
    assert gap["error"] == pytest.approx(1.1079, abs=1e-3)
    assert built.notes["expert_load"]["pairs"] == 256 * 2
    state = built.init_train(params)
    tokens = jax.device_put(built.pool[0][0], built.batch_shardings[0])
    lowered = built.step.lower(state, tokens)
    assert "ragged_dot" not in lowered.as_text()
    table = profiling.scope_table(lowered.compile())
    ours = [s for s in table.values() if profiling.MOE_GROUPED in s.op_name]
    assert {s.phase for s in ours} >= {"forward", "backward"}
    assert {s.module for s in ours} == {
        f"Transformer/layer_N/moe_mlp/{profiling.MOE_EXPERTS}/"
        f"{profiling.MOE_GROUPED}"}


def test_the_reference_blocked_is_the_reference_unblocked(family):
    reference = load_module("reference", "moe_lm")
    cfg = family.reference_config(TINY)
    key = jax.random.PRNGKey(0)
    rnd = lambda i, *shape: 0.1 * jax.random.normal(  # noqa: E731
        jax.random.fold_in(key, i), shape)
    layer = {"input_layernorm": jnp.ones(64), "q_proj": rnd(1, 64, 64),
             "q_norm": jnp.ones(64), "k_proj": rnd(2, 64, 64),
             "k_norm": jnp.ones(64), "v_proj": rnd(3, 64, 64),
             "o_proj": rnd(4, 64, 64), "post_attention_layernorm":
             jnp.ones(64), "router": rnd(5, 64, 8),
             "gate_proj": rnd(6, 8, 64, 32), "up_proj": rnd(7, 8, 64, 32),
             "down_proj": rnd(8, 8, 32, 64)}
    params = {"embed_tokens": rnd(9, 256, 64), "layers": [layer, layer],
              "norm": jnp.ones(64), "lm_head": rnd(10, 64, 256)}
    tokens = jax.random.randint(key, (96,), 0, 256)
    whole = reference.logits_last(params, tokens, cfg, last=16)
    blocked = reference.logits_last(params, tokens, cfg, last=16,
                                    query_block=32, token_block=24)
    assert float(compare.relative_l2(blocked, whole)) < 1e-5
    # the picks it is handed replace its own choice and nothing else
    (loss, terms), _ = reference.loss_and_grads(params, tokens, cfg)
    own = [r["picks"] for r in terms["routing"]]
    (same, _), _ = reference.loss_and_grads(params, tokens, cfg, picks=own)
    assert float(same) == pytest.approx(float(loss), rel=1e-6)
    assert float(loss) == pytest.approx(
        float(terms["cross_entropy"] + 0.01 * terms["load_balance"]
              + 0.001 * terms["router_z"]), rel=1e-6)
    # a float8 product is another result, far outside any tolerance here
    (low, _), _ = jax.jit(lambda p, t, own: reference.loss_and_grads(
        p, t, cfg, picks=own, operand_dtype=jnp.float8_e4m3fn))(
        params, tokens, own)
    assert abs(float(low) - float(loss)) / float(loss) > 1e-4


def test_flops_count_the_experts_a_token_visits():
    dense_like = dict(TINY, num_experts_per_tok=1, num_experts=0,
                      intermediate_size=32)
    # one expert a token and no router is a dense decoder of that width
    assert flops_moe.moe_lm_train_flops_per_token(dense_like, 256) == \
        flops.decoder_lm_train_flops_per_token(dense_like, 256)
    one, two = (flops_moe.moe_lm_train_flops_per_token(
        dict(TINY, num_experts_per_tok=k), 256) for k in (1, 2))
    assert two - one == 6.0 * 2 * 3 * 64 * 32       # a layer, 2 layers
    assert flops_moe.grouped_matmul_train_flops(1024, 64, 32) == \
        6.0 * 1024 * 3 * 64 * 32
    more = flops_moe.grouped_matmul_train_bytes(2048, 8, 64, 32)
    assert more > flops_moe.grouped_matmul_train_bytes(1024, 8, 64, 32) > 0
    assert 0.0 < flops_moe.moe_lm_head_share(TINY, 256) < 1.0


def reader(stem):
    path = os.path.join(ROOT, "benchmarks", "metrics", f"{stem}.py")
    spec = importlib.util.spec_from_file_location(f"_metric_{stem}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


MOE_READERS = ("moe_ms", "moe_route_ms", "moe_dispatch_ms", "moe_experts_ms",
               "moe_experts_roofline")


def joined_run(module_s, pass_s, steps=2):
    j = scopes.Joined(chips=1, calls=steps, phase_s={}, module_s=module_s,
                      pass_s=pass_s, buckets={}, lead_s=0.0, tail_s=0.0,
                      joined_share=1.0, span_s={})
    cfg = {"hidden_size": 2048, "intermediate_size": 1024, "num_experts": 64,
           "num_hidden_layers": 1}
    built = types.SimpleNamespace(
        steps_per_call=1, flash_calls=[{}],
        notes={"pairs_per_step_a_chip": 131072,
               "expert_load": {"pairs": 131072, "max_over_mean": 2.5,
                               "empty_experts": 0}})
    return types.SimpleNamespace(
        _scopes=j, trace=object(), built=built, chips=1, traced_steps=steps,
        config=cfg, peaks={"bf16_flops_per_s": 197e12,
                           "hbm_bytes_per_s": 819e9})


def test_the_moe_readers_split_the_layers_time_by_the_programs_names(capsys):
    lay = "Transformer/layer_N/moe_mlp"
    run = joined_run({
        f"{lay}/{profiling.MOE_ROUTE}": 4e-3,
        f"{lay}/{profiling.MOE_ROUTE}/top_k": 2e-3,
        f"{lay}/{profiling.MOE_DISPATCH}": 6e-3,
        f"{lay}/{profiling.MOE_EXPERTS}": 10e-3,
        f"{lay}/{profiling.MOE_COMBINE}": 8e-3,
        f"{lay}": 1e-3,                      # under the layer, under no name
        "Transformer/layer_N/attn/q": 50e-3,
        "Transformer/lm_head": 70e-3,
        profiling.OPTIMIZER: 30e-3},
        {profiling.MOE_EXPERTS: 90e-3, profiling.FLASH_FWD: 20e-3})
    route, dispatch, experts = (reader(s).read(run) for s in (
        "moe_route_ms", "moe_dispatch_ms", "moe_experts_ms"))
    assert route == pytest.approx(3.0)               # ms a step, 2 steps
    assert dispatch == pytest.approx(3.0 + 4.0 + 0.5)
    assert experts == pytest.approx(5.0 + 45.0)      # XLA's ops + the kernels
    assert reader("moe_ms").read(run) == pytest.approx(
        route + dispatch + experts)
    # 6 * 131072 * 3 * 2048 * 1024 operations at 197 T/s is 25.12 ms
    assert reader("moe_experts_roofline").read(run) == pytest.approx(
        100 * 25.1157 / 50.0, rel=1e-4)
    assert "bound_by=flops" in capsys.readouterr().out
    assert reader("moe_load_max_over_mean").read(run) == 2.5


@pytest.mark.parametrize("stem", MOE_READERS)
def test_a_moe_reader_reports_nothing_where_there_is_nothing_to_read(
        stem, monkeypatch):
    """An untraced run or a rehearsal (no join), and the parent commit (a
    join, but a program without the names): None, never a raise."""
    no_join = joined_run({}, {})
    no_join._scopes = None
    assert reader(stem).read(no_join) is None
    run = joined_run({"Transformer/layer_N/mlp/up": 1e-3}, {})
    monkeypatch.delattr(profiling, "MOE_EXPERTS")
    assert reader(stem).read(run) is None


def test_the_load_reader_reports_nothing_for_a_dense_family():
    run = joined_run({}, {})
    run.built.notes = {}
    assert reader("moe_load_max_over_mean").read(run) is None


def test_the_manifest_holds_the_cell_and_its_metrics():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    cell = next(c for c in manifest["workloads"] if c["name"] == "olmoe-s4096")
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "olmoe-1b-7b", "moe-pretrain-s4096", 1)
    entry = next(c for c in manifest["configs"] if c["name"] == cell["config"])
    assert entry["reduced"] == ["num_hidden_layers"]
    with open(os.path.join(ROOT, entry["file"])) as f:
        cfg = json.load(f)
    published = {"hidden_size": 2048, "intermediate_size": 1024,
                 "num_attention_heads": 16, "num_key_value_heads": 16,
                 "num_experts": 64, "num_experts_per_tok": 8,
                 "vocab_size": 50304, "max_position_embeddings": 4096,
                 "rms_norm_eps": 1e-05, "rope_theta": 10000,
                 "norm_topk_prob": False, "tie_word_embeddings": False}
    assert {k: cfg[k] for k in published} == published
    assert cfg["num_hidden_layers"] == 1 and list(cfg["reduced"]) == [
        "num_hidden_layers"]
    reported = {m["name"] for g in ("end_to_end", "per_layer")
                for m in manifest[g]
                if "workloads" not in m or "olmoe-s4096" in m["workloads"]}
    assert {"tokens_per_s", "peak_hbm", "setup_s", "moe_ms", "moe_route_ms",
            "moe_dispatch_ms", "moe_experts_ms", "moe_experts_roofline",
            "moe_load_max_over_mean", "flash_fwd_ms", "mfu.lm"} <= reported
    # the compiler's grouped-matmul kernels are Mosaic calls too, which
    # trace.kind_of counts as flash: the two sums of that kind stay out
    assert not {"flash_ms", "flash_roofline", "img_per_s"} & reported
    for m in manifest["per_layer"]:
        assert os.path.exists(os.path.join(
            ROOT, "benchmarks", "metrics", m["name"].split(".")[0] + ".py"))


def test_a_tiny_moe_cell_walks_run_py_on_the_cpu(tmp_path):
    base = tmp_path / "manifest"
    (base / "configs").mkdir(parents=True)
    (base / "traffic").mkdir()
    (base / "configs" / "tiny-moe.json").write_text(json.dumps(TINY))
    (base / "traffic" / "tiny-moe-2x256.json").write_text(json.dumps(TRAFFIC))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        real = json.load(f)
    manifest = {
        "command": real["command"], "paths": ["."], "run_seconds": 2,
        "configs": [{"name": "tiny-moe", "source": "toy", "reduced": [],
                     "file": "configs/tiny-moe.json", "why": "rehearsal"}],
        "workloads": [{"name": "tiny-moe-1", "config": "tiny-moe",
                       "traffic": "tiny-moe-2x256", "chips": 1,
                       "why": "rehearsal"}],
        **{g: [{k: v for k, v in m.items() if k != "workloads"}
               for m in real[g]] for g in ("end_to_end", "per_layer")}}
    (base / "BENCHMARK.json").write_text(json.dumps(manifest))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=1")
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
         "--manifest", str(base / "BENCHMARK.json"), "--workload",
         "tiny-moe-1", "--seed", str(2**31 + 7), "--seconds", "2", "--trace",
         "1", "--out", str(tmp_path / "out"), "--rehearse-on-cpu"],
        env=env, cwd=ROOT, capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-2000:]
    last = proc.stdout.strip().splitlines()[-1]
    marker = "REHEARSAL on cpu, no result: "
    assert last.startswith(marker), last
    result = json.loads(last[len(marker):])
    assert result["failed"] == 0 and result["attempted"] >= 5
    names = set(result["metrics"])
    assert {"moe_load_max_over_mean", "dispatch_ms.lm"} <= names
    # device metrics are never made up from a CPU trace
    assert not names & {"moe_ms", "moe_experts_ms", "moe_experts_roofline",
                        "flash_ms", "mfu.lm"}
    assert "family=moe_lm" in proc.stdout and "reference: " in proc.stdout
    checks = json.loads(proc.stdout.split("checks=")[1].splitlines()[0])
    assert [c["name"] for c in checks][:2] == [
        "routing_picks_not_among_the_references",
        "routing_disagreement_log_prob_gap"]
    assert "loss: first_segment=" in proc.stdout and "fell=True" in proc.stdout
