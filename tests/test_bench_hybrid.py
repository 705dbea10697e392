"""The benchmark's side of the Mamba-2 / attention hybrid (PR 33):
``families/hybrid_lm.py`` through ``hvd.shard`` +
``DistributedOptimizer(optax.sgd(1.0))`` against ``reference/hybrid_lm.py``
at a tiny size (the functions the chip compares at published widths), the
same comparison failing under each of six faults in the program, the
``ssm_*`` readers on hand-made joins, the FLOP and byte counts, the manifest's
entries, and a ``--rehearse-on-cpu`` walk of a tiny ``hybrid_lm`` cell.
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
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import compare, flops_ssm, scopes  # noqa: E402
from benchmarks.run import load_module  # noqa: E402
from horovod_tpu.utils import profiling  # noqa: E402

TINY = {"family": "hybrid_lm", "attention_bias": False,
        "attention_multiplier": 0.015625, "embedding_multiplier": 12,
        "hidden_act": "silu", "hidden_size": 64, "intermediate_size": 128,
        "shared_intermediate_size": 128,
        "layer_types": ["mamba", "attention", "mamba"], "logits_scaling": 8,
        "mamba_chunk_size": 16, "mamba_conv_bias": True, "mamba_d_conv": 4,
        "mamba_d_head": 16, "mamba_d_state": 32, "mamba_expand": 2,
        "mamba_n_groups": 1, "mamba_n_heads": 8, "mamba_proj_bias": False,
        "max_position_embeddings": 512, "normalization_function": "rmsnorm",
        "num_attention_heads": 4, "num_key_value_heads": 2,
        "num_hidden_layers": 3, "num_local_experts": 0,
        "position_embedding_type": "nope", "residual_multiplier": 0.22,
        "rms_norm_eps": 1e-05, "tie_word_embeddings": True, "vocab_size": 256}
TRAFFIC = {"why": "rehearsal", "unit": "tokens", "seq_len": 104,
           "per_chip": 1, "remat": True,
           "optimizer": {"name": "adamw", "learning_rate": 0.003},
           "stream": {"kind": "markov_zipf_tokens", "pool_batches": 4,
                      "zipf_a": 1.1, "follow_prob": 0.5, "max_run": 8},
           "expect_loss_to_fall": True, "compare_seq_len": 56,
           "compare_last": 16}


@pytest.fixture(scope="module")
def hvd():
    import horovod_tpu as hvd

    hvd.init()
    yield hvd
    hvd.shutdown()


@pytest.fixture(scope="module")
def family():
    return load_module("families", "hybrid_lm")


def in_float32(family, monkeypatch, **changed):
    """The family's model in float32 with dense attention, where program
    and reference differ by summation order alone; ``changed`` are fields
    of the program's configuration set wrong on purpose."""
    bf16_config = family.model_config

    def f32_config(cfg, traffic):
        return dataclasses.replace(
            bf16_config(cfg, traffic), dtype=jnp.float32,
            logits_dtype=jnp.float32, attention_fn=None, **changed)

    monkeypatch.setattr(family, "model_config", f32_config)


def checks_of(hvd, family):
    built = family.build(TINY, TRAFFIC, hvd.num_chips(), 2**31 + 11)
    with jax.default_matmul_precision("highest"):
        return built, {c["name"]: c
                       for c in built.compare(built.init_model())}


def test_the_family_agrees_with_the_reference_in_float32(hvd, family,
                                                         monkeypatch, capsys):
    """Loss, every gradient leaf as ``DistributedOptimizer`` hands it on (one
    sequence a device, averaged; 56 tokens are three and a half of the
    program's chunks, the reference's quadratic form has none), and the
    logits of the last positions against the sequential recurrence."""
    in_float32(family, monkeypatch)
    built, checks = checks_of(hvd, family)
    assert set(checks) == {"loss", "grads_from_distributed_optimizer",
                           "logits_last16_of_104"}
    assert checks["loss"]["error"] < 1e-5
    assert checks["grads_from_distributed_optimizer"]["error"] < 2e-4, checks
    assert checks["logits_last16_of_104"]["error"] < 1e-4
    assert built.flash_calls == [dict(b=1, h=4, s=104, d=16, causal=True)]
    assert built.notes["ssd_scan_flops_per_step_a_chip"] == \
        flops_ssm.ssd_scan_step_flops(TINY, 104, remat=True)
    # the program's counter, on every run's output
    ssm = json.loads(capsys.readouterr().out.split("ssm: ")[1].splitlines()[0])
    assert ssm == {"layers": {"attention": 1, "mamba": 2}, "chunk": 16,
                   "chunks_per_sequence": 7,
                   "carried_state_bytes_per_layer_and_sequence": 8 * 16 * 32 * 4,
                   "scan": "xla", "conv": "xla"}


def test_the_family_as_the_chip_runs_it_passes_its_tolerances(hvd, family):
    """bf16 with the flash kernels (interpreted here): grouped K and V, the
    caller's scale and no rotary embedding through ``flash_attention``."""
    built = family.build(TINY, TRAFFIC, hvd.num_chips(), 2**31 + 11)
    checks = built.compare(built.init_model())
    assert all(c["ok"] for c in checks), checks


def drop_d_skip(monkeypatch):
    from horovod_tpu.models import mamba

    scan = mamba.ssd_scan
    monkeypatch.setattr(mamba, "ssd_scan", lambda x, dt, a, b, c, d, chunk:
                        scan(x, dt, a, b, c, 0.0 * d, chunk))


def drop_conv_bias(monkeypatch):
    """In the ``jnp`` form, which the tiny widths run."""
    from horovod_tpu.ops import causal_conv as ops

    conv = ops.causal_conv
    monkeypatch.setattr(ops, "causal_conv", lambda x, kernel, bias:
                        conv(x, kernel, 0.0 * bias))


def misgroup_kv_heads(monkeypatch):
    """Query head j reads KV head j % KV where it should read j // group."""
    from horovod_tpu.models import transformer

    monkeypatch.setattr(
        transformer, "repeat_kv_heads", lambda x, heads:
        jnp.tile(x, (1, 1, heads // x.shape[2], 1)))


FAULTS = {
    "the D skip dropped": (drop_d_skip, {}),
    "the conv bias dropped": (drop_conv_bias, {}),
    "residual_multiplier 1": (None, {"residual_multiplier": 1.0}),
    "softmax scale d^-1/2": (None, {"attention_scale": None}),
    "rope applied": (None, {"rotary": True}),
    "K/V heads mis-grouped": (misgroup_kv_heads, {}),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_the_comparison_fails_under_a_fault_in_the_program(hvd, family,
                                                           monkeypatch, fault):
    patch, changed = FAULTS[fault]
    in_float32(family, monkeypatch, **changed)
    if patch is not None:
        patch(monkeypatch)
    _, checks = checks_of(hvd, family)
    failed = [name for name, c in checks.items() if not c["ok"]]
    assert failed, (fault, checks)
    # a gradient leaf judged alone is what catches a dropped term
    assert "grads_from_distributed_optimizer" in failed, (fault, checks)


def test_the_references_two_recurrences_agree():
    """The quadratic form (loss and gradients) and the sequential recurrence
    (long-context logits) are one function computed two ways."""
    reference = load_module("reference", "hybrid_lm")
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (40, 8, 16))
    dt = jax.nn.softplus(jax.random.normal(jax.random.fold_in(key, 1),
                                           (40, 8)) - 2.0)
    a = -jnp.exp(jax.random.uniform(jax.random.fold_in(key, 2), (8,)) * 2.7)
    b, c = (jax.random.normal(jax.random.fold_in(key, i), (40, 8, 32))
            for i in (3, 4))
    with jax.default_matmul_precision("highest"):
        quadratic = reference.ssm_quadratic(x, dt, a, b, c)
        sequential = reference.ssm_sequential(x, dt, a, b, c)
    assert float(compare.relative_l2(quadratic, sequential)) < 1e-5


def test_flops_count_each_layer_type():
    cfg = TINY
    e, f, v = 64, 128, 256
    mlp = 6.0 * 3 * e * f
    scan = flops_ssm.ssd_scan_forward_flops_per_token(cfg)
    # chunk 16: 8.5 pairs a position; one group of 32, eight heads of 16
    assert scan == 2 * 8.5 * (32 + 8 * 16) + 4.0 * 8 * 16 * 32
    mamba = 6.0 * (e * (2 * 128 + 2 * 32 + 8) + 128 * e) + 3 * scan + mlp
    attention = 6.0 * (2 * e * 4 * 16 + 2 * e * 2 * 16) + 6.0 * 104 * 64 + mlp
    assert flops_ssm.hybrid_lm_train_flops_per_token(cfg, 104) == \
        2 * mamba + attention + 6.0 * e * v
    assert 0 < flops_ssm.hybrid_lm_head_share(cfg, 104) < 1
    # a rematted step runs the scan's forward twice and its backward once
    assert flops_ssm.ssd_scan_step_flops(cfg, 104, remat=True) == \
        4 * 2 * 104 * scan
    assert flops_ssm.ssd_scan_step_flops(cfg, 104, remat=False) == \
        3 * 2 * 104 * scan
    one = (8 * 16 + 2 * 32) * 2 + 8 * 4           # a token's inputs, bytes
    y = 8 * 16 * 2
    assert flops_ssm.ssd_scan_step_bytes(cfg, 104, remat=True) == \
        2 * 104 * (2 * (one + y) + (one + y + one))
    # the published sizes: what PERF.md's roofline rests on
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "granite-4.0-h-micro.json")) as fh:
        real = json.load(fh)
    assert flops_ssm.hybrid_lm_train_flops_per_token(real, 8192) \
        == pytest.approx(4.8179e9, rel=1e-4)     # 1.606 G a token forward


def reader(stem):
    path = os.path.join(ROOT, "benchmarks", "metrics", f"{stem}.py")
    spec = importlib.util.spec_from_file_location(f"_metric_{stem}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


SSM_READERS = ("ssm_ms", "ssm_proj_ms", "ssm_conv_ms", "ssm_scan_ms",
               "ssm_gate_ms", "ssm_scan_roofline")


def joined_run(module_s, steps=2, kernel_s=None):
    kernel_s = kernel_s or {}
    pass_s: dict = {}
    for (kernel, _), v in kernel_s.items():
        pass_s[kernel] = pass_s.get(kernel, 0.0) + v
    j = scopes.Joined(chips=1, calls=steps, phase_s={}, module_s=module_s,
                      pass_s=pass_s, buckets={}, lead_s=0.0, tail_s=0.0,
                      joined_share=1.0, span_s={}, kernel_s=kernel_s)
    built = types.SimpleNamespace(
        steps_per_call=1, flash_calls=[{}],
        notes={"ssd_scan_flops_per_step_a_chip": 197e12 * 4e-3,
               "ssd_scan_bytes_per_step_a_chip": 819e9 * 10e-3})
    return types.SimpleNamespace(
        _scopes=j, trace=object(), built=built, chips=1, traced_steps=steps,
        config={}, peaks={"bf16_flops_per_s": 197e12,
                          "hbm_bytes_per_s": 819e9})


def test_the_ssm_readers_split_the_mixers_time_by_the_programs_names(capsys):
    mix = "Transformer/layer_N/mamba"
    run = joined_run({
        f"{mix}/{profiling.SSM_PROJ}/in_proj": 30e-3,
        f"{mix}/{profiling.SSM_PROJ}/out_proj": 10e-3,
        f"{mix}/{profiling.SSM_CONV}": 8e-3,
        f"{mix}/{profiling.SSM_SCAN}": 50e-3,
        f"{mix}/{profiling.SSM_SCAN}/bcghqs,bcsghp->bcqghp": 30e-3,
        f"{mix}/{profiling.SSM_GATE}": 4e-3,
        f"{mix}/{profiling.SSM_GATE}/norm": 2e-3,
        f"{mix}": 1e-3,                     # under the mixer, under no name
        "Transformer/layer_N/mamba_norm": 5e-3,     # the layer's, not its
        "Transformer/layer_N/mlp/up": 70e-3,
        "Transformer/layer_N/attn/q": 9e-3})
    proj, conv, scan, gate = (reader(f"ssm_{s}_ms").read(run)
                              for s in ("proj", "conv", "scan", "gate"))
    assert (proj, conv, scan, gate) == pytest.approx((20.0, 4.0, 40.0, 3.0))
    assert reader("ssm_ms").read(run) == pytest.approx(
        proj + conv + scan + gate + 0.5)
    assert "elsewhere=0.500" in capsys.readouterr().out
    # least max(4 ms of FLOPs, 10 ms of bytes) over 40 ms
    assert reader("ssm_scan_roofline").read(run) == pytest.approx(25.0)
    assert "bound_by=bytes" in capsys.readouterr().out


def test_the_convs_kernels_are_counted_under_the_convs_scope_alone():
    """``hvd_causal_conv_fwd`` / ``_bwd`` are launched under
    ``hvd_ssm_conv``: their time is ``ssm_conv_ms``'s (beside what XLA still
    does there) and no other part's; the scan's kernels stay the scan's."""
    mix = "Transformer/layer_N/mamba"
    conv = f"{mix}/{profiling.SSM_CONV}"
    scan = f"{mix}/{profiling.SSM_SCAN}"
    run = joined_run(
        {f"{mix}/{profiling.SSM_PROJ}/in_proj": 30e-3,
         f"{conv}": 1e-3,                   # the taps' slices, XLA's
         f"{scan}": 6e-3,
         f"{mix}/{profiling.SSM_GATE}": 4e-3},
        kernel_s={
            (profiling.CAUSAL_CONV_FWD,
             f"{conv}/{profiling.CAUSAL_CONV_FWD}"): 8e-3,
            (profiling.CAUSAL_CONV_BWD,
             f"{conv}/{profiling.CAUSAL_CONV_BWD}"): 7e-3,
            (profiling.SSD_FWD, f"{scan}/{profiling.SSD_FWD}"): 10e-3,
            (profiling.SSD_BWD, f"{scan}/{profiling.SSD_BWD}"): 20e-3})
    assert run._scopes.kernel_module_s[
        f"{conv}/{profiling.CAUSAL_CONV_FWD}"] == pytest.approx(8e-3)
    proj, conv_ms, scan_ms, gate = (reader(f"ssm_{s}_ms").read(run)
                                    for s in ("proj", "conv", "scan", "gate"))
    assert conv_ms == pytest.approx((1.0 + 8.0 + 7.0) / 2)
    assert (proj, scan_ms, gate) == pytest.approx((15.0, 18.0, 2.0))
    assert reader("ssm_ms").read(run) == pytest.approx(
        proj + conv_ms + scan_ms + gate)
    # the conv's kernels are no flash pass and no part of the scan's roofline
    assert "hvd_flash" not in profiling.CAUSAL_CONV_FWD
    assert reader("ssm_scan_roofline").read(run) == pytest.approx(
        100.0 * 10.0 / 18.0)


@pytest.mark.parametrize("stem", SSM_READERS)
def test_an_ssm_reader_reports_nothing_where_there_is_nothing_to_read(
        stem, monkeypatch):
    """An untraced run or a rehearsal (no join), and the parent commit (a
    join, but a program without the names): None, never a raise."""
    no_join = joined_run({})
    no_join._scopes = None
    assert reader(stem).read(no_join) is None
    run = joined_run({"Transformer/layer_N/mlp/up": 1e-3})
    monkeypatch.delattr(profiling, "SSM_SCAN")
    assert reader(stem).read(run) is None


def test_the_manifest_holds_the_cell_and_its_metrics():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    # by name: a later cell, configuration or metric goes after these
    cell = next(c for c in manifest["workloads"]
                if c["name"] == "granite4hm-s8192")
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "granite-4.0-h-micro", "hybrid-pretrain-1x8192", 1)
    entry = next(c for c in manifest["configs"]
                 if c["name"] == cell["config"])
    assert entry["reduced"] == ["num_hidden_layers", "layer_types",
                                "vocab_size"]
    with open(os.path.join(ROOT, entry["file"])) as f:
        cfg = json.load(f)
    published = {
        "hidden_size": 2048, "intermediate_size": 8192,
        "shared_intermediate_size": 8192, "num_attention_heads": 32,
        "num_key_value_heads": 8, "mamba_n_heads": 64, "mamba_d_head": 64,
        "mamba_d_state": 128, "mamba_n_groups": 1, "mamba_d_conv": 4,
        "mamba_chunk_size": 256, "mamba_expand": 2,
        "attention_multiplier": 0.015625, "embedding_multiplier": 12,
        "residual_multiplier": 0.22, "logits_scaling": 8,
        "rms_norm_eps": 1e-05, "tie_word_embeddings": True,
        "position_embedding_type": "nope",
        "max_position_embeddings": 131072}
    assert {k: cfg[k] for k in published} == published
    assert cfg["num_hidden_layers"] == 10 and cfg["vocab_size"] == 100352 // 8
    assert cfg["layer_types"] == ["mamba"] * 5 + ["attention"] + ["mamba"] * 4
    assert list(cfg["reduced"]) == entry["reduced"]
    with open(os.path.join(ROOT, "benchmarks", "traffic",
                           cell["traffic"] + ".json")) as f:
        traffic = json.load(f)
    assert {k: traffic[k] for k in (
        "seq_len", "per_chip", "remat", "compare_seq_len", "compare_last",
        "expect_loss_to_fall")} == {
        "seq_len": 8192, "per_chip": 1, "remat": True,
        "compare_seq_len": 1024, "compare_last": 256,
        "expect_loss_to_fall": True}
    assert traffic["optimizer"] == {"name": "adamw", "learning_rate": 0.0003}
    assert traffic["stream"] == {
        "kind": "markov_zipf_tokens", "pool_batches": 32, "zipf_a": 1.1,
        "follow_prob": 0.5, "max_run": 8}
    reported = {m["name"] for g in ("end_to_end", "per_layer")
                for m in manifest[g]
                if "workloads" not in m or cell["name"] in m["workloads"]}
    assert set(SSM_READERS) | {
        "tokens_per_s", "peak_hbm", "setup_s", "recompute_ms.lm",
        "flash_fwd_ms", "flash_bwd_ms", "attn_glue_ms.lm", "mfu.lm"} \
        <= reported
    assert not {"flash_ms", "flash_roofline", "flash_dq_ms", "flash_dkv_ms",
                "moe_ms", "img_per_s", "allreduce_ms"} & reported
    ssm = [m for m in manifest["per_layer"] if m["name"] in SSM_READERS]
    assert len(ssm) == 6
    for m in ssm:
        assert m["layer"] == "models (models/mamba.py)"
        assert m["workloads"] == [cell["name"]]


def test_a_tiny_hybrid_cell_walks_run_py_on_the_cpu(tmp_path):
    base = tmp_path / "manifest"
    (base / "configs").mkdir(parents=True)
    (base / "traffic").mkdir()
    (base / "configs" / "tiny-hybrid.json").write_text(json.dumps(TINY))
    (base / "traffic" / "tiny-hybrid-1x104.json").write_text(
        json.dumps(TRAFFIC))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        real = json.load(f)
    manifest = {
        "command": real["command"], "paths": ["."], "run_seconds": 2,
        "configs": [{"name": "tiny-hybrid", "source": "toy", "reduced": [],
                     "file": "configs/tiny-hybrid.json", "why": "rehearsal"}],
        "workloads": [{"name": "tiny-hybrid-1", "config": "tiny-hybrid",
                       "traffic": "tiny-hybrid-1x104", "chips": 1,
                       "why": "rehearsal"}],
        **{g: [{k: v for k, v in m.items() if k != "workloads"}
               for m in real[g] if not m["name"].startswith("moe_")]
           for g in ("end_to_end", "per_layer")}}
    (base / "BENCHMARK.json").write_text(json.dumps(manifest))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=1")
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
         "--manifest", str(base / "BENCHMARK.json"), "--workload",
         "tiny-hybrid-1", "--seed", str(2**31 + 7), "--seconds", "2",
         "--trace", "1", "--out", str(tmp_path / "out"),
         "--rehearse-on-cpu"],
        env=env, cwd=ROOT, capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-2000:]
    last = proc.stdout.strip().splitlines()[-1]
    marker = "REHEARSAL on cpu, no result: "
    assert last.startswith(marker), last
    result = json.loads(last[len(marker):])
    assert result["failed"] == 0 and result["attempted"] >= 5
    names = set(result["metrics"])
    assert "dispatch_ms.lm" in names
    # device metrics are never made up from a CPU trace
    assert not names & set(SSM_READERS) | {"mfu.lm"} & names
    assert "family=hybrid_lm" in proc.stdout and "ssm: {" in proc.stdout
    checks = json.loads(proc.stdout.split("checks=")[1].splitlines()[0])
    assert [c["name"] for c in checks] == [
        "loss", "grads_from_distributed_optimizer", "logits_last16_of_104"]
    assert all(c["ok"] for c in checks), checks
    assert "loss: first_segment=" in proc.stdout and "fell=True" in proc.stdout


def test_a_float8_product_is_another_result():
    """What sets the tolerances' upper reading: the reference with every
    product's operands rounded to an 8-bit float lands far outside what
    bf16 reads (PERF.md has the chip's numbers at the published widths)."""
    reference = load_module("reference", "hybrid_lm")
    key = jax.random.PRNGKey(0)
    rnd = lambda i, *shape: 0.1 * jax.random.normal(  # noqa: E731
        jax.random.fold_in(key, i), shape)
    mlp = {"post_attention_layernorm": jnp.ones(64),
           "gate_proj": rnd(1, 64, 128), "up_proj": rnd(2, 64, 128),
           "down_proj": rnd(3, 128, 64), "input_layernorm": jnp.ones(64)}
    mamba = dict(mlp, in_proj=rnd(4, 64, 328), conv_weight=rnd(5, 4, 192),
                 conv_bias=rnd(6, 192), dt_bias=rnd(7, 8) - 3.0,
                 A_log=jnp.log(jnp.linspace(1.0, 8.0, 8)), D=jnp.ones(8),
                 mamba_norm=jnp.ones(128), out_proj=rnd(8, 128, 64))
    attention = dict(mlp, q_proj=rnd(9, 64, 64), k_proj=rnd(10, 64, 32),
                     v_proj=rnd(11, 64, 32), o_proj=rnd(12, 64, 64))
    params = {"embed_tokens": rnd(13, 256, 64), "norm": jnp.ones(64),
              "layers": [mamba, attention, mamba]}
    tokens = jax.random.randint(key, (48,), 0, 256)
    # each form traced once and run as one program: eagerly every product
    # of the reference and of its gradient is a program of its own
    under = lambda **kw: jax.jit(lambda p, t: reference.loss_and_grads(  # noqa: E731
        p, t, TINY, **kw))(params, tokens)
    loss, grads = under()
    for low in (jnp.bfloat16, jnp.float8_e4m3fn):
        lo_loss, lo_grads = under(operand_dtype=low)
        worst = compare.check_tree("g", lo_grads, grads, 1.0)["error"]
        if low == jnp.bfloat16:
            assert worst < 0.05
        else:
            assert worst > 0.15, worst
    whole = reference.logits_last(params, tokens, TINY, last=8)
    blocked = reference.logits_last(params, tokens, TINY, last=8,
                                    query_block=16)
    assert float(compare.relative_l2(blocked, whole)) < 1e-5
