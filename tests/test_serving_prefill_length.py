"""A served prefill tells its attention kernels where the prompt ends
(PR 45): on small configurations of the four served kinds, a prompt of 5/8
of a flash bucket through ``TransformerBackend.prefill`` gives the first
token, the logits and the pool's rows below the length that the same prompt
gives with the length withheld from the kernel, and twenty decode steps
after it are finite and the same too; the ``hvd_srv_prefill`` span carries
``attn_rows`` and ``span_summary()`` sums it.  The kernels are interpreted
here, at their real 1024-row tiles: the buckets are long and the models
thin."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.models import Transformer, TransformerConfig
from horovod_tpu.models import transformer as T
from horovod_tpu.serving.engine import (ServingConfig, ServingEngine,
                                        TransformerBackend)
from horovod_tpu.utils import profiling

THIN = dict(vocab_size=32, embed_dim=32, mlp_dim=32, dtype=jnp.float32,
            param_dtype=jnp.float32, logits_dtype=jnp.float32)
# kind -> (configuration, bucket, the form its prefill reports)
KINDS = {
    "dense_mha": (TransformerConfig(
        num_layers=2, num_heads=2, head_dim=16, max_seq_len=4096, **THIN),
        4096, "flash"),
    "window_and_full_gqa_held_experts": (TransformerConfig(
        num_layers=2, num_heads=4, num_kv_heads=1, head_dim=16,
        max_seq_len=4096, layer_types=("sliding_attention", "full_attention"),
        sliding_window=1200, parallel_block=True, num_experts=4,
        experts_per_token=2, experts_held=(1, 3), moe_selection="sigmoid",
        num_shared_experts=1, **THIN), 4096, "flash"),
    "latent_attention": (TransformerConfig(
        num_layers=2, num_heads=2, max_seq_len=4096,
        layer_types=("latent_attention",) * 2, q_lora_rank=16,
        kv_lora_rank=8, qk_nope_head_dim=16, qk_rope_head_dim=8,
        v_head_dim=16, **THIN), 4096, "flash"),
    "eva_merged": (TransformerConfig(
        num_layers=2, num_heads=2, head_dim=16, max_seq_len=512,
        layer_types=("eva_attention",) * 2, eva_window=128, eva_chunk=16,
        **THIN), 512, "merged"),
}
STEPS = 20


def _serve(cfg, params, bucket, prompt):
    """(first token, its logits, the pool, the decoded tokens and their
    logits) of one prompt through a fresh backend's prefill and STEPS decode
    steps in slot 1 of 2."""
    backend = TransformerBackend(Transformer(cfg), params, cfg, 2,
                                 cfg.max_seq_len)
    padded = np.zeros((1, bucket), np.int32)
    padded[0, :len(prompt)] = prompt
    first, logits = backend.prefill(padded, len(prompt), 1)
    pool = (np.asarray(backend.kk), np.asarray(backend.vv))
    tokens, steps = [first], []
    lengths = np.array([0, len(prompt)], np.int32)
    for _ in range(STEPS):
        lengths[1] += 1
        nxt, step_logits = backend.decode(
            np.array([0, tokens[-1]], np.int32), lengths)
        tokens.append(int(nxt[1]))
        steps.append(np.asarray(step_logits[1]))
    return first, np.asarray(logits), pool, tokens, np.stack(steps), backend


@pytest.mark.parametrize("kind", list(KINDS))
def test_a_prefill_told_its_length_serves_what_it_served(kind, monkeypatch):
    cfg, bucket, form = KINDS[kind]
    # every bucket takes the kernels (the limits are byte counts of shapes
    # that the chip's configurations pass and these thin ones do not)
    monkeypatch.setattr(TransformerBackend, "FLASH_PREFILL_LOGITS_BYTES", -1)
    monkeypatch.setattr(T, "EVA_DENSE_LOGITS_BYTES", -1)
    model = Transformer(cfg)
    params = jax.jit(model.init)(jax.random.PRNGKey(0),
                                 jnp.zeros((1, 8), jnp.int32))
    length = bucket * 5 // 8
    prompt = [int(t) for t in np.random.RandomState(3).randint(
        1, cfg.vocab_size, length)]

    told = _serve(cfg, params, bucket, prompt)
    backend = told[-1]
    assert backend.prefill_attention(bucket) == form
    # 5/8 of the bucket ends inside a q block of 1024 (of 128, EVA's window):
    # that block is worked whole, the rest of the bucket not at all
    block = min(1024, cfg.eva_window or 1024)
    assert backend.prefill_attn_rows(bucket, length) \
        == -(-length // block) * block < bucket

    # the same, the length withheld from the kernels: the mixers tell their
    # attention function nothing, as before the lengths existed
    with monkeypatch.context() as m:
        m.setattr(T, "_prompt_end", lambda cfg, lengths: {})
        merged = T.eva_merged_attention
        m.setattr(T, "eva_merged_attention",
                  lambda *a, length=None, **kw: merged(*a, **kw))
        held = _serve(cfg, params, bucket, prompt)

    assert told[0] == held[0]
    np.testing.assert_array_equal(told[1], held[1])
    # the pool's rows below the length (a row a position; EVA's pool is a
    # ring then summaries: the ring's rows below the prompt's place in its
    # window, and the summaries of its whole chunks)
    if cfg.eva:
        w, c = cfg.eva_window, cfg.eva_chunk
        rows = np.r_[0:length % w, w:w + length // c]
    else:
        rows = np.r_[0:length]
    for got, want in zip(told[2], held[2]):
        assert np.isfinite(got).all()
        np.testing.assert_array_equal(got[:, 1, rows], want[:, 1, rows])
    assert told[3] == held[3]
    assert np.isfinite(told[4]).all()
    np.testing.assert_array_equal(told[4], held[4])


def test_the_prefill_span_carries_the_rows_the_kernels_worked(monkeypatch):
    cfg, bucket, _ = KINDS["dense_mha"]
    cfg = dataclasses.replace(cfg, num_layers=1)
    model = Transformer(cfg)
    params = jax.jit(model.init)(jax.random.PRNGKey(0),
                                 jnp.zeros((1, 8), jnp.int32))
    # the 2048 bucket's logits (4 * 2 * 2048**2 bytes) at the limit: dense;
    # the 4096 bucket's past it: the kernel
    monkeypatch.setattr(TransformerBackend, "FLASH_PREFILL_LOGITS_BYTES",
                        4 * 2 * 2048 ** 2)
    engine = ServingEngine(
        TransformerBackend(model, params, cfg, 1, 4096),
        ServingConfig(num_slots=1, buckets=(2048, 4096), max_seq_len=4096))
    mark = profiling.open_span("mark").id
    for n in (1500, 2100, 3500):
        engine.submit([1 + i % 31 for i in range(n)], 1)
        engine.run_until_idle()
    calls = [r.fields for r in profiling.spans()
             if r.id > mark and r.name == profiling.SRV_PREFILL]
    assert [c["attn"] for c in calls] == ["dense", "flash", "flash"]
    assert "attn_rows" not in calls[0]              # no kernel, nothing to say
    assert [c["attn_rows"] for c in calls[1:]] == [3072, 4096]
    by_form = ServingEngine.span_summary()[profiling.SRV_PREFILL]["attn"]
    assert by_form["flash"]["attn_rows"] >= 3072 + 4096
    assert by_form["flash"]["bucket_rows"] >= 2 * 4096
    assert "attn_rows" not in by_form["dense"]
