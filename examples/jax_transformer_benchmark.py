"""Transformer training throughput + MFU harness.

Companion to examples/jax_synthetic_benchmark.py (the ResNet harness that
mirrors reference examples/pytorch_synthetic_benchmark.py:14-107): synthetic
token data, full train step (fwd + bwd + adamw), hard-sync timing windows,
reports tokens/sec and model FLOPs utilization.

MFU accounting (PaLM appendix-B style): train FLOPs/token ≈ 6·N_params
+ 6·L·S·E for causal attention (12·L·S·E for full attention — the causal
mask halves the realized score/value matmul work).  Peak is the chip's
bf16 figure, looked up by ``device_kind`` (horovod_tpu/utils/chip.py); a
device that is not in that table is refused before anything is timed.

Run (real chip):   python examples/jax_transformer_benchmark.py
Long-context:      python examples/jax_transformer_benchmark.py \
                       --seq-len 32768 --batch 1 --layers 4
"""

from __future__ import annotations

import argparse
import functools
import json
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax

import horovod_tpu as hvd
from horovod_tpu.models import Transformer, TransformerConfig
from horovod_tpu.ops.flash_attention import make_flash_attention
from horovod_tpu.utils import chip


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--layers", type=int, default=12)
    ap.add_argument("--embed", type=int, default=768)
    # head_dim = embed/heads = 128 by default: the MXU contracts 128-wide,
    # so d=64 heads cap every attention matmul at half utilization —
    # measured 38.2% vs 56.7% MFU at S=8192 (docs/benchmarks.md).  Same
    # parameter count either way (the projections stay embed x embed).
    ap.add_argument("--heads", type=int, default=6)
    ap.add_argument("--seq-len", type=int, default=1024)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--vocab", type=int, default=32000)
    ap.add_argument("--num-warmup-batches", type=int, default=3)
    ap.add_argument("--num-iters", type=int, default=5)
    ap.add_argument("--num-batches-per-iter", type=int, default=5)
    ap.add_argument("--no-flash", action="store_true",
                    help="dense einsum attention (for comparison / to "
                         "demonstrate where it OOMs)")
    ap.add_argument("--block-q", type=int, default=1024,
                    help="q-side super tile (streamed in the backward; "
                         "2048 exceeds the 16 MiB VMEM scope at d128)")
    ap.add_argument("--block-k", type=int, default=None,
                    help="k-side super tile (streamed in the forward). "
                         "Default min(seq_len, 2048), matching the "
                         "library default (_default_block_k): the bigger "
                         "streaming tile measured 57.4->59.6%% MFU at "
                         "S=8192, and 4096 (explicit) 60.3%% but VMEM-"
                         "OOMs the S=32768 remat config (round 5; "
                         "pre-r5 rows used 1024)")
    ap.add_argument("--sub", type=int, default=1024,
                    help="in-kernel compute sub-tile")
    ap.add_argument("--remat", action="store_true",
                    help="rematerialize each block in backward "
                         "(jax.checkpoint) — required for very long S")
    ap.add_argument("--steps-per-call", type=int, default=8,
                    help="training steps per dispatched program (lax.scan "
                         "device loop — amortizes per-dispatch latency; "
                         "8 matches bench.py's BENCH_STEPS_PER_CALL "
                         "protocol, measured +0.4 MFU pts over 4)")
    ap.add_argument("--profile", default=None, metavar="DIR",
                    help="capture an XLA profiler trace of one timed "
                         "dispatch into DIR (view in XProf/TensorBoard; "
                         "rank 0 only — horovod_tpu.profiling.trace)")
    ap.add_argument("--accumulate", type=int, default=1,
                    help="gradient-accumulation microbatches per step "
                         "(hvd.accumulate_gradients — the reference's "
                         "backward_passes_per_step): raises tokens/step "
                         "past the per-chip batch memory ceiling; "
                         "--batch is the EFFECTIVE batch, activations "
                         "peak at batch/accumulate")
    ap.add_argument("--bf16-params", action="store_true",
                    help="keep parameters resident in bf16 with f32 master "
                         "weights inside the optimizer state (kills the "
                         "per-use f32->bf16 casts; adamw math stays f32)")
    args = ap.parse_args()
    if args.block_k is None:
        # The library default, resolved eagerly so the JSON record shows
        # the actual tile (incl. the d>128 -> 1024 safety branch).
        from horovod_tpu.ops.flash_attention import _default_block_k
        args.block_k = _default_block_k(args.seq_len,
                                        args.embed // args.heads)

    chip.enable_compile_cache()
    hvd.init()
    peak_flops = chip.peak_bf16_flops()
    cfg = dict(vocab_size=args.vocab, num_layers=args.layers,
               num_heads=args.heads, head_dim=args.embed // args.heads,
               embed_dim=args.embed, mlp_dim=4 * args.embed,
               max_seq_len=args.seq_len, dtype=jnp.bfloat16,
               remat=args.remat,
               param_dtype=(jnp.bfloat16 if args.bf16_params
                            else jnp.float32),
               # bf16 logits buffer (f32 softmax via the fused upcast below)
               logits_dtype=jnp.bfloat16)
    attn = None if args.no_flash else make_flash_attention(
        block_q=args.block_q, block_k=args.block_k, sub=args.sub)
    model = Transformer(TransformerConfig(
        **cfg, **({"attention_fn": attn} if attn else {})))

    # Params are sequence-length independent (RoPE, no learned positional
    # table), so init on a short dummy sequence — initializing through the
    # dense O(S²) path at --seq-len 32768 would OOM before flash ever ran.
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, min(args.seq_len, 128)), jnp.int32))
    n_params = sum(int(np.prod(p.shape)) for p in jax.tree.leaves(params))
    inner = optax.adamw(3e-4)
    if args.bf16_params:
        # bf16-resident params read straight into the MXU (no per-use
        # f32->bf16 cast, bf16 gradients on the wire); adamw math runs on
        # the f32 master copy inside the wrapper's state.
        inner = hvd.master_weights(inner)
    opt = hvd.DistributedOptimizer(inner)
    opt_state = opt.init(params)

    # Distributed like jax_synthetic_benchmark.py: batch sharded over the
    # data axis, gradients averaged by DistributedOptimizer inside the step.
    from jax.sharding import PartitionSpec as P

    K = max(1, args.steps_per_call)

    # Donate params + opt_state: without donation XLA must preserve the
    # input buffers across the step, forcing copy-on-write DMA for every
    # in-place-updatable buffer (measured as part of the round-3 profile's
    # "un-hidden DMA" bucket).
    @functools.partial(jax.jit, donate_argnums=(0, 1))
    @hvd.shard(in_specs=(P(), P(), hvd.batch_spec(2)),
               out_specs=(P(), P(), P()))
    def train_step(params, opt_state, tokens):
        def one(carry, _):
            params, opt_state = carry

            def loss_fn(p, toks):
                logits = model.apply(p, toks)
                # f32 softmax numerics with a logits-dtype cotangent
                # (ops/losses.py).  Measured perf-neutral at this size —
                # the CE chain overlaps with async DMA (profile notes in
                # docs/benchmarks.md) — kept for the numerics-safe bf16
                # cotangent contract.
                return hvd.softmax_cross_entropy(
                    logits[:, :-1], toks[:, 1:]).mean()

            if args.accumulate > 1:
                # backward_passes_per_step: activations peak at the
                # microbatch, one fused allreduce+update per step
                # (training.accumulate_gradients; reference
                # torch/__init__.py:62-112).
                loss, grads = hvd.accumulate_gradients(
                    lambda p, mb: jax.value_and_grad(loss_fn)(p, mb),
                    params, tokens, args.accumulate)
            else:
                loss, grads = jax.value_and_grad(
                    lambda p: loss_fn(p, tokens))(params)
            updates, opt_state = opt.update(grads, opt_state, params)
            return (optax.apply_updates(params, updates), opt_state), loss

        (params, opt_state), losses = jax.lax.scan(
            one, (params, opt_state), None, length=K)
        return params, opt_state, losses[-1]

    rng = np.random.RandomState(0)
    tokens = jnp.asarray(rng.randint(0, args.vocab,
                                     (args.batch, args.seq_len)))

    loss = None
    for _ in range(args.num_warmup_batches):
        params, opt_state, loss = train_step(params, opt_state, tokens)
    if loss is not None:
        float(loss)  # host fetch: waits for every warmup step

    if args.profile:
        from horovod_tpu import profiling

        with profiling.trace(args.profile):
            params, opt_state, loss = train_step(params, opt_state, tokens)
            float(loss)

    rates = []
    for _ in range(args.num_iters):
        t0 = time.perf_counter()
        for _ in range(args.num_batches_per_iter):
            params, opt_state, loss = train_step(params, opt_state, tokens)
        float(loss)
        dt = time.perf_counter() - t0
        rates.append(args.batch * args.seq_len
                     * args.num_batches_per_iter * K / dt)

    tok_s = float(np.mean(rates))
    # 6N matmul FLOPs/token + causal attention FLOPs/token.
    flops_per_token = (6 * n_params
                       + 6 * args.layers * args.seq_len * args.embed)
    mfu = tok_s * flops_per_token / (hvd.num_chips() * peak_flops)
    step_ms = (args.batch * args.seq_len / tok_s) * 1e3
    if hvd.rank() == 0:
        print(json.dumps({
            "metric": "transformer_train_throughput",
            "params_m": round(n_params / 1e6, 1),
            "seq_len": args.seq_len,
            "batch": args.batch,
            "tok_per_s": round(tok_s, 1),
            "step_ms": round(step_ms, 1),
            "mfu": round(mfu, 4),
            "flash": not args.no_flash,
            "block_q": args.block_q,
            "block_k": args.block_k,
            "sub": args.sub,
            "remat": args.remat,
        }))


if __name__ == "__main__":
    main()
