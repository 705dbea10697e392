"""chip_smoke.py — does the system still start on the chip?

One process drives the main paths once, through the entry points a user
calls, at the full width of the repo's 162M dense transformer, and checks
what comes out.  Legs, in order; the first failure ends the run non-zero
with the leg's name:

``train``   hvd.init -> hvd.broadcast_parameters -> jit(hvd.shard(step),
            donated state) with hvd.DistributedOptimizer(adamw) inside, the
            12-layer/768/6x128/3072/32000 bf16 transformer with the flash
            kernels at library-default tiles, S=1024, 8 sequences per chip
            (the dense training cells' step at a smaller model, one
            optimizer step per call).  Asserts: the compiled step holds Mosaic custom
            calls (the kernels were compiled, not interpreted) and, on more
            than one chip, all-reduces; parameters and optimizer state come
            back replicated on every chip and the batch is split across
            them; the loss is finite and lower after four steps.
``resnet``  ResNet-50 bf16, batch 128 per chip, 8 steps scanned inside one
            program, DistributedOptimizer(sgd+momentum), donated state —
            the step the resnet50-imagenet cell builds — three calls.
``serve``   ServingEngine over TransformerBackend as
            ``python -m horovod_tpu.serving`` builds it, at the 162M
            widths: 8 slots, one prefill bucket, 6 requests of mixed
            lengths.  Asserts every request returns the token count asked
            for, and one request's prefill logits agree with a plain
            ``model.apply`` of its unpadded prompt within SERVE_LOGIT_TOL.
``eager``   one hvd.allreduce_async + hvd.synchronize through the native
            engine; asserts libhvdcore.so sits in this checkout and is
            newer than every source it is built from.

Times are plain observations (compile seconds, steady seconds per step);
no utilization, no comparison: a ``summary:`` line carries them per leg.
The last line of stdout is the result, one JSON object with these keys and
no others: ``{"ok": true, "device": {"platform", "kind", "count"}}`` as jax
reports the device (``"ok": false`` when a leg failed).  Without a TPU the
script fails, says so and prints no result.  ``--rehearse-on-cpu`` walks
the same code at toy sizes for debugging in a sandbox; it checks nothing
on a device and prints no result line.
"""

from __future__ import annotations

import argparse
import dataclasses
import functools
import glob
import importlib.metadata
import json
import os
import re
import sys
import time
import traceback

# Largest |prefill logit - plain-forward logit| accepted in the serve leg,
# relative to the largest |plain-forward logit|.  Both sides compute in
# bf16 with f32 accumulation; they differ in sequence padding (the bucket)
# and therefore in matmul tiling, not in arithmetic.
SERVE_LOGIT_TOL = 0.05


@dataclasses.dataclass(frozen=True)
class Sizes:
    layers: int
    embed: int
    heads: int
    vocab: int
    seq: int
    seqs_per_chip: int
    resnet_batch: int
    resnet_image: int
    resnet_steps_per_call: int
    serve_bucket: int
    serve_max_len: int
    # Train leg's global batch when not seqs_per_chip * chips: for comparing
    # chip counts at equal batch.
    global_batch: int | None = None


FULL = Sizes(layers=12, embed=768, heads=6, vocab=32000, seq=1024,
             seqs_per_chip=8, resnet_batch=128, resnet_image=224,
             resnet_steps_per_call=8, serve_bucket=128, serve_max_len=256)
REHEARSAL = Sizes(layers=2, embed=64, heads=2, vocab=256, seq=128,
                  seqs_per_chip=2, resnet_batch=2, resnet_image=32,
                  resnet_steps_per_call=2, serve_bucket=16, serve_max_len=32)


def _model_dims(sz: Sizes) -> dict:
    return dict(vocab_size=sz.vocab, num_layers=sz.layers,
                num_heads=sz.heads, head_dim=sz.embed // sz.heads,
                embed_dim=sz.embed, mlp_dim=4 * sz.embed)


def _timed_calls(call, n: int):
    """Run ``call`` n times, blocking on each result; returns the results
    and the seconds each took."""
    import jax

    outs, secs = [], []
    for _ in range(n):
        t0 = time.perf_counter()
        outs.append(jax.block_until_ready(call()))
        secs.append(time.perf_counter() - t0)
    return outs, secs


def _lower_and_compile(jitted, *args):
    """AOT-compile ``jitted`` for ``args``; returns the executable, the
    seconds spent tracing and lowering (never cached) and the seconds in
    the backend compiler (near zero on a persistent-cache hit)."""
    t0 = time.perf_counter()
    lowered = jitted.lower(*args)
    t1 = time.perf_counter()
    compiled = lowered.compile()
    return compiled, t1 - t0, time.perf_counter() - t1


def build_train(sz: Sizes):
    """The train leg's jitted step and its arguments: the README quick
    start at the 162M widths.  Returns (step, params, opt_state, tokens)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    from jax.sharding import PartitionSpec as P

    import horovod_tpu as hvd
    from horovod_tpu.models import Transformer, TransformerConfig

    batch = sz.global_batch or sz.seqs_per_chip * hvd.num_chips()
    model = Transformer(TransformerConfig(
        **_model_dims(sz), max_seq_len=sz.seq, dtype=jnp.bfloat16,
        logits_dtype=jnp.bfloat16,
        attention_fn=hvd.make_flash_attention()))
    # init under jit: run eagerly it dispatches every initializer as its
    # own tiny program, half a minute of them on a chip.
    params = jax.jit(model.init)(
        jax.random.PRNGKey(0), jnp.zeros((1, min(sz.seq, 128)), jnp.int32))
    params = hvd.broadcast_parameters(params, root_rank=0)
    opt = hvd.DistributedOptimizer(optax.adamw(3e-4))
    opt_state = opt.init(params)

    def train_step(params, opt_state, tokens):
        def loss_fn(p):
            logits = model.apply(p, tokens)
            return hvd.softmax_cross_entropy(
                logits[:, :-1], tokens[:, 1:]).mean()

        loss, grads = jax.value_and_grad(loss_fn)(params)
        updates, opt_state = opt.update(grads, opt_state, params)
        # The mean over every chip's sequences, not this chip's own: the
        # number is then comparable across chip counts at one global batch.
        return (optax.apply_updates(params, updates), opt_state,
                hvd.allreduce(loss))

    step = jax.jit(
        hvd.shard(train_step, in_specs=(P(), P(), hvd.batch_spec(2)),
                  out_specs=(P(), P(), P())),
        donate_argnums=(0, 1))
    tokens = jnp.asarray(np.random.RandomState(0).randint(
        0, sz.vocab, (batch, sz.seq)))
    return step, params, opt_state, tokens


def leg_train(sz: Sizes) -> dict:
    import jax
    import numpy as np

    import horovod_tpu as hvd

    n = hvd.num_chips()
    step, params, opt_state, tokens = build_train(sz)
    batch = tokens.shape[0]
    n_params = sum(int(np.prod(p.shape)) for p in jax.tree.leaves(params))

    compiled, trace_s, compile_s = _lower_and_compile(
        step, params, opt_state, tokens)
    text = compiled.as_text()
    mosaic_calls = text.count("tpu_custom_call")
    all_reduces = len(re.findall(r"\ball-reduce(?:-start)?\(", text))
    plan = hvd.overlap_plan()
    print(f"train: params={n_params / 1e6:.1f}M global_batch={batch} "
          f"seq={sz.seq} trace_s={trace_s:.1f} compile_s={compile_s:.1f} "
          f"mosaic_custom_calls={mosaic_calls} all_reduces={all_reduces}")
    print(f"train: overlap_plan={json.dumps(plan)}")
    if jax.default_backend() == "tpu":  # not in a CPU rehearsal
        assert mosaic_calls > 0, (
            "no Mosaic custom call in the compiled train step: the flash "
            "kernels did not compile for the TPU")
        assert plan["headroom_mb"] is not None, (
            "device.memory_stats() gave the planner no headroom on a TPU "
            "(ops/schedule_plan.probe_headroom_mb)")
    if n > 1:
        assert all_reduces > 0, "no all-reduce in a multi-chip train step"
        assert plan["width"] == n and plan["chained"], plan
    batch_sharding = compiled.input_shardings[0][2]
    assert batch_sharding.shard_shape(tokens.shape) == (batch // n, sz.seq), \
        (batch_sharding, tokens.shape)

    def one_step():
        nonlocal params, opt_state
        params, opt_state, loss = compiled(params, opt_state, tokens)
        return loss

    outs, secs = _timed_calls(one_step, 4)
    losses = [float(x) for x in outs]
    for leaf in jax.tree.leaves((params, opt_state)):
        assert leaf.sharding.is_fully_replicated and \
            len(leaf.sharding.device_set) == n, (leaf.shape, leaf.sharding)
    stats = [d.memory_stats() for d in jax.local_devices()]
    in_use = [s["bytes_in_use"] for s in stats if s]  # none on the CPU
    steady = sum(secs[1:]) / len(secs[1:])
    print(f"train: losses={[round(x, 4) for x in losses]} "
          f"first_step_s={secs[0]:.3f} steady_step_s={steady:.3f} "
          f"bytes_in_use_per_device={in_use}")
    assert all(np.isfinite(losses)), losses
    assert losses[-1] < losses[0], f"loss did not fall: {losses}"
    return {"compile_s": round(compile_s, 1),
            "steady_step_s": round(steady, 4),
            "mosaic_custom_calls": mosaic_calls, "all_reduces": all_reduces,
            "global_batch": batch, "losses": [round(x, 4) for x in losses]}


def leg_resnet(sz: Sizes) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    from jax.sharding import PartitionSpec as P

    import horovod_tpu as hvd
    from horovod_tpu.models import ResNet50

    n = hvd.num_chips()
    batch, img = sz.resnet_batch * n, sz.resnet_image
    model = ResNet50(num_classes=1000, dtype=jnp.bfloat16)
    rng = jax.random.PRNGKey(0)
    x = jax.random.normal(rng, (batch, img, img, 3), jnp.float32)
    y = jax.random.randint(rng, (batch,), 0, 1000)
    variables = jax.jit(functools.partial(model.init, train=True))(
        rng, x[:2])
    params, batch_stats = variables["params"], variables["batch_stats"]
    opt = hvd.DistributedOptimizer(optax.sgd(0.01, momentum=0.9))
    opt_state = opt.init(params)

    def train_step(carry, x, y):
        params, batch_stats, opt_state = carry

        def loss_fn(p):
            logits, mutated = model.apply(
                {"params": p, "batch_stats": batch_stats}, x, train=True,
                mutable=["batch_stats"])
            return optax.softmax_cross_entropy_with_integer_labels(
                logits, y).mean(), mutated["batch_stats"]

        (loss, new_stats), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params)
        updates, opt_state = opt.update(grads, opt_state, params)
        return (optax.apply_updates(params, updates), new_stats,
                opt_state), loss

    def k_steps(params, batch_stats, opt_state, x, y):
        (params, batch_stats, opt_state), losses = jax.lax.scan(
            lambda c, _: train_step(c, x, y),
            (params, batch_stats, opt_state), None,
            length=sz.resnet_steps_per_call)
        return params, batch_stats, opt_state, losses[-1]

    step = jax.jit(
        hvd.shard(k_steps,
                  in_specs=(P(), P(), P(), hvd.batch_spec(4),
                            hvd.batch_spec(1)),
                  out_specs=(P(), P(), P(), P())),
        donate_argnums=(0, 1, 2))

    compiled, trace_s, compile_s = _lower_and_compile(
        step, params, batch_stats, opt_state, x, y)

    def one_call():
        nonlocal params, batch_stats, opt_state
        params, batch_stats, opt_state, loss = compiled(
            params, batch_stats, opt_state, x, y)
        return loss

    outs, secs = _timed_calls(one_call, 3)
    losses = [float(v) for v in outs]
    steady = sum(secs[1:]) / len(secs[1:]) / sz.resnet_steps_per_call
    print(f"resnet: global_batch={batch} image={img} "
          f"steps_per_call={sz.resnet_steps_per_call} "
          f"trace_s={trace_s:.1f} compile_s={compile_s:.1f} "
          f"first_call_s={secs[0]:.3f} "
          f"steady_step_s={steady:.4f} "
          f"losses={[round(v, 4) for v in losses]}")
    print(f"resnet: overlap_plan={json.dumps(hvd.overlap_plan())}")
    assert all(np.isfinite(losses)), losses
    return {"compile_s": round(compile_s, 1),
            "steady_step_s": round(steady, 4),
            "losses": [round(v, 4) for v in losses]}


def leg_serve(sz: Sizes) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from horovod_tpu.models.transformer import Transformer, TransformerConfig
    from horovod_tpu.serving.engine import (ServingConfig, ServingEngine,
                                            TransformerBackend)

    cfg = ServingConfig(num_slots=8, buckets=(sz.serve_bucket,),
                        max_seq_len=sz.serve_max_len, record_logits=True)
    mcfg = TransformerConfig(**_model_dims(sz), max_seq_len=cfg.max_seq_len)
    model = Transformer(mcfg)
    params = jax.jit(model.init)(
        jax.random.PRNGKey(0), jnp.zeros((1, cfg.buckets[0]), jnp.int32))
    engine = ServingEngine(
        TransformerBackend(model, params, mcfg, cfg.num_slots,
                           cfg.max_seq_len), cfg)

    # (prompt length as a fraction of the bucket, tokens asked for): short
    # and bucket-filling prompts, one-token and long answers, in one queue.
    mix = ((0.05, 4), (0.15, 9), (0.3, 1), (0.5, 16), (0.8, 12), (1.0, 7))
    rng = np.random.RandomState(0)
    requests = []
    for frac, max_new in mix:
        plen = max(1, int(frac * sz.serve_bucket))
        max_new = min(max_new, cfg.max_seq_len - plen)
        prompt = [int(t) for t in rng.randint(0, sz.vocab, plen)]
        requests.append((engine.submit(prompt, max_new), max_new))

    t0 = time.perf_counter()
    done = engine.step()          # compiles prefill and decode
    first_step_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    done += engine.run_until_idle()
    rest_s = time.perf_counter() - t0
    steps = engine.counters["steps"]
    assert len(done) == len(requests), (len(done), len(requests))
    for req, asked in requests:
        assert req.finish_reason == "max_new_tokens" and \
            len(req.tokens) == asked, (req.rid, req.finish_reason,
                                       len(req.tokens), asked)

    probe = requests[3][0]
    ref = np.asarray(model.apply(
        params, jnp.asarray([probe.prompt], jnp.int32))[0, -1], np.float32)
    assert np.all(np.isfinite(ref)) and ref.shape == (sz.vocab,), ref.shape
    err = float(np.max(np.abs(probe.logits[0] - ref)))
    scale = float(np.max(np.abs(ref)))
    steady = rest_s / max(steps - 1, 1)
    print(f"serve: requests={len(done)} steps={steps} "
          f"tokens={engine.counters['tokens']} "
          f"first_step_s={first_step_s:.1f} steady_step_s={steady:.4f} "
          f"prefill_logit_max_abs_err={err:.4g} ref_max_abs={scale:.4g} "
          f"tolerance={SERVE_LOGIT_TOL}*ref_max_abs")
    assert err <= SERVE_LOGIT_TOL * scale, (err, scale)
    return {"first_step_s": round(first_step_s, 1),
            "steady_step_s": round(steady, 4),
            "prefill_logit_rel_err": round(err / scale, 5)}


def leg_eager(sz: Sizes) -> dict:
    import numpy as np

    import horovod_tpu as hvd
    from horovod_tpu.core import engine as core_engine

    lib, core = core_engine._LIB_PATH, core_engine._HERE
    here = os.path.dirname(os.path.abspath(__file__))
    assert os.path.commonpath([here, lib]) == here, (
        f"the engine would load {lib}, outside this checkout")
    prebuilt = os.path.exists(lib)
    t0 = time.perf_counter()
    h = hvd.allreduce_async(np.ones(1024, np.float32), name="chip_smoke")
    out = np.asarray(hvd.synchronize(h))
    first_op_s = time.perf_counter() - t0
    np.testing.assert_array_equal(out, np.ones(1024, np.float32))

    sources = glob.glob(os.path.join(core, "src", "*.cc")) + \
        glob.glob(os.path.join(core, "src", "*.h"))
    stale = [s for s in sources
             if os.path.getmtime(s) > os.path.getmtime(lib)]
    assert not stale, f"libhvdcore.so is older than {stale}"
    print(f"eager: allreduce ok built_by_this_run={not prebuilt} "
          f"first_op_s={first_op_s:.1f} sources={len(sources)}")
    return {"built_by_this_run": not prebuilt,
            "first_op_s": round(first_op_s, 1)}


LEGS = {"train": leg_train, "resnet": leg_resnet, "serve": leg_serve,
        "eager": leg_eager}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--legs", default=",".join(LEGS),
                    help="comma-separated subset, run in the order given")
    ap.add_argument("--global-batch", type=int, default=None,
                    help="train leg's global batch (default 8 per chip); "
                         "for comparing chip counts at equal batch")
    ap.add_argument("--rehearse-on-cpu", action="store_true",
                    help="toy sizes on whatever backend is there, for "
                         "debugging; checks nothing on a device")
    args = ap.parse_args()
    legs = [name for name in args.legs.split(",") if name]
    unknown = [name for name in legs if name not in LEGS]
    if unknown:
        ap.error(f"unknown legs {unknown}; choose from {list(LEGS)}")

    import jax
    import jaxlib

    from horovod_tpu.utils import chip

    cache_dir = chip.enable_compile_cache()
    if not args.rehearse_on_cpu:
        try:
            chip.require_tpu("chip_smoke.py")
        except RuntimeError as e:
            print(f"chip_smoke: {e}", file=sys.stderr)
            return 2
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    print(f"platform={dev.platform} device_kind={dev.device_kind!r} "
          f"device_count={device['count']} jax={jax.__version__} "
          f"jaxlib={jaxlib.__version__} "
          f"libtpu={importlib.metadata.version('libtpu')}")
    print(f"compile_cache={cache_dir} (JAX_COMPILATION_CACHE_DIR "
          f"{'set' if os.environ.get('JAX_COMPILATION_CACHE_DIR') else 'unset'})")

    import horovod_tpu as hvd

    hvd.init()
    mesh = hvd.global_mesh()
    print(f"mesh axes={mesh.axis_names} shape={dict(mesh.shape)} devices="
          f"{[(d.id, getattr(d, 'coords', None)) for d in mesh.devices.flat]}")

    sz = dataclasses.replace(REHEARSAL if args.rehearse_on_cpu else FULL,
                             global_batch=args.global_batch)
    results = {}
    started = time.perf_counter()
    for name in legs:
        t0 = time.perf_counter()
        try:
            results[name] = LEGS[name](sz)
        except Exception:
            traceback.print_exc()
            print(f"chip_smoke: leg {name!r} FAILED", file=sys.stderr)
            if not args.rehearse_on_cpu:
                print(json.dumps({"ok": False, "device": device}), flush=True)
            return 1
        results[name]["leg_s"] = round(time.perf_counter() - t0, 1)
    hvd.shutdown()
    total_s = round(time.perf_counter() - started, 1)

    if args.rehearse_on_cpu:
        print(f"chip_smoke: rehearsal of {legs} finished in {total_s}s at "
              f"toy sizes; NOTHING WAS CHECKED ON A DEVICE")
        return 0
    print("summary: " + json.dumps({"legs": results, "total_s": total_s,
                                    "claim": None}))
    # The result line, last on stdout: these two keys and no others.
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
