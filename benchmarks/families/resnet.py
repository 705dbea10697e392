"""Family ``resnet``: ResNet-50 v1.5 of ``models/resnet.py`` in bf16,
trained as ``bench.py``'s headline phase trains it -- several optimizer
steps scanned inside one program on one batch, ``DistributedOptimizer``
around the optimizer, the state donated.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import PartitionSpec as P

import horovod_tpu as hvd
from horovod_tpu.models import ResNet50

from benchmarks import compare, flops, streams
from benchmarks.built import Built
from benchmarks.reference import resnet as reference

# Tolerances of the reference comparison (measured on the chip, PERF.md
# PR 23).  The program convolves and normalises in bf16 (2**-8 = 0.4% a
# rounding) through 53 convolutions with a batch norm after each; the
# reference is f32 at "highest".  Forward, the roundings average out: over
# 13 runs of 7 seeds the loss agrees to 0.7-1.2e-4 and the logits to
# 0.52-0.55% of their norm.  Backward they do not: a batch norm's backward
# subtracts nearly equal numbers, and on the chip a single leaf of the
# gradient differs from the reference's by 23% at the median and 30-38% at
# worst (18 runs), whatever the number of images (8 or 32) and even with the
# program's model in float32 (whose convolutions the TPU multiplies in bf16
# all the same).  So the gradient is judged three ways, each bound at about
# 1.5 times the worst reading: as one vector (0.081-0.085 over those runs),
# stage by stage (STAGE_TOL: a fault confined to one stage cannot hide
# behind the norm of the others), and by its worst single leaf.  An 8-bit
# float (2**-4 a rounding, sixteen times bf16's) or a dropped term lands far
# outside all three.
LOSS_TOL = 1e-3
LOGITS_TOL = 0.02
GRAD_TOL = 0.12
LEAF_TOL = 0.55
# relative L2 error of each stage's gradient taken as one vector; read over
# five seeds: stem 0.240-0.251, stage1 0.241-0.249, stage2 0.232-0.236,
# stage3 0.211-0.215, stage4 0.148-0.152, fc 0.0050-0.0052
STAGE_TOL = {"stem": 0.38, "stage1": 0.37, "stage2": 0.35, "stage3": 0.32,
             "stage4": 0.23, "fc": 0.008}
# models/resnet.py starts every block's last batch-norm scale at 0, which
# makes each block the identity and the gradient of the convolutions in it
# exactly 0.  The comparison sets those scales to this, so that every
# layer's forward and backward take part; a larger value makes the gradient
# of the reference itself unstable (f32 against f64: 2.6% at 0.7, 0.5% here).
REDRAWN_SCALE = 0.1


def to_reference(tree: dict) -> dict:
    """The program's parameter (or gradient) tree in the reference's
    layout: renaming only."""
    p = tree
    stages, k = [], 0
    for blocks in (3, 4, 6, 3):
        stage = []
        for _ in range(blocks):
            b = p[f"BottleneckBlock_{k}"]
            k += 1
            r = {f"conv{i + 1}": b[f"Conv_{i}"]["kernel"] for i in range(3)}
            r.update({f"bn{i + 1}": dict(b[f"BatchNorm_{i}"])
                      for i in range(3)})
            if "conv_proj" in b:
                r["down_conv"] = b["conv_proj"]["kernel"]
                r["down_bn"] = dict(b["norm_proj"])
            stage.append(r)
        stages.append(stage)
    return {"conv1": p["conv_init"]["kernel"], "bn1": dict(p["bn_init"]),
            "stages": stages, "fc": dict(p["head"])}


def build(cfg: dict, traffic: dict, chips: int, seed: int) -> Built:
    if cfg["depth"] != 50:
        raise ValueError("this family builds models/resnet.ResNet50")
    image, classes = int(cfg["image_size"]), int(cfg["num_classes"])
    per_chip, inner = int(traffic["per_chip"]), int(traffic["steps_per_call"])
    model = ResNet50(num_classes=classes, dtype=jnp.bfloat16)
    replicated = hvd.replicated_sharding()
    o = dict(traffic["optimizer"])
    opt = hvd.DistributedOptimizer(getattr(optax, o.pop("name"))(**o))

    def loss_fn(params, batch_stats, x, y):
        logits, mutated = model.apply(
            {"params": params, "batch_stats": batch_stats}, x, train=True,
            mutable=["batch_stats"])
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, y).mean(), mutated["batch_stats"]

    def step_with(opt, carry, x, y):
        """One optimizer step through ``opt``.  The timed program scans this
        function and the comparison calls it once; they differ in ``opt``
        alone."""
        params, batch_stats, opt_state = carry
        (loss, batch_stats), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params, batch_stats, x, y)
        updates, opt_state = opt.update(grads, opt_state, params)
        return (optax.apply_updates(params, updates), batch_stats,
                opt_state), loss, updates

    def k_steps(state, x, y):
        state, losses = jax.lax.scan(
            lambda c, _: step_with(opt, c, x, y)[:2], state, None,
            length=inner)
        return state, hvd.allreduce(losses.mean())

    step = jax.jit(
        hvd.shard(k_steps, in_specs=(P(), hvd.batch_spec(4),
                                     hvd.batch_spec(1)),
                  out_specs=(P(), P())),
        donate_argnums=(0,))

    def init_model():
        key = jax.random.fold_in(jax.random.PRNGKey(seed >> 31),
                                 seed & 0x7FFFFFFF)
        v = jax.jit(functools.partial(model.init, train=True),
                    out_shardings=replicated)(
            key, jnp.zeros((2, image, image, 3), jnp.float32))
        return v["params"], v["batch_stats"]

    def init_train(model_state):
        params, batch_stats = model_state
        return params, batch_stats, jax.jit(
            opt.init, out_shardings=replicated)(params)

    pool = streams.make_pool(traffic["stream"], seed, per_chip * chips,
                             image=image, classes=classes)

    def compare_with_reference(model_state) -> list[dict]:
        return _compare(model_state, model, step_with, pool, chips, per_chip,
                        int(traffic["compare_images"]))

    return Built(
        init_model=init_model, init_train=init_train, step=step, pool=pool,
        batch_shardings=(hvd.data_sharding(4), hvd.data_sharding(1)),
        units_per_call=per_chip * chips * inner, steps_per_call=inner,
        flops_per_unit=flops.resnet50_train_flops_per_image(image, classes),
        compare=compare_with_reference, flash_calls=[])


def by_stage(tree: dict) -> dict:
    """A tree in the reference's layout, cut into the groups of STAGE_TOL."""
    groups = {"stem": [tree["conv1"], tree["bn1"]], "fc": tree["fc"]}
    groups.update({f"stage{i + 1}": stage
                   for i, stage in enumerate(tree["stages"])})
    return groups


def _compare(model_state, model, step_with, pool, chips, per_chip, n
             ) -> list[dict]:
    """Logits, loss and gradients on ``compare_images`` (n) images a chip
    with per-batch statistics, against the reference's mean over the chips'
    batches, on the seeded parameters with every zero-initialised scale at
    REDRAWN_SCALE.  The program's side is the timed program's own
    ``step_with`` under the same ``hvd.shard``, with
    ``hvd.DistributedOptimizer(optax.sgd(1.0))`` in the optimizer's place:
    the update is then the negated gradient as ``DistributedOptimizer``
    averaged it over the chips."""
    params, batch_stats = model_state
    params = jax.jit(lambda p: jax.tree_util.tree_map_with_path(
        lambda path, leaf: jnp.where(jnp.all(leaf == 0), REDRAWN_SCALE, leaf)
        if path[-1].key == "scale" else leaf, p))(params)
    idx = np.concatenate([np.arange(c * per_chip, c * per_chip + n)
                          for c in range(chips)])
    x, y = pool[0][0][idx], pool[0][1][idx]
    probe = hvd.DistributedOptimizer(optax.sgd(1.0))

    def program(p, stats, x, y):
        _, loss, updates = step_with(probe, (p, stats, probe.init(p)), x, y)
        logits, _ = model.apply({"params": p, "batch_stats": stats}, x,
                                train=True, mutable=["batch_stats"])
        return (hvd.allreduce(loss),
                to_reference(jax.tree.map(jnp.negative, updates)), logits)

    loss, grads, logits = jax.jit(hvd.shard(
        program, in_specs=(P(), P(), hvd.batch_spec(4), hvd.batch_spec(1)),
        out_specs=(P(), P(), hvd.batch_spec(2))))(params, batch_stats, x, y)

    slices = [(params, x[c * n:(c + 1) * n], y[c * n:(c + 1) * n])
              for c in range(chips)]
    ref_loss, ref_grads = compare.mean_over(
        jax.jit(lambda p, x, y: reference.loss_and_grads(
            to_reference(p), x, y)), slices)
    ref_loss = float(ref_loss)
    ref_logits = jax.jit(lambda p, x: reference.logits(to_reference(p), x))
    got, want = by_stage(grads), by_stage(ref_grads)
    return [
        compare.check("logits", compare.relative_l2(
            logits, jnp.concatenate([ref_logits(p, x) for p, x, _ in slices])),
            LOGITS_TOL),
        compare.check("loss", abs(float(loss) - ref_loss) / abs(ref_loss),
                      LOSS_TOL),
        compare.check_global("grads_from_distributed_optimizer", grads,
                             ref_grads, GRAD_TOL),
        *(compare.check_global(f"grads.{stage}", got[stage], want[stage], tol)
          for stage, tol in STAGE_TOL.items()),
        compare.check_tree("grads.worst_leaf", grads, ref_grads, LEAF_TOL)]
