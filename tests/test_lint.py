"""hvd-lint rule catalog: every rule must fire on its seeded violation
(exact error code asserted), stay quiet on the clean twin, and honor the
``# hvd-lint: disable=CODE`` suppression syntax.  The final test dogfoods
the analyzer on the repo itself — the tree must stay lint-clean
(docs/static_analysis.md; `make -C horovod_tpu/core check` runs the same
gate)."""

import os
import subprocess
import sys
import textwrap

from horovod_tpu.analysis.lint import lint_paths, lint_source

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def codes(src: str) -> list[str]:
    return [e.code for e in lint_source(textwrap.dedent(src), "fixture.py")]


# ---------------------------------------------------------------------------
# HVD101 — rank-divergent collective
# ---------------------------------------------------------------------------

def test_hvd101_collective_under_rank_branch():
    assert codes("""
        import horovod_tpu as hvd

        def step(x):
            if hvd.rank() == 0:
                hvd.allreduce(x)
    """) == ["HVD101"]


def test_hvd101_unbalanced_else_branch():
    assert codes("""
        import horovod_tpu as hvd

        def step(x):
            if hvd.rank() == 0:
                hvd.allreduce(x)
            else:
                hvd.allgather(x)
    """) == ["HVD101"]


def test_hvd101_ifexp_and_local_rank():
    assert codes("""
        import horovod_tpu as hvd

        def step(x):
            y = hvd.broadcast(x, 0) if hvd.local_rank() == 0 else None
            return y
    """) == ["HVD101"]


def test_hvd101_clean_when_branches_match():
    assert codes("""
        import horovod_tpu as hvd

        def step(x, obj):
            if hvd.rank() == 0:
                out = hvd.broadcast_object(obj)
            else:
                out = hvd.broadcast_object(None)
            if hvd.rank() == 0:
                print("root only, no collectives")
            return out
    """) == []


def test_hvd101_clean_tensor_rank_not_flagged():
    # tf.rank(x) takes an argument — it's a tensor property, not process
    # identity; must not trip the rule.
    assert codes("""
        import tensorflow as tf
        import horovod_tpu as hvd

        def step(x):
            if tf.rank(x) == 2:
                hvd.allreduce(x)
    """) == []


# ---------------------------------------------------------------------------
# HVD102 — unnamed engine collective in a loop
# ---------------------------------------------------------------------------

def test_hvd102_async_in_loop_without_name():
    assert codes("""
        import horovod_tpu as hvd

        def push(grads):
            hs = []
            while grads:
                hs.append(hvd.allreduce_async(grads.pop()))
            return hs
    """) == ["HVD102"]


def test_hvd102_clean_with_name_or_outside_loop():
    assert codes("""
        import horovod_tpu as hvd

        def push(grads, x):
            hvd.allreduce_async(x)  # not in a loop: auto-name is fine
            return [hvd.allreduce_async(g, name=f"g.{i}")
                    for i, g in enumerate(grads)]
    """) == []


# ---------------------------------------------------------------------------
# HVD103 — nondeterministic collective names
# ---------------------------------------------------------------------------

def test_hvd103_name_from_set_iteration():
    assert codes("""
        import horovod_tpu as hvd

        def push(x):
            for k in {"a", "b"}:
                hvd.allreduce_async(x, name=f"t.{k}")
    """) == ["HVD103"]


def test_hvd103_name_from_dict_items():
    assert codes("""
        import horovod_tpu as hvd

        def push(params):
            for k, v in params.items():
                hvd.allreduce_async(v, name=k)
    """) == ["HVD103"]


def test_hvd103_name_from_id():
    assert codes("""
        import horovod_tpu as hvd

        def push(t):
            hvd.broadcast_async(t, 0, name=str(id(t)))
    """) == ["HVD103"]


def test_hvd103_clean_sorted_iteration():
    assert codes("""
        import horovod_tpu as hvd

        def push(params, x):
            for k in sorted(params.items()):
                hvd.allreduce_async(x, name=f"t.{k}")
    """) == []


# ---------------------------------------------------------------------------
# HVD104 — impure jitted step functions
# ---------------------------------------------------------------------------

def test_hvd104_random_time_nprandom_in_jit():
    assert codes("""
        import jax
        import numpy as np
        import random
        import time

        @jax.jit
        def step(x):
            return x * random.random() + time.time() + np.random.uniform()
    """) == ["HVD104", "HVD104", "HVD104"]


def test_hvd104_partial_jit_and_shard_decorators():
    assert codes("""
        import jax
        import time
        from functools import partial
        import horovod_tpu as hvd

        @partial(jax.jit, donate_argnums=(0,))
        def step(x):
            return x + time.monotonic()

        @hvd.shard
        def step2(x):
            return x + time.time()
    """) == ["HVD104", "HVD104"]


def test_hvd104_clean_jax_random_and_undecorated():
    assert codes("""
        import jax
        from jax import random
        import time

        @jax.jit
        def step(x, key):
            return x + random.normal(key, x.shape)

        def host_loop(x):
            t0 = time.time()  # not traced: fine
            return x, t0
    """) == []


# ---------------------------------------------------------------------------
# HVD105 — unknown mesh axis names
# ---------------------------------------------------------------------------

def test_hvd105_typoed_axis():
    assert codes("""
        from jax import lax
        from jax.sharding import Mesh
        import numpy as np

        mesh = Mesh(np.array([0, 1]).reshape(1, 2), ("hvd", "tp"))

        def f(x):
            return lax.psum(x, "tpp")
    """) == ["HVD105"]


def test_hvd105_clean_declared_and_builtin_axes():
    assert codes("""
        from jax import lax
        import horovod_tpu as hvd

        hvd.init(mesh_axes={"tp": 2})

        def f(x):
            return lax.psum(lax.psum(x, "tp"), ("dcn", "ici"))
    """) == []


def test_hvd105_inactive_without_mesh_declaration():
    # No mesh in the module: the rule cannot know the axes — stays quiet.
    assert codes("""
        from jax import lax

        def f(x):
            return lax.psum(x, "model")
    """) == []


# ---------------------------------------------------------------------------
# HVD106 — topology values cached where elastic resize can't reach them
# ---------------------------------------------------------------------------

def test_hvd106_module_level_size_constant():
    assert codes("""
        import horovod_tpu as hvd

        WORLD = hvd.size()

        def shard(data):
            return data[::WORLD]
    """) == ["HVD106"]


def test_hvd106_default_parameter_value():
    assert codes("""
        import horovod_tpu as hvd

        def scale_lr(lr, world=hvd.size()):
            return lr * world
    """) == ["HVD106"]


def test_hvd106_rank_in_class_constant_and_derived_expression():
    assert codes("""
        from horovod_tpu import rank

        class Cfg:
            is_chief = rank() == 0
    """) == ["HVD106"]


def test_hvd106_clean_call_at_use_time_and_unrelated_size():
    # Calling at use time is the fix; q.size() on some object is not a
    # topology call and module-level constants from it are fine.
    assert codes("""
        import horovod_tpu as hvd

        N = my_queue.size()

        def shard(data):
            return data[:: hvd.size()]

        def inner():
            world = hvd.size()   # runtime local: re-read every call
            return world
    """) == []


def test_hvd106_exempt_when_refreshed_in_on_reconfigure_callback():
    assert codes("""
        import horovod_tpu as hvd

        WORLD = hvd.size()

        @hvd.on_reconfigure
        def _refresh(event):
            global WORLD
            WORLD = hvd.size()
    """) == []


def test_documented_rule_table_is_the_registry():
    # The catalog in docs/static_analysis.md lists exactly the registered
    # codes, in order; the code between 106 and 108 was retired with the
    # knob it guarded (PR 29) and is not reused.
    import re

    from horovod_tpu.analysis.rules import RULES

    registered = [r.code for r in RULES]
    assert registered == [f"HVD{n}" for n in
                          (101, 102, 103, 104, 105, 106, 108, 109, 110)]
    doc = os.path.join(os.path.dirname(__file__), os.pardir, "docs",
                       "static_analysis.md")
    with open(doc) as f:
        documented = re.findall(r"^\| (HVD\d+) \|", f.read(), re.M)
    assert documented == registered


# ---------------------------------------------------------------------------
# HVD108 — hand-tuned context layout (the context planner owns it)
# ---------------------------------------------------------------------------

def test_hvd108_plain_causal_literal_and_default():
    # causal=True literal AND causal-left-to-default both run causal work
    # on the plain ring layout — the planner routes that to zigzag.
    assert codes("""
        from horovod_tpu.parallel import ring_flash_attention

        def f(q, k, v, bq, bk):
            a = ring_flash_attention(q, k, v, "sp", causal=True,
                                     block_q=bq, block_k=bk)
            b = ring_flash_attention(q, k, v, "sp", block_q=bq, block_k=bk)
            return a, b
    """) == ["HVD108", "HVD108"]


def test_hvd108_block_literals_all_entry_points():
    assert codes("""
        from horovod_tpu.parallel import (
            make_ring_flash_attention,
            make_zigzag_ring_flash_attention,
            ring_flash_attention,
            zigzag_ring_flash_attention,
        )

        def f(q, k, v, causal):
            a = ring_flash_attention(q, k, v, "sp", causal, 512, block_k=4096)
            b = zigzag_ring_flash_attention(q, k, v, "sp", causal, block_q=256)
            c = make_ring_flash_attention("sp", block_k=2048)
            d = make_zigzag_ring_flash_attention("sp", 128)
            return a, b, c, d
    """) == ["HVD108"] * 5  # a fires twice (block_q positional + block_k)


def test_hvd108_clean_planner_driven_sites():
    # Variables — including plan fields — are the planner speaking;
    # causal=False on the plain ring wastes nothing.  None of it fires.
    assert codes("""
        from horovod_tpu.parallel import (
            ring_flash_attention,
            zigzag_ring_flash_attention,
        )

        def f(q, k, v, plan, causal):
            a = ring_flash_attention(q, k, v, "sp", causal,
                                     plan.block_q, plan.block_k)
            b = ring_flash_attention(q, k, v, "sp", causal=False)
            c = zigzag_ring_flash_attention(q, k, v, "sp", True,
                                            plan.block_q, plan.block_k)
            return a, b, c
    """) == []


def test_hvd108_suppressible_for_audit_fixtures():
    # The longctx audit pins the plain causal path on purpose (the
    # step-skip contract is specific to it) — sanctioned, line by line.
    assert codes("""
        from horovod_tpu.parallel import ring_flash_attention

        def f(q, k, v):
            return ring_flash_attention(  # hvd-lint: disable=HVD108
                q, k, v, "sp", True, block_q=4, block_k=4)
    """) == []


# ---------------------------------------------------------------------------
# HVD109 — unbucketed serve shapes (one compile per request length)
# ---------------------------------------------------------------------------

def test_hvd109_len_shaped_jit_input_in_serve_loop():
    # The canonical recompile-per-length bug: a jit-bound callee fed a
    # len(prompt)-shaped array inside the serve loop.
    assert codes("""
        import jax
        import jax.numpy as jnp

        decode_fn = jax.jit(lambda t: t * 2)

        def serve(requests):
            while requests:
                prompt = requests.pop()
                decode_fn(jnp.zeros((len(prompt),), jnp.int32))
    """) == ["HVD109"]


def test_hvd109_len_sliced_prefill_input():
    # Slices bounded by len() shape the operand too — and the backend
    # verbs (prefill/decode) count as serve entry points even when the
    # jit binding is in another module.
    assert codes("""
        import numpy as np

        def serve(backend, requests, tokens):
            for prompt in requests:
                backend.prefill(tokens[:len(prompt)], len(prompt), 0)
    """) == ["HVD109"]


def test_hvd109_clean_bucketed_twin():
    # The sanctioned shape discipline: pad to a fixed bucket, pass the
    # true length as a scalar (0-d operands never recompile).
    assert codes("""
        import numpy as np

        def serve(backend, requests, buckets):
            for prompt in requests:
                bucket = min(b for b in buckets if b >= len(prompt))
                padded = np.zeros((1, bucket), np.int32)
                padded[0, :len(prompt)] = prompt
                backend.prefill(padded, len(prompt), 0)
    """) == []


def test_hvd109_suppressible_for_one_shape_fixtures():
    assert codes("""
        import jax
        import jax.numpy as jnp

        decode_fn = jax.jit(lambda t: t * 2)

        def serve(requests):
            for prompt in requests:
                decode_fn(  # hvd-lint: disable=HVD109
                    jnp.zeros((len(prompt),), jnp.int32))
    """) == []


# ---------------------------------------------------------------------------
# HVD110 — collective before reconfigure in a MembershipChanged handler
# ---------------------------------------------------------------------------

def test_hvd110_retry_without_reconfigure():
    assert codes("""
        import horovod_tpu as hvd
        from horovod_tpu.elastic import MembershipChanged

        def step(x):
            try:
                return hvd.allreduce(x)
            except MembershipChanged:
                return hvd.allreduce(x)
    """) == ["HVD110"]


def test_hvd110_engine_enqueue_and_dotted_exception():
    # The engine-level verb and a dotted exception path both count; two
    # pre-reconfigure issues -> two findings.
    assert codes("""
        import horovod_tpu as hvd

        def pump(engine, x):
            try:
                engine.enqueue("t", 0, 5, -1, 0, x)
            except hvd.elastic.MembershipChanged:
                engine.enqueue("t", 0, 5, -1, 0, x)
                hvd.barrier()
    """) == ["HVD110", "HVD110"]


def test_hvd110_clean_reconfigure_first():
    # The sanctioned serving/worker.py shape: reconfigure, rebuild, retry.
    assert codes("""
        import horovod_tpu as hvd
        from horovod_tpu import elastic
        from horovod_tpu.elastic import MembershipChanged

        def step(x):
            try:
                return hvd.allreduce(x)
            except MembershipChanged:
                ev = elastic.reconfigure()
                return hvd.allreduce(x)
    """) == []


def test_hvd110_clean_cleanup_only_handler_and_other_exceptions():
    assert codes("""
        import horovod_tpu as hvd
        from horovod_tpu.elastic import MembershipChanged

        def step(x, log):
            try:
                return hvd.allreduce(x)
            except MembershipChanged:
                log.warning("resized")
                raise
            except ValueError:
                return hvd.allreduce(x)
    """) == []


def test_hvd110_tuple_exception_type_and_suppression():
    src = """
        import horovod_tpu as hvd
        from horovod_tpu.elastic import MembershipChanged

        def step(x):
            try:
                return hvd.allreduce(x)
            except (MembershipChanged, RuntimeError):
                return hvd.allreduce(x)  # hvd-lint: disable=HVD110
    """
    assert codes(src) == []
    assert codes(src.replace("  # hvd-lint: disable=HVD110", "")) \
        == ["HVD110"]


# ---------------------------------------------------------------------------
# Suppression + driver behaviour
# ---------------------------------------------------------------------------

def test_suppression_comment_and_all():
    src = """
        import horovod_tpu as hvd

        def step(x):
            if hvd.rank() == 0:
                hvd.allreduce(x)  # hvd-lint: disable=HVD101
            if hvd.rank() == 1:
                hvd.allgather(x)  # hvd-lint: disable=all
    """
    assert codes(src) == []


def test_suppression_wrong_code_does_not_silence():
    src = """
        import horovod_tpu as hvd

        def step(x):
            if hvd.rank() == 0:
                hvd.allreduce(x)  # hvd-lint: disable=HVD102
    """
    assert codes(src) == ["HVD101"]


def test_syntax_error_reported_not_crash():
    assert codes("def broken(:\n    pass") == ["HVD000"]


def test_cli_exit_codes(tmp_path):
    bad = tmp_path / "bad.py"
    bad.write_text(textwrap.dedent("""
        import horovod_tpu as hvd

        def f(x):
            if hvd.rank() == 0:
                hvd.barrier()
    """))
    good = tmp_path / "good.py"
    good.write_text("x = 1\n")
    env = {**os.environ, "PYTHONPATH": REPO}
    rc_bad = subprocess.run(
        [sys.executable, "-m", "horovod_tpu.analysis.lint", str(bad)],
        capture_output=True, text=True, env=env)
    assert rc_bad.returncode == 1
    assert "HVD101" in rc_bad.stdout
    assert "hint:" in rc_bad.stdout
    rc_good = subprocess.run(
        [sys.executable, "-m", "horovod_tpu.analysis.lint", str(good)],
        capture_output=True, text=True, env=env)
    assert rc_good.returncode == 0, rc_good.stderr


def test_repo_is_lint_clean():
    """Dogfood: the analyzer must pass over our own tree (the acceptance
    gate `python -m horovod_tpu.analysis.lint examples/ horovod_tpu/
    tests/` and the lint leg of make check)."""
    errors = lint_paths([os.path.join(REPO, d)
                         for d in ("horovod_tpu", "examples", "tests")])
    assert errors == [], "\n".join(e.render() for e in errors)
