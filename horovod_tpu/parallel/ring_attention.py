"""Ring attention — sequence-parallel exact attention over the ICI ring.

Not in the reference (it predates the technique; SURVEY §2.9) but first-class
here: long sequences are sharded across a mesh axis, each chip keeps its
query block resident, and key/value blocks rotate around the ring via
``lax.ppermute`` while a flash-style online softmax accumulates the exact
result.  Peak memory per chip is O(S/n), and the flash ring passes are
double-buffered: each scan step issues the next block's ``ppermute``
BEFORE its own kernel, so the ICI transfer is structurally independent of
the same step's attention output and overlaps its compute (pinned by
``examples/longctx_audit.py``).  Causal runs on the plain layout skip the
fully-masked ring steps outright (exact, via the lse-merge identity); the
zigzag layout balances the causal triangle across ranks instead.  Layout
and kernel parameters are planner-decided — see
``ops/schedule_plan.plan_context`` and ``parallel/context.py`` — the
TPU-native form of ring attention (Liu et al. 2023) built from the same
collective vocabulary as the data plane.

Numerics: logits and softmax statistics in float32, block matmuls in the
input dtype (bf16 on the MXU); fully-masked blocks are handled by masking
probabilities (not just logits) so causal shards never divide by zero.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

NEG_INF = -1e30


def _block_attend(q, k, v, q_pos, k_pos, causal, m, l, acc):
    """One online-softmax accumulation step.

    q: [B, Sq, H, D]; k, v: [B, Sk, H, D]; positions: [Sq]/[Sk] globals;
    m, l: [B, H, Sq]; acc: [B, H, Sq, D] (f32).
    """
    scale = q.shape[-1] ** -0.5
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    if causal:
        mask = q_pos[:, None] >= k_pos[None, :]          # [Sq, Sk]
        logits = jnp.where(mask, logits, NEG_INF)
    m_new = jnp.maximum(m, logits.max(axis=-1))
    p = jnp.exp(logits - m_new[..., None])
    if causal:
        p = jnp.where(mask, p, 0.0)
    correction = jnp.exp(m - m_new)
    l_new = l * correction + p.sum(axis=-1)
    pv = jnp.einsum("bhqk,bkhd->bhqd", p.astype(v.dtype), v,
                    preferred_element_type=jnp.float32)
    acc_new = acc * correction[..., None] + pv
    return m_new, l_new, acc_new


def ring_attention(q, k, v, axis_name: str, causal: bool = True):
    """Exact attention over a sequence sharded on ``axis_name``.

    Shapes: [B, S_local, H, D] per chip; global sequence = n × S_local in
    ring order (shard i holds positions [i·S_local, (i+1)·S_local)).  Returns
    the local output shard, same shape/dtype as ``q``.
    """
    n = lax.axis_size(axis_name)
    my = lax.axis_index(axis_name)
    b, s_local, h, d = q.shape
    q_pos = my * s_local + jnp.arange(s_local)
    # Accumulators start device-invariant but become device-varying inside the
    # scan; mark them varying over the ring axis up front (shard_map vma rule).
    varying = functools.partial(lax.pcast, axis_name=axis_name, to="varying")
    m = varying(jnp.full((b, h, s_local), NEG_INF, jnp.float32))
    l = varying(jnp.zeros((b, h, s_local), jnp.float32))
    acc = varying(jnp.zeros((b, h, s_local, d), jnp.float32))
    perm = [(i, (i + 1) % n) for i in range(n)]

    def step(carry, i):
        k, v, m, l, acc = carry
        # After i forward rotations this chip holds the block that originated
        # at ring neighbour (my - i) mod n.
        owner = (my - i) % n
        k_pos = owner * s_local + jnp.arange(s_local)
        m, l, acc = _block_attend(q, k, v, q_pos, k_pos, causal, m, l, acc)
        # Rotate K/V for the next step; XLA overlaps this ICI transfer with
        # the next block's matmuls (the send is not data-dependent on them).
        k = lax.ppermute(k, axis_name, perm)
        v = lax.ppermute(v, axis_name, perm)
        return (k, v, m, l, acc), None

    (_, _, m, l, acc), _ = lax.scan(step, (k, v, m, l, acc), jnp.arange(n))
    # Guard l==0 (a causal top-left shard attending nothing can't occur —
    # every query sees at least itself — but keep the division total).
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.transpose(0, 2, 1, 3).astype(q.dtype)


def make_ring_attention(axis_name: str):
    """Adapter producing a ``TransformerConfig.attention_fn``."""
    return functools.partial(ring_attention, axis_name=axis_name)


# ---------------------------------------------------------------------------
# Ring + flash: Pallas kernel inside each ring step
# ---------------------------------------------------------------------------

def _merge_partial(out, lse, o_i, lse_i):
    """Exact merge of two normalized partial attentions via their lse:
    combined = (out·e^{lse} + o_i·e^{lse_i}) / (e^{lse} + e^{lse_i}),
    computed at shifted max m.  Shapes: out [B,S,H,D]; weights [B,S,H,1]."""
    m = jnp.maximum(lse, lse_i)
    w_old = jnp.exp(lse - m)[..., None]
    w_new = jnp.exp(lse_i - m)[..., None]
    denom = jnp.maximum(w_old + w_new, 1e-30)
    out = (out * w_old + o_i.astype(jnp.float32) * w_new) / denom
    lse = m + jnp.log(denom[..., 0])
    return out, lse


def _ring_flash_forward(q, k, v, axis_name, causal, block_q, block_k):
    """Forward ring pass; returns (out_f32, merged lse, steps_run).

    Double-buffered: each scan step issues the NEXT K/V ``ppermute`` before
    this step's flash kernel, so the ICI transfer is never data-dependent on
    the same step's attention output and overlaps its compute.  The final
    step is unrolled outside the scan — there is no next block to fetch, so
    the old code's wasted n-th rotation disappears.  On the plain causal
    layout, steps whose whole K block sits above the diagonal are skipped
    (merging with lse = −inf is the identity, so the skip is exact);
    ``steps_run`` counts the kernels this rank actually executed
    (``rank + 1`` of ``n`` when causal — see examples/longctx_audit.py).
    """
    n = lax.axis_size(axis_name)
    my = lax.axis_index(axis_name)
    b, s_local, h, d = q.shape
    from horovod_tpu.ops.flash_attention import flash_attention_with_lse

    varying = functools.partial(lax.pcast, axis_name=axis_name, to="varying")
    out = varying(jnp.zeros((b, s_local, h, d), jnp.float32))
    lse = varying(jnp.full((b, s_local, h), NEG_INF, jnp.float32))
    steps = varying(jnp.zeros((), jnp.int32))
    perm = [(i, (i + 1) % n) for i in range(n)]

    def attend(k, v, out, lse, steps, i):
        owner = (my - i) % n

        def run(ops):
            k, v, out, lse = ops
            o_i, lse_i = flash_attention_with_lse(
                q, k, v, causal=causal, q_offset=my * s_local,
                k_offset=owner * s_local, block_q=block_q, block_k=block_k)
            return _merge_partial(out, lse, o_i, lse_i)

        if not causal:
            out, lse = run((k, v, out, lse))
            return out, lse, steps + 1
        # A block that originated at a later shard (owner > my) is entirely
        # above the causal diagonal — skip the kernel launch and the merge.
        needed = owner <= my
        out, lse = lax.cond(needed, run, lambda ops: (ops[2], ops[3]),
                            (k, v, out, lse))
        return out, lse, steps + needed.astype(jnp.int32)

    def step(carry, i):
        k, v, out, lse, steps = carry
        # Issue step i+1's ICI transfer BEFORE this step's kernel: the
        # ppermute reads only the resident buffer, never this step's
        # attention output (double buffering; audited structurally).
        k_nxt = lax.ppermute(k, axis_name, perm)
        v_nxt = lax.ppermute(v, axis_name, perm)
        out, lse, steps = attend(k, v, out, lse, steps, i)
        return (k_nxt, v_nxt, out, lse, steps), None

    if n > 1:
        (k, v, out, lse, steps), _ = lax.scan(
            step, (k, v, out, lse, steps), jnp.arange(n - 1))
    out, lse, steps = attend(k, v, out, lse, steps, n - 1)
    return out, lse, steps


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def ring_flash_attention(q, k, v, axis_name: str, causal: bool = True,
                         block_q: int = 512, block_k: int = 1024):
    """Ring attention whose per-step block attention is the fused Pallas
    flash kernel (ops/flash_attention.py), merged across steps with exact
    log-sum-exp combining.

    Versus :func:`ring_attention` (einsum blocks): per-step peak memory
    drops from O(S_local²) logits to O(S_local·D), so the maximum
    per-chip sequence shard is set by K/V residency, not by the score
    matrix.  Backward is a second ring pass over the fused Pallas backward
    kernels, driven by the globally-merged log-sum-exp — dq accumulates
    locally while dk/dv ride the ring with their K/V blocks, so the
    cotangent pass is O(S_local·D) memory too (no O(S²) transient).
    """
    out, _, _ = _ring_flash_forward(q, k, v, axis_name, causal, block_q,
                                    block_k)
    return out.astype(q.dtype)


def ring_flash_attention_stats(q, k, v, axis_name: str, causal: bool = True,
                               block_q: int = 512, block_k: int = 1024):
    """Forward-only variant returning ``(out, steps_run)`` where
    ``steps_run`` is the number of flash kernels this rank executed — the
    causal step-skipping observability hook used by the structural audit
    and the parity tests (expected ``rank + 1`` of ``n`` when causal)."""
    out, _, steps = _ring_flash_forward(q, k, v, axis_name, causal, block_q,
                                        block_k)
    return out.astype(q.dtype), steps


def _ring_flash_fwd(q, k, v, axis_name, causal, block_q, block_k):
    out, lse, _ = _ring_flash_forward(q, k, v, axis_name, causal, block_q,
                                      block_k)
    return out.astype(q.dtype), (q, k, v, out, lse)


def _ring_flash_bwd(axis_name, causal, block_q, block_k, res, g):
    from horovod_tpu.ops.flash_attention import flash_attention_backward

    q, k, v, out, lse = res
    n = lax.axis_size(axis_name)
    my = lax.axis_index(axis_name)
    b, s_local, h, d = q.shape
    # Δ = rowsum(dO·O) with the FINAL (globally merged) output — valid for
    # every block because p recomputes against the merged lse.
    delta = jnp.sum(g.astype(jnp.float32) * out, axis=-1)  # [B, S, H]
    interpret = jax.default_backend() != "tpu"

    varying = functools.partial(lax.pcast, axis_name=axis_name, to="varying")
    dq = varying(jnp.zeros(q.shape, jnp.float32))
    dk = varying(jnp.zeros(k.shape, jnp.float32))
    dv = varying(jnp.zeros(v.shape, jnp.float32))
    perm = [(i, (i + 1) % n) for i in range(n)]

    def attend(k, v, dq, dk, dv, i):
        owner = (my - i) % n

        def run(ops):
            k, v, dq, dk, dv = ops
            dq_i, dk_i, dv_i = flash_attention_backward(
                q, k, v, g, lse, delta, causal,
                my * s_local, owner * s_local, block_q, block_k, interpret)
            return (dq + dq_i.astype(jnp.float32),
                    dk + dk_i.astype(jnp.float32),
                    dv + dv_i.astype(jnp.float32))

        if not causal:
            return run((k, v, dq, dk, dv))
        # Fully-masked block (owner > my): p ≡ 0, so dq/dk/dv partials are
        # exactly zero — skip the backward kernel entirely.
        needed = owner <= my
        return lax.cond(needed, run, lambda ops: (ops[2], ops[3], ops[4]),
                        (k, v, dq, dk, dv))

    def step(carry, i):
        k, v, dk, dv, dq = carry
        # Prefetch the next K/V block before this step's kernels — the
        # transfer is independent of their outputs (double buffering).
        k_nxt = lax.ppermute(k, axis_name, perm)
        v_nxt = lax.ppermute(v, axis_name, perm)
        dq, dk, dv = attend(k, v, dq, dk, dv, i)
        # dk/dv travel WITH their K/V blocks: they accumulate this step's
        # kernel output, so their rotation necessarily trails the compute.
        dk = lax.ppermute(dk, axis_name, perm)
        dv = lax.ppermute(dv, axis_name, perm)
        return (k_nxt, v_nxt, dk, dv, dq), None

    if n > 1:
        (k, v, dk, dv, dq), _ = lax.scan(
            step, (k, v, dk, dv, dq), jnp.arange(n - 1))
    dq, dk, dv = attend(k, v, dq, dk, dv, n - 1)
    # The final rotation is dk/dv's n-th: it carries them home.  K/V rotate
    # only n−1 times (the old code paid a wasted n-th ppermute pair).
    dk = lax.ppermute(dk, axis_name, perm)
    dv = lax.ppermute(dv, axis_name, perm)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


ring_flash_attention.defvjp(_ring_flash_fwd, _ring_flash_bwd)


def make_ring_flash_attention(axis_name: str, block_q: int = 512,
                              block_k: int = 1024):
    """Adapter producing a ``TransformerConfig.attention_fn``."""
    return functools.partial(ring_flash_attention, axis_name=axis_name,
                             block_q=block_q, block_k=block_k)


# ---------------------------------------------------------------------------
# Zigzag ring attention: load-balanced causal sequence parallelism
# ---------------------------------------------------------------------------
# Plain causal ring attention is imbalanced: shard r's queries see only the
# first r+1 of n K/V shards, so at every ring step roughly half the chips
# hold a fully-masked block and idle at the next ppermute barrier.  The
# zigzag layout splits the sequence into 2n chunks and gives rank r chunks
# (r, 2n−1−r) — one early, one late — so every rank does the same
# (2n+1)·c²-sized triangle of work in total and near-uniform work per step.
# The flash kernel's dynamic diagonal bound (ops/flash_attention.py) turns
# the masked half-pairs into ~zero-cost launches.


def zigzag_permutation(seq_len: int, n: int):
    """Global index order that makes contiguous shard r hold zigzag chunks
    (r, 2n−1−r).  Apply as ``x[:, perm]`` before a P(None, axis) shard."""
    c, rem = divmod(seq_len, 2 * n)
    if rem or c == 0:
        raise ValueError(
            f"zigzag needs seq_len divisible by 2·n ({seq_len} vs n={n})")
    idx = []
    for r in range(n):
        idx.extend(range(r * c, (r + 1) * c))
        idx.extend(range((2 * n - 1 - r) * c, (2 * n - r) * c))
    return np.asarray(idx)


def zigzag_inverse_permutation(seq_len: int, n: int):
    """Inverse of :func:`zigzag_permutation` (restores natural order)."""
    perm = zigzag_permutation(seq_len, n)
    inv = np.empty_like(perm)
    inv[perm] = np.arange(seq_len)
    return inv


def zigzag_positions(s_local: int, axis_name: str):
    """Global sequence positions of this rank's zigzag shard ([s_local]).

    For models with position-dependent layers (RoPE): pass as
    ``Transformer(..., positions=...)`` so embeddings match the layout.
    """
    n = lax.axis_size(axis_name)
    r = lax.axis_index(axis_name)
    c = s_local // 2
    lo = r * c + jnp.arange(c)
    hi = (2 * n - 1 - r) * c + jnp.arange(c)
    return jnp.concatenate([lo, hi])


def _zigzag_chunks(x, c):
    return x[:, :c], x[:, c:]


def _zigzag_flash_forward(q, k, v, axis_name, causal, block_q, block_k):
    """Forward zigzag ring pass; returns (out_f32, merged lse), local order
    [chunk_lo ∥ chunk_hi]."""
    n = lax.axis_size(axis_name)
    r = lax.axis_index(axis_name)
    b, s_local, h, d = q.shape
    if s_local % 2:
        raise ValueError(f"zigzag shard length must be even, got {s_local}")
    c = s_local // 2
    from horovod_tpu.ops.flash_attention import flash_attention_with_lse

    varying = functools.partial(lax.pcast, axis_name=axis_name, to="varying")
    outs = [varying(jnp.zeros((b, c, h, d), jnp.float32)) for _ in range(2)]
    lses = [varying(jnp.full((b, c, h), NEG_INF, jnp.float32))
            for _ in range(2)]
    q_halves = _zigzag_chunks(q, c)
    q_offs = (r * c, (2 * n - 1 - r) * c)
    perm = [(i, (i + 1) % n) for i in range(n)]

    def attend(k, v, out0, lse0, out1, lse1, i):
        owner = (r - i) % n
        k_offs = (owner * c, (2 * n - 1 - owner) * c)
        k_halves = _zigzag_chunks(k, c)
        v_halves = _zigzag_chunks(v, c)
        acc = [[out0, lse0], [out1, lse1]]
        for qi in range(2):
            for ki in range(2):
                o_p, lse_p = flash_attention_with_lse(
                    q_halves[qi], k_halves[ki], v_halves[ki], causal=causal,
                    q_offset=q_offs[qi], k_offset=k_offs[ki],
                    block_q=block_q, block_k=block_k)
                acc[qi][0], acc[qi][1] = _merge_partial(
                    acc[qi][0], acc[qi][1], o_p, lse_p)
        return acc[0][0], acc[0][1], acc[1][0], acc[1][1]

    def step(carry, i):
        k, v, out0, lse0, out1, lse1 = carry
        # Prefetch before the half-pair kernels (double buffering); masked
        # half-pairs are already ~free via the kernel's diagonal bound.
        k_nxt = lax.ppermute(k, axis_name, perm)
        v_nxt = lax.ppermute(v, axis_name, perm)
        out0, lse0, out1, lse1 = attend(k, v, out0, lse0, out1, lse1, i)
        return (k_nxt, v_nxt, out0, lse0, out1, lse1), None

    out0, lse0, out1, lse1 = outs[0], lses[0], outs[1], lses[1]
    if n > 1:
        (k, v, out0, lse0, out1, lse1), _ = lax.scan(
            step, (k, v, out0, lse0, out1, lse1), jnp.arange(n - 1))
    # Final step unrolled: no next block to fetch, no wasted rotation.
    out0, lse0, out1, lse1 = attend(k, v, out0, lse0, out1, lse1, n - 1)
    return (jnp.concatenate([out0, out1], axis=1),
            jnp.concatenate([lse0, lse1], axis=1))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def zigzag_ring_flash_attention(q, k, v, axis_name: str, causal: bool = True,
                                block_q: int = 512, block_k: int = 1024):
    """Load-balanced causal ring attention over zigzag-sharded sequences.

    Inputs are this rank's zigzag shard ([B, 2c, H, D], chunks (r, 2n−1−r)
    concatenated — see :func:`zigzag_permutation`); output is the matching
    local shard of the exact attention result.  Numerics are identical to
    :func:`ring_flash_attention`; only the work distribution changes — with
    causal masking every rank streams the same number of unmasked K/V
    blocks, instead of rank n−1 doing n× rank 0's work.
    """
    out, _ = _zigzag_flash_forward(q, k, v, axis_name, causal, block_q,
                                   block_k)
    return out.astype(q.dtype)


def _zigzag_fwd(q, k, v, axis_name, causal, block_q, block_k):
    out, lse = _zigzag_flash_forward(q, k, v, axis_name, causal, block_q,
                                     block_k)
    return out.astype(q.dtype), (q, k, v, out, lse)


def _zigzag_bwd(axis_name, causal, block_q, block_k, res, g):
    from horovod_tpu.ops.flash_attention import flash_attention_backward

    q, k, v, out, lse = res
    n = lax.axis_size(axis_name)
    r = lax.axis_index(axis_name)
    b, s_local, h, d = q.shape
    c = s_local // 2
    delta = jnp.sum(g.astype(jnp.float32) * out, axis=-1)   # [B, 2c, H]
    interpret = jax.default_backend() != "tpu"

    varying = functools.partial(lax.pcast, axis_name=axis_name, to="varying")
    half = (b, c, h, d)
    dqs = [varying(jnp.zeros(half, jnp.float32)) for _ in range(2)]
    dks = [varying(jnp.zeros(half, jnp.float32)) for _ in range(2)]
    dvs = [varying(jnp.zeros(half, jnp.float32)) for _ in range(2)]
    q_halves = _zigzag_chunks(q, c)
    g_halves = _zigzag_chunks(g, c)
    lse_halves = _zigzag_chunks(lse, c)
    delta_halves = _zigzag_chunks(delta, c)
    q_offs = (r * c, (2 * n - 1 - r) * c)
    perm = [(i, (i + 1) % n) for i in range(n)]

    rot = functools.partial(lax.ppermute, axis_name=axis_name, perm=perm)

    def attend(k, v, dk_halves, dv_halves, dq_halves, i):
        dk_halves, dv_halves = list(dk_halves), list(dv_halves)
        dq_halves = list(dq_halves)
        owner = (r - i) % n
        k_offs = (owner * c, (2 * n - 1 - owner) * c)
        k_halves = _zigzag_chunks(k, c)
        v_halves = _zigzag_chunks(v, c)
        for qi in range(2):
            for ki in range(2):
                dq_p, dk_p, dv_p = flash_attention_backward(
                    q_halves[qi], k_halves[ki], v_halves[ki], g_halves[qi],
                    lse_halves[qi], delta_halves[qi], causal,
                    q_offs[qi], k_offs[ki], block_q, block_k, interpret)
                dq_halves[qi] = dq_halves[qi] + dq_p.astype(jnp.float32)
                dk_halves[ki] = dk_halves[ki] + dk_p.astype(jnp.float32)
                dv_halves[ki] = dv_halves[ki] + dv_p.astype(jnp.float32)
        return tuple(dk_halves), tuple(dv_halves), tuple(dq_halves)

    def step(carry, i):
        k, v, dk_halves, dv_halves, dq_halves = carry
        # Prefetch the next K/V block before this step's kernels — the
        # transfer is independent of their outputs (double buffering).
        k_nxt, v_nxt = rot(k), rot(v)
        dk_halves, dv_halves, dq_halves = attend(
            k, v, dk_halves, dv_halves, dq_halves, i)
        # dk/dv travel WITH their K/V blocks: they accumulate this step's
        # kernel output, so their rotation necessarily trails the compute.
        return (k_nxt, v_nxt, tuple(map(rot, dk_halves)),
                tuple(map(rot, dv_halves)), dq_halves), None

    dk_halves, dv_halves, dq_halves = tuple(dks), tuple(dvs), tuple(dqs)
    if n > 1:
        (k, v, dk_halves, dv_halves, dq_halves), _ = lax.scan(
            step, (k, v, dk_halves, dv_halves, dq_halves), jnp.arange(n - 1))
    dk_halves, dv_halves, dq_halves = attend(
        k, v, dk_halves, dv_halves, dq_halves, n - 1)
    # dk/dv's n-th rotation carries them home; K/V rotate only n−1 times.
    dk_halves = tuple(map(rot, dk_halves))
    dv_halves = tuple(map(rot, dv_halves))
    cat = functools.partial(jnp.concatenate, axis=1)
    return (cat(dq_halves).astype(q.dtype), cat(dk_halves).astype(k.dtype),
            cat(dv_halves).astype(v.dtype))


zigzag_ring_flash_attention.defvjp(_zigzag_fwd, _zigzag_bwd)


def make_zigzag_ring_flash_attention(axis_name: str, block_q: int = 512,
                                     block_k: int = 1024):
    """Adapter producing a ``TransformerConfig.attention_fn`` (pair with
    ``positions=zigzag_positions(...)`` so RoPE matches the layout)."""
    return functools.partial(zigzag_ring_flash_attention,
                             axis_name=axis_name, block_q=block_q,
                             block_k=block_k)
