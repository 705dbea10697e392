"""Fused flash attention — Pallas TPU kernel for the attention hot op.

The reference has no attention code (SURVEY §2.9); this kernel exists because
the task's long-context path must not materialize S×S logits.  Dense
attention (models/transformer.py) is O(S²) HBM; this kernel streams K/V
blocks through VMEM with an online softmax, so HBM traffic is O(S·D) and the
block matmuls run back-to-back on the MXU — the standard flash-attention
scheme expressed as a Pallas grid over (batch·heads, query-blocks).

Integration points:
* ``make_flash_attention()`` → drop-in ``TransformerConfig.attention_fn``.
* ``parallel/ring_attention.py`` can use it per ring step (each step is
  exactly one q-block × local-K/V attention with carried (m, l, acc)).

Backward is fused too, and is ONE kernel (grid over k-blocks, then q-blocks):
per tile it recomputes the probabilities from the forward's saved
log-sum-exp — p = exp(s − lse) — and, with Δ = rowsum(dO·O), forms
ds = p·(dO·vᵀ − Δ) once and feeds dv, dk and dq from it: the five matrix
products a tile the backward needs, no more (two passes, one for dq and
one for dk/dv, each formed q·kᵀ and dO·vᵀ: seven).  dk/dv accumulate in
their resident output blocks; dq, whose rows are revisited once per
k-block, in a VMEM-resident f32 block of the head's whole q length.  No
O(S²) tensor is ever materialized in HBM in either direction.  The kernel
takes lse/Δ as explicit inputs so ring attention can drive it per ring step
with globally-merged statistics.

Non-TPU backends fall back to Pallas interpret mode (tests) so numerics are
identical everywhere.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from horovod_tpu.utils import profiling

NEG_INF = -1e30
LOG2E = 1.4426950408889634  # log2(e): folded into the q scale so the
# online softmax runs on exp2 — the VPU's native exponential — instead
# of exp (which lowers to a multiply + exp2 per element).  ln2 factors
# re-enter only at block boundaries (lse output, dk finish), never on
# the hot [bq, sub_k] tiles.
LN2 = 0.6931471805599453


def _sub_bounds(k_len, q_min, q_max, ks_min, sub_k, nsub, causal):
    """The forward kernel's sub-tile split bounds: ``hi``
    is the causal sweep end (tiles past the diagonal contribute p == 0),
    ``interior_end`` the mask-free prefix (entirely below the diagonal and
    inside the valid K range)."""
    if causal:
        hi = jnp.clip((q_max - ks_min) // sub_k + 1, 0, nsub)
    else:
        hi = nsub
    valid_end = (k_len - ks_min) // sub_k
    if causal:
        interior_end = jnp.minimum((q_min - ks_min + 1) // sub_k, valid_end)
    else:
        interior_end = valid_end
    return hi, jnp.clip(interior_end, 0, hi)


def _flash_kernel(meta_ref, q_ref, k_ref, v_ref, o_ref, lse_ref, m_ref,
                  l_ref, *, block_q: int, block_k: int, sub_k: int,
                  num_k_blocks: int, causal: bool, scale: float):
    """One (batch·head, q-block, K-super-tile) program: online softmax.

    Two-level streaming: the grid's K axis moves (block_k, D) SUPER tiles
    HBM→VMEM double-buffered (few grid steps → the per-step fixed cost is
    amortized), while an in-kernel fori loop computes over (block_q,
    sub_k) SUB tiles so the [bq, sub_k] intermediates stay small.  Scoped
    VMEM is one super tile of K/V plus the sub-tile intermediates —
    independent of S.

    meta_ref (SMEM int32[3]): [q_offset, k_offset, k_len] — global position
    offsets (sequence parallelism) and the unpadded K length.

    The sub-tile loop is SPLIT: an interior prefix (entirely below the
    causal diagonal and inside the valid K range) runs a mask-free body —
    no per-element iota/compare/select (VPU work bracketing the MXU
    matmuls) — and only the diagonal/boundary suffix pays for masking.

    ``m_ref``/``l_ref`` are carry storage in the lse layout (sublane-
    replicated (8, block_q)); callers discard them.  ``o_ref`` is f32
    (accumulation precision); the caller casts.
    """
    qi, ki = pl.program_id(1), pl.program_id(2)
    nsub = block_k // sub_k

    @pl.when(ki == 0)
    def _init():
        m_ref[0] = jnp.full_like(m_ref[0], NEG_INF)
        l_ref[0] = jnp.zeros_like(l_ref[0])
        o_ref[0] = jnp.zeros_like(o_ref[0])

    q_min = meta_ref[0] + qi * block_q
    q_max = q_min + block_q - 1
    ks_min = meta_ref[1] + ki * block_k   # super-tile base position
    # Sub-tile bounds (scalar arithmetic on SMEM values):
    hi, interior_end = _sub_bounds(meta_ref[2], q_min, q_max, ks_min,
                                   sub_k, nsub, causal)

    # The s matmul runs on INPUT-dtype operands: under JAX's default TPU
    # matmul precision an f32×f32 dot already executes as a single bf16
    # MXU pass (measured — the dtype of the operands does not change the
    # MXU rate), so what the input-dtype form buys is skipping the
    # per-tile k up-cast VPU pass.  The scale folds into q (together
    # with log2(e) — scores live in the log2 domain so the hot
    # exponentials are exp2, see LOG2E) with one rounding to the input
    # dtype (f32 inputs round-trip exactly).
    q = (q_ref[0].astype(jnp.float32) * (scale * LOG2E)).astype(q_ref.dtype)

    def body(si, carry, masked):
        m, l = carry
        k = k_ref[0, pl.ds(si * sub_k, sub_k), :]         # [sk, D]
        v = v_ref[0, pl.ds(si * sub_k, sub_k), :]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)           # [bq, sk]
        if masked:
            q_pos = (q_min + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, sub_k), 0))
            k_pos = (ks_min + si * sub_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, sub_k), 1))
            mask = k_pos < meta_ref[2]                    # padding mask
            if causal:
                mask = jnp.logical_and(mask, q_pos >= k_pos)
            s = jnp.where(mask, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp2(s - m_new)
        if masked:
            p = jnp.where(mask, p, 0.0)
        corr = jnp.exp2(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=-1, keepdims=True)
        # p stays f32 for the PV matmul: rounding it to bf16 costs a VPU
        # pass over the [bq, sub_k] tile that measured LARGER than any
        # MXU saving (fwd 0.98→1.28 ms on the A/B) — under JAX's default
        # TPU matmul precision the f32×(up-cast) v dot already executes
        # as a single bf16 MXU pass with f32 accumulation.
        pv = jax.lax.dot_general(
            p, v.astype(jnp.float32), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        o_ref[0] = o_ref[0] * corr + pv
        return m_new, l_new

    def _writeback(m, l):
        m_ref[0] = jnp.broadcast_to(m[:, 0][None, :], m_ref.shape[1:])
        l_ref[0] = jnp.broadcast_to(l[:, 0][None, :], l_ref.shape[1:])

    if nsub == 1:
        # Static single-tile case (the measured optimum): straight-line
        # bodies under pl.when — a dynamic-bound fori_loop here defeats
        # Mosaic's scheduling and costs ~5 MFU points (docs/benchmarks.md).
        run = hi >= 1
        interior = interior_end >= 1

        @pl.when(jnp.logical_and(run, interior))
        def _one_interior():
            _writeback(*body(0, (m_ref[0, 0, :][:, None],
                                 l_ref[0, 0, :][:, None]), masked=False))

        @pl.when(jnp.logical_and(run, jnp.logical_not(interior)))
        def _one_boundary():
            _writeback(*body(0, (m_ref[0, 0, :][:, None],
                                 l_ref[0, 0, :][:, None]), masked=True))
    else:
        # Static UNROLL over sub-tiles (round 5, replacing the dynamic
        # fori_loop): each sub-tile is a straight-line body under pl.when
        # guards with the m/l carry staged through its VMEM refs, so
        # Mosaic sees independent MXU matmuls (s_{i+1} depends only on
        # q/k) it can schedule against the previous sub-tile's VPU
        # softmax chain — the VPU work is ~2-3x the MXU time per tile
        # and a dynamic-bound loop serialized them.
        for si in range(nsub):
            @pl.when(si < interior_end)
            def _interior(si=si):
                _writeback(*body(si, (m_ref[0, 0, :][:, None],
                                      l_ref[0, 0, :][:, None]),
                                 masked=False))

            @pl.when(jnp.logical_and(si >= interior_end, si < hi))
            def _boundary(si=si):
                _writeback(*body(si, (m_ref[0, 0, :][:, None],
                                      l_ref[0, 0, :][:, None]),
                                 masked=True))

    @pl.when(ki == num_k_blocks - 1)
    def _finish():
        m = m_ref[0, 0, :][:, None]
        l = l_ref[0, 0, :][:, None]
        o_ref[0] = o_ref[0] / jnp.maximum(l, 1e-30)
        # log-sum-exp per query row (NEG_INF where a row attended to
        # nothing) — lets callers combine partial attentions exactly
        # (ring attention).  m carries log2-domain scores (LOG2E fold),
        # so the NATURAL-log contract converts here: lse = m·ln2 +
        # log(l) — a per-row op at block end, off the hot tiles.
        # Stored sublane-replicated (8, block_q): Mosaic requires the
        # last two block dims be (8k, 128k)-tileable, which a
        # (1, block_q) row is not.
        lse = jnp.where(l > 0, m * LN2 + jnp.log(jnp.maximum(l, 1e-30)),
                        NEG_INF)
        lse_ref[0] = jnp.broadcast_to(lse[:, 0][None, :], lse_ref.shape[1:])


def _pad_to(x, axis, multiple):
    n = x.shape[axis]
    pad = (-n) % multiple
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


# The kernels unroll the sub-tile sweep statically (a dynamic-bound
# fori_loop defeats Mosaic's scheduling, docs/benchmarks.md round 5), so
# each extra sub-tile emits TWO more guarded matmul bodies (interior +
# boundary).  Past this many sub-tiles the code-size/compile-time bill
# grows with no measured MFU return — warn instead of silently bloating.
MAX_SUB_TILES = 8


def _sub_fit(block: int, sub: int) -> tuple[int, int]:
    """Clamp the compute sub-tile to the (super) block and make the block a
    multiple of it.  Warns when the resulting unroll factor exceeds
    :data:`MAX_SUB_TILES`."""
    sub = min(sub, block)
    block = max(block // sub, 1) * sub
    nsub = block // sub
    if nsub > MAX_SUB_TILES:
        import warnings

        warnings.warn(
            f"flash attention: block={block} with sub={sub} unrolls "
            f"{nsub} sub-tiles (> {MAX_SUB_TILES}); the static unroll "
            f"emits {2 * nsub} guarded matmul bodies — expect code-size "
            f"and compile-time bloat with no MFU return. Raise sub= or "
            f"lower block_q=/block_k= so block/sub <= {MAX_SUB_TILES}.",
            stacklevel=2)
    return block, sub


# Mosaic gives one kernel 16 MiB of scoped VMEM by default on the v5e
# (device_kind "TPU v5 lite").  The forward lives under that default; the
# backward asks for a limit of its own (_bwd_vmem_limit_bytes below).
# Confirmed on that device with libtpu 0.0.34: at the default tiles the
# forward compiles and runs at S=1024/B=8, S=8192/B=4 and S=32768/B=1 with
# remat (d=128, bf16; CHANGES.md PR 21), forward and the one backward pass
# at S=2048/B=8, S=4096/B=4 and S=16384/B=1 (PR 27).  The
# fit budget sits below the limit because this estimate cannot see
# Mosaic's scheduling windows — exactly how the hand-set block_k=4096
# passed review at S=8192 and then overflowed the remat backward at
# S=32768 (docs/benchmarks.md round 5).  Requested blocks whose estimated
# resident set exceeds the budget are halved with a warning instead of
# failing inside Pallas.  Another device kind needs its own confirmation.
VMEM_FIT_BUDGET_MB = 13.0
_VMEM_MIN_BLOCK = 128
_vmem_clamp_warned: set = set()


def _vmem_estimate_bytes(block_q: int, block_k: int, d: int,
                         sub: int = 1024, itemsize: int = 2) -> int:
    """Resident-set model that sizes the tiles (the entry points' clamp
    and ``ContextPlan``'s), priced for a pass of the forward's layout
    that also streams dO and Δ — more than the forward holds, on purpose:
    double-buffered K/V streaming super tiles, q/dO tiles, the f32
    accumulator, the sublane-replicated lse/Δ rows, and two live
    [block_q, sub] f32 compute tiles (Mosaic fuses the elementwise chain,
    so s/p share ~two buffers in practice).  The backward's resident set,
    whose dq accumulator grows with S_q, is
    :func:`_bwd_vmem_estimate_bytes`."""
    sub_k = min(sub, max(block_k, 1))
    kv = 2 * 2 * block_k * d * itemsize          # K+V, double-buffered
    qdo = 2 * 2 * block_q * d * itemsize         # q + dO tiles
    acc = block_q * d * 4                        # f32 dq/o accumulator
    stats = 2 * 8 * block_q * 4                  # lse + Δ, sublane-replicated
    tiles = 2 * block_q * sub_k * 4              # live f32 compute tiles
    return kv + qdo + acc + stats + tiles


# The backward's dq accumulator is the one VMEM term that grows with the
# sequence (S_q·d·4 bytes: 8 MiB at S=16384, d=128), so that call asks
# Mosaic for its own limit (``vmem_limit_bytes``) instead of living under
# the 16 MiB default.  A v5e core has 128 MiB of VMEM (jax's own
# ``pallas.tpu.get_tpu_info`` table; that call needs an attached TPU, this
# file must also trace for a described one).  One call asks for at most
# three quarters of it; a longer q is cut into row ranges, one call each.
VMEM_PHYSICAL_MB = 128.0
_BWD_VMEM_ASK_MAX_BYTES = int(0.75 * VMEM_PHYSICAL_MB * 2 ** 20)


def _bwd_vmem_estimate_bytes(block_q: int, block_k: int, d: int, s_q: int,
                             sub: int = 1024, itemsize: int = 2) -> int:
    """Resident-set model of the one backward pass (:func:`_bwd_kernel`):
    double-buffered Q/dO super tiles and K/V tiles, the sublane-replicated
    lse/Δ rows, the double-buffered f32 dk/dv blocks, three live
    [sub_q, block_k] f32 compute tiles (Mosaic fuses the elementwise
    chain: the v5e compiler's own scoped allocation at the default tiles
    is 15.7 MiB at S=2048 where this says 17.1), and the one term that
    grows with the sequence: the f32 dq accumulator over the ``s_q`` padded
    q rows of one call, single-buffered.  ``block_k`` is the kernel's own
    k tile (≤ 1024 at the defaults)."""
    sub_q = min(sub, max(block_q, 1))
    qdo = 2 * 2 * block_q * d * itemsize         # q + dO, double-buffered
    kv = 2 * 2 * block_k * d * itemsize          # K + V, double-buffered
    stats = 2 * 2 * 8 * block_q * 4              # lse + Δ, double-buffered
    dkv = 2 * 2 * block_k * d * 4                # f32 dk + dv blocks
    tiles = 3 * sub_q * block_k * 4              # live f32 compute tiles
    dq = s_q * d * 4                             # the call's accumulator
    return qdo + kv + stats + dkv + tiles + dq


def _bwd_vmem_limit_bytes(block_q: int, block_k: int, d: int, s_q: int,
                          sub: int = 1024, itemsize: int = 2) -> int:
    """What the backward call asks Mosaic for: its estimate and a quarter
    more (the estimate cannot see the scheduler's windows), never under
    the 16 MiB default."""
    est = _bwd_vmem_estimate_bytes(block_q, block_k, d, s_q, sub, itemsize)
    return max(16 * 2 ** 20, est + est // 4)


def _bwd_q_rows_per_call(block_q: int, block_k: int, d: int, s_q: int,
                         sub: int = 1024, itemsize: int = 2) -> int:
    """How many of the ``s_q`` padded q rows one backward call takes: all
    of them while the limit it would ask for stays within
    ``_BWD_VMEM_ASK_MAX_BYTES``, else the fewest equal ranges of whole q
    blocks that do (never under one block)."""
    blocks = s_q // block_q
    calls = 1
    while calls < blocks and _bwd_vmem_limit_bytes(
            block_q, block_k, d, -(-blocks // calls) * block_q, sub,
            itemsize) > _BWD_VMEM_ASK_MAX_BYTES:
        calls += 1
    return -(-blocks // calls) * block_q


def clamp_blocks_to_vmem(block_q: int, block_k: int, d: int,
                         sub: int = 1024, itemsize: int = 2,
                         where: str = "flash_attention") -> tuple[int, int]:
    """Halve (block_k first — the K/V tiles dominate — then block_q, never
    below 128) until :func:`_vmem_estimate_bytes` fits the VMEM budget.
    One-line rank-0 warning per distinct clamp; ``ContextPlan`` routes
    through the same estimate so planned configs never trip it."""
    bq, bk = block_q, block_k
    budget = int(VMEM_FIT_BUDGET_MB * 2 ** 20)
    while _vmem_estimate_bytes(bq, bk, d, sub, itemsize) > budget:
        if bk > _VMEM_MIN_BLOCK and bk >= bq:
            bk //= 2
        elif bq > _VMEM_MIN_BLOCK:
            bq //= 2
        elif bk > _VMEM_MIN_BLOCK:
            bk //= 2
        else:
            break
    if (bq, bk) != (block_q, block_k):
        key = (where, block_q, block_k, bq, bk, d, itemsize)
        if key not in _vmem_clamp_warned:
            _vmem_clamp_warned.add(key)
            if jax.process_index() == 0:
                import warnings

                warnings.warn(
                    f"{where}: block_q/block_k={block_q}/{block_k} at d={d} "
                    f"itemsize={itemsize} estimated over the "
                    f"{VMEM_FIT_BUDGET_MB:g} MiB VMEM fit budget — clamped "
                    f"to {bq}/{bk} (derive kernel params from "
                    f"ops.schedule_plan.plan_context instead of "
                    f"hand-setting them).", stacklevel=3)
    return bq, bk


def _flash_forward(q, k, v, causal, q_offset, k_offset, block_q, block_k,
                   interpret, *, sub: int = 1024, with_lse: bool = False):
    b, s_q, h, d = q.shape
    s_k = k.shape[1]
    scale = d ** -0.5
    block_k, sub_k = _sub_fit(block_k, sub)
    # [B, S, H, D] → [B·H, S, D]
    def to_bh(x):
        return x.transpose(0, 2, 1, 3).reshape(b * h, x.shape[1], d)

    qb = _pad_to(to_bh(q), 1, block_q)
    meta = jnp.asarray(
        [jnp.asarray(q_offset, jnp.int32),
         jnp.asarray(k_offset, jnp.int32),
         jnp.asarray(k_offset, jnp.int32) + s_k], jnp.int32)
    num_q_blocks = qb.shape[1] // block_q
    carry_shape = jax.ShapeDtypeStruct((qb.shape[0], 8, qb.shape[1]),
                                       jnp.float32)

    kb = _pad_to(to_bh(k), 1, block_k)
    vb = _pad_to(to_bh(v), 1, block_k)
    num_k_blocks = kb.shape[1] // block_k
    kernel = functools.partial(
        _flash_kernel, block_q=block_q, block_k=block_k, sub_k=sub_k,
        num_k_blocks=num_k_blocks, causal=causal, scale=scale)
    out, lse, _m, _l = pl.pallas_call(
        kernel,
        grid=(b * h, num_q_blocks, num_k_blocks),
        in_specs=[
            pl.BlockSpec((3,), lambda bh, qi, ki: (0,),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((1, block_q, d),
                         lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, block_k, d),
                         lambda bh, qi, ki: (bh, ki, 0)),
            pl.BlockSpec((1, block_k, d),
                         lambda bh, qi, ki: (bh, ki, 0)),
        ],
        out_specs=(
            pl.BlockSpec((1, block_q, d),
                         lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, 8, block_q),
                         lambda bh, qi, ki: (bh, 0, qi)),
            pl.BlockSpec((1, 8, block_q),
                         lambda bh, qi, ki: (bh, 0, qi)),
            pl.BlockSpec((1, 8, block_q),
                         lambda bh, qi, ki: (bh, 0, qi)),
        ),
        out_shape=(
            jax.ShapeDtypeStruct(qb.shape, jnp.float32),  # f32 acc
            carry_shape,   # lse
            carry_shape,   # m carry (discarded)
            carry_shape,   # l carry (discarded)
        ),
        # outer axes parallel, the innermost the sequential K sweep
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name=profiling.FLASH_FWD,
    )(meta, qb, kb, vb)
    out = out.astype(q.dtype)
    out = out[:, :s_q].reshape(b, h, s_q, d)
    out = out.transpose(0, 2, 1, 3)
    if with_lse:
        # [B·H, 8, S] (sublane-replicated) → [B, S, H]
        lse = lse[:, 0, :s_q].reshape(b, h, s_q).transpose(0, 2, 1)
        return out, lse
    return out


def _bwd_kernel(meta_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dq_ref, dk_ref, dv_ref, *, block_q: int, block_k: int,
                sub_q: int, num_q_blocks: int, causal: bool, scale: float):
    """One (batch·head, k-block, Q-super-tile) program of the one backward
    pass: per tile s, p, dp and ds = p·(dp − Δ) are formed ONCE and feed
    all three gradients — dv += pᵀ·dO, dk += dsᵀ·(q·scale), dq += ds·k.

    The forward's layout with the roles swapped: the grid streams
    (block_q, D) Q/dO super tiles (lse/Δ alongside) double-buffered while
    the in-kernel loop computes (sub_q, block_k) sub tiles.  dk/dv are f32
    output blocks that stay VMEM-resident across the qi sweep.  dq's rows
    are revisited once per k-block, so its f32 output block is the head's
    WHOLE padded q length (index map constant in ki and qi): resident for
    the head, zeroed at the head's first grid step (so a call in which no
    tile runs — K wholly after Q — still returns zeros), written back
    once a head.  That block is what grows with S_q;
    :func:`_bwd_vmem_estimate_bytes` prices it.

    Sub-tile split, from the K block's point of view: q sub-tiles entirely
    ABOVE the diagonal (q_sub_max < k_min) are skipped; the diagonal band
    runs masked; q sub-tiles entirely below (q_sub_min >= k_max, with the
    K block fully valid) run mask-free — padded q rows are safe maskless
    (lse = +1e30 ⇒ p = 0).
    """
    ki, qi = pl.program_id(1), pl.program_id(2)
    nsub = block_q // sub_q

    @pl.when(jnp.logical_and(ki == 0, qi == 0))
    def _init_dq():
        dq_ref[0] = jnp.zeros_like(dq_ref[0])

    @pl.when(qi == 0)
    def _init():
        dk_ref[0] = jnp.zeros_like(dk_ref[0])
        dv_ref[0] = jnp.zeros_like(dv_ref[0])

    qs_min = meta_ref[0] + qi * block_q   # super-tile base position
    k_min = meta_ref[1] + ki * block_k
    k_max = k_min + block_k - 1
    if causal:
        # First sub-tile whose q_sub_max >= k_min.
        lo = jnp.clip((k_min - qs_min) // sub_q, 0, nsub)
        # First sub-tile with q_sub_min >= k_max (mask-free from there on).
        int_start = jnp.clip(-((qs_min - k_max) // sub_q), 0, nsub)
    else:
        lo = jnp.int32(0)
        int_start = jnp.int32(0)
    k_valid = k_max < meta_ref[2]
    # An invalid K block (padding columns) needs the padding mask in every
    # sub-tile: push the interior start past the end.
    int_start = jnp.where(k_valid, int_start, nsub)
    int_start = jnp.maximum(int_start, lo)

    k = k_ref[0]                                          # [bk, D]
    v = v_ref[0]

    def body(si, masked):
        # Input-dtype matmul operands with f32 accumulation — see
        # _flash_kernel.  The scale-fold rounding (incl. LOG2E) matches
        # the forward's, so s — hence p = exp2(s − lse·log2e) — recomputes
        # consistently; the saved lse arrives in natural units (the public
        # ring-attention contract) and converts per row.  The fold's log2e
        # surplus on dk is repaid by the ·ln2 in _finish (dv uses p
        # directly and needs none; dq takes plain ``scale`` after the
        # call, with its cast).
        q = (q_ref[0, pl.ds(si * sub_q, sub_q), :].astype(jnp.float32)
             * (scale * LOG2E)).astype(q_ref.dtype)       # [sq, D]
        do = do_ref[0, pl.ds(si * sub_q, sub_q), :]
        lse = lse_ref[0, 0, pl.ds(si * sub_q, sub_q)][:, None]  # natural
        lse2 = lse * LOG2E
        delta = delta_ref[0, 0, pl.ds(si * sub_q, sub_q)][:, None]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if masked:
            row_ok = lse > NEG_INF / 2                    # rows that attended
            q_pos = (qs_min + si * sub_q + jax.lax.broadcasted_iota(
                jnp.int32, (sub_q, block_k), 0))
            k_pos = (k_min + jax.lax.broadcasted_iota(
                jnp.int32, (sub_q, block_k), 1))
            mask = k_pos < meta_ref[2]
            if causal:
                mask = jnp.logical_and(mask, q_pos >= k_pos)
            p = jnp.where(jnp.logical_and(mask, row_ok),
                          jnp.exp2(s - lse2), 0.0)
        else:
            p = jnp.exp2(s - lse2)
        # p stays f32 (mirroring the forward's PV choice); do up-casts for
        # this one dot since lax.dot_general needs matching dtypes.
        dv_ref[0] += jax.lax.dot_general(
            p, do.astype(jnp.float32), (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = (p * (dp - delta)).astype(q_ref.dtype)       # one cast, two uses
        # q is pre-scaled (incl. LOG2E), so this is d s/d k contracted
        # with ds up to the log2e surplus repaid in _finish.
        dk_ref[0] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        rows = pl.ds(pl.multiple_of(qi * block_q + si * sub_q, sub_q), sub_q)
        dq_ref[0, rows, :] += jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    if nsub == 1:
        # Static single-tile case: straight-line pl.when (see _flash_kernel).
        run = lo < 1
        interior = int_start < 1

        @pl.when(jnp.logical_and(run, jnp.logical_not(interior)))
        def _one_boundary():
            body(0, masked=True)

        @pl.when(interior)
        def _one_interior():
            body(0, masked=False)
    else:
        # Static unroll (see _flash_kernel); the three gradients accumulate
        # in refs so sub-tile bodies are independent.  Masked band first
        # (lo <= si < int_start), mask-free tail (si >= int_start).
        for si in range(nsub):
            @pl.when(jnp.logical_and(si >= lo, si < int_start))
            def _boundary(si=si):
                body(si, masked=True)

            @pl.when(si >= int_start)
            def _interior(si=si):
                body(si, masked=False)

    @pl.when(qi == num_q_blocks - 1)
    def _finish():
        # The q fold carried scale·log2e; dk needs plain scale — repay
        # the log2e once per resident block (log2e·ln2 == 1).
        dk_ref[0] = dk_ref[0] * LN2


def flash_attention_backward(q, k, v, dout, lse, delta, causal,
                             q_offset, k_offset, block_q, block_k,
                             interpret, sub: int = 1024):
    """Fused backward: (dq, dk, dv) from saved lse and Δ = rowsum(dO·O),
    one kernel, one sweep over the tiles (:func:`_bwd_kernel`).

    ``lse``/``delta``: [B, S_q, H] float32 — from ``_flash_forward(...,
    with_lse=True)`` (or the ring's globally-merged statistics), so the
    per-block probabilities recompute exactly without an O(S²) tensor.
    """
    b, s_q, h, d = q.shape
    s_k = k.shape[1]
    scale = d ** -0.5
    # Clamp to the actual sequence lengths (like the public forward
    # wrappers): ring/zigzag drive this entry per ring step with SHARD
    # lengths — without the clamp the 512/1024 defaults would pad small
    # shards up to the block size and double the backward work.
    block_q = min(block_q, max(s_q, 1))
    block_k = min(block_k, max(s_k, 1))
    block_q, block_k = clamp_blocks_to_vmem(
        block_q, block_k, d, sub, q.dtype.itemsize,
        where="flash_attention_backward")
    block_q, sub_q = _sub_fit(block_q, sub)
    block_k, sub_k = _sub_fit(block_k, sub)
    # The k tile is BOTH the resident dk/dv accumulator width and the
    # compute-tile width (intermediates are [sub_q, k_tile]) — cap it near
    # 1024 (keeping the s/p/dp/ds buffers ~2 MB) instead of letting it
    # scale with the streaming super-tile chosen for the forward, while
    # keeping it a divisor of the padded K length.
    bk = sub_k
    while (bk * 2 <= min(block_k, max(1024, sub_k))
           and block_k % (bk * 2) == 0):
        bk *= 2

    def to_bh(x):
        return x.transpose(0, 2, 1, 3).reshape(b * h, x.shape[1], d)

    def to_bh2(x):  # [B, S, H] → [B·H, S]
        return x.transpose(0, 2, 1).reshape(b * h, x.shape[1])

    qb = _pad_to(to_bh(q), 1, block_q)
    dob = _pad_to(to_bh(dout.astype(q.dtype)), 1, block_q)
    kb = _pad_to(to_bh(k), 1, block_k)
    vb = _pad_to(to_bh(v), 1, block_k)
    # Padded q rows get lse = +inf-ish so p = exp(s − lse) = 0 there.
    # Both vectors are stored sublane-replicated [B·H, 8, S] (Mosaic tiling
    # constraint — see the forward's lse output).
    lse_b = jnp.pad(to_bh2(lse.astype(jnp.float32)),
                    ((0, 0), (0, qb.shape[1] - s_q)),
                    constant_values=-NEG_INF)
    lse_b = jnp.broadcast_to(lse_b[:, None, :],
                             (lse_b.shape[0], 8, lse_b.shape[1]))
    delta_b = _pad_to(to_bh2(delta.astype(jnp.float32)), 1, block_q)
    delta_b = jnp.broadcast_to(delta_b[:, None, :],
                               (delta_b.shape[0], 8, delta_b.shape[1]))
    rows = _bwd_q_rows_per_call(block_q, bk, d, qb.shape[1], sub,
                                q.dtype.itemsize)

    def call(r0, n):
        meta = jnp.asarray(
            [jnp.asarray(q_offset, jnp.int32) + r0,
             jnp.asarray(k_offset, jnp.int32),
             jnp.asarray(k_offset, jnp.int32) + s_k], jnp.int32)
        # One kernel over the n q rows from r0 against all of K.  Outputs
        # accumulate in f32 in their VMEM-resident blocks.  dq's block is
        # revisited across BOTH inner axes, so both are sequential; one
        # buffer for it (its index changes once a head — a second would
        # only double the one term that grows with S_q).
        return pl.pallas_call(
            functools.partial(
                _bwd_kernel, block_q=block_q, block_k=bk, sub_q=sub_q,
                num_q_blocks=n // block_q, causal=causal, scale=scale),
            grid=(b * h, kb.shape[1] // bk, n // block_q),
            in_specs=[
                pl.BlockSpec((3,), lambda bh, ki, qi: (0,),
                             memory_space=pltpu.SMEM),
                pl.BlockSpec((1, block_q, d), lambda bh, ki, qi: (bh, qi, 0)),
                pl.BlockSpec((1, bk, d), lambda bh, ki, qi: (bh, ki, 0)),
                pl.BlockSpec((1, bk, d), lambda bh, ki, qi: (bh, ki, 0)),
                pl.BlockSpec((1, block_q, d), lambda bh, ki, qi: (bh, qi, 0)),
                pl.BlockSpec((1, 8, block_q), lambda bh, ki, qi: (bh, 0, qi)),
                pl.BlockSpec((1, 8, block_q), lambda bh, ki, qi: (bh, 0, qi)),
            ],
            out_specs=(
                pl.BlockSpec((1, n, d), lambda bh, ki, qi: (bh, 0, 0),
                             pipeline_mode=pl.Buffered(1)),
                pl.BlockSpec((1, bk, d), lambda bh, ki, qi: (bh, ki, 0)),
                pl.BlockSpec((1, bk, d), lambda bh, ki, qi: (bh, ki, 0)),
            ),
            out_shape=(
                jax.ShapeDtypeStruct((b * h, n, d), jnp.float32),
                jax.ShapeDtypeStruct(kb.shape, jnp.float32),
                jax.ShapeDtypeStruct(vb.shape, jnp.float32),
            ),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary", "arbitrary"),
                vmem_limit_bytes=_bwd_vmem_limit_bytes(
                    block_q, bk, d, n, sub, q.dtype.itemsize)),
            interpret=interpret,
            name=profiling.FLASH_BWD,
        )(meta, qb[:, r0:r0 + n], kb, vb, dob[:, r0:r0 + n],
          lse_b[:, :, r0:r0 + n], delta_b[:, :, r0:r0 + n])

    parts = [call(r0, min(rows, qb.shape[1] - r0))
             for r0 in range(0, qb.shape[1], rows)]
    dqs, dks, dvs = zip(*parts)
    dq = dqs[0] if len(parts) == 1 else jnp.concatenate(dqs, axis=1)
    dk, dv = functools.reduce(jnp.add, dks), functools.reduce(jnp.add, dvs)
    # q was pre-scaled for s; the K-contraction needs one more scale.
    dq = (dq * scale).astype(q.dtype)
    dk, dv = dk.astype(k.dtype), dv.astype(v.dtype)

    def from_bh(x, s):
        return x[:, :s].reshape(b, h, s, d).transpose(0, 2, 1, 3)

    return from_bh(dq, s_q), from_bh(dk, s_k), from_bh(dv, s_k)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 6, 7, 8, 9))
def _flash(q, k, v, causal, q_offset, k_offset, block_q, block_k, sub,
           interpret):
    return _flash_forward(q, k, v, causal, q_offset, k_offset, block_q,
                          block_k, interpret, sub=sub)


def _flash_fwd(q, k, v, causal, q_offset, k_offset, block_q, block_k, sub,
               interpret):
    out, lse = _flash_forward(q, k, v, causal, q_offset, k_offset, block_q,
                              block_k, interpret, sub=sub, with_lse=True)
    return out, (q, k, v, out, lse, q_offset, k_offset)


def _flash_bwd(causal, block_q, block_k, sub, interpret, res, g):
    q, k, v, out, lse, q_offset, k_offset = res
    # Δ = rowsum(dO·O) — the softmax-normalization term of the backward.
    # [B, S, H, D] → [B, S, H], matching the lse layout.
    delta = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)
    dq, dk, dv = flash_attention_backward(
        q, k, v, g, lse, delta, causal, q_offset, k_offset, block_q,
        block_k, interpret, sub=sub)
    return dq, dk, dv, None, None


_flash.defvjp(_flash_fwd, _flash_bwd)


def _default_block_k(s_k: int, d: int) -> int:
    """Measured default for the K-side streaming super tile: min(S, 2048)
    at d ≤ 128 — the larger tile amortizes per-grid-step cost (57.4 →
    59.6 % MFU at S=8192 vs the same-session 1024-tile baseline;
    block_k=4096 adds 0.7 more there but overflows the 16 MiB VMEM scope
    by ~0.5 MB in the remat backward at S=32768, so 2048 is the largest
    tile that compiles on EVERY shipped long-context config — pass
    block_k=4096 explicitly for the last bit at S ≤ 8192.  At d > 128
    the K/V tile bytes scale with d; the proven 1024 stays.
    docs/benchmarks.md round 5."""
    return min(max(s_k, 1), 2048 if d <= 128 else 1024)


def flash_attention(q, k, v, causal: bool = True, q_offset=0, k_offset=0,
                    block_q: int = 1024, block_k: int | None = None,
                    sub: int = 1024, interpret: bool | None = None):
    """Fused attention over [B, S, H, D] tensors.

    ``q_offset``/``k_offset`` are global sequence positions of the first
    row/col (sequence-parallel shards pass shard_index × shard_len).

    Tiling: the grid streams (block_k, D) K/V super tiles (Q/dO super
    tiles of block_q rows in the backward, against k tiles of at most
    1024 rows) double-buffered — few, large
    DMAs and few grid steps — while the in-kernel loop computes over
    ``sub``-sized slices so the [block_q, sub] intermediates bound scoped
    VMEM independent of S (the round-2 whole-sequence layout hit the
    16 MiB wall at block_k >= 1024).  See docs/benchmarks.md for the
    measured sweep.  ``block_k=None`` (the default) resolves to
    ``min(S, 2048)`` at d ≤ 128 (:func:`_default_block_k`): the larger
    streaming tile amortizes per-grid-step cost — 57.4 → 59.6 % MFU at
    S=8192 vs the 1024-tile baseline; ``block_k=4096`` (explicit)
    measures 60.3 % there but VMEM-overflows the S=32768 remat backward
    — while the statically-unrolled sub loop keeps scoped VMEM bounded.
    ``block_q`` stays ≤1024: the [block_q, sub] s-tile is VMEM-resident
    and 2048 exceeds the 16 MiB scope at d=128.  The backward is one
    kernel (:func:`_bwd_kernel`) whose f32 dq accumulator is the head's
    whole q length in VMEM, so it asks Mosaic for a limit of its own
    (:func:`_bwd_vmem_limit_bytes`; 30 MiB at S=16384) and a q too long
    for the chip's VMEM (past 65536 rows at d=128) is cut into row
    ranges, one call each.

    Keep ``block_k / sub`` (and ``block_q / sub`` in the backward) at or
    below :data:`MAX_SUB_TILES` (8): the sub-tile sweep is statically
    unrolled, so every sub-tile emits two guarded matmul bodies — deeper
    unrolls bloat code size and compile time with no measured MFU return
    (a warning fires past the bound).
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    if block_k is None:
        block_k = _default_block_k(k.shape[1], q.shape[-1])
    block_q = min(block_q, max(q.shape[1], 1))
    block_k = min(block_k, max(k.shape[1], 1))
    block_q, block_k = clamp_blocks_to_vmem(
        block_q, block_k, q.shape[-1], sub, q.dtype.itemsize)
    return _flash(q, k, v, causal, q_offset, k_offset, block_q, block_k,
                  sub, interpret)


def flash_attention_with_lse(q, k, v, causal: bool = True, q_offset=0,
                             k_offset=0, block_q: int = 1024,
                             block_k: int | None = None, sub: int = 1024,
                             interpret: bool | None = None):
    """Forward-only fused attention returning (out, lse).

    ``lse[b, s, h] = logsumexp_k(q·kᵀ·scale)`` (NEG_INF for rows that
    attended to nothing) — the combiner state ring attention needs to merge
    partial attentions over K/V blocks exactly.  Differentiation is handled
    by the caller (ring attention drives ``flash_attention_backward`` per
    ring step with the globally-merged lse under its own vjp).
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    if block_k is None:
        block_k = _default_block_k(k.shape[1], q.shape[-1])
    block_q = min(block_q, max(q.shape[1], 1))
    block_k = min(block_k, max(k.shape[1], 1))
    block_q, block_k = clamp_blocks_to_vmem(
        block_q, block_k, q.shape[-1], sub, q.dtype.itemsize,
        where="flash_attention_with_lse")
    return _flash_forward(q, k, v, causal, q_offset, k_offset, block_q,
                          block_k, interpret, sub=sub, with_lse=True)


def make_flash_attention(block_q: int = 1024, block_k: int | None = None,
                         sub: int = 1024):
    """Adapter producing a ``TransformerConfig.attention_fn``.  block_k
    defaults per-call to min(S, 2048) at d<=128 (_default_block_k)."""
    def attn(q, k, v, causal=True):
        return flash_attention(q, k, v, causal=causal, block_q=block_q,
                               block_k=block_k, sub=sub)
    return attn
