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
one for dk/dv, each formed q·kᵀ and dO·vᵀ: seven).  No O(S²) tensor is
ever materialized in HBM in either direction.  The kernel takes lse/Δ as
explicit inputs so ring attention can drive it per ring step with
globally-merged statistics.

What is scratch and what is output.  Every accumulator is float32 VMEM
scratch that never reaches HBM: the forward's o accumulator and its
running max and sum; the backward's dk/dv blocks and dq, whose rows are
revisited once per k-block, over the head's whole q length.  An output
block is written once, cast to the output's own dtype on the grid step
that finishes it.  That dtype is decided by the caller's structure and by
nothing a user sets: ``flash_attention`` (one device, one call over the
whole sequence: nothing sums its results again) takes o, dq, dk and dv in
the compute dtype, so no float32 copy of an activation lies in HBM between
a kernel and the cast that followed it; ``flash_attention_with_lse`` and
``flash_attention_backward``, which ring and zigzag attention call once
per ring step and whose results they merge and sum, return float32, as
does a backward cut into several calls (dk and dv are summed over them
first).  Same arithmetic, same single rounding: the compute-dtype
outputs are the float32 ones cast, to the bit.

The kernels' own layout is [B·H, S, D], padded to whole blocks.
``flash_attention`` lays q, k, v out once, for the forward call, and
keeps them, o and lse that way as the residuals its backward reads: only
the incoming cotangent is laid out again.

Non-TPU backends fall back to Pallas interpret mode (tests) so numerics are
identical everywhere.
"""

from __future__ import annotations

import dataclasses
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


def _sub_bounds(k_len, q_min, q_max, ks_min, sub_k, nsub, causal,
                q_len=None):
    """The forward kernel's sub-tile split bounds: ``hi``
    is the causal sweep end (tiles past the diagonal contribute p == 0),
    ``interior_end`` the mask-free prefix (entirely below the diagonal and
    inside the valid K range).  A call that says how many of its rows and
    keys count (``q_len`` given) also ends the sweep at the last sub-tile
    that holds a counted key, and runs none for a q block that holds no
    counted row."""
    if causal:
        hi = jnp.clip((q_max - ks_min) // sub_k + 1, 0, nsub)
    else:
        hi = nsub
    if q_len is not None:
        hi = jnp.minimum(hi, jnp.clip(-((ks_min - k_len) // sub_k), 0, nsub))
        hi = jnp.where(q_min < q_len, hi, 0)
    valid_end = (k_len - ks_min) // sub_k
    if causal:
        interior_end = jnp.minimum((q_min - ks_min + 1) // sub_k, valid_end)
    else:
        interior_end = valid_end
    return hi, jnp.clip(interior_end, 0, hi)


def _window_bounds(q_min, q_max, ks_min, sub_k, nsub, window):
    """The band's other side, for a sliding window in which query ``i`` sees
    keys ``i - window < j <= i``: ``lo`` is the first sub-tile that holds a
    key some row of the q block still sees (tiles before it lie wholly
    outside the band and are skipped, as tiles past the diagonal are), and
    ``int_start`` the first sub-tile every row sees whole (before it the
    band's lower edge cuts the tile and the mask is needed)."""
    lo = jnp.clip((q_min - window + 1 - ks_min) // sub_k, 0, nsub)
    int_start = jnp.clip(-((ks_min - (q_max - window + 1)) // sub_k), 0, nsub)
    return lo, int_start


def _flash_kernel(meta_ref, q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref,
                  m_ref, l_ref, *qs_ref, block_q: int, block_k: int,
                  sub_k: int, num_k_blocks: int, causal: bool, scale: float,
                  window: int | None = None, bounded: bool = False,
                  block: int | None = None):
    """One (batch·head, q-block, K-super-tile) program: online softmax.

    Two-level streaming: the grid's K axis moves (block_k, D) SUPER tiles
    HBM→VMEM double-buffered (few grid steps → the per-step fixed cost is
    amortized), while an in-kernel fori loop computes over (block_q,
    sub_k) SUB tiles so the [bq, sub_k] intermediates stay small.  Scoped
    VMEM is one super tile of K/V plus the sub-tile intermediates —
    independent of S.

    meta_ref (SMEM int32[3]): [q_offset, k_offset, k_len] — global position
    offsets (sequence parallelism) and the unpadded K length.  ``bounded``
    (a caller said how many of its rows or keys count: a serving prefill's
    prompt in its padded bucket) makes it int32[4] with ``q_len`` last, the
    position one past the last counted query row, and the kernel then runs
    no sub-tile that holds no counted key, and none at all for a q block
    whose rows are all at or past ``q_len``.  The meta is then prefetched
    (a scalar-prefetch operand, in SMEM before the grid starts) so that the
    K / V index map reads it too and hands a step that runs nothing the
    tile of the step before (:func:`_kv_tile_run`): the tiles of the
    bucket's padding, and in this call those past the diagonal and before
    the band too, cost their grid steps, no copy and no arithmetic (a step
    that only copied its 1024-row K and V tiles in took 1.5 us beside 5.4
    for a computed one, v5e, keys of 192; PERF.md section 6, PR 45); and
    the q tile is scaled once a q block, into a VMEM scratch of its own
    (``qs_ref``), where the unbounded kernel scales it anew every step.
    ``_init`` and ``_finish`` run all the same, so a skipped q block writes
    o = 0 and lse = NEG_INF, and ``_finish`` writes the same for the rows at
    and past ``q_len`` of the block the prompt ends in: finite, whatever lay
    in the padded rows of q, because a prefill's padded rows go on through
    the layers into the cache, where a decode step's dense products multiply
    a probability of exactly 0 by whatever lies there.  Rows below ``q_len``
    see the sub-tiles the unbounded kernel gives them, in the same order,
    less only those whose every probability was exactly 0: the same numbers
    to the bit.  Without ``bounded`` the kernel's text is what it was.

    ``block`` (a power of two that divides the sub-tile; the q offset a
    multiple of it) makes the in-tile mask block-causal: a row sees the keys
    up to the end of its own block of ``block`` positions, ``k_pos <= q_pos
    | (block - 1)``.  Which (q tile, K sub-tile) pairs run, and which of
    them run mask-free, is the causal sweep's: a block never straddles a
    tile, so a tile wholly below the diagonal is seen whole by both masks
    and one past it by neither.  Without ``block`` the kernel's text is what
    it was.

    The sub-tile loop is SPLIT: an interior prefix (entirely below the
    causal diagonal and inside the valid K range) runs a mask-free body —
    no per-element iota/compare/select (VPU work bracketing the MXU
    matmuls) — and only the diagonal/boundary suffix pays for masking.

    Scratch and output.  ``acc_ref`` (f32 [block_q, D]) and ``m_ref`` /
    ``l_ref`` (the running max and sum in the lse layout, sublane-
    replicated (8, block_q)) are VMEM scratch: they carry the online
    softmax across the K sweep and never reach HBM.  ``o_ref`` and
    ``lse_ref`` are the outputs, written once, on the sweep's last step:
    ``o_ref`` takes ``acc / l`` cast to its own dtype there -- the caller's
    compute dtype, or float32 for a caller that merges partial results
    (ring attention) -- which is the one rounding a cast after the call
    would make, without the f32 array in HBM between the two.
    """
    qi, ki = pl.program_id(1), pl.program_id(2)
    nsub = block_k // sub_k

    # The s matmul runs on INPUT-dtype operands: under JAX's default TPU
    # matmul precision an f32×f32 dot already executes as a single bf16
    # MXU pass (measured — the dtype of the operands does not change the
    # MXU rate), so what the input-dtype form buys is skipping the
    # per-tile k up-cast VPU pass.  The scale folds into q (together
    # with log2(e) — scores live in the log2 domain so the hot
    # exponentials are exp2, see LOG2E) with one rounding to the input
    # dtype (f32 inputs round-trip exactly).
    def scaled_q():
        return (q_ref[0].astype(jnp.float32)
                * (scale * LOG2E)).astype(q_ref.dtype)

    @pl.when(ki == 0)
    def _init():
        m_ref[0] = jnp.full_like(m_ref[0], NEG_INF)
        l_ref[0] = jnp.zeros_like(l_ref[0])
        acc_ref[...] = jnp.zeros_like(acc_ref)
        if bounded:
            # once a q block, into scratch: a step that runs no sub-tile
            # then does no vector work at all
            qs_ref[0][...] = scaled_q()

    q_min = meta_ref[0] + qi * block_q
    q_max = q_min + block_q - 1
    ks_min = meta_ref[1] + ki * block_k   # super-tile base position
    # Sub-tile bounds (scalar arithmetic on SMEM values):
    hi, interior_end = _sub_bounds(meta_ref[2], q_min, q_max, ks_min,
                                   sub_k, nsub, causal,
                                   meta_ref[3] if bounded else None)
    if window is not None:
        lo, int_start = _window_bounds(q_min, q_max, ks_min, sub_k, nsub,
                                       window)

    # (the unbounded kernel scales its q tile anew every step)
    q = None if bounded else scaled_q()

    def body(si, carry, masked):
        m, l = carry
        qs = qs_ref[0][...] if bounded else q
        k = k_ref[0, pl.ds(si * sub_k, sub_k), :]         # [sk, D]
        v = v_ref[0, pl.ds(si * sub_k, sub_k), :]
        s = jax.lax.dot_general(
            qs, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)           # [bq, sk]
        if masked:
            q_pos = (q_min + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, sub_k), 0))
            k_pos = (ks_min + si * sub_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, sub_k), 1))
            mask = k_pos < meta_ref[2]                    # padding mask
            if block is not None:
                mask = jnp.logical_and(mask, (q_pos | (block - 1)) >= k_pos)
            elif causal:
                mask = jnp.logical_and(mask, q_pos >= k_pos)
            if window is not None:
                mask = jnp.logical_and(mask, q_pos - k_pos < window)
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
        acc_ref[...] = acc_ref[...] * corr + pv
        return m_new, l_new

    def _writeback(m, l):
        m_ref[0] = jnp.broadcast_to(m[:, 0][None, :], m_ref.shape[1:])
        l_ref[0] = jnp.broadcast_to(l[:, 0][None, :], l_ref.shape[1:])

    if window is not None:
        # The band has two edges: sub-tiles in [lo, hi) run, of them those
        # in [int_start, interior_end) mask-free.  Same static unroll.
        for si in range(nsub):
            inside = jnp.logical_and(si >= int_start, si < interior_end)

            @pl.when(inside)
            def _interior(si=si):
                _writeback(*body(si, (m_ref[0, 0, :][:, None],
                                      l_ref[0, 0, :][:, None]),
                                 masked=False))

            @pl.when(jnp.logical_and(
                jnp.logical_and(si >= lo, si < hi), jnp.logical_not(inside)))
            def _boundary(si=si):
                _writeback(*body(si, (m_ref[0, 0, :][:, None],
                                      l_ref[0, 0, :][:, None]),
                                 masked=True))
    elif nsub == 1:
        # Static single-tile case (the measured optimum): straight-line
        # bodies under pl.when — a dynamic-bound fori_loop here defeats
        # Mosaic's scheduling and costs ~5 MFU points (round 5's chip).
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
        o = acc_ref[...] / jnp.maximum(l, 1e-30)
        if bounded:
            # the rows at and past q_len of the block the prompt ends in
            # leave as a skipped block's do
            counted = (q_min + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, 1), 0)) < meta_ref[3]
            o = jnp.where(counted, o, 0.0)
            l = jnp.where(counted, l, 0.0)
        o_ref[0] = o.astype(o_ref.dtype)
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
# fori_loop defeats Mosaic's scheduling, measured in round 5), so
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
# S=32768 (round 5's chip).  Requested blocks whose estimated
# resident set exceeds the budget are halved with a warning instead of
# failing inside Pallas.  Another device kind needs its own confirmation.
VMEM_FIT_BUDGET_MB = 13.0
_VMEM_MIN_BLOCK = 128
_vmem_clamp_warned: set = set()


def _vmem_estimate_bytes(block_q: int, block_k: int, d: int,
                         sub: int = 1024, itemsize: int = 2,
                         d_v: int | None = None) -> int:
    """Resident-set model that sizes the tiles (the entry points' clamp
    and ``ContextPlan``'s), priced for a pass of the forward's layout
    that also streams dO and Δ — more than the forward holds, on purpose:
    double-buffered K/V streaming super tiles, q/dO tiles, the f32
    accumulator, the sublane-replicated lse/Δ rows, and two live
    [block_q, sub] f32 compute tiles (Mosaic fuses the elementwise chain,
    so s/p share ~two buffers in practice).  ``d`` is the width of q and
    k, ``d_v`` that of v, dO and the accumulator (None: ``d``).  The
    backward's resident set, whose dq accumulator grows with S_q, is
    :func:`_bwd_vmem_estimate_bytes`."""
    d_v = d if d_v is None else d_v
    sub_k = min(sub, max(block_k, 1))
    kv = 2 * block_k * (d + d_v) * itemsize      # K+V, double-buffered
    qdo = 2 * block_q * (d + d_v) * itemsize     # q + dO tiles
    acc = block_q * d_v * 4                      # f32 dq/o accumulator
    stats = 2 * 8 * block_q * 4                  # lse + Δ, sublane-replicated
    tiles = 2 * block_q * sub_k * 4              # live f32 compute tiles
    return kv + qdo + acc + stats + tiles


# The backward's dq accumulator and the dq output block beside it are the
# VMEM terms that grow with the sequence (S_q·d·(4 + 2) bytes in bf16:
# 8 + 4 MiB at S=16384, d=128), so that call asks Mosaic for its own limit
# (``vmem_limit_bytes``) instead of living under the 16 MiB default.  A v5e
# core has 128 MiB of VMEM (jax's own ``pallas.tpu.get_tpu_info`` table;
# that call needs an attached TPU, this file must also trace for a
# described one).  One call asks for at most three quarters of it; a
# longer q is cut into row ranges, one call each.
VMEM_PHYSICAL_MB = 128.0
_BWD_VMEM_ASK_MAX_BYTES = int(0.75 * VMEM_PHYSICAL_MB * 2 ** 20)


def _bwd_vmem_estimate_bytes(block_q: int, block_k: int, d: int, s_q: int,
                             sub: int = 1024, itemsize: int = 2,
                             out_itemsize: int | None = None) -> int:
    """Resident-set model of the one backward pass (:func:`_bwd_kernel`):
    double-buffered Q/dO super tiles and K/V tiles, the sublane-replicated
    lse/Δ rows, the f32 dk/dv scratch and their double-buffered output
    blocks, three live [sub_q, block_k] f32 compute tiles (Mosaic fuses the
    elementwise chain: the v5e compiler's own scoped allocation at the
    default tiles ran 1.4 MiB under this model at S=2048, PR 27), and the
    two terms that grow with the sequence: the f32 dq accumulator over the
    ``s_q`` padded q rows of one call, and the single-buffered dq output
    block of the same rows in the output's dtype.  ``block_k`` is the
    kernel's own k tile (≤ 1024 at the defaults); ``out_itemsize`` is the
    gradients' (None: the inputs')."""
    out_itemsize = itemsize if out_itemsize is None else out_itemsize
    sub_q = min(sub, max(block_q, 1))
    qdo = 2 * 2 * block_q * d * itemsize         # q + dO, double-buffered
    kv = 2 * 2 * block_k * d * itemsize          # K + V, double-buffered
    stats = 2 * 2 * 8 * block_q * 4              # lse + Δ, double-buffered
    dkv = 2 * block_k * d * (4 + 2 * out_itemsize)   # dk + dv: scratch, out
    tiles = 3 * sub_q * block_k * 4              # live f32 compute tiles
    dq = s_q * d * (4 + out_itemsize)            # accumulator + out block
    return qdo + kv + stats + dkv + tiles + dq


def _bwd_vmem_limit_bytes(block_q: int, block_k: int, d: int, s_q: int,
                          sub: int = 1024, itemsize: int = 2,
                          out_itemsize: int | None = None) -> int:
    """What the backward call asks Mosaic for: its estimate and a quarter
    more (the estimate cannot see the scheduler's windows), never under
    the 16 MiB default."""
    est = _bwd_vmem_estimate_bytes(block_q, block_k, d, s_q, sub, itemsize,
                                   out_itemsize)
    return max(16 * 2 ** 20, est + est // 4)


def _bwd_q_rows_per_call(block_q: int, block_k: int, d: int, s_q: int,
                         sub: int = 1024, itemsize: int = 2,
                         out_itemsize: int | None = None) -> int:
    """How many of the ``s_q`` padded q rows one backward call takes: all
    of them while the limit it would ask for stays within
    ``_BWD_VMEM_ASK_MAX_BYTES``, else the fewest equal ranges of whole q
    blocks that do (never under one block)."""
    blocks = s_q // block_q
    calls = 1
    while calls < blocks and _bwd_vmem_limit_bytes(
            block_q, block_k, d, -(-blocks // calls) * block_q, sub,
            itemsize, out_itemsize) > _BWD_VMEM_ASK_MAX_BYTES:
        calls += 1
    return -(-blocks // calls) * block_q


def clamp_blocks_to_vmem(block_q: int, block_k: int, d: int,
                         sub: int = 1024, itemsize: int = 2,
                         where: str = "flash_attention",
                         d_v: int | None = None) -> tuple[int, int]:
    """Halve (block_k first — the K/V tiles dominate — then block_q, never
    below 128) until :func:`_vmem_estimate_bytes` fits the VMEM budget.
    One-line rank-0 warning per distinct clamp; ``ContextPlan`` routes
    through the same estimate so planned configs never trip it."""
    bq, bk = block_q, block_k
    budget = int(VMEM_FIT_BUDGET_MB * 2 ** 20)
    while _vmem_estimate_bytes(bq, bk, d, sub, itemsize, d_v) > budget:
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


def repeat_kv_heads(x, num_heads: int):
    """[B, S, H_kv, D] -> [B, S, H, D]: query head j reads KV head
    j // (H / H_kv).  The same array where the two counts agree."""
    kv = x.shape[2]
    if kv == num_heads:
        return x
    if num_heads % kv:
        raise ValueError(f"{kv} KV heads do not divide {num_heads} query "
                         f"heads")
    return jnp.repeat(x, num_heads // kv, axis=2)


def _need_equal_widths(k, v, who: str) -> None:
    """Only the forward kernel takes values of another width than the keys
    (a serving prefill of latent attention); everything that differentiates
    or merges partial results says so by name."""
    if v.shape[-1] != k.shape[-1]:
        raise NotImplementedError(
            f"{who} takes values as wide as the keys: value width "
            f"{v.shape[-1]} against key width {k.shape[-1]} is the forward "
            f"kernel's alone (flash_attention, a serving prefill); the "
            f"backward kernel and ring / zigzag attention size dk, dv and "
            f"the merged output by one width.  Differentiate "
            f"dense_causal_attention instead")


def _to_bh(x):
    """[B, S, H, D] → [B·H, S, D], the kernels' layout."""
    b, s, h, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b * h, s, d)


def _from_bh(x, b: int, s: int):
    """[B·H, S_pad, D] → [B, S, H, D], the padding rows dropped."""
    return x[:, :s].reshape(b, -1, s, x.shape[-1]).transpose(0, 2, 1, 3)


def _meta(q_offset, k_offset, s_k, s_q=None):
    """The kernels' SMEM int32[3]: [q_offset, k_offset, k_len]; with
    ``s_q`` (the forward kernel told how many of its rows count) int32[4],
    ``q_len`` last.  Both lengths leave as positions, the offsets added."""
    k_offset = jnp.asarray(k_offset, jnp.int32)
    meta = [jnp.asarray(q_offset, jnp.int32), k_offset, k_offset + s_k]
    if s_q is not None:
        meta.append(meta[0] + s_q)
    return jnp.stack(meta)


def _kv_tile_run(meta_ref, qi, ki, block_q, block_k, num_k_blocks, causal,
                 window):
    """The K / V super tile the bounded kernel's grid step (qi, ki) is
    handed: ``ki`` held inside the tiles that q block runs a sub-tile of
    (:func:`_sub_bounds`, :func:`_window_bounds`, a super tile at a time).
    A step outside them runs nothing and is handed the tile of the step
    before it, which the pipeline does not copy again: a tile that is not
    computed is not fetched either, past the prompt, past the diagonal and
    before the band alike.  Tile 0 for a q block that holds no counted
    row."""
    q_min = meta_ref[0] + qi * block_q
    last = (meta_ref[2] - 1 - meta_ref[1]) // block_k   # holds key k_len - 1
    if causal:
        last = jnp.minimum(last, (q_min + block_q - 1 - meta_ref[1])
                           // block_k)
    last = jnp.clip(last, 0, num_k_blocks - 1)
    first = 0
    if window is not None:
        first = jnp.clip((q_min - window + 1 - meta_ref[1]) // block_k,
                         0, last)
    return jnp.where(q_min < meta_ref[3], jnp.clip(ki, first, last), 0)


def _forward_bh(q, k, v, causal, q_offset, k_offset, block_q, block_k,
                interpret, sub, out_dtype, scale=None, window=None,
                q_len=None, k_len=None, block=None):
    """The forward kernel on [B, S, H, D] inputs, everything it read and
    wrote left in the kernels' layout, padded to whole blocks:
    ``(qb, kb, vb, ob, lse_b)`` with ``ob`` [B·H, S_q_pad, D_v] in
    ``out_dtype`` and ``lse_b`` [B·H, 8, S_q_pad] float32 (sublane-
    replicated).  The backward reads all five as they are.  ``v`` may be
    narrower or wider than ``q`` and ``k`` (latent attention: keys of 192,
    values of 128): its width is the output's and the accumulator's.
    ``q_len`` and ``k_len`` (may be traced) are how many of the rows and of
    the keys count, where that is fewer than all.  Given either, the kernel
    is the bounded one (:func:`_flash_kernel`): the keys past ``k_len`` lie
    behind the padding mask and their sub-tiles are not run, the rows at and
    past ``q_len`` come out o = 0, lse = NEG_INF and their q blocks run no
    sub-tile.  Given neither, the call is what it was before they
    existed."""
    d, d_v = q.shape[-1], v.shape[-1]
    s_k = k.shape[1]
    block_k, sub_k = _sub_fit(block_k, sub)
    told = {}
    if block is not None:
        if not causal or window is not None or block & (block - 1) \
                or sub_k % block or block_q % block:
            raise ValueError(
                f"flash_attention(block={block}): the block-causal mask is "
                f"causal, has no window, and its block is a power of two "
                f"that divides the q tile ({block_q}) and the K sub-tile "
                f"({sub_k}); dense_causal_attention(block=) takes any")
        told["block"] = block
    qb = _pad_to(_to_bh(q), 1, block_q)
    kb = _pad_to(_to_bh(k), 1, block_k)
    vb = _pad_to(_to_bh(v), 1, block_k)
    num_q_blocks = qb.shape[1] // block_q
    num_k_blocks = kb.shape[1] // block_k
    bounded = q_len is not None or k_len is not None
    if bounded:
        meta = _meta(q_offset, k_offset, s_k if k_len is None else k_len,
                     q.shape[1] if q_len is None else q_len)
    else:
        meta = _meta(q_offset, k_offset, s_k)
    kernel = functools.partial(
        _flash_kernel, block_q=block_q, block_k=block_k, sub_k=sub_k,
        num_k_blocks=num_k_blocks, causal=causal,
        scale=d ** -0.5 if scale is None else scale, window=window,
        bounded=bounded, **told)

    # The index maps see the meta only in the bounded call, where it is
    # prefetched (``*meta`` is then its one ref, else nothing).
    def q_tile(bh, qi, ki, *meta):
        return bh, qi, 0

    def kv_tile(bh, qi, ki, *meta):
        if meta:
            ki = _kv_tile_run(meta[0], qi, ki, block_q, block_k,
                              num_k_blocks, causal, window)
        return bh, ki, 0

    tiles = dict(
        grid=(qb.shape[0], num_q_blocks, num_k_blocks),
        in_specs=[pl.BlockSpec((1, block_q, d), q_tile),
                  pl.BlockSpec((1, block_k, d), kv_tile),
                  pl.BlockSpec((1, block_k, d_v), kv_tile)],
        out_specs=(
            pl.BlockSpec((1, block_q, d_v), q_tile),
            pl.BlockSpec((1, 8, block_q),
                         lambda bh, qi, ki, *meta: (bh, 0, qi))),
        scratch_shapes=[
            pltpu.VMEM((block_q, d_v), jnp.float32),    # acc
            pltpu.VMEM((1, 8, block_q), jnp.float32),   # m carry
            pltpu.VMEM((1, 8, block_q), jnp.float32),   # l carry
        ])
    if bounded:
        # q, scaled: inside what _vmem_estimate_bytes prices for the dO
        # tile, which no forward streams
        tiles["scratch_shapes"].append(pltpu.VMEM((block_q, d), q.dtype))
        tiles = {"grid_spec": pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, **tiles)}
    else:
        tiles["in_specs"].insert(0, pl.BlockSpec(
            meta.shape, lambda bh, qi, ki: (0,), memory_space=pltpu.SMEM))
    ob, lse_b = pl.pallas_call(
        kernel,
        out_shape=(
            jax.ShapeDtypeStruct(qb.shape[:2] + (d_v,), out_dtype),
            jax.ShapeDtypeStruct((qb.shape[0], 8, qb.shape[1]), jnp.float32),
        ),
        # outer axes parallel, the innermost the sequential K sweep
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name=profiling.FLASH_FWD,
        **tiles,
    )(meta, qb, kb, vb)
    return qb, kb, vb, ob, lse_b


def _bwd_kernel(meta_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dq_ref, dk_ref, dv_ref, dq_acc, dk_acc, dv_acc, *,
                block_q: int, block_k: int, sub_q: int, num_q_blocks: int,
                num_k_blocks: int, causal: bool, scale: float):
    """One (batch·head, k-block, Q-super-tile) program of the one backward
    pass: per tile s, p, dp and ds = p·(dp − Δ) are formed ONCE and feed
    all three gradients — dv += pᵀ·dO, dk += dsᵀ·(q·scale), dq += ds·k.

    The forward's layout with the roles swapped: the grid streams
    (block_q, D) Q/dO super tiles (lse/Δ alongside) double-buffered while
    the in-kernel loop computes (sub_q, block_k) sub tiles.

    Scratch and output.  The three gradients accumulate in f32 VMEM
    scratch that never reaches HBM: ``dk_acc`` / ``dv_acc`` ([block_k, D])
    across the qi sweep, and ``dq_acc``, whose rows are revisited once per
    k-block, over the head's WHOLE padded q length: zeroed at the head's
    first grid step (so a call in which no tile runs — K wholly after Q —
    still returns zeros).  That accumulator is what grows with S_q;
    :func:`_bwd_vmem_estimate_bytes` prices it.  The outputs are written
    once each, cast to their own dtype (the caller's compute dtype, or
    float32 where the caller sums partial results again), on the step
    that finishes them: ``dk_ref`` / ``dv_ref`` on the k-block's last q
    tile, and each q tile's rows of ``dq_ref`` (the head's whole length,
    index map constant in ki and qi, written back once a head) during
    the head's last k-block.

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
        dq_acc[...] = jnp.zeros_like(dq_acc)

    @pl.when(qi == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

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
        # surplus on dk is repaid by the ·ln2 in _finish_dkv (dv uses p
        # directly and needs none; dq takes plain ``scale`` in
        # _finish_dq, with its cast).
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
        dv_acc[...] += jax.lax.dot_general(
            p, do.astype(jnp.float32), (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = (p * (dp - delta)).astype(q_ref.dtype)       # one cast, two uses
        # q is pre-scaled (incl. LOG2E), so this is d s/d k contracted
        # with ds up to the log2e surplus repaid in _finish_dkv.
        dk_acc[...] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        rows = pl.ds(pl.multiple_of(qi * block_q + si * sub_q, sub_q), sub_q)
        dq_acc[rows, :] += jax.lax.dot_general(
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
    def _finish_dkv():
        # The q fold carried scale·log2e; dk needs plain scale — repay
        # the log2e once per finished block (log2e·ln2 == 1).
        dk_ref[0] = (dk_acc[...] * LN2).astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)

    @pl.when(ki == num_k_blocks - 1)
    def _finish_dq():
        # This q tile's rows have met every k-block.  q was pre-scaled
        # for s; the K-contraction needs one more scale.
        rows = pl.ds(pl.multiple_of(qi * block_q, block_q), block_q)
        dq_ref[0, rows, :] = (dq_acc[rows, :] * scale).astype(dq_ref.dtype)


def _backward_bh(qb, kb, vb, dob, lse_b, delta_b, s_q, s_k, causal, q_offset,
                 k_offset, block_q, block_k, interpret, sub, out_dtype,
                 scale=None):
    """The backward kernel on arrays in the kernels' layout: ``qb`` / ``dob``
    [B·H, ≥ s_q, D], ``kb`` / ``vb`` [B·H, ≥ s_k, D], ``lse_b`` [B·H, 8,
    ≥ s_q] (sublane-replicated) and ``delta_b`` [B·H, ≥ s_q] float32; rows
    past ``s_q`` / ``s_k`` are padding (zeros in q, k, v, dO and Δ).
    Returns (dq, dk, dv) in that layout, padded to whole blocks, in
    ``out_dtype`` — cast inside the kernel, so float32 gradients reach HBM
    only for a caller that asks for them, or where q is cut into several
    calls (dk and dv are then summed over the calls in f32 first)."""
    d = qb.shape[-1]
    itemsize = qb.dtype.itemsize
    # Clamp to the actual sequence lengths (like the public forward
    # wrappers): ring/zigzag drive this entry per ring step with SHARD
    # lengths — without the clamp the 512/1024 defaults would pad small
    # shards up to the block size and double the backward work.
    block_q = min(block_q, max(s_q, 1))
    block_k = min(block_k, max(s_k, 1))
    block_q, block_k = clamp_blocks_to_vmem(
        block_q, block_k, d, sub, itemsize,
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

    # What the forward saved is padded to ITS blocks, which are these
    # unless block/sub does not divide: pad on only then.
    qb, dob = _pad_to(qb, 1, block_q), _pad_to(dob, 1, block_q)
    kb, vb = _pad_to(kb, 1, block_k), _pad_to(vb, 1, block_k)
    n_q = qb.shape[1]
    # Padded q rows get lse = +inf-ish so p = exp(s − lse) = 0 there.
    # Both vectors are stored sublane-replicated [B·H, 8, S] (Mosaic tiling
    # constraint — see the forward's lse output).
    lse_b = _pad_to(lse_b.astype(jnp.float32), 2, block_q)
    if n_q > s_q:
        lse_b = jnp.where(jnp.arange(n_q) < s_q, lse_b, -NEG_INF)
    delta_b = _pad_to(delta_b.astype(jnp.float32), 1, block_q)
    delta_b = jnp.broadcast_to(delta_b[:, None, :], lse_b.shape)

    rows = _bwd_q_rows_per_call(block_q, bk, d, n_q, sub, itemsize,
                                jnp.dtype(out_dtype).itemsize)
    part_dtype = out_dtype
    if rows < n_q:      # several calls: dk and dv are summed over them
        part_dtype = jnp.float32
        rows = _bwd_q_rows_per_call(block_q, bk, d, n_q, sub, itemsize, 4)
    part_itemsize = jnp.dtype(part_dtype).itemsize
    scale = d ** -0.5 if scale is None else scale

    def call(r0, n):
        # One kernel over the n q rows from r0 against all of K.  dq's
        # rows are revisited across BOTH inner axes, so both are
        # sequential; one buffer for its output block (its index changes
        # once a head — a second would only double a term that grows
        # with S_q).
        return pl.pallas_call(
            functools.partial(
                _bwd_kernel, block_q=block_q, block_k=bk, sub_q=sub_q,
                num_q_blocks=n // block_q, num_k_blocks=kb.shape[1] // bk,
                causal=causal, scale=scale),
            grid=(qb.shape[0], kb.shape[1] // bk, n // block_q),
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
                jax.ShapeDtypeStruct((qb.shape[0], n, d), part_dtype),
                jax.ShapeDtypeStruct(kb.shape, part_dtype),
                jax.ShapeDtypeStruct(vb.shape, part_dtype),
            ),
            scratch_shapes=[
                pltpu.VMEM((n, d), jnp.float32),     # dq accumulator
                pltpu.VMEM((bk, d), jnp.float32),    # dk
                pltpu.VMEM((bk, d), jnp.float32),    # dv
            ],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary", "arbitrary"),
                vmem_limit_bytes=_bwd_vmem_limit_bytes(
                    block_q, bk, d, n, sub, itemsize, part_itemsize)),
            interpret=interpret,
            name=profiling.FLASH_BWD,
        )(_meta(jnp.asarray(q_offset, jnp.int32) + r0, k_offset, s_k),
          qb[:, r0:r0 + n], kb, vb, dob[:, r0:r0 + n],
          lse_b[:, :, r0:r0 + n], delta_b[:, :, r0:r0 + n])

    parts = [call(r0, min(rows, n_q - r0)) for r0 in range(0, n_q, rows)]
    if len(parts) == 1:
        return parts[0]
    dqs, dks, dvs = zip(*parts)
    return (jnp.concatenate(dqs, axis=1).astype(out_dtype),
            functools.reduce(jnp.add, dks).astype(out_dtype),
            functools.reduce(jnp.add, dvs).astype(out_dtype))


def flash_attention_backward(q, k, v, dout, lse, delta, causal,
                             q_offset, k_offset, block_q, block_k,
                             interpret, sub: int = 1024):
    """Fused backward: (dq, dk, dv) from saved lse and Δ = rowsum(dO·O),
    one kernel, one sweep over the tiles (:func:`_bwd_kernel`).

    ``lse``/``delta``: [B, S_q, H] float32 — from
    ``flash_attention_with_lse`` (or the ring's globally-merged
    statistics), so the per-block probabilities recompute exactly without
    an O(S²) tensor.

    The entry of callers that sum what it returns again (ring and zigzag
    attention, once per ring step): the gradients come back in float32,
    [B, S, H, D], whatever the inputs' dtype, and the caller rounds once,
    after its last sum.  ``flash_attention`` itself differentiates through
    :func:`_backward_bh` and takes the compute dtype from the kernel.
    """
    _need_equal_widths(k, v, "flash_attention_backward")
    b, s_q = q.shape[:2]
    s_k = k.shape[1]

    def stat_bh(x):     # [B, S, H] → [B·H, S]
        return x.transpose(0, 2, 1).reshape(-1, x.shape[1])

    lse_b = stat_bh(lse)
    lse_b = jnp.broadcast_to(lse_b[:, None, :], (lse_b.shape[0], 8, s_q))
    dq, dk, dv = _backward_bh(
        _to_bh(q), _to_bh(k), _to_bh(v), _to_bh(dout.astype(q.dtype)),
        lse_b, stat_bh(delta), s_q, s_k, causal, q_offset, k_offset,
        block_q, block_k, interpret, sub, jnp.float32)
    return _from_bh(dq, b, s_q), _from_bh(dk, b, s_k), _from_bh(dv, b, s_k)


@jax.tree_util.register_static
@dataclasses.dataclass(frozen=True)
class _Lengths:
    """The unpadded lengths, carried beside the padded residuals, and
    whether the forward was told how many of them count."""
    s_q: int
    s_k: int
    bounded: bool = False


@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(3, 6, 7, 8, 9, 10, 11, 12))
def _flash(q, k, v, causal, q_offset, k_offset, block_q, block_k, sub,
           interpret, scale, window, block, q_len=None, k_len=None):
    """One device, one call over the whole sequence: nothing sums its
    results again, so the kernels write the compute dtype themselves."""
    return _flash_fwd(q, k, v, causal, q_offset, k_offset, block_q, block_k,
                      sub, interpret, scale, window, block, q_len, k_len)[0]


def _flash_fwd(q, k, v, causal, q_offset, k_offset, block_q, block_k, sub,
               interpret, scale, window, block, q_len=None, k_len=None):
    # The residuals stay as the forward call read and wrote them, in the
    # layout the backward kernel reads: nothing is laid out twice.
    qb, kb, vb, ob, lse_b = _forward_bh(
        q, k, v, causal, q_offset, k_offset, block_q, block_k, interpret,
        sub, q.dtype, scale, window, q_len, k_len,
        **({} if block is None else {"block": block}))
    return _from_bh(ob, q.shape[0], q.shape[1]), (
        qb, kb, vb, ob, lse_b, q_offset, k_offset,
        _Lengths(q.shape[1], k.shape[1],
                 q_len is not None or k_len is not None))


def _flash_bwd(causal, block_q, block_k, sub, interpret, scale, window,
               block, res, g):
    if block is not None:
        raise NotImplementedError(
            f"flash_attention(block={block}) has no backward: the "
            f"block-causal mask is in the forward kernel alone (a serving "
            f"prefill); the backward kernel knows the causal mask only. "
            f"Differentiate dense_causal_attention(block=...) instead")
    if window is not None:
        raise NotImplementedError(
            f"flash_attention(window={window}) has no backward: the "
            f"sliding window is in the forward kernel alone (a serving "
            f"prefill); the backward kernel knows the causal mask only. "
            f"Differentiate dense_causal_attention(window=...) instead")
    qb, kb, vb, ob, lse_b, q_offset, k_offset, lengths = res
    if lengths.bounded:
        raise NotImplementedError(
            "flash_attention(q_len=, k_len=) has no backward: how many rows "
            "and keys count is the forward kernel's to know alone (a serving "
            "prefill's prompt in its padded bucket); the backward kernel "
            "would read lse = NEG_INF in the rows past q_len as a row that "
            "attended.  Differentiate the call without them")
    _need_equal_widths(kb, vb, "flash_attention's backward")
    b = g.shape[0]
    # Only the incoming cotangent is laid out here.
    dob = _pad_to(_to_bh(g.astype(qb.dtype)), 1, ob.shape[1])
    # Δ = rowsum(dO·O) — the softmax-normalization term of the backward,
    # [B·H, S_q_pad] beside lse.
    delta_b = jnp.sum(dob.astype(jnp.float32) * ob.astype(jnp.float32),
                      axis=-1)
    dq, dk, dv = _backward_bh(
        qb, kb, vb, dob, lse_b, delta_b, lengths.s_q, lengths.s_k, causal,
        q_offset, k_offset, block_q, block_k, interpret, sub, qb.dtype,
        scale)
    grads = (_from_bh(dq, b, lengths.s_q), _from_bh(dk, b, lengths.s_k),
             _from_bh(dv, b, lengths.s_k))
    # The gradients leave as they are: in the compute dtype.  Without the
    # barrier XLA:TPU moves a consumer's float32 cast (rope's backward)
    # ahead of the change of layout and copies the f32 array, twice the
    # bytes (read off the compiled text of an Attention layer, PR 31).
    # no cotangent for the two offsets and the two lengths
    return jax.lax.optimization_barrier(grads) + (None,) * 4


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
    Readings of round 5's chip."""
    return min(max(s_k, 1), 2048 if d <= 128 else 1024)


def flash_attention(q, k, v, causal: bool = True, q_offset=0, k_offset=0,
                    block_q: int = 1024, block_k: int | None = None,
                    sub: int = 1024, interpret: bool | None = None,
                    scale: float | None = None, window: int | None = None,
                    q_len=None, k_len=None, block: int | None = None):
    """Fused attention over [B, S, H, D] tensors.

    ``q_len`` and ``k_len`` (traced scalars may be given) count the leading
    rows of ``q`` and the leading keys that count, for a caller whose
    programs share one shape and differ in how much of it is filled: a
    serving prefill's prompt in its padded bucket passes its length as
    both.  The forward kernel then runs no sub-tile that holds no counted
    key and none for a q block past ``q_len``; the rows at and past
    ``q_len`` come out 0 (finite whatever the padding held), the rows below
    it to the bit what the call without the lengths gives them, where no
    key past ``k_len`` was theirs to see.  Forward only: differentiating
    such a call raises ``NotImplementedError``.  A call that names neither
    is, to its jaxpr, the call from before they existed.

    ``window`` (causal only) is a sliding window: query ``i`` sees keys
    ``i - window < j <= i``.  The forward kernel skips the sub-tiles that lie
    wholly before the band as it skips those past the diagonal, and masks
    the two edges.  The backward kernel has no window: differentiating a
    windowed call raises ``NotImplementedError`` (docs/inference.md).

    ``block`` (causal only, no window; a power of two) makes the mask
    block-causal: query ``i`` sees the keys of its own block of ``block``
    positions and of every block before it (a model that generates by
    diffusion over blocks).  The forward kernel runs the causal sweep's
    tiles and masks the diagonal ones by the block; forward only, as the
    window is: differentiating such a call raises ``NotImplementedError``.

    ``v`` may have another width than ``q`` and ``k`` (latent attention's
    expanded form: keys of 192, values of 128); the output has ``v``'s.  The
    forward kernel alone takes that: differentiating such a call, and ring /
    zigzag attention, raise ``NotImplementedError`` by name.

    ``scale`` is the softmax scale, ``d ** -0.5`` when None.  It reaches the
    kernels as the constant they fold into q (an argument, not a pre-scale
    of q outside: exact for any value, and no op of its own).  ``k`` and
    ``v`` may have fewer heads than ``q`` (grouped-query attention, query
    head j reading KV head j // group): they are repeated to q's heads
    before the kernels, which see as many KV heads as query heads, and JAX
    sums the group's dk and dv; the repeat and the sum are XLA's ops around
    the kernels (``attn_glue_ms`` in the benchmark).

    ``q_offset``/``k_offset`` are global sequence positions of the first
    row/col (sequence-parallel shards pass shard_index × shard_len).

    Tiling: the grid streams (block_k, D) K/V super tiles (Q/dO super
    tiles of block_q rows in the backward, against k tiles of at most
    1024 rows) double-buffered — few, large
    DMAs and few grid steps — while the in-kernel loop computes over
    ``sub``-sized slices so the [block_q, sub] intermediates bound scoped
    VMEM independent of S (the round-2 whole-sequence layout hit the
    16 MiB wall at block_k >= 1024).  The sweep was measured on round
    5's chip.  ``block_k=None`` (the default) resolves to
    ``min(S, 2048)`` at d ≤ 128 (:func:`_default_block_k`): the larger
    streaming tile amortizes per-grid-step cost — 57.4 → 59.6 % MFU at
    S=8192 vs the 1024-tile baseline; ``block_k=4096`` (explicit)
    measures 60.3 % there but VMEM-overflows the S=32768 remat backward
    — while the statically-unrolled sub loop keeps scoped VMEM bounded.
    ``block_q`` stays ≤1024: the [block_q, sub] s-tile is VMEM-resident
    and 2048 exceeds the 16 MiB scope at d=128.  The backward is one
    kernel (:func:`_bwd_kernel`) whose f32 dq accumulator, and the dq
    output block beside it, are the head's whole q length in VMEM, so it
    asks Mosaic for a limit of its own (:func:`_bwd_vmem_limit_bytes`;
    35 MiB at S=16384) and a q too long for the chip's VMEM (past 65536
    rows at d=128) is cut into row ranges, one call each.  Output and
    gradients come back in the inputs' dtype, written so by the kernels.

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
        block_q, block_k, q.shape[-1], sub, q.dtype.itemsize,
        d_v=v.shape[-1])
    if window is not None and not causal:
        raise ValueError("a sliding window is a causal band: causal=True")
    if block is not None and window is not None:
        raise ValueError("a block-causal mask has no window")
    k, v = repeat_kv_heads(k, q.shape[2]), repeat_kv_heads(v, q.shape[2])
    return _flash(q, k, v, causal, q_offset, k_offset, block_q, block_k,
                  sub, interpret, None if scale is None else float(scale),
                  None if window is None else int(window),
                  None if block is None else int(block), q_len, k_len)


def flash_attention_with_lse(q, k, v, causal: bool = True, q_offset=0,
                             k_offset=0, block_q: int = 1024,
                             block_k: int | None = None, sub: int = 1024,
                             interpret: bool | None = None, q_len=None,
                             k_len=None):
    """Forward-only fused attention returning (out, lse): a PARTIAL
    attention, for a caller that merges several of them.  ``q_len`` and
    ``k_len`` (traced scalars may be given) count the leading rows that
    count and the leading keys that are seen, for a caller whose programs
    share one shape and differ in how much of it is filled (EVA attention's
    windows and summaries, a window at a time): the sub-tiles past
    ``k_len`` and the q blocks past ``q_len`` are not run
    (:func:`flash_attention`); a row that sees no key, and every row at or
    past ``q_len``, has lse NEG_INF and out 0.

    ``lse[b, s, h] = logsumexp_k(q·kᵀ·scale)`` (NEG_INF for rows that
    attended to nothing) — the combiner state ring attention needs to merge
    partial attentions over K/V blocks exactly; ``out`` is float32 whatever
    the inputs' dtype (the kernel's accumulator, not yet rounded: the
    merge weights and sums it again, and the caller rounds once at the
    end).  Differentiation is handled by the caller (ring attention drives
    ``flash_attention_backward`` per ring step with the globally-merged
    lse under its own vjp).
    """
    _need_equal_widths(k, v, "flash_attention_with_lse")
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    if block_k is None:
        block_k = _default_block_k(k.shape[1], q.shape[-1])
    block_q = min(block_q, max(q.shape[1], 1))
    block_k = min(block_k, max(k.shape[1], 1))
    block_q, block_k = clamp_blocks_to_vmem(
        block_q, block_k, q.shape[-1], sub, q.dtype.itemsize,
        where="flash_attention_with_lse")
    b, s_q = q.shape[:2]
    *_, ob, lse_b = _forward_bh(q, k, v, causal, q_offset, k_offset, block_q,
                                block_k, interpret, sub, jnp.float32,
                                q_len=q_len, k_len=k_len)
    # [B·H, 8, S_pad] (sublane-replicated) → [B, S, H]
    lse = lse_b[:, 0, :s_q].reshape(b, -1, s_q).transpose(0, 2, 1)
    return _from_bh(ob, b, s_q), lse


def make_flash_attention(block_q: int = 1024, block_k: int | None = None,
                         sub: int = 1024):
    """Adapter producing a ``TransformerConfig.attention_fn``.  block_k
    defaults per-call to min(S, 2048) at d<=128 (_default_block_k)."""
    def attn(q, k, v, causal=True, scale=None, window=None, q_len=None,
             k_len=None, block=None):
        return flash_attention(q, k, v, causal=causal, block_q=block_q,
                               block_k=block_k, sub=sub, scale=scale,
                               window=window, q_len=q_len, k_len=k_len,
                               block=block)
    return attn


def rows_worked(q_len: int, s_q: int, block_q: int = 1024) -> int:
    """The query rows the forward kernel works for a call of ``s_q`` rows
    told ``q_len`` of them count: the q blocks that hold a counted row,
    whole (``block_q`` as the entry points clamp it to the sequence)."""
    block_q = min(block_q, max(s_q, 1))
    return min(-(-q_len // block_q) * block_q, s_q)
