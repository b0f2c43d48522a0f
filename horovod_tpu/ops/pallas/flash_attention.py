"""Fused blockwise (flash) attention as a Pallas TPU kernel.

The hot op of the transformer model family. Online-softmax attention that
never materialises the ``(seq, seq)`` score matrix: the grid walks
(batch, head, q-block, k-block) with the k-block axis innermost, so exactly
one ``(block, head_dim)`` tile of each of q/k/v is resident in VMEM at a
time while a running (max, sum, accumulator) triple lives in VMEM scratch —
the MXU does the two matmuls, the VPU the rescaling. A custom VJP provides
the matching blockwise backward kernels (dq; dk/dv), so both compute and
VMEM stay O(block² + block·head_dim) per grid step end to end, independent
of sequence length.

This kernel is also the *local* building block of ring attention
(horovod_tpu/parallel/ring.py): it accepts dynamic ``q_offset``/``k_offset``
global position scalars and returns the per-row log-sum-exp, so partial
results computed against one shard of keys/values can be merged exactly
across ppermute steps (see ``merge_partials``).

The reference framework has no attention kernels at all (it is a pure
data-parallel gradient-averaging layer — SURVEY.md §5.7); this module is part
of the TPU-first long-context extension, not a port.

On non-TPU backends (CPU tests) the kernels run in Pallas interpret mode;
set ``HOROVOD_PALLAS_INTERPRET=0/1`` to force either way.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from horovod_tpu.ops.pallas._backend import use_interpret

NEG_INF = float("-inf")

# Per-row scalars (lse, delta) are stored as (B, H, S, LANES) with the value
# broadcast across lanes, satisfying the TPU (8, 128) tiling constraint.
LANES = 128

# Softmax runs in base 2 inside the kernels (exp2 is cheaper than exp on the
# VPU): scores are pre-scaled by log2(e), the log-sum-exp converts back on
# the way out.
LOG2E = float(np.log2(np.e))


def _vma(*arrays) -> frozenset:
    """Union of the inputs' varying-mesh-axes, so pallas_call outputs carry
    the right vma under ``shard_map(check_vma=True)``."""
    out = frozenset()
    for a in arrays:
        out |= jax.typeof(a).vma
    return out


def _pick_block(seq: int, requested: int) -> int:
    """Largest block ≤ requested that divides seq (power-of-two friendly)."""
    b = min(requested, seq)
    while seq % b:
        b -= 1
    return b


def _compiler_params(grid_len: int):
    # All grid axes are embarrassingly parallel except the innermost, which
    # carries the online-softmax accumulator in scratch.
    sem = ("parallel",) * (grid_len - 1) + ("arbitrary",)
    return pltpu.CompilerParams(dimension_semantics=sem)


# ---------------------------------------------------------------------------
# Forward kernel
# ---------------------------------------------------------------------------


def _fwd_kernel(q_off_ref, k_off_ref, q_ref, k_ref, v_ref, o_ref, lse_ref,
                acc_ref, m_ref, l_ref, *, sm_scale, causal, block_q, block_k):
    qi = pl.program_id(2)
    kj = pl.program_id(3)
    nk = pl.num_programs(3)

    q_start = q_off_ref[0] + qi * block_q
    k_start = k_off_ref[0] + kj * block_k
    last_q = q_start + block_q - 1

    @pl.when(kj == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def update(masked):
        # Scores and the running max are tracked in base 2 (pre-scaled by
        # LOG2E) so the inner loop uses exp2, which is cheaper on the VPU.
        q = q_ref[0, 0, :, :].astype(jnp.float32) * (sm_scale * LOG2E)
        k = k_ref[0, 0, :, :].astype(jnp.float32)  # (bk, d)
        v = v_ref[0, 0, :, :].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)  # (bq, bk)
        if masked:
            q_ids = q_start + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            k_ids = k_start + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(q_ids >= k_ids, s, NEG_INF)
        m_prev = m_ref[:, 0]
        l_prev = l_ref[:, 0]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        # Rows with every key masked so far have m_new == -inf; subtracting
        # -inf would give NaN, so shift by a safe 0 instead — every exp()
        # argument is then -inf and the row correctly accumulates nothing.
        m_safe = jnp.where(m_new == NEG_INF, 0.0, m_new)
        alpha = jnp.exp2(m_prev - m_safe)
        p = jnp.exp2(s - m_safe[:, None])
        l_new = l_prev * alpha + jnp.sum(p, axis=-1)
        acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = jax.lax.broadcast_in_dim(m_new, m_ref.shape, (0,))
        l_ref[...] = jax.lax.broadcast_in_dim(l_new, l_ref.shape, (0,))

    if causal:
        # Skip k blocks entirely in this q block's future; mask only blocks
        # straddling the diagonal — interior blocks skip the iota/where.
        # Offsets are dynamic scalars, so this is predicated rather than
        # pruned from the (static) grid.
        interior = k_start + block_k - 1 <= q_start
        pl.when(interior)(lambda: update(False))
        pl.when(jnp.logical_and(k_start <= last_q,
                                jnp.logical_not(interior)))(
            lambda: update(True))
    else:
        update(False)

    @pl.when(kj == nk - 1)
    def _finalize():
        m = m_ref[:, 0]
        l = l_ref[:, 0]
        # Fully-masked rows (l == 0): output 0, lse -inf so a later merge
        # treats this partial as absent.
        empty = l == 0.0
        l_safe = jnp.where(empty, 1.0, l)
        m_fin = jnp.where(empty, 0.0, m)
        o_ref[0, 0, :, :] = (acc_ref[...] / l_safe[:, None]).astype(o_ref.dtype)
        lse = jnp.where(empty, NEG_INF,
                        m_fin * (1.0 / LOG2E) + jnp.log(l_safe))
        # Row vectors are stored broadcast across LANES lanes to satisfy TPU
        # tiling (same layout as the stock TPU flash kernel's l/m buffers).
        lse_ref[0, 0, :, :] = jax.lax.broadcast_in_dim(
            lse, (block_q, LANES), (0,))


def _fwd_single_kernel(q_off_ref, k_off_ref, q_ref, k_ref, v_ref, o_ref,
                       lse_ref, *, sm_scale, causal, block_q, block_k):
    """Single-k-block forward: the whole key sequence is resident, so the
    softmax is direct — no m/l/acc scratch, no revolving online-softmax
    arithmetic, no @pl.when machinery. Measured r5 (B8 H16 S512 D64,
    docs/perf_experiments.md): 0.130 ms/call vs 0.321 ms for the general
    online-softmax kernel at the same shape — 2.5x — with the general
    kernel already 2.8x faster than the stock pallas flash kernel and
    1.3x faster than unfused XLA attention. The win is the removed
    scratch traffic and per-block bookkeeping, NOT the MXU (a 2-head
    128-deep-contraction packing variant measured the same 0.12 ms)."""
    qi = pl.program_id(2)
    q_start = q_off_ref[0] + qi * block_q
    k_start = k_off_ref[0]
    last_q = q_start + block_q - 1

    def compute(bk):
        # bk: static k extent — the causal wedge passes block_k//2 so
        # q blocks whose rows never see the upper half of the keys skip
        # half the dots and half the softmax arithmetic
        q = q_ref[0, 0, :, :].astype(jnp.float32) * (sm_scale * LOG2E)
        k = k_ref[0, 0, :bk, :].astype(jnp.float32)
        v = v_ref[0, 0, :bk, :].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if causal:
            q_ids = q_start + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, bk), 0)
            k_ids = k_start + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, bk), 1)
            s = jnp.where(q_ids >= k_ids, s, NEG_INF)
        m = jnp.max(s, axis=-1)
        # fully-masked rows: m = -inf; shift by 0 so p is 0, not NaN
        m_safe = jnp.where(m == NEG_INF, 0.0, m)
        p = jnp.exp2(s - m_safe[:, None])
        l = jnp.sum(p, axis=-1)
        empty = l == 0.0
        l_safe = jnp.where(empty, 1.0, l)
        o = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
        o_ref[0, 0, :, :] = (o / l_safe[:, None]).astype(o_ref.dtype)
        lse = jnp.where(empty, NEG_INF,
                        m_safe * (1.0 / LOG2E) + jnp.log(l_safe))
        lse_ref[0, 0, :, :] = jax.lax.broadcast_in_dim(
            lse, (block_q, LANES), (0,))

    if causal:
        # kv shards entirely in this q block's future are no-ops — the
        # ring-attention contract (parallel/ring.py: causal ring does
        # ~half the FLOPs because future shards self-skip). Offsets are
        # dynamic scalars, so predicate rather than prune the grid.
        relevant = k_start <= last_q
        half = block_k // 2
        if half and block_k % 2 == 0 and half % 128 == 0:
            # causal wedge: rows that never reach the keys' upper half
            # run the half-extent body — for in-model causal attention
            # (offsets 0) the first half of the q blocks take this
            # branch, cutting ~25% of the attention MACs and softmax
            # arithmetic overall
            needs_hi = last_q >= k_start + half

            @pl.when(needs_hi)
            def _():
                compute(block_k)

            @pl.when(jnp.logical_and(relevant,
                                     jnp.logical_not(needs_hi)))
            def _():
                compute(half)
        else:
            @pl.when(relevant)
            def _():
                compute(block_k)

        @pl.when(jnp.logical_not(relevant))
        def _():
            o_ref[0, 0, :, :] = jnp.zeros_like(o_ref[0, 0, :, :])
            lse_ref[0, 0, :, :] = jnp.full_like(lse_ref[0, 0, :, :],
                                                NEG_INF)
    else:
        compute(block_k)


def _single_specs(block_q, block_k, dim, ride):
    """BlockSpecs for the single-block (b, h, i) grids: ``ride`` names
    the operand the grid axis walks ("q" or "k"); the opposite side is
    pinned to block 0 (its whole extent is resident). Returns
    (q_spec, k_spec, q_row_spec) — the row spec follows the q side
    (lse/delta are per-q-row, lane-broadcast)."""
    walk = lambda b, h, i: (b, h, i, 0)
    pin = lambda b, h, i: (b, h, 0, 0)
    q_ix, k_ix = (walk, pin) if ride == "q" else (pin, walk)
    return (pl.BlockSpec((1, 1, block_q, dim), q_ix),
            pl.BlockSpec((1, 1, block_k, dim), k_ix),
            pl.BlockSpec((1, 1, block_q, LANES), q_ix))


def _make_specs(block_q, block_k, dim):
    """BlockSpecs for a (b, h, q-block, k-block) grid: q-side tiles index by
    the q-block id, k-side tiles by the k-block id — one block of each input
    is in VMEM per grid step regardless of sequence length."""
    q_spec = pl.BlockSpec((1, 1, block_q, dim), lambda b, h, i, j: (b, h, i, 0))
    k_spec = pl.BlockSpec((1, 1, block_k, dim), lambda b, h, i, j: (b, h, j, 0))
    qrow_spec = pl.BlockSpec((1, 1, block_q, LANES),
                             lambda b, h, i, j: (b, h, i, 0))
    return q_spec, k_spec, qrow_spec


# The scalar offsets ride as int32 arrays of shape (1,); gridded kernels see
# the whole array in scalar memory, indexed as ref[0].
_OFF_SPEC = pl.BlockSpec(memory_space=pltpu.SMEM)


def _flash_fwd(q, k, v, q_offset, k_offset, *, sm_scale, causal,
               block_q, block_k, interpret):
    batch, heads, q_seq, dim = q.shape
    kv_seq = k.shape[2]
    block_q = _pick_block(q_seq, block_q)
    block_k = _pick_block(kv_seq, block_k)
    grid = (batch, heads, q_seq // block_q, kv_seq // block_k)
    q_spec, k_spec, qrow_spec = _make_specs(block_q, block_k, dim)
    vma = _vma(q, k, v, q_offset, k_offset)

    if kv_seq == block_k:
        # whole key sequence in one block: direct softmax, no scratch
        # (see _fwd_single_kernel — measured 2.5x at the bench shapes)
        sq_spec, sk_spec, srow_spec = _single_specs(
            block_q, block_k, dim, ride="q")
        o, lse = pl.pallas_call(
            functools.partial(
                _fwd_single_kernel, sm_scale=sm_scale, causal=causal,
                block_q=block_q, block_k=block_k),
            grid=grid[:3],
            in_specs=[_OFF_SPEC, _OFF_SPEC, sq_spec, sk_spec, sk_spec],
            out_specs=[sq_spec, srow_spec],
            out_shape=[
                jax.ShapeDtypeStruct(q.shape, q.dtype, vma=vma),
                jax.ShapeDtypeStruct((batch, heads, q_seq, LANES),
                                     jnp.float32, vma=vma),
            ],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel",) * 3),
            interpret=interpret,
            name="flash_fwd",
        )(q_offset, k_offset, q, k, v)
        return o, lse

    kernel = functools.partial(
        _fwd_kernel, sm_scale=sm_scale, causal=causal,
        block_q=block_q, block_k=block_k)

    o, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[_OFF_SPEC, _OFF_SPEC, q_spec, k_spec, k_spec],
        out_specs=[q_spec, qrow_spec],
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, q.dtype, vma=vma),
            jax.ShapeDtypeStruct((batch, heads, q_seq, LANES), jnp.float32,
                                 vma=vma),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, dim), jnp.float32),
            pltpu.VMEM((block_q, LANES), jnp.float32),
            pltpu.VMEM((block_q, LANES), jnp.float32),
        ],
        compiler_params=_compiler_params(len(grid)),
        interpret=interpret,
        name="flash_fwd",
    )(q_offset, k_offset, q, k, v)
    return o, lse  # lse lane-broadcast: (B, H, S, LANES)


# ---------------------------------------------------------------------------
# Backward kernels
# ---------------------------------------------------------------------------


def _bwd_dq_kernel(q_off_ref, k_off_ref, q_ref, k_ref, v_ref, do_ref,
                   lse_ref, delta_ref, dq_ref, dq_acc_ref,
                   *, sm_scale, causal, block_q, block_k):
    qi = pl.program_id(2)
    kj = pl.program_id(3)
    nk = pl.num_programs(3)

    q_start = q_off_ref[0] + qi * block_q
    k_start = k_off_ref[0] + kj * block_k
    last_q = q_start + block_q - 1

    @pl.when(kj == 0)
    def _init():
        dq_acc_ref[...] = jnp.zeros_like(dq_acc_ref)

    def update(masked):
        cast = lambda r: r[0, 0, :, :].astype(jnp.float32)
        q = cast(q_ref)
        do = cast(do_ref)
        lse = lse_ref[0, 0, :, 0]
        delta = delta_ref[0, 0, :, 0]
        # Fully-masked rows have lse = -inf and all s = -inf; shifting by 0
        # instead of -inf keeps exp(s - lse) at 0 rather than NaN.
        lse_safe = jnp.where(lse == NEG_INF, 0.0, lse) * LOG2E
        k = cast(k_ref)
        v = cast(v_ref)
        s = (sm_scale * LOG2E) * jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        if masked:
            q_ids = q_start + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            k_ids = k_start + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(q_ids >= k_ids, s, NEG_INF)
        p = jnp.exp2(s - lse_safe[:, None])
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta[:, None]) * sm_scale
        dq_acc_ref[...] = dq_acc_ref[...] + jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    if causal:
        interior = k_start + block_k - 1 <= q_start
        pl.when(interior)(lambda: update(False))
        pl.when(jnp.logical_and(k_start <= last_q,
                                jnp.logical_not(interior)))(
            lambda: update(True))
    else:
        update(False)

    @pl.when(kj == nk - 1)
    def _finalize():
        dq_ref[0, 0, :, :] = dq_acc_ref[...].astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_off_ref, k_off_ref, q_ref, k_ref, v_ref, do_ref,
                    lse_ref, delta_ref, dk_ref, dv_ref, dk_acc_ref,
                    dv_acc_ref, *, sm_scale, causal, block_q, block_k):
    ki = pl.program_id(2)
    qj = pl.program_id(3)
    nq = pl.num_programs(3)

    k_start = k_off_ref[0] + ki * block_k
    q_start = q_off_ref[0] + qj * block_q
    last_q = q_start + block_q - 1

    @pl.when(qj == 0)
    def _init():
        dk_acc_ref[...] = jnp.zeros_like(dk_acc_ref)
        dv_acc_ref[...] = jnp.zeros_like(dv_acc_ref)

    def update(masked):
        cast = lambda r: r[0, 0, :, :].astype(jnp.float32)
        k = cast(k_ref)
        v = cast(v_ref)
        q = cast(q_ref)
        do = cast(do_ref)
        lse = lse_ref[0, 0, :, 0]
        delta = delta_ref[0, 0, :, 0]
        lse_safe = jnp.where(lse == NEG_INF, 0.0, lse) * LOG2E
        s = (sm_scale * LOG2E) * jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)  # (bq, bk)
        if masked:
            q_ids = q_start + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            k_ids = k_start + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(q_ids >= k_ids, s, NEG_INF)
        p = jnp.exp2(s - lse_safe[:, None])
        dv_acc_ref[...] = dv_acc_ref[...] + jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta[:, None]) * sm_scale
        dk_acc_ref[...] = dk_acc_ref[...] + jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    if causal:
        # q blocks entirely before this k block contribute nothing; blocks
        # entirely past the diagonal need no mask.
        interior = k_start + block_k - 1 <= q_start
        pl.when(interior)(lambda: update(False))
        pl.when(jnp.logical_and(last_q >= k_start,
                                jnp.logical_not(interior)))(
            lambda: update(True))
    else:
        update(False)

    @pl.when(qj == nq - 1)
    def _finalize():
        dk_ref[0, 0, :, :] = dk_acc_ref[...].astype(dk_ref.dtype)
        dv_ref[0, 0, :, :] = dv_acc_ref[...].astype(dv_ref.dtype)


def _bwd_dq_single_kernel(q_off_ref, k_off_ref, q_ref, k_ref, v_ref,
                          do_ref, lse_ref, delta_ref, dq_ref,
                          *, sm_scale, causal, block_q, block_k):
    """Single-k-block dq: the general kernel's accumulator scratch and
    per-k-block @pl.when machinery removed (same specialization as
    _fwd_single_kernel), with the causal wedge — q blocks whose rows
    never reach the keys' upper half run half-extent dots."""
    qi = pl.program_id(2)
    q_start = q_off_ref[0] + qi * block_q
    k_start = k_off_ref[0]
    last_q = q_start + block_q - 1

    def compute(bk):
        cast = lambda r, n: r[0, 0, :n, :].astype(jnp.float32)
        q = cast(q_ref, block_q)
        do = cast(do_ref, block_q)
        k = cast(k_ref, bk)
        v = cast(v_ref, bk)
        lse = lse_ref[0, 0, :, 0]
        delta = delta_ref[0, 0, :, 0]
        lse_safe = jnp.where(lse == NEG_INF, 0.0, lse) * LOG2E
        s = (sm_scale * LOG2E) * jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        if causal:
            q_ids = q_start + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, bk), 0)
            k_ids = k_start + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, bk), 1)
            s = jnp.where(q_ids >= k_ids, s, NEG_INF)
        p = jnp.exp2(s - lse_safe[:, None])
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta[:, None]) * sm_scale
        dq_ref[0, 0, :, :] = jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32).astype(dq_ref.dtype)

    if causal:
        relevant = k_start <= last_q
        half = block_k // 2
        if half and block_k % 2 == 0 and half % 128 == 0:
            needs_hi = last_q >= k_start + half

            @pl.when(needs_hi)
            def _():
                compute(block_k)

            @pl.when(jnp.logical_and(relevant,
                                     jnp.logical_not(needs_hi)))
            def _():
                compute(half)
        else:
            @pl.when(relevant)
            def _():
                compute(block_k)

        @pl.when(jnp.logical_not(relevant))
        def _():
            dq_ref[0, 0, :, :] = jnp.zeros_like(dq_ref[0, 0, :, :])
    else:
        compute(block_k)


def _bwd_dkv_single_kernel(q_off_ref, k_off_ref, q_ref, k_ref, v_ref,
                           do_ref, lse_ref, delta_ref, dk_ref, dv_ref,
                           *, sm_scale, causal, block_q, block_k):
    """Single-q-block dk/dv: scratch-free like _bwd_dq_single_kernel.
    (No wedge here — the causal cut for dk/dv runs along k COLUMNS,
    which does not map to a uniform static extent slice of the q
    operand.)"""
    ki = pl.program_id(2)
    k_start = k_off_ref[0] + ki * block_k
    q_start = q_off_ref[0]
    last_q = q_start + block_q - 1

    def compute():
        cast = lambda r: r[0, 0, :, :].astype(jnp.float32)
        q = cast(q_ref)
        k = cast(k_ref)
        v = cast(v_ref)
        do = cast(do_ref)
        lse = lse_ref[0, 0, :, 0]
        delta = delta_ref[0, 0, :, 0]
        lse_safe = jnp.where(lse == NEG_INF, 0.0, lse) * LOG2E
        s = (sm_scale * LOG2E) * jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        if causal:
            q_ids = q_start + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            k_ids = k_start + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(q_ids >= k_ids, s, NEG_INF)
        p = jnp.exp2(s - lse_safe[:, None])
        dv_ref[0, 0, :, :] = jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32).astype(dv_ref.dtype)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta[:, None]) * sm_scale
        dk_ref[0, 0, :, :] = jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32).astype(dk_ref.dtype)

    if causal:
        # a kv shard entirely in the future of every q row gets no
        # gradient (ring contract, mirror of the forward predication)
        relevant = k_start <= last_q

        @pl.when(relevant)
        def _():
            compute()

        @pl.when(jnp.logical_not(relevant))
        def _():
            dk_ref[0, 0, :, :] = jnp.zeros_like(dk_ref[0, 0, :, :])
            dv_ref[0, 0, :, :] = jnp.zeros_like(dv_ref[0, 0, :, :])
    else:
        compute()


def compute_delta(o, do) -> jax.Array:
    """The backward's per-row correction term, lane-broadcast: delta_i =
    sum_d do[i,d]·o[i,d], shape (B, H, S, LANES). Depends only on the final
    output/cotangent, so callers running many partial backwards against the
    same (o, do) — e.g. the ring sweep — compute it once and pass it in."""
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    return jnp.broadcast_to(delta[..., None], delta.shape + (LANES,))


def _flash_bwd(q, k, v, o, lse, do, q_offset, k_offset, *, sm_scale, causal,
               block_q, block_k, interpret, delta=None):
    batch, heads, q_seq, dim = q.shape
    kv_seq = k.shape[2]
    block_q = _pick_block(q_seq, block_q)
    block_k = _pick_block(kv_seq, block_k)
    if (causal and kv_seq == block_k and block_q == q_seq
            and q_seq >= 1024 and (q_seq // 2) % 128 == 0):
        # single-k-block causal: two q blocks let the dq wedge skip the
        # first block's upper-half dots (measured r5 at the GPT-2
        # shape: fwd+bwd 1.697 -> 1.555 ms, incl. the dkv kernel
        # falling back to the general path).
        block_q = q_seq // 2

    if delta is None:
        delta = compute_delta(o, do)

    q_spec, k_spec, qrow_spec = _make_specs(block_q, block_k, dim)

    vma = _vma(q, k, v, do, q_offset, k_offset)

    if kv_seq == block_k:
        # scratch-free single-k-block dq (with causal wedge), any nq
        sq_spec, sk_spec, srow_spec = _single_specs(
            block_q, block_k, dim, ride="q")
        dq = pl.pallas_call(
            functools.partial(
                _bwd_dq_single_kernel, sm_scale=sm_scale, causal=causal,
                block_q=block_q, block_k=block_k),
            grid=(batch, heads, q_seq // block_q),
            in_specs=[_OFF_SPEC, _OFF_SPEC, sq_spec, sk_spec, sk_spec,
                      sq_spec, srow_spec, srow_spec],
            out_specs=sq_spec,
            out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype, vma=vma),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel",) * 3),
            interpret=interpret,
            name="flash_dq",
        )(q_offset, k_offset, q, k, v, do, lse, delta)
    else:
        dq = None

    if q_seq == block_q:
        # scratch-free single-q-block dk/dv, any nk
        gq_spec, gk_spec, grow_spec = _single_specs(
            block_q, block_k, dim, ride="k")
        dk, dv = pl.pallas_call(
            functools.partial(
                _bwd_dkv_single_kernel, sm_scale=sm_scale,
                causal=causal, block_q=block_q, block_k=block_k),
            grid=(batch, heads, kv_seq // block_k),
            in_specs=[_OFF_SPEC, _OFF_SPEC, gq_spec, gk_spec, gk_spec,
                      gq_spec, grow_spec, grow_spec],
            out_specs=[gk_spec, gk_spec],
            out_shape=[
                jax.ShapeDtypeStruct(k.shape, k.dtype, vma=vma),
                jax.ShapeDtypeStruct(v.shape, v.dtype, vma=vma),
            ],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel",) * 3),
            interpret=interpret,
            name="flash_dkv",
        )(q_offset, k_offset, q, k, v, do, lse, delta)
    else:
        dk = dv = None

    if dq is None:
        # multi-k-block: the general accumulating dq kernel
        dq = pl.pallas_call(
            functools.partial(
                _bwd_dq_kernel, sm_scale=sm_scale, causal=causal,
                block_q=block_q, block_k=block_k),
            grid=(batch, heads, q_seq // block_q, kv_seq // block_k),
            in_specs=[_OFF_SPEC, _OFF_SPEC, q_spec, k_spec, k_spec,
                      q_spec, qrow_spec, qrow_spec],
            out_specs=q_spec,
            out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype, vma=vma),
            scratch_shapes=[pltpu.VMEM((block_q, dim), jnp.float32)],
            compiler_params=_compiler_params(4),
            interpret=interpret,
            name="flash_dq",
        )(q_offset, k_offset, q, k, v, do, lse, delta)

    if dk is None:
        # multi-q-block: general dk/dv — grid over (b, h, k-block,
        # q-block), q-side tiles streaming along the innermost axis
        # while dk/dv accumulate in scratch.
        kq_k_spec = pl.BlockSpec((1, 1, block_k, dim),
                                 lambda b, h, i, j: (b, h, i, 0))
        kq_q_spec = pl.BlockSpec((1, 1, block_q, dim),
                                 lambda b, h, i, j: (b, h, j, 0))
        kq_qrow_spec = pl.BlockSpec((1, 1, block_q, LANES),
                                    lambda b, h, i, j: (b, h, j, 0))

        dk, dv = pl.pallas_call(
            functools.partial(
                _bwd_dkv_kernel, sm_scale=sm_scale, causal=causal,
                block_q=block_q, block_k=block_k),
            grid=(batch, heads, kv_seq // block_k, q_seq // block_q),
            in_specs=[_OFF_SPEC, _OFF_SPEC, kq_q_spec, kq_k_spec,
                      kq_k_spec, kq_q_spec, kq_qrow_spec, kq_qrow_spec],
            out_specs=[kq_k_spec, kq_k_spec],
            out_shape=[
                jax.ShapeDtypeStruct(k.shape, k.dtype, vma=vma),
                jax.ShapeDtypeStruct(v.shape, v.dtype, vma=vma),
            ],
            scratch_shapes=[
                pltpu.VMEM((block_k, dim), jnp.float32),
                pltpu.VMEM((block_k, dim), jnp.float32),
            ],
            compiler_params=_compiler_params(4),
            interpret=interpret,
            name="flash_dkv",
        )(q_offset, k_offset, q, k, v, do, lse, delta)

    return dq, dk, dv


# ---------------------------------------------------------------------------
# Public API: differentiable flash attention (+ residuals for ring merging)
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9, 10))
def _flash(q, k, v, q_offset, k_offset, sm_scale, causal, block_q, block_k,
           bwd_block_q, bwd_block_k):
    o, _ = _flash_fwd(q, k, v, q_offset, k_offset, sm_scale=sm_scale,
                      causal=causal, block_q=block_q, block_k=block_k,
                      interpret=use_interpret())
    return o


def _flash_vjp_fwd(q, k, v, q_offset, k_offset, sm_scale, causal,
                   block_q, block_k, bwd_block_q, bwd_block_k):
    o, lse = _flash_fwd(q, k, v, q_offset, k_offset, sm_scale=sm_scale,
                        causal=causal, block_q=block_q, block_k=block_k,
                        interpret=use_interpret())
    return o, (q, k, v, o, lse, q_offset, k_offset)


def _flash_vjp_bwd(sm_scale, causal, block_q, block_k, bwd_block_q,
                   bwd_block_k, res, do):
    q, k, v, o, lse, q_offset, k_offset = res
    dq, dk, dv = _flash_bwd(q, k, v, o, lse, do, q_offset, k_offset,
                            sm_scale=sm_scale, causal=causal,
                            block_q=bwd_block_q, block_k=bwd_block_k,
                            interpret=use_interpret())
    zero = jnp.zeros((1,), jnp.int32)
    return dq, dk, dv, zero, zero


_flash.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


def _as_offset(x) -> jax.Array:
    return jnp.asarray(x, jnp.int32).reshape((1,))


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = False,
    sm_scale: Optional[float] = None,
    q_offset=0,
    k_offset=0,
    block_q: int = 512,
    block_k: int = 1024,
    bwd_block_q: int = 1024,
    bwd_block_k: int = 1024,
) -> jax.Array:
    """Fused attention over ``(batch, heads, seq, head_dim)`` inputs.

    ``q_offset``/``k_offset`` are the global sequence positions of the first
    query/key row — used by ring attention, where each device holds one
    sequence shard and the causal mask depends on global, not local, indices.
    They may be traced scalars (e.g. derived from ``lax.axis_index``).

    Block-size defaults are tuned on v5e (head_dim 128): the forward prefers
    tall k blocks, the backward square 1024 blocks. Sequences shorter than a
    block fall back to the largest divisor automatically.
    """
    if q.ndim != 4 or k.ndim != 4 or v.ndim != 4:
        raise ValueError("flash_attention expects (batch, heads, seq, dim)")
    if sm_scale is None:
        sm_scale = 1.0 / float(np.sqrt(q.shape[-1]))
    return _flash(q, k, v, _as_offset(q_offset), _as_offset(k_offset),
                  float(sm_scale), bool(causal), int(block_q), int(block_k),
                  int(bwd_block_q), int(bwd_block_k))


def flash_attention_partial(
    q, k, v, *, causal=False, sm_scale=None, q_offset=0, k_offset=0,
    block_q: int = 512, block_k: int = 1024,
):
    """Forward-only partial attention returning ``(out, lse)``.

    ``out`` is normalised over the *local* keys only; ``lse`` is the per-row
    log-sum-exp normaliser, so partials over disjoint key shards can be
    combined exactly with :func:`merge_partials`. Used by the ring-attention
    forward (the ring backward re-derives gradients through its own loop).
    """
    if sm_scale is None:
        sm_scale = 1.0 / float(np.sqrt(q.shape[-1]))
    o, lse = _flash_fwd(q, k, v, _as_offset(q_offset), _as_offset(k_offset),
                        sm_scale=float(sm_scale), causal=bool(causal),
                        block_q=int(block_q), block_k=int(block_k),
                        interpret=use_interpret())
    return o, lse[..., 0]


def merge_partials(o_a, lse_a, o_b, lse_b):
    """Exactly combine two attention partials over disjoint key sets.

    Each partial is (normalised output, log-sum-exp). Rows absent from one
    side carry ``lse = -inf`` and contribute nothing.
    """
    lse = jnp.logaddexp(lse_a, lse_b)
    # exp(-inf - -inf) would be NaN; an absent row has weight exactly 0.
    w_a = jnp.where(lse_a == NEG_INF, 0.0, jnp.exp(lse_a - lse))
    w_b = jnp.where(lse_b == NEG_INF, 0.0, jnp.exp(lse_b - lse))
    o = (o_a.astype(jnp.float32) * w_a[..., None]
         + o_b.astype(jnp.float32) * w_b[..., None])
    return o.astype(o_a.dtype), lse


def attention_reference(q, k, v, *, causal=False, sm_scale=None,
                        q_offset=0, k_offset=0):
    """Naive O(seq²) attention — ground truth for kernel tests."""
    if sm_scale is None:
        sm_scale = 1.0 / float(np.sqrt(q.shape[-1]))
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * sm_scale
    if causal:
        q_ids = q_offset + jnp.arange(q.shape[2])[:, None]
        k_ids = k_offset + jnp.arange(k.shape[2])[None, :]
        s = jnp.where(q_ids >= k_ids, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    p = jnp.where(jnp.isnan(p), 0.0, p)  # fully-masked rows
    return jnp.einsum("bhqk,bhkd->bhqd", p,
                      v.astype(jnp.float32)).astype(q.dtype)
