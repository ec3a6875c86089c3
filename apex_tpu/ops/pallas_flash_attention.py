"""Pallas blocked flash attention (fwd + bwd) for the MXU.

New capability relative to the reference (2019, pre-attention — SURVEY.md
§5): apex_tpu treats transformer workloads as first-class.  This kernel
is the compute core of ``transformer.dot_product_attention`` and, through
it, ``ulysses_attention``'s per-head local attention.  (Ring attention
keeps its own jnp online-softmax accumulation: its inner blocks interleave
with ppermutes and XLA fuses them against the collective.)

Design (FlashAttention-style, true blocked form): the grid is
(batch*heads, q_blocks, k_blocks) with the k axis innermost ("arbitrary"
semantics, executed sequentially per core).  K and V are *streamed* one
(BLK, D) block at a time — nothing scales with T in VMEM — while online
softmax state (running max m, running sum l, unnormalized accumulator)
lives in VMEM scratch that persists across the k-block sweep.  The
forward emits the per-row logsumexp; the backward recomputes
probabilities from it in two streamed passes: a dQ pass (K/V streamed)
and a dK/dV pass (Q/dO streamed), each a handful of MXU contractions per
block pair.  Causal q/k block pairs above the diagonal are skipped via
``pl.when``.  With a sliding ``window`` (key j visible to query i iff
i - W < j <= i) the streamed axis of each grid covers only the
``_band_blocks`` blocks a row of blocks can see: a block pair wholly
outside the band is no grid step at all, so it is neither computed nor
fetched, and the cost of a windowed layer is linear in T.

Per-row statistics (lse, delta and the m/l scratch) are stored
lane-broadcast as (rows, 128) tiles — Mosaic requires the last two block
dims to be (8k, 128k)-aligned, so a (rows,) vector is carried as a full
lane tile with every lane equal (same layout the upstream
jax.experimental.pallas.ops.tpu.flash_attention uses).

Matmuls feed the MXU in the input dtype (bf16 stays bf16) with fp32
accumulation via ``preferred_element_type``; softmax state is always
fp32.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .pallas_common import LANES, interpret

_VMEM_BUDGET = 12 * 1024 * 1024
_BLK = 512          # q/k rows per block (clamped to the padded seq len)
_NEG = -1e30


def _dot(a, b, contract):
    """MXU contraction with fp32 accumulation.  Precision is pinned here
    rather than inherited from jax_default_matmul_precision: fp32
    operands get the full-precision passes (parity-grade), while bf16
    operands stay native — Mosaic rejects fp32 contract precision on
    bf16 inputs."""
    prec = (jax.lax.Precision.HIGHEST if a.dtype == jnp.float32
            else jax.lax.Precision.DEFAULT)
    return lax.dot_general(a, b, (contract, ((), ())),
                           preferred_element_type=jnp.float32,
                           precision=prec)


def _keep_unit(seed0, seed1, bh, qpos, kpos):
    """Deterministic per-(batch·head, q-pos, k-pos) uniform in [0, 1).

    Counter-based murmur3-finalizer hash over plain int32 ops (multiply
    wraps two's-complement, xor, logical shifts) — the same code runs
    inside the Pallas kernels, under interpret mode, and as the dense
    test reference, so dropout masks are bitwise-identical across the
    forward, both backward passes, and the reference implementation.
    ``seed0``/``seed1`` carry 64 bits of seed (two int32 words — one
    word would collide by birthday bound across ~1e6 layer·step draws);
    ``bh`` scalar; ``qpos``/``kpos`` broadcastable int32 position
    arrays."""
    # numpy scalar constants inline as jaxpr literals — jnp constants
    # would become constvars, which pallas_call cannot lower
    h = (qpos * np.int32(-1640531527)                      # 2654435761
         ^ kpos * np.int32(-2048144777)                    # 2246822519
         ^ bh * np.int32(-1028477379)                      # 3266489917
         ^ seed0)
    h = h ^ lax.shift_right_logical(h, np.int32(16))
    h = h * np.int32(-2048144789)
    h = h ^ seed1
    h = h ^ lax.shift_right_logical(h, np.int32(16))
    h = h * np.int32(-1028477387)
    h = h ^ lax.shift_right_logical(h, np.int32(16))
    # 31 uniform bits -> [0, 1)
    bits = jnp.bitwise_and(h, np.int32(0x7FFFFFFF))
    return bits.astype(jnp.float32) * np.float32(1.0 / 2147483648.0)


def _block_for(T: int) -> int:
    """Largest block in {512, 256, 128} that divides the lane-padded
    length — bounds zero-padding at 127 rows (a fixed 512 block would pad
    T=600 to 1024, wasting 41% of every MXU contraction)."""
    Tp = -(-T // LANES) * LANES
    for blk in (_BLK, 256, LANES):
        if Tp % blk == 0:
            return min(blk, Tp)
    return LANES


def _band_blocks(window: int, blk: int, n: int) -> int:
    """Blocks of ``blk`` keys that the queries of one block can see through
    a causal window of ``window`` keys (at most all ``n``): the diagonal
    block and those the lowest query row reaches back into."""
    return min(n, -(-(window - 1) // blk) + 1)


def fits_vmem(T: int, D: int, dropout: bool = False,
              segments: bool = False) -> bool:
    """VMEM needed per grid step — independent of T now that K/V stream
    through the grid.  Sized for the worst pass (backward dK/dV): six
    double-buffered operand blocks (q, k, v, do in; dk, dv out), two fp32
    accumulator scratches, the lane-broadcast stats tiles, and the
    (blk, blk) score/prob/dp/ds intermediates.  Dropout holds two more
    live (blk, blk) tiles in the dk/dv pass (the hash tile u and p_acc
    alongside p/dp/ds); segments double-buffer the q-id (blk, LANES) and
    k-id (8, blk) tiles plus the (blk, blk) equality mask."""
    blk = _block_for(T)
    Dp = -(-D // LANES) * LANES
    operands = 6 * blk * Dp          # q, k, v, do, dk, dv blocks
    stats = 2 * blk * LANES          # lse + delta tiles
    resident = 2 * (operands + stats) * 4          # double-buffered
    scratch = 2 * blk * Dp * 4                     # dk/dv fp32 accumulators
    ntiles = 6 if dropout else 4     # s/p, dp, ds (+ u, p_acc)
    if segments:
        ntiles += 1                  # the id-equality mask
        resident += 2 * (blk * LANES + 8 * blk) * 4    # qseg + kseg tiles
    score = ntiles * blk * blk * 4
    return resident + scratch + score <= _VMEM_BUDGET


def _pad_to(x, T, D):
    t, d = x.shape[-2:]
    if t == T and d == D:
        return x
    pad = [(0, 0)] * (x.ndim - 2) + [(0, T - t), (0, D - d)]
    return jnp.pad(x, pad)


def _lanes(vec, Tp):
    """(BH, T) → (BH, Tp, LANES) lane-broadcast fp32."""
    BH, T = vec.shape
    v = jnp.pad(vec.astype(jnp.float32), ((0, 0), (0, Tp - T)))
    return jax.lax.broadcast_in_dim(v, (BH, Tp, LANES), (0, 1))


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _fwd_kernel(*refs, scale, causal, has_mask, has_segments,
                dropout_rate, T_real, blk, nk, window=None):
    refs = list(refs)
    q_ref, k_ref, v_ref = refs[:3]
    del refs[:3]
    kvm_ref = refs.pop(0) if has_mask else None
    if has_segments:
        qseg_ref = refs.pop(0)
        kseg_ref = refs.pop(0)
    else:
        qseg_ref = kseg_ref = None
    seed_ref = refs.pop(0) if dropout_rate else None
    o_ref, lse_ref, m_ref, l_ref, acc_ref = refs
    b = pl.program_id(0)
    i = pl.program_id(1)
    step = pl.program_id(2)

    @pl.when(step == 0)
    def _init():
        m_ref[...] = jnp.full(m_ref.shape, _NEG, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    if window is None:
        j = step
        # causal: the (i, j) block pair is dead when its lowest q row sits
        # above its lowest k column (j*blk > i*blk + blk - 1 ⇔ j > i)
        run = (j <= i) if causal else (j >= 0)
    else:
        # the nk steps end at the diagonal block; those that would start
        # before block 0 are dead (their fetch is clamped onto block 0)
        j = i - (nk - 1) + step
        run = j >= 0

    @pl.when(run)
    def _compute():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        s = _dot(q, k, ((1,), (1,))) * scale
        kpos = j * blk + lax.broadcasted_iota(jnp.int32, s.shape, 1)
        valid = kpos < T_real
        qpos = i * blk + lax.broadcasted_iota(jnp.int32, s.shape, 0)
        if causal:
            valid = jnp.logical_and(valid, qpos >= kpos)
        if window is not None:
            valid = jnp.logical_and(valid, kpos > qpos - window)
        if has_mask:
            # (1, blk) key-validity row, sublane-broadcast tile layout:
            # k positions on the lane axis, matching s's column axis
            valid = jnp.logical_and(valid, kvm_ref[0][:1, :] > 0.5)
        if has_segments:
            # packed sequences: attend only within the same segment —
            # q ids ride the lane-broadcast (stat) layout as a (blk, 1)
            # column, k ids the sublane layout as a (1, blk) row
            valid = jnp.logical_and(
                valid, qseg_ref[0][:, :1] == kseg_ref[0][:1, :])
        s = jnp.where(valid, s, _NEG)
        m_prev = m_ref[...][:, :1]                      # (blk, 1)
        l_prev = l_ref[...][:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        # explicit zeroing: when a row is fully masked m_new == _NEG and
        # exp(s - m_new) would be exp(0) = 1 on the masked entries
        p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
        # the softmax normalizer uses the UNdropped probabilities; only
        # the value accumulation is dropped+rescaled (FlashAttention's
        # dropout placement — the mask is regenerated bitwise in both
        # backward passes from the same counter hash)
        l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        if dropout_rate:
            u = _keep_unit(seed_ref[0, 0], seed_ref[0, 1], b, qpos, kpos)
            p_acc = jnp.where(u >= dropout_rate, p, 0.0) * (
                1.0 / (1.0 - dropout_rate))
        else:
            p_acc = p
        pv = _dot(p_acc.astype(v.dtype), v, ((1,), (0,)))
        acc_ref[...] = acc_ref[...] * alpha + pv
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(step == nk - 1)
    def _done():
        l = l_ref[...][:, :1]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_ref[...] / l_safe).astype(o_ref.dtype)
        lse_ref[0] = m_ref[...] + jnp.log(jnp.broadcast_to(l_safe,
                                                           lse_ref.shape[1:]))


@functools.partial(jax.jit, static_argnames=("scale", "causal", "H",
                                             "dropout_rate", "window"))
def _fwd(q, k, v, kvm, qseg, kseg, seed, scale, causal, H, dropout_rate,
         window=None):
    """kvm: (B, 8, Tp) fp32 key-validity (sublane-broadcast) or None.
    qseg/kseg: (B, Tp, LANES) lane- / (B, 8, Tp) sublane-broadcast int32
    segment ids or None.  seed: (1, 2) int32 dropout seed or None."""
    BH, T, D = q.shape
    blk = _block_for(T)
    Tp = -(-T // blk) * blk
    Dp = -(-D // LANES) * LANES
    qp, kp, vp = (_pad_to(x, Tp, Dp) for x in (q, k, v))
    nq, nk = Tp // blk, Tp // blk
    if window is None:
        kb = lambda i, j: j              # the k block of grid step (i, j)
    else:
        nk = _band_blocks(window, blk, nk)
        kb = lambda i, j: jnp.maximum(i - (nk - 1) + j, 0)
    grid = (BH, nq, nk)
    row = pl.BlockSpec((1, blk, Dp), lambda b, i, j: (b, i, 0))
    col = pl.BlockSpec((1, blk, Dp), lambda b, i, j: (b, kb(i, j), 0))
    stat = pl.BlockSpec((1, blk, LANES), lambda b, i, j: (b, i, 0))
    has_mask = kvm is not None
    has_segments = qseg is not None
    in_specs = [row, col, col]
    operands = [qp, kp, vp]
    if has_mask:
        in_specs.append(pl.BlockSpec((1, 8, blk),
                                     lambda b, i, j: (b // H, 0, kb(i, j))))
        operands.append(kvm)
    if has_segments:
        in_specs.append(pl.BlockSpec((1, blk, LANES),
                                     lambda b, i, j: (b // H, i, 0)))
        operands.append(qseg)
        in_specs.append(pl.BlockSpec((1, 8, blk),
                                     lambda b, i, j: (b // H, 0, kb(i, j))))
        operands.append(kseg)
    if dropout_rate:
        in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
        operands.append(seed)
    o, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, causal=causal,
                          has_mask=has_mask, has_segments=has_segments,
                          dropout_rate=dropout_rate,
                          T_real=T, blk=blk, nk=nk, window=window),
        grid=grid,
        in_specs=in_specs,
        out_specs=[row, stat],
        out_shape=[jax.ShapeDtypeStruct((BH, Tp, Dp), q.dtype),
                   jax.ShapeDtypeStruct((BH, Tp, LANES), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((blk, LANES), jnp.float32),
                        pltpu.VMEM((blk, LANES), jnp.float32),
                        pltpu.VMEM((blk, Dp), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret(),
        name="flash_fwd",
    )(*operands)
    return o[:, :T, :D], lse[:, :T, 0]


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------

def _dq_kernel(*refs, scale, causal, has_mask, has_segments, dropout_rate,
               T_real, blk, nk, window=None):
    refs = list(refs)
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref = refs[:6]
    del refs[:6]
    kvm_ref = refs.pop(0) if has_mask else None
    if has_segments:
        qseg_ref = refs.pop(0)
        kseg_ref = refs.pop(0)
    else:
        qseg_ref = kseg_ref = None
    seed_ref = refs.pop(0) if dropout_rate else None
    dq_ref, dq_acc = refs
    b = pl.program_id(0)
    i = pl.program_id(1)
    step = pl.program_id(2)

    @pl.when(step == 0)
    def _init():
        dq_acc[...] = jnp.zeros(dq_acc.shape, jnp.float32)

    if window is None:
        j = step
        run = (j <= i) if causal else (j >= 0)
    else:                       # as in _fwd_kernel
        j = i - (nk - 1) + step
        run = j >= 0

    @pl.when(run)
    def _compute():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        lse = lse_ref[0][:, :1]
        delta = delta_ref[0][:, :1]
        s = _dot(q, k, ((1,), (1,))) * scale
        kpos = j * blk + lax.broadcasted_iota(jnp.int32, s.shape, 1)
        valid = kpos < T_real
        qpos = i * blk + lax.broadcasted_iota(jnp.int32, s.shape, 0)
        if causal:
            valid = jnp.logical_and(valid, qpos >= kpos)
        if window is not None:
            valid = jnp.logical_and(valid, kpos > qpos - window)
        if has_mask:
            valid = jnp.logical_and(valid, kvm_ref[0][:1, :] > 0.5)
        if has_segments:
            valid = jnp.logical_and(
                valid, qseg_ref[0][:, :1] == kseg_ref[0][:1, :])
        p = jnp.where(valid, jnp.exp(s - lse), 0.0)
        dp = _dot(do, v, ((1,), (1,)))
        if dropout_rate:
            # dS = P ∘ (M ∘ (dO Vᵀ)/keep − delta): same counter hash as
            # the forward, so the mask is bitwise-identical
            u = _keep_unit(seed_ref[0, 0], seed_ref[0, 1], b, qpos, kpos)
            dp = jnp.where(u >= dropout_rate, dp, 0.0) * (
                1.0 / (1.0 - dropout_rate))
        ds = (p * (dp - delta)).astype(k.dtype)
        dq_acc[...] += _dot(ds, k, ((1,), (0,))) * scale

    @pl.when(step == nk - 1)
    def _done():
        dq_ref[0] = dq_acc[...].astype(dq_ref.dtype)


def _dkv_kernel(*refs, scale, causal, has_mask, has_segments,
                dropout_rate, T_real, blk, nq, window=None, nq_all=None):
    refs = list(refs)
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref = refs[:6]
    del refs[:6]
    kvm_ref = refs.pop(0) if has_mask else None
    if has_segments:
        qseg_ref = refs.pop(0)
        kseg_ref = refs.pop(0)
    else:
        qseg_ref = kseg_ref = None
    seed_ref = refs.pop(0) if dropout_rate else None
    dk_ref, dv_ref, dk_acc, dv_acc = refs
    b = pl.program_id(0)
    i = pl.program_id(1)          # k block
    step = pl.program_id(2)       # q block (streamed)

    @pl.when(step == 0)
    def _init():
        dk_acc[...] = jnp.zeros(dk_acc.shape, jnp.float32)
        dv_acc[...] = jnp.zeros(dv_acc.shape, jnp.float32)

    if window is None:
        j = step
        # causal: q block j only sees k block i when j*blk + blk - 1 >= i*blk
        run = (j >= i) if causal else (j >= 0)
    else:
        # the nq steps start at the diagonal block; those past the last q
        # block are dead (their fetch is clamped onto the last block)
        j = i + step
        run = j < nq_all

    @pl.when(run)
    def _compute():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        lse = lse_ref[0][:, :1]
        delta = delta_ref[0][:, :1]
        s = _dot(q, k, ((1,), (1,))) * scale
        kpos = i * blk + lax.broadcasted_iota(jnp.int32, s.shape, 1)
        valid = kpos < T_real
        qpos = j * blk + lax.broadcasted_iota(jnp.int32, s.shape, 0)
        if causal:
            valid = jnp.logical_and(valid, qpos >= kpos)
        if window is not None:
            valid = jnp.logical_and(valid, kpos > qpos - window)
        if has_mask:
            valid = jnp.logical_and(valid, kvm_ref[0][:1, :] > 0.5)
        if has_segments:
            valid = jnp.logical_and(
                valid, qseg_ref[0][:, :1] == kseg_ref[0][:1, :])
        # padded q rows contribute nothing: their do rows are zero
        p = jnp.where(valid, jnp.exp(s - lse), 0.0)       # (bq, bk)
        dp = _dot(do, v, ((1,), (1,)))
        if dropout_rate:
            # absolute (qpos, kpos) arguments match the fwd/dq passes
            # exactly, so the regenerated mask is bitwise-identical
            u = _keep_unit(seed_ref[0, 0], seed_ref[0, 1], b, qpos, kpos)
            keep = u >= dropout_rate
            inv_keep = 1.0 / (1.0 - dropout_rate)
            p_acc = jnp.where(keep, p, 0.0) * inv_keep
            dp = jnp.where(keep, dp, 0.0) * inv_keep
        else:
            p_acc = p
        dv_acc[...] += _dot(p_acc.astype(do.dtype), do, ((0,), (0,)))
        ds = (p * (dp - delta)).astype(q.dtype)
        dk_acc[...] += _dot(ds, q, ((0,), (0,))) * scale

    @pl.when(step == nq - 1)
    def _done():
        dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "causal", "H",
                                             "dropout_rate", "window"))
def _bwd(q, k, v, o, lse, do, kvm, qseg, kseg, seed, scale, causal, H,
         dropout_rate, window=None):
    BH, T, D = q.shape
    blk = _block_for(T)
    Tp = -(-T // blk) * blk
    Dp = -(-D // LANES) * LANES
    qp, kp, vp = (_pad_to(x, Tp, Dp) for x in (q, k, v))
    dop = _pad_to(do, Tp, Dp)
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), -1)
    deltap = _lanes(delta, Tp)
    lsep = _lanes(lse, Tp)
    nq = nk = n = Tp // blk
    has_mask = kvm is not None
    has_segments = qseg is not None
    sem = pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"))

    rowi = pl.BlockSpec((1, blk, Dp), lambda b, i, j: (b, i, 0))
    stati = pl.BlockSpec((1, blk, LANES), lambda b, i, j: (b, i, 0))
    kvmi = pl.BlockSpec((1, 8, blk), lambda b, i, j: (b // H, 0, i))
    qsegi = pl.BlockSpec((1, blk, LANES), lambda b, i, j: (b // H, i, 0))
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)

    # what streams along a grid's last axis, ``sj(i, j)`` being the block of
    # step j in row i: operand blocks, row statistics and q-segment ids (the
    # lane-broadcast stat layout), key-validity / k-segment tiles
    def operand(sj):
        return pl.BlockSpec((1, blk, Dp), lambda b, i, j: (b, sj(i, j), 0))

    def stat(sj, per_head=True):
        return pl.BlockSpec(
            (1, blk, LANES),
            lambda b, i, j: (b if per_head else b // H, sj(i, j), 0))

    def key_tile(sj):
        return pl.BlockSpec((1, 8, blk),
                            lambda b, i, j: (b // H, 0, sj(i, j)))

    if window is None:
        kstep = qstep = lambda i, j: j
    else:
        # dq: the k blocks up to the diagonal; dk/dv: the q blocks from it
        nk = nq = _band_blocks(window, blk, n)
        kstep = lambda i, j: jnp.maximum(i - (nk - 1) + j, 0)
        qstep = lambda i, j: jnp.minimum(i + j, n - 1)
    # the dq pass streams K/V (and their tiles) along j; the dk/dv pass has
    # them on its i axis and streams Q, dO, their statistics and q ids
    colj, kvmj = operand(kstep), key_tile(kstep)
    colq, statj, qsegj = operand(qstep), stat(qstep), stat(qstep, False)

    dq_specs = [rowi, colj, colj, rowi, stati, stati]
    dq_ops = [qp, kp, vp, dop, lsep, deltap]
    if has_mask:
        dq_specs.append(kvmj)
        dq_ops.append(kvm)
    if has_segments:
        dq_specs += [qsegi, kvmj]        # k ids share the kvm layout
        dq_ops += [qseg, kseg]
    if dropout_rate:
        dq_specs.append(smem)
        dq_ops.append(seed)
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, scale=scale, causal=causal,
                          has_mask=has_mask, has_segments=has_segments,
                          dropout_rate=dropout_rate,
                          T_real=T, blk=blk, nk=nk, window=window),
        grid=(BH, n, nk),
        in_specs=dq_specs,
        out_specs=rowi,
        out_shape=jax.ShapeDtypeStruct((BH, Tp, Dp), q.dtype),
        scratch_shapes=[pltpu.VMEM((blk, Dp), jnp.float32)],
        compiler_params=sem,
        interpret=interpret(),
        name="flash_dq",
    )(*dq_ops)

    dkv_specs = [colq, rowi, rowi, colq, statj, statj]
    dkv_ops = [qp, kp, vp, dop, lsep, deltap]
    if has_mask:
        dkv_specs.append(kvmi)
        dkv_ops.append(kvm)
    if has_segments:
        # dkv grid: i = k block, j = q block — q ids stream along j,
        # k ids along i (sharing the kvm layouts)
        dkv_specs += [qsegj, kvmi]
        dkv_ops += [qseg, kseg]
    if dropout_rate:
        dkv_specs.append(smem)
        dkv_ops.append(seed)
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, scale=scale, causal=causal,
                          has_mask=has_mask, has_segments=has_segments,
                          dropout_rate=dropout_rate,
                          T_real=T, blk=blk, nq=nq, window=window,
                          nq_all=n),
        grid=(BH, n, nq),
        in_specs=dkv_specs,
        out_specs=[rowi, rowi],
        out_shape=[jax.ShapeDtypeStruct((BH, Tp, Dp), k.dtype),
                   jax.ShapeDtypeStruct((BH, Tp, Dp), v.dtype)],
        scratch_shapes=[pltpu.VMEM((blk, Dp), jnp.float32),
                        pltpu.VMEM((blk, Dp), jnp.float32)],
        compiler_params=sem,
        interpret=interpret(),
        name="flash_dkv",
    )(*dkv_ops)
    return dq[:, :T, :D], dk[:, :T, :D], dv[:, :T, :D]


# ---------------------------------------------------------------------------
# public op
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8, 9, 10, 11))
def _flash(q3, k3, v3, kvm, qseg, kseg, seed, scale: float, causal: bool,
           H: int, dropout_rate: float, window: Optional[int]):
    o, _ = _fwd(q3, k3, v3, kvm, qseg, kseg, seed, scale, causal, H,
                dropout_rate, window)
    return o


def _flash_fwd(q3, k3, v3, kvm, qseg, kseg, seed, scale, causal, H,
               dropout_rate, window):
    o, lse = _fwd(q3, k3, v3, kvm, qseg, kseg, seed, scale, causal, H,
                  dropout_rate, window)
    return o, (q3, k3, v3, o, lse, kvm, qseg, kseg, seed)


def _flash_bwd(scale, causal, H, dropout_rate, window, res, do):
    q3, k3, v3, o, lse, kvm, qseg, kseg, seed = res
    dq, dk, dv = _bwd(q3, k3, v3, o, lse, do, kvm, qseg, kseg, seed,
                      scale, causal, H, dropout_rate, window)
    dkvm = None if kvm is None else jnp.zeros_like(kvm)
    # int primals -> float0 cotangents
    f0 = lambda a: (None if a is None
                    else np.zeros(a.shape, jax.dtypes.float0))
    return (dq.astype(q3.dtype), dk.astype(k3.dtype), dv.astype(v3.dtype),
            dkvm, f0(qseg), f0(kseg), f0(seed))


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    causal: bool = False,
                    scale: Optional[float] = None,
                    kv_mask: Optional[jax.Array] = None,
                    dropout_rate: float = 0.0,
                    dropout_seed: Optional[jax.Array] = None,
                    segment_ids: Optional[jax.Array] = None,
                    window: Optional[int] = None) -> jax.Array:
    """softmax(q k^T * scale [+ causal mask]) v without materializing the
    score matrix in HBM.  q, k, v: (B, H, T, D) self-attention operands
    (equal sequence lengths).  K/V are streamed through VMEM in blocks,
    so the sequence length is bounded by HBM, not VMEM.

    ``kv_mask``: optional (B, T) bool key-validity (True = attend) — the
    key-padding mask of BERT-style batches, streamed alongside the K/V
    blocks as sublane-broadcast (B, 8, T) tiles (the upstream
    jax.experimental flash kernel's SegmentIds layout).  Composes with
    ``causal``.  Queries whose keys are ALL masked produce zero output
    rows (the dense softmax would give a uniform average instead).

    ``dropout_rate`` + ``dropout_seed`` (int32 scalar, e.g. drawn per
    step from a PRNGKey): attention-probability dropout computed INSIDE
    the kernel from a counter-based hash of the absolute positions —
    no (T, T) mask materializes, and the backward passes regenerate the
    identical mask from the same counters (FlashAttention's dropout
    placement: the softmax normalizer is undropped, the value
    accumulation is dropped and rescaled by 1/keep).

    ``segment_ids``: optional (B, T) int32 for packed sequences —
    position pairs attend only within equal ids (q-ids stream as
    lane-broadcast tiles, k-ids as sublane tiles).  Composes with
    ``causal``/``kv_mask``/dropout.  Rows whose segment has no other
    member still see themselves (the diagonal id always matches).

    ``window``: a static sliding window on top of ``causal=True`` — key j
    is visible to query i iff ``i - window < j <= i``.  Each of the three
    kernels then visits only the block pairs the band touches; with
    ``window=None`` they are the programs they are without it."""
    if q.ndim != 4:
        raise ValueError(f"expected (B, H, T, D), got {q.shape}")
    if q.shape != k.shape or k.shape != v.shape:
        raise ValueError("flash_attention requires matching q/k/v shapes")
    dropout_rate = float(dropout_rate)
    if not 0.0 <= dropout_rate < 1.0:
        raise ValueError(f"dropout_rate must be in [0, 1), got "
                         f"{dropout_rate}")
    if dropout_rate and dropout_seed is None:
        raise ValueError("dropout_rate > 0 requires dropout_seed")
    if window is not None:
        window = int(window)
        if not causal or window < 1:
            raise ValueError("window needs causal=True and window >= 1, "
                             f"got causal={causal}, window={window}")
    B, H, T, D = q.shape
    if scale is None:
        scale = 1.0 / math.sqrt(D)
    blk = _block_for(T)
    Tp = -(-T // blk) * blk
    kvm = None
    if kv_mask is not None:
        if kv_mask.shape != (B, T):
            raise ValueError(f"kv_mask must be (B, T) = {(B, T)}, got "
                             f"{kv_mask.shape}")
        m = jnp.pad(kv_mask.astype(jnp.float32), ((0, 0), (0, Tp - T)))
        kvm = jax.lax.broadcast_in_dim(m, (B, 8, Tp), (0, 2))
    seed = None
    if dropout_rate:
        s = jnp.asarray(dropout_seed, jnp.int32).reshape(-1)
        if s.size == 1:
            # single-word seeds get a derived second word (no extra
            # entropy, but the kernel contract is two words)
            s = jnp.stack([s[0], s[0] ^ np.int32(0x5555AAAA)])
        elif s.size != 2:
            raise ValueError("dropout_seed must be 1 or 2 int32 words, "
                             f"got {s.size}")
        seed = s.reshape(1, 2)
    qseg = kseg = None
    if segment_ids is not None:
        if segment_ids.shape != (B, T):
            raise ValueError(f"segment_ids must be (B, T) = {(B, T)}, "
                             f"got {segment_ids.shape}")
        # padded positions get id -1 on the q side and -2 on the k side,
        # so padding never matches anything (incl. other padding)
        ids = segment_ids.astype(jnp.int32)
        idq = jnp.pad(ids, ((0, 0), (0, Tp - T)), constant_values=-1)
        idk = jnp.pad(ids, ((0, 0), (0, Tp - T)), constant_values=-2)
        qseg = jax.lax.broadcast_in_dim(idq, (B, Tp, LANES), (0, 1))
        kseg = jax.lax.broadcast_in_dim(idk, (B, 8, Tp), (0, 2))
    fold = lambda x: x.reshape(B * H, T, D)
    out = _flash(fold(q), fold(k), fold(v), kvm, qseg, kseg, seed,
                 float(scale), bool(causal), H, dropout_rate, window)
    return out.reshape(B, H, T, D)
