"""Pallas fused LayerNorm forward/backward row-reduction kernels.

Equivalent of csrc/layer_norm_cuda_kernel.cu: forward is a per-row Welford
pass producing (out, fp32 mean, fp32 invvar) (:51-245, host :640-668);
backward fuses the dx computation (:522-638) and produces per-block partial
gamma/beta gradients (:403-470) that a jnp epilogue reduces (:471-521) —
the same two-stage structure, with stage 2 left to XLA.

The (n1, n2) row view is padded to (rows multiple of block, cols multiple
of 128); column masking keeps the statistics exact for arbitrary n2.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .pallas_common import LANES, interpret

_VMEM_BUDGET = 4 * 1024 * 1024  # per-operand block budget (bytes)


def _block_rows(C: int) -> int:
    br = _VMEM_BUDGET // (C * 4)
    br = max(8, min(256, br))
    return (br // 8) * 8


def _pad2(x, R, C):
    r, c = x.shape
    if r == R and c == C:
        return x
    return jnp.pad(x, ((0, R - r), (0, C - c)))


def _fwd_kernel(x_ref, w_ref, b_ref, y_ref, mean_ref, inv_ref, *, n2, eps):
    x = x_ref[:].astype(jnp.float32)
    mask = lax.broadcasted_iota(jnp.int32, x.shape, 1) < n2
    xm = jnp.where(mask, x, 0.0)
    mean = jnp.sum(xm, axis=1, keepdims=True) / n2
    # shifted two-pass variance: the block is already resident in VMEM, so
    # a second read costs nothing and avoids the E[x^2]-mean^2 catastrophic
    # cancellation the reference's single-pass Welford exists to prevent
    # (layer_norm_cuda_kernel.cu:11-50)
    d = jnp.where(mask, x - mean, 0.0)
    var = jnp.sum(d * d, axis=1, keepdims=True) / n2
    inv = lax.rsqrt(var + eps)
    y = (x - mean) * inv * w_ref[:].astype(jnp.float32) + \
        b_ref[:].astype(jnp.float32)
    y_ref[:] = y.astype(y_ref.dtype)
    mean_ref[:] = mean
    inv_ref[:] = inv


@functools.partial(jax.jit, static_argnames=("n2", "eps", "out_dtype"))
def _fwd(x2, w, b, *, n2, eps, out_dtype):
    n1 = x2.shape[0]
    C = -(-n2 // LANES) * LANES
    BR = _block_rows(C)
    R = -(-max(n1, 1) // BR) * BR
    xp = _pad2(x2, R, C)
    wp = jnp.pad(w.astype(jnp.float32), (0, C - n2)).reshape(1, C)
    bp = jnp.pad(b.astype(jnp.float32), (0, C - n2)).reshape(1, C)
    grid = R // BR
    row_blk = pl.BlockSpec((BR, C), lambda i: (i, 0),
                           memory_space=pltpu.VMEM)
    vec_blk = pl.BlockSpec((1, C), lambda i: (0, 0),
                           memory_space=pltpu.VMEM)
    col_blk = pl.BlockSpec((BR, 1), lambda i: (i, 0),
                           memory_space=pltpu.VMEM)
    y, mean, inv = pl.pallas_call(
        functools.partial(_fwd_kernel, n2=n2, eps=eps),
        grid=(grid,),
        in_specs=[row_blk, vec_blk, vec_blk],
        out_specs=[row_blk, col_blk, col_blk],
        out_shape=[jax.ShapeDtypeStruct((R, C), out_dtype),
                   jax.ShapeDtypeStruct((R, 1), jnp.float32),
                   jax.ShapeDtypeStruct((R, 1), jnp.float32)],
        interpret=interpret(),
        name="layer_norm_fwd",
    )(xp, wp, bp)
    return y[:n1, :n2], mean[:n1, 0], inv[:n1, 0]


def forward(x2: jax.Array, weight: Optional[jax.Array],
            bias: Optional[jax.Array], eps: float):
    n1, n2 = x2.shape
    w = weight if weight is not None else jnp.ones((n2,), jnp.float32)
    b = bias if bias is not None else jnp.zeros((n2,), jnp.float32)
    y, mean, inv = _fwd(x2, w, b, n2=n2, eps=float(eps),
                        out_dtype=x2.dtype)
    return y, mean, inv


def _bwd_kernel(dy_ref, x_ref, w_ref, mean_ref, inv_ref,
                dx_ref, dw_ref, db_ref, *, n2):
    # dw/db are (1, C) accumulators revisited by every (sequential) grid
    # step — the fused form of the reference's two-stage partial-buffer
    # reduction (layer_norm_cuda_kernel.cu:403-521)
    @pl.when(pl.program_id(0) == 0)
    def _():
        dw_ref[:] = jnp.zeros_like(dw_ref)
        db_ref[:] = jnp.zeros_like(db_ref)
    dy = dy_ref[:].astype(jnp.float32)
    x = x_ref[:].astype(jnp.float32)
    mask = lax.broadcasted_iota(jnp.int32, x.shape, 1) < n2
    mean = mean_ref[:]
    inv = inv_ref[:]
    xhat = (x - mean) * inv
    dy = jnp.where(mask, dy, 0.0)
    dy_g = dy * w_ref[:].astype(jnp.float32)
    c1 = jnp.sum(dy_g, axis=1, keepdims=True) / n2
    c2 = jnp.sum(dy_g * xhat, axis=1, keepdims=True) / n2
    dx = inv * (dy_g - c1 - xhat * c2)
    dx_ref[:] = dx.astype(dx_ref.dtype)
    dw_ref[:] += jnp.sum(dy * xhat, axis=0, keepdims=True)
    db_ref[:] += jnp.sum(dy, axis=0, keepdims=True)


@functools.partial(jax.jit, static_argnames=("n2", "in_dtype"))
def _bwd(dy2, x2, w, mean, inv, *, n2, in_dtype):
    n1 = x2.shape[0]
    C = -(-n2 // LANES) * LANES
    BR = _block_rows(C)
    R = -(-max(n1, 1) // BR) * BR
    xp = _pad2(x2, R, C)
    dyp = _pad2(dy2, R, C)
    wp = jnp.pad(w.astype(jnp.float32), (0, C - n2)).reshape(1, C)
    meanp = jnp.pad(mean.reshape(-1, 1), ((0, R - n1), (0, 0)))
    invp = jnp.pad(inv.reshape(-1, 1), ((0, R - n1), (0, 0)))
    grid = R // BR
    row_blk = pl.BlockSpec((BR, C), lambda i: (i, 0),
                           memory_space=pltpu.VMEM)
    vec_blk = pl.BlockSpec((1, C), lambda i: (0, 0),
                           memory_space=pltpu.VMEM)
    col_blk = pl.BlockSpec((BR, 1), lambda i: (i, 0),
                           memory_space=pltpu.VMEM)
    acc_blk = pl.BlockSpec((1, C), lambda i: (0, 0),
                           memory_space=pltpu.VMEM)
    dx, dwa, dba = pl.pallas_call(
        functools.partial(_bwd_kernel, n2=n2),
        grid=(grid,),
        in_specs=[row_blk, row_blk, vec_blk, col_blk, col_blk],
        out_specs=[row_blk, acc_blk, acc_blk],
        out_shape=[jax.ShapeDtypeStruct((R, C), in_dtype),
                   jax.ShapeDtypeStruct((1, C), jnp.float32),
                   jax.ShapeDtypeStruct((1, C), jnp.float32)],
        # dw/db are revisited by every grid step: the row axis must run
        # sequentially on one core
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret(),
        name="layer_norm_bwd",
    )(dyp, xp, wp, meanp, invp)
    return dx[:n1, :n2], dwa[0, :n2], dba[0, :n2]


def backward(dy: jax.Array, x2: jax.Array, weight: Optional[jax.Array],
             bias: Optional[jax.Array], mean: jax.Array, inv: jax.Array):
    n1, n2 = x2.shape
    w = weight if weight is not None else jnp.ones((n2,), jnp.float32)
    dx, dw, db = _bwd(dy, x2, w, mean, inv, n2=n2, in_dtype=x2.dtype)
    dw_out = dw.astype(weight.dtype) if weight is not None else None
    db_out = db.astype(bias.dtype) if bias is not None else None
    return dx, dw_out, db_out
