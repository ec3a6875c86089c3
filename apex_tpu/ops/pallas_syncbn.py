"""Pallas fused BatchNorm normalize-apply kernels (fwd + bwd).

Equivalent of the reference's syncbn elementwise kernels: forward apply
``batchnorm_forward`` (csrc/welford.cu:298-318) and the backward pair
``reduce_bn`` (per-channel sum_dy / sum_dy_xmu + dgamma/dbeta,
welford.cu:325-383) and ``batchnorm_backward`` (dx apply, :387-410).

Division of labor (SURVEY.md §2.2 TPU sketch): the *cross-device* Welford/
Chan stat merge lives in SyncBatchNorm._sync_stats as a psum — jax
autodiff of that psum produces the allreduced mean_dy/mean_dy_xmu pattern
of the reference's backward (optimized_sync_batchnorm_kernel.py:92-97) with
no custom collective code here.  This kernel's custom_vjp therefore only
has to treat (x, mean, var, w, b) as independent inputs and return local
gradients; the chain rule through the stats supplies the rest.

Layout: NCHW viewed as (N*C, H*W) rows — each row one (sample, channel)
plane, per-row scalars (mean, inv_std, w, b) carried as (rows, 1) column
operands, lanes padded to 128 with masking.
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

_VMEM_BUDGET = 8 * 1024 * 1024


def _block_rows(C: int, n_row_operands: int) -> int:
    """Rows per grid block, budgeted across every row-sized operand the
    kernel keeps resident (x2 for the grid pipeline's double buffering) so
    ImageNet-scale planes (hw ~ 112*112) still fit VMEM."""
    br = _VMEM_BUDGET // (C * 4 * n_row_operands * 2)
    return max(8, min(256, (br // 8) * 8))


def fits_vmem(hw: int) -> bool:
    """True if the minimum 8-row block of the 3-operand backward fits the
    budget; callers fall back to the jnp path for larger planes."""
    Cpad = -(-hw // LANES) * LANES
    return Cpad * 4 * 8 * 3 * 2 <= _VMEM_BUDGET


def _pad2(x, R, C):
    r, c = x.shape
    if r == R and c == C:
        return x
    return jnp.pad(x, ((0, R - r), (0, C - c)))


def _fwd_kernel(x_ref, mean_ref, inv_ref, w_ref, b_ref, y_ref):
    x = x_ref[:].astype(jnp.float32)
    y = (x - mean_ref[:]) * inv_ref[:] * w_ref[:] + b_ref[:]
    y_ref[:] = y.astype(y_ref.dtype)


def _bwd_kernel(dy_ref, x_ref, mean_ref, inv_ref, w_ref,
                dx_ref, sdy_ref, sdyx_ref, *, hw):
    dy = dy_ref[:].astype(jnp.float32)
    x = x_ref[:].astype(jnp.float32)
    mask = lax.broadcasted_iota(jnp.int32, x.shape, 1) < hw
    dy = jnp.where(mask, dy, 0.0)
    xhat = jnp.where(mask, (x - mean_ref[:]) * inv_ref[:], 0.0)
    dx_ref[:] = (dy * w_ref[:] * inv_ref[:]).astype(dx_ref.dtype)
    sdy_ref[:] = jnp.sum(dy, axis=1, keepdims=True)
    sdyx_ref[:] = jnp.sum(dy * xhat, axis=1, keepdims=True)


def _rowify(v, N):
    """(C,) channel vector -> (N*C, 1) per-row column."""
    return jnp.tile(v.astype(jnp.float32), N).reshape(-1, 1)


@functools.partial(jax.jit, static_argnames=("eps",))
def _fwd(x4, mean, var, w, b, *, eps):
    N, Cch, H, W = x4.shape
    hw = H * W
    rows = N * Cch
    Cpad = -(-hw // LANES) * LANES
    BR = _block_rows(Cpad, 2)  # resident row operands: x, y
    R = -(-rows // BR) * BR
    xp = _pad2(x4.reshape(rows, hw), R, Cpad)
    inv = lax.rsqrt(var.astype(jnp.float32) + eps)
    cols = [_pad2(_rowify(v, N), R, 1) for v in (mean, inv, w, b)]
    row_blk = pl.BlockSpec((BR, Cpad), lambda i: (i, 0),
                           memory_space=pltpu.VMEM)
    col_blk = pl.BlockSpec((BR, 1), lambda i: (i, 0),
                           memory_space=pltpu.VMEM)
    y = pl.pallas_call(
        _fwd_kernel,
        grid=(R // BR,),
        in_specs=[row_blk, col_blk, col_blk, col_blk, col_blk],
        out_specs=row_blk,
        out_shape=jax.ShapeDtypeStruct((R, Cpad), x4.dtype),
        interpret=interpret(),
        name="bn_apply_fwd",
    )(xp, *cols)
    return y[:rows, :hw].reshape(N, Cch, H, W)


@functools.partial(jax.jit, static_argnames=("eps",))
def _bwd(x4, mean, var, w, dy4, *, eps):
    N, Cch, H, W = x4.shape
    hw = H * W
    rows = N * Cch
    Cpad = -(-hw // LANES) * LANES
    BR = _block_rows(Cpad, 3)  # resident row operands: dy, x, dx
    R = -(-rows // BR) * BR
    xp = _pad2(x4.reshape(rows, hw), R, Cpad)
    dyp = _pad2(dy4.reshape(rows, hw), R, Cpad)
    inv = lax.rsqrt(var.astype(jnp.float32) + eps)
    cols = [_pad2(_rowify(v, N), R, 1) for v in (mean, inv, w)]
    row_blk = pl.BlockSpec((BR, Cpad), lambda i: (i, 0),
                           memory_space=pltpu.VMEM)
    col_blk = pl.BlockSpec((BR, 1), lambda i: (i, 0),
                           memory_space=pltpu.VMEM)
    dx, sdy, sdyx = pl.pallas_call(
        functools.partial(_bwd_kernel, hw=hw),
        grid=(R // BR,),
        in_specs=[row_blk, row_blk, col_blk, col_blk, col_blk],
        out_specs=[row_blk, col_blk, col_blk],
        out_shape=[jax.ShapeDtypeStruct((R, Cpad), dy4.dtype),
                   jax.ShapeDtypeStruct((R, 1), jnp.float32),
                   jax.ShapeDtypeStruct((R, 1), jnp.float32)],
        interpret=interpret(),
        name="bn_apply_bwd",
    )(dyp, xp, *cols)
    dx = dx[:rows, :hw].reshape(N, Cch, H, W)
    # per-channel epilogue: (N*C, 1) partials -> (C,) (the reference's
    # stage-2 reduce, welford.cu:345-366, left to XLA)
    sum_dy = jnp.sum(sdy[:rows, 0].reshape(N, Cch), axis=0)
    sum_dy_xhat = jnp.sum(sdyx[:rows, 0].reshape(N, Cch), axis=0)
    return dx, sum_dy, sum_dy_xhat, inv


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def batch_norm_apply_fused(x4, mean, var, w, b, eps: float):
    """Fused y = (x - mean_c) * rsqrt(var_c + eps) * w_c + b_c on NCHW."""
    return _fwd(x4, mean, var, w, b, eps=eps)


def _vjp_fwd(x4, mean, var, w, b, eps):
    return _fwd(x4, mean, var, w, b, eps=eps), (x4, mean, var, w)


def _vjp_bwd(eps, res, dy4):
    x4, mean, var, w = res
    dx, sum_dy, sum_dy_xhat, inv = _bwd(x4, mean, var, w, dy4, eps=eps)
    w32 = w.astype(jnp.float32)
    dmean = (-w32 * inv * sum_dy).astype(mean.dtype)
    dvar = (-0.5 * w32 * inv * inv * sum_dy_xhat).astype(var.dtype)
    dw = sum_dy_xhat.astype(w.dtype)
    db = sum_dy.astype(w.dtype)
    return dx.astype(x4.dtype), dmean, dvar, dw, db


batch_norm_apply_fused.defvjp(_vjp_fwd, _vjp_bwd)
