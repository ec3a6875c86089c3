"""Pallas fused Adam kernel.

Equivalent of csrc/fused_adam_cuda_kernel.cu:15-55: one pass over the flat
(p, m, v, g) buffers computing the scaled-grad Adam update, with the
optional half-precision parameter write-out (p_copy, :94-115) fused into
the same pass.  Bias correction is folded into ``step_size`` host-side
(:83-91), matching the reference.

Inputs are fp32 flat buffers viewed as (rows, 128), the gradient in any
float dtype (widened and unscaled in registers); p/m/v are updated via
``input_output_aliases``.  A gradient shorter than the buffers is the
gradient of the elements from ``start``, both on block boundaries (a
segment of amp's flat layout): the grid walks that segment's row blocks
only, and the aliases leave every other block of p/m/v as it was.  That
is in place on the CALLER's buffers only when their length is a whole
number of blocks (``pallas_common.aligned_len``;
amp's flat state is kept at such a length): ``to_2d`` is then a reshape and
the alias reaches the optimizer state itself.  At any other length the
kernel updates padded copies in place and ``from_2d`` slices the results
back out — four pads and three or four slices of the whole buffer a step.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .pallas_common import (LANES, from_2d, interpret, pick_block_rows,
                            to_2d)


def _adam_kernel(scal_ref, p_ref, m_ref, v_ref, g_ref,
                 p_out, m_out, v_out, *half_out, beta1, beta2, eps,
                 eps_inside_sqrt, weight_decay, half_dtype):
    step_size = scal_ref[0, 0]
    inv_scale = scal_ref[0, 1]
    g = g_ref[:].astype(jnp.float32) * inv_scale
    p = p_ref[:]
    m = beta1 * m_ref[:] + (1.0 - beta1) * g
    v = beta2 * v_ref[:] + (1.0 - beta2) * g * g
    if eps_inside_sqrt:
        denom = jnp.sqrt(v + eps)
    else:
        denom = jnp.sqrt(v) + eps
    update = m / denom + weight_decay * p
    new_p = p - step_size * update
    p_out[:] = new_p
    m_out[:] = m
    v_out[:] = v
    if half_dtype is not None:
        # the fp16/bf16 parameter write-out fused into the same pass
        # (the reference kernel's p_copy, fused_adam_cuda_kernel.cu:94-115)
        half_out[0][:] = new_p.astype(half_dtype)


@functools.partial(
    jax.jit, static_argnames=("beta1", "beta2", "eps", "eps_inside_sqrt",
                              "weight_decay", "half_dtype", "start"))
def _adam_flat(p, m, v, g, step_size, combined_scale, *, beta1, beta2, eps,
               eps_inside_sqrt, weight_decay, half_dtype, start=0):
    # shard-aware block sizing: a ZeRO master shard (1/ici or 1/world
    # of the model) must stay ONE kernel launch without padding up to a
    # full 512-row block — pick_block_rows shrinks the block (multiple
    # of the fp32 min-tile sublanes) for sub-block buffers
    block_rows = pick_block_rows(g.shape[0])
    block = block_rows * LANES
    if g.shape[0] != p.shape[0] and (start % block or g.shape[0] % block):
        # a padded segment would walk into its neighbour's elements
        raise ValueError(
            f"a gradient segment of {g.shape[0]} elements from {start} of "
            f"{p.shape[0]} must lie on blocks of {block} elements")
    p2, n = to_2d(p, block_rows)
    m2, _ = to_2d(m, block_rows)
    v2, _ = to_2d(v, block_rows)
    g2, ng = to_2d(g, block_rows)
    rows = p2.shape[0]
    seg_rows = g2.shape[0]
    grid = seg_rows // block_rows
    first = start // block

    def blk(first=0):
        return pl.BlockSpec((block_rows, LANES), lambda i: (i + first, 0),
                            memory_space=pltpu.VMEM)
    scal = jnp.stack([jnp.asarray(step_size, jnp.float32),
                      1.0 / jnp.asarray(combined_scale, jnp.float32)]
                     ).reshape(1, 2)
    out_specs = [blk(first), blk(first), blk(first)]
    out_shape = [jax.ShapeDtypeStruct((rows, LANES), jnp.float32)] * 3
    if half_dtype is not None:
        out_specs.append(blk())
        out_shape.append(jax.ShapeDtypeStruct((seg_rows, LANES), half_dtype))
    outs = pl.pallas_call(
        functools.partial(_adam_kernel, beta1=beta1, beta2=beta2, eps=eps,
                          eps_inside_sqrt=eps_inside_sqrt,
                          weight_decay=weight_decay, half_dtype=half_dtype),
        grid=(grid,),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                  blk(first), blk(first), blk(first), blk()],
        out_specs=out_specs,
        out_shape=out_shape,
        input_output_aliases={1: 0, 2: 1, 3: 2},
        interpret=interpret(),
    )(scal, p2, m2, v2, g2)
    new_p2, new_m2, new_v2 = outs[:3]
    half = from_2d(outs[3], ng) if half_dtype is not None else None
    return from_2d(new_p2, n), from_2d(new_m2, n), from_2d(new_v2, n), half


def fused_adam(p, m, v, g, step_size, combined_scale, beta1, beta2, eps,
               eps_inside_sqrt, weight_decay, half_dtype=None, start=0
               ) -> Tuple[jax.Array, jax.Array, jax.Array,
                          Optional[jax.Array]]:
    """Flat-buffer fused Adam step; signature mirrors the jnp reference
    path in apex_tpu.optimizers.fused_adam._adam_kernel."""
    return _adam_flat(p, m, v, g, step_size, combined_scale,
                      beta1=float(beta1), beta2=float(beta2), eps=float(eps),
                      eps_inside_sqrt=bool(eps_inside_sqrt),
                      weight_decay=float(weight_decay),
                      half_dtype=half_dtype, start=int(start))
