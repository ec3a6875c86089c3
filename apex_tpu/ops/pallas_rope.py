"""Pallas rotary position embedding on token-major operands.

``x`` is a projection's own output, ``(B, T, heads * D)``; a head is ``D``
lanes of a token's row (``D % 128 == 0``), and its leading ``rd`` dims
rotate in rotate-half pairing, ``d <-> d + rd / 2``.  One pass reads x in
its dtype, rotates in fp32 and writes x's dtype; the gradient is the same
pass with the sines negated (the rotation transposed), so neither
direction holds an fp32 array of x's size.

Why a kernel: the rotation pairs lanes half a rotated width apart, and
cos/sin are per token, shared by every head of its row.  XLA on a TPU
writes that as a convert of x to fp32, a slice-and-negate into two fp32
halves and a combine, with cos and sin broadcast over the heads into
arrays of x's size (compiled for a v5e in every formulation tried:
PERF.md section 6, PR 29).  Here a head's (rows, D) tile is rolled along
its lanes on the XLU and meets three (rows, D) tables that every head of
the block shares.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .pallas_common import LANES, interpret

_ROWS = 512         # tokens a block
_HEADS = 8          # heads a block: 512 x 8 x 128 x 2 B = 1 MiB in, 1 out


def rows_per_block(T: int) -> int:
    """The largest block of tokens in 512 .. 8 that divides T, 0 if none
    does (the caller then takes the jnp form)."""
    return next((r for r in (_ROWS, 256, 128, 64, 32, 16, 8) if T % r == 0),
                0)


def _kernel(x_ref, c_ref, sa_ref, sb_ref, o_ref, *, D, half, heads):
    c, sa, sb = c_ref[...], sa_ref[...], sb_ref[...]
    for h in range(heads):
        lanes = pl.ds(h * D, D)
        x = x_ref[0, :, lanes].astype(jnp.float32)
        # lane d takes -x[d + half] * sin below half, x[d - half] * sin
        # from there to the rotated width: sa and sb carry sign and zeros
        out = (x * c + pltpu.roll(x, D - half, 1) * sa
               + pltpu.roll(x, half, 1) * sb)
        o_ref[0, :, lanes] = out.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("D", "half"))
def _launch(x, c, sa, sb, D, half):
    B, T, HD = x.shape
    rows = rows_per_block(T)
    heads = next(n for n in (_HEADS, 4, 2, 1) if (HD // D) % n == 0)
    table = pl.BlockSpec((rows, D), lambda t, b, h: (t, 0))
    block = pl.BlockSpec((1, rows, heads * D), lambda t, b, h: (b, t, h))
    return pl.pallas_call(
        functools.partial(_kernel, D=D, half=half, heads=heads),
        # a token block's tables stay resident over its batch entries and
        # head groups
        grid=(T // rows, B, HD // (heads * D)),
        in_specs=[block, table, table, table],
        out_specs=block,
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel")),
        interpret=interpret(),
        name="rope",
    )(x, c, sa, sb)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _rope(x, c, sa, sb, D, half):
    return _launch(x, c, sa, sb, D, half)


def _rope_fwd(x, c, sa, sb, D, half):
    return _launch(x, c, sa, sb, D, half), (c, sa, sb)


def _rope_bwd(D, half, tables, g):
    c, sa, sb = tables
    # the rotation transposed: the same pairs, the sines negated
    return (_launch(g, c, -sa, -sb, D, half),
            jnp.zeros_like(c), jnp.zeros_like(sa), jnp.zeros_like(sb))


_rope.defvjp(_rope_fwd, _rope_bwd)


def rope_token_major(x: jax.Array, cos: jax.Array, sin: jax.Array,
                     D: int) -> jax.Array:
    """x: (B, T, heads * D) with ``D % 128 == 0`` and T a multiple of 8.
    cos, sin: (T, rd) fp32, ``rd <= D`` even, each angle in both halves of
    the rotated width (``concatenate([ang, ang])``), any scale folded in.
    -> x with the leading ``rd`` dims of every head rotated, the rest as
    they were.  Differentiable in x."""
    T, rd = cos.shape
    if D % LANES or not rows_per_block(x.shape[1]) or x.shape[2] % D:
        raise ValueError("rope_token_major needs heads of whole lane tiles "
                         f"and T a multiple of 8, got {x.shape}, D = {D}")
    half = rd // 2
    low = jnp.arange(rd) < half

    def table(t, rest):         # (T, rd) -> (T, D): the lanes that pass
        if rd == D:
            return t
        return jnp.concatenate(
            [t, jnp.full((T, D - rd), rest, jnp.float32)], axis=-1)

    return _rope(x, table(cos, 1.0), table(jnp.where(low, -sin, 0.0), 0.0),
                 table(jnp.where(low, 0.0, sin), 0.0), D, half)
