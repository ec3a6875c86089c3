"""The short depthwise causal convolution along the sequence, with the
elementwise work around it, as one Pallas pass forward and one backward.

Two forms over one body (``form`` is a static argument each call site
passes), ``x`` the array a projection wrote, ``(B, T, W)``, read by column
blocks where the parts stand in it and never sliced:

``gated``   ``x[..., offset:offset + 3 d] = [B | C | z]``,
            ``out = C * sum_k w[k] * (B * z)[t - (L - 1) + k]``
            (``transformer/short_conv.gated_short_conv``, no bias);
``silu``    ``x[..., offset:offset + c]``,
            ``out = silu(sum_k w[k] * x[t - (L - 1) + k] + bias)``
            (``transformer/mamba2.causal_conv_silu``).

Float32 between a read and a write in ``x``'s dtype; what is convolved is
zero before a sequence's first token.  XLA writes the shift as a ``pad`` and
``L`` slices that start one to three rows into an (8, 128) tile, which it
does not fuse: float32 arrays of the convolved operand's size go to HBM, one
forward and three backward (PERF.md section 6, PR 49).

**A grid step** owns ``rows`` tokens x ``lanes`` channels of one sequence.
The rows a shift reaches past the block come as a second, 16-row block of the
same array through a clamped index map (the rows before for the causal
shift, the rows after for the transposed one), zeroed at a sequence's ends.
A step works through its block 64 rows at a time (a loop: the kernel's code
does not grow with the block), float32 in registers between the read and the
write, and the shift itself is a sublane roll of a piece with the 8 rows in
front of it (the piece before's last, carried) or behind it.
Nothing is carried from step to step, so no grid axis is sequential for the
halo's sake: the backward needs the convolved operand's rows BEFORE a block
(it recomputes the convolution for the output gate's, or SiLU's, gradient)
and the incoming cotangent's rows AFTER it (the transposed convolution),
and a walk in either direction could carry only one of the two.

**Backward**: recomputes the forward's float32 values from the operand in
VMEM, writes the operand's cotangent once in its dtype (the ``gated`` form's
three parts are the three column groups of one ``(B, T, 3 d)`` array: the
grid's last axis walks the parts, the first step of a block computes all
three and keeps two in VMEM, and the operand's blocks are fetched once a
block, the next block's while that first step computes), and each step's
sums over its rows for the taps' (and the bias's) gradient as eight float32
rows a block, added up outside: ``(B, T / rows, 8, c)``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .pallas_common import LANES, interpret

__all__ = ["takes", "short_conv", "FORMS"]

FORMS = ("gated", "silu")
_F32 = jnp.float32
# rows of a halo block: a packed bf16 tile (a float32 tile is 8; a shift
# reaches L - 1 <= 7 rows, the last 8 of the block before or the first 8 of
# the block after)
_HALO = 16
_TILE = 8
# a step's block (PERF.md section 6, PR 49, has the sizes tried on the chip)
_ROWS = 512
_LANES = 512
# rows a step works through at a time (see ``_pieces``)
_PIECE = 64
# the sums a backward step writes: the taps' rows, then the bias's
_SUMS = 8


def _parts(form: str) -> int:
    return 3 if form == "gated" else 1


def _blocks(T: int, c: int, offset: int):
    """``(rows, lanes)`` of a step's block, or None where the token or the
    channel count is not whole blocks."""
    rows = _ROWS if T % _ROWS == 0 else T if T < _ROWS else 0
    lanes = next((n for n in (_LANES, 256, LANES)
                  if c % n == 0 and offset % n == 0), 0)
    if not rows or rows % _HALO or not lanes:
        return None
    return rows, lanes


def takes(x, taps, bias=None, *, form: str, offset: int = 0) -> bool:
    """Whether the kernels take these operands: a float array ``(B, T, W)``
    whose read columns and the offset are whole lane tiles, whole blocks of
    tokens, taps ``(L, c)`` with ``L`` (and the bias's row) inside eight
    rows, the bias with the ``silu`` form only."""
    if form not in FORMS or x.ndim != 3 or taps.ndim != 2:
        return False
    L, c = taps.shape
    if (bias is None) != (form == "gated") or (
            bias is not None and bias.shape != (c,)):
        return False
    return (x.dtype in (jnp.bfloat16, jnp.float32)
            and 1 <= L <= _SUMS - (bias is not None)
            and offset + _parts(form) * c <= x.shape[2]
            and _blocks(x.shape[1], c, offset) is not None)


def _f32(a):
    return a.astype(_F32)


def _before(ref, t):
    """The 8 rows in front of a block, float32, zero at a sequence's start."""
    rows = _f32(ref[0])[_HALO - _TILE:]
    return jnp.where(t > 0, rows, jnp.zeros_like(rows))


def _after(ref, t, nT):
    """The 8 rows behind a block, float32, zero at a sequence's end."""
    rows = _f32(ref[0])[:_TILE]
    return jnp.where(t < nT - 1, rows, jnp.zeros_like(rows))


def _rows_from(ext, start: int, n: int, back: int):
    """``out[i] = ext[start + i - back]`` for ``i < n``: a sublane roll of
    the rows with their halo, then whole tiles of it."""
    if back:
        ext = pltpu.roll(ext, back % ext.shape[0], 0)
    return ext[start:start + n]


def _conv(front, rows, w_ref, L: int):
    """``(sum_k w[k] * ext[8 + i - (L - 1 - k)], the L shifted views)`` with
    ``ext`` the 8 rows ``front`` and then ``rows``: the causal taps."""
    ext = jnp.concatenate([front, rows], axis=0)
    views = [_rows_from(ext, _TILE, rows.shape[0], L - 1 - k)
             for k in range(L)]
    return sum(w_ref[k:k + 1, :] * v for k, v in enumerate(views)), views


def _conv_t(rows, behind, w_ref, L: int):
    """``sum_k w[k] * ext[i + (L - 1 - k)]`` with ``ext`` ``rows`` and then
    the 8 rows ``behind``: the transposed taps."""
    ext = jnp.concatenate([rows, behind], axis=0)
    return sum(w_ref[k:k + 1, :] * _rows_from(ext, 0, rows.shape[0],
                                              -(L - 1 - k))
               for k in range(L))


def _silu_grad(pre):
    s = jax.nn.sigmoid(pre)
    return s * (1.0 + pre * (1.0 - s))


def _pieces(n: int):
    """``(how many, rows each)`` of the pieces a step works through: float32
    values of ``_PIECE`` rows live in registers from the read to the write,
    where a whole block's would each go through VMEM."""
    size = _PIECE if n % _PIECE == 0 else _HALO
    return n // size, size


def _each_piece(n: int, body, carry):
    """``carry = body(i, rows of piece i, carry)`` over a step's pieces, in
    order; a loop, so that the kernel's code does not grow with the block."""
    count, size = _pieces(n)

    def step(i, carry):
        return body(i, pl.ds(pl.multiple_of(i * size, size), size), carry)

    return lax.fori_loop(0, count, step, carry)


def _next_rows(refs, i, n: int, at_end):
    """The first 8 rows after piece ``i`` of each of ``refs`` multiplied
    together, float32: the next piece's, or ``at_end`` behind the last."""
    count, size = _pieces(n)
    start = pl.multiple_of(jnp.minimum((i + 1) * size, n - _HALO), _HALO)
    rows = None
    for ref in refs:
        r = _f32(ref[0, pl.ds(start, _HALO)])[:_TILE]
        rows = r if rows is None else rows * r
    return jnp.where(i == count - 1, at_end, rows)


def _conv_fwd(*refs, form: str, L: int):
    t = pl.program_id(2)
    if form == "gated":
        b_ref, c_ref, z_ref, bb_ref, zb_ref, w_ref, o_ref = refs
        front = _before(bb_ref, t) * _before(zb_ref, t)
    else:
        x_ref, xb_ref, w_ref, bias_ref, o_ref = refs
        front = _before(xb_ref, t)

    def piece(i, at, front):
        if form == "gated":
            rows = _f32(b_ref[0, at]) * _f32(z_ref[0, at])
            out = _f32(c_ref[0, at]) * _conv(front, rows, w_ref, L)[0]
        else:
            rows = _f32(x_ref[0, at])
            pre = _conv(front, rows, w_ref, L)[0] + bias_ref[...]
            out = pre * jax.nn.sigmoid(pre)
        o_ref[0, at] = out.astype(o_ref.dtype)
        return rows[-_TILE:]

    _each_piece(o_ref.shape[1], piece, front)


def _row_sums(h, views, bias: bool):
    """A piece's part of the taps' (and the bias's) gradient: ``(8, lanes)``,
    a tap a row, then the bias's, then zeros."""
    rows = [jnp.sum(h * v, axis=0, keepdims=True) for v in views]
    if bias:
        rows.append(jnp.sum(h, axis=0, keepdims=True))
    rows.append(jnp.zeros((_SUMS - len(rows), h.shape[1]), _F32))
    return jnp.concatenate(rows, axis=0)


def _gated_bwd(b_ref, c_ref, z_ref, bb_ref, zb_ref, ca_ref, dy_ref, dya_ref,
               w_ref, dx_ref, sums_ref, kept, *, L: int, nT: int):
    t, part = pl.program_id(2), pl.program_id(3)
    n = dx_ref.shape[1]

    @pl.when(part == 0)
    def _():
        at_end = _after(ca_ref, t, nT) * _after(dya_ref, t, nT)

        def piece(i, at, carry):
            front, sums = carry
            b, c, z, dy = (_f32(r[0, at])
                           for r in (b_ref, c_ref, z_ref, dy_ref))
            g, h = b * z, c * dy        # h: the convolution's cotangent
            mixed, views = _conv(front, g, w_ref, L)
            dg = _conv_t(h, _next_rows((c_ref, dy_ref), i, n, at_end),
                         w_ref, L)
            dx_ref[0, at] = (z * dg).astype(dx_ref.dtype)
            kept[0, at] = (dy * mixed).astype(kept.dtype)
            kept[1, at] = (b * dg).astype(kept.dtype)
            return g[-_TILE:], sums + _row_sums(h, views, False)

        front = _before(bb_ref, t) * _before(zb_ref, t)
        sums_ref[0, 0] = _each_piece(
            n, piece, (front, jnp.zeros(sums_ref.shape[2:], _F32)))[1]

    @pl.when(part > 0)
    def _():
        dx_ref[0] = kept[part - 1]


def _silu_bwd(x_ref, xb_ref, xa_ref, dy_ref, dya_ref, w_ref, bias_ref,
              dx_ref, sums_ref, *, L: int, nT: int):
    t = pl.program_id(2)
    n = dx_ref.shape[1]
    x_end, dy_end = _after(xa_ref, t, nT), _after(dya_ref, t, nT)

    def piece(i, at, carry):
        front, sums = carry
        x = _f32(x_ref[0, at])
        size = x.shape[0]
        # the pre-activation over the rows and the 8 behind them, whose
        # cotangents the transposed taps reach
        pre, views = _conv(
            front, jnp.concatenate(
                [x, _next_rows((x_ref,), i, n, x_end)], axis=0), w_ref, L)
        dy = jnp.concatenate(
            [_f32(dy_ref[0, at]), _next_rows((dy_ref,), i, n, dy_end)], axis=0)
        h = dy * _silu_grad(pre + bias_ref[...])
        dx_ref[0, at] = _conv_t(h[:size], h[size:], w_ref, L).astype(
            dx_ref.dtype)
        return x[-_TILE:], sums + _row_sums(
            h[:size], [v[:size] for v in views], True)

    sums_ref[0, 0] = _each_piece(
        n, piece, (_before(xb_ref, t), jnp.zeros(sums_ref.shape[2:], _F32)))[1]


class _Layout:
    """Static shapes of a launch and the block specs over them: ``x`` is
    ``(B, T, W)``, the parts ``c`` columns each from ``offset``."""

    def __init__(self, x, taps, form: str, offset: int):
        (self.B, self.T, _), (self.L, self.c) = x.shape, taps.shape
        self.rows, self.lanes = _blocks(self.T, self.c, offset)
        self.nT, self.nC = self.T // self.rows, self.c // self.lanes
        self.first = offset // self.lanes       # column block of part 0
        self.parts = _parts(form)

    def _token_block(self, t, part_step):
        """The token block a step's operands are of.  A backward that walks
        the cotangent's parts computes at a block's first step and only
        copies at the others, so from the second on its operands name the
        NEXT block: the pipeline fetches it while the first step of this one
        computes, and not between two blocks with nothing to overlap."""
        if not part_step:
            return t
        return jnp.minimum(t + jnp.minimum(part_step[0], 1), self.nT - 1)

    def _column_block(self, part):
        """The first column block of a part of ``x`` (``part`` None: of an
        array of ``c`` columns)."""
        return 0 if part is None else self.first + part * self.nC

    def block(self, part=None):
        """A step's block of one part of ``x`` (or, ``part`` None, of an
        array of ``c`` columns)."""
        at = self._column_block(part)
        return pl.BlockSpec(
            (1, self.rows, self.lanes),
            lambda i, j, t, *p: (i, self._token_block(t, p), at + j))

    def halo(self, part=None, after: bool = False):
        """The 16 rows before (or after) a step's block, clamped into the
        sequence: the kernel zeroes them at its ends."""
        at = self._column_block(part)
        per, last = self.rows // _HALO, self.T // _HALO - 1
        if after:
            rows = lambda t: jnp.minimum((t + 1) * per, last)
        else:
            rows = lambda t: jnp.maximum(t * per - 1, 0)
        return pl.BlockSpec(
            (1, _HALO, self.lanes),
            lambda i, j, t, *p: (i, rows(self._token_block(t, p)), at + j))

    def channel_rows(self, n: int):
        """``(n, c)`` a row a tap (or the bias alone)."""
        return pl.BlockSpec((n, self.lanes), lambda i, j, t, *p: (0, j))


def _params(*semantics):
    return pltpu.CompilerParams(dimension_semantics=semantics)


@functools.partial(jax.jit, static_argnames=("form", "offset"))
def _forward(x, taps, bias, form, offset):
    lay = _Layout(x, taps, form, offset)
    taps = taps.astype(_F32)
    if form == "gated":
        ops = (x, x, x, x, x, taps)
        specs = [lay.block(0), lay.block(1), lay.block(2), lay.halo(0),
                 lay.halo(2), lay.channel_rows(lay.L)]
    else:
        ops = (x, x, taps, bias.astype(_F32)[None])
        specs = [lay.block(0), lay.halo(0), lay.channel_rows(lay.L),
                 lay.channel_rows(1)]
    return pl.pallas_call(
        functools.partial(_conv_fwd, form=form, L=lay.L),
        grid=(lay.B, lay.nC, lay.nT),
        in_specs=specs,
        out_specs=lay.block(),
        out_shape=jax.ShapeDtypeStruct((lay.B, lay.T, lay.c), x.dtype),
        compiler_params=_params("parallel", "parallel", "parallel"),
        interpret=interpret(),
        name="short_conv_fwd",
    )(*ops)


@functools.partial(jax.jit, static_argnames=("form", "offset"))
def _backward(x, taps, bias, dy, form, offset):
    """-> the cotangent of ``x``'s read columns ``(B, T, parts * c)`` in
    ``x``'s dtype, and the steps' sums ``(B, T / rows, 8, c)`` float32."""
    lay = _Layout(x, taps, form, offset)
    taps, dy = taps.astype(_F32), dy.astype(x.dtype)
    if form == "gated":
        ops = (x, x, x, x, x, x, dy, dy, taps)
        specs = [lay.block(0), lay.block(1), lay.block(2), lay.halo(0),
                 lay.halo(2), lay.halo(1, after=True), lay.block(),
                 lay.halo(after=True), lay.channel_rows(lay.L)]
        scratch = [pltpu.VMEM((2, lay.rows, lay.lanes), x.dtype)]
    else:
        ops = (x, x, x, dy, dy, taps, bias.astype(_F32)[None])
        specs = [lay.block(0), lay.halo(0), lay.halo(0, after=True),
                 lay.block(), lay.halo(after=True), lay.channel_rows(lay.L),
                 lay.channel_rows(1)]
        scratch = []
    nC = lay.nC
    return pl.pallas_call(
        functools.partial(_gated_bwd if form == "gated" else _silu_bwd,
                          L=lay.L, nT=lay.nT),
        grid=(lay.B, nC, lay.nT, lay.parts),
        in_specs=specs,
        out_specs=[
            pl.BlockSpec((1, lay.rows, lay.lanes),
                         lambda i, j, t, p: (i, t, p * nC + j)),
            pl.BlockSpec((1, 1, _SUMS, lay.lanes),
                         lambda i, j, t, p: (i, t, 0, j))],
        out_shape=[
            jax.ShapeDtypeStruct((lay.B, lay.T, lay.parts * lay.c), x.dtype),
            jax.ShapeDtypeStruct((lay.B, lay.nT, _SUMS, lay.c), _F32)],
        scratch_shapes=scratch,
        compiler_params=_params("parallel", "parallel", "parallel",
                                "arbitrary"),
        interpret=interpret(),
        name="short_conv_bwd",
    )(*ops)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _short_conv(x, taps, bias, form, offset):
    return _forward(x, taps, bias, form, offset)


def _vjp_fwd(x, taps, bias, form, offset):
    return _forward(x, taps, bias, form, offset), (x, taps, bias)


def _vjp_bwd(form, offset, kept, dy):
    x, taps, bias = kept
    dx, sums = _backward(x, taps, bias, dy, form, offset)
    behind = x.shape[2] - offset - dx.shape[2]
    if offset or behind:        # the projection's other columns: zeros
        dx = jnp.pad(dx, ((0, 0), (0, 0), (offset, behind)))
    sums = sums.sum((0, 1))
    L = taps.shape[0]
    return (dx, sums[:L].astype(taps.dtype),
            None if bias is None else sums[L].astype(bias.dtype))


_short_conv.defvjp(_vjp_fwd, _vjp_bwd)


def short_conv(x, taps, bias=None, *, form: str, offset: int = 0):
    """The module docstring's ``out``, ``(B, T, c)`` in ``x``'s dtype, through
    the kernels: ``x`` ``(B, T, W)``; ``taps`` ``(L, c)``, a tap a row;
    ``bias`` ``(c,)`` with the ``silu`` form.  The shapes are
    :func:`takes`'s."""
    if not takes(x, taps, bias, form=form, offset=offset):
        raise ValueError(
            f"short_conv does not take x {x.shape} {x.dtype}, taps "
            f"{taps.shape}, form {form!r}, offset {offset}, bias "
            f"{None if bias is None else bias.shape}")
    return _short_conv(x, taps, bias, form, offset)
