"""The chunked selective scan of a Mamba-2 mixer as a Pallas kernel pair.

``transformer/mamba2.py:ssd_chunked`` is the form and the reference: the row
cut into chunks of ``Q`` positions, ``c_i`` the running sum of ``delta A``
inside a chunk, ``L_ij = exp(c_i - c_j)`` for ``j <= i``,

    y = (L o C B^T)(delta x) + exp(c_i) C S_prev + D x
    S_next = exp(c_last) S_prev + B^T (exp(c_last - c_j) delta x)

XLA writes ``L`` and ``C B^T o L`` to HBM for every head and chunk (268 MB
a layer at 64 heads x 64 chunks of 128) and moves ``(b, nc, Q, G, R, P)`` axes
about around every product.  Here one grid step owns one chunk of one GROUP
of heads (``R = H / G`` heads, ``R P`` lanes: whole lane tiles); it builds a
head's ``L`` and scores in VMEM, uses them and drops them, and the state
``S`` (float32, ``(N, R P)`` a group) stays in a VMEM scratch from one chunk
to the next along the last grid axis, which is sequential.  The operands are
read where the mixer has them: ``x`` as ``(b, T, H P)``, ``B`` and ``C`` as
``(b, T, G N)`` once a group, by index maps.

Same types as the XLA form: decays float32 from the cumulative sum (made
outside by XLA, 2 MB a layer, and handed over twice, a head a lane and a head
a sublane: ``L`` wants ``c`` as a column and as a row, and the other of the
two would cost a transpose inside), every exponent <= 0 and masked before
the ``exp``; products take operands in the compute type (``x.dtype``)
and accumulate in float32; the carried state is float32 and is rounded only
as the operand of ``C S_prev``; ``y`` leaves float32.

**Heads of 64 channels** are half a lane tile.  Elementwise work runs on a
WINDOW of whole tiles (two such heads, or one head of whole tiles); a head's
product with its own ``(Q, Q)`` scores takes the window with the other head's
lanes zeroed, which costs the matrix unit what a 64-wide product would and
moves no lane.  Products that all heads of a group share (``C S_prev``, the
end states, ``dB``, ``dC``, the state's gradient) are one product a group.

**Backward** (:func:`_backward`): the forward rule also writes each chunk's
entering state, ``(b, nc, N, H P)`` float32.  The backward kernel walks the
chunks in reverse, carries ``dS`` in VMEM, rebuilds a chunk's decays and
scores, and writes ``dx``, ``dB``, ``dC`` (summed over the group's heads in
the kernel), the direct part of ``d delta``, and the gradient of the running
sums ``c`` (its sums over a row of scores as a column a head, over a column as
a row a head, and over a head's channels by masked lane sums).  What is left
to XLA is small: the two layouts added, the pullback of the cumulative sum to
``delta`` and ``A``, and ``dD``'s and the chunk-end decay's last sums over a
head's channels from one ``(b, nc, 2, H P)`` array of partial sums.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .pallas_common import LANES, interpret

__all__ = ["takes", "ssd_scan"]

_SEM = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"))
_F32 = jnp.float32


# what one grid step holds in VMEM grows with chunk x state x a group's
# channels; this is the envelope both kernels were compiled in for a v5e's
# default 16 MiB (PERF.md section 6, PR 46): a group of 4096 channels at
# chunks of 128 and state 128 in bf16 fits, one of 5120 does not
_GROUP_LANES = 4096
_STEP_BYTES = 1 << 27


def takes(x, B, C, chunk: int) -> bool:
    """Whether the kernels take these operands: chunks, state and a group's
    channels whole lane tiles, a head half a tile or whole tiles, the row
    whole chunks, one float type for ``x``, ``B`` and ``C``, and a grid
    step inside the VMEM envelope."""
    (b, T, H, P), (G, N) = x.shape, B.shape[2:]
    if x.dtype not in (jnp.bfloat16, jnp.float32) or H % G:
        return False
    lanes = H // G * P
    return (x.dtype == B.dtype == C.dtype
            and chunk % LANES == 0 and N % LANES == 0 and T % chunk == 0
            and lanes % LANES == 0 and (P == 64 or P % LANES == 0)
            and lanes <= _GROUP_LANES
            and chunk * N * lanes * x.dtype.itemsize <= _STEP_BYTES)


class _Layout:
    """Static shapes of one launch and the block specs over them.  ``win``
    lanes make a window of ``hw`` heads (module docstring)."""

    def __init__(self, b, T, H, P, G, N, Q):
        self.P, self.N, self.Q = P, N, Q
        self.R, self.nc = H // G, T // Q
        self.RP = self.R * P
        self.win = max(P, LANES)
        self.hw = self.win // P
        self.grid = (b, G, self.nc)

    def specs(self, reverse: bool):
        """Block specs by operand kind; the chunk axis runs backwards for
        the backward kernel."""
        nc, Q, R, RP, N = self.nc, self.Q, self.R, self.RP, self.N
        at = (lambda c: nc - 1 - c) if reverse else (lambda c: c)
        return dict(
            x=pl.BlockSpec((1, Q, RP), lambda i, g, c: (i, at(c), g)),
            bc=pl.BlockSpec((1, Q, N), lambda i, g, c: (i, at(c), g)),
            col=pl.BlockSpec((1, 1, Q, R), lambda i, g, c: (i, g, at(c), 0)),
            row=pl.BlockSpec((1, 1, R, Q), lambda i, g, c: (i, g, 0, at(c))),
            d=pl.BlockSpec((1, RP), lambda i, g, c: (0, g)),
            end=pl.BlockSpec((1, 1, 1, RP), lambda i, g, c: (i, at(c), 0, g)),
            state=pl.BlockSpec((1, 1, N, RP),
                               lambda i, g, c: (i, at(c), 0, g)),
            part=pl.BlockSpec((1, 1, 2, RP), lambda i, g, c: (i, at(c), 0, g)))

    def windows(self):
        """``(lanes of the window, [(head of the group, its lanes' mask or
        None)])``, a window at a time."""
        lo = lax.broadcasted_iota(jnp.int32, (1, self.win), 1) < self.P
        for k in range(self.RP // self.win):
            lanes = slice(k * self.win, (k + 1) * self.win)
            if self.hw == 1:
                yield lanes, [(k, None)]
            else:
                yield lanes, [(2 * k, lo), (2 * k + 1, jnp.logical_not(lo))]


def _spread(ref, heads, rows=slice(None)):
    """A window's ``(Q, win)`` array of a per-head column (or, with ``rows``
    one position, its ``(1, win)`` row): ``ref`` is the ``(1, 1, Q, R)``
    block, a head a lane."""
    cols = [ref[0, 0, rows, h:h + 1] for h, _ in heads]
    if len(cols) == 1:
        return cols[0]
    return jnp.where(heads[0][1], cols[0], cols[1])


def _to_end(cc_ref, heads, c_w, Q):
    """``exp(c_last - c_j)`` in the window's lanes: what is left of position
    ``j``'s input at the chunk's end."""
    return jnp.exp(_spread(cc_ref, heads, slice(Q - 1, Q)) - c_w)


def _only(a, mask):
    """``a`` with the other head's lanes zeroed."""
    return a if mask is None else jnp.where(mask, a, jnp.zeros_like(a))


def _decay(cc_ref, cr_ref, h, later):
    """``L`` of head ``h``: ``exp(c_i - c_j)`` for ``j <= i``, else 0."""
    gap = cc_ref[0, 0, :, h:h + 1] - cr_ref[0, 0, h:h + 1, :]
    return jnp.exp(jnp.where(later, gap, -jnp.inf))


def _nt(a, b):
    """``a b^T``: both contracted over their lanes."""
    return lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                           preferred_element_type=_F32)


def _tn(a, b):
    """``a^T b``: both contracted over their rows."""
    return lax.dot_general(a, b, (((0,), (0,)), ((), ())),
                           preferred_element_type=_F32)


def _nn(a, b):
    return jnp.dot(a, b, preferred_element_type=_F32)


def _later(Q):
    return (lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
            >= lax.broadcasted_iota(jnp.int32, (Q, Q), 1))


def _fwd_kernel(x_ref, b_ref, c_ref, dt_ref, cc_ref, cr_ref, d_ref, end_ref,
                *rest, lay: _Layout, save: bool):
    if save:
        y_ref, s_ref, S, wbuf = rest
    else:
        y_ref, S, wbuf = rest
    cdt = x_ref.dtype

    @pl.when(pl.program_id(2) == 0)
    def _():
        S[...] = jnp.zeros_like(S)

    Bm, Cm = b_ref[0], c_ref[0]
    prev = S[...]
    if save:
        s_ref[0, 0] = prev
    cb = _nt(Cm, Bm)                                    # (Q, Q)
    carried = _nn(Cm, prev.astype(cdt))                 # (Q, RP)
    later = _later(lay.Q)
    for lanes, heads in lay.windows():
        dt_w, c_w = _spread(dt_ref, heads), _spread(cc_ref, heads)
        x32 = x_ref[0, :, lanes].astype(_F32)
        u32 = x32 * dt_w                                # delta x
        u = u32.astype(cdt)
        y = carried[:, lanes] * jnp.exp(c_w) + x32 * d_ref[:, lanes]
        for h, mask in heads:
            m = (cb * _decay(cc_ref, cr_ref, h, later)).astype(cdt)
            y = y + _nn(m, _only(u, mask))
        y_ref[0, :, lanes] = y
        wbuf[:, lanes] = (u32 * _to_end(cc_ref, heads, c_w, lay.Q)).astype(cdt)
    S[...] = end_ref[0, 0] * prev + _tn(Bm, wbuf[...])


def _bwd_kernel(x_ref, b_ref, c_ref, dt_ref, cc_ref, cr_ref, d_ref, end_ref,
                s_ref, dy_ref, dx_ref, db_ref, dc_ref, ddt_ref, dcol_ref,
                drow_ref, part_ref, dS, wbuf, vbuf, *, lay: _Layout):
    cdt = x_ref.dtype

    @pl.when(pl.program_id(2) == 0)
    def _():
        dS[...] = jnp.zeros_like(dS)

    Bm, Cm = b_ref[0], c_ref[0]
    prev = s_ref[0, 0]                                  # entering, float32
    prev_c = prev.astype(cdt)
    d_next = dS[...]                                    # of the state leaving
    d_next_c = d_next.astype(cdt)
    cb = _nt(Cm, Bm)
    carried = _nn(Cm, prev_c)                           # C S_prev
    d_w = _nn(Bm, d_next_c)                             # of u o to_end
    later = _later(lay.Q)
    d_cb = jnp.zeros((lay.Q, lay.Q), _F32)
    for lanes, heads in lay.windows():
        dt_w, c_w = _spread(dt_ref, heads), _spread(cc_ref, heads)
        at_i = jnp.exp(c_w)
        to_end = _to_end(cc_ref, heads, c_w, lay.Q)
        x32 = x_ref[0, :, lanes].astype(_F32)
        u32 = x32 * dt_w
        u = u32.astype(cdt)
        dy = dy_ref[0, :, lanes]
        dy_c = dy.astype(cdt)
        wbuf[:, lanes] = (u32 * to_end).astype(cdt)
        vbuf[:, lanes] = (dy * at_i).astype(cdt)
        ended = d_w[:, lanes] * to_end
        d_u = ended
        rows = {}
        for h, mask in heads:
            L = _decay(cc_ref, cr_ref, h, later)
            m32 = cb * L
            dy_h = _only(dy_c, mask)
            d_m = _nt(dy_h, u)                          # (Q, Q) of head h
            d_cb = d_cb + d_m * L
            of_gap = d_m * m32
            rows[h] = jnp.sum(of_gap, axis=1, keepdims=True)
            drow_ref[0, 0, h:h + 1, :] = jnp.sum(of_gap, axis=0, keepdims=True)
            d_u = d_u + _tn(m32.astype(cdt), dy_h)
        # gradient of c at a position from the two decays that are no
        # (Q, Q) array, and of delta where it multiplies x; a head's sum
        # over its channels is a masked sum over the window's lanes
        of_c = dy * carried[:, lanes] * at_i - ended * u32
        of_dt = d_u * x32
        for h, mask in heads:
            dcol_ref[0, 0, :, h:h + 1] = rows[h] + jnp.sum(
                _only(of_c, mask), axis=1, keepdims=True)
            ddt_ref[0, 0, :, h:h + 1] = jnp.sum(
                _only(of_dt, mask), axis=1, keepdims=True)
        dx_ref[0, :, lanes] = (d_u * dt_w + dy * d_ref[:, lanes]).astype(
            dx_ref.dtype)
        # sums over the chunk's positions, a channel a lane: D's, and
        # c_last's (from to_end, and from the carry exp(c_last) S_prev)
        part_ref[0, 0, 0:1, lanes] = jnp.sum(dy * x32, axis=0, keepdims=True)
        part_ref[0, 0, 1:2, lanes] = (
            jnp.sum(ended * u32, axis=0, keepdims=True)
            + end_ref[0, 0, :, lanes] * jnp.sum(
                d_next[:, lanes] * prev[:, lanes], axis=0, keepdims=True))
    d_cb_c = d_cb.astype(cdt)
    dc_ref[0] = (_nt(vbuf[...], prev_c) + _nn(d_cb_c, Bm)).astype(dc_ref.dtype)
    db_ref[0] = (_nt(wbuf[...], d_next_c) + _tn(d_cb_c, Cm)).astype(
        db_ref.dtype)
    dS[...] = end_ref[0, 0] * d_next + _tn(Cm, vbuf[...])


def _chunk_sums(dt, A, Q):
    """``c``: the running sum of ``delta A`` inside each chunk, (b, T, H)
    float32, as ``ssd_chunked`` makes it."""
    b, T, H = dt.shape
    a = (dt.astype(_F32) * A.astype(_F32)).reshape(b, T // Q, Q, H)
    return jnp.cumsum(a, axis=2).reshape(b, T, H)


def _cols(a, G):
    """(b, T, H) -> (b, G, T, R): a head a lane, for columns."""
    b, T, H = a.shape
    return a.reshape(b, T, G, H // G).transpose(0, 2, 1, 3)


def _rows(a, G):
    """(b, T, H) -> (b, G, R, T): a head a sublane, for rows."""
    b, T, H = a.shape
    return a.reshape(b, T, G, H // G).transpose(0, 2, 3, 1)


def _operands(x, dt, A, B, C, D, Q):
    """The arrays both kernels read, and the layout."""
    (b, T, H, P), (G, N) = x.shape, B.shape[2:]
    lay = _Layout(b, T, H, P, G, N, Q)
    dt = dt.astype(_F32)
    c = _chunk_sums(dt, A, Q)
    end = jnp.exp(c.reshape(b, lay.nc, Q, H)[:, :, -1])         # (b, nc, H)
    ops = (x.reshape(b, T, H * P), B.reshape(b, T, G * N),
           C.reshape(b, T, G * N), _cols(dt, G), _cols(c, G), _rows(c, G),
           jnp.repeat(D.astype(_F32), P)[None],
           jnp.repeat(end, P, axis=-1)[:, :, None])
    kinds = ("x", "bc", "bc", "col", "col", "row", "d", "end")
    return lay, ops, kinds


@functools.partial(jax.jit, static_argnames=("Q", "save"))
def _forward(x, dt, A, B, C, D, Q, save):
    """-> ``y`` (b, T, H, P) float32, and with ``save`` each chunk's
    entering state (b, nc, N, H P) float32."""
    lay, ops, kinds = _operands(x, dt, A, B, C, D, Q)
    s = lay.specs(False)
    b, T, HP = ops[0].shape
    out_shape = [jax.ShapeDtypeStruct((b, T, HP), _F32)]
    out_specs = [s["x"]]
    if save:
        out_shape.append(jax.ShapeDtypeStruct((b, lay.nc, lay.N, HP), _F32))
        out_specs.append(s["state"])
    out = pl.pallas_call(
        functools.partial(_fwd_kernel, lay=lay, save=save),
        grid=lay.grid,
        in_specs=[s[k] for k in kinds],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((lay.N, lay.RP), _F32),
                        pltpu.VMEM((Q, lay.RP), x.dtype)],
        compiler_params=_SEM,
        interpret=interpret(),
        name="ssd_fwd",
    )(*ops)
    y = out[0].reshape(x.shape)
    return (y, out[1]) if save else (y, None)


@functools.partial(jax.jit, static_argnames=("Q",))
def _backward(x, dt, A, B, C, D, states, dy, Q):
    lay, ops, kinds = _operands(x, dt, A, B, C, D, Q)
    s = lay.specs(True)
    (b, T, H, P), (G, N) = x.shape, B.shape[2:]
    HP, R, nc = H * P, lay.R, lay.nc
    col = jax.ShapeDtypeStruct((b, G, T, R), _F32)
    dx, dB, dC, ddt, dcol, drow, part = pl.pallas_call(
        functools.partial(_bwd_kernel, lay=lay),
        grid=lay.grid,
        in_specs=[s[k] for k in kinds] + [s["state"], s["x"]],
        out_specs=[s["x"], s["bc"], s["bc"], s["col"], s["col"], s["row"],
                   s["part"]],
        out_shape=[jax.ShapeDtypeStruct((b, T, HP), x.dtype),
                   jax.ShapeDtypeStruct((b, T, G * N), B.dtype),
                   jax.ShapeDtypeStruct((b, T, G * N), C.dtype),
                   col, col, jax.ShapeDtypeStruct((b, G, R, T), _F32),
                   jax.ShapeDtypeStruct((b, nc, 2, HP), _F32)],
        scratch_shapes=[pltpu.VMEM((N, lay.RP), _F32),
                        pltpu.VMEM((Q, lay.RP), x.dtype),
                        pltpu.VMEM((Q, lay.RP), x.dtype)],
        compiler_params=_SEM,
        interpret=interpret(),
        name="ssd_bwd",
    )(*ops, states, dy.astype(_F32).reshape(b, T, HP))
    heads = lambda a: a.transpose(0, 2, 1, 3).reshape(b, T, H)   # of _cols
    d_c = heads(dcol) - drow.transpose(0, 3, 1, 2).reshape(b, T, H)
    sums = part.reshape(b, nc, 2, H, P).sum(-1)                 # (b, nc, 2, H)
    d_c = d_c.reshape(b, nc, Q, H).at[:, :, -1].add(sums[:, :, 1])
    # c = cumsum(delta A) inside a chunk: its pullback to delta and A
    _, pull = jax.vjp(lambda dt_, A_: _chunk_sums(dt_, A_, Q),
                      dt.astype(_F32), A.astype(_F32))
    ddt_c, dA = pull(d_c.reshape(b, T, H))
    return (dx.reshape(x.shape), (heads(ddt) + ddt_c).astype(dt.dtype),
            dA.astype(A.dtype), dB.reshape(B.shape), dC.reshape(C.shape),
            sums[:, :, 0].sum((0, 1)).astype(D.dtype))


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def _ssd(x, dt, A, B, C, D, Q):
    return _forward(x, dt, A, B, C, D, Q, False)[0]


def _ssd_fwd(x, dt, A, B, C, D, Q):
    y, states = _forward(x, dt, A, B, C, D, Q, True)
    return y, (x, dt, A, B, C, D, states)


def _ssd_bwd(Q, kept, dy):
    return _backward(*kept, dy, Q)


_ssd.defvjp(_ssd_fwd, _ssd_bwd)


def ssd_scan(x, dt, A, B, C, D, chunk: int):
    """``ssd_chunked``'s signature and result through the kernels: ``x``
    (b, T, H, P); ``dt`` (b, T, H) float32, after its softplus; ``A`` (H,)
    float32, negative; ``B``, ``C`` (b, T, G, N); ``D`` (H,) -> ``y``
    (b, T, H, P) float32.  The shapes are :func:`takes`'s."""
    if not takes(x, B, C, chunk):
        raise ValueError(f"ssd_scan does not take x {x.shape} {x.dtype}, "
                         f"B {B.shape} {B.dtype}, chunk {chunk}")
    return _ssd(x, dt, A, B, C, D, chunk)
