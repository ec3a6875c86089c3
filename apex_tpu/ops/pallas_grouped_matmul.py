"""Pallas grouped matrix products over rows sorted by group.

``rows`` is a row buffer ``(R, K)`` whose first ``sum(group_sizes)`` rows
lie group after group (the routed experts' dispatch sorts them so); group
``g`` meets ``stack[g]``, a ``(K, N)`` matrix of a ``(G, K, N)`` stack.
Three products, tied by one ``custom_vjp``:

- the forward, ``rows x stack -> (R, N)``, and the rows' gradient,
  ``dy (R, N) x stack^T -> (R, K)``: one kernel, the second reading the same
  weight block with its contraction on the other axis, so no transposed copy
  of the stack exists;
- the stack's gradient, ``rows^T x dy -> (G, K, N)``, one output block a
  group, accumulated over the group's row tiles in fp32 in VMEM.

**What the grid walks.**  Not the buffer's tiles but *work items*: a (group,
row tile) pair for every tile a group has rows in, a tile that straddles two
groups once a group.  The items are computed from ``group_sizes`` by a few
small XLA operations (``work_items``, once a layer) and reach the kernel by scalar
prefetch, where the index maps read them: consecutive items of one group
name the same weight block, which is then not fetched again, so a group
reads its weights once.  A tile outside every group is one item that stores
zeros: no product, no fetch (its operand index stays where it was).  The
stack's gradient never visits such a tile, and visits an empty group once,
to write its zeros.  The grid's length is static, ``R / tile + G - 1``, the
most items any split of the rows can make; what is left over does nothing.

**The rows outside every group are the kernel's.**  Its results read zero
there, and what its operands hold there is never multiplied into a live row
or a weight (the stack's gradient masks both operands on a boundary tile: a
NaN times a zero would still be a NaN).  Operands arrive in the compute type
(bf16 under amp), products accumulate in fp32, and a result is rounded once,
to the operands' type: the arithmetic of ``lax.ragged_dot`` with
``preferred_element_type=float32`` and a cast behind it, without the fp32
array between them.

``row_tile`` chooses the tile from shapes, and 0 where the kernel does not
take them (K or N neither whole lane tiles nor whole tiles and a half one, a
row count no tile divides, another dtype): the caller keeps
``lax.ragged_dot`` there.  A width that ends in half a lane tile (an expert
width of 1856 = 14.5 x 128) is taken as it is, the leaves at their published
shapes: a block spans the array's whole width, and inside the kernel the half
tile is one more, narrower, matrix step (a masked load and store), so no
padded copy of a weight or of the rows exists anywhere.
``moe_grouped_dot_calls_total{impl, tile}`` counts the products traced
(docs/observability.md).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .pallas_common import LANES, interpret

__all__ = ["grouped_matmul", "work_items", "row_tile", "count_product"]

# what a work item of the rows' kernel does: nothing; the product, into a
# tile met for the first time (the rows outside the group become zeros) or
# met before (they stay); zeros only
_SKIP, _FIRST, _AGAIN, _ZERO = 0, 1, 2, 3
# bits of a work item of the stack's kernel: the group's first item (the
# accumulator starts at zero), its last (the block is stored), rows to add
_OPENS, _CLOSES, _ADDS = 1, 2, 4

# of the 16 MiB a kernel gets unless it asks, what the compiler does not
# keep for itself
_VMEM_BUDGET = 14 * 2 ** 20
# what the rows' kernels may ask for where a group's weight block does not
# fit the default twice (``_limit`` states the ask at the launch; a v5e core
# has 128 MiB of VMEM), and the tiles tried under it: at K x N = 2048 x 1792,
# 8 groups of about 2048 rows in a 32 768-row buffer, the three products take
# 2.80 ms at 256, 2.88 at 512 (the rows' kernels lose 7 %, the stack's gains
# 3) and 3.00 at 128, against 6.8-8.0 for ``lax.ragged_dot`` (PERF.md
# section 6, PR 33)
_VMEM_ASK = 32 * 2 ** 20
_ASK_TILES = (256, 128)
_COLUMNS = 1024                 # most result columns a matrix step writes
_ACCUMULATOR = 3 * 2 ** 20      # bytes of the stack kernel's fp32 block


def count_product(impl: str, tile: int, products: int = 1) -> None:
    """Grouped products traced, on the host (the registry counts traced
    programs, not executed steps)."""
    from ..observability.metrics import get_registry
    get_registry().counter(
        "moe_grouped_dot_calls_total",
        help="grouped matrix products of the routed experts traced, by what "
             "implements them (mosaic: ops/pallas_grouped_matmul.py, forward "
             "and both gradients; ragged_dot: lax.ragged_dot, whose gradients "
             "autodiff writes uncounted) and the row tile").labels(
                 impl=impl, tile=str(tile)).inc(products)


def _takes(K: int, N: int) -> bool:
    """Widths the kernels take: whole lane tiles, the smaller of the two
    with or without half a tile at its end (the stack's kernel cuts the
    larger side into whole tiles)."""
    return (min(K, N) >= LANES and K % (LANES // 2) == 0
            and N % (LANES // 2) == 0 and max(K, N) % LANES == 0)


def _columns(n: int) -> int:
    """Result columns a matrix step of the rows' kernel writes: the most
    lane tiles under ``_COLUMNS`` that divide ``n``'s whole tiles, so that
    the fp32 result of a step stays a fraction of the tile's."""
    tiles = n // LANES
    return LANES * max(c for c in range(1, _COLUMNS // LANES + 1)
                       if tiles % c == 0)


def _chunks(n: int) -> tuple:
    """``(start, width)`` of the matrix steps over ``n`` result columns:
    runs of ``_columns(n)`` over the whole lane tiles, then the half tile
    ``n`` may end in."""
    whole, cols = n - n % LANES, _columns(n)
    steps = [(c, cols) for c in range(0, whole, cols)]
    return tuple(steps + ([(whole, n - whole)] if n > whole else []))


def _rows_vmem(tm: int, K: int, N: int, itemsize: int) -> int:
    """Bytes the rows' kernel holds in VMEM at a row tile of ``tm``: the
    operand tile, the group's weight block and the result tile, each twice
    (the pipeline's two buffers), and a matrix step's fp32 result with its
    select."""
    return (2 * itemsize * (tm * K + K * N + tm * N)
            + 2 * 4 * tm * _columns(N))


def _stack_blocks(K: int, N: int) -> tuple:
    """``(bk, bn)``: the block of a group's ``(K, N)`` gradient one sweep
    over the rows accumulates, the longer side cut so that the fp32
    accumulator stays near 3 MiB."""
    def cut(side, other):
        tiles = side // LANES
        return LANES * max(
            c for c in range(1, tiles + 1) if tiles % c == 0
            and (c == 1 or c * LANES * other * 4 <= _ACCUMULATOR))
    # (the side that is cut is whole lane tiles: ``_takes``)
    return (cut(K, N), N) if K >= N else (K, cut(N, K))


def row_tile(R: int, K: int, N: int, groups: int, dtype) -> int:
    """Rows of a tile for ``(R, K) x (groups, K, N)``, 0 where the kernels do
    not take the shapes.  A larger tile feeds the matrix unit longer between
    two weight pushes and pays the fixed cost of a grid step (about 0.35 us
    on a v5e) less often; a smaller one loses less to group boundaries, where
    a straddled tile is multiplied once a group (at R / groups rows a group,
    ``groups - 1`` tiles more than the rows need).  The largest of 512, 256,
    128 that divides R, fits VMEM with the group's whole weight block
    resident in both directions, and is at most a quarter of a group's
    expected rows (PERF.md section 5 has the timings on a v5e).  Where no
    tile fits the kernel's default VMEM (a block of 2048 x 1792 in bf16 is
    7.3 MB, twice 14.7), the same rule over ``_ASK_TILES`` under ``_VMEM_ASK``,
    which the launch then asks for."""
    dtype = jnp.dtype(dtype)
    if (dtype not in (jnp.dtype(jnp.bfloat16), jnp.dtype(jnp.float32))
            or not _takes(K, N) or groups < 1):
        return 0
    for room, tiles in ((_VMEM_BUDGET, (512, 256, 128)),
                        (_VMEM_ASK, _ASK_TILES)):
        fits = [tm for tm in tiles if R % tm == 0
                and _rows_vmem(tm, K, N, dtype.itemsize) <= room]
        if fits:
            return next((tm for tm in fits if 4 * tm <= R // groups),
                        fits[-1])
    return 0


def work_items(group_sizes, R: int, tm: int):
    """The grids' work items from the groups' sizes (module docstring), for
    a buffer of ``R`` rows in tiles of ``tm``: a pair of int32 tuples,
    ``(offsets, group, read, tile, kind)`` for the rows' kernels and
    ``(offsets, group, tile, kind)`` for the stack's: ``offsets`` (G + 1,)
    where each group starts (a group is cut where the buffer ends), and per
    item, ``R / tm + G - 1`` of them, its group, the row tile it reads and
    the one it writes (the stack's kernel only reads) and what it does
    (``_FIRST`` ... for the rows' kernels, the bits ``_OPENS`` ... for the
    stack's).  Every product of a layer takes the same two, so a layer
    computes them once; they are sums and compares over (items, G) and
    (G, G) arrays, which the compiler fuses into a few small kernels."""
    G = group_sizes.shape[0]
    tiles = R // tm
    items = jnp.arange(tiles + G - 1, dtype=jnp.int32)
    g = jnp.arange(G, dtype=jnp.int32)

    def running(v):             # an inclusive cumulative sum over groups
        return jnp.sum(jnp.where(g[None, :] <= g[:, None], v[None, :], 0),
                       axis=1, dtype=jnp.int32)

    ends = jnp.minimum(running(group_sizes.astype(jnp.int32)), R)
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32), ends])
    starts = offsets[:-1]
    held = ends - starts > 0
    first = starts // tm
    spans = (ends - 1) // tm - first + 1        # tiles a group has rows in

    def walk(visits):
        """Items group after group, ``visits[g]`` of them on consecutive
        tiles from the group's first; past them the last one's blocks
        again, so that nothing is fetched."""
        upto = running(visits)
        group = jnp.minimum(jnp.sum(upto[None, :] <= items[:, None], axis=1,
                                    dtype=jnp.int32), G - 1)
        mine = group[:, None] == g[None, :]
        pick = lambda v: jnp.sum(jnp.where(mine, v[None, :], 0), axis=1,
                                 dtype=jnp.int32)
        opens_at, closes_at = pick(upto - visits), pick(upto) - 1
        tile = jnp.minimum(pick(first) + items - opens_at, tiles - 1)
        real = items < upto[-1]
        last = jnp.maximum(upto[-1] - 1, 0)
        at_last = lambda v: jnp.sum(jnp.where(items == last, v, 0),
                                    dtype=jnp.int32)
        return (real, jnp.where(real, group, at_last(group)),
                jnp.where(real, tile, at_last(tile)), opens_at, closes_at,
                pick(held.astype(jnp.int32)))

    # the rows' kernels: a group's tiles, then the tiles no group reaches
    real, group, read, _, _, _ = walk(jnp.where(held, spans, 0))
    dead = (ends[-1] + tm - 1) // tm + items - jnp.sum(real, dtype=jnp.int32)
    fresh = read != jnp.concatenate([read[:1] - 1, read[:-1]])
    kind = jnp.where(real, jnp.where(fresh, _FIRST, _AGAIN),
                     jnp.where(dead < tiles, _ZERO, _SKIP))
    tile = jnp.where(real, read, jnp.minimum(dead, tiles - 1))
    for_rows = (offsets, group, read, tile, kind.astype(jnp.int32))
    # the stack's kernel: an empty group is visited once, to write its zeros
    real, group, read, opens_at, closes_at, adds = walk(
        jnp.where(held, spans, 1))
    kind = jnp.where(real, (items == opens_at) * _OPENS
                     + (items == closes_at) * _CLOSES + adds * _ADDS, _SKIP)
    return for_rows, (offsets, group, read, kind.astype(jnp.int32))


def _dot(a, b, contract):
    return lax.dot_general(a, b, (contract, ((), ())),
                           preferred_element_type=jnp.float32)


def _inside(offsets, group, tile, tm):
    """(tm, 1) bool: the rows of ``tile`` that are ``group``'s."""
    row = tile * tm + lax.broadcasted_iota(jnp.int32, (tm, 1), 0)
    return (row >= offsets[group]) & (row < offsets[group + 1])


def _rows_kernel(offsets, group, read, tile, kind, x_ref, w_ref, o_ref, *,
                 tm, transposed):
    i = pl.program_id(0)
    what = kind[i]

    @pl.when(what == _ZERO)
    def _():
        o_ref[...] = jnp.zeros(o_ref.shape, o_ref.dtype)

    @pl.when((what == _FIRST) | (what == _AGAIN))
    def _():
        inside = _inside(offsets, group[i], tile[i], tm)
        x = x_ref[...]
        for c, cols in _chunks(o_ref.shape[1]):
            at = (slice(None), slice(c, c + cols))
            if transposed:      # the block's rows are the result's columns
                y = _dot(x, w_ref[0, c:c + cols, :], ((1,), (1,)))
            else:
                y = _dot(x, w_ref[0, :, c:c + cols], ((1,), (0,)))

            @pl.when(what == _FIRST)
            def _():
                o_ref[at] = jnp.where(inside, y, 0.0).astype(o_ref.dtype)

            @pl.when(what == _AGAIN)
            def _():
                o_ref[at] = jnp.where(
                    inside, y, o_ref[at].astype(jnp.float32)).astype(
                        o_ref.dtype)


# (jitted as the family's launches are: a step's launches of one shape are
# then traced and lowered to Mosaic once, not once a layer and product,
# which is 5 s of every start of a 36-product step)
@functools.partial(jax.jit, static_argnames=("tm", "transposed"))
def _rows_product(x, stack, scalars, tm: int, transposed: bool):
    """x (R, A) x stack (G, K, N) -> (R, B): A, B = K, N, or N, K with the
    stack read ``transposed``; ``scalars`` the rows' kernels' work items."""
    R, A = x.shape
    G, K, N = stack.shape
    B = K if transposed else N
    need = _rows_vmem(tm, A, B, x.dtype.itemsize)
    return pl.pallas_call(
        functools.partial(_rows_kernel, tm=tm, transposed=transposed),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(scalars),
            grid=(R // tm + G - 1,),
            in_specs=[
                pl.BlockSpec((tm, A), lambda i, o, g, r, t, k: (r[i], 0)),
                pl.BlockSpec((1, K, N), lambda i, o, g, r, t, k: (g[i], 0, 0)),
            ],
            out_specs=pl.BlockSpec((tm, B),
                                   lambda i, o, g, r, t, k: (t[i], 0))),
        out_shape=jax.ShapeDtypeStruct((R, B), x.dtype),
        # a straddled tile is written by two consecutive items
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_limit(need)),
        cost_estimate=pl.CostEstimate(
            flops=2 * R * K * N, transcendentals=0,
            bytes_accessed=(R * (K + N) + G * K * N) * x.dtype.itemsize),
        interpret=interpret(),
        name="grouped_rows_t" if transposed else "grouped_rows",
    )(*scalars, x, stack)


def _limit(need: int):
    """``vmem_limit_bytes`` for a kernel that holds ``need`` bytes: the
    default where that is enough."""
    return None if need <= _VMEM_BUDGET else need + 4 * 2 ** 20


def _stack_kernel(offsets, group, tile, kind, x_ref, dy_ref, o_ref, acc, *,
                  tm):
    i = pl.program_id(1)
    what = kind[i]

    @pl.when(what & _OPENS != 0)
    def _():
        acc[...] = jnp.zeros(acc.shape, acc.dtype)

    @pl.when(what & _ADDS != 0)
    def _():
        g, row0 = group[i], tile[i] * tm
        whole = (row0 >= offsets[g]) & (row0 + tm <= offsets[g + 1])

        @pl.when(whole)
        def _():
            acc[...] += _dot(x_ref[...], dy_ref[...], ((0,), (0,)))

        @pl.when(jnp.logical_not(whole))
        def _():
            # both sides: a zero does not silence a NaN across from it
            inside = _inside(offsets, g, tile[i], tm)
            x, dy = x_ref[...], dy_ref[...]
            acc[...] += _dot(jnp.where(inside, x, jnp.zeros_like(x)),
                             jnp.where(inside, dy, jnp.zeros_like(dy)),
                             ((0,), (0,)))

    @pl.when(what & _CLOSES != 0)
    def _():
        o_ref[0] = acc[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("tm", "dtype"))
def _stack_product(x, dy, scalars, tm: int, dtype):
    """x (R, K), dy (R, N) -> (G, K, N) in ``dtype``: ``x[g]^T dy[g]`` over
    each group's rows; ``scalars`` the stack's kernel's work items."""
    R, K = x.shape
    N, G = dy.shape[1], scalars[0].shape[0] - 1
    bk, bn = _stack_blocks(K, N)
    # one sweep over the rows a block of the gradient; the side that is cut
    # picks the block, the other is whole
    over_k = bk != K
    pick = (lambda j: (j, 0)) if over_k else (lambda j: (0, j))
    return pl.pallas_call(
        functools.partial(_stack_kernel, tm=tm),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(scalars),
            grid=((K // bk) * (N // bn), R // tm + G - 1),
            in_specs=[
                pl.BlockSpec((tm, bk),
                             lambda j, i, o, g, t, k: (t[i], pick(j)[0])),
                pl.BlockSpec((tm, bn),
                             lambda j, i, o, g, t, k: (t[i], pick(j)[1])),
            ],
            out_specs=pl.BlockSpec(
                (1, bk, bn), lambda j, i, o, g, t, k: (g[i], *pick(j))),
            scratch_shapes=[pltpu.VMEM((bk, bn), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((G, K, N), dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        cost_estimate=pl.CostEstimate(
            flops=2 * R * K * N, transcendentals=0,
            bytes_accessed=(R * (K + N) * x.dtype.itemsize
                            + G * K * N * jnp.dtype(dtype).itemsize)),
        interpret=interpret(),
        name="grouped_stack",
    )(*scalars, x, dy)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _grouped(rows, stack, for_rows, for_stack, tm):
    return _grouped_fwd(rows, stack, for_rows, for_stack, tm)[0]


def _grouped_fwd(rows, stack, for_rows, for_stack, tm):
    count_product("mosaic", tm)
    return (_rows_product(rows, stack, for_rows, tm, False),
            (rows, stack, for_rows, for_stack))


def _grouped_bwd(tm, kept, dy):
    rows, stack, for_rows, for_stack = kept
    count_product("mosaic", tm, 2)
    return (_rows_product(dy, stack, for_rows, tm, True),
            _stack_product(rows, dy, for_stack, tm, stack.dtype),
            None, None)


_grouped.defvjp(_grouped_fwd, _grouped_bwd)


def grouped_matmul(rows: jax.Array, stack: jax.Array, items,
                   tile: int) -> jax.Array:
    """rows (R, K) x stack (G, K, N) -> (R, N) in the operands' dtype, the
    rows lying group after group from the front as ``items =
    work_items(group_sizes, R, tile)`` says; the result's rows outside
    every group are zeros, and so are theirs of the rows' gradient.
    Differentiable in rows and stack."""
    R, K = rows.shape
    G, _, N = stack.shape
    if (tile < 8 or R % tile or not _takes(K, N) or stack.shape[1] != K
            or rows.dtype != stack.dtype
            or items[0][0].shape != (G + 1,)
            or items[0][1].shape != (R // tile + G - 1,)):
        raise ValueError(
            "grouped_matmul needs rows (R, K) and a stack (G, K, N) of one "
            "dtype, K and N whole lane tiles (the smaller may end in half a "
            "tile), R a whole number of row tiles "
            f"and the work items of G groups over them: got {rows.shape} "
            f"{rows.dtype}, {stack.shape} {stack.dtype}, tile {tile}")
    return _grouped(rows, stack, *items, tile)
