"""Row moves between token order and the routed experts' row buffer: both
ways as gathers, where the way home has few empty slots.

The sorted dispatch of ``parallel/expert_parallel.py`` keeps the rows of the
experts a layer holds in one buffer ``(R, d)``, sorted by expert.  A row
travels there by ``x[token]``, a gather by the sort; it travels back weighted
by its gate, and the line that says so most plainly,
``zeros.at[token].add(ys * weight)``, is a scatter-add: on a TPU a
read-modify-write of HBM, one row after another, about 120 ns a row of the
buffer, live or dead, where the compiler's gather moves a row in 8 (PERF.md
section 6, PR 45).  So where the slots a token may fill are few beside the
buffer's rows (``home_by_gathers``) the way back is written as a gather too,
by the *inverse* of the sort:

- **rows from tokens**, ``out[p] = x[token[p]]`` (:func:`gather`);
- **tokens from rows**, ``out[t] = add[t] + sum_c scale[t, c] *
  rows[at[c * T + t]]`` over a token's ``k`` assignments, ``at`` where each
  assignment (choice-major) lies in the buffer and -1 where it has no row
  (held elsewhere, or past the buffer's end): the ``k * T`` rows are gathered,
  weighed and summed in fp32 in one pass, and rounded once.  A slot whose
  ``scale`` is 0 adds exactly nothing, whatever the row it names holds.  The
  buffer is gathered from in chunks of columns small enough for the compiler
  to hold a chunk in VMEM (``_column_chunks``), where its gather runs ten
  times as fast as from HBM.

Each is the other's transpose, and ``gather`` and ``combine`` say so by
``custom_vjp``: the gather's cotangent is tokens-from-rows with 0/1 scales
(summed in fp32, where autodiff's scatter-add summed in the cotangent's
dtype), the combine's is rows-from-tokens scaled by the gate weights, and the
gate weights' gradient is read back by ``at``.  No scatter-add is traced for
the rows in either direction, on any backend.  A layer most of whose slots
would be empty (``home_by_gathers`` says which) keeps ``x[token]`` and the
scatter-add, whose cost follows the buffer's rows and not the slots.
``moe_row_move_calls_total{impl, move}`` counts the moves traced
(docs/observability.md).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .pallas_common import LANES

__all__ = ["gather", "combine", "tokens_from_rows", "home_by_gathers",
           "count_move"]


# slots a row of the buffer up to which the way home is a gather: on a v5e
# a gathered row costs about 8 ns and the pass that sums the slots 6 more, a
# row of a scatter-add 85-120, whether live or dead (PERF.md section 6, PR
# 45), so the two meet near 8 slots a row, where the benchmark's two cells
# that hold a sixteenth of their experts stand; its two that hold a quarter
# stand at 2
_SLOTS_A_ROW = 4


def home_by_gathers(slots: int, rows: int) -> bool:
    """Whether ``slots`` (tokens x top_k) come home to ``rows`` rows of a
    buffer by :func:`combine`'s gather; by ``zeros.at[token].add`` where
    not."""
    return slots <= _SLOTS_A_ROW * rows


# bytes of a gather's source up to which the TPU's compiler keeps it in VMEM
# beside what else a step prefetches there, where a gathered row costs 4-8 ns;
# from HBM it costs 38-44 (PERF.md section 6, PR 45: sources of 48 MiB and
# under read 0.23-0.27 ms for 65 536 rows, one of 64 MiB 0.90 inside a step
# that has other uses for VMEM, a (32 768, 2304) buffer whole 2.9)
_SOURCE_BYTES = 48 * 2 ** 20


def _column_chunks(R: int, d: int, itemsize: int) -> int:
    """Chunks of whole lane tiles of columns a row buffer is cut into so that
    a chunk, the source of one gather, is at most ``_SOURCE_BYTES``: the
    fewest that divide the tiles (one where ``d`` is not whole tiles)."""
    tiles = d // LANES if d % LANES == 0 else 1
    fit = [c for c in range(1, tiles + 1)
           if tiles % c == 0 and R * (d // c) * itemsize <= _SOURCE_BYTES]
    return fit[0] if fit else tiles


def count_move(impl: str, move: str, moves: int = 1) -> None:
    """Row moves traced, on the host (the registry counts traced programs,
    not executed steps)."""
    from ..observability.metrics import get_registry
    get_registry().counter(
        "moe_row_move_calls_total",
        help="moves of the routed experts' rows between token order and the "
             "row buffer traced, by what implements them (gather: XLA's "
             "gather by the sort or by its inverse, ops/row_moves.py, each "
             "where it is traced; scatter_add: zeros.at[token].add, counted "
             "with the transposes autodiff writes) and the direction").labels(
                 impl=impl, move=move).inc(moves)


def _take(a, index):
    return jnp.asarray(a).at[index].get(mode="promise_in_bounds")


def tokens_from_rows(rows, at, scale, add=None):
    """rows (R, d), at (k * T,), scale (T, k) fp32, add (T, d) or None ->
    (T, d) in rows' dtype (module docstring)."""
    count_move("gather", "tokens_from_rows")
    T, k = scale.shape
    index, scale = jnp.maximum(at, 0), scale.astype(jnp.float32)
    wide = rows.shape[1] // _column_chunks(*rows.shape, rows.dtype.itemsize)
    out = []
    for lo in range(0, rows.shape[1], wide):
        got = _take(rows[:, lo:lo + wide], index)
        y = 0.0 if add is None else add[:, lo:lo + wide].astype(jnp.float32)
        # a choice's rows are one slice of what was gathered: k slices of one
        # 2-D array fuse into one pass over it on a TPU, where a (k, T, d)
        # view costs a pass of its own (a convert to fp32 before it; for k
        # under 8 a relayout too)
        for c in range(k):
            w = scale[:, c:c + 1]
            # a select, not a product alone: a row nobody has may hold anything
            y = y + jnp.where(w != 0, got[c * T:(c + 1) * T].astype(
                jnp.float32), 0.0) * w
        out.append(y.astype(rows.dtype))
    return jnp.concatenate(out, axis=1)


@jax.custom_vjp
def gather(x, token, at):
    """x (T, d) -> (R, d), row ``p`` is ``x[token[p]]``.  ``at`` (k * T,) is
    where each assignment (choice-major, ``choice * T + token``) lies in the
    buffer, -1 where it has no row there: what the cotangent is gathered by,
    a sum in fp32 over a token's rows, rounded once."""
    return _gather_fwd(x, token, at)[0]


def _gather_fwd(x, token, at):
    count_move("gather", "rows_from_tokens")
    # (of ``x`` its token count alone is kept, as an array without columns)
    return _take(x, token), (at, x[:, :0])


def _gather_bwd(kept, dxs):
    at, tokens = kept
    has_row = (at >= 0).astype(jnp.float32).reshape(-1, tokens.shape[0]).T
    return tokens_from_rows(dxs, at, has_row), None, None


gather.defvjp(_gather_fwd, _gather_bwd)


@jax.custom_vjp
def combine(ys, gates, add, token, weight, at):
    """ys (R, d), gates (T, k) -> (T, d) in ys' dtype: ``add[t] + sum_c
    gates[t, c] * ys[at[c * T + t]]`` over the assignments with a row (``at``
    as in :func:`gather`); ``add`` (T, d) or None.  ``weight`` (R,) is
    ``gates`` in the buffer's order, row ``p`` its token ``token[p]``'s, 0
    where a row is nobody's: the cotangent of ``ys`` is ``weight[p] *
    dy[token[p]]``.  Differentiable in ys, gates (not through ``weight``)
    and add."""
    return _combine_fwd(ys, gates, add, token, weight, at)[0]


def _combine_fwd(ys, gates, add, token, weight, at):
    # (of ``add`` its dtype alone is kept, as an array without rows)
    return (tokens_from_rows(ys, at, gates, add),
            (ys, gates, None if add is None else add[:0], token, weight, at))


def _combine_bwd(kept, dy):
    ys, gates, add, token, weight, at = kept
    count_move("gather", "rows_from_tokens")
    got = _take(dy, token).astype(jnp.float32)
    dys = (got * weight.astype(jnp.float32)[:, None]).astype(ys.dtype)
    by_row = jnp.sum(ys.astype(jnp.float32) * got, axis=1)
    at = at.reshape(-1, dy.shape[0]).T
    dgates = jnp.where(at >= 0, _take(by_row, jnp.maximum(at, 0)), 0.0)
    return (dys, dgates.astype(gates.dtype),
            None if add is None else dy.astype(add.dtype), None, None, None)


combine.defvjp(_combine_fwd, _combine_bwd)
