"""Shared plumbing for the Pallas kernel family.

The reference's multi_tensor_apply harness (csrc/multi_tensor_apply.cuh:
40-126) exists to smuggle tensor addresses into 4KB CUDA kernel-arg
structs, chunking and relaunching as the struct fills.  TPU has no such
constraint: the tensor list is concatenated into one flat buffer on device
(a fusion XLA performs as pure data movement) and each kernel tiles over a
2-D (rows, 128) view of it — lanes fixed at 128, row blocks sized for VMEM.

The view is free only when the buffer's length is a whole number of
blocks: ``to_2d`` is then a reshape (a bitcast on the chip) and
``from_2d`` slices nothing, so a kernel's ``input_output_aliases`` reach
the caller's own buffer.  Any other length costs one ``pad`` per operand
on the way in and one ``slice`` per result on the way out, each a full
copy of the buffer.  ``aligned_len`` is the rule for a caller that keeps
a buffer across steps and can choose its length (amp's flat masters,
gradients and moments do); the two counters ``flat_pad_copies_total`` /
``flat_pad_copy_elements_total`` in the observability registry count, per
traced program, the copies that were made anyway.

Beside them, counted the same way (once per traced program), what amp's
step does with the gradient on its way into these kernels:
``amp_unscale_total{where="kernel"|"pass"}``, once a traced
``AmpOptimizer.step``: unscaled in the inner optimizer's kernel
(``FusedAdam``'s, which widens its gradient operand in registers and
takes ``scale=``), or by a pass of its own that writes an unscaled
float32 copy (``_scale_flat``); and
``amp_grad_pack_total{path="native"|"float32", dtype}``, once a
segment of the flat layout packed for a ``kernel`` step: in the dtype
its leaves came in, or widened to float32 before the concatenate
(``docs/observability.md``).
"""

from __future__ import annotations

from typing import Any, List, Sequence, Tuple

import jax
import jax.numpy as jnp

from ..multi_tensor_apply.flatten import pack_flat, unpack_flat  # noqa: F401
# (re-exported: the kernels' flatten plumbing is the shared helper in
# multi_tensor_apply.flatten — one implementation, three call sites)

LANES = 128
# rows per grid block: 512 rows x 128 lanes x 4B = 256 KiB per buffer in
# VMEM — small enough for several operands to co-reside, large enough to
# amortize grid overhead
BLOCK_ROWS = 512
BLOCK_ELEMS = BLOCK_ROWS * LANES


def token_tile_axes(B: int, T: int) -> Tuple[int, int, int]:
    """Leading axes ``(B, T / 8, 8)`` of the view ``(B, T / 8, 8, heads, D)``
    of a token-major ``(B, T, heads * D)`` array: the one in which
    elementwise work and reductions per head cost no copy on a TPU.  A
    tile of such an array is 8 tokens x 128 lanes, so this view's tiles
    are whole and the compiler takes the reshape as a bitcast, where the
    tiles of ``(B, T, heads, D)``, 8 heads x 128 lanes, make it a relayout
    of the array on the way in and another on the way out.  ``(B, T, 1)``
    where 8 does not divide T."""
    t8 = 8 if T % 8 == 0 else 1
    return B, T // t8, t8


def interpret() -> bool:
    from . import dispatch
    return dispatch.interpret_mode()


# fp32 minimum tile is (8, 128): any block_rows the optimizer kernels
# use must stay a multiple of this sublane count
MIN_SUBLANES = 8


def pick_block_rows(n: int) -> int:
    """Rows per grid block for an ``n``-element buffer: BLOCK_ROWS for
    full-model buffers, but a ZeRO-sharded update runs on a 1/ici (or
    1/world) slice that can be far smaller than BLOCK_ELEMS — padding
    it up to a 512-row block and launching a 1-block grid would move
    up to 65535 dead elements through VMEM per operand.  For buffers
    under one block, shrink the block to the smallest multiple of the
    fp32 min-tile sublane count (8 rows x 128 lanes) that covers the
    buffer, so the shard update stays ONE kernel launch with at most
    one sublane tile of padding.  Rows stay divisible by the block by
    construction — the partial-tile lint (analysis.pallas_lint) holds
    for every shard size."""
    rows = max(1, -(-int(n) // LANES))
    if rows >= BLOCK_ROWS:
        return BLOCK_ROWS
    return -(-rows // MIN_SUBLANES) * MIN_SUBLANES


def aligned_len(n: int) -> int:
    """Smallest length >= ``n`` that ``to_2d(buf, pick_block_rows(n))``
    views without padding: a whole number of ``pick_block_rows(n) *
    LANES``-element blocks (65 536 elements from one full block up, so
    at most 256 KiB of fp32 more than ``n``).  An empty buffer stays
    empty."""
    n = int(n)
    if n <= 0:
        return 0
    block = pick_block_rows(n) * LANES
    return -(-n // block) * block


def _count_copy(elements: int) -> None:
    """One operand padded or one result sliced, at TRACE time (the
    registry's DDP counters work the same way: totals count traced
    programs, not executed steps)."""
    from ..observability.metrics import get_registry
    reg = get_registry()
    reg.counter(
        "flat_pad_copies_total",
        help="flat-buffer operands padded by to_2d and results sliced "
             "by from_2d, per traced program; 0 when every buffer has "
             "a block-aligned length").inc()
    reg.counter(
        "flat_pad_copy_elements_total",
        help="elements those pads and slices copy per traced program"
    ).inc(elements)


def count_grad_pack(path: str, dtype: str) -> None:
    """One segment of a gradient packed for amp's flat optimizer step, at
    TRACE time like ``_count_copy``."""
    from ..observability.metrics import get_registry
    get_registry().counter(
        "amp_grad_pack_total",
        help="gradient segments amp packed for the flat optimizer step, "
             "per traced program: path=native in the dtype the leaves "
             "came in, path=float32 widened before the concatenate"
    ).labels(path=path, dtype=dtype).inc()


def count_unscale(where: str) -> None:
    """One amp step traced, by where its gradient is unscaled."""
    from ..observability.metrics import get_registry
    get_registry().counter(
        "amp_unscale_total",
        help="amp steps traced, by where the gradient is unscaled: "
             "where=kernel in the inner optimizer's kernel (one finite "
             "read outside it), where=pass by a pass of its own that "
             "writes the unscaled float32 gradient"
    ).labels(where=where).inc()


def to_2d(flat: jax.Array, block_rows: int = BLOCK_ROWS
          ) -> Tuple[jax.Array, int]:
    """View a 1-D buffer as (rows, LANES), rows a multiple of
    ``block_rows`` so every grid block is full: a reshape when the
    length already is one (see ``aligned_len``), else a zero pad first
    (a copy of the buffer, counted).  Returns (arr2d, orig_len)."""
    n = flat.shape[0]
    rows = max(1, -(-n // LANES))
    rows = -(-rows // block_rows) * block_rows
    padded = rows * LANES
    if padded != n:
        _count_copy(padded)
        flat = jnp.pad(flat, (0, padded - n))
    return flat.reshape(rows, LANES), n


def from_2d(arr2d: jax.Array, n: int) -> jax.Array:
    """Inverse of ``to_2d``: the first ``n`` elements as a 1-D buffer —
    a reshape when the view held nothing else, else a slice (a copy,
    counted)."""
    flat = arr2d.reshape(-1)
    if flat.shape[0] == n:
        return flat
    _count_copy(n)
    return flat[:n]
