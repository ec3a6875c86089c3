"""AmpOptimizer: master weights, unscale, overflow-skip — functionally.

The reference performs in-place surgery on torch optimizers
(apex/amp/_process_optimizer.py): clones fp16 params to fp32 masters and
swaps them into param_groups (:13-73), patches ``step`` to copy masters
back to the model (:286-296), and installs pre/post-backward hooks that the
``scale_loss`` context drives (:76-239).  Here the same observable behavior
is a pure wrapper: masters are optimizer *state*, unscale+overflow-check is
the fused multi_tensor_scale, and a skipped step is a ``lax.cond`` that
leaves (params, masters, inner state) untouched — the whole thing lives
inside jit with no host sync.
"""

from __future__ import annotations

import math
from typing import Any, Callable, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from .scaler import LossScaler, ScalerState
from ..ops.pallas_common import (LANES, aligned_len, count_grad_pack,
                                 count_unscale, pick_block_rows)
from ..optimizers.base import GradSegments, Optimizer


def _axis_in_scope(name: str) -> bool:
    """True iff ``name`` is a currently-mapped collective axis — local
    copy of parallel.sync_batchnorm._axis_in_scope (imported inline
    would pull the parallel package into amp's import graph).  Public
    probe: ``lax.axis_index`` raises NameError for an unbound axis;
    pinned by tests/test_syncbn.py::test_axis_scope_probe."""
    try:
        jax.lax.axis_index(name)
        return True
    except NameError:
        return False
    except Exception:
        return True

__all__ = ["AmpOptState", "AmpOptimizer", "FlatMasters",
           "zero_optimizer_specs", "zero_gather_params",
           "zero_gather_checkpoint_policy"]


def _zero_slice_groups(axis_name: str, ici: int):
    """(ici_groups, dcn_groups) of the hierarchical fabric for the
    mapped axis — the same consecutive-block/same-offset split the DDP
    hierarchical allreduce uses (lazy import: the parallel package must
    not enter amp's import graph at module load)."""
    from ..parallel import topology as _topology
    world = jax.lax.axis_size(axis_name)
    return _topology.hierarchical_axis_groups(int(world), int(ici))


def _validate_zero_knobs(zero_stage: int, zero_ici_size, compress: bool):
    if zero_stage not in (1, 2, 3):
        raise ValueError(f"zero_stage must be 1, 2 or 3, got "
                         f"{zero_stage!r}")
    if zero_stage >= 2 and zero_ici_size is None:
        raise ValueError(
            f"ZeRO stage {zero_stage} shards over the ICI slice of the "
            f"hierarchical fabric; pass zero_ici_size= (devices per "
            f"slice)")
    if compress and zero_stage < 2:
        raise ValueError(
            "zero_compress_bf16 compresses the DCN hop of the stage-2/3 "
            "grad reduction; stage 1 shards over the full axis and has "
            "no DCN hop to shrink")


def zero_optimizer_specs(optimizer: "AmpOptimizer", params: Any,
                         axis_name: str = "data",
                         zero_stage: int = 1,
                         zero_ici_size: Optional[int] = None,
                         zero_compress_bf16: bool = False) -> Any:
    """PartitionSpec tree for ``optimizer.init(params, zero_axis=...)``
    run inside shard_map — flat master/moment shards are ``P(axis)``
    (device-concat layout), scalars replicated.  Use as the out_specs of
    the mapped init and the in/out specs of the mapped step::

        ospecs = amp.zero_optimizer_specs(optimizer, params, "data")
        opt_state = jax.jit(jax.shard_map(
            lambda p: optimizer.init(p, zero_axis="data"), mesh=mesh,
            in_specs=(P(),), out_specs=ospecs, check_vma=False))(params)

    The ZeRO knobs must MATCH the ``init`` call exactly: the layout is
    the FlatMasters pytree's aux data, so a spec tree built with
    different knobs is a different treedef and shard_map rejects it.
    For stages 2/3 the buffer is the ICI-slice concat replicated across
    slices, so the global view is still ``P(axis)`` over the mapped
    axis only when every slice holds identical bytes — which the
    stage-2/3 step maintains (DCN-reduced shards are bitwise equal);
    the spec stays ``P(axis)`` for the world-concat layout of stage 1
    and ``P()`` is wrong for all stages (the buffer is never
    replicated per device).  Stage 2/3 specs remain ``P(axis)``: jax
    materializes the device-concat global, slices repeat across DCN.
    """
    from jax.sharding import PartitionSpec as P
    if not (optimizer.master_weights
            and getattr(optimizer.inner, "elementwise", False)):
        # same precondition init enforces — fail at the first API call
        # instead of inside a jitted trace later
        raise ValueError(
            "zero_axis requires master weights and an elementwise inner "
            "optimizer (the flat-buffer path)")
    _validate_zero_knobs(zero_stage, zero_ici_size, zero_compress_bf16)
    layout = _FlatLayout(params, axis_name, zero_stage, zero_ici_size,
                         zero_compress_bf16)

    def leaf_spec(l):
        return P() if getattr(l, "ndim", 0) == 0 else P(axis_name)

    inner_abs = jax.eval_shape(
        optimizer.inner.init,
        jax.ShapeDtypeStruct((max(layout.total, 1),), jnp.float32))
    inner_specs = jax.tree_util.tree_map(leaf_spec, inner_abs)
    scaler_abs = jax.eval_shape(optimizer.scaler.init_state)
    scaler_specs = tuple(
        jax.tree_util.tree_map(lambda _: P(), scaler_abs)
        for _ in range(optimizer.num_losses))
    return AmpOptState(inner=inner_specs,
                       masters=FlatMasters(P(axis_name), layout),
                       scalers=scaler_specs)


# checkpoint_name tag on the ZeRO-3 gathered flat parameter buffer —
# the policy below rematerializes exactly this value in the backward
ZERO3_GATHER_NAME = "zero3_gathered_params"


# the gather -> rebuild chain of zero_gather_params, by primitive: the
# remat policy must mark EVERY eqn on it unsaveable, because partial
# eval cuts the replay at the first saveable ancestor — a name tag on
# the leaves alone is useless when the producing slice/reshape/convert
# outputs are unnamed saveable aliases one eqn upstream
_ZERO3_REPLAY_PRIMS = frozenset(
    ("all_gather", "slice", "dynamic_slice", "reshape",
     "convert_element_type", "custom_vjp_call", "custom_vjp_call_jaxpr"))


def zero_gather_checkpoint_policy():
    """Rematerialization policy for a ZeRO-3 forward: save every
    residual EXCEPT the just-in-time gathered parameters, which the
    backward re-gathers from the master shard (one extra in-slice
    all_gather on the wire — the ZeRO-3 trade: the full fp32 model
    never stays live across the backward).  Activations stay saved;
    only the gather/rebuild chain (and any other pure data-movement
    slice/reshape/cast the model does) is recomputed.  Use as
    ``jax.checkpoint(loss_fn, policy=zero_gather_checkpoint_policy())``
    around a loss that calls :func:`zero_gather_params`."""
    from jax._src.ad_checkpoint import name_p

    def policy(prim, *_, **params):
        if prim is name_p:
            return params["name"] != ZERO3_GATHER_NAME
        return prim.name not in _ZERO3_REPLAY_PRIMS
    return policy


def _zero3_gather_tables(layout: "_FlatLayout", ici: int):
    """Static index tables for the ZeRO-3 mixed-dtype gather.

    The wire-heavy gather runs at the model's half dtype (the values
    the forward needs are ``half(master)`` anyway), but leaves that
    stay fp32 (BN affine under O2) must arrive bit-exact — a bf16
    round-trip would diverge from the replicated-param stages.  Those
    "exact" elements are scattered through the flat buffer and the
    shard cut does not align with leaf boundaries, so each device
    contributes its local exact elements through a per-device index
    row (padded to the max count ``M`` so the all_gather stays
    uniform).  Returns ``(idx [ici, max(M,1)] int32 local-shard
    indices, rebuild [n32] int32 indices into the gathered
    [ici*max(M,1)] aux buffer, n32, M)`` — all plain numpy, computed
    identically by :func:`zero_gather_params` and the comm plan so
    graph and plan cannot desync on the aux payload."""
    import numpy as np
    padded = -(-layout.total // ici) * ici
    shard = padded // ici
    half = (str(layout.half_dtype) if layout.half_dtype is not None
            else None)
    pos = []
    for dt, f, off, n in zip(layout.dtypes, layout.is_float,
                             layout.offsets, layout.sizes):
        if f and dt != half:
            pos.extend(range(off, off + n))
    per = [[p - d * shard for p in pos if d * shard <= p < (d + 1) * shard]
           for d in range(ici)]
    m_max = max((len(p) for p in per), default=0)
    idx = np.zeros((ici, max(m_max, 1)), np.int32)
    rebuild = np.zeros(len(pos), np.int32)
    k = 0
    for d, p in enumerate(per):
        idx[d, :len(p)] = p
        # offsets ascend, so concatenating the per-device partitions in
        # device order walks the exact elements in layout order
        for slot in range(len(p)):
            rebuild[k] = d * max(m_max, 1) + slot
            k += 1
    return idx, rebuild, len(pos), m_max


def zero_gather_params(masters: "FlatMasters", axis_name: Optional[str]
                       = None) -> Any:
    """ZeRO-3 just-in-time parameter materialization: all_gather the
    master shard within its ICI slice, slice off the layout pad, and
    rebuild the params tree at the model dtypes.

    The gather runs at the model's HALF dtype when the layout has one
    (O2): the forward only ever consumes ``half(master)``, so casting
    the shard before the collective halves both the wire bytes and the
    gathered buffer that XLA must hold live — the fp32 full model never
    exists.  Leaves that stay fp32 (BN affine) ride a second tiny
    all_gather of the exact elements (see :func:`_zero3_gather_tables`)
    so their values match the replicated-param stages bit for bit.
    All-fp32 layouts (no half dtype) fall back to one fp32 gather.

    The backward is a hand-written VJP, not the autodiff transpose:
    transposing 60+ per-leaf ``slice``/``reshape``/``cast`` chains
    pads every leaf cotangent back to the FULL flat length and
    ``add_any``s the padded buffers — XLA materializes several
    whole-model fp32 temporaries.  The custom rule packs the leaf
    cotangents with ONE concatenate (each element belongs to exactly
    one leaf, so the values are bitwise those of the transpose) and
    feeds the in-slice ``psum_scatter`` — which is exactly the flat
    grad shard ``AmpOptimizer.step`` expects: call this at the top of
    the loss function, differentiate w.r.t. ``masters`` (a pytree
    whose only leaf is the shard), and pass the cotangent straight in
    as ``scaled_grads``.

    The gathered values are tagged ``checkpoint_name(...,
    ZERO3_GATHER_NAME)``: wrap the loss function in
    ``jax.checkpoint(f, policy=zero_gather_checkpoint_policy())`` and
    the full parameter set is NOT a residual — the backward RE-GATHERS
    the slice params just in time (everything else — activations —
    stays saved) instead of holding ``total`` fp32 elements live
    across the whole backward."""
    from jax.ad_checkpoint import checkpoint_name
    layout = masters.layout
    if layout.zero_axis is None or layout.zero_stage != 3:
        raise RuntimeError(
            "zero_gather_params requires a ZeRO-3 layout (init with "
            "zero_stage=3); stages 1/2 gather inside the step itself")
    axis = axis_name if axis_name is not None else layout.zero_axis
    ici_groups, _ = _zero_slice_groups(axis, layout.zero_ici)
    padded = -(-layout.total // layout.zero_ici) * layout.zero_ici
    half = layout.half_dtype
    if half is not None:
        idx_np, rebuild_np, n32, _ = _zero3_gather_tables(
            layout, layout.zero_ici)
        # concrete device constants (constvars in the jaxpr) — a plain
        # numpy capture would stage per-dispatch device_put transfers
        with jax.ensure_compile_time_eval():
            idx_t = jnp.asarray(idx_np)
            rebuild_t = jnp.asarray(rebuild_np)

    @jax.custom_vjp
    def gather(buf):
        # the tag lands on every value derived from the gather that
        # the backward would otherwise keep as a residual: the flat
        # gathered buffer AND the reshaped/cast leaves (conv
        # dgrad/wgrad read the leaves, not the buffer)
        if half is None:
            full = jax.lax.all_gather(
                buf, axis, axis=0, tiled=True,
                axis_index_groups=ici_groups)[:layout.total]
            full = checkpoint_name(full, ZERO3_GATHER_NAME)
            leaves = []
            for shape, dt, off, n in zip(layout.shapes, layout.dtypes,
                                         layout.offsets, layout.sizes):
                piece = jax.lax.slice_in_dim(full, off, off + n)
                piece = piece.reshape(shape)
                if str(piece.dtype) != dt:
                    piece = piece.astype(jnp.dtype(dt))
                leaves.append(checkpoint_name(piece, ZERO3_GATHER_NAME))
            return tuple(leaves)
        fullh = jax.lax.all_gather(
            buf.astype(half), axis, axis=0, tiled=True,
            axis_index_groups=ici_groups)[:layout.total]
        fullh = checkpoint_name(fullh, ZERO3_GATHER_NAME)
        exact = None
        if n32:
            row = jnp.take(idx_t,
                           jax.lax.axis_index(axis) % layout.zero_ici,
                           axis=0)
            aux = jnp.take(buf, row)
            g32 = jax.lax.all_gather(aux, axis, axis=0, tiled=True,
                                     axis_index_groups=ici_groups)
            exact = jnp.take(g32, rebuild_t)
        leaves, ex_off = [], 0
        for shape, dt, f, off, n in zip(layout.shapes, layout.dtypes,
                                        layout.is_float, layout.offsets,
                                        layout.sizes):
            if f and dt == str(half):
                piece = jax.lax.slice_in_dim(fullh, off, off + n)
                piece = piece.reshape(shape)
            else:
                piece = jax.lax.slice_in_dim(exact, ex_off, ex_off + n)
                ex_off += n
                piece = piece.reshape(shape).astype(jnp.dtype(dt))
            leaves.append(checkpoint_name(piece, ZERO3_GATHER_NAME))
        return tuple(leaves)

    def gather_fwd(buf):
        return gather(buf), None

    def gather_bwd(_, cts):
        # commit each cotangent to its leaf dtype before widening: XLA's
        # excess-precision pass would otherwise elide the f16 round-trip
        # (cotangent -> f16 -> f32) and hand the optimizer higher-precision
        # grads than the replicated-param (ZeRO-1/2) path sees, breaking
        # bitwise master parity across stages
        cts = jax.lax.optimization_barrier(cts)
        flat = jnp.concatenate(
            [ct.astype(jnp.float32).reshape(-1) for ct in cts])
        if padded != layout.total:
            flat = jnp.pad(flat, (0, padded - layout.total))
        shard = jax.lax.psum_scatter(
            flat, axis, scatter_dimension=0, tiled=True,
            axis_index_groups=ici_groups)
        return (shard,)

    gather.defvjp(gather_fwd, gather_bwd)
    return jax.tree_util.tree_unflatten(layout.treedef,
                                        list(gather(masters.buf)))


class AmpOptState(NamedTuple):
    inner: Any                     # wrapped optimizer's state
    masters: Any                   # FlatMasters | fp32 master pytree | None
    scalers: Tuple[ScalerState, ...]  # one per loss (num_losses)


def _to_fp32(tree):
    return jax.tree_util.tree_map(
        lambda p: p.astype(jnp.float32) if jnp.issubdtype(
            jnp.result_type(p), jnp.floating) else p, tree)


def _cast_like(tree, like):
    return jax.tree_util.tree_map(
        lambda x, l: x.astype(l.dtype) if jnp.issubdtype(
            jnp.result_type(l), jnp.floating) else x, tree, like)


def _found_nonfinite(tree) -> jax.Array:
    """1.0 where any float leaf holds an inf or a nan, else 0.0: one
    reduction a leaf, in the leaf's own dtype (the flag
    ``multi_tensor_scale`` raises, without its pass that writes)."""
    ok = [jnp.all(jnp.isfinite(l)) for l in jax.tree_util.tree_leaves(tree)
          if jnp.issubdtype(jnp.result_type(l), jnp.floating)]
    if not ok:
        return jnp.zeros((), jnp.float32)
    return jnp.where(jnp.all(jnp.stack(ok)), 0.0, 1.0).astype(jnp.float32)


def _cut(buf: jax.Array, offset: int, size: int, shape) -> jax.Array:
    """``buf[offset:offset + size]`` as a leaf of ``shape``, the piece
    held whole before it is reshaped (``_FlatLayout``'s docstring has
    what the compiler does to a slice that is reshaped)."""
    piece = jax.lax.slice_in_dim(buf, offset, offset + size)
    return jax.lax.optimization_barrier(piece).reshape(shape)


def _joined(parts, dtype) -> jax.Array:
    """``parts`` end to end, the empty ones left out."""
    parts = [p for p in parts if p.shape[0]]
    if not parts:
        return jnp.zeros((0,), dtype)
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts)


class _FlatLayout:
    """Static description of a float-leaf flattening, computed once at
    ``AmpOptimizer.init``.  The reference flattens each param group once at
    construction (apex/optimizers/fp16_optimizer.py:57-70); round-1 apex_tpu
    instead re-packed the whole tree every step
    (round-2 VERDICT weak-item 2) — this layout makes pack a single concat
    and unpack one static slice a leaf.

    Order.  The un-sharded layout keeps its float leaves BY DTYPE, as the
    reference keeps its parameter groups (``fp16_groups`` and
    ``fp32_from_fp32_groups``, apex/amp/_process_optimizer.py): the leaves
    of the one half dtype first, in tree order, then from the next block
    boundary the float32 leaves (norms, routers, biases and taps that stay
    float32 under O2), in tree order, inside the ONE buffer each of
    masters and moments.  ``segments`` lists them, ``(start, length)`` in
    elements with both on block boundaries, an empty one left out.  A
    gradient can then be packed one array a segment in the dtype the
    backward wrote it (``pack_grads``), float32 leaves unrounded, and the
    Adam kernel runs once a segment over that segment's blocks of the
    buffers; half leaves come first so that the half copy the kernel
    writes for their segment is indexed by the same ``offsets`` as the
    float32 buffer.  Where a leaf sits is this class's private matter:
    everything outside reads ``offsets``.  A tree with two half dtypes
    and every ZeRO layout (whose shard arithmetic and comm plans count in
    tree order, and whose reduce is float32) keep TREE order, one segment.

    Three lengths.  ``total`` is the LOGICAL element count, the sum of the
    float leaves' sizes: the ZeRO shard arithmetic and the comm plans
    count in it.  ``offsets[i]`` is where float leaf ``i`` starts in a
    buffer: ``rebuild`` and ``unpack_masters`` cut ``sizes[i]`` elements
    from there.  ``storage`` is the length the un-sharded path keeps its
    persistent buffers at (masters, the inner optimizer's moments, a
    float32 pack): every segment rounded up to the kernels' block
    (``ops.pallas_common.pick_block_rows(total)`` rows), so the Adam and
    unscale kernels view them without a pad or a slice and update them in
    place; with one segment that is ``aligned_len(total)``.  The elements
    no leaf owns (after each segment's last leaf) are zero and stay zero
    under every elementwise inner optimizer (g = m = v = p = 0 updates to
    0).  ZeRO shards keep their own length, ``ceil(total / population)``,
    with no tail.

    How a leaf leaves a buffer (``_cut``).  A slice that is reshaped is not
    what the TPU's compiler runs: it turns ``slice(buf).reshape(rows, W)``
    round into ``slice(buf.reshape(N / W, W))``, a reshape of the WHOLE
    buffer once for every distinct last dimension W among the leaves (a
    relayout, so a copy of the buffer each; a float32 leaf narrower than a
    lane tile has the master buffer copied lane-padded), and only then cuts
    rows out of that.  So the cut piece is held whole behind an
    ``optimization_barrier`` before it is reshaped: the compiler then
    writes each leaf twice, one slice and one reshape of the leaf's own
    size, whatever the widths are, and plans no temporary of the buffer's
    size (``tests/test_flat_storage.py`` reads the program compiled for a
    v5e).  The second pass is the relayout from the 1-D tiling to the
    leaf's; one pass would take a kernel of our own.

    ZeRO: with ``zero_axis`` the flat master/moment buffers hold only
    THIS device's slice (sharded over the named data axis); the step
    reduce-scatters grads and all-gathers the updated params.
      stage 1 — shard over the FULL axis (world-concat layout)
      stage 2 — shard over the ICI slice of the hierarchical fabric
                (zero_ici devices); state replicated across slices,
                grads DCN-reduced on the 1/ici shard, params
                re-gathered within the slice only
      stage 3 — like 2, but params are NEVER gathered back by the
                step: the fp32 master shard IS the parameter store
                and the forward regathers just-in-time
                (zero_gather_params)"""

    def __init__(self, params, zero_axis: Optional[str] = None,
                 zero_stage: int = 1, zero_ici: Optional[int] = None,
                 zero_compress: bool = False):
        leaves, self.treedef = jax.tree_util.tree_flatten(params)
        self.shapes = tuple(tuple(l.shape) for l in leaves)
        self.dtypes = tuple(str(jnp.result_type(l)) for l in leaves)
        self.is_float = tuple(
            jnp.issubdtype(jnp.result_type(l), jnp.floating) for l in leaves)
        self.zero_axis = zero_axis
        self.zero_stage = int(zero_stage)
        self.zero_ici = int(zero_ici) if zero_ici is not None else None
        self.zero_compress = bool(zero_compress)   # bf16 DCN grad hop
        self.sizes = tuple(int(math.prod(shape)) if f else 0
                           for shape, f in zip(self.shapes, self.is_float))
        self.total = sum(self.sizes)
        halves = {d for d, f in zip(self.dtypes, self.is_float)
                  if f and d != "float32"}
        # the single non-fp32 float dtype (O2's cast_model_type), if any —
        # lets the fused Adam kernel emit the half model copy in-pass
        self.half_dtype = (jnp.dtype(next(iter(halves)))
                           if len(halves) == 1 else None)
        floats = [i for i, f in enumerate(self.is_float) if f]
        # "dtype": half leaves, then float32 leaves; "tree": as they come
        self.order = ("dtype" if zero_axis is None and len(halves) <= 1
                      else "tree")
        if self.order == "dtype":
            groups = [[i for i in floats if self.dtypes[i] != "float32"],
                      [i for i in floats if self.dtypes[i] == "float32"]]
        else:
            groups = [floats]
        block = pick_block_rows(self.total) * LANES
        offsets, segments, members, at = [0] * len(leaves), [], [], 0
        for group in filter(None, groups):
            start = at
            for i in group:
                offsets[i] = at
                at += self.sizes[i]
            if zero_axis is None:
                at = start + -(-(at - start) // block) * block
            segments.append((start, at - start))
            members.append(tuple(group))
        self.offsets = tuple(offsets)
        self.segments = tuple(segments)
        self._members = tuple(members)
        self._end = at

    # layouts are jit-cache keys via FlatMasters aux_data
    def _key(self):
        return (self.treedef, self.shapes, self.dtypes, self.zero_axis,
                self.zero_stage, self.zero_ici, self.zero_compress)

    def __eq__(self, other):
        return isinstance(other, _FlatLayout) and self._key() == other._key()

    def __hash__(self):
        return hash(self._key())

    @property
    def storage(self) -> int:
        """Length of the un-sharded path's persistent flat buffers."""
        return aligned_len(self.total) if self.zero_axis else self._end

    def pack(self, tree) -> jax.Array:
        """Float leaves → one flat fp32 buffer (single concat), every
        leaf at its offset: of ``storage`` elements, the zeros after a
        segment's last leaf more operands of the concat, on the
        un-sharded path; of ``total`` elements under ZeRO, whose callers
        pad to their shard population."""
        leaves = jax.tree_util.tree_leaves(tree)
        parts, at = [], 0
        for (start, length), group in zip(self.segments, self._members):
            parts.append(jnp.zeros((start - at,), jnp.float32))
            parts += [leaves[i].reshape(-1).astype(jnp.float32)
                      for i in group]
            at = start + sum(self.sizes[i] for i in group)
        if parts:
            parts.append(jnp.zeros((start + length - at,), jnp.float32))
        return _joined(parts, jnp.float32)

    def pack_grads(self, tree) -> GradSegments:
        """A gradient tree → one flat array a segment, each in the dtype
        its leaves came in (bf16 as the backward or
        ``ddp.allreduce_grads`` wrote them; float32 leaves unrounded): the
        operand the Adam kernel widens in its registers.  A segment whose
        leaves came in more than one dtype is packed in their common one.
        For the un-sharded layouts."""
        leaves = jax.tree_util.tree_leaves(tree)
        out = []
        for (_, length), group in zip(self.segments, self._members):
            own = [leaves[i].reshape(-1) for i in group]
            dt = jnp.result_type(*own)
            count_grad_pack("native" if all(l.dtype == dt for l in own)
                            else "float32", str(dt))
            used = sum(self.sizes[i] for i in group)
            # the zeros behind a barrier: as a constant operand the TPU's
            # compiler writes the concatenate and then a pad of it, a
            # second pass over the packed gradient
            tail = jax.lax.optimization_barrier(
                jnp.zeros((length - used,), dt))
            out.append(_joined([l.astype(dt) for l in own] + [tail], dt))
        return GradSegments(tuple(out))

    def rebuild(self, flat32: jax.Array, half: Optional[jax.Array],
                like_leaves) -> Any:
        """Params tree from the updated flat fp32 buffer (+ optional half
        copy emitted by the kernel).  Non-float leaves pass through from
        ``like_leaves``; fp32 leaves slice from ``flat32``; half leaves
        slice from ``half`` when present (no extra cast pass)."""
        out = []
        for i, (shape, f) in enumerate(zip(self.shapes, self.is_float)):
            if not f:
                out.append(like_leaves[i])
                continue
            dt = jnp.dtype(self.dtypes[i])
            src = half if (half is not None and dt == half.dtype) else flat32
            piece = _cut(src, self.offsets[i], self.sizes[i], shape)
            if piece.dtype != dt:
                piece = piece.astype(dt)
            out.append(piece)
        return jax.tree_util.tree_unflatten(self.treedef, out)

    def unpack_masters(self, flat32: jax.Array) -> Any:
        """Masters as an fp32 tree (inspection / master_params parity).
        Non-float leaves have no master; they come back as None."""
        if self.zero_axis is not None:
            # the buffer holds only this device's shard: offsets past it
            # would clamp and silently return duplicated tail data
            raise RuntimeError(
                f"masters are ZeRO-sharded over axis {self.zero_axis!r}; "
                f"all_gather the buffer (axis=0, tiled=True) and slice "
                f"[:layout.total] before unpacking")
        out = []
        for i, (shape, f) in enumerate(zip(self.shapes, self.is_float)):
            if not f:
                out.append(None)
                continue
            out.append(_cut(flat32, self.offsets[i], self.sizes[i], shape))
        return jax.tree_util.tree_unflatten(self.treedef, out)


@jax.tree_util.register_pytree_node_class
class FlatMasters:
    """fp32 master weights as one persistent flat buffer + static layout.
    Being its own pytree node keeps the layout attached to the state (so a
    reused AmpOptimizer or a checkpoint round-trip stays self-describing)
    while jit sees a single array leaf."""

    def __init__(self, buf: jax.Array, layout: _FlatLayout):
        self.buf = buf
        self.layout = layout

    def tree_flatten(self):
        return (self.buf,), self.layout

    @classmethod
    def tree_unflatten(cls, layout, children):
        return cls(children[0], layout)

    def as_tree(self):
        return self.layout.unpack_masters(self.buf)


class AmpOptimizer(Optimizer):
    """Wraps a base Optimizer with loss scaling and optional fp32 masters."""

    def __init__(self, inner: Optimizer, scaler: LossScaler,
                 master_weights: bool, num_losses: int = 1):
        self.inner = inner
        self.scaler = scaler
        self.master_weights = bool(master_weights)
        self.num_losses = int(num_losses)
        # eager/stateful-mode fields (see amp/stateful.py)
        self._bound = None

    # -- functional API ----------------------------------------------------
    def init(self, params: Any, zero_axis: Optional[str] = None,
             zero_stage: int = 1, zero_ici_size: Optional[int] = None,
             zero_compress_bf16: bool = False) -> AmpOptState:
        """``zero_axis``: ZeRO — shard the fp32 masters and the inner
        optimizer's moments across the named DATA-parallel mesh axis.
        ``zero_stage`` picks how far the sharding goes:

        * 1 (default) — shard over the FULL axis: each device owns
          ``ceil(N/world)`` elements; the step reduce-scatters the
          un-reduced grads and all-gathers the updated params.
        * 2 — shard over the ICI slice (``zero_ici_size`` devices) of
          the hierarchical fabric: state is replicated across slices,
          grads are psum_scatter'd within the slice then DCN-reduced on
          the 1/ici shard, and the updated params are gathered back
          within the slice only (the DCN never carries params).
        * 3 — like 2 for grads, but the step never gathers params
          back: the fp32 master shard IS the parameter store, the
          forward regathers just-in-time via :func:`zero_gather_params`
          and the step receives the flat 1-D grad shard its transpose
          produces.  Requires every param leaf to be floating point.

        ``zero_compress_bf16`` (stages 2/3) quantizes only the DCN hop
        of the grad reduction to bf16 — same contract as DDP's
        ``allreduce_compress_bf16`` (fp32 accumulate, half wire).

        Must run inside shard_map with the axis mapped (it degrades to
        the full replicated state outside one); requires an elementwise
        inner optimizer + master weights (the flat path).  The matching
        step reduces the grads itself — do NOT pre-allreduce them with
        DDP."""
        if zero_axis is not None and _axis_in_scope(zero_axis):
            if not (self.master_weights
                    and getattr(self.inner, "elementwise", False)):
                raise ValueError(
                    "zero_axis requires master weights and an "
                    "elementwise inner optimizer (the flat-buffer path)")
            _validate_zero_knobs(zero_stage, zero_ici_size,
                                 zero_compress_bf16)
            layout = _FlatLayout(params, zero_axis, zero_stage,
                                 zero_ici_size, zero_compress_bf16)
            if zero_stage == 3 and not all(layout.is_float):
                raise ValueError(
                    "ZeRO-3 rebuilds every param from the flat fp32 "
                    "master shard; non-float leaves have no master "
                    "storage to regather from")
            dp = jax.lax.axis_size(zero_axis)
            if zero_stage >= 2:
                # validates world % ici == 0 (static) and pins the
                # slice geometry the step will reuse
                _zero_slice_groups(zero_axis, layout.zero_ici)
                shard_count = layout.zero_ici
                idx = jax.lax.axis_index(zero_axis) % shard_count
            else:
                shard_count = dp
                idx = jax.lax.axis_index(zero_axis)
            shard_n = -(-layout.total // shard_count)          # ceil
            full = jnp.pad(layout.pack(params),
                           (0, shard_n * shard_count - layout.total))
            shard = jax.lax.dynamic_slice_in_dim(full, idx * shard_n,
                                                 shard_n)
            masters = FlatMasters(shard, layout)
            inner_state = self.inner.init(shard)
            scalers = tuple(self.scaler.init_state()
                            for _ in range(self.num_losses))
            return AmpOptState(inner=inner_state, masters=masters,
                               scalers=scalers)
        if self.master_weights:
            if getattr(self.inner, "elementwise", False):
                # elementwise inner optimizers (SGD, FusedAdam) run on one
                # persistent flat fp32 buffer: no per-step tree pack/unpack
                layout = _FlatLayout(params)
                masters = FlatMasters(layout.pack(params), layout)
                inner_state = self.inner.init(masters.buf)
            else:
                # optimizers with per-tensor semantics (FusedLAMB trust
                # ratios) keep the master pytree
                masters = _to_fp32(params)
                inner_state = self.inner.init(masters)
        else:
            masters = None
            inner_state = self.inner.init(params)
        scalers = tuple(self.scaler.init_state()
                        for _ in range(self.num_losses))
        return AmpOptState(inner=inner_state, masters=masters,
                           scalers=scalers)

    def loss_scale(self, opt_state: AmpOptState, loss_id: int = 0):
        return opt_state.scalers[loss_id].loss_scale

    def step(self, params: Any = None, opt_state: AmpOptState = None,
             scaled_grads: Any = None, loss_id: int = 0,
             found_inf_extra: Optional[jax.Array] = None,
             found_inf_axes: Optional[Sequence[str]] = None,
             grad_health: Any = None
             ) -> Tuple[Any, AmpOptState, dict]:
        """Unscale grads, update the scaler, apply-or-skip the inner update.

        ``scaled_grads`` are gradients of ``loss * loss_scale`` w.r.t. the
        *model* params.  ``found_inf_extra`` lets callers merge additional
        overflow sources (e.g. a pre-computed grad norm).
        ``found_inf_axes``: mesh axes whose devices hold DISJOINT param
        shards (tensor/pipeline parallel) — the local overflow flag is
        pmax'd over them so every shard skips together and the loss
        scale stays in lockstep.  (A pure data axis doesn't need this:
        the pre-step gradient allreduce propagates inf to every
        replica.)  Axes not currently mapped are ignored, so the same
        step code runs inside and outside shard_map.
        Returns (new_params, new_opt_state, info).

        ``grad_health``: an enabled
        ``observability.numerics.NumericsMonitor`` built over the
        gradient tree — per-layer nonfinite/abs-max/norm/underflow
        stats (pure local jnp math on the pre-pack tree, at the
        scaler's CURRENT loss scale) come back as
        ``info["grad_health"]`` so a skipped step can name the culprit
        layer instead of just counting the skip.  ``None`` (or a
        disabled monitor) computes nothing and leaves the traced graph
        byte-identical — the key is simply absent from ``info``.

        Called with no arguments in eager mode (after amp.stateful.bind +
        scale_loss/backward), it steps the bound state like torch's
        ``optimizer.step()``.
        """
        if params is None:
            if self._bound is None:
                raise RuntimeError("step() without arguments requires a "
                                   "bound optimizer (amp.stateful.bind)")
            return self._bound.step()
        sstate = opt_state.scalers[loss_id]
        health_stats = None
        if grad_health is not None and getattr(grad_health, "enabled",
                                               True):
            # on the tree, BEFORE the flat-buffer pack: per-layer
            # boundaries only exist here, and the stats are what the
            # overflow attribution and underflow accounting read
            health_stats = grad_health.leaf_stats(scaled_grads,
                                                  sstate.loss_scale)
        flat = isinstance(opt_state.masters, FlatMasters)
        zaxis = (opt_state.masters.layout.zero_axis
                 if flat else None)
        zero = zaxis is not None and _axis_in_scope(zaxis)
        if zaxis is not None and not zero:
            # falling through to the plain flat path would apply
            # UN-reduced grads element-misaligned against the
            # device-concat shard buffer — silent corruption when the
            # sizes happen to line up, an opaque shape error when not
            raise RuntimeError(
                f"optimizer state is ZeRO-sharded over axis {zaxis!r} "
                f"but step() was called outside a shard_map mapping it")
        # what the code can observe chooses the gradient's path: an inner
        # optimizer that unscales in its kernel, the un-sharded layout
        # with its leaves by dtype
        in_kernel = (flat and zaxis is None
                     and opt_state.masters.layout.order == "dtype"
                     and getattr(self.inner, "unscales_grads", False))
        count_unscale("kernel" if in_kernel else "pass")
        zstage = (opt_state.masters.layout.zero_stage if zero else 1)
        zero_groups = (_zero_slice_groups(
            zaxis, opt_state.masters.layout.zero_ici)
            if zero and zstage >= 2 else None)
        if zstage == 3 and zero:
            # the gather transpose hands back the flat in-slice-summed
            # grad SHARD (possibly still wrapped in the FlatMasters
            # pytree scaled_grad differentiated through)
            if isinstance(scaled_grads, FlatMasters):
                scaled_grads = scaled_grads.buf
            if (getattr(scaled_grads, "ndim", None) != 1
                    or scaled_grads.shape
                    != opt_state.masters.buf.shape):
                raise ValueError(
                    f"ZeRO-3 step expects the flat grad shard the "
                    f"zero_gather_params transpose produces "
                    f"(shape {opt_state.masters.buf.shape}), got "
                    f"{getattr(scaled_grads, 'shape', type(scaled_grads))}")
        elif in_kernel:
            # the gradient reaches the kernel as the backward wrote it:
            # read once here for the finite flag, copied once, in its own
            # dtype, into the kernel's operand, unscaled and widened in
            # the kernel's registers
            with jax.named_scope("amp.pack"):
                grads32 = opt_state.masters.layout.pack_grads(scaled_grads)
            with jax.named_scope("amp.unscale"):
                found_inf = _found_nonfinite(grads32)
        elif flat:
            # fused-buffer hot path: one concat, one fused unscale, one
            # optimizer kernel, static slices back out
            with jax.named_scope("amp.pack"):
                scaled_grads = opt_state.masters.layout.pack(scaled_grads)
        if zero:
            layout = opt_state.masters.layout
            dp = jax.lax.axis_size(zaxis)
            shard_n = opt_state.masters.buf.shape[0]
            if zstage >= 2:
                # ZeRO-2/3: two-level reduce mirroring the DDP
                # hierarchical path — psum_scatter within the ICI slice
                # lands the 1/ici shard, the DCN hop reduces only that
                # shard (optionally as a bf16 all_gather + fp32 local
                # sum), and unlike DDP there is no gather-back: the
                # shard is exactly what the local optimizer state needs
                ici_groups, dcn_groups = zero_groups
                if zstage == 2:
                    scaled_grads = jnp.pad(
                        scaled_grads,
                        (0, shard_n * layout.zero_ici - layout.total))
                    scaled_grads = jax.lax.psum_scatter(
                        scaled_grads, zaxis, scatter_dimension=0,
                        axis_index_groups=ici_groups, tiled=True)
                # stage 3 grads arrive already in-slice summed (the
                # all_gather transpose is exactly that psum_scatter)
                if layout.zero_compress:
                    q = scaled_grads.astype(jnp.bfloat16)
                    wire = jax.lax.all_gather(
                        q, zaxis, axis_index_groups=dcn_groups)
                    scaled_grads = jnp.sum(
                        wire.astype(jnp.float32), axis=0)
                else:
                    scaled_grads = jax.lax.psum(
                        scaled_grads, zaxis,
                        axis_index_groups=dcn_groups)
            else:
                # ZeRO-1: reduce-scatter the UN-reduced local grads —
                # each device receives the summed grads for exactly its
                # master shard (the psum+slice DDP would do, in one
                # collective), then averages like gradient_average
                scaled_grads = jnp.pad(
                    scaled_grads, (0, shard_n * dp - layout.total))
                scaled_grads = jax.lax.psum_scatter(
                    scaled_grads, zaxis, scatter_dimension=0, tiled=True)
            scaled_grads = scaled_grads / dp
        if not in_kernel:
            with jax.named_scope("amp.unscale"):
                grads32, found_inf = self.scaler.unscale(scaled_grads,
                                                         sstate)
        if found_inf_extra is not None:
            found_inf = jnp.maximum(found_inf, found_inf_extra)
        if zero:
            # each device saw only its grad window: the skip decision
            # must be global or shards diverge
            found_inf = jax.lax.pmax(found_inf, zaxis)
        for ax in (found_inf_axes or ()):
            if _axis_in_scope(ax):
                found_inf = jax.lax.pmax(found_inf, ax)
        with jax.named_scope("amp.scaler_update"):
            new_sstate = self.scaler.update(sstate, found_inf)
        scalers = tuple(new_sstate if i == loss_id else s
                        for i, s in enumerate(opt_state.scalers))

        if zero and zstage == 3:
            def do_update(operand):
                p, masters, inner = operand
                # the master shard IS the parameter store: update it in
                # place, no half copy, no gather-back — the next
                # forward's zero_gather_params reads the new shard
                new_buf, new_inner = self.inner.update(
                    grads32, inner, masters.buf)
                return p, FlatMasters(new_buf, masters.layout), new_inner
        elif zero:
            gather_groups = zero_groups[0] if zstage == 2 else None

            def do_update(operand):
                p, masters, inner = operand
                layout = masters.layout
                new_buf, new_inner, half = self._flat_inner_step(
                    masters, inner, grads32)
                # params are replicated: gather every shard's update
                # (stage 2: within the ICI slice only — cross-slice
                # shards are bitwise equal after the DCN grad reduce).
                # rebuild reads full32 only for fp32 float leaves — skip
                # that gather (the biggest collective here) when every
                # float leaf has the half dtype
                any_fp32 = any(f and d == "float32" for f, d in
                               zip(layout.is_float, layout.dtypes))
                full32 = (jax.lax.all_gather(
                    new_buf, zaxis, axis=0, tiled=True,
                    axis_index_groups=gather_groups)[:layout.total]
                    if any_fp32 or half is None else None)
                full_half = (jax.lax.all_gather(
                    half, zaxis, axis=0, tiled=True,
                    axis_index_groups=gather_groups)[:layout.total]
                    if half is not None else None)
                with jax.named_scope("amp.rebuild"):
                    new_p = layout.rebuild(full32, full_half,
                                           jax.tree_util.tree_leaves(p))
                return new_p, FlatMasters(new_buf, layout), new_inner
        elif flat:
            def do_update(operand):
                p, masters, inner = operand
                new_buf, new_inner, half = self._flat_inner_step(
                    masters, inner, grads32,
                    sstate.loss_scale if in_kernel else None)
                with jax.named_scope("amp.rebuild"):
                    new_p = masters.layout.rebuild(
                        new_buf, half, jax.tree_util.tree_leaves(p))
                return new_p, FlatMasters(new_buf, masters.layout), new_inner
        elif opt_state.masters is not None:
            def do_update(operand):
                p, masters, inner = operand
                new_masters, new_inner = self.inner.update(
                    grads32, inner, masters)
                # master -> model copy (the reference's
                # _master_params_to_model_params, _process_optimizer.py:242-253)
                with jax.named_scope("amp.rebuild"):
                    new_p = _cast_like(new_masters, p)
                return new_p, new_masters, new_inner
        else:
            def do_update(operand):
                p, masters, inner = operand
                new_p, new_inner = self.inner.update(
                    _cast_like(grads32, p), inner, p)
                return new_p, masters, new_inner

        def skip_update(operand):
            return operand

        with jax.named_scope("amp.update"):
            new_params, new_masters, new_inner = jax.lax.cond(
                found_inf > 0, skip_update, do_update,
                (params, opt_state.masters, opt_state.inner))

        from ..optimizers.base import global_grad_norm
        # grad-norm gauge (observability): the unscaled fp32 grads are
        # already in hand (flat buffer on the fused path), so the norm is
        # one reduction; callers that drop it from the step's outputs get
        # it DCE'd — no cost unless consumed.  Under ZeRO each device
        # holds a disjoint grad window, so the squared sums psum to the
        # global norm (the pad elements are zero).
        with jax.named_scope("amp.grad_norm"):
            if zero and zstage >= 2:
                # windows are disjoint within the slice but REPLICATED
                # across slices (post-DCN grads are identical): a
                # full-axis psum would overcount by dcn_size
                grad_norm = jnp.sqrt(jax.lax.psum(
                    jnp.sum(jnp.square(grads32)), zaxis,
                    axis_index_groups=zero_groups[0]))
            elif zero:
                grad_norm = jnp.sqrt(jax.lax.psum(
                    jnp.sum(jnp.square(grads32)), zaxis))
            else:
                grad_norm = global_grad_norm(grads32)
                if in_kernel:
                    grad_norm = grad_norm * (1.0 / sstate.loss_scale)
        info = {"found_inf": found_inf,
                "loss_scale": new_sstate.loss_scale,
                "steps_skipped": new_sstate.steps_skipped,
                "grad_norm": grad_norm}
        if health_stats is not None:
            info["grad_health"] = health_stats
        return new_params, AmpOptState(inner=new_inner, masters=new_masters,
                                       scalers=scalers), info

    def _flat_inner_step(self, masters: FlatMasters, inner_state, flat_g32,
                         scale=None):
        """Inner update on the flat master buffer.  When the inner
        optimizer can emit the half model copy inside its kernel (FusedAdam
        output_params_dtype, reference fused_adam_cuda_kernel.cu:94-115)
        that saves the separate cast pass; otherwise one astype.  With
        ``scale`` the gradient is ``GradSegments``, still scaled, for an
        inner optimizer with ``unscales_grads``."""
        half_dtype = masters.layout.half_dtype
        if scale is not None:
            out = self.inner.step(masters.buf, inner_state, flat_g32,
                                  scale=scale,
                                  output_params_dtype=half_dtype)
            return out[0], out[1], (out[2] if half_dtype is not None
                                    else None)
        if (half_dtype is not None
                and getattr(self.inner, "supports_output_params_dtype",
                            False)):
            new_buf, new_inner, half = self.inner.step(
                masters.buf, inner_state, flat_g32,
                output_params_dtype=half_dtype)
            return new_buf, new_inner, half
        new_buf, new_inner = self.inner.update(flat_g32, inner_state,
                                               masters.buf)
        half = (new_buf.astype(half_dtype) if half_dtype is not None
                else None)
        return new_buf, new_inner, half

    def masters_tree(self, opt_state: AmpOptState) -> Any:
        """Masters as a params-shaped fp32 tree, whatever the internal
        representation."""
        m = opt_state.masters
        return m.as_tree() if isinstance(m, FlatMasters) else m

    # -- checkpoint (the amp.state_dict gap called out in SURVEY §5) -------
    def state_dict(self, opt_state: AmpOptState) -> dict:
        return {"scalers": [s._asdict() for s in opt_state.scalers]}

    def load_state_dict(self, opt_state: AmpOptState, sd: dict) -> AmpOptState:
        scalers = tuple(ScalerState(**{k: jnp.asarray(v) for k, v in d.items()})
                        for d in sd["scalers"])
        return opt_state._replace(scalers=scalers)

    # -- stateful-mode conveniences (amp/stateful.py fills these in) -------
    @property
    def masters(self):
        if self._bound is None:
            return None
        return self._bound.opt_state.masters
