"""DistributedDataParallel for device meshes.

The reference DDP (apex/parallel/distributed.py:129-512) overlaps NCCL
allreduce with backward by hooking per-param grad accumulators, assembling
flat dtype-split buckets in backward arrival order, and draining them on a
dedicated reduction stream.  On TPU/XLA none of that machinery is needed or
desirable (SURVEY.md §7 hard parts): collectives are compiler-scheduled, so
overlap comes from XLA's latency-hiding scheduler.  What *is* preserved is
every observable option of the reference wrapper:

- ``message_size``        — bucket granularity (elements) for chunked psum,
                            letting XLA interleave collectives with the
                            backward's tail (distributed.py:162-171),
- ``delay_allreduce``     — one fused allreduce after backward (:148-158),
- ``allreduce_always_fp32`` — upcast half grads before the collective
                            (:383-396),
- ``gradient_average``    — divide by world size after (:391-393),
- ``gradient_predivide_factor`` — pre/post divide split for fp16 range
                            control (:386-393),
- ``retain_allreduce_buffers`` — expose the flat reduced buckets.

Beyond the reference, ``comm_topology=`` makes the allreduce
topology-aware: ``"hierarchical"`` reduce-scatters each bucket within
the ICI slice, crosses DCN on the 1/ici_size shard, and all_gathers
back (arXiv:2004.13336's placement applied to the ICI/DCN split), with
optional bf16 compression of the DCN hop
(``allreduce_compress_bf16=``); ``"auto"`` engages it when the data
axis spans processes.  See docs/parallel.md §Topology-aware gradient
communication.

Usage inside a shard_map/pmap'd step over axis ``data``::

    ddp = DistributedDataParallel(model)          # wrapper parity
    ...
    grads = ddp.allreduce_grads(grads)            # inside the mapped fn

or functionally via ``allreduce_grads_tree(grads, axis_name='data')``.
``DistributedDataParallel.make_step`` builds a whole shard_map'd train step
over a 1-D mesh for the common data-parallel case.
"""

from __future__ import annotations

import math
from collections import Counter
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from . import topology as _topology

__all__ = ["DistributedDataParallel", "Reducer", "allreduce_grads_tree",
           "allreduce_comm_plan", "plan_collective_expectations",
           "plan_resharding_expectations", "zero_update_comm_plan",
           "predivide_factors", "flat_dist_call", "staged_grads",
           "overlap_comm_schedule", "overlap_collective_expectations",
           "OVERLAP_MODES"]

# where the gradient bytes travel: "flat" is one psum over the whole
# axis (every byte crosses the slowest link in it), "hierarchical" is
# psum_scatter within the ICI slice -> cross-slice reduce over DCN on
# the 1/ici shard -> in-slice all_gather (arXiv:2004.13336's
# reduce-scatter placement applied to the ICI/DCN split), "auto" picks
# per topology.auto_comm_topology (hierarchical iff the axis spans
# processes).
COMM_TOPOLOGIES = ("flat", "hierarchical", "auto")

# when the gradient bytes travel, relative to the backward that makes
# them: "reduce_after_backward" is the classic schedule (every bucket's
# collective trails the whole backward — today's measured
# overlap_fraction ~ 0.0 baseline), "overlapped" is the staged schedule
# where bucket i's reduction is ISSUED while bucket i-1's gradients are
# still being computed (the reference DDP's arrival-order bucket drain,
# expressed as jaxpr program order so XLA's latency-hiding scheduler —
# and the collective lint rule — can see it).
OVERLAP_MODES = ("overlapped", "reduce_after_backward")


def _axis_size(axis_name: str) -> jax.Array:
    return lax.psum(jnp.ones((), jnp.float32), axis_name)


def predivide_factors(world, gradient_predivide_factor: float = 1.0):
    """The reference's pre/post division split (distributed.py:386-393)
    in ONE audited place: gradients are divided by ``pre`` BEFORE the
    collective (fp16 range control) and by ``post`` after it when
    ``gradient_average`` is on, with ``pre * post == world`` by
    construction — the mean is taken exactly once, no matter how the
    split is chosen, whether the reduction runs over the full axis or
    ``axis_index_groups`` (``world`` is the *averaging* population:
    group size when grouped), or how many fabric levels carry the sum
    (the hierarchical path divides once on the final result, never
    per level)."""
    f = float(gradient_predivide_factor)
    if f == 1.0:
        return 1.0, world
    return f, world / f


def _validate_topology_knobs(comm_topology: str,
                             allreduce_compress_bf16: bool):
    """The one place the knob rules live — shared by the runtime, the
    static plan, and the DDP constructor (which validates eagerly so a
    typo fails at construction, not at first trace).  Explicit ``flat``
    + compression is rejected: there is no inner level to keep full
    precision, quantizing the only collective would just lose bits."""
    if comm_topology not in COMM_TOPOLOGIES:
        raise ValueError(
            f"comm_topology must be one of {COMM_TOPOLOGIES}, got "
            f"{comm_topology!r}")
    if allreduce_compress_bf16 and comm_topology == "flat":
        raise ValueError(
            "allreduce_compress_bf16 compresses the DCN hop of the "
            "hierarchical reduction; comm_topology='flat' has no inner "
            "level to keep full precision (use 'hierarchical' or "
            "'auto')")


def _resolve_topology(comm_topology: str, allreduce_compress_bf16: bool,
                      nproc: Optional[int] = None):
    """Validate the knobs and return ``(topology, compress)`` with
    ``auto`` resolved.  ``auto`` that resolves to flat drops
    compression silently, since a single-process axis has no DCN hop
    to shrink."""
    _validate_topology_knobs(comm_topology, allreduce_compress_bf16)
    topo = comm_topology
    if topo == "auto":
        topo = _topology.auto_comm_topology(nproc)
    return topo, (allreduce_compress_bf16 and topo == "hierarchical")


def _bucket_wire_accounting(n: int, comm_dt, topo: str, ici: int,
                            compress: bool, message_size: int,
                            delay_allreduce: bool, triggered: bool
                            ) -> Dict[str, Any]:
    """Per-bucket on-wire accounting, shared by the runtime
    ``comm_stats`` records and the static :func:`allreduce_comm_plan`
    so the two can never disagree.  All byte counts are TRUE wire
    bytes — chunk/shard padding included — and match what
    ``analysis.eqn_payload_bytes`` reads off the traced collectives:

    - flat: one psum; ``chunked`` pads to ``chunks * message_size``.
    - hierarchical: one ``reduce_scatter`` (full padded bucket, ICI),
      the DCN reduce on the 1/ici shard (a psum, or a bf16 all_gather
      when compressed), and the in-slice ``all_gather`` back.

    ``ici_wire_bytes`` / ``dcn_wire_bytes`` split the total by fabric
    level; for flat both equal the full payload (a flat psum over a
    DCN-spanning axis drags every byte across the slow link — the
    asymmetry the hierarchical path exists to fix)."""
    isz = jnp.dtype(comm_dt).itemsize
    if topo == "hierarchical":
        cause = ("trigger" if triggered else
                 "delay" if delay_allreduce else "single")
        n_pad = n + ((-n) % ici)
        m = n_pad // ici
        dcn_dt = jnp.dtype(jnp.bfloat16) if compress else jnp.dtype(comm_dt)
        dcn_bytes = m * dcn_dt.itemsize
        ici_bytes = n_pad * isz + m * isz        # scatter + gather back
        eqns = {"reduce_scatter": 1,
                "all_gather": 2 if compress else 1}
        payload = {"reduce_scatter": n_pad * isz,
                   "all_gather": m * isz + (dcn_bytes if compress else 0)}
        if not compress:
            eqns["psum"] = 1
            payload["psum"] = dcn_bytes
        return {"cause": cause, "chunks": 1, "topology": "hierarchical",
                "wire_elements": n_pad, "padded_elements": n_pad - n,
                "bytes": ici_bytes + dcn_bytes,
                "ici_wire_bytes": ici_bytes, "dcn_wire_bytes": dcn_bytes,
                "dcn_comm_dtype": str(dcn_dt),
                "eqns": eqns, "eqn_payload_bytes": payload}
    if delay_allreduce or triggered or n <= message_size:
        cause = ("trigger" if triggered
                 else "delay" if delay_allreduce else "single")
        chunks, wire = 1, n
    else:
        cause = "chunked"
        chunks = math.ceil(n / message_size)
        wire = chunks * message_size
    b = wire * isz
    return {"cause": cause, "chunks": chunks, "topology": "flat",
            "wire_elements": wire, "padded_elements": wire - n,
            "bytes": b, "ici_wire_bytes": b, "dcn_wire_bytes": b,
            "dcn_comm_dtype": str(jnp.dtype(comm_dt)),
            "eqns": {"psum": 1}, "eqn_payload_bytes": {"psum": b}}


def _hierarchical_reduce(comm: jax.Array, axis_name: str,
                         ici_groups, dcn_groups,
                         compress: bool, want_error: bool = False
                         ) -> Tuple[jax.Array, Optional[jax.Array]]:
    """Two-level sum of one flat bucket: ``psum_scatter`` within the
    ICI slice (the fast fabric carries the full payload and does the
    wide accumulation), cross-slice reduce over DCN on the 1/ici
    shard, in-slice ``all_gather`` back.  ``compress=True`` quantizes
    ONLY the DCN hop to bf16 and reduces it as all_gather + local sum
    in the communication dtype — the wire is half, the accumulation
    is not (the fp32-accumulate contract of allreduce_always_fp32
    survives compression).

    Returns ``(reduced, compression_sq_error)``: with ``want_error``
    (numerics observability, PR 9) the second element is the squared
    quantization error of THIS replica's own 1/ici shard on the bf16
    DCN hop — local elementwise math, no extra collectives, and
    ``None`` otherwise so the uninstrumented graph is unchanged."""
    n = comm.shape[0]
    shard, err = _hier_scatter_reduce(comm, axis_name, ici_groups,
                                      dcn_groups, compress, want_error)
    return _hier_gather(shard, axis_name, ici_groups, n), err


def _hier_scatter_reduce(comm: jax.Array, axis_name: str,
                         ici_groups, dcn_groups, compress: bool,
                         want_error: bool = False
                         ) -> Tuple[jax.Array, Optional[jax.Array]]:
    """The scatter half of :func:`_hierarchical_reduce`: pad to the
    slice size, ``psum_scatter`` within ICI, DCN-reduce the 1/ici
    shard — and STOP.  This is exactly the ZeRO-2 gradient reduction
    (arXiv:2004.13336's reduce-scatter placement with the gather-back
    deleted): the caller that owns only the matching 1/ici optimizer
    shard never needs the full gradient, so the in-slice all_gather of
    grads is replaced by an all_gather of *updated params* after the
    shard update (:func:`_hier_gather`, same payload, same fabric
    level)."""
    ici = len(ici_groups[0])
    pad = (-comm.shape[0]) % ici
    if pad:
        comm = jnp.pad(comm, (0, pad))
    shard = lax.psum_scatter(comm, axis_name, scatter_dimension=0,
                             axis_index_groups=ici_groups, tiled=True)
    err = None
    if compress:
        q = shard.astype(jnp.bfloat16)
        if want_error:
            d = (shard.astype(jnp.float32)
                 - q.astype(jnp.float32))
            err = jnp.sum(d * d)
        wire = lax.all_gather(q, axis_name,
                              axis_index_groups=dcn_groups)
        shard = jnp.sum(wire.astype(shard.dtype), axis=0)
    else:
        shard = lax.psum(shard, axis_name, axis_index_groups=dcn_groups)
    return shard, err


def _hier_gather(shard: jax.Array, axis_name: str, ici_groups,
                 n: int) -> jax.Array:
    """The gather half: in-slice ``all_gather`` of a 1/ici shard back
    to the full (unpadded) buffer."""
    full = lax.all_gather(shard, axis_name,
                          axis_index_groups=ici_groups, tiled=True)
    return full[:n] if full.shape[0] != n else full


def _path_str(path) -> str:
    """'/'-joined readable key path for a tree_flatten_with_path entry."""
    parts = []
    for k in path:
        if hasattr(k, "key"):
            parts.append(str(k.key))
        elif hasattr(k, "idx"):
            parts.append(str(k.idx))
        else:
            parts.append(str(k))
    return "/".join(parts)


def allreduce_grads_tree(grads: Any, axis_name: str = "data",
                         message_size: int = 10_000_000,
                         allreduce_always_fp32: bool = False,
                         gradient_average: bool = True,
                         gradient_predivide_factor: float = 1.0,
                         delay_allreduce: bool = False,
                         axis_index_groups: Optional[List[List[int]]] = None,
                         retain_buffers: Optional[list] = None,
                         trigger_paths: Optional[set] = None,
                         comm_stats: Optional[list] = None,
                         comm_topology: str = "flat",
                         allreduce_compress_bf16: bool = False,
                         ici_size: Optional[int] = None,
                         numerics_out: Optional[list] = None,
                         world_scalar: Optional[jax.Array] = None) -> Any:
    """Bucketed gradient allreduce with the reference's semantics
    (allreduce_bucket, distributed.py:378-398).  Must run inside a context
    where ``axis_name`` is a mapped mesh axis.

    ``trigger_paths``: the reference's ``allreduce_trigger_params``
    (distributed.py:162-171) — user-chosen params whose grad readiness
    fires a bucket flush, overriding message_size.  Arrival order doesn't
    exist under XLA, so the faithful mapping is: the listed leaves mark
    *bucket boundaries* in tree order; each bucket is one psum the
    scheduler can overlap independently.  Paths are '/'-joined key paths
    (e.g. 'layer1/conv/weight'); unknown paths raise.

    ``comm_topology``: where the bytes travel.  ``"flat"`` (default)
    reduces every bucket with one psum over the whole axis — on a
    multi-host mesh that drags the full payload across DCN, the slowest
    link.  ``"hierarchical"`` runs each bucket as psum_scatter within
    the ICI slice, a cross-slice reduce over DCN on the 1/ici_size
    shard, and an in-slice all_gather back — DCN carries 1/ici_size of
    the traffic, the sum is unchanged up to reduction-order round-off
    (pinned in tests/test_ddp.py like the ZeRO-1 psum_scatter-vs-psum
    ordering).  ``"auto"`` picks hierarchical iff the axis spans
    processes (topology.auto_comm_topology).  ``ici_size`` is the
    inner-level width (consecutive ranks per slice, make_mesh's
    multi-host ordering); it defaults to axis_size / process_count.
    Hierarchical within explicit ``axis_index_groups`` is not wired.
    ``message_size`` does NOT sub-chunk hierarchical buckets: each
    bucket is one reduce_scatter whose per-member shards XLA already
    schedules independently — the in-bucket psum chunking is a
    flat-path overlap device (its ``chunked`` cause never appears
    under hierarchical; bucket *boundaries* from triggers/dtypes still
    apply).

    ``allreduce_compress_bf16``: quantize the DCN hop to bf16 — on-wire
    payload halves; the ICI reduce-scatter and the per-slice
    accumulation stay in the communication dtype, so it composes with
    ``allreduce_always_fp32`` (fp32 adds, bf16 wire).  Hierarchical
    only.

    ``comm_stats``: observability out-param — one dict per reduced
    bucket ({dtype, comm_dtype, leaves, elements, bytes, cause, chunks,
    topology, wire_elements, padded_elements, ici_wire_bytes,
    dcn_wire_bytes, ...}) appended at TRACE time (like
    ``retain_buffers``), i.e. once per compiled step, describing what
    every execution of that step communicates.  ``bytes`` is true
    on-wire traffic (chunk/shard padding included, all levels summed);
    ``cause`` records why the bucket flushed: a trigger boundary,
    ``delay_allreduce``, fitting under ``message_size`` (``single``),
    or the chunked-psum path.

    ``numerics_out``: numerics observability out-param (PR 9) — one
    dict per bucket, in the same order as the comm plan, carrying the
    static bucket identity plus DEVICE scalars (``nonfinite`` /
    ``abs_max`` / ``sq_sum`` of the pre-divide communication buffer,
    and ``compression_sq_error`` of this replica's shard on the bf16
    DCN hop when compressed).  Unlike ``comm_stats`` these are traced
    values: thread them into the step carry in the SAME trace (e.g.
    ``NumericsMonitor.update(bucket_stats=...)``).  All stats are
    local elementwise math — the collective census and host-transfer
    audit of the step are unchanged.

    ``world_scalar``: the traced axis-size scalar to average by,
    computed ONCE by a caller that reduces several stage subtrees in
    one step (``DistributedDataParallel.staged_allreduce_grads``) —
    without it every per-stage call would psum its own 4-byte scalar
    and the step's collective census would grow by the stage count.
    ``None`` (the default) keeps the classic behavior: this call psums
    the scalar itself."""
    leaves, treedef = jax.tree_util.tree_flatten(grads)
    if not leaves:
        return grads
    topo, compress = _resolve_topology(comm_topology,
                                       allreduce_compress_bf16)
    ici_groups = dcn_groups = None
    ici = 1
    if topo == "hierarchical":
        if axis_index_groups is not None:
            raise NotImplementedError(
                "comm_topology='hierarchical' over explicit "
                "axis_index_groups is not wired — the hierarchy defines "
                "its own ICI/DCN groups")
        world_static = int(lax.axis_size(axis_name))
        ici = (int(ici_size) if ici_size is not None
               else _topology.default_ici_size(world_static))
        ici_groups, dcn_groups = _topology.hierarchical_axis_groups(
            world_static, ici)
    paths = None
    if trigger_paths:
        flat_paths = jax.tree_util.tree_flatten_with_path(grads)[0]
        paths = [_path_str(p) for p, _ in flat_paths]
        unknown = set(trigger_paths) - set(paths)
        if unknown:
            raise ValueError(
                f"allreduce_trigger_params paths not found in the gradient "
                f"tree: {sorted(unknown)}; available: {paths[:8]}...")

    # dtype-split buckets, like split_half_float_double (distributed.py:51-58)
    groups: Dict[Any, List[int]] = {}
    for i, g in enumerate(leaves):
        groups.setdefault(jnp.dtype(g.dtype), []).append(i)

    world = world_scalar if world_scalar is not None \
        else _axis_size(axis_name)
    if axis_index_groups is not None:
        world = jnp.asarray(float(len(axis_index_groups[0])), jnp.float32)

    new_leaves: List[Any] = [None] * len(leaves)
    for dt, idxs in groups.items():
        # trigger params split the group into separately-reduced buckets
        if trigger_paths:
            buckets, cur = [], []
            for i in idxs:
                cur.append(i)
                if paths[i] in trigger_paths:
                    buckets.append(cur)
                    cur = []
            if cur:
                buckets.append(cur)
        else:
            buckets = [idxs]

        for bucket in buckets:
            with jax.named_scope("ddp.pack"):
                flat = jnp.concatenate(
                    [leaves[i].reshape(-1) for i in bucket])
                comm = (flat.astype(jnp.float32) if allreduce_always_fp32
                        else flat)
            nstat = None
            if numerics_out is not None:
                # bucket health on the pre-divide comm buffer: what
                # actually goes on the wire, before the predivide
                # shifts magnitudes.  Nonfinite masked out of the
                # magnitude stats so one inf doesn't erase them.
                x = comm.astype(jnp.float32)
                fin = jnp.isfinite(x)
                ax = jnp.abs(jnp.where(fin, x, 0.0))
                nstat = {"dtype": str(dt),
                         "comm_dtype": str(comm.dtype),
                         "leaves": len(bucket),
                         "elements": int(flat.shape[0]),
                         "nonfinite": jnp.sum(~fin).astype(jnp.float32),
                         "abs_max": jnp.max(ax, initial=0.0),
                         "sq_sum": jnp.sum(ax * ax)}
            pre, post = predivide_factors(world,
                                          gradient_predivide_factor)
            if pre != 1.0:
                with jax.named_scope("ddp.pack"):
                    comm = comm / jnp.asarray(pre, comm.dtype)

            n = comm.shape[0]
            acct = _bucket_wire_accounting(
                n, comm.dtype, topo, ici, compress, message_size,
                delay_allreduce, bool(trigger_paths))
            with jax.named_scope("ddp.reduce"):
                if topo == "hierarchical":
                    reduced, comp_err = _hierarchical_reduce(
                        comm, axis_name, ici_groups, dcn_groups, compress,
                        want_error=numerics_out is not None)
                    if nstat is not None and comp_err is not None:
                        nstat["compression_sq_error"] = comp_err
                elif acct["chunks"] == 1:
                    reduced = lax.psum(comm, axis_name,
                                       axis_index_groups=axis_index_groups)
                else:
                    # chunked psum: XLA schedules the pieces
                    # independently — the compiler-native form of the
                    # reference's bucket overlap
                    nchunks = acct["chunks"]
                    pad = nchunks * message_size - n
                    padded = jnp.pad(comm, (0, pad))
                    chunks = padded.reshape(nchunks, message_size)
                    reduced = lax.psum(chunks, axis_name,
                                       axis_index_groups=axis_index_groups)
                    reduced = reduced.reshape(-1)[:n]

            if comm_stats is not None:
                comm_stats.append({
                    "dtype": str(dt), "comm_dtype": str(comm.dtype),
                    "leaves": len(bucket), "elements": int(n),
                    **{k: v for k, v in acct.items()
                       if k not in ("eqns", "eqn_payload_bytes")}})
            if nstat is not None:
                numerics_out.append(nstat)

            with jax.named_scope("ddp.unpack"):
                if gradient_average:
                    reduced = reduced / post.astype(reduced.dtype)
                reduced = reduced.astype(dt)
                if retain_buffers is not None:
                    retain_buffers.append(reduced)
                off = 0
                for i in bucket:
                    sz = leaves[i].size
                    new_leaves[i] = reduced[off:off + sz].reshape(
                        leaves[i].shape)
                    off += sz
    return jax.tree_util.tree_unflatten(treedef, new_leaves)


def allreduce_comm_plan(grads: Any, message_size: int = 10_000_000,
                        allreduce_always_fp32: bool = False,
                        delay_allreduce: bool = False,
                        trigger_paths: Optional[set] = None,
                        comm_topology: str = "flat",
                        allreduce_compress_bf16: bool = False,
                        ici_size: Optional[int] = None,
                        world: Optional[int] = None,
                        nproc: Optional[int] = None) -> List[dict]:
    """Static twin of :func:`allreduce_grads_tree`'s bucketing: what the
    comm pattern of one allreduce WILL be, computed from shapes alone
    (no tracing).  One dict per bucket::

        {dtype, comm_dtype, leaves, elements, chunks, cause, topology,
         ici_size, dcn_size, wire_elements, padded_elements, wire_bytes,
         ici_wire_bytes, dcn_wire_bytes, dcn_comm_dtype,
         eqns, eqn_payload_bytes}

    ``wire_elements`` includes chunk/shard padding — the elements the
    bucket's first collective actually moves per replica; ``wire_bytes``
    is the TRUE total on-wire traffic summed over every fabric level
    (for the flat topology that is the one psum; for the hierarchical
    topology the ICI reduce_scatter + the DCN reduce + the ICI
    all_gather), split per level as ``ici_wire_bytes`` /
    ``dcn_wire_bytes``.  ``eqns`` / ``eqn_payload_bytes`` give the
    exact per-primitive collective census of the bucket, matching what
    ``analysis.eqn_payload_bytes`` reads off the traced graph.
    ``apex_tpu.analysis``'s collective-accounting rule derives its DDP
    expectations from this plan (see
    :func:`plan_collective_expectations`): if the bucketing or topology
    algorithm changes, the plan and the traced graph move together,
    while an accidental extra/missing/fatter collective still flags.

    The topology knobs mirror the runtime: for ``"hierarchical"`` (or
    ``"auto"`` resolving there — ``nproc`` defaults to
    ``jax.process_count()``) the static axis size must be supplied as
    ``world=`` since there is no mapped axis to read it from."""
    leaves = jax.tree_util.tree_leaves(grads)
    plan: List[dict] = []
    if not leaves:
        return plan
    topo, compress = _resolve_topology(comm_topology,
                                       allreduce_compress_bf16, nproc)
    ici = dcn = 1
    if topo == "hierarchical":
        if world is None:
            raise ValueError(
                "a hierarchical comm plan needs world= (the static "
                "axis size); the runtime reads it from the mapped axis")
        ici = (int(ici_size) if ici_size is not None
               else _topology.default_ici_size(int(world), nproc))
        # validates divisibility the same way the runtime does
        _topology.hierarchical_axis_groups(int(world), ici)
        dcn = int(world) // ici
    paths = None
    if trigger_paths:
        flat_paths = jax.tree_util.tree_flatten_with_path(grads)[0]
        paths = [_path_str(p) for p, _ in flat_paths]
        unknown = set(trigger_paths) - set(paths)
        if unknown:
            # mirror allreduce_grads_tree: a plan for a comm pattern
            # the real step would refuse to trace is not a plan
            raise ValueError(
                f"allreduce_trigger_params paths not found in the "
                f"gradient tree: {sorted(unknown)}; available: "
                f"{paths[:8]}...")

    groups: Dict[Any, List[int]] = {}
    for i, g in enumerate(leaves):
        groups.setdefault(jnp.dtype(g.dtype), []).append(i)

    for dt, idxs in groups.items():
        if trigger_paths:
            buckets, cur = [], []
            for i in idxs:
                cur.append(i)
                if paths[i] in trigger_paths:
                    buckets.append(cur)
                    cur = []
            if cur:
                buckets.append(cur)
        else:
            buckets = [idxs]
        for bucket in buckets:
            n = sum(int(leaves[i].size) for i in bucket)
            comm_dt = jnp.dtype(jnp.float32) if allreduce_always_fp32 \
                else dt
            acct = _bucket_wire_accounting(
                n, comm_dt, topo, ici, compress, message_size,
                delay_allreduce, bool(trigger_paths))
            plan.append({
                "dtype": str(dt), "comm_dtype": str(comm_dt),
                "leaves": len(bucket), "elements": n,
                "chunks": acct["chunks"], "cause": acct["cause"],
                "topology": acct["topology"],
                "ici_size": ici, "dcn_size": dcn,
                "wire_elements": acct["wire_elements"],
                "padded_elements": acct["padded_elements"],
                "wire_bytes": acct["bytes"],
                "ici_wire_bytes": acct["ici_wire_bytes"],
                "dcn_wire_bytes": acct["dcn_wire_bytes"],
                "dcn_comm_dtype": acct["dcn_comm_dtype"],
                "eqns": acct["eqns"],
                "eqn_payload_bytes": acct["eqn_payload_bytes"]})
    return plan


def plan_collective_expectations(plan: List[dict],
                                 extra_psums: int = 0,
                                 extra_psum_bytes: int = 0) -> dict:
    """Fold a :func:`allreduce_comm_plan` into the ``collectives``
    expectation dict the analysis rule consumes: exact per-primitive
    eqn counts, the total on-wire payload, and the per-primitive
    payload split — which IS the ici-vs-dcn distinction at graph level
    (under the hierarchical topology the bucket's psum — or compressed
    bf16 all_gather — payload is exactly the DCN hop).

    ``extra_psums`` / ``extra_psum_bytes`` account for the step's
    scalar psums outside the grad reduction (the axis-size scalar
    ``gradient_average`` divides by, the loss pmean)."""
    counts: Counter = Counter()
    by_prim: Counter = Counter()
    total = 0
    for b in plan:
        for prim, k in b["eqns"].items():
            counts[prim] += k
        for prim, by in b["eqn_payload_bytes"].items():
            by_prim[prim] += by
        total += b["wire_bytes"]
    if extra_psums:
        counts["psum"] += extra_psums
        by_prim["psum"] += extra_psum_bytes
    return {"counts": dict(counts),
            "payload_bytes": total + extra_psum_bytes,
            "payload_bytes_by_primitive": dict(by_prim)}


def plan_resharding_expectations(plan: List[dict],
                                 budget: Optional[Dict[str, int]] = None
                                 ) -> dict:
    """Fold a comm plan (:func:`allreduce_comm_plan` buckets, or
    ``overlap_comm_schedule()["buckets"]``) into the ``resharding``
    expectation the census rule consumes: the exact per-eqn payload
    list of every *placement-changing* collective the plan issues.

    Unlike :func:`plan_collective_expectations` (which pins totals),
    the census needs per-eqn payloads so it can match graph eqns one by
    one and name the unexplained gather.  Per bucket:

    - ``reduce_scatter``: one eqn, the full padded bucket.
    - ``all_gather``: the in-slice gather-back of the 1/ici shard;
      under bf16 compression the DCN reduce is itself an all_gather of
      ``dcn_wire_bytes``, so the bucket contributes two payloads —
      ``[dcn_wire_bytes, total - dcn_wire_bytes]``.

    ``budget`` declares per-primitive counts of *additional* resharding
    eqns the entry point is allowed beyond the plan (default: none —
    any unplanned gather is an error finding)."""
    planned: Dict[str, List[int]] = {}
    for b in plan:
        eqns = b.get("eqns", {})
        payload = b.get("eqn_payload_bytes", {})
        for prim in ("all_gather", "all_to_all", "reduce_scatter",
                     "pgather"):
            k = int(eqns.get(prim, 0))
            if not k:
                continue
            total = int(payload.get(prim, 0))
            if prim == "all_gather" and k == 2:
                dcn = int(b.get("dcn_wire_bytes", 0))
                pays = [dcn, total - dcn]
            elif k == 1:
                pays = [total]
            else:
                pays = [total // k] * k
                pays[0] += total - sum(pays)
            planned.setdefault(prim, []).extend(pays)
    exp: Dict[str, Any] = {"planned": planned}
    if budget:
        exp["budget"] = {k: int(v) for k, v in budget.items()}
    return exp


def zero_update_comm_plan(params: Any, *, zero_stage: int,
                          world: int, ici_size: Optional[int] = None,
                          zero_compress_bf16: bool = False
                          ) -> List[dict]:
    """Static comm plan of one ZeRO-sharded optimizer step
    (``amp.AmpOptimizer.step`` with a ``zero_axis`` layout), in the
    same bucket schema as :func:`allreduce_comm_plan` so
    :func:`plan_collective_expectations` and
    :func:`plan_resharding_expectations` fold it unchanged — the
    analysis rules pin the ZeRO collective structure from the same
    source the runtime derives it from.  Buckets, by ``role``:

    - ``grad_reduce`` — the gradient reduction.  Stage 1: one
      full-axis ``reduce_scatter`` of the padded flat buffer (flat
      accounting: every byte crosses the slowest link).  Stages 2/3:
      the in-slice ``reduce_scatter`` plus the DCN reduce of the
      1/ici shard (a ``psum``, or a bf16 ``all_gather`` when
      compressed) — stage 3's scatter is the *transpose* of the
      just-in-time parameter gather, but it is the same eqn with the
      same payload, so the plan does not care who emitted it.
    - ``param_gather`` (stages 1/2, one bucket per gathered dtype) —
      the updated-shard all_gather back to full params: the half
      model copy, plus the fp32 copy only when some float leaf stays
      fp32 (``amp`` skips that gather otherwise, and so does the
      plan).
    - ``jit_gather`` (stage 3) — the ``zero_gather_params`` collectives
      in the forward and again in the ``jax.checkpoint`` replay: the
      half-dtype shard all_gather plus, when some float leaf stays
      fp32, the tiny fp32 aux gather of the exact elements (one fp32
      all_gather total when the layout has no half dtype).  Stage 3 has
      NO param_gather buckets: the master shard is the parameter store.

    ``params`` is the model parameter tree (shapes/dtypes only — the
    plan is static)."""
    from ..amp._process_optimizer import (_FlatLayout,
                                          _validate_zero_knobs)
    _validate_zero_knobs(zero_stage, ici_size, zero_compress_bf16)
    # a ZeRO layout (tree order), as the step's own: the plan counts the
    # float32 elements of each shard by their offsets
    layout = _FlatLayout(params, "data", zero_stage, ici_size,
                         zero_compress_bf16)
    n = layout.total
    isz = 4                                    # grads reduce in fp32
    if zero_stage >= 2:
        ici = int(ici_size)
        _topology.hierarchical_axis_groups(int(world), ici)
        dcn = int(world) // ici
        pop = ici
        topo = "hierarchical"
    else:
        pop = ici = int(world)
        dcn = 1
        topo = "flat"
    n_pad = n + ((-n) % pop)
    m = n_pad // pop
    half = layout.half_dtype
    any_fp32 = any(f and d == "float32" for f, d in
                   zip(layout.is_float, layout.dtypes))
    n_float = sum(1 for f in layout.is_float if f)

    def bucket(role, dtype, comm_dtype, leaves, elements, padded,
               eqns, payload, ici_bytes, dcn_bytes, dcn_dt):
        return {"role": role, "zero_stage": int(zero_stage),
                "dtype": str(dtype), "comm_dtype": str(comm_dtype),
                "leaves": leaves, "elements": elements,
                "chunks": 1, "cause": "zero", "topology": topo,
                "ici_size": ici, "dcn_size": dcn,
                "wire_elements": elements + padded,
                "padded_elements": padded,
                "wire_bytes": sum(payload.values()),
                "ici_wire_bytes": ici_bytes,
                "dcn_wire_bytes": dcn_bytes,
                "dcn_comm_dtype": str(jnp.dtype(dcn_dt)),
                "eqns": eqns, "eqn_payload_bytes": payload}

    plan: List[dict] = []
    if zero_stage >= 2:
        if zero_compress_bf16:
            eqns = {"reduce_scatter": 1, "all_gather": 1}
            payload = {"reduce_scatter": n_pad * isz,
                       "all_gather": m * 2}
            dcn_bytes, dcn_dt = m * 2, jnp.bfloat16
        else:
            eqns = {"reduce_scatter": 1, "psum": 1}
            payload = {"reduce_scatter": n_pad * isz, "psum": m * isz}
            dcn_bytes, dcn_dt = m * isz, jnp.float32
        plan.append(bucket("grad_reduce", jnp.float32, jnp.float32,
                           n_float, n, n_pad - n, eqns, payload,
                           n_pad * isz, dcn_bytes, dcn_dt))
    else:
        plan.append(bucket("grad_reduce", jnp.float32, jnp.float32,
                           n_float, n, n_pad - n,
                           {"reduce_scatter": 1},
                           {"reduce_scatter": n_pad * isz},
                           n_pad * isz, n_pad * isz, jnp.float32))
    if zero_stage == 3:
        # the jit gather runs at the model half dtype when the layout
        # has one (zero_gather_params): the half all_gather plus a tiny
        # fp32 aux gather for the exact (non-half) elements; all-fp32
        # layouts gather once in fp32.  Both appear twice: forward +
        # remat replay (zero_gather_checkpoint_policy re-gathers in the
        # backward instead of keeping the full model live).
        if half is not None:
            from ..amp._process_optimizer import _zero3_gather_tables
            _, _, n32, m32 = _zero3_gather_tables(layout, ici)
            hsz = jnp.dtype(half).itemsize
            gathers = [(half, {"all_gather": m * hsz}, m * hsz)]
            if n32:
                gathers.append((jnp.float32,
                                {"all_gather": max(m32, 1) * isz},
                                max(m32, 1) * isz))
        else:
            gathers = [(jnp.float32, {"all_gather": m * isz}, m * isz)]
        for _ in range(2):                     # forward + remat replay
            for dt, payload, ici_bytes in gathers:
                plan.append(bucket(
                    "jit_gather", dt, dt, n_float,
                    payload["all_gather"] // jnp.dtype(dt).itemsize, 0,
                    {"all_gather": 1}, dict(payload),
                    ici_bytes, 0, dt))
    else:
        gathers = []
        if any_fp32 or half is None:
            gathers.append((jnp.float32, 4,
                            sum(1 for f, d in zip(layout.is_float,
                                                  layout.dtypes)
                                if f and d == "float32")))
        if half is not None:
            gathers.append((half, jnp.dtype(half).itemsize,
                            sum(1 for f, d in zip(layout.is_float,
                                                  layout.dtypes)
                                if f and d == str(half))))
        for dt, dsz, leaves in gathers:
            b = m * dsz
            plan.append(bucket(
                "param_gather", dt, dt, leaves, m, 0,
                {"all_gather": 1}, {"all_gather": b},
                b, b if zero_stage == 1 else 0, dt))
    return plan


def _stamp_stage_labels(records: List[dict], stage: int,
                        issue_start: int) -> int:
    """Stamp one stage's bucket records (plan buckets OR runtime
    ``comm_stats``/``numerics_out`` dicts) with their place in the
    overlap schedule: ``stage`` (which forward stage owns the bucket)
    and ``issue_order`` (global position in the issue sequence).  ONE
    implementation shared by :func:`overlap_comm_schedule` and the
    runtime path, so a schedule change cannot relabel one side only.
    Returns the next free issue index."""
    for i, rec in enumerate(records):
        rec["stage"] = int(stage)
        rec["issue_order"] = issue_start + i
    return issue_start + len(records)


def staged_grads(stage_fns: Sequence[Callable], loss_head: Callable,
                 stage_params: Sequence[Any], x: Any,
                 reduce_stage: Optional[Callable] = None,
                 overlap: bool = True) -> Tuple[jax.Array, List[Any]]:
    """Manual chain rule over a sequential stage decomposition — the
    comm/compute-overlap engine (ROADMAP item 2; reference DDP's
    arrival-order bucket drain, distributed.py:378-398, expressed as
    program order).

    ``stage_fns[i](stage_params[i], act) -> act`` compose the forward;
    ``loss_head(act) -> scalar`` closes over labels.  The forward runs
    every stage under :func:`jax.vjp`; the backward then walks stages
    in :func:`topology.overlap_issue_order` (back-to-front — reverse
    AD makes the LAST stage's gradients first).  With ``overlap=True``
    each stage's ``reduce_stage(stage, issue_idx, grads)`` is called
    the moment that stage's gradients exist, BEFORE the next stage's
    VJP runs — so in the traced jaxpr the first bucket's
    psum_scatter/DCN-reduce/all_gather chain sits ahead of the earlier
    layers' grad eqns and a latency-hiding scheduler can run them
    concurrently (statically pinned by the collective lint rule's
    interleaving check).  With ``overlap=False`` the same reductions
    are issued in the same order but only AFTER the whole backward —
    the reduce-after-backward baseline the overlapped schedule is
    numerically pinned against (identical buckets, identical
    collectives, only the issue positions differ; grads match at fp32
    rtol 1e-6 in tests/test_overlap.py).

    Returns ``(loss, [per-stage grads])`` with grads in STAGE order
    (``grads[i]`` matches ``stage_params[i]``), reduced when
    ``reduce_stage`` is given."""
    n = len(stage_fns)
    if n != len(stage_params):
        raise ValueError(f"{n} stage fns vs {len(stage_params)} stage "
                         f"param trees")
    order = _topology.overlap_issue_order(n)
    act = x
    vjps = []
    for fn, p in zip(stage_fns, stage_params):
        act, vjp = jax.vjp(fn, p, act)
        vjps.append(vjp)
    loss, loss_vjp = jax.vjp(loss_head, act)
    (ct,) = loss_vjp(jnp.ones_like(loss))
    grads: List[Any] = [None] * n
    for issue, s in enumerate(order):
        g, ct = vjps[s](ct)
        if overlap and reduce_stage is not None:
            g = reduce_stage(s, issue, g)
        grads[s] = g
    if not overlap and reduce_stage is not None:
        # reduce-after-backward: SAME buckets, SAME issue order, issued
        # only once the full backward has been emitted
        for issue, s in enumerate(order):
            grads[s] = reduce_stage(s, issue, grads[s])
    return loss, grads


def overlap_comm_schedule(stage_trees: Sequence[Any],
                          message_size: int = 10_000_000,
                          allreduce_always_fp32: bool = False,
                          comm_topology: str = "flat",
                          allreduce_compress_bf16: bool = False,
                          ici_size: Optional[int] = None,
                          world: Optional[int] = None,
                          nproc: Optional[int] = None,
                          overlap: bool = True,
                          zero_stage: Optional[int] = None
                          ) -> Dict[str, Any]:
    """The static overlap schedule: :func:`allreduce_comm_plan`
    extended with WHEN each bucket's reduction is issued, computed from
    shapes alone.  Returns::

        {"overlap_mode": "overlapped" | "reduce_after_backward",
         "n_stages": S,
         "issue_order": [S-1, ..., 0],        # stage-level issue order
         "buckets": [...]}                    # plan buckets + stage/
                                              #   issue_order labels

    Every bucket dict is an :func:`allreduce_comm_plan` bucket — same
    shared :func:`_bucket_wire_accounting`, so per-level wire bytes are
    UNCHANGED by overlapping (the schedule moves issue positions, not
    payloads) — stamped by the same :func:`_stamp_stage_labels` the
    runtime uses.  Bucket order in ``buckets`` IS issue order, which is
    also the order ``comm_stats``/``numerics_out`` records arrive in at
    trace time; ``tests/test_overlap.py`` pins the two sides equal.
    The collective lint rule derives its expectations (census, per-
    primitive payloads, AND the static interleaving property) from this
    schedule via :func:`overlap_collective_expectations`.

    ``zero_stage=2`` describes the ZeRO-2 fused staged step
    (:meth:`DistributedDataParallel.staged_zero2_allreduce_grads`):
    per-stage wire accounting is IDENTICAL to the plain hierarchical
    schedule — the in-slice all_gather carries the *updated params*
    instead of the reduced grads, same shard, same payload, same
    fabric level — so the buckets are unchanged and the schedule is
    merely tagged (requires ``comm_topology='hierarchical'``)."""
    if zero_stage is not None:
        if zero_stage != 2:
            raise ValueError(
                f"overlap_comm_schedule composes with ZeRO stage 2 "
                f"only (stage 3's gather lives in the forward, not "
                f"the grad schedule); got zero_stage={zero_stage!r}")
        if comm_topology != "hierarchical":
            raise ValueError(
                "the fused ZeRO-2 staged schedule shards over the ICI "
                "slice; comm_topology must be 'hierarchical'")
    order = _topology.overlap_issue_order(len(stage_trees))
    buckets: List[dict] = []
    issue = 0
    for s in order:
        stage_buckets = allreduce_comm_plan(
            stage_trees[s], message_size=message_size,
            allreduce_always_fp32=allreduce_always_fp32,
            comm_topology=comm_topology,
            allreduce_compress_bf16=allreduce_compress_bf16,
            ici_size=ici_size, world=world, nproc=nproc)
        issue = _stamp_stage_labels(stage_buckets, s, issue)
        buckets.extend(stage_buckets)
    return {"overlap_mode": ("overlapped" if overlap
                             else "reduce_after_backward"),
            "n_stages": len(stage_trees),
            "issue_order": order,
            "zero_stage": zero_stage,
            "buckets": buckets}


def overlap_collective_expectations(schedule: Dict[str, Any],
                                    extra_psums: int = 0,
                                    extra_psum_bytes: int = 0) -> dict:
    """Fold an :func:`overlap_comm_schedule` into the collective rule's
    expectation dict: the exact census/payloads of
    :func:`plan_collective_expectations` over the schedule's buckets,
    PLUS — for the overlapped mode — the static interleaving pin: the
    first issued bucket's reduction eqns must appear in the jaxpr
    BEFORE the last layers' grad (conv/dot) eqns, not trail the whole
    backward.  ``min_payload_bytes`` separates grad-bucket collectives
    from the step's scalar psums (axis size, loss pmean): it is the
    smallest per-level hop any bucket puts on the wire, which a real
    gradient bucket always clears and a 4-byte scalar never does."""
    exp = plan_collective_expectations(schedule["buckets"],
                                       extra_psums=extra_psums,
                                       extra_psum_bytes=extra_psum_bytes)
    if schedule["overlap_mode"] == "overlapped" and schedule["buckets"]:
        min_hop = min(
            min(b["dcn_wire_bytes"], b["ici_wire_bytes"])
            for b in schedule["buckets"])
        # every bucket of every stage except the LAST-issued one (stage
        # 0 — reverse AD drains back-to-front) is emitted before that
        # stage's VJP, hence before the last grad matmul: each of its
        # eqns clears min_payload_bytes (every per-eqn payload is at
        # least its bucket's smaller fabric hop, which is at least the
        # global min_hop), so the schedule implies an exact FLOOR on
        # how many reductions precede the last matmul — the static
        # proof that the overlap did not silently collapse to
        # reduce-after-backward for all but one stage
        last_stage = schedule["issue_order"][-1]
        n_before = sum(sum(b["eqns"].values())
                       for b in schedule["buckets"]
                       if b["stage"] != last_stage)
        exp["interleaving"] = {
            "min_payload_bytes": max(int(min_hop), 16),
            "min_matmuls_after": 1,
            "min_collectives_before_last_matmul": int(n_before)}
    return exp


def _broadcast0(flat: jax.Array, axis_name: str,
                axis_index_groups=None) -> jax.Array:
    """Broadcast from rank 0 expressed as a masked psum (XLA lowers this
    to a collective-broadcast-shaped pattern over ICI).  psum runs in the
    leaf's own dtype — an fp32 round-trip would corrupt integer leaves
    beyond 2^24 (e.g. PRNG keys)."""
    comm = flat.astype(jnp.int32) if flat.dtype == jnp.bool_ else flat
    src = jnp.where(lax.axis_index(axis_name) == 0, comm,
                    jnp.zeros_like(comm))
    return lax.psum(src, axis_name,
                    axis_index_groups=axis_index_groups).astype(flat.dtype)


def flat_dist_call(tree: Any, axis_name: str = "data", op: str = "psum",
                   axis_index_groups=None) -> Any:
    """apply_flat_dist_call parity (distributed.py:36-49): one collective
    per dtype group over the flattened tree."""
    reducer = {"psum": lax.psum, "pmean": lax.pmean, "pmax": lax.pmax,
               "pmin": lax.pmin, "broadcast": _broadcast0}[op]
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    groups: Dict[Any, List[int]] = {}
    for i, g in enumerate(leaves):
        groups.setdefault(jnp.dtype(g.dtype), []).append(i)
    out: List[Any] = [None] * len(leaves)
    for dt, idxs in groups.items():
        with jax.named_scope("ddp.pack"):
            flat = jnp.concatenate([leaves[i].reshape(-1) for i in idxs])
        with jax.named_scope("ddp.reduce"):
            red = reducer(flat, axis_name,
                          axis_index_groups=axis_index_groups)
        with jax.named_scope("ddp.unpack"):
            off = 0
            for i in idxs:
                sz = leaves[i].size
                out[i] = red[off:off + sz].reshape(leaves[i].shape)
                off += sz
    return jax.tree_util.tree_unflatten(treedef, out)


class DistributedDataParallel:
    """Model wrapper with the reference's constructor surface
    (distributed.py:129-171)."""

    def __init__(self, module=None, message_size: int = 10_000_000,
                 delay_allreduce: bool = False,
                 shared_param: Optional[bool] = None,
                 allreduce_trigger_params: Optional[list] = None,
                 retain_allreduce_buffers: bool = False,
                 allreduce_always_fp32: bool = False,
                 gradient_average: bool = True,
                 gradient_predivide_factor: float = 1.0,
                 axis_name: str = "data",
                 comm_topology: str = "flat",
                 allreduce_compress_bf16: bool = False,
                 ici_size: Optional[int] = None,
                 overlap: bool = False,
                 zero_stage: Optional[int] = None):
        if shared_param is not None:
            raise ValueError("shared_param is deprecated (reference "
                             "distributed.py:176-180)")
        self.module = module
        self.message_size = int(message_size)
        self.delay_allreduce = delay_allreduce
        self.allreduce_trigger_params = allreduce_trigger_params
        self.retain_allreduce_buffers = retain_allreduce_buffers
        self.allreduce_always_fp32 = allreduce_always_fp32
        self.gradient_average = gradient_average
        self.gradient_predivide_factor = gradient_predivide_factor
        self.axis_name = axis_name
        # topology knobs (allreduce_grads_tree): where the gradient
        # bytes travel — validated eagerly so a typo fails at
        # construction, not at first trace
        _validate_topology_knobs(comm_topology, allreduce_compress_bf16)
        self.comm_topology = comm_topology
        self.allreduce_compress_bf16 = allreduce_compress_bf16
        self.ici_size = ici_size
        # overlap=True selects the overlapped bucket schedule for
        # staged_allreduce_grads: each stage's reduction is issued
        # while earlier stages' gradients are still being computed.
        # It contradicts delay_allreduce (ONE fused reduce after
        # backward is the opposite schedule) and allreduce_trigger_
        # params (stage boundaries ARE the bucket boundaries in the
        # staged world).  Topology / compression / predivide all
        # compose — the per-bucket reduction is the unchanged
        # hierarchical chain, only its issue position moves.
        self.overlap = bool(overlap)
        if self.overlap:
            clashes = [name for name, bad in (
                ("delay_allreduce", delay_allreduce),
                ("allreduce_trigger_params",
                 bool(allreduce_trigger_params))) if bad]
            if clashes:
                raise ValueError(
                    f"overlap=True issues per-stage bucket reductions "
                    f"inside the backward; these options contradict "
                    f"that schedule: {clashes}")
        # zero_stage=2 arms the fused ZeRO-2 staged path
        # (staged_zero2_allreduce_grads): per-stage scatter-reduce to
        # the 1/ici shard, shard update, in-slice gather of the
        # UPDATED params — state sharding composed with the overlap
        # schedule.  Stages 1/3 shard inside amp.AmpOptimizer (the
        # step owns the flat master buffer), not here.
        if zero_stage is not None:
            if zero_stage != 2:
                raise ValueError(
                    f"DistributedDataParallel composes with ZeRO "
                    f"stage 2 only (stages 1/3 live in "
                    f"amp.AmpOptimizer's flat-buffer step); got "
                    f"zero_stage={zero_stage!r}")
            if comm_topology != "hierarchical":
                raise ValueError(
                    "zero_stage=2 shards the update over the ICI "
                    "slice; comm_topology must be 'hierarchical'")
        self.zero_stage = zero_stage
        self.allreduce_buffers: list = []
        # trace-time comm accounting (observability): one record per
        # bucket of the most recently traced allreduce — see
        # allreduce_grads_tree(comm_stats=...)
        self.last_comm_stats: list = []
        # the most recently traced overlap schedule
        # (staged_allreduce_grads): overlap_mode / n_stages /
        # issue_order / stage-stamped bucket records — None until a
        # staged step traces
        self.last_overlap_schedule: Optional[dict] = None

    # -- forward passthrough (wrapper parity) ------------------------------
    def __call__(self, *args, **kwargs):
        return self.module(*args, **kwargs)

    def apply(self, *args, **kwargs):
        return self.module.apply(*args, **kwargs)

    def __getattr__(self, name):
        return getattr(self.module, name)

    # -- the hot path ------------------------------------------------------
    def allreduce_grads(self, grads: Any,
                        axis_index_groups: Optional[List[List[int]]] = None,
                        numerics_out: Optional[list] = None) -> Any:
        if self.zero_stage is not None:
            raise ValueError(
                "zero_stage=2 shards the update — a full-gradient "
                "allreduce would gather bytes the shard update never "
                "reads; use staged_zero2_allreduce_grads (or "
                "amp.AmpOptimizer's zero_axis step)")
        retain = [] if self.retain_allreduce_buffers else None
        triggers = (set(self.allreduce_trigger_params)
                    if self.allreduce_trigger_params else None)
        comm_stats: list = []
        out = allreduce_grads_tree(
            grads, axis_name=self.axis_name, message_size=self.message_size,
            allreduce_always_fp32=self.allreduce_always_fp32,
            gradient_average=self.gradient_average,
            gradient_predivide_factor=self.gradient_predivide_factor,
            delay_allreduce=self.delay_allreduce,
            axis_index_groups=axis_index_groups,
            retain_buffers=retain, trigger_paths=triggers,
            comm_stats=comm_stats,
            comm_topology=self.comm_topology,
            allreduce_compress_bf16=self.allreduce_compress_bf16,
            ici_size=self.ici_size,
            numerics_out=numerics_out)
        if retain is not None:
            self.allreduce_buffers = retain
        self.last_comm_stats = comm_stats
        self._record_comm_stats()
        return out

    def staged_allreduce_grads(self, stage_fns: Sequence[Callable],
                               loss_head: Callable,
                               stage_params: Sequence[Any], x: Any,
                               numerics_out: Optional[list] = None
                               ) -> Tuple[jax.Array, List[Any]]:
        """The overlapped train-step hot path: forward + backward over
        a sequential stage decomposition with each stage's gradient
        bucket reduced on arrival (``self.overlap=True``) or after the
        full backward (``False`` — the pinned baseline schedule).  See
        :func:`staged_grads`; the per-stage reduction is
        :func:`allreduce_grads_tree` under this wrapper's knobs, so
        topology / compression / predivide / fp32-comm all behave
        exactly as in :meth:`allreduce_grads` — the schedule moves
        WHEN buckets are issued, never what they carry.

        The axis-size scalar is psum'd ONCE and shared across stages
        (``world_scalar=``), keeping the census at one scalar psum +
        whatever the plan budgets per bucket.  ``comm_stats`` /
        ``numerics_out`` records arrive stamped with
        ``stage``/``issue_order`` in exactly
        :func:`overlap_comm_schedule` bucket order (the plan-order
        contract PR 9's per-bucket scalars ride on), and
        ``self.last_overlap_schedule`` keeps the traced schedule."""
        if self.zero_stage is not None:
            raise ValueError(
                "zero_stage=2 replaces the per-stage gather-back of "
                "grads with a gather of updated params; use "
                "staged_zero2_allreduce_grads")
        if self.delay_allreduce or self.allreduce_trigger_params:
            raise ValueError(
                "staged_allreduce_grads: stage boundaries define the "
                "buckets; delay_allreduce / allreduce_trigger_params "
                "contradict the staged schedule")
        world_static = int(lax.axis_size(self.axis_name))
        world_scalar = _axis_size(self.axis_name)
        retain = [] if self.retain_allreduce_buffers else None
        comm_stats: list = []
        issue_state = {"comm": 0, "num": 0}

        def reduce_stage(stage, issue, grads_s):
            cs: list = []
            nout: Optional[list] = \
                [] if numerics_out is not None else None
            out = allreduce_grads_tree(
                grads_s, axis_name=self.axis_name,
                message_size=self.message_size,
                allreduce_always_fp32=self.allreduce_always_fp32,
                gradient_average=self.gradient_average,
                gradient_predivide_factor=self.gradient_predivide_factor,
                retain_buffers=retain,
                comm_stats=cs,
                comm_topology=self.comm_topology,
                allreduce_compress_bf16=self.allreduce_compress_bf16,
                ici_size=self.ici_size,
                numerics_out=nout,
                world_scalar=world_scalar)
            issue_state["comm"] = _stamp_stage_labels(
                cs, stage, issue_state["comm"])
            comm_stats.extend(cs)
            if nout is not None:
                issue_state["num"] = _stamp_stage_labels(
                    nout, stage, issue_state["num"])
                numerics_out.extend(nout)
            return out

        loss, grads = staged_grads(stage_fns, loss_head, stage_params,
                                   x, reduce_stage=reduce_stage,
                                   overlap=self.overlap)
        if retain is not None:
            self.allreduce_buffers = retain
        self.last_comm_stats = comm_stats
        self.last_overlap_schedule = {
            "overlap_mode": ("overlapped" if self.overlap
                             else "reduce_after_backward"),
            "n_stages": len(stage_fns),
            "issue_order": _topology.overlap_issue_order(len(stage_fns)),
            "buckets": comm_stats,
            "world": world_static}
        self._record_comm_stats()
        return loss, grads

    def staged_zero2_allreduce_grads(
            self, stage_fns: Sequence[Callable], loss_head: Callable,
            stage_params: Sequence[Any], x: Any,
            update_shard: Callable) -> Tuple[jax.Array, List[Any]]:
        """The fused ZeRO-2 overlapped step (requires
        ``zero_stage=2``): the staged backward of
        :meth:`staged_allreduce_grads`, but each stage's arrival-order
        reduction is the *sharded weight update* instead of a plain
        allreduce —

        1. the stage's flat gradient bucket is scatter-reduced to its
           1/ici shard (``psum_scatter`` within the ICI slice + the
           DCN reduce, :func:`_hier_scatter_reduce` — the same eqns,
           payloads and fabric levels as the hierarchical allreduce's
           first two hops);
        2. ``update_shard(stage, param_shard, grad_shard)`` applies
           the optimizer to the local 1/ici window of the stage's
           params — shard-sized math, one fused kernel launch when the
           caller dispatches to the Pallas optimizer kernels;
        3. the in-slice ``all_gather`` carries the UPDATED param shard
           back (same payload the plain schedule spends gathering
           reduced grads — ZeRO-2 costs nothing extra on the wire).

        All three are issued the moment the stage's grads exist
        (``overlap=True``), so by the time the backward reaches stage
        0, the later stages' params for the next step are already in
        flight — update/backward overlap on top of comm/backward
        overlap.  With ``overlap=False`` the same chain runs after the
        full backward (the pinned baseline).

        Returns ``(loss, new_stage_params)`` — NOT grads: the update
        already happened.  The traced schedule lands in
        ``last_overlap_schedule`` tagged ``zero_stage=2``; bucket wire
        accounting is byte-identical to
        ``overlap_comm_schedule(..., zero_stage=2)``."""
        if self.zero_stage != 2:
            raise ValueError(
                "staged_zero2_allreduce_grads requires "
                "DistributedDataParallel(zero_stage=2, "
                "comm_topology='hierarchical')")
        world_static = int(lax.axis_size(self.axis_name))
        ici = (int(self.ici_size) if self.ici_size is not None
               else _topology.default_ici_size(world_static))
        ici_groups, dcn_groups = _topology.hierarchical_axis_groups(
            world_static, ici)
        compress = self.allreduce_compress_bf16
        world_scalar = _axis_size(self.axis_name)
        comm_stats: list = []
        issue_state = {"comm": 0}

        def reduce_stage(stage, issue, grads_s):
            leaves, treedef = jax.tree_util.tree_flatten(grads_s)
            dts = {jnp.dtype(l.dtype) for l in leaves}
            if len(dts) != 1:
                raise ValueError(
                    f"stage {stage} mixes gradient dtypes {dts}: the "
                    f"fused shard update runs on ONE flat buffer per "
                    f"stage — cast the stage params to a single dtype")
            (dt,) = dts
            with jax.named_scope("ddp.pack"):
                flat = (leaves[0].reshape(-1) if len(leaves) == 1 else
                        jnp.concatenate([l.reshape(-1) for l in leaves]))
                comm = (flat.astype(jnp.float32)
                        if self.allreduce_always_fp32 else flat)
            pre, post = predivide_factors(
                world_scalar, self.gradient_predivide_factor)
            if pre != 1.0:
                with jax.named_scope("ddp.pack"):
                    comm = comm / jnp.asarray(pre, comm.dtype)
            n = comm.shape[0]
            with jax.named_scope("ddp.reduce"):
                g_shard, _ = _hier_scatter_reduce(
                    comm, self.axis_name, ici_groups, dcn_groups,
                    compress)
            with jax.named_scope("ddp.unpack"):
                if self.gradient_average:
                    g_shard = g_shard / post.astype(g_shard.dtype)
                g_shard = g_shard.astype(dt)
            m = g_shard.shape[0]
            # the local window of the CURRENT params at the shard's
            # offset — a static-offset slice, no communication
            p_leaves = jax.tree_util.tree_leaves(stage_params[stage])
            flat_par = (p_leaves[0].reshape(-1) if len(p_leaves) == 1
                        else jnp.concatenate(
                            [l.reshape(-1) for l in p_leaves]))
            flat_par = jnp.pad(flat_par, (0, m * ici - n))
            idx = lax.axis_index(self.axis_name) % ici
            p_shard = lax.dynamic_slice_in_dim(flat_par, idx * m, m)
            new_shard = update_shard(stage, p_shard, g_shard)
            with jax.named_scope("ddp.reduce"):
                full = _hier_gather(new_shard, self.axis_name,
                                    ici_groups, n)
            out, off = [], 0
            with jax.named_scope("ddp.unpack"):
                for l in leaves:
                    sz = int(l.size)
                    out.append(full[off:off + sz].reshape(l.shape))
                    off += sz
            acct = _bucket_wire_accounting(
                n, comm.dtype, "hierarchical", ici, compress,
                self.message_size, False, False)
            rec = {"dtype": str(dt), "comm_dtype": str(comm.dtype),
                   "leaves": len(leaves), "elements": int(n),
                   **{k: v for k, v in acct.items()
                      if k not in ("eqns", "eqn_payload_bytes")}}
            issue_state["comm"] = _stamp_stage_labels(
                [rec], stage, issue_state["comm"])
            comm_stats.append(rec)
            return jax.tree_util.tree_unflatten(treedef, out)

        loss, new_params = staged_grads(stage_fns, loss_head,
                                        stage_params, x,
                                        reduce_stage=reduce_stage,
                                        overlap=self.overlap)
        self.last_comm_stats = comm_stats
        self.last_overlap_schedule = {
            "overlap_mode": ("overlapped" if self.overlap
                             else "reduce_after_backward"),
            "n_stages": len(stage_fns),
            "issue_order": _topology.overlap_issue_order(len(stage_fns)),
            "zero_stage": 2,
            "buckets": comm_stats,
            "world": world_static}
        self._record_comm_stats()
        return loss, new_params

    def _record_comm_stats(self):
        """Fold the per-bucket accounting into the process observability
        registry: per-(dtype, cause) bucket counts and per-dtype bytes.
        Runs at TRACE time — totals count compiled traces, not executed
        steps (per-step totals = these x steps on that executable); the
        cross-replica sharding comm work in PAPERS.md plans against
        exactly this per-bucket record."""
        from ..observability import get_registry
        reg = get_registry()
        buckets = reg.counter(
            "ddp_allreduce_buckets_total",
            help="gradient allreduce buckets per compiled trace")
        bts = reg.counter(
            "ddp_allreduce_bytes_total",
            help="one replica's communicated gradient bytes per trace")
        lvl = reg.counter(
            "ddp_allreduce_level_bytes_total",
            help="one replica's gradient bytes per fabric level (ici = "
                 "fast in-slice interconnect, dcn = cross-host) per "
                 "trace; flat psums count fully on both levels")
        for b in self.last_comm_stats:
            buckets.labels(dtype=b["comm_dtype"], cause=b["cause"]).inc()
            bts.labels(dtype=b["comm_dtype"]).inc(b["bytes"])
            lvl.labels(level="ici", dtype=b["comm_dtype"]).inc(
                b.get("ici_wire_bytes", b["bytes"]))
            lvl.labels(level="dcn", dtype=b["comm_dtype"]).inc(
                b.get("dcn_wire_bytes", b["bytes"]))

    def broadcast_params(self, params: Any) -> Any:
        """Rank-0 parameter broadcast (reference DDP does this at
        construction, distributed.py:234).  Under shard_map replicated
        in_specs make it implicit; call this explicitly when ranks may
        have diverged (e.g. after independent init under multi-process)."""
        return flat_dist_call(params, self.axis_name, "broadcast")

    # -- whole-step builder for the common 1-D data-parallel mesh ---------
    def make_step(self, step_fn: Callable, mesh: Optional[Mesh] = None,
                  donate_state: bool = True,
                  steps_per_call: int = 1,
                  state_specs: Any = None) -> Callable:
        """shard_map ``step_fn(state..., batch) -> (state..., aux)`` over a
        1-D mesh: replicated state, batch sharded on axis 0.  ``step_fn``
        runs per-device and should call ``self.allreduce_grads`` on its
        gradient tree (param broadcast from rank 0 is implicit: replicated
        inputs to shard_map stay replicated, the analogue of the init-time
        broadcast at distributed.py:234).

        ``state_specs``: PartitionSpec pytree for the state when parts of
        it are NOT replicated — e.g. a ZeRO-sharded optimizer state
        (``(P(), P(), amp.zero_optimizer_specs(...))``) or TP-sharded
        params (``tensor_parallel.partition_specs``).  Defaults to fully
        replicated (``P()``), the plain-DDP contract.

        ``steps_per_call > 1`` wraps ``step_fn`` in a ``lax.scan`` over a
        leading micro-batch axis (batch shaped ``(K, per_step...)``) so
        one dispatch runs K optimizer steps — amortizes host→device
        dispatch latency.
        The aux output then carries the K per-step values."""
        if mesh is None:
            mesh = Mesh(jax.devices(), (self.axis_name,))
        an = self.axis_name
        K = int(steps_per_call)
        if K < 1:
            raise ValueError(f"steps_per_call must be >= 1, got {K}")
        if state_specs is None:
            state_specs = P()

        if K == 1:
            wrapped = step_fn
        else:
            def wrapped(state, batch):
                lead = {l.shape[0] for l in jax.tree_util.tree_leaves(batch)}
                if lead != {K}:
                    raise ValueError(
                        f"steps_per_call={K} needs every batch leaf shaped "
                        f"(K, per_step...); got leading dims {sorted(lead)}")
                return lax.scan(step_fn, state, batch)

        # batch sharded on the data axis: micro-batch axis (if any) first
        bspec = P(an) if K == 1 else P(None, an)
        mapped = jax.shard_map(
            wrapped, mesh=mesh,
            in_specs=(state_specs, bspec),
            out_specs=(state_specs, P()),
            check_vma=False)
        return jax.jit(mapped, donate_argnums=(0,) if donate_state else ())


class Reducer:
    """Manual allreduce helper, parity with apex.parallel.Reducer
    (distributed.py:89-126): call ``reduce(tree)`` inside a mapped context
    to sum (and average) a pytree across the axis, and
    ``broadcast_params(tree)`` for the construction-time rank-0 parameter
    broadcast the reference performs (distributed.py:100-104) — in the
    functional world construction has no params in hand, so the broadcast
    is an explicit call at the top of the first step (or skipped when
    params are replicated by shard_map, which is the common case)."""

    def __init__(self, module_or_tree=None, axis_name: str = "data",
                 gradient_average: bool = True):
        self.module = module_or_tree
        self.axis_name = axis_name
        self.gradient_average = gradient_average

    def reduce(self, tree: Any) -> Any:
        red = flat_dist_call(tree, self.axis_name, "psum")
        if self.gradient_average:
            world = _axis_size(self.axis_name)
            red = jax.tree_util.tree_map(
                lambda x: x / world.astype(x.dtype), red)
        return red

    def broadcast_params(self, tree: Any) -> Any:
        """Every rank gets rank 0's values (reference init broadcast,
        distributed.py:100-104 / DDP :234)."""
        return flat_dist_call(tree, self.axis_name, "broadcast")
