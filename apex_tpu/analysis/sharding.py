"""Static partition-spec propagation over ``shard_map`` jaxprs.

The collective rule counts psums and the memory rule counts live bytes,
but neither can *see* placement: which arrays are replicated across the
mesh, which are sharded, and whether a refactor smuggled an unplanned
all-gather into the hot path.  This module closes that hole statically —
no device execution, no mocks, just the traced jaxpr.

The model is a **partition of ranks**: every intermediate value is
assigned a partition of the flattened device ranks (row-major over the
mesh axes) such that ranks in the same cell are *guaranteed* to hold
bit-identical values.  Fully replicated = one cell; fully varying =
singleton cells.  The replication factor of an array is
``world / n_cells``, and the deletable bytes are
``local_bytes * (world - n_cells)`` — exactly the fp32 master/optimizer
state ZeRO-2/3 (ROADMAP item 2) will shard away.

Propagation rules (validated against the jax 0.9.0 jaxprs the entry
points actually trace):

- ``shard_map`` body inputs: partition keyed by each rank's coordinates
  along the axes named in ``in_specs`` (``P()`` -> replicated).
- default eqn: outputs get the meet (common refinement) of the input
  partitions — sound for any deterministic op (same inputs, same
  outputs).
- ``psum``/``pmax``/``pmin``: two ranks agree afterwards iff their
  participant groups reduce equal multisets — groups merge iff their
  *count-vectors* over input cells match.
- ``all_gather``: groups merge iff their members are element-wise in the
  same input cells (this is what makes the hierarchical
  psum_scatter(ici) -> psum(dcn) -> all_gather(ici) chain resolve to
  fully replicated).
- ``reduce_scatter`` (``psum_scatter``): output cell = (count-vector
  class of the group, position within the group).
- ``all_to_all``: output cell = (element-wise cell tuple of the group,
  position).  ``ppermute``: each destination inherits its source's cell;
  untargeted ranks share a "zero" cell.  ``axis_index``: cell = the
  coordinate along the axis.
- control flow: ``scan``/``while`` run the body to a fixpoint on the
  carry partitions (finite lattice — converges in <= world steps);
  ``while`` additionally meets the carry with the predicate partition
  (rank-varying trip counts de-replicate everything they touch);
  ``cond`` meets all branch outputs with the predicate.
- unknown higher-order prims: recursed when the sub-jaxpr arity matches;
  otherwise outputs are conservatively *varying* if the body contains
  collectives or ``axis_index``, else the meet of the inputs.

Consumers (wired through :mod:`.rules` and the exporters):

- :func:`entry_point_sharding_record` — the **replication ledger**, a
  ``kind: sharding`` record per train entry point:
  ``replicated_bytes`` is what the ZeRO-2/3 stages bring down.
- :func:`check_shard_map_specs` — spec-vs-mesh consistency (axis-name
  existence, divisibility, replicated-output claims the propagated
  partition contradicts; ``check_vma=False`` means XLA never checks the
  latter at runtime).
- :func:`collective_sites` — the resharding census the
  ``resharding-census`` rule matches against
  ``allreduce_comm_plan``/``overlap_comm_schedule``.
"""

from __future__ import annotations

import dataclasses
import functools
import re
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.extend.core  # noqa: F401  (jax.extend is not auto-imported)

from . import graphs
from ..parallel.topology import collective_rank_groups

__all__ = [
    "Partition", "ArgSharding", "CollectiveSite", "ShardMapAnalysis",
    "RESHARD_PRIMS", "shard_map_eqns", "analyze_shard_map",
    "analyze_sharding", "check_shard_map_specs",
    "divergent_output_claims", "entry_point_sharding_record",
]

# collectives that change *placement* (vs psum/pmax/pmin which only
# reduce): the census rule requires every one of these in a hot graph to
# be explained by the comm plan or a declared budget
RESHARD_PRIMS = ("all_gather", "all_to_all", "reduce_scatter", "pgather")

_REDUCE_PRIMS = ("psum", "pmax", "pmin")


# -- the partition lattice ------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Partition:
    """A partition of the flattened mesh ranks into agreement cells:
    ranks in the same cell are guaranteed to hold identical values.
    ``cells[r]`` is rank r's cell id, canonicalized by first
    occurrence so equal partitions compare equal."""

    cells: Tuple[int, ...]

    @staticmethod
    def from_keys(keys: Sequence[Any]) -> "Partition":
        ids: Dict[Any, int] = {}
        out = []
        for k in keys:
            if k not in ids:
                ids[k] = len(ids)
            out.append(ids[k])
        return Partition(tuple(out))

    @staticmethod
    def replicated(world: int) -> "Partition":
        return Partition((0,) * world)

    @staticmethod
    def varying(world: int) -> "Partition":
        return Partition(tuple(range(world)))

    @property
    def world(self) -> int:
        return len(self.cells)

    @property
    def n_cells(self) -> int:
        return max(self.cells) + 1 if self.cells else 0

    @property
    def is_replicated(self) -> bool:
        return self.n_cells <= 1

    def replication_factor(self) -> float:
        f = self.world / max(1, self.n_cells)
        return int(f) if float(f).is_integer() else f

    def meet(self, other: "Partition") -> "Partition":
        """Common refinement: same cell afterwards iff same cell in
        BOTH inputs (the sound combine for multi-input ops)."""
        return Partition.from_keys(tuple(zip(self.cells, other.cells)))


def _meet_all(parts: Sequence[Partition], world: int) -> Partition:
    if not parts:
        return Partition.replicated(world)
    return functools.reduce(lambda a, b: a.meet(b), parts)


class _MeshCtx:
    """Rank bookkeeping for one mesh: coordinates, collective groups."""

    def __init__(self, axis_sizes: Dict[str, int]):
        self.axis_sizes = {k: int(v) for k, v in axis_sizes.items()}
        self.names = list(self.axis_sizes)
        sizes = [self.axis_sizes[n] for n in self.names]
        self.world = int(np.prod(sizes)) if sizes else 1
        import itertools
        self.coords = list(itertools.product(*[range(s) for s in sizes]))
        self._pos = {n: i for i, n in enumerate(self.names)}

    def groups(self, axes, axis_index_groups=None) -> List[Tuple[int, ...]]:
        return collective_rank_groups(self.axis_sizes, axes,
                                      axis_index_groups)

    def coord_partition(self, axes: Sequence[str]) -> Partition:
        """Partition keyed by each rank's coordinates along ``axes`` —
        the input partition of an array sharded over those axes, and
        the output of ``axis_index``."""
        idxs = [self._pos[a] for a in axes]
        return Partition.from_keys(
            [tuple(c[i] for i in idxs) for c in self.coords])

    def names_partition(self, names_dict: Dict[int, Tuple[str, ...]]
                        ) -> Partition:
        axes = sorted({a for t in names_dict.values() for a in t})
        if not axes:
            return Partition.replicated(self.world)
        return self.coord_partition(axes)

    def varies_along(self, part: Partition, axis: str) -> bool:
        """True if two ranks differing only in their ``axis`` coordinate
        can hold different values."""
        i = self._pos[axis]
        seen: Dict[Tuple, int] = {}
        for r, c in enumerate(self.coords):
            key = c[:i] + c[i + 1:]
            if key in seen and part.cells[seen[key]] != part.cells[r]:
                return True
            seen.setdefault(key, r)
        return False

    def spec_str(self, part: Partition) -> str:
        if part.is_replicated:
            return "replicated"
        axes = [a for a in self.names if self.varies_along(part, a)]
        if axes:
            return "varies(" + ",".join(axes) + ")"
        return f"varies({part.n_cells} cells)"


# -- collective transfer functions ----------------------------------------

def _reduce_part(p: Partition, groups, world: int) -> Partition:
    keys: List[Any] = [("solo", r) for r in range(world)]
    for g in groups:
        cnt: Dict[int, int] = {}
        for r in g:
            cnt[p.cells[r]] = cnt.get(p.cells[r], 0) + 1
        k = tuple(sorted(cnt.items()))
        for r in g:
            keys[r] = k
    return Partition.from_keys(keys)


def _gather_part(p: Partition, groups, world: int) -> Partition:
    keys: List[Any] = [("solo", r) for r in range(world)]
    for g in groups:
        k = tuple(p.cells[m] for m in g)
        for r in g:
            keys[r] = k
    return Partition.from_keys(keys)


def _scatter_part(p: Partition, groups, world: int) -> Partition:
    keys: List[Any] = [("solo", r) for r in range(world)]
    for g in groups:
        cnt: Dict[int, int] = {}
        for r in g:
            cnt[p.cells[r]] = cnt.get(p.cells[r], 0) + 1
        base = tuple(sorted(cnt.items()))
        for idx, r in enumerate(g):
            keys[r] = (base, idx)
    return Partition.from_keys(keys)


def _all_to_all_part(p: Partition, groups, world: int) -> Partition:
    keys: List[Any] = [("solo", r) for r in range(world)]
    for g in groups:
        base = tuple(p.cells[m] for m in g)
        for idx, r in enumerate(g):
            keys[r] = (base, idx)
    return Partition.from_keys(keys)


def _ppermute_part(p: Partition, groups, perm, world: int) -> Partition:
    keys: List[Any] = [("solo", r) for r in range(world)]
    src_of = {int(d): int(s) for s, d in perm}
    for g in groups:
        for idx, r in enumerate(g):
            if idx in src_of:
                keys[r] = ("v", p.cells[g[src_of[idx]]])
            else:
                keys[r] = ("zero",)
    return Partition.from_keys(keys)


# -- the propagator -------------------------------------------------------

@dataclasses.dataclass
class CollectiveSite:
    """One collective eqn inside a shard_map body, with the statically
    inferred placement of its operand *before* the op — the name the
    census rule prints when a gather is unplanned."""

    primitive: str
    payload_bytes: int
    shape: Tuple[int, ...]
    dtype: str
    spec: str          # inferred operand placement ("replicated", ...)
    axes: Tuple[str, ...]

    def describe(self) -> str:
        return (f"{self.primitive} over {self.axes} on "
                f"{self.dtype}{list(self.shape)} "
                f"({self.payload_bytes} B/replica, operand {self.spec})")


def _is_jaxpr(x) -> bool:
    return isinstance(x, (jax.extend.core.Jaxpr,
                          jax.extend.core.ClosedJaxpr))


def _sub_jaxprs(params: Dict[str, Any]) -> List[Any]:
    subs = []
    for v in params.values():
        for leaf in jax.tree_util.tree_leaves(v, is_leaf=_is_jaxpr):
            if _is_jaxpr(leaf):
                subs.append(leaf)
    return subs


def _contains_rank_dependence(jaxpr) -> bool:
    names = graphs.COLLECTIVE_PRIMS | {"axis_index"}
    jx = jaxpr.jaxpr if isinstance(jaxpr, jax.extend.core.ClosedJaxpr) \
        else jaxpr
    return any(e.primitive.name in names for e in graphs.walk_jaxpr(jx))


def _aval_bytes(aval) -> int:
    return int(np.prod(aval.shape)) * aval.dtype.itemsize


def _axes_param(params: Dict[str, Any]):
    axes = params.get("axes", params.get("axis_name"))
    if isinstance(axes, str):
        axes = (axes,)
    return tuple(axes) if axes is not None else ()


class _Propagator:
    def __init__(self, ctx: _MeshCtx,
                 sites: Optional[List[CollectiveSite]] = None):
        self.ctx = ctx
        self.sites = sites

    def run(self, jaxpr, in_parts: Sequence[Partition],
            const_parts: Optional[Sequence[Partition]] = None
            ) -> List[Partition]:
        """Propagate partitions through an (open or closed) jaxpr.
        Returns the outvar partitions."""
        closed_consts = None
        if isinstance(jaxpr, jax.extend.core.ClosedJaxpr):
            closed_consts = jaxpr.consts
            jaxpr = jaxpr.jaxpr
        w = self.ctx.world
        env: Dict[Any, Partition] = {}
        if const_parts is None:
            const_parts = [Partition.replicated(w)] * len(jaxpr.constvars)
        for v, p in zip(jaxpr.constvars, const_parts):
            env[v] = p
        if len(in_parts) != len(jaxpr.invars):
            raise ValueError(
                f"arity mismatch: {len(in_parts)} partitions for "
                f"{len(jaxpr.invars)} invars")
        for v, p in zip(jaxpr.invars, in_parts):
            env[v] = p

        def read(atom) -> Partition:
            if isinstance(atom, jax.extend.core.Literal):
                return Partition.replicated(w)
            return env.get(atom, Partition.replicated(w))

        for eqn in jaxpr.eqns:
            outs = self._eqn(eqn, [read(a) for a in eqn.invars])
            for v, p in zip(eqn.outvars, outs):
                env[v] = p
        return [read(a) for a in jaxpr.outvars]

    # one eqn -> outvar partitions
    def _eqn(self, eqn, in_parts: List[Partition]) -> List[Partition]:
        ctx, w = self.ctx, self.ctx.world
        name = eqn.primitive.name
        params = eqn.params

        if name in _REDUCE_PRIMS or name in RESHARD_PRIMS \
                or name in ("ppermute",):
            axes = _axes_param(params)
            try:
                groups = ctx.groups(axes, params.get("axis_index_groups"))
            except (KeyError, ValueError):
                # malformed axis reference: spec rule reports it; stay
                # sound here
                return [Partition.varying(w) for _ in eqn.outvars]
            if self.sites is not None and name in graphs.COLLECTIVE_PRIMS:
                op = _meet_all(in_parts, w)
                aval = eqn.invars[0].aval
                self.sites.append(CollectiveSite(
                    primitive=name,
                    payload_bytes=graphs.eqn_payload_bytes(eqn),
                    shape=tuple(aval.shape),
                    dtype=str(aval.dtype),
                    spec=ctx.spec_str(op),
                    axes=axes))
            if name in _REDUCE_PRIMS:
                return [_reduce_part(p, groups, w) for p in in_parts]
            if name == "all_gather":
                return [_gather_part(p, groups, w) for p in in_parts]
            if name == "reduce_scatter":
                return [_scatter_part(p, groups, w) for p in in_parts]
            if name == "all_to_all":
                return [_all_to_all_part(p, groups, w) for p in in_parts]
            if name == "ppermute":
                return [_ppermute_part(p, groups, params["perm"], w)
                        for p in in_parts]
            # pgather etc.: placement semantics not modeled — varying
            return [Partition.varying(w) for _ in eqn.outvars]

        if name == "axis_index":
            axes = _axes_param(params)
            try:
                return [ctx.coord_partition(list(axes))]
            except KeyError:
                return [Partition.varying(w)]

        if name == "scan":
            return self._scan(eqn, in_parts)
        if name == "while":
            return self._while(eqn, in_parts)
        if name == "cond":
            return self._cond(eqn, in_parts)
        if name == "pjit":
            return self.run(params["jaxpr"], in_parts)

        subs = _sub_jaxprs(params)
        if len(subs) == 1:
            sub = subs[0]
            jx = sub.jaxpr if isinstance(
                sub, jax.extend.core.ClosedJaxpr) else sub
            if len(jx.invars) == len(eqn.invars):
                try:
                    outs = self.run(sub, in_parts)
                    if len(outs) == len(eqn.outvars):
                        return outs
                except ValueError:
                    pass
        if subs and any(_contains_rank_dependence(s) for s in subs):
            return [Partition.varying(w) for _ in eqn.outvars]
        meet = _meet_all(in_parts, w)
        return [meet for _ in eqn.outvars]

    def _scan(self, eqn, in_parts: List[Partition]) -> List[Partition]:
        params = eqn.params
        nc, nk = params["num_consts"], params["num_carry"]
        consts, carry = in_parts[:nc], list(in_parts[nc:nc + nk])
        xs = in_parts[nc + nk:]
        quiet = _Propagator(self.ctx, sites=None)
        body = params["jaxpr"]
        for _ in range(4 * self.ctx.world + 4):
            outs = quiet.run(body, consts + carry + xs)
            new = [c.meet(o) for c, o in zip(carry, outs[:nk])]
            if new == carry:
                break
            carry = new
        # final pass with the sound carry, recording sites once
        outs = self.run(body, consts + carry + xs)
        return list(carry) + list(outs[nk:])

    def _while(self, eqn, in_parts: List[Partition]) -> List[Partition]:
        params = eqn.params
        cn, bn = params["cond_nconsts"], params["body_nconsts"]
        cconsts = in_parts[:cn]
        bconsts = in_parts[cn:cn + bn]
        carry = list(in_parts[cn + bn:])
        quiet = _Propagator(self.ctx, sites=None)
        for _ in range(4 * self.ctx.world + 4):
            pred = quiet.run(params["cond_jaxpr"], cconsts + carry)[0]
            outs = quiet.run(params["body_jaxpr"], bconsts + carry)
            new = [c.meet(o).meet(pred) for c, o in zip(carry, outs)]
            if new == carry:
                break
            carry = new
        self.run(params["body_jaxpr"], bconsts + carry)  # record sites
        return carry

    def _cond(self, eqn, in_parts: List[Partition]) -> List[Partition]:
        pred, ops = in_parts[0], in_parts[1:]
        outs = None
        for branch in eqn.params["branches"]:
            b_outs = self.run(branch, ops)
            outs = b_outs if outs is None else [
                a.meet(b) for a, b in zip(outs, b_outs)]
        return [o.meet(pred) for o in (outs or [])]


# -- shard_map analysis ---------------------------------------------------

@dataclasses.dataclass
class ArgSharding:
    """Static placement of one shard_map body argument."""

    index: int
    shape: Tuple[int, ...]          # LOCAL (per-device block) shape
    dtype: str
    local_bytes: int
    n_cells: int
    replication_factor: float
    spec: str

    def replicated_bytes(self, world: int) -> int:
        return self.local_bytes * (world - self.n_cells)


@dataclasses.dataclass
class ShardMapAnalysis:
    """Everything the ledger and the two sharding rules need from one
    shard_map eqn: per-arg placement, the propagated output partitions,
    and the collective census with inferred operand specs."""

    world: int
    mesh_axes: Dict[str, int]
    args: List[ArgSharding]
    out_parts: List[Partition]
    out_names: Tuple[Dict[int, Tuple[str, ...]], ...]
    sites: List[CollectiveSite]

    @property
    def argument_bytes(self) -> int:
        return sum(a.local_bytes for a in self.args)

    @property
    def replicated_bytes(self) -> int:
        return sum(a.replicated_bytes(self.world) for a in self.args)

    @property
    def unique_bytes(self) -> int:
        return sum(a.local_bytes * a.n_cells for a in self.args)

    def replicated_bytes_by_dtype(self) -> Dict[str, int]:
        out: Dict[str, int] = {}
        for a in self.args:
            b = a.replicated_bytes(self.world)
            if b:
                out[a.dtype] = out.get(a.dtype, 0) + b
        return out

    def resharding_eqns(self) -> Dict[str, int]:
        out: Dict[str, int] = {}
        for s in self.sites:
            if s.primitive in RESHARD_PRIMS:
                out[s.primitive] = out.get(s.primitive, 0) + 1
        return out


def _spec_names(spec) -> Dict[int, Tuple[str, ...]]:
    """A shard_map eqn's ``PartitionSpec`` as ``{dim: (axis, ...)}`` —
    the form the partition model is keyed on (unsharded dims absent)."""
    return {d: (entry,) if isinstance(entry, str) else tuple(entry)
            for d, entry in enumerate(spec) if entry is not None}


def _names_spec_str(names_dict: Dict[int, Tuple[str, ...]]) -> str:
    if not names_dict:
        return "replicated"
    return "sharded(" + ", ".join(
        f"dim{d}->{'*'.join(names_dict[d])}"
        for d in sorted(names_dict)) + ")"


def shard_map_eqns(jaxpr) -> List[Any]:
    """Every shard_map eqn anywhere in a (closed) jaxpr, including under
    pjit wrapper layers."""
    return graphs.prim_eqns(jaxpr, ("shard_map",))


def analyze_shard_map(eqn) -> ShardMapAnalysis:
    """Propagate partitions through one shard_map eqn's body.

    Body input partitions come from ``in_specs`` alone (shard_map
    semantics: the specs say how the global operand is laid out across
    the mesh, independent of outer context); captured consts are
    replicated."""
    params = eqn.params
    mesh = params["mesh"]
    axis_sizes = dict(mesh.shape)
    ctx = _MeshCtx(axis_sizes)
    body = params["jaxpr"]                       # open Jaxpr, LOCAL shapes
    in_names = [_spec_names(s) for s in params["in_specs"]]
    out_names = [_spec_names(s) for s in params["out_specs"]]

    in_parts = []
    for nm in in_names:
        try:
            in_parts.append(ctx.names_partition(nm))
        except KeyError:
            # axis name not in the mesh — spec rule reports it
            in_parts.append(Partition.varying(ctx.world))

    sites: List[CollectiveSite] = []
    prop = _Propagator(ctx, sites=sites)
    const_parts = [Partition.replicated(ctx.world)] * len(body.constvars)
    out_parts = prop.run(body, in_parts, const_parts=const_parts)

    args = []
    for i, (v, part) in enumerate(zip(body.invars, in_parts)):
        args.append(ArgSharding(
            index=i,
            shape=tuple(v.aval.shape),
            dtype=str(v.aval.dtype),
            local_bytes=_aval_bytes(v.aval),
            n_cells=part.n_cells,
            replication_factor=part.replication_factor(),
            spec=_names_spec_str(in_names[i])))
    for j, v in enumerate(body.constvars):
        args.append(ArgSharding(
            index=len(in_parts) + j,
            shape=tuple(v.aval.shape),
            dtype=str(v.aval.dtype),
            local_bytes=_aval_bytes(v.aval),
            n_cells=1,
            replication_factor=ctx.world,
            spec="replicated(const)"))

    return ShardMapAnalysis(
        world=ctx.world, mesh_axes=dict(ctx.axis_sizes), args=args,
        out_parts=out_parts, out_names=tuple(out_names),
        sites=sites)


def analyze_sharding(closed_jaxpr) -> List[ShardMapAnalysis]:
    """Analyze every shard_map in an entry point's traced jaxpr."""
    return [analyze_shard_map(e) for e in shard_map_eqns(closed_jaxpr)]


# -- spec-consistency checks ----------------------------------------------

def check_shard_map_specs(eqn,
                          expected_mesh_axes: Optional[Dict[str, int]]
                          = None,
                          analysis: Optional[ShardMapAnalysis] = None
                          ) -> List[str]:
    """Static spec-vs-mesh consistency for one shard_map eqn.  Returns
    human-readable problem strings (empty = consistent):

    - the eqn's mesh axes must match ``expected_mesh_axes`` (the mesh
      ``topology.make_mesh`` was asked for) when given;
    - every axis named in in/out specs must exist on the mesh;
    - globally, every sharded dim must divide evenly across its axes
      (outer eqn operands carry GLOBAL shapes).

    Output specs that *over-claim* agreement are a separate, declared
    count — see :func:`divergent_output_claims`.
    """
    params = eqn.params
    mesh = params["mesh"]
    axis_sizes = {k: int(v) for k, v in dict(mesh.shape).items()}
    problems: List[str] = []

    if expected_mesh_axes is not None and \
            axis_sizes != {k: int(v) for k, v in expected_mesh_axes.items()}:
        problems.append(
            f"shard_map mesh axes {axis_sizes} != expected "
            f"{dict(expected_mesh_axes)}")

    def _check_names(kind, specs, vars_, global_shapes: bool):
        for i, (spec, v) in enumerate(zip(specs, vars_)):
            for d, axes in _spec_names(spec).items():
                missing = [a for a in axes if a not in axis_sizes]
                if missing:
                    problems.append(
                        f"{kind}[{i}] names unknown mesh axis "
                        f"{missing} (mesh has {list(axis_sizes)})")
                    continue
                factor = int(np.prod([axis_sizes[a] for a in axes]))
                shape = tuple(v.aval.shape)
                if global_shapes:
                    if d >= len(shape) or shape[d] % factor != 0:
                        dim = shape[d] if d < len(shape) else "<missing>"
                        problems.append(
                            f"{kind}[{i}] dim {d} (= {dim}) not divisible "
                            f"by axes {tuple(axes)} (x{factor})")

    _check_names("in_specs", params["in_specs"], eqn.invars, True)
    _check_names("out_specs", params["out_specs"], eqn.outvars, True)
    return problems


def divergent_output_claims(eqn,
                            analysis: Optional[ShardMapAnalysis] = None
                            ) -> List[str]:
    """Outputs whose spec claims MORE agreement than the propagated body
    partition guarantees (e.g. ``out_specs`` says replicated, the body
    value still varies across the data axis).  With ``check_vma=False``
    the runtime silently keeps one replica's value, so this is the
    silent-wrong-answer class — but it is also how non-synced BatchNorm
    running stats intentionally behave on the DDP entry points, so the
    rule pins a *declared count* per entry point instead of flat-zero.

    One message per divergent output."""
    params = eqn.params
    axis_sizes = {k: int(v) for k, v in dict(params["mesh"].shape).items()}
    if analysis is None:
        analysis = analyze_shard_map(eqn)
    ctx = _MeshCtx(axis_sizes)
    claims: List[str] = []
    for i, (nm, part) in enumerate(zip(analysis.out_names,
                                       analysis.out_parts)):
        claimed_axes = sorted({a for t in nm.values() for a in t})
        try:
            claimed = ctx.names_partition(nm) if nm else \
                Partition.replicated(ctx.world)
        except KeyError:
            continue  # unknown axis: check_shard_map_specs reports it
        # sound iff the claim refines what the body guarantees: every
        # pair of ranks the claim merges must be merged by the
        # propagated partition too
        rep: Dict[int, int] = {}
        for r in range(ctx.world):
            c = claimed.cells[r]
            if c in rep:
                if part.cells[rep[c]] != part.cells[r]:
                    out_v = eqn.outvars[i] if i < len(eqn.outvars) else None
                    what = (f"{out_v.aval.dtype}{list(out_v.aval.shape)}"
                            if out_v is not None and
                            hasattr(out_v, "aval") else f"output {i}")
                    claim = ("replicated" if not nm else
                             f"sharded over {claimed_axes}")
                    claims.append(
                        f"out_specs[{i}] claims {what} is {claim} but the "
                        f"propagated body value {ctx.spec_str(part)} — "
                        f"a collective is missing before the return "
                        f"(check_vma=False hides this at runtime)")
                    break
            else:
                rep[c] = r
    return claims


# -- the replication ledger ----------------------------------------------

def entry_point_sharding_record(ep, top_n: int = 8) -> Dict[str, Any]:
    """The replication ledger for one entry point, as a
    ``kind: sharding`` record.

    ``argument_bytes`` counts the shard_map body's LOCAL operands (incl.
    captured consts) — the same accounting as
    ``memory.jaxpr_live_bytes``'s ``argument_bytes``, so the two planes
    cross-check.  ``replicated_bytes`` is the world-total of deletable
    duplicate bytes: ``sum(local_bytes * (world - n_cells))``; the
    identity ``unique_bytes + replicated_bytes == world *
    argument_bytes`` is enforced by ``validate_sharding_record``.

    Entry points that trace no shard_map (the serving engines) raise a
    bare ``RuntimeError`` — the documented CLI skip-gate class.
    """
    graph = ep.graph()
    eqns = shard_map_eqns(graph.jaxpr)
    if not eqns:
        raise RuntimeError(
            f"entry point {ep.name!r} traces no shard_map; the "
            f"replication ledger does not apply")
    analyses = [analyze_shard_map(e) for e in eqns]
    worlds = {a.world for a in analyses}
    if len(worlds) != 1:
        raise ValueError(
            f"entry point {ep.name!r} mixes shard_map worlds {worlds}")
    world = worlds.pop()
    mesh_axes = analyses[0].mesh_axes

    by_dtype: Dict[str, int] = {}
    resharding: Dict[str, int] = {}
    all_args: List[Tuple[ArgSharding, int]] = []
    for a in analyses:
        for dt, b in a.replicated_bytes_by_dtype().items():
            by_dtype[dt] = by_dtype.get(dt, 0) + b
        for prim, n in a.resharding_eqns().items():
            resharding[prim] = resharding.get(prim, 0) + n
        for arg in a.args:
            all_args.append((arg, arg.replicated_bytes(world)))

    all_args.sort(key=lambda t: t[1], reverse=True)
    top = [{
        "index": arg.index,
        "shape": list(arg.shape),
        "dtype": arg.dtype,
        "local_bytes": arg.local_bytes,
        "replication_factor": arg.replication_factor,
        "spec": arg.spec,
    } for arg, b in all_args[:top_n] if b > 0]

    argument_bytes = sum(a.argument_bytes for a in analyses)
    replicated = sum(a.replicated_bytes for a in analyses)
    unique = sum(a.unique_bytes for a in analyses)
    # zero EPs name their stage in the registry name
    # (ddp_resnet18_o2_zero3, ddp_mlp_overlap_zero2) — stamp it so the
    # ledger says which stage its replicated_bytes claim measured
    zero_m = re.search(r"zero([123])", ep.name)
    rec = {
        "kind": "sharding",
        "entry_point": ep.name,
        "source": "jaxpr",
        "world": world,
        "mesh_axes": {k: int(v) for k, v in mesh_axes.items()},
        "shard_maps": len(analyses),
        "argument_bytes": argument_bytes,
        "unique_bytes": unique,
        "replicated_bytes": replicated,
        "replicated_bytes_by_dtype": by_dtype,
        "replicated_fraction": (
            replicated / (world * argument_bytes)
            if argument_bytes else 0.0),
        "top_replicated": top,
        "resharding_eqns": resharding,
    }
    if zero_m:
        rec["zero_stage"] = int(zero_m.group(1))
    return rec
