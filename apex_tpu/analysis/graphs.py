"""Graph plumbing for the static analyzer: jaxpr walking, primitive
vocabularies, and compat helpers over lowered StableHLO modules.

Everything here is *description*, not judgement: these helpers surface
what a traced/lowered graph contains (host-transfer primitives,
convolution operands, collective payloads, donation aliasing) and the
rules in :mod:`.rules` decide whether that violates an entry point's
expectations.
"""

from __future__ import annotations

from typing import Any, Callable, Iterable, Iterator, List, Optional, Tuple

import numpy as np

import jax
import jax.extend.core  # noqa: F401  (jax.extend is not auto-imported)

__all__ = [
    "HOST_TRANSFER_PRIMS", "COLLECTIVE_PRIMS",
    "walk_jaxpr", "prim_eqns", "host_transfer_eqns", "conv_eqns",
    "large_dot_eqns", "transpose_eqns", "collective_eqns",
    "eqn_payload_bytes", "lowered_text", "aliased_output_count",
    "donated_arg_names", "duplicate_donated_leaves", "Graph",
]

# primitives that move data across the host boundary: any of these
# inside a jitted hot graph means a per-dispatch host round-trip — the
# exact cost the device-resident scaler, telemetry, and the serving
# decode window exist to avoid (pinned since PR 1 by
# tests/test_step_graph_audit.py)
HOST_TRANSFER_PRIMS = frozenset({
    "pure_callback", "io_callback", "debug_callback", "callback",
    "outfeed", "infeed", "device_put",
})

# cross-replica communication primitives the accounting rule budgets
COLLECTIVE_PRIMS = frozenset({
    "psum", "pmax", "pmin", "all_gather", "all_to_all",
    "reduce_scatter", "ppermute", "pgather",
})


def _as_jaxpr(jaxpr):
    if isinstance(jaxpr, jax.extend.core.ClosedJaxpr):
        return jaxpr.jaxpr
    return jaxpr


def walk_jaxpr(jaxpr) -> Iterator[Any]:
    """Yield every eqn in a (closed) jaxpr, recursing into sub-jaxprs
    (scan/while/cond bodies, shard_map, pjit calls, custom-vjp …)."""
    jaxpr = _as_jaxpr(jaxpr)
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in jax.tree_util.tree_leaves(
                    v, is_leaf=lambda x: isinstance(
                        x, (jax.extend.core.Jaxpr,
                            jax.extend.core.ClosedJaxpr))):
                if isinstance(sub, (jax.extend.core.Jaxpr,
                                    jax.extend.core.ClosedJaxpr)):
                    yield from walk_jaxpr(sub)


def prim_eqns(jaxpr, names: Iterable[str]) -> List[Any]:
    names = frozenset(names)
    return [e for e in walk_jaxpr(jaxpr) if e.primitive.name in names]


def host_transfer_eqns(jaxpr) -> List[Any]:
    return prim_eqns(jaxpr, HOST_TRANSFER_PRIMS)


def conv_eqns(jaxpr) -> List[Any]:
    return prim_eqns(jaxpr, ("conv_general_dilated",))


def large_dot_eqns(jaxpr, min_elems: int = 256) -> List[Any]:
    """dot_general eqns whose operands are all activation/param sized
    (>= ``min_elems`` elements) — the matmuls that hit the MXU; tiny
    bookkeeping dots (scalars, index math) are exempt from dtype
    policy."""
    return [e for e in prim_eqns(jaxpr, ("dot_general",))
            if all(int(np.prod(v.aval.shape)) >= min_elems
                   for v in e.invars)]


def transpose_eqns(jaxpr, min_elems: int = 0) -> List[Any]:
    return [e for e in prim_eqns(jaxpr, ("transpose",))
            if int(np.prod(e.invars[0].aval.shape)) >= min_elems]


def collective_eqns(jaxpr) -> List[Any]:
    return prim_eqns(jaxpr, COLLECTIVE_PRIMS)


def eqn_payload_bytes(eqn) -> int:
    """Bytes of operand data an eqn moves (sum over invars) — for a
    psum/all_gather this is the on-wire payload of one replica."""
    return sum(int(np.prod(v.aval.shape)) * v.aval.dtype.itemsize
               for v in eqn.invars
               if hasattr(v, "aval") and hasattr(v.aval, "shape"))


# -- lowered-module helpers ----------------------------------------------

def lowered_text(lowered, debug_info: bool = False) -> str:
    """`Lowered.as_text()` across the jax API drift: jax >= 0.5 takes
    ``debug_info=`` directly; 0.4.x needs the MLIR module's
    ``get_asm(enable_debug_info=True)`` to see scope/name metadata
    (named nvtx ranges, arg locations)."""
    if not debug_info:
        return lowered.as_text()
    try:
        return lowered.as_text(debug_info=True)
    except TypeError:
        mod = lowered.compiler_ir("stablehlo")
        return mod.operation.get_asm(enable_debug_info=True)


def aliased_output_count(stablehlo_text: str) -> int:
    """Number of input buffers the lowered module aliases to an output
    (``tf.aliasing_output`` entry-function attributes) — i.e. how many
    donations XLA actually honored.  Donation that is requested but not
    aliased silently keeps both copies alive."""
    return stablehlo_text.count("tf.aliasing_output")


def donated_arg_names(lowered, arg_names: Tuple[str, ...]):
    """Map ``Lowered.args_info`` donation flags back to the wrapped
    function's parameter names.

    Returns ``(donated, partial)``: names with at least one donated
    leaf, and the subset of those whose leaves are only *partially*
    donated (a donation hole inside one logical argument)."""
    args_info, _kwargs_info = lowered.args_info
    if len(args_info) != len(arg_names):
        raise ValueError(
            f"arg_names has {len(arg_names)} entries but the lowering "
            f"has {len(args_info)} positional args")
    donated, partial = [], []
    for name, info in zip(arg_names, args_info):
        flags = [bool(i.donated) for i in jax.tree_util.tree_leaves(info)]
        if any(flags):
            donated.append(name)
            if not all(flags):
                partial.append(name)
    return donated, partial


def duplicate_donated_leaves(lowered, arg_names: Tuple[str, ...],
                             example_args: Tuple[Any, ...]) -> List[str]:
    """Donated leaves that are the *same buffer object* appearing more
    than once in the donated argument set.  XLA rejects this at compile
    time ("Attempt to donate the same buffer twice"), and the classic
    way to ship it is a cache init that shares one zeros buffer across
    layers (the ``dict(layer)`` shallow copy PR 2 hit in
    ``gpt.init_cache``).  Returns a description per duplicated buffer."""
    donated, _ = donated_arg_names(lowered, arg_names)
    seen = {}
    dups = []
    for name, arg in zip(arg_names, example_args):
        if name not in donated:
            continue
        for path, leaf in jax.tree_util.tree_flatten_with_path(arg)[0]:
            key = id(leaf)
            where = f"{name}{jax.tree_util.keystr(path)}"
            if key in seen:
                dups.append(f"{where} shares a buffer with {seen[key]}")
            else:
                seen[key] = where
    return dups


class Graph:
    """One traced entry point: the jaxpr and (lazily) the lowered
    StableHLO module, plus the metadata the donation rule needs to name
    arguments."""

    def __init__(self,
                 trace: Optional[Callable[[], Any]] = None,
                 lower: Optional[Callable[[], Any]] = None,
                 arg_names: Optional[Tuple[str, ...]] = None,
                 example_args: Optional[Tuple[Any, ...]] = None):
        self._trace = trace
        self._lower = lower
        self.arg_names = arg_names
        self.example_args = example_args
        self._jaxpr = None
        self._lowered = None
        self._lowered_text = None
        self._compiled = None

    @property
    def jaxpr(self):
        if self._jaxpr is None:
            if self._trace is None:
                raise ValueError("entry point has no jaxpr tracer")
            self._jaxpr = self._trace()
        return self._jaxpr

    @property
    def lowered(self):
        if self._lowered is None:
            if self._lower is None:
                raise ValueError("entry point has no lowering")
            self._lowered = self._lower()
        return self._lowered

    @property
    def stablehlo(self) -> str:
        if self._lowered_text is None:
            self._lowered_text = self.lowered.as_text()
        return self._lowered_text

    @property
    def has_lowering(self) -> bool:
        return self._lower is not None or self._lowered is not None

    @property
    def compiled(self):
        """The compiled executable for memory/cost analysis.

        Entry points with a real lowering compile it (donation aliasing
        and all); trace-only entry points compile an ``eval_jaxpr``
        re-staging of the traced graph — structurally identical compute,
        but no donation, so alias_bytes reads 0 there.  Cached: the
        compile is paid once per process like the trace."""
        if self._compiled is None:
            if self.has_lowering:
                self._compiled = self.lowered.compile()
            else:
                closed = self.jaxpr
                args = [jax.ShapeDtypeStruct(v.aval.shape, v.aval.dtype)
                        for v in closed.jaxpr.invars]
                fn = jax.jit(lambda *xs: jax.core.eval_jaxpr(
                    closed.jaxpr, closed.consts, *xs))
                self._compiled = fn.lower(*args).compile()
        return self._compiled
