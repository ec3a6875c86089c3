"""Analytic cost model over jaxprs: FLOPs, transcendentals, and bytes.

Walk a traced jaxpr, count the arithmetic each primitive performs, and
report totals plus per-primitive / per-dtype breakdowns.
``analysis.EntryPoint.cost()`` caches one per entry point and
``analysis.rules.FlopAccountingRule`` budgets them.  (Peak rates and MFU
are the benchmark's: ``benchmark/lib/peaks.py``.)

The op-cost table deliberately mirrors XLA's ``HloCostAnalysis`` (the
engine behind ``Compiled.cost_analysis()``), calibrated primitive by
primitive against ``jax.stages.Lowered.cost_analysis()`` on this jax
version — so the analytic counts can be cross-validated against XLA's
own counts (tests/test_costmodel.py pins the resnet18 O2 and GPT O2
entry points within 5%, the way tests/test_remat.py already consumes
``cost_analysis()``).  Known, documented divergences:

- **scan**: XLA lowers scan to ``while`` and counts the body ONCE; the
  honest cost of a K-tick decode window is K bodies.  Default mode
  multiplies by the trace-time trip count; ``xla_parity=True`` counts
  once, for cross-validation.
- **cond**: one branch executes; honest mode costs the max branch,
  parity mode sums branches (XLA counts every computation it lowered).
- **while**: the trip count is unknowable statically — the body is
  counted once in both modes and ``Cost.while_loops`` records how many
  loops were so truncated.
- **cumsum**: XLA's reduce-window lowering scores O(n^2); the analytic
  model charges the honest O(n).

Do NOT cross-validate against ``Compiled.cost_analysis()`` on graphs
holding the flat-buffer optimizer: XLA's *post-fusion* counter bills a
fusion's producer instructions at full shape into every consumer, so
the 62 per-leaf ``rebuild`` slices of the flat Adam buffer each
re-count the whole 11M-element update (~8x overcount on the resnet18
step).  ``Lowered.cost_analysis()`` (pre-optimization, structurally
1:1 with the jaxpr) is the sane cross-check there; post-optimization
counts are only meaningful on fusion-free-producer graphs like the
fwd+bwd cores test_remat pins.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Dict, Optional, Tuple

import numpy as np

__all__ = ["Cost", "jaxpr_cost", "eqn_flops", "conv_flops", "dot_flops",
           "xla_cost"]


# -- per-eqn FLOP counting -------------------------------------------------

def _nelem(v) -> int:
    return int(np.prod(v.aval.shape)) if hasattr(v, "aval") else 0


def _nbytes(v) -> int:
    if not (hasattr(v, "aval") and hasattr(v.aval, "shape")):
        return 0
    return _nelem(v) * np.dtype(v.aval.dtype).itemsize


def conv_flops(eqn) -> float:
    """XLA ``HandleConvolution`` parity: 2 FMAs per *valid* (output
    position, kernel tap) pair — taps landing in padding or in the
    holes of a dilated input are not arithmetic and are not counted
    (this is why a strided conv's dgrad costs the same as its forward,
    not kernel-size times more — the trap the old hand-rolled
    ``2*B*H*W*Cout*Cin*k^2`` counters fell into on backward graphs).
    Validity factorizes per spatial dimension, so the count is a
    product of per-dimension tallies."""
    lhs, rhs = eqn.invars[0].aval, eqn.invars[1].aval
    out = eqn.outvars[0].aval
    p = eqn.params
    dn = p["dimension_numbers"]
    strides = p["window_strides"]
    pad = p["padding"]
    lhs_dil = p.get("lhs_dilation") or (1,) * len(strides)
    rhs_dil = p.get("rhs_dilation") or (1,) * len(strides)
    fg = p.get("feature_group_count", 1)
    bg = p.get("batch_group_count", 1)
    batch = lhs.shape[dn.lhs_spec[0]]
    cin = lhs.shape[dn.lhs_spec[1]]
    cout = out.shape[dn.out_spec[1]]
    valid = 1
    for i, d in enumerate(dn.lhs_spec[2:]):
        n = lhs.shape[d]
        k = rhs.shape[dn.rhs_spec[2:][i]]
        s = strides[i]
        plo = pad[i][0]
        bd = lhs_dil[i]
        wd = rhs_dil[i]
        o = out.shape[dn.out_spec[2:][i]]
        span = (n - 1) * bd
        cnt = 0
        for ki in range(k):
            # output positions where tap ki lands on a real element:
            # pos = oi*s + ki*wd - plo in [0, span] and pos % bd == 0
            for oi in range(o):
                pos = oi * s + ki * wd - plo
                if 0 <= pos <= span and pos % bd == 0:
                    cnt += 1
        valid *= cnt
    return 2.0 * batch * cout * (cin // fg) * valid / max(bg, 1)


def dot_flops(eqn) -> float:
    """2*M*N*K (batch dims included in the output element count)."""
    (lc, _rc), _batch = eqn.params["dimension_numbers"]
    lhs = eqn.invars[0].aval
    k = int(np.prod([lhs.shape[i] for i in lc])) if lc else 1
    return 2.0 * _nelem(eqn.outvars[0]) * k


# one flop per output element (XLA elementwise default; convert and
# compare count too — calibrated against Lowered.cost_analysis())
_ELEMENTWISE_1 = frozenset({
    "add", "sub", "mul", "div", "max", "min", "neg", "abs", "sign",
    "floor", "ceil", "round", "and", "or", "xor", "not",
    "shift_left", "shift_right_logical", "shift_right_arithmetic",
    "convert_element_type", "bitcast_convert_type", "reduce_precision",
    "eq", "ne", "lt", "le", "gt", "ge", "is_finite", "select_n",
    "sqrt_inv", "square", "add_any", "nextafter", "population_count",
    "clz", "real", "imag", "conj",
})
# sqrt/rsqrt et al are transcendentals in XLA's ledger, not flops
_TRANSCENDENTAL = frozenset({
    "exp", "exp2", "expm1", "log", "log1p", "tanh", "sin", "cos",
    "tan", "asin", "acos", "atan", "atan2", "sinh", "cosh", "asinh",
    "acosh", "atanh", "erf", "erfc", "erf_inv", "cbrt", "sqrt",
    "rsqrt", "pow", "digamma", "lgamma", "regularized_incomplete_beta",
    "igamma", "igammac",
})
_ELEMENTWISE_N = {"rem": 8, "clamp": 2}  # calibrated composites
# pure data movement / addressing: no arithmetic
_FREE = frozenset({
    "broadcast_in_dim", "reshape", "transpose", "slice",
    "dynamic_slice", "dynamic_update_slice", "concatenate", "pad",
    "rev", "squeeze", "expand_dims", "copy", "stop_gradient", "iota",
    "gather", "scatter", "sort", "split", "device_put",
    "random_seed", "random_wrap", "random_unwrap", "rng_bit_generator",
    "axis_index", "pvary", "sharding_constraint", "iota_32x2_shape",
    "broadcast", "empty", "real_part", "create_token", "optimization_barrier",
})
# collectives: XLA charges the reduction adds (one per payload element
# for psum/pmax/pmin); pure-movement collectives are free
_COLLECTIVE_REDUCE = frozenset({"psum", "pmax", "pmin", "pmean",
                                "reduce_scatter", "psum_scatter"})
_COLLECTIVE_FREE = frozenset({"all_gather", "all_to_all", "ppermute",
                              "pgather", "pbroadcast"})
_REDUCES = frozenset({"reduce_sum", "reduce_max", "reduce_min",
                      "reduce_prod", "reduce_and", "reduce_or",
                      "reduce_xor"})
# everything eqn_flops prices deliberately; anything else lands in
# Cost.unknown_prims (priced at the 1-flop/elem elementwise default)
# so table gaps surface in records instead of hiding
_KNOWN_PRIMS = (_ELEMENTWISE_1 | _TRANSCENDENTAL | _FREE
                | _COLLECTIVE_REDUCE | _COLLECTIVE_FREE | _REDUCES
                | frozenset(_ELEMENTWISE_N)
                | frozenset({
                    "dot_general", "conv_general_dilated", "argmax",
                    "argmin", "cumsum", "cumprod", "cummax", "cummin",
                    "cumlogsumexp", "reduce_window", "reduce_window_sum",
                    "reduce_window_max", "reduce_window_min",
                    "select_and_scatter_add", "integer_pow", "logistic",
                    "threefry2x32", "random_bits", "random_gamma",
                    "random_fold_in", "scatter-add", "scatter-mul",
                    "scatter-min", "scatter-max", "scatter_add",
                    "scatter_mul",
                }))


def eqn_flops(eqn) -> Tuple[float, float]:
    """(flops, transcendentals) of one leaf eqn (no sub-jaxprs)."""
    name = eqn.primitive.name
    if name == "dot_general":
        return dot_flops(eqn), 0.0
    if name == "conv_general_dilated":
        return conv_flops(eqn), 0.0
    if name in _REDUCES:
        return float(max(sum(map(_nelem, eqn.invars))
                         - sum(map(_nelem, eqn.outvars)), 0)), 0.0
    if name in ("argmax", "argmin"):
        # variadic reduce with a ~9-op comparator (calibrated)
        n_in = _nelem(eqn.invars[0])
        n_out = _nelem(eqn.outvars[0])
        return 9.0 * max(n_in - n_out, 0), 0.0
    if name in ("cumsum", "cumprod", "cummax", "cummin", "cumlogsumexp"):
        # honest O(n); XLA's reduce-window lowering would say O(n^2)
        return float(_nelem(eqn.outvars[0])), 0.0
    if name == "reduce_window_sum" or name == "reduce_window":
        win = int(np.prod(eqn.params.get("window_dimensions", (1,))))
        return float(_nelem(eqn.outvars[0]) * max(win - 1, 0)), 0.0
    if name in ("reduce_window_max", "reduce_window_min"):
        win = int(np.prod(eqn.params.get("window_dimensions", (1,))))
        return float(_nelem(eqn.outvars[0]) * max(win - 1, 0)), 0.0
    if name == "select_and_scatter_add":
        win = int(np.prod(eqn.params.get("window_dimensions", (1,))))
        return float(_nelem(eqn.outvars[0]) * win), 0.0
    if name == "integer_pow":
        p = abs(int(eqn.params.get("y", 2)))
        if p <= 1:
            return float(_nelem(eqn.outvars[0])), 0.0
        muls = int(np.floor(np.log2(p))) + bin(p).count("1") - 1
        return float(_nelem(eqn.outvars[0]) * muls), 0.0
    if name == "logistic":
        n = _nelem(eqn.outvars[0])
        return 3.0 * n, float(n)
    if name in _TRANSCENDENTAL:
        return 0.0, float(_nelem(eqn.outvars[0]))
    if name in _ELEMENTWISE_N:
        return float(_nelem(eqn.outvars[0]) * _ELEMENTWISE_N[name]), 0.0
    if name in _COLLECTIVE_REDUCE:
        return float(sum(map(_nelem, eqn.invars))), 0.0
    if name in _COLLECTIVE_FREE or name in _FREE:
        return 0.0, 0.0
    if name in ("scatter-add", "scatter-mul", "scatter-min",
                "scatter-max", "scatter_add", "scatter_mul"):
        # combining scatters do one op per update element; plain
        # "scatter" (at[].set) is movement and sits in _FREE
        ups = eqn.invars[2] if len(eqn.invars) > 2 else eqn.invars[-1]
        return float(_nelem(ups)), 0.0
    if name in ("threefry2x32", "random_bits"):
        # counter-based PRNG rounds (calibrated ~18-20 ops/element on
        # the lowered module; only sampling/dropout graphs carry these)
        return 18.0 * float(sum(map(_nelem, eqn.outvars))), 0.0
    if name in _ELEMENTWISE_1:
        return float(_nelem(eqn.outvars[0])), 0.0
    # unknown primitive: charge one flop per output element (the
    # elementwise default XLA applies) and record it so a census can
    # surface table gaps instead of silently mispricing them
    return float(sum(map(_nelem, eqn.outvars))), 0.0


# -- whole-graph accounting ------------------------------------------------

@dataclass
class Cost:
    """Analytic cost of one traced graph (totals are per device for a
    shard_map'd program: the body is the per-device program)."""
    flops: float = 0.0
    transcendentals: float = 0.0
    bytes_accessed: int = 0            # operand + result bytes, all eqns
    matmul_flops: float = 0.0          # dot_general + conv flops only
    flops_by_prim: Dict[str, float] = field(default_factory=dict)
    matmul_flops_by_dtype: Dict[str, float] = field(default_factory=dict)
    bytes_by_dtype: Dict[str, int] = field(default_factory=dict)
    eqns: int = 0
    while_loops: int = 0               # bodies counted once (trip unknown)
    unknown_prims: Dict[str, int] = field(default_factory=dict)

    @property
    def dominant_matmul_dtype(self) -> Optional[str]:
        """Operand dtype carrying the most dot/conv flops — the dtype
        whose peak rate an MFU figure should be quoted against."""
        if not self.matmul_flops_by_dtype:
            return None
        return max(self.matmul_flops_by_dtype,
                   key=self.matmul_flops_by_dtype.get)

    def fp32_matmul_fraction(self) -> float:
        """Fraction of dot/conv flops with fp32 operands — the silent
        O2-upcast signal the FlopAccountingRule budgets."""
        if not self.matmul_flops:
            return 0.0
        return self.matmul_flops_by_dtype.get("float32", 0.0) \
            / self.matmul_flops

    def to_record(self) -> Dict[str, Any]:
        """Flat JSONL payload (enriched + kind-tagged by callers)."""
        rec = {
            "flops": self.flops,
            "transcendentals": self.transcendentals,
            "bytes_accessed": int(self.bytes_accessed),
            "matmul_flops": self.matmul_flops,
            "matmul_flops_by_dtype": dict(self.matmul_flops_by_dtype),
            "bytes_by_dtype": {k: int(v)
                               for k, v in self.bytes_by_dtype.items()},
            "eqns": int(self.eqns),
        }
        if self.while_loops:
            rec["while_loops"] = int(self.while_loops)
        if self.unknown_prims:
            rec["unknown_prims"] = dict(self.unknown_prims)
        return rec


def _live_eqns(jx):
    """Backward DCE sweep: eqns whose outputs are (transitively) unused
    and that carry no effects never execute — XLA prunes them before
    lowering, so counting them would overstate the step (the classic
    case: an entry point's step drops the info dict, killing the whole
    grad-norm chain)."""
    import jax.extend.core
    needed = {id(v) for v in jx.outvars
              if isinstance(v, jax.extend.core.Var)}
    keep = [False] * len(jx.eqns)
    for i in range(len(jx.eqns) - 1, -1, -1):
        eqn = jx.eqns[i]
        if getattr(eqn, "effects", None) or any(
                id(v) in needed for v in eqn.outvars):
            keep[i] = True
            for v in eqn.invars:
                if isinstance(v, jax.extend.core.Var):
                    needed.add(id(v))
    return [e for e, k in zip(jx.eqns, keep) if k]


def _subjaxprs(eqn):
    import jax
    import jax.extend.core
    kinds = (jax.extend.core.Jaxpr, jax.extend.core.ClosedJaxpr)
    out = []
    for v in eqn.params.values():
        for s in jax.tree_util.tree_leaves(
                v, is_leaf=lambda x: isinstance(x, kinds)):
            if isinstance(s, kinds):
                out.append(s)
    return out


def jaxpr_cost(jaxpr, xla_parity: bool = False) -> Cost:
    """Analytic :class:`Cost` of a (closed) jaxpr.

    Default mode is the honest execution cost: scan bodies multiply by
    their trace-time trip count, cond costs its most expensive branch.
    ``xla_parity=True`` switches both to what ``HloCostAnalysis``
    counts on the lowered-but-unoptimized module (scan body once, cond
    branches summed) for cross-validation against
    ``Lowered.cost_analysis()``."""
    import jax.extend.core
    cost = Cost()

    def accumulate(jx, mult):
        if isinstance(jx, jax.extend.core.ClosedJaxpr):
            jx = jx.jaxpr
        for eqn in _live_eqns(jx):
            name = eqn.primitive.name
            if name == "scan":
                length = 1 if xla_parity else eqn.params.get("length", 1)
                accumulate(eqn.params["jaxpr"], mult * length)
                continue
            if name == "while":
                cost.while_loops += 1
                accumulate(eqn.params["body_jaxpr"], mult)
                accumulate(eqn.params["cond_jaxpr"], mult)
                continue
            if name == "cond":
                branches = eqn.params["branches"]
                if xla_parity:
                    for b in branches:
                        accumulate(b, mult)
                else:
                    best, best_cost = None, -1.0
                    for b in branches:
                        sub = jaxpr_cost(b, xla_parity=False)
                        if sub.flops > best_cost:
                            best, best_cost = b, sub.flops
                    if best is not None:
                        accumulate(best, mult)
                continue
            subs = _subjaxprs(eqn)
            if subs:
                for s in subs:
                    accumulate(s, mult)
                continue
            f, t = eqn_flops(eqn)
            cost.flops += mult * f
            cost.transcendentals += mult * t
            cost.eqns += 1
            if f:
                cost.flops_by_prim[name] = \
                    cost.flops_by_prim.get(name, 0.0) + mult * f
            if name in ("dot_general", "conv_general_dilated"):
                cost.matmul_flops += mult * f
                dt = str(eqn.invars[0].aval.dtype)
                cost.matmul_flops_by_dtype[dt] = \
                    cost.matmul_flops_by_dtype.get(dt, 0.0) + mult * f
            if name not in _KNOWN_PRIMS:
                cost.unknown_prims[name] = \
                    cost.unknown_prims.get(name, 0) + 1
            for v in list(eqn.invars) + list(eqn.outvars):
                b = _nbytes(v)
                if b:
                    cost.bytes_accessed += int(mult * b)
                    dt = str(v.aval.dtype)
                    cost.bytes_by_dtype[dt] = \
                        cost.bytes_by_dtype.get(dt, 0) + int(mult * b)

    accumulate(jaxpr, 1.0)
    return cost


def xla_cost(stage) -> Dict[str, float]:
    """``Lowered.cost_analysis()`` / ``Compiled.cost_analysis()`` as a
    flat dict with at least ``flops``/``transcendentals`` keys."""
    out = dict(stage.cost_analysis())
    out.setdefault("flops", 0.0)
    out.setdefault("transcendentals", 0.0)
    return out
