"""The core rule set: every hot-path invariant the repo has paid to
learn, pinned mechanically.

Expectation schema (per entry point, all keys optional — a rule only
runs where its key is present):

``host_transfer`` (always on; opt out with ``allow_host_transfers``)
    No host-boundary primitive may appear in a jitted hot graph.

``donation``::

    {"expect_donated": ("ids", "cache", "keys"),   # must be aliased
     "forbid_donated": ("temps",),                 # extra local bans
     "min_aliased": None}                          # default: donated leaf count

    The global blocklist (``serving.DONATION_BLOCKLIST``: per-slot
    length vectors ``cur_len``/``n_new``) is enforced on every donation
    entry point — donating that argnum class corrupted executables
    reloaded from the persistent XLA:CPU compile cache (PR 2).

``amp``::

    {"opt_level": "O2", "conv_dtype": "bfloat16", "dot_dtype": "bfloat16",
     "min_convs": 40, "min_dots": 0, "dot_min_elems": 256}

    ``conv_dtype``/``dot_dtype`` of ``None`` skips that op family.  The
    ``min_*`` floors keep the rule non-vacuous: an empty graph is a
    finding, not a pass.

``layout``::

    {"min_activation_elems": 12288, "allowed_6d_rearranges": 0}

    No transpose on activation-sized tensors in channels-last graphs;
    the 6-D block rearrange inside space_to_depth is the one sanctioned
    exception (budgeted, not open-ended).

``flops``::

    {"expected_flops": 3.8e6, "rtol": 0.05,
     "max_fp32_matmul_fraction": 0.02, "min_matmul_flops": 1e6}

    Analytic FLOP accounting (``observability.costmodel``):
    ``expected_flops`` pins the whole-graph count within ``rtol`` (an
    unexplained delta means the graph grew or lost work nobody
    budgeted); ``max_fp32_matmul_fraction`` caps the share of dot/conv
    FLOPs running on fp32 operands — under a bf16 compute policy a
    silent upcast moves flops into fp32 exactly where the arithmetic
    is, even when each individual op dodges the amp-dtype rule's
    element thresholds.  ``min_matmul_flops`` is the vacuity floor.

``memory``::

    {"budget_bytes": 500_000_000,
     "max_live_to_argument_ratio": 4.0,
     "temp_budget_bytes_by_dtype": {"float32": 250_000_000}}

    Analytic peak-live-bytes budgets (``observability.memory.
    jaxpr_live_bytes`` — a static last-use scan, no compile on the
    lint path).  ``budget_bytes`` caps the absolute peak;
    ``max_live_to_argument_ratio`` caps peak live bytes relative to
    the graph's argument+const bytes (portable across model sizes: a
    train step that suddenly holds a second copy of everything doubles
    the ratio no matter the model); the per-dtype temp budgets catch
    an fp32 upcast doubling fp32 temp bytes under O2 while the bf16
    peak is unchanged.

``collectives``::

    {"counts": {"psum": 4}, "payload_bytes": 40038408,
     "payload_bytes_by_primitive": {"psum": 40038408},
     "interleaving": {"min_payload_bytes": 1056,
                      "min_matmuls_after": 1}}

    Exact comm accounting: any collective primitive not named in
    ``counts`` is budgeted at zero, and the total on-wire payload must
    match to the byte (``payload_tolerance`` relaxes it when needed).
    ``payload_bytes_by_primitive`` (optional) additionally pins the
    per-primitive split — for the hierarchical DDP topology that is the
    fabric-level split: the bucket psum (or compressed bf16 all_gather)
    payload is exactly the DCN hop, so a bucket sneaking a full-size
    cross-host psum flags even if the total happens to balance.
    ``parallel.plan_collective_expectations`` derives all three fields
    from ``allreduce_comm_plan``.

    ``interleaving`` (optional) is the overlapped-schedule pin (PR 14):
    in jaxpr program order, the FIRST gradient-bucket collective (the
    first collective eqn moving at least ``min_payload_bytes`` — which
    separates grad buckets from the step's 4-byte scalar psums) must
    appear BEFORE the last conv/dot eqn, with at least
    ``min_matmuls_after`` matmul eqns after it.  A reduce-after-
    backward schedule has identical counts and payloads — only eqn
    POSITIONS distinguish it — so this is the one check that can tell
    the two apart statically.
    ``parallel.overlap_collective_expectations`` derives it (and the
    census) from ``overlap_comm_schedule``.

``numerics``::

    {"baseline": "ddp_resnet18_o2", "enabled": True,
     "extra_collectives": {"psum": 1}, "extra_payload_bytes": 520}

    The numerics-instrumentation pin (PR 9): enabled ⇒ zero host
    transfers + collective census exactly the baseline's plus the
    digest plan's delta; disabled ⇒ the step traces to the
    byte-identical jaxpr of the baseline (no residue).

``supervisor``::

    {"baseline": "ddp_resnet18_o2", "enabled": True}

    The operational-plane pin (PR 10): a run-supervised step must
    trace to the BYTE-IDENTICAL jaxpr of its unsupervised baseline
    and contain zero host-transfer primitives — enabled or disabled,
    because the supervisor consumes host-side flush points only and
    ``RunSupervisor.wrap_step`` is an identity by contract.

``sharding``::

    {"mesh_axes": {"data": 8},
     "divergent_outputs": 40,            # default 0
     "max_replicated_bytes": None}       # optional budget

    Spec-vs-mesh consistency (PR 18): the traced ``shard_map``'s mesh
    axes must be exactly what ``topology.make_mesh`` was asked for,
    every axis named in in/out specs must exist, every sharded dim
    must divide across its axes, and the number of outputs whose spec
    claims MORE agreement than ``analysis.sharding``'s propagated
    partition guarantees is pinned (``divergent_outputs`` — 40 on the
    resnet DDP entry points: two unsynced BatchNorm running stats per
    BN layer, the documented non-SyncBN semantics; any OTHER count,
    up or down, is a finding, so a new missing collective flags and a
    fixed sync forces a ratchet).  ``max_replicated_bytes`` caps the
    replication ledger's world-total duplicate bytes — the budget
    ZeRO-2/3 stages (ROADMAP item 2) will ratchet down.

``resharding``::

    {"planned": {"reduce_scatter": [38400, 22344088],
                 "all_gather": [9600, 5586022]},
     "budget": {"all_gather": 0}}        # extra eqns allowed, default 0

    The resharding census (PR 18): every placement-changing collective
    (``all_gather``/``all_to_all``/``reduce_scatter``/``pgather``) in
    the hot graph must be explained — matched one-for-one by payload
    against the comm plan's per-eqn list
    (``parallel.plan_resharding_expectations`` derives it from
    ``allreduce_comm_plan`` / ``overlap_comm_schedule``) or covered by
    a declared per-primitive ``budget``.  An unplanned gather (the
    classic "XLA silently replicated my shard") is an error naming the
    culprit operand's shape, dtype, payload, and statically inferred
    spec; a planned payload missing from the graph flags too
    (plan/graph desync).  psum/pmax/pmin stay the collective rule's
    business — a reduce changes values, not placement, which is why
    an unplanned all-gather can hide behind an identical psum census.
"""

from __future__ import annotations

from collections import Counter
from typing import List

from .core import Rule, Finding, register_rule
from . import graphs as G

__all__ = ["HostTransferRule", "DonationRule", "AmpDtypeRule",
           "LayoutRule", "CollectiveRule", "FlopAccountingRule",
           "MemoryBudgetRule", "NumericsRule", "SupervisorRule",
           "SpecConsistencyRule", "ReshardingCensusRule"]


@register_rule
class HostTransferRule(Rule):
    """No device_get/callback/transfer primitives inside jitted hot
    graphs — each one is a per-dispatch host round-trip."""

    name = "host-transfer"
    expect_key = None                        # unconditional

    def applies(self, ep):
        return not ep.expect.get("allow_host_transfers", False)

    def check(self, ep, graph) -> List[Finding]:
        hits = Counter(e.primitive.name
                       for e in G.host_transfer_eqns(graph.jaxpr))
        return [self.finding(
            ep, f"host-transfer primitive {prim!r} appears {n}x in the "
                f"jitted graph — a per-dispatch host sync",
            primitive=prim, count=n) for prim, n in sorted(hits.items())]


@register_rule
class DonationRule(Rule):
    """Every buffer the entry point promises to donate is actually
    aliased in the lowered module; blocklisted per-slot length vectors
    are never donated; no donated buffer is shared (double donation)."""

    name = "donation"
    expect_key = "donation"

    def check(self, ep, graph) -> List[Finding]:
        from ..serving import DONATION_BLOCKLIST
        want = ep.expect["donation"]
        out: List[Finding] = []
        if graph.arg_names is None:
            return [self.finding(
                ep, "donation expectation without arg_names — cannot "
                    "map donated buffers to arguments")]
        donated, partial = G.donated_arg_names(graph.lowered,
                                               graph.arg_names)
        for name in want.get("expect_donated", ()):
            if name not in donated:
                out.append(self.finding(
                    ep, f"argument {name!r} must be donated (multi-GB "
                        f"buffer mutated every dispatch) but the "
                        f"lowering does not alias it", argument=name))
        forbid = tuple(want.get("forbid_donated", ())) + \
            tuple(DONATION_BLOCKLIST)
        for name in forbid:
            if name in donated:
                blocked = name in DONATION_BLOCKLIST
                out.append(self.finding(
                    ep, f"argument {name!r} is donated but "
                        + ("is on the donation blocklist (per-slot "
                           "length vectors corrupt executables reloaded "
                           "from the persistent XLA compile cache — "
                           "PR 2 gotcha)" if blocked else
                           "this entry point forbids donating it"),
                    argument=name, blocklisted=blocked))
        for name in partial:
            out.append(self.finding(
                ep, f"argument {name!r} is only partially donated — "
                    f"some leaves alias, some keep a second copy alive",
                argument=name))
        # the lowering must honor every requested donation
        import jax
        args_info, _ = graph.lowered.args_info
        n_donated = sum(bool(i.donated)
                        for i in jax.tree_util.tree_leaves(args_info))
        min_aliased = want.get("min_aliased")
        if min_aliased is None:
            min_aliased = n_donated
        n_aliased = G.aliased_output_count(graph.stablehlo)
        if n_aliased < min_aliased:
            out.append(self.finding(
                ep, f"lowering aliases {n_aliased} buffers but "
                    f"{min_aliased} donations were requested — XLA "
                    f"silently dropped some (both copies stay alive)",
                aliased=n_aliased, requested=min_aliased))
        if graph.example_args is not None:
            for dup in G.duplicate_donated_leaves(
                    graph.lowered, graph.arg_names, graph.example_args):
                out.append(self.finding(
                    ep, f"double donation: {dup} — XLA rejects donating "
                        f"one buffer twice (per-layer cache allocation "
                        f"required; no dict(layer) shallow copies)",
                    duplicate=dup))
        return out


@register_rule
class AmpDtypeRule(Rule):
    """Conv/matmul operand dtypes match the O-level policy — forward,
    dgrad, and wgrad.  A single silently-upcast fp32 conv halves MXU
    rate and doubles HBM traffic on that op; fp32 accumulation belongs
    in ``preferred_element_type``, not operand upcasts."""

    name = "amp-dtype"
    expect_key = "amp"

    def check(self, ep, graph) -> List[Finding]:
        want = ep.expect["amp"]
        out: List[Finding] = []
        lvl = want.get("opt_level", "?")

        conv_dtype = want.get("conv_dtype")
        if conv_dtype is not None:
            convs = G.conv_eqns(graph.jaxpr)
            floor = want.get("min_convs", 1)
            if len(convs) < floor:
                out.append(self.finding(
                    ep, f"vacuous check: expected >= {floor} convs "
                        f"(fwd+dgrad+wgrad) in the {lvl} step, traced "
                        f"{len(convs)}", convs=len(convs), floor=floor))
            bad = Counter(
                (str(e.invars[0].aval.dtype), str(e.invars[1].aval.dtype))
                for e in convs
                if not all(str(v.aval.dtype) == conv_dtype
                           for v in e.invars[:2]))
            for (lhs, rhs), n in sorted(bad.items()):
                out.append(self.finding(
                    ep, f"{n} conv(s) with ({lhs}, {rhs}) operands in "
                        f"the {lvl} step — policy requires {conv_dtype} "
                        f"(silent upcast)",
                    lhs=lhs, rhs=rhs, count=n, expected=conv_dtype))

        dot_dtype = want.get("dot_dtype")
        if dot_dtype is not None:
            dots = G.large_dot_eqns(graph.jaxpr,
                                    want.get("dot_min_elems", 256))
            floor = want.get("min_dots", 1)
            if len(dots) < floor:
                out.append(self.finding(
                    ep, f"vacuous check: expected >= {floor} large dots "
                        f"in the {lvl} step, traced {len(dots)}",
                    dots=len(dots), floor=floor))
            bad = Counter(
                tuple(str(v.aval.dtype) for v in e.invars) for e in dots
                if not all(str(v.aval.dtype) == dot_dtype
                           for v in e.invars))
            for dts, n in sorted(bad.items()):
                out.append(self.finding(
                    ep, f"{n} large dot(s) with {dts} operands in the "
                        f"{lvl} step — policy requires {dot_dtype}",
                    operands=list(dts), count=n, expected=dot_dtype))
        return out


@register_rule
class LayoutRule(Rule):
    """Channels-last graphs stay transpose-free on activation-sized
    tensors — the whole point of the NHWC mode; a layout leak pays a
    relayout on every step."""

    name = "layout"
    expect_key = "layout"

    def check(self, ep, graph) -> List[Finding]:
        want = ep.expect["layout"]
        min_elems = want["min_activation_elems"]
        out: List[Finding] = []
        big = G.transpose_eqns(graph.jaxpr, min_elems)
        # the 6-D block rearrange inside F.space_to_depth is the one
        # sanctioned activation transpose (forward-only); it gets a
        # budget, not a blanket pass
        six_d = [e for e in big if e.invars[0].aval.ndim == 6]
        other = [e for e in big if e.invars[0].aval.ndim != 6]
        for e in other:
            out.append(self.finding(
                ep, f"activation-sized transpose "
                    f"{tuple(e.invars[0].aval.shape)} "
                    f"(permutation {e.params.get('permutation')}) in a "
                    f"channels-last graph — layout leak",
                shape=list(map(int, e.invars[0].aval.shape)),
                permutation=list(e.params.get("permutation", ()))))
        budget = want.get("allowed_6d_rearranges", 0)
        if len(six_d) > budget:
            out.append(self.finding(
                ep, f"{len(six_d)} 6-D block rearranges, budget is "
                    f"{budget} (space_to_depth runs forward-only; a "
                    f"second copy means gradient flows through the "
                    f"rearrange)", count=len(six_d), budget=budget))
        return out


@register_rule
class FlopAccountingRule(Rule):
    """The analytic FLOP count stays explained: totals within a pinned
    tolerance, and under a reduced-precision policy no meaningful
    share of matmul flops runs in fp32.  This is the flops-weighted
    twin of the amp-dtype rule: that one counts *ops*, this one counts
    *work* — a single upcast conv carrying half the step's FLOPs flags
    here even if 39 other convs are clean."""

    name = "flop-accounting"
    expect_key = "flops"

    def check(self, ep, graph) -> List[Finding]:
        from ..observability import costmodel
        want = ep.expect["flops"]
        out: List[Finding] = []
        cost = ep.cost() if hasattr(ep, "cost") \
            else costmodel.jaxpr_cost(graph.jaxpr)
        expected = want.get("expected_flops")
        if expected is not None:
            rtol = want.get("rtol", 0.05)
            if expected <= 0:
                out.append(self.finding(
                    ep, f"expected_flops must be > 0, got {expected}"))
            elif abs(cost.flops - expected) > rtol * expected:
                out.append(self.finding(
                    ep, f"unexplained FLOP delta: analytic count is "
                        f"{cost.flops:.4g}, expected {expected:.4g} "
                        f"(+/- {rtol:.0%}) — the graph gained or lost "
                        f"arithmetic nobody budgeted",
                    flops=cost.flops, expected_flops=expected,
                    rtol=rtol))
        cap = want.get("max_fp32_matmul_fraction")
        if cap is not None:
            floor = want.get("min_matmul_flops", 1.0)
            if cost.matmul_flops < floor:
                out.append(self.finding(
                    ep, f"vacuous check: expected >= {floor:.4g} "
                        f"dot/conv FLOPs, traced {cost.matmul_flops:.4g}",
                    matmul_flops=cost.matmul_flops, floor=floor))
            frac = cost.fp32_matmul_fraction()
            if frac > cap:
                fp32 = cost.matmul_flops_by_dtype.get("float32", 0.0)
                out.append(self.finding(
                    ep, f"{frac:.1%} of dot/conv FLOPs "
                        f"({fp32:.4g} of {cost.matmul_flops:.4g}) run "
                        f"on fp32 operands — cap is {cap:.1%} (silent "
                        f"upcast where the work is)",
                    fp32_matmul_fraction=frac, cap=cap,
                    fp32_matmul_flops=fp32,
                    matmul_flops=cost.matmul_flops))
        return out


@register_rule
class MemoryBudgetRule(Rule):
    """Peak live bytes stay within budget — the static early warning:
    a refactor that keeps a dead copy of the cache, un-donates a buffer
    upstream, or upcasts a temp tree to fp32 moves the analytic
    liveness peak long before anyone reruns the benchmark on the
    chip."""

    name = "memory-budget"
    expect_key = "memory"

    def check(self, ep, graph) -> List[Finding]:
        from ..observability import memory
        want = ep.expect["memory"]
        out: List[Finding] = []
        lb = memory.jaxpr_live_bytes(graph.jaxpr)
        peak = lb["peak_live_bytes"]
        budget = want.get("budget_bytes")
        if budget is not None and peak > budget:
            out.append(self.finding(
                ep, f"analytic peak live bytes {peak:,} exceed the "
                    f"{budget:,}-byte budget",
                peak_live_bytes=peak, budget_bytes=budget))
        ratio_cap = want.get("max_live_to_argument_ratio")
        if ratio_cap is not None:
            args = max(lb["argument_bytes"], 1)
            ratio = peak / args
            if ratio > ratio_cap:
                out.append(self.finding(
                    ep, f"peak live bytes are {ratio:.2f}x the "
                        f"argument bytes ({peak:,} vs {args:,}); "
                        f"budget is {ratio_cap}x — the graph is "
                        f"holding duplicate state",
                    peak_live_bytes=peak, argument_bytes=args,
                    ratio=round(ratio, 3), cap=ratio_cap))
        for dt, cap in sorted(
                want.get("temp_budget_bytes_by_dtype", {}).items()):
            got = lb["peak_temp_bytes_by_dtype"].get(dt, 0)
            if got > cap:
                out.append(self.finding(
                    ep, f"peak {dt} temp bytes {got:,} exceed the "
                        f"{cap:,}-byte budget — e.g. an fp32 upcast "
                        f"materializing a second activation tree",
                    dtype=dt, peak_temp_bytes=got, budget_bytes=cap))
        return out


@register_rule
class NumericsRule(Rule):
    """Numerics instrumentation is free where enabled and ABSENT where
    disabled (PR 9's audit pin).  Expectation::

        {"baseline": "ddp_resnet18_o2",      # name or EntryPoint/Graph
         "enabled": True,
         "extra_collectives": {"psum": 1},   # the divergence digest
         "extra_payload_bytes": 520}

    Enabled: the instrumented step must contain ZERO host-transfer
    primitives (the accounting is device-resident; ``flush()`` is the
    one fetch, outside the step) and its collective census must be
    EXACTLY the baseline's plus the planned delta
    (``numerics.digest_comm_plan`` derives it) — an instrumentation
    change that sneaks an extra collective or callback into the hot
    loop flags here before any profiler sees it.  Disabled: the step
    must trace to the byte-identical jaxpr of the baseline — the
    off-switch leaves no residue."""

    name = "numerics"
    expect_key = "numerics"

    @staticmethod
    def _baseline_graph(want):
        base = want.get("baseline")
        if base is None:
            return None
        if isinstance(base, str):
            from .entry_points import get as _get_ep
            return _get_ep(base).graph()
        return base.graph() if hasattr(base, "graph") else base

    def check(self, ep, graph) -> List[Finding]:
        want = ep.expect["numerics"]
        out: List[Finding] = []
        base = self._baseline_graph(want)
        if not want.get("enabled", True):
            if base is None:
                return [self.finding(
                    ep, "a disabled-numerics expectation needs a "
                        "baseline to compare against")]
            ours, theirs = str(graph.jaxpr), str(base.jaxpr)
            if ours != theirs:
                n_eq = sum(1 for _ in G.walk_jaxpr(graph.jaxpr))
                n_eq_b = sum(1 for _ in G.walk_jaxpr(base.jaxpr))
                out.append(self.finding(
                    ep, f"numerics residue: the disabled-numerics step "
                        f"traces to a different jaxpr than the "
                        f"uninstrumented baseline ({n_eq} vs {n_eq_b} "
                        f"eqns) — the off-switch must be free",
                    eqns=n_eq, baseline_eqns=n_eq_b))
            return out
        hits = Counter(e.primitive.name
                       for e in G.host_transfer_eqns(graph.jaxpr))
        for prim, n in sorted(hits.items()):
            out.append(self.finding(
                ep, f"numerics-instrumented step contains "
                    f"host-transfer primitive {prim!r} {n}x — the "
                    f"accounting must accumulate device-resident "
                    f"(flush() is the one host fetch, outside the "
                    f"step)", primitive=prim, count=n))
        if base is not None:
            got = Counter(e.primitive.name
                          for e in G.collective_eqns(graph.jaxpr))
            base_counts = Counter(
                e.primitive.name for e in G.collective_eqns(base.jaxpr))
            extra = dict(want.get("extra_collectives", {}))
            for prim in sorted(set(got) | set(base_counts) | set(extra)):
                w = base_counts.get(prim, 0) + extra.get(prim, 0)
                g = got.get(prim, 0)
                if g != w:
                    out.append(self.finding(
                        ep, f"expected {w} {prim} eqn(s) (baseline "
                            f"{base_counts.get(prim, 0)} + planned "
                            f"numerics delta {extra.get(prim, 0)}), "
                            f"instrumented graph has {g}",
                        primitive=prim, expected=w, got=g,
                        baseline=base_counts.get(prim, 0)))
            if "extra_payload_bytes" in want:
                ours = sum(G.eqn_payload_bytes(e)
                           for e in G.collective_eqns(graph.jaxpr))
                theirs = sum(G.eqn_payload_bytes(e)
                             for e in G.collective_eqns(base.jaxpr))
                delta, w = ours - theirs, want["extra_payload_bytes"]
                if delta != w:
                    out.append(self.finding(
                        ep, f"numerics adds {delta} collective payload "
                            f"bytes over the baseline, the digest plan "
                            f"budgets exactly {w}",
                        payload_delta=delta, expected_delta=w))
        return out


@register_rule
class SupervisorRule(Rule):
    """A run-supervised step is the UNSUPERVISED step, to the byte
    (PR 10's operational-plane pin).  Expectation::

        {"baseline": "ddp_resnet18_o2", "enabled": True}

    Unlike the numerics monitor — device-resident state that is free
    only when *disabled* — the supervisor holds no device state at
    all: it consumes signals the host already fetched at existing
    flush points, and ``RunSupervisor.wrap_step`` returns the step
    function unchanged.  So the pinned property is the same in BOTH
    directions: the supervised step's jaxpr must be byte-identical to
    the baseline's and contain zero host-transfer primitives, enabled
    or disabled.  A supervisor change that instruments the step —
    smuggles a callback to read the loss per step, adds a collective,
    threads extra carry state — flags here before any profiler sees
    the regression (mutation-tested both ways in
    tests/test_analysis.py)."""

    name = "supervisor"
    expect_key = "supervisor"

    def check(self, ep, graph) -> List[Finding]:
        want = ep.expect["supervisor"]
        out: List[Finding] = []
        hits = Counter(e.primitive.name
                       for e in G.host_transfer_eqns(graph.jaxpr))
        for prim, n in sorted(hits.items()):
            out.append(self.finding(
                ep, f"supervised step contains host-transfer "
                    f"primitive {prim!r} {n}x — the supervisor reads "
                    f"existing host flush points, it never instruments "
                    f"the jitted step", primitive=prim, count=n))
        base = NumericsRule._baseline_graph(want)
        if base is None:
            out.append(self.finding(
                ep, "a supervisor expectation needs a baseline to "
                    "compare against"))
            return out
        ours, theirs = str(graph.jaxpr), str(base.jaxpr)
        if ours != theirs:
            n_eq = sum(1 for _ in G.walk_jaxpr(graph.jaxpr))
            n_eq_b = sum(1 for _ in G.walk_jaxpr(base.jaxpr))
            state = ("enabled" if want.get("enabled", True)
                     else "disabled")
            out.append(self.finding(
                ep, f"supervisor residue: the {state}-supervisor step "
                    f"traces to a different jaxpr than the "
                    f"unsupervised baseline ({n_eq} vs {n_eq_b} eqns) "
                    f"— wrap_step must be an identity in both "
                    f"directions", eqns=n_eq, baseline_eqns=n_eq_b))
        return out


@register_rule
class CollectiveRule(Rule):
    """The comm pattern is exactly what the algorithm assumes: expected
    psum/all-gather eqn counts and on-wire payload bytes in DDP/TP/ZeRO
    graphs.  A missing psum is a wrong answer; an extra one is a
    regression the profiler would surface weeks later."""

    name = "collective"
    expect_key = "collectives"

    def check(self, ep, graph) -> List[Finding]:
        want = ep.expect["collectives"]
        out: List[Finding] = []
        eqns = G.collective_eqns(graph.jaxpr)
        got = Counter(e.primitive.name for e in eqns)
        expected = dict(want.get("counts", {}))
        for prim in sorted(set(got) | set(expected)):
            g, w = got.get(prim, 0), expected.get(prim, 0)
            if g != w:
                out.append(self.finding(
                    ep, f"expected {w} {prim} eqn(s), graph has {g}",
                    primitive=prim, expected=w, got=g))
        if "payload_bytes" in want:
            total = sum(G.eqn_payload_bytes(e) for e in eqns)
            w = want["payload_bytes"]
            tol = want.get("payload_tolerance", 0)
            if abs(total - w) > tol:
                out.append(self.finding(
                    ep, f"collective payload is {total} bytes on the "
                        f"wire, expected {w}"
                        + (f" (+/- {tol})" if tol else ""),
                    payload_bytes=total, expected_bytes=w))
        if "payload_bytes_by_primitive" in want:
            got_by = Counter()
            for e in eqns:
                got_by[e.primitive.name] += G.eqn_payload_bytes(e)
            want_by = dict(want["payload_bytes_by_primitive"])
            tol = want.get("payload_tolerance", 0)
            # only a hierarchical plan (it budgets a reduce_scatter per
            # bucket) makes the per-primitive split a fabric-level
            # statement — don't point a flat-plan mismatch at ICI/DCN
            hier = "reduce_scatter" in want.get("counts", want_by)
            for prim in sorted(set(got_by) | set(want_by)):
                g, w = got_by.get(prim, 0), want_by.get(prim, 0)
                if abs(g - w) > tol:
                    out.append(self.finding(
                        ep, f"{prim} payload is {g} bytes on the wire, "
                            f"expected {w}"
                            + (f" (+/- {tol})" if tol else "")
                            + (" — the per-primitive split is the "
                               "fabric-level split under a "
                               "hierarchical comm plan (the psum hop "
                               "is the DCN payload)" if hier else ""),
                        primitive=prim, payload_bytes=g,
                        expected_bytes=w))
        inter = want.get("interleaving")
        if inter:
            out.extend(self._check_interleaving(ep, graph, inter))
        return out

    def _check_interleaving(self, ep, graph, inter) -> List[Finding]:
        """The overlapped-schedule position pin: the first issued
        gradient bucket's reduction must sit AHEAD of the tail of the
        backward in jaxpr program order — a reduce-after-backward
        graph (every collective trailing every matmul) has the exact
        same census and payloads, so only the eqn positions can flag
        it.  Scalar psums (axis size, loss pmean) are excluded by the
        ``min_payload_bytes`` threshold, which
        ``parallel.overlap_collective_expectations`` derives as the
        smallest per-level hop any planned bucket puts on the wire."""
        out: List[Finding] = []
        thresh = int(inter.get("min_payload_bytes", 16))
        ordered = list(G.walk_jaxpr(graph.jaxpr))
        first_coll = None
        coll_pos: List[int] = []
        matmul_pos: List[int] = []
        for i, e in enumerate(ordered):
            name = e.primitive.name
            if (name in G.COLLECTIVE_PRIMS
                    and G.eqn_payload_bytes(e) >= thresh):
                if first_coll is None:
                    first_coll = i
                coll_pos.append(i)
            if name in ("dot_general", "conv_general_dilated"):
                matmul_pos.append(i)
        if first_coll is None:
            return [self.finding(
                ep, f"vacuous interleaving check: no collective eqn "
                    f"moves >= {thresh} bytes — there is no gradient "
                    f"bucket reduction to position",
                min_payload_bytes=thresh)]
        if not matmul_pos:
            return [self.finding(
                ep, "vacuous interleaving check: the graph has no "
                    "conv/dot eqns to interleave the reduction with")]
        last_mm = matmul_pos[-1]
        if first_coll > last_mm:
            out.append(self.finding(
                ep, f"reduce-after-backward schedule: the first "
                    f"gradient-bucket collective (eqn #{first_coll}) "
                    f"trails the last matmul (eqn #{last_mm}) — the "
                    f"overlapped schedule must issue the first "
                    f"bucket's reduction while later stages' backward "
                    f"is still being emitted",
                first_collective_eqn=first_coll,
                last_matmul_eqn=last_mm))
            return out
        after = sum(1 for i in matmul_pos if i > first_coll)
        floor = int(inter.get("min_matmuls_after", 1))
        if after < floor:
            out.append(self.finding(
                ep, f"only {after} matmul eqn(s) follow the first "
                    f"gradient-bucket collective (eqn #{first_coll}); "
                    f"the overlap schedule budgets >= {floor} — "
                    f"nothing is left for the reduction to overlap "
                    f"with", matmuls_after=after, floor=floor,
                first_collective_eqn=first_coll))
        # the per-stage pin: one bucket sneaking ahead of the last
        # matmul satisfies the first-collective check even if every
        # OTHER stage's reduction collapsed to reduce-after-backward.
        # The schedule knows exactly how many bucket eqns belong to
        # stages issued before the last one, so it declares a floor on
        # qualifying collectives preceding the last matmul
        # (parallel.overlap_collective_expectations).
        coll_floor = inter.get("min_collectives_before_last_matmul")
        if coll_floor is not None:
            before = sum(1 for i in coll_pos if i < last_mm)
            if before < int(coll_floor):
                out.append(self.finding(
                    ep, f"only {before} gradient-bucket collective(s) "
                        f"precede the last matmul (eqn #{last_mm}); "
                        f"the overlap schedule issues "
                        f">= {int(coll_floor)} before the final "
                        f"stage's backward — the staged overlap "
                        f"partially collapsed to "
                        f"reduce-after-backward",
                    collectives_before=before,
                    floor=int(coll_floor),
                    last_matmul_eqn=last_mm))
        return out


# a declared max_replicated_bytes budget whose measured ledger value
# sits below this fraction of it is "stale": the deterministic
# propagation means real headroom never exceeds the declaration slack
# (entry points declare ~1.05x measured), so >25% slack is a budget
# that outlived a ZeRO-stage (or sharding) change and must ratchet down
RATCHET_FRACTION = 0.75


@register_rule
class SpecConsistencyRule(Rule):
    """``shard_map`` specs are consistent with the mesh and with what
    the body actually computes: axes exist, sharded dims divide, the
    mesh is the one ``topology.make_mesh`` was asked for, and the
    number of outputs claiming more agreement than the propagated
    partition guarantees is exactly the declared count.  With
    ``check_vma=False`` (how every train entry point runs) NOTHING at
    runtime checks the last property — a replicated out-spec over a
    still-varying value silently keeps one replica's answer."""

    name = "sharding"
    expect_key = "sharding"

    def check(self, ep, graph) -> List[Finding]:
        from . import sharding as S
        want = ep.expect["sharding"]
        out: List[Finding] = []
        eqns = S.shard_map_eqns(graph.jaxpr)
        if not eqns:
            return [self.finding(
                ep, "a sharding expectation is declared but the graph "
                    "traces no shard_map eqn")]
        analyses = [S.analyze_shard_map(e) for e in eqns]
        divergent: List[str] = []
        for eqn, a in zip(eqns, analyses):
            for msg in S.check_shard_map_specs(
                    eqn, want.get("mesh_axes"), analysis=a):
                out.append(self.finding(ep, msg))
            divergent.extend(S.divergent_output_claims(eqn, a))
        declared = int(want.get("divergent_outputs", 0))
        if len(divergent) != declared:
            sample = "; ".join(divergent[:3])
            if len(divergent) > declared:
                out.append(self.finding(
                    ep, f"{len(divergent)} output spec(s) claim more "
                        f"agreement than the propagated partitions "
                        f"guarantee; {declared} are declared (the "
                        f"non-synced BatchNorm stats class) — a "
                        f"collective went missing before a return. "
                        f"First undeclared: {sample}",
                    divergent=len(divergent), declared=declared))
            else:
                out.append(self.finding(
                    ep, f"only {len(divergent)} divergent output "
                        f"claim(s) but {declared} are declared — "
                        f"ratchet divergent_outputs down",
                    divergent=len(divergent), declared=declared))
        budget = want.get("max_replicated_bytes")
        if budget is not None:
            repl = sum(a.replicated_bytes for a in analyses)
            if repl > int(budget):
                worst = max(
                    (arg for a in analyses for arg in a.args),
                    key=lambda g: g.replicated_bytes(analyses[0].world))
                out.append(self.finding(
                    ep, f"replication ledger reports {repl:,} "
                        f"world-total duplicate bytes, budget is "
                        f"{int(budget):,} — largest contributor: "
                        f"{worst.dtype}{list(worst.shape)} x"
                        f"{worst.replication_factor} ({worst.spec})",
                    replicated_bytes=repl, budget_bytes=int(budget)))
            elif repl < int(int(budget) * RATCHET_FRACTION):
                # the ratchet-both-ways contract: a ZeRO stage that
                # collapses the replicated state must tighten the
                # declared budget with it, or the budget silently
                # stops guarding anything (a later regression back to
                # full replication would still "pass")
                out.append(self.finding(
                    ep, f"replication budget is stale: the ledger "
                        f"reports {repl:,} world-total duplicate "
                        f"bytes but {int(budget):,} are budgeted "
                        f"(> {100 - int(RATCHET_FRACTION * 100)}% "
                        f"headroom) — ratchet max_replicated_bytes "
                        f"down to the measured value",
                    replicated_bytes=repl, budget_bytes=int(budget)))
        return out


@register_rule
class ReshardingCensusRule(Rule):
    """Every placement-changing collective in the hot graph is
    explained by the comm plan or a declared budget.  The collective
    rule pins counts and payload totals — but an unplanned all-gather
    introduced while a planned one is dropped can leave both intact.
    This rule matches graph eqns against the plan's per-eqn payload
    list one by one, and names the operand (shape, dtype, inferred
    spec) of anything unexplained — the "XLA silently replicated my
    shard" failure, caught statically."""

    name = "resharding-census"
    expect_key = "resharding"

    def check(self, ep, graph) -> List[Finding]:
        from . import sharding as S
        want = ep.expect["resharding"]
        out: List[Finding] = []
        eqns = S.shard_map_eqns(graph.jaxpr)
        if not eqns:
            return [self.finding(
                ep, "a resharding expectation is declared but the "
                    "graph traces no shard_map eqn")]
        sites = [s for e in eqns for s in S.analyze_shard_map(e).sites
                 if s.primitive in S.RESHARD_PRIMS]
        planned = {prim: list(pays)
                   for prim, pays in want.get("planned", {}).items()}
        budget = {k: int(v) for k, v in want.get("budget", {}).items()}
        unplanned: dict = {}
        for s in sites:
            pool = planned.get(s.primitive, [])
            if s.payload_bytes in pool:
                pool.remove(s.payload_bytes)
            else:
                unplanned.setdefault(s.primitive, []).append(s)
        for prim in sorted(unplanned):
            extra = unplanned[prim]
            allowed = budget.get(prim, 0)
            if len(extra) <= allowed:
                continue
            for s in extra:
                out.append(self.finding(
                    ep, f"unplanned {s.describe()} — not in the comm "
                        f"plan's {prim} payload list and beyond the "
                        f"declared budget of {allowed}; an unexplained "
                        f"resharding in the hot path",
                    primitive=s.primitive,
                    payload_bytes=s.payload_bytes,
                    shape=list(map(int, s.shape)), dtype=s.dtype,
                    spec=s.spec, budget=allowed))
        for prim in sorted(planned):
            left = planned[prim]
            if left:
                out.append(self.finding(
                    ep, f"comm plan schedules {len(left)} {prim} "
                        f"eqn(s) of {sorted(left)} bytes that the "
                        f"traced graph never issues — plan/graph "
                        f"desync",
                    primitive=prim, missing=len(left),
                    payloads=sorted(int(x) for x in left)))
        return out
