"""Registry of HOT entry points: the real graphs the examples and the
serving engines execute, traced for the rule engine.

Each entry point builds the same step the production path dispatches —
DDP ResNet train steps across O0–O3 (telemetry on/off, channels-last
variants), the transformer-family O2 steps, the serving engines' jitted
mutators, and the tensor-parallel step — and carries the expectations
the rules check.  Expectations are *derived from the subsystems that
own them* wherever possible: conv/matmul dtypes from
``amp.compute_dtype``, DDP psum counts and on-wire bytes from
``parallel.allreduce_comm_plan``, donation names/blocklist from
``serving``'s constants.  Jaxpr properties are backend-independent, so
tracing on the CPU mesh pins what the TPU executable will see.

Builders run lazily and cache: registering is free, ``ep.graph()`` pays
the trace once per process (tests, the CI gate and the CLI share it).
"""

from __future__ import annotations

from typing import Any, Callable, Dict, Iterable, List, Optional

import numpy as np

from .graphs import Graph

__all__ = ["EntryPoint", "ENTRY_POINTS", "register_entry_point", "get",
           "select", "names", "entry_point_memory_record"]


def entry_point_memory_record(ep: "EntryPoint") -> Dict[str, Any]:
    """One ``kind: memory`` JSONL payload for an entry point: the
    analytic cost (``ep.cost()``) merged with the compiled memory plan
    (``ep.memory_plan()``).  Shared by ``python -m apex_tpu.analysis
    --memory`` and tests so the record shape cannot drift from
    ``exporters.validate_memory_record``."""
    cost = ep.cost()
    rec = {"kind": "memory", "entry_point": ep.name,
           "source": "compiled", **cost.to_record(), **ep.memory_plan()}
    dt = cost.dominant_matmul_dtype
    if dt is not None:
        rec["dominant_matmul_dtype"] = dt
    return rec


class EntryPoint:
    """One hot graph: ``build(ep)`` returns a :class:`Graph` and may
    fill derived expectations into ``ep.expect`` before rules run."""

    def __init__(self, name: str, build: Callable[["EntryPoint"], Graph],
                 tags: Iterable[str] = (),
                 expect: Optional[Dict[str, Any]] = None,
                 description: str = ""):
        self.name = name
        self.tags = frozenset(tags)
        self.expect: Dict[str, Any] = dict(expect or {})
        self.description = description
        self._build = build
        self._graph: Optional[Graph] = None
        self._cost = None
        self._memory_plan: Optional[Dict[str, Any]] = None

    def graph(self) -> Graph:
        if self._graph is None:
            # leak barrier: amp.initialize(O1) installs a PROCESS-WIDE
            # cast policy (the reference's monkey-patch analogue) and
            # nothing uninstalls it — without this restore, building
            # the O1 entry point would silently re-dtype every graph
            # built after it (tests only dodge this via conftest's
            # autouse _reset_amp_policy).  Builders that need a policy
            # at trace time scope it explicitly via _scoped().
            from ..amp import policy as amp_policy
            base = amp_policy.current_policy()
            try:
                self._graph = self._build(self)
            finally:
                amp_policy.set_policy(base)
        return self._graph

    def cost(self):
        """Analytic :class:`observability.costmodel.Cost` of the traced
        graph (honest mode: scan bodies times trip count).  Cached per
        process like ``graph()`` — the FlopAccountingRule, the CLI
        ``--memory`` dump and tests share one count."""
        if self._cost is None:
            from ..observability import costmodel
            self._cost = costmodel.jaxpr_cost(self.graph().jaxpr)
        return self._cost

    def memory_plan(self) -> Dict[str, Any]:
        """Compiled memory plan (``Compiled.memory_analysis()``) plus
        the analytic liveness estimate.  Unlike ``cost()`` this pays a
        compile on first call (cached after); the lint rules use only
        the analytic fields, so plain lint never compiles."""
        if self._memory_plan is None:
            from ..observability import memory
            plan = memory.memory_plan(self.graph().compiled)
            lb = memory.jaxpr_live_bytes(self.graph().jaxpr)
            plan["analytic_live_bytes"] = lb["peak_live_bytes"]
            plan["analytic_temp_bytes"] = lb["peak_temp_bytes"]
            plan["analytic_temp_bytes_by_dtype"] = \
                lb["peak_temp_bytes_by_dtype"]
            self._memory_plan = plan
        return self._memory_plan

    def __repr__(self):
        return f"EntryPoint({self.name!r}, tags={sorted(self.tags)})"


ENTRY_POINTS: Dict[str, EntryPoint] = {}


def register_entry_point(name: str, tags: Iterable[str] = (),
                         expect: Optional[Dict[str, Any]] = None,
                         description: str = ""):
    def deco(build):
        if name in ENTRY_POINTS:
            raise ValueError(f"duplicate entry point {name!r}")
        ENTRY_POINTS[name] = EntryPoint(name, build, tags=tags,
                                        expect=expect,
                                        description=description)
        return build
    return deco


def get(name: str) -> EntryPoint:
    try:
        return ENTRY_POINTS[name]
    except KeyError:
        raise KeyError(f"unknown entry point {name!r}; known: "
                       f"{sorted(ENTRY_POINTS)}")


def names() -> List[str]:
    return list(ENTRY_POINTS)


def select(names: Optional[Iterable[str]] = None,
           tags: Optional[Iterable[str]] = None) -> List[EntryPoint]:
    if names is not None:
        return [get(n) for n in names]
    eps = list(ENTRY_POINTS.values())
    if tags is not None:
        tags = frozenset(tags)
        eps = [ep for ep in eps if ep.tags & tags]
    return eps


def _scoped(pol, fn):
    """Defer ``fn`` under the amp cast-policy environment the builder
    intends — traces run lazily, long after the builder's global policy
    state has been restored by the EntryPoint.graph() leak barrier."""
    def run():
        from ..amp import policy as amp_policy
        with amp_policy.use_policy(pol):
            return fn()
    return run


def _no_policy():
    from ..amp import policy as amp_policy
    return amp_policy.NoPolicy()


def _require_devices(n: int):
    import jax
    if len(jax.devices()) < n:
        raise RuntimeError(
            f"this entry point traces an {n}-device mesh; run under "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={n} "
            f"(the CLI and tests/ci/graph_lint.py set this before the "
            f"backend initializes)")


# -- DDP ResNet train steps (O0-O3, layouts, telemetry) -------------------

# activation threshold for the layout rule: one NHWC input batch
# (4, 32, 32, 3) on the 8-way mesh — anything that size or bigger being
# transposed is a relayout of real data, not index bookkeeping
_RESNET_ACT_ELEMS = 4 * 3 * 32 * 32


def _ddp_resnet_graph(ep, opt_level, channels_last=False,
                      input_format="NCHW", stem="conv7",
                      telemetry=False, B=8, image=32,
                      comm_topology="flat", compress=False,
                      ici_size=None, numerics=None, supervised=None,
                      world=None):
    """Trace the REAL DDP train step — shard_map over the 8-device CPU
    mesh with the grad allreduce inside — the same graph
    examples/imagenet executes.  ``telemetry=True`` threads
    a DeviceMetrics state through the step carry (the fully
    instrumented shape of the hot loop).  ``numerics="on"`` threads a
    NumericsMonitor through the carry — per-layer grad health from
    ``opt.step(grad_health=...)``, per-bucket stats from
    ``allreduce_grads(numerics_out=...)``, and the one-psum divergence
    digest over the updated params; ``numerics="off"`` runs the SAME
    step code with a disabled monitor, which must trace byte-identical
    to the uninstrumented baseline (the numerics rule pins both).
    ``supervised="on"``/``"off"`` routes the step through
    ``RunSupervisor.wrap_step`` with an enabled/disabled supervisor —
    which must be an IDENTITY both ways: the supervisor consumes
    host-side flush points only, and the supervisor rule pins the
    wrapped step's jaxpr byte-identical to the baseline's.
    ``world=N`` traces over a SUB-mesh of the first N ambient devices
    — the post-recovery shrunk-world step (fleet.recovery): the
    collective expectations are re-derived from ``allreduce_comm_plan``
    at that world, which is exactly the contract the elastic trainer's
    re-jit relies on."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, PartitionSpec as P
    from .. import amp, observability, optimizers, parallel, models
    from ..nn import functional as F
    from ..observability import numerics as obs_numerics

    model, opt = amp.initialize(
        models.resnet18(num_classes=10, channels_last=channels_last,
                        input_format=input_format, stem=stem),
        optimizers.FusedAdam(1e-3), opt_level=opt_level, verbosity=0)
    ddp = parallel.DistributedDataParallel(
        model, comm_topology=comm_topology,
        allreduce_compress_bf16=compress, ici_size=ici_size)
    params, bn = model.init(jax.random.PRNGKey(0))
    ost = opt.init(params)
    rng = np.random.RandomState(0)
    shape = (B, 3, image, image) if input_format == "NCHW" \
        else (B, image, image, 3)
    x = jnp.asarray(rng.randn(*shape), jnp.float32)
    y = jnp.asarray(rng.randint(0, 10, B), jnp.int32)
    dm = observability.DeviceMetrics(
        counters=("steps", "overflows"),
        gauges=("loss_scale", "grad_norm")) if telemetry else None
    ndev = world if world is not None else len(jax.devices())
    if world is not None:
        _require_devices(world)
    if ici_size is not None and (ndev < ici_size
                                 or ndev % ici_size):
        # bare RuntimeError = the device-count skip gate (run_lint's
        # skip_runtime_errors): a 1-device smoke host cannot trace a
        # 2-level mesh, and the old ValueError from the group builder
        # crashed the lint run instead of skipping the EP
        raise RuntimeError(
            f"this entry point needs an axis of a multiple of "
            f"ici_size={ici_size} devices; ambient mesh has {ndev}")
    nm = None
    digest_plan = []
    if numerics is not None:
        grad_plan = parallel.allreduce_comm_plan(
            params, comm_topology=comm_topology,
            allreduce_compress_bf16=compress, ici_size=ici_size,
            world=ndev, nproc=1)
        digest_plan = obs_numerics.digest_comm_plan(params)
        nm = obs_numerics.NumericsMonitor(
            params, half_dtype="bfloat16",
            bucket_labels=obs_numerics.bucket_labels(grad_plan),
            digest=True, axis_name="data",
            enabled=(numerics == "on"))

    def step(state, batch):
        if telemetry:
            params, bn, ost, tele = state
        elif nm is not None:
            params, bn, ost, ntele = state
        else:
            params, bn, ost = state
        xb, yb = batch

        def loss_fn(p):
            out, nb = model.apply(p, xb, state=bn, train=True)
            return F.cross_entropy(out, yb), nb

        loss, nb, g = amp.scaled_grad(loss_fn, params, ost, has_aux=True)
        if nm is not None and nm.enabled:
            nout: list = []
            g = ddp.allreduce_grads(g, numerics_out=nout)
            params, ost2, info = opt.step(params, ost, g,
                                          grad_health=nm)
            ntele = nm.update(ntele, grad_stats=info["grad_health"],
                              bucket_stats=nout,
                              found_inf=info["found_inf"],
                              loss_scale=info["loss_scale"],
                              sync_tree=params)
            return (params, nb, ost2, ntele), jax.lax.pmean(loss, "data")
        g = ddp.allreduce_grads(g)
        params, ost2, info = opt.step(params, ost, g)
        if telemetry:
            tele = dm.inc(tele, "steps")
            tele = dm.inc(tele, "overflows", info["found_inf"])
            tele = dm.set(tele, "loss_scale", info["loss_scale"])
            tele = dm.set(tele, "grad_norm", info["grad_norm"])
            return (params, nb, ost2, tele), jax.lax.pmean(loss, "data")
        if nm is not None:
            # disabled monitor: ntele is an empty pytree and update is
            # an identity — zero extra leaves, zero extra eqns, so the
            # trace is byte-identical to the uninstrumented baseline
            ntele = nm.update(ntele)
            return (params, nb, ost2, ntele), jax.lax.pmean(loss, "data")
        return (params, nb, ost2), jax.lax.pmean(loss, "data")

    # divergent-output ledger (spec-consistency rule): the seed's
    # intended non-SyncBN semantics — every rank updates its BN running
    # stats from LOCAL batch statistics, so each floating BN-state leaf
    # (2 stats x 20 BN layers = 40) diverges across ranks despite the
    # replicated out_spec.  The ENABLED numerics monitor adds 3 carry
    # leaves derived from rank-local bucket stats before their flush.
    divergent = sum(
        1 for leaf in jax.tree_util.tree_leaves(bn)
        if np.issubdtype(np.asarray(leaf).dtype, np.floating))
    if numerics == "on":
        divergent += 3
    _fill_ddp_expectations(ep, opt_level, params,
                           comm_topology=comm_topology,
                           compress=compress, ici_size=ici_size,
                           extra_plan=digest_plan if (
                               numerics == "on") else None,
                           world=ndev, divergent_outputs=divergent)
    if numerics is not None:
        ep.expect.setdefault("numerics", {
            "baseline": "ddp_resnet18_o2",
            "enabled": numerics == "on",
            "extra_collectives": {"psum": 1} if numerics == "on" else {},
            "extra_payload_bytes": (digest_plan[0]["wire_bytes"]
                                    if numerics == "on" else 0)})
    if supervised is not None:
        # the operational-plane contract (PR 10): attaching a run
        # supervisor changes NOTHING in the jitted step — wrap_step is
        # an identity whether the supervisor is enabled or not, and
        # the supervisor rule verifies the traced jaxpr stays
        # byte-identical to the unsupervised baseline
        sup = observability.RunSupervisor(
            f"ep_{ep.name}", enabled=(supervised == "on"))
        step = sup.wrap_step(step)
        ep.expect.setdefault("supervisor", {
            "baseline": "ddp_resnet18_o2",
            "enabled": supervised == "on"})
    state = (params, bn, ost) \
        + ((dm.init(),) if telemetry else ()) \
        + ((nm.init(),) if nm is not None else ())
    mesh = Mesh(np.array(jax.devices()[:ndev]), ("data",))
    mapped = jax.shard_map(step, mesh=mesh,
                           in_specs=(P(), (P("data"), P("data"))),
                           out_specs=(P(), P()), check_vma=False)
    # O1's op-boundary casts consult the policy amp.initialize just
    # installed; capture it for the deferred trace (O0/O2/O3 see the
    # clean base policy thanks to the graph() leak barrier)
    from ..amp import policy as amp_policy
    pol = amp_policy.current_policy()
    return Graph(trace=_scoped(
        pol, lambda: jax.make_jaxpr(mapped)(state, (x, y))))


def _fill_ddp_expectations(ep, opt_level, params, comm_topology="flat",
                           compress=False, ici_size=None,
                           extra_plan=None, world=None,
                           divergent_outputs=0):
    """Derive the amp + collective expectations for a DDP train step.

    Comm accounting: the step's collective population is exactly the
    grad buckets of ``allreduce_comm_plan`` under the SAME topology
    knobs the step's DDP wrapper carries — one psum per bucket for the
    flat topology; reduce_scatter + DCN reduce + all_gather per bucket
    for the hierarchical one, per-level payloads included — folded by
    ``plan_collective_expectations``, plus two fp32 scalars: the
    axis-size psum ``gradient_average`` divides by, and the
    ``pmean(loss)`` the step returns.  Grad dtypes equal the amp-cast
    param dtypes (``scaled_grad`` differentiates wrt the cast tree), so
    the plan over ``params`` IS the plan over the grads.
    """
    from .. import amp, parallel
    import jax
    dt = str(np.dtype(amp.compute_dtype(opt_level)))
    ep.expect.setdefault("amp", {
        # resnet18 fwd has 20 convs; backward adds dgrad+wgrad per conv
        # minus the input dgrad — 40 is a sanity floor, not a census
        "opt_level": opt_level, "conv_dtype": dt, "min_convs": 40,
        # the fc head forward dot; dgrad/wgrad have a (B, 10)-sized
        # operand below the large-dot threshold
        "dot_dtype": dt, "min_dots": 1})
    plan = parallel.allreduce_comm_plan(
        params, comm_topology=comm_topology,
        allreduce_compress_bf16=compress, ici_size=ici_size,
        world=world if world is not None else len(jax.devices()),
        nproc=1)
    # ``extra_plan``: additional planned collectives beyond the grad
    # reduction — the numerics divergence digest's one psum
    # (numerics.digest_comm_plan) folds in here so the collective
    # rule's expectations stay exact on instrumented steps
    ep.expect.setdefault(
        "collectives",
        parallel.plan_collective_expectations(
            plan + list(extra_plan or []),
            extra_psums=2, extra_psum_bytes=2 * 4))
    # cost/memory accounting (PR 8): under a bf16 compute policy no
    # measurable share of dot/conv FLOPs may run in fp32 (the silent
    # upcast halves MXU rate exactly where the flops are), and the
    # step's peak live bytes stay within a fixed multiple of its
    # argument bytes (~2.6x today: params + fp32 masters/moments +
    # activations; 4x flags a graph suddenly holding a second copy of
    # everything).  Resnet18's train step traces ~126 MFLOP of matmul
    # work — the floor keeps the fraction check non-vacuous.
    if np.dtype(amp.compute_dtype(opt_level)) != np.dtype(np.float32):
        ep.expect.setdefault("flops", {"max_fp32_matmul_fraction": 0.02,
                                       "min_matmul_flops": 1e6})
    ep.expect.setdefault("memory", {"max_live_to_argument_ratio": 4.0})
    # sharding plane (PR 18): the mesh the step maps over, plus the
    # DECLARED divergent-output count — the spec-consistency rule
    # re-derives the count from the partition propagator and flags any
    # drift in either direction (see _ddp_resnet_graph for what the
    # declared leaves are).  The resharding census is plan-derived like
    # the collective census: the hierarchical buckets' reduce_scatter /
    # all_gather payloads are the ONLY sanctioned reshards, and the
    # flat plan sanctions none (psums never reshard).
    ep.expect.setdefault("sharding", {
        "mesh_axes": {"data": world if world is not None
                      else len(jax.devices())},
        "divergent_outputs": divergent_outputs})
    ep.expect.setdefault(
        "resharding",
        parallel.plan_resharding_expectations(
            plan + list(extra_plan or [])))


for _lvl in ("O0", "O1", "O2", "O3"):
    register_entry_point(
        f"ddp_resnet18_{_lvl.lower()}", tags=("training", "ddp", "amp"),
        description=f"DDP resnet18 {_lvl} train step, NCHW, 8-way mesh")(
        lambda ep, lvl=_lvl: _ddp_resnet_graph(ep, lvl))

register_entry_point(
    "ddp_resnet18_o2_telemetry", tags=("training", "ddp", "amp",
                                       "telemetry"),
    description="DDP resnet18 O2 step with DeviceMetrics threaded "
                "through the carry — must stay host-transfer-free")(
    lambda ep: _ddp_resnet_graph(ep, "O2", telemetry=True))

# numerics observability (PR 9): the SAME O2 step with a
# NumericsMonitor threaded through the carry — per-layer grad health
# (amp's grad_health hook), per-bucket stats riding the allreduce
# bucket structure, and the one-psum cross-replica divergence digest.
# The numerics rule pins the contract both ways: the "on" variant adds
# zero host transfers and EXACTLY the digest plan's collective delta
# over the uninstrumented baseline; the "off" variant (same step code,
# disabled monitor) must trace to the byte-identical jaxpr.
register_entry_point(
    "ddp_resnet18_o2_numerics", tags=("training", "ddp", "amp",
                                      "numerics", "telemetry"),
    description="DDP resnet18 O2 step with device-resident numerics "
                "accounting (grad health + bucket stats + divergence "
                "digest) — zero host transfers, plan-exact collectives")(
    lambda ep: _ddp_resnet_graph(ep, "O2", numerics="on"))

register_entry_point(
    "ddp_resnet18_o2_numerics_off", tags=("training", "ddp",
                                          "numerics"),
    description="DDP resnet18 O2 step with numerics DISABLED — must "
                "lower byte-identical to the uninstrumented step")(
    lambda ep: _ddp_resnet_graph(ep, "O2", numerics="off"))

# operational plane (PR 10): the SAME O2 step routed through
# RunSupervisor.wrap_step.  The supervisor is host-side by contract —
# it consumes already-flushed signals — so BOTH the enabled and the
# disabled variant must trace to the byte-identical jaxpr of the
# uninstrumented baseline with zero host transfers (the supervisor
# rule; mutation-tested both ways in tests/test_analysis.py like the
# numerics rule).
register_entry_point(
    "ddp_resnet18_o2_supervised", tags=("training", "ddp", "amp",
                                        "supervisor", "telemetry"),
    description="DDP resnet18 O2 step under an ENABLED run supervisor "
                "— must stay byte-identical to the bare step (the "
                "supervisor reads host flush points only)")(
    lambda ep: _ddp_resnet_graph(ep, "O2", supervised="on"))

register_entry_point(
    "ddp_resnet18_o2_supervised_off", tags=("training", "ddp",
                                            "supervisor"),
    description="DDP resnet18 O2 step under a DISABLED run supervisor "
                "— byte-identical to the bare step")(
    lambda ep: _ddp_resnet_graph(ep, "O2", supervised="off"))

register_entry_point(
    "ddp_resnet18_o2_nhwc", tags=("training", "ddp", "amp", "layout"),
    expect={"layout": {"min_activation_elems": _RESNET_ACT_ELEMS,
                       "allowed_6d_rearranges": 0}},
    description="DDP resnet18 O2 channels-last step — transpose-free")(
    lambda ep: _ddp_resnet_graph(ep, "O2", channels_last=True,
                                 input_format="NHWC"))

# hierarchical two-level gradient communication (ICI/DCN): the same O2
# step with comm_topology="hierarchical" over a virtual 2-slice mesh
# (ici_size=4 on the 8-device CPU mesh — jaxpr properties are
# backend-independent, so the group structure pins what a real
# 2-host x 4-chip run communicates).  The collective expectations are
# DERIVED from allreduce_comm_plan under the same knobs: per-bucket
# reduce_scatter/psum/all_gather counts and the per-primitive payload
# split, where the bucket psum payload IS the DCN hop — 1/ici_size of
# the flat payload.
register_entry_point(
    "ddp_resnet18_o2_hier", tags=("training", "ddp", "amp", "hier"),
    description="DDP resnet18 O2 step, hierarchical ICI/DCN allreduce "
                "(ici_size=4 on the 8-way mesh)")(
    lambda ep: _ddp_resnet_graph(ep, "O2", comm_topology="hierarchical",
                                 ici_size=4))

register_entry_point(
    "ddp_resnet18_o2_hier_bf16", tags=("training", "ddp", "amp", "hier"),
    description="DDP resnet18 O2 step, hierarchical allreduce with "
                "bf16-compressed DCN hop")(
    lambda ep: _ddp_resnet_graph(ep, "O2", comm_topology="hierarchical",
                                 ici_size=4, compress=True))

# elastic recovery (PR 11): the POST-SHRINK step.  When a replica dies
# mid-run, fleet.recovery.ElasticTrainer re-jits the train step on the
# surviving world (here 8 → 4, ici_size 4 → 2: losing a host halves
# the slice, the same placement at half the fabric) — this entry point
# pins that the shrunk step lints clean with collective expectations
# RE-DERIVED from allreduce_comm_plan at the new world size: per-
# bucket reduce_scatter/psum/all_gather counts and per-level payloads
# all recomputed, the axis-size psum and the loss pmean still exactly
# two fp32 scalars.  predivide_factors needs no pinning beyond this:
# it divides by the mapped axis size, which IS the new world.
register_entry_point(
    "ddp_resnet18_o2_hier_world4", tags=("training", "ddp", "amp",
                                         "hier", "recovery"),
    description="DDP resnet18 O2 step re-jitted on the shrunk 4-device "
                "world (ici_size=2) — the post-recovery step, "
                "plan-derived expectations at world 4")(
    lambda ep: _ddp_resnet_graph(ep, "O2", comm_topology="hierarchical",
                                 ici_size=2, world=4))

register_entry_point(
    "ddp_resnet18_o2_nhwc_s2d", tags=("training", "ddp", "amp", "layout"),
    # the 6-D block rearrange inside F.space_to_depth is the ONE
    # legitimate activation transpose (forward-only: the input is a
    # constant, so no gradient flows back through it)
    expect={"layout": {"min_activation_elems": _RESNET_ACT_ELEMS,
                       "allowed_6d_rearranges": 1}},
    description="DDP resnet18 O2 NHWC space-to-depth stem step")(
    lambda ep: _ddp_resnet_graph(ep, "O2", channels_last=True,
                                 input_format="NHWC",
                                 stem="space_to_depth"))


# -- overlapped gradient communication (PR 14) ----------------------------

def _staged_mlp_graph(ep, overlap=True, comm_topology="hierarchical",
                      compress=False, ici_size=4, stages=4, hidden=32,
                      B=8):
    """The overlapped DDP train step (ROADMAP item 2): a sequential
    ``stages``-deep MLP whose backward runs stage-by-stage through
    ``DistributedDataParallel.staged_allreduce_grads`` — with
    ``overlap=True`` each stage's bucket reduction is ISSUED while the
    earlier stages' gradients are still being computed, which is a
    *position* property of the jaxpr: the collective census and
    payloads are byte-identical to the reduce-after-backward schedule,
    and only the interleaving check (derived from
    ``overlap_comm_schedule`` like every other expectation here) can
    tell them apart.  ``overlap=False`` builds that baseline schedule
    from the SAME staged step — the mutation tests lint it under the
    overlap expectations and require the position check to flag."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import Mesh, PartitionSpec as P
    from .. import parallel

    ndev = len(jax.devices())
    if ici_size is not None and (ndev < ici_size or ndev % ici_size):
        # bare RuntimeError = the device-count skip gate (see
        # _ddp_resnet_graph): a 1-device smoke host cannot trace the
        # 2-level mesh
        raise RuntimeError(
            f"this entry point needs an axis of a multiple of "
            f"ici_size={ici_size} devices; ambient mesh has {ndev}")
    rng = np.random.RandomState(14)
    stage_params = [
        {"w": jnp.asarray(rng.randn(hidden, hidden) * 0.1, jnp.float32),
         "b": jnp.zeros((hidden,), jnp.float32)}
        for _ in range(stages)]
    x = jnp.asarray(rng.randn(B, hidden), jnp.float32)
    y = jnp.asarray(rng.randn(B, hidden), jnp.float32)
    stage_fns = [lambda p, a: jnp.tanh(a @ p["w"] + p["b"])] * stages
    ddp = parallel.DistributedDataParallel(
        comm_topology=comm_topology, allreduce_compress_bf16=compress,
        ici_size=ici_size, overlap=overlap)

    def step(params_list, batch):
        xb, yb = batch
        loss, grads = ddp.staged_allreduce_grads(
            stage_fns, lambda a: jnp.mean((a - yb) ** 2), params_list,
            xb)
        new = [jax.tree_util.tree_map(lambda w, g: w - 0.1 * g, p, g)
               for p, g in zip(params_list, grads)]
        return new, lax.pmean(loss, "data")

    schedule = parallel.overlap_comm_schedule(
        stage_params, comm_topology=comm_topology,
        allreduce_compress_bf16=compress, ici_size=ici_size,
        world=ndev, nproc=1, overlap=overlap)
    # census/payloads from the schedule (the same per-bucket accounting
    # allreduce_comm_plan uses) + 2 fp32 scalars: the ONE shared
    # axis-size psum (world_scalar=) and the loss pmean; overlapped
    # mode additionally pins the interleaving position property
    ep.expect.setdefault(
        "collectives",
        parallel.overlap_collective_expectations(
            schedule, extra_psums=2, extra_psum_bytes=2 * 4))
    ep.expect.setdefault("memory", {"max_live_to_argument_ratio": 4.0})
    # sharding plane: params replicated, batch sharded over data, and
    # every output provably agrees (the per-stage allreduce chains
    # resolve to replicated); the census sanctions exactly the
    # schedule's per-bucket reduce_scatter/all_gather payloads
    ep.expect.setdefault("sharding", {"mesh_axes": {"data": ndev},
                                      "divergent_outputs": 0})
    ep.expect.setdefault(
        "resharding",
        parallel.plan_resharding_expectations(schedule["buckets"]))
    mesh = Mesh(np.array(jax.devices()), ("data",))
    mapped = jax.shard_map(step, mesh=mesh,
                           in_specs=(P(), (P("data"), P("data"))),
                           out_specs=(P(), P()), check_vma=False)
    return Graph(trace=_scoped(
        _no_policy(),
        lambda: jax.make_jaxpr(mapped)(stage_params, (x, y))))


register_entry_point(
    "ddp_mlp_overlap_flat", tags=("training", "ddp", "overlap"),
    description="staged 4-stage MLP DDP step, OVERLAPPED flat "
                "allreduce — per-stage psums interleaved with the "
                "backward, position-pinned")(
    lambda ep: _staged_mlp_graph(ep, comm_topology="flat",
                                 ici_size=None))

register_entry_point(
    "ddp_mlp_overlap_hier", tags=("training", "ddp", "overlap", "hier"),
    description="staged 4-stage MLP DDP step, OVERLAPPED hierarchical "
                "ICI/DCN allreduce (ici_size=4) — bucket i's "
                "reduce_scatter/DCN-psum/all_gather chain issued while "
                "bucket i-1's grads are still in backward")(
    lambda ep: _staged_mlp_graph(ep))

register_entry_point(
    "ddp_mlp_overlap_hier_bf16", tags=("training", "ddp", "overlap",
                                       "hier"),
    description="staged 4-stage MLP DDP step, overlapped hierarchical "
                "allreduce with bf16-compressed DCN hop")(
    lambda ep: _staged_mlp_graph(ep, compress=True))


# -- ZeRO weight-update sharding (PR 20) ----------------------------------

def _zero_collective_expectations(plan, parallel):
    """Fold a ``zero_update_comm_plan`` into the collectives
    expectation: the plan's buckets plus the step's three scalar
    collectives OUTSIDE the plan — the grad-norm psum (full-axis for
    stage 1, in-slice for stages 2/3: one eqn either way), the loss
    pmean, and the ``pmax(found_inf)`` the loss scaler syncs skips
    with (ZeRO shards must overflow-skip together or the master
    shards diverge)."""
    exp = parallel.plan_collective_expectations(
        plan, extra_psums=2, extra_psum_bytes=2 * 4)
    exp["counts"]["pmax"] = exp["counts"].get("pmax", 0) + 1
    exp["payload_bytes"] += 4
    by = exp["payload_bytes_by_primitive"]
    by["pmax"] = by.get("pmax", 0) + 4
    return exp


def _zero_resnet_graph(ep, zero_stage, compress=False, ici_size=4,
                       B=8, image=32):
    """The ZeRO train step over the 8-device mesh: the SAME O2 resnet18
    forward/backward as ``ddp_resnet18_o2`` but with NO separate grad
    allreduce — ``AmpOptimizer.step`` owns the reduction, and what it
    issues depends on the stage:

    - stage 1: full-axis reduce_scatter of the flat fp32 grads, shard
      update, full-axis all_gather of the updated half params.
    - stage 2: in-slice reduce_scatter (ici groups) + DCN reduce of
      the 1/ici shard, shard update against the DCN-replicated
      optimizer state, in-slice all_gather back.
    - stage 3: the fp32 master shard IS the parameter store —
      ``zero_gather_params`` all-gathers each slice's params
      just-in-time in the forward (and its ``jax.checkpoint`` replay
      re-gathers in the backward), the cotangent arrives as the flat
      in-slice grad shard via the gather's transpose
      (reduce_scatter), and the step updates the shard with NO
      gathers of its own.

    Every collective/resharding expectation is derived from
    ``parallel.zero_update_comm_plan`` under the same knobs — the
    static plan the runtime documentation and this census share."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, PartitionSpec as P
    from .. import amp, optimizers, parallel, models
    from ..nn import functional as F

    world = 8
    _require_devices(world)
    isz = ici_size if zero_stage >= 2 else None
    if isz is not None and world % isz:
        raise RuntimeError(
            f"this entry point needs an axis of a multiple of "
            f"ici_size={isz} devices; ambient mesh has {world}")
    model, opt = amp.initialize(
        models.resnet18(num_classes=10),
        optimizers.FusedAdam(1e-3), opt_level="O2", verbosity=0)
    params, bn = model.init(jax.random.PRNGKey(0))
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(B, 3, image, image), jnp.float32)
    y = jnp.asarray(rng.randint(0, 10, B), jnp.int32)
    mesh = Mesh(np.array(jax.devices()[:world]), ("data",))
    ospecs = amp.zero_optimizer_specs(
        opt, params, "data", zero_stage=zero_stage, zero_ici_size=isz,
        zero_compress_bf16=compress)
    ost = jax.jit(jax.shard_map(
        lambda p: opt.init(p, zero_axis="data", zero_stage=zero_stage,
                           zero_ici_size=isz,
                           zero_compress_bf16=compress),
        mesh=mesh, in_specs=(P(),), out_specs=ospecs,
        check_vma=False))(params)

    if zero_stage == 3:
        # masters ARE the params: the carry holds no model param tree,
        # and the loss differentiates wrt the flat fp32 shard through
        # the just-in-time gather.  The forward is wrapped under the
        # named-checkpoint policy: activations stay saved, but the
        # gathered parameter buffer is rematerialized — the backward
        # RE-GATHERS the slice params instead of holding the full
        # model live across the step, which is the ZeRO-3 memory/wire
        # trade the plan's two jit_gather buckets account for
        def step(state, batch):
            bn, ost = state
            xb, yb = batch

            def fwd(m):
                p = amp.zero_gather_params(m)
                out, nb = model.apply(p, xb, state=bn, train=True)
                return F.cross_entropy(out, yb), nb

            loss_fn = jax.checkpoint(
                fwd, policy=amp.zero_gather_checkpoint_policy())

            loss, nb, g = amp.scaled_grad(loss_fn, ost.masters, ost,
                                          has_aux=True)
            _, ost2, _ = opt.step((), ost, g)
            return (nb, ost2), jax.lax.pmean(loss, "data")

        state = (bn, ost)
        in_state = (P(), ospecs)
    else:
        def step(state, batch):
            params, bn, ost = state
            xb, yb = batch

            def loss_fn(p):
                out, nb = model.apply(p, xb, state=bn, train=True)
                return F.cross_entropy(out, yb), nb

            loss, nb, g = amp.scaled_grad(loss_fn, params, ost,
                                          has_aux=True)
            # no ddp.allreduce_grads: step() reduce-scatters the grads
            # and gathers the updated params internally
            params, ost2, _ = opt.step(params, ost, g)
            return (params, nb, ost2), jax.lax.pmean(loss, "data")

        state = (params, bn, ost)
        in_state = (P(), P(), ospecs)

    plan = parallel.zero_update_comm_plan(
        params, zero_stage=zero_stage, world=world, ici_size=isz,
        zero_compress_bf16=compress)
    dt = str(np.dtype(amp.compute_dtype("O2")))
    ep.expect.setdefault("amp", {
        "opt_level": "O2", "conv_dtype": dt, "min_convs": 40,
        "dot_dtype": dt, "min_dots": 1})
    ep.expect.setdefault("collectives",
                         _zero_collective_expectations(plan, parallel))
    ep.expect.setdefault("flops", {"max_fp32_matmul_fraction": 0.02,
                                   "min_matmul_flops": 1e6})
    # measured jaxpr_live_bytes on the 8-device CPU mesh, declared at
    # ~1.05x so a regression (an un-donated buffer, a second fp32
    # activation tree) trips the budget while trace noise does not:
    #   zero1  live/args 3.283  temps {bf16 22.5M, f32 89.5M, bool 2.8M}
    #   zero2  live/args 2.599  temps {bf16 22.5M, f32 89.5M, bool 5.6M}
    #   zero3  live/args 2.658  temps {bf16 22.5M, f32 55.9M, bool 5.6M}
    # (stage 3's fp32 temp peak is ~37% below stage 1/2: the half-dtype
    # jit gather + custom-vjp grad pack never materialize the fp32
    # full model)
    mem_budget = {
        1: {"max_live_to_argument_ratio": 3.45,
            "temp_budget_bytes_by_dtype": {
                dt: 23_700_000, "float32": 94_000_000,
                "bool": 2_950_000, "int32": 128}},
        2: {"max_live_to_argument_ratio": 2.73,
            "temp_budget_bytes_by_dtype": {
                dt: 23_700_000, "float32": 94_000_000,
                "bool": 5_900_000, "int32": 128}},
        3: {"max_live_to_argument_ratio": 2.80,
            "temp_budget_bytes_by_dtype": {
                dt: 23_700_000, "float32": 58_700_000,
                "bool": 5_900_000, "int32": 128}},
    }[zero_stage]
    ep.expect.setdefault("memory", mem_budget)
    divergent = sum(
        1 for leaf in jax.tree_util.tree_leaves(bn)
        if np.issubdtype(np.asarray(leaf).dtype, np.floating))
    if zero_stage == 2:
        # stage 2's gather-back is IN-SLICE: each returned param leaf
        # is provably equal only within its ICI slice, and the
        # cross-slice agreement rests on the DCN-replicated optimizer
        # state (P("data") in-specs can't express that), so the
        # partition propagator reports varies(data) for every param
        # output despite the replicated out-spec — the same declared
        # class as the non-synced BN stats, one per param leaf
        divergent += len(jax.tree_util.tree_leaves(params))
    # measured replication ledger (entry_point_sharding_record):
    # stages 1/2 keep the bf16 model replicated (156.9 MB world-total
    # duplicates); stage 3's only replicated bytes are the BN state,
    # scaler scalars and the gather index tables (1.27 MB) — the fp32
    # optimizer state's replicated fraction collapses 0.875 -> 0.005
    # vs ddp_resnet18_o2.  ~1.05x measured: the ratchet-down check
    # fires on stale over-declarations (RATCHET_FRACTION)
    ep.expect.setdefault("sharding", {
        "mesh_axes": {"data": world},
        "divergent_outputs": divergent,
        "max_replicated_bytes": (1_333_000 if zero_stage == 3
                                 else 164_800_000)})
    ep.expect.setdefault(
        "resharding", parallel.plan_resharding_expectations(plan))
    mapped = jax.shard_map(step, mesh=mesh,
                           in_specs=(in_state,
                                     (P("data"), P("data"))),
                           out_specs=(in_state, P()), check_vma=False)
    from ..amp import policy as amp_policy
    pol = amp_policy.current_policy()
    return Graph(trace=_scoped(
        pol, lambda: jax.make_jaxpr(mapped)(state, (x, y))))


register_entry_point(
    "ddp_resnet18_o2_zero1", tags=("training", "ddp", "amp", "zero"),
    description="O2 resnet18 ZeRO-1 step — optimizer state sharded "
                "1/world, full-axis reduce_scatter + all_gather owned "
                "by the optimizer (the memory baseline the zero2/3 "
                "budgets ratchet against)")(
    lambda ep: _zero_resnet_graph(ep, 1))

register_entry_point(
    "ddp_resnet18_o2_zero2", tags=("training", "ddp", "amp", "zero",
                                   "hier"),
    description="O2 resnet18 ZeRO-2 step on the hierarchical fabric "
                "(ici_size=4): in-slice grad reduce_scatter + DCN "
                "shard reduce, DCN-replicated optimizer state, "
                "in-slice gather-back")(
    lambda ep: _zero_resnet_graph(ep, 2))

register_entry_point(
    "ddp_resnet18_o2_zero3", tags=("training", "ddp", "amp", "zero",
                                   "hier"),
    description="O2 resnet18 ZeRO-3 step: fp32 master shard is the "
                "parameter store, just-in-time in-slice param gather "
                "in forward + checkpoint re-gather in backward, grads "
                "arrive pre-scattered via the gather's transpose")(
    lambda ep: _zero_resnet_graph(ep, 3))


def _staged_mlp_zero2_graph(ep, compress=False, ici_size=4, stages=4,
                            hidden=32, B=8):
    """ZeRO-2 fused with the OVERLAPPED staged schedule (the tentpole
    composition): each stage's backward hands its flat grads to
    ``staged_zero2_allreduce_grads``, which reduce-scatters in-slice,
    DCN-reduces the 1/ici shard, updates the stage's PARAM SHARD in
    place, and gathers the updated params back — all issued while
    earlier stages' grads are still in backward.  Wire accounting is
    byte-identical to the plain hierarchical staged schedule (the
    gather carries updated params instead of grads), so the
    expectations come from ``overlap_comm_schedule(zero_stage=2)``
    exactly like the non-ZeRO overlap entry points — including the
    interleaving floor ``min_collectives_before_last_matmul`` that
    pins the overlap as a POSITION property."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import Mesh, PartitionSpec as P
    from .. import parallel

    ndev = len(jax.devices())
    if ndev < ici_size or ndev % ici_size:
        # bare RuntimeError = the device-count skip gate (see
        # _ddp_resnet_graph)
        raise RuntimeError(
            f"this entry point needs an axis of a multiple of "
            f"ici_size={ici_size} devices; ambient mesh has {ndev}")
    rng = np.random.RandomState(20)
    stage_params = [
        {"w": jnp.asarray(rng.randn(hidden, hidden) * 0.1, jnp.float32),
         "b": jnp.zeros((hidden,), jnp.float32)}
        for _ in range(stages)]
    x = jnp.asarray(rng.randn(B, hidden), jnp.float32)
    y = jnp.asarray(rng.randn(B, hidden), jnp.float32)
    stage_fns = [lambda p, a: jnp.tanh(a @ p["w"] + p["b"])] * stages
    ddp = parallel.DistributedDataParallel(
        comm_topology="hierarchical", allreduce_compress_bf16=compress,
        ici_size=ici_size, overlap=True, zero_stage=2)

    def step(params_list, batch):
        xb, yb = batch
        loss, new = ddp.staged_zero2_allreduce_grads(
            stage_fns, lambda a: jnp.mean((a - yb) ** 2), params_list,
            xb, lambda stage, p_sh, g_sh: p_sh - 0.1 * g_sh)
        return new, lax.pmean(loss, "data")

    schedule = parallel.overlap_comm_schedule(
        stage_params, comm_topology="hierarchical",
        allreduce_compress_bf16=compress, ici_size=ici_size,
        world=ndev, nproc=1, overlap=True, zero_stage=2)
    ep.expect.setdefault(
        "collectives",
        parallel.overlap_collective_expectations(
            schedule, extra_psums=2, extra_psum_bytes=2 * 4))
    # measured jaxpr_live_bytes: live/args 2.293, temps {f32 22,180,
    # int32 12, bool 1} — declared at ~1.05x (see _zero_resnet_graph)
    ep.expect.setdefault("memory", {
        "max_live_to_argument_ratio": 2.41,
        "temp_budget_bytes_by_dtype": {"float32": 23_300,
                                       "int32": 16, "bool": 4}})
    # every returned stage param came back through the IN-SLICE gather
    # of a shard updated against the slice-local window — cross-slice
    # agreement is real (the DCN reduce equalized the grads) but not
    # propagator-provable, so all 8 param leaves land in the declared
    # divergent class (see _zero_resnet_graph stage 2).  Replicated
    # ledger measures 118,272 bytes (the replicated activations/loss).
    ep.expect.setdefault("sharding", {
        "mesh_axes": {"data": ndev},
        "divergent_outputs": len(jax.tree_util.tree_leaves(
            stage_params)),
        "max_replicated_bytes": 124_000})
    ep.expect.setdefault(
        "resharding",
        parallel.plan_resharding_expectations(schedule["buckets"]))
    mesh = Mesh(np.array(jax.devices()), ("data",))
    mapped = jax.shard_map(step, mesh=mesh,
                           in_specs=(P(), (P("data"), P("data"))),
                           out_specs=(P(), P()), check_vma=False)
    return Graph(trace=_scoped(
        _no_policy(),
        lambda: jax.make_jaxpr(mapped)(stage_params, (x, y))))


register_entry_point(
    "ddp_mlp_overlap_zero2", tags=("training", "ddp", "overlap", "hier",
                                   "zero"),
    description="staged 4-stage MLP, OVERLAPPED hierarchical ZeRO-2 "
                "fused update: per-stage in-slice reduce_scatter + DCN "
                "shard reduce + shard update + in-slice gather-back, "
                "issued while earlier stages are still in backward")(
    lambda ep: _staged_mlp_zero2_graph(ep))


# -- transformer-family O2 train steps ------------------------------------

def _transformer_graph(ep, family):
    """The real O2 DDP train step (fused-head loss) for a tiny
    transformer config over the 8-device CPU mesh."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, PartitionSpec as P
    from .. import amp, optimizers, parallel, models

    if family == "gpt":
        net = models.GPT(models.GPTConfig(
            vocab_size=97, block_size=16, n_layer=2, n_head=4,
            n_embd=32, dropout=0.0))
    else:
        net = models.Llama(models.LlamaConfig(
            vocab_size=97, hidden_size=32, intermediate_size=64,
            num_hidden_layers=2, num_attention_heads=4,
            num_key_value_heads=2, max_position_embeddings=16,
            tie_word_embeddings=True))
    model, opt = amp.initialize(net, optimizers.FusedAdam(1e-3),
                                opt_level="O2", verbosity=0)
    ddp = parallel.DistributedDataParallel(model)
    params, _ = model.init(jax.random.PRNGKey(0))
    ost = opt.init(params)
    ids = jnp.asarray(np.random.RandomState(0).randint(0, 97, (8, 16)))

    def step(state, batch):
        params, ost = state
        (ids_b,) = batch

        def loss_fn(p):
            return model.loss(p, ids_b), ()

        loss, _, g = amp.scaled_grad(loss_fn, params, ost, has_aux=True)
        g = ddp.allreduce_grads(g)
        params, ost2, _ = opt.step(params, ost, g)
        return (params, ost2), jax.lax.pmean(loss, "data")

    dt = str(np.dtype(amp.compute_dtype("O2")))
    ep.expect.setdefault("amp", {
        # qkv/attention/MLP/fused-head dots, fwd and bwd
        "opt_level": "O2", "dot_dtype": dt, "min_dots": 10})
    plan = parallel.allreduce_comm_plan(params)
    ep.expect.setdefault(
        "collectives",
        parallel.plan_collective_expectations(
            plan, extra_psums=2, extra_psum_bytes=2 * 4))
    ep.expect.setdefault("flops", {"max_fp32_matmul_fraction": 0.02,
                                   "min_matmul_flops": 1e6})
    ep.expect.setdefault("memory", {"max_live_to_argument_ratio": 4.0})
    # sharding plane: flat DDP — the plan sanctions NO reshards (every
    # bucket is a bare psum), so any all_gather that creeps into the
    # transformer step is an immediate census finding
    ep.expect.setdefault("sharding", {
        "mesh_axes": {"data": len(jax.devices())},
        "divergent_outputs": 0})
    ep.expect.setdefault("resharding",
                         parallel.plan_resharding_expectations(plan))
    mesh = Mesh(np.array(jax.devices()), ("data",))
    mapped = jax.shard_map(step, mesh=mesh,
                           in_specs=(P(), (P("data"),)),
                           out_specs=(P(), P()), check_vma=False)
    from ..amp import policy as amp_policy
    pol = amp_policy.current_policy()
    return Graph(trace=_scoped(
        pol, lambda: jax.make_jaxpr(mapped)((params, ost), (ids,))))


register_entry_point(
    "gpt_o2_train_step", tags=("training", "ddp", "amp", "transformer"),
    description="GPT O2 DDP train step (fused-head loss)")(
    lambda ep: _transformer_graph(ep, "gpt"))

register_entry_point(
    "llama_o2_train_step", tags=("training", "ddp", "amp", "transformer"),
    description="Llama O2 DDP train step (GQA, tied embeddings)")(
    lambda ep: _transformer_graph(ep, "llama"))


# -- serving engines ------------------------------------------------------

def _tiny_engine():
    import jax
    from .. import models, serving
    m = models.GPT(models.GPTConfig(vocab_size=64, block_size=32,
                                    n_layer=2, n_head=4, n_embd=32,
                                    dropout=0.0, n_kv_head=2))
    params, _ = m.init(jax.random.PRNGKey(0))
    return serving.Engine(m, params, slots=2, buf_len=32, window=8)


def _engine_step_k_graph(ep):
    import jax
    from .. import serving
    eng = _tiny_engine()
    args = (eng.ids, eng.cur_len, eng.cache, eng._slot_keys,
            eng._slot_temp, eng.limit, eng._eos)
    n_cache = len(jax.tree_util.tree_leaves(eng.cache))
    ep.expect.setdefault("donation", {
        # the big mutated window inputs — ids, the KV cache tree, the
        # RNG keys — must alias; the per-slot length vector cur_len is
        # covered by serving.DONATION_BLOCKLIST (PR 2 compile-cache
        # gotcha), and limit/eos are read-only scheduler state
        "expect_donated": ("ids", "cache", "keys"),
        "forbid_donated": ("temps", "limit", "eos"),
        "min_aliased": n_cache + 2})
    # a K-tick decode window mutates in place: live bytes stay O(cache
    # + params); a second cache copy materializing mid-window flags
    ep.expect.setdefault("memory", {"max_live_to_argument_ratio": 2.5})
    return Graph(trace=_scoped(
                     _no_policy(),
                     lambda: jax.make_jaxpr(eng._step_k)(*args)),
                 lower=_scoped(_no_policy(),
                               lambda: eng._step_k.lower(*args)),
                 arg_names=serving.STEP_K_ARG_NAMES, example_args=args)


register_entry_point(
    "engine_step_k", tags=("serving", "donation"),
    description="Engine._step_k: the K-tick jitted decode window")(
    _engine_step_k_graph)


def _engine_prefill_graph(ep):
    import jax
    import jax.numpy as jnp
    from .. import serving
    eng = _tiny_engine()
    args = (eng.ids, eng.cache, None, 0, jnp.zeros((32,), jnp.int32))
    n_cache = len(jax.tree_util.tree_leaves(eng.cache))
    ep.expect.setdefault("donation", {
        # admission-path mutator: the cache row is scattered in place
        "expect_donated": ("ids", "cache"),
        "forbid_donated": ("slot", "row"),
        "min_aliased": n_cache + 1})
    # admission runs a full-buffer forward: activations push live bytes
    # to ~1.5x (params + cache); 2.5x budgets real headroom, not a leak
    ep.expect.setdefault("memory", {"max_live_to_argument_ratio": 2.5})
    return Graph(trace=_scoped(
                     _no_policy(),
                     lambda: jax.make_jaxpr(eng._prefill_slot)(*args)),
                 lower=_scoped(_no_policy(),
                               lambda: eng._prefill_slot.lower(*args)),
                 arg_names=serving.PREFILL_SLOT_ARG_NAMES,
                 example_args=args)


register_entry_point(
    "engine_prefill_slot", tags=("serving", "donation"),
    description="Engine._prefill_slot: per-slot admission prefill")(
    _engine_prefill_graph)


def _tiny_paged_engine():
    import jax
    from .. import models, serving
    m = models.GPT(models.GPTConfig(vocab_size=64, block_size=32,
                                    n_layer=2, n_head=4, n_embd=32,
                                    dropout=0.0, n_kv_head=2))
    params, _ = m.init(jax.random.PRNGKey(0))
    return serving.PagedEngine(m, params, slots=2, buf_len=32,
                               block_size=8, prefill_chunk=8, window=8)


def _paged_step_k_graph(ep):
    import jax
    from .. import serving
    eng = _tiny_paged_engine()
    pending = eng._stage_pending()
    args = (eng.ids, eng.cur_len, eng.kv_len, eng.pool,
            eng._slot_keys, eng._slot_temp, eng.limit, eng._eos,
            eng.tables, eng.n_blk, eng.free_stack, eng.free_top,
            pending)
    n_pool = len(jax.tree_util.tree_leaves(eng.pool))
    ep.expect.setdefault("donation", {
        # the block pool is THE multi-GB resident and must alias in
        # place through the whole K-tick scan (gather/compute/scatter
        # per tick); ids and the RNG keys ride along.  cur_len /
        # kv_len / n_blk are per-slot length vectors covered by
        # serving.DONATION_BLOCKLIST (PR 2 compile-cache corruption
        # class), and the scheduler vectors (tables, free stack,
        # pending pack) are read-mostly
        "expect_donated": ("ids", "pool", "keys"),
        "forbid_donated": ("temps", "limit", "eos", "tables",
                           "free_stack", "free_top", "pending"),
        "min_aliased": n_pool + 2})
    # the dense per-slot gather materializes a pool-sized temporary
    # per tick next to the donated pool itself — ~2x pool + params is
    # the honest working set; 4x budgets headroom, not a leak
    ep.expect.setdefault("memory", {"max_live_to_argument_ratio": 4.0})
    return Graph(trace=_scoped(
                     _no_policy(),
                     lambda: jax.make_jaxpr(eng._paged_step_k)(*args)),
                 lower=_scoped(_no_policy(),
                               lambda: eng._paged_step_k.lower(*args)),
                 arg_names=serving.PAGED_STEP_K_ARG_NAMES,
                 example_args=args)


register_entry_point(
    "paged_step_k", tags=("serving", "donation", "paged"),
    description="PagedEngine._paged_step_k: K continuous-batching "
                "ticks (chunked prefill + decode + in-graph block "
                "recycling + iteration-boundary admission)")(
    _paged_step_k_graph)


def _paged_admit_graph(ep):
    import jax
    import jax.numpy as jnp
    from .. import serving
    eng = _tiny_paged_engine()
    args = (eng.ids, eng.cur_len, eng.kv_len, eng.limit, eng._eos,
            eng._slot_keys, eng._slot_temp, eng.tables, eng.n_blk,
            eng.free_stack, eng.free_top, jnp.int32(0),
            jnp.zeros((32,), jnp.int32), jnp.int32(3), jnp.int32(8),
            jnp.int32(-1), jax.random.PRNGKey(1), jnp.float32(0.0),
            jnp.int32(1))
    ep.expect.setdefault("donation", {
        # admission is a scheduler-row seed, NOT a prefill: it writes
        # the ids row + key and pops block ids — there is no KV
        # argument to donate, and the blocklisted length vectors
        # (cur_len/kv_len/n_blk) must never alias
        "expect_donated": ("ids", "keys"),
        "forbid_donated": ("limit", "eos", "temps", "tables",
                           "free_stack", "free_top", "slot", "row"),
        "min_aliased": 2})
    ep.expect.setdefault("memory", {"max_live_to_argument_ratio": 2.5})
    return Graph(trace=_scoped(
                     _no_policy(),
                     lambda: jax.make_jaxpr(eng._paged_admit)(*args)),
                 lower=_scoped(_no_policy(),
                               lambda: eng._paged_admit.lower(*args)),
                 arg_names=serving.PAGED_ADMIT_ARG_NAMES,
                 example_args=args)


register_entry_point(
    "paged_admit", tags=("serving", "donation", "paged"),
    description="PagedEngine._paged_admit: window-boundary block "
                "reservation + scheduler-row seed (no prefill)")(
    _paged_admit_graph)


def _seq2seq_step_k_graph(ep):
    import jax
    from .. import models, serving
    t5 = models.T5(models.T5Config(
        vocab_size=64, d_model=32, d_kv=8, d_ff=64, num_layers=1,
        num_heads=4, dropout_rate=0.0, relative_attention_num_buckets=8,
        relative_attention_max_distance=16))
    t5p, _ = t5.init(jax.random.PRNGKey(0))
    eng = serving.Seq2SeqEngine(t5, t5p, slots=2, src_len=8,
                                max_new_cap=8, window=4)
    args = (eng.state, eng.out, eng.n_new, eng.s_limit, eng._eos)
    ep.expect.setdefault("donation", {
        # slot state + the output buffer mutate every window; n_new is
        # the per-slot length vector (global blocklist)
        "expect_donated": ("state", "out"),
        "forbid_donated": ("limit", "eos")})
    ep.expect.setdefault("memory", {"max_live_to_argument_ratio": 2.5})
    return Graph(trace=_scoped(
                     _no_policy(),
                     lambda: jax.make_jaxpr(eng._step_k)(*args)),
                 lower=_scoped(_no_policy(),
                               lambda: eng._step_k.lower(*args)),
                 arg_names=serving.SEQ2SEQ_STEP_K_ARG_NAMES,
                 example_args=args)


register_entry_point(
    "seq2seq_step_k", tags=("serving", "donation", "seq2seq"),
    description="Seq2SeqEngine._step_k: K decoder ticks in-graph")(
    _seq2seq_step_k_graph)


# -- tensor parallel ------------------------------------------------------

def _tp_train_step_graph(ep):
    """2x4 (data, model) mesh ParallelMLP train step: Megatron comm
    pattern — ONE row-parallel psum forward, ONE f-copy psum backward,
    plus the DDP grad bucket + axis-size scalar over data."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, PartitionSpec as P
    from .. import parallel
    from ..parallel import tensor_parallel as tp
    from ..nn import functional as F

    _require_devices(8)
    devs = np.array(jax.devices()[:8]).reshape(2, 4)
    mesh = Mesh(devs, ("data", "model"))
    mlp = tp.ParallelMLP(8, 32, activation="relu")
    params, _ = mlp.init(jax.random.PRNGKey(6))
    specs = tp.partition_specs(mlp, params)
    ddp = parallel.DistributedDataParallel(mlp)
    rng = np.random.RandomState(6)
    x = jnp.asarray(rng.randn(8, 8), jnp.float32)
    y = jnp.asarray(rng.randn(8, 8), jnp.float32)

    def step(p, xb, yb):
        def loss_fn(pp):
            return F.mse_loss(mlp(pp, xb), yb)
        grads = jax.grad(loss_fn)(p)
        grads = ddp.allreduce_grads(grads)     # data axis only
        return jax.tree_util.tree_map(lambda w, g: w - 0.1 * g, p, grads)

    # comm accounting, derived: ONE model-axis psum — the row-parallel
    # forward output, (B/2, 8) fp32 rows per device (the f-copy
    # backward psum computes dL/dx, which nothing consumes, so DCE
    # removes it); DDP over data contributes one psum per comm-plan
    # bucket over the LOCAL param shards (specs divide the model-axis
    # dims by 4) plus the axis-size scalar gradient_average divides by
    local = [
        jax.ShapeDtypeStruct(tp.local_shape(leaf.shape, spec, mesh),
                             leaf.dtype)
        for leaf, spec in zip(jax.tree_util.tree_leaves(params),
                              jax.tree_util.tree_leaves(
                                  specs, is_leaf=lambda s:
                                  isinstance(s, P)))]
    plan = parallel.allreduce_comm_plan(local)
    act_bytes = (x.shape[0] // mesh.shape["data"]) * 8 * 4
    ep.expect.setdefault(
        "collectives",
        parallel.plan_collective_expectations(
            plan, extra_psums=2, extra_psum_bytes=act_bytes + 4))
    ep.expect.setdefault("memory", {"max_live_to_argument_ratio": 4.0})
    # sharding plane: the 2x4 mesh, ONE declared divergent output — a
    # precision limit of the static propagator, not a real divergence:
    # DDP concatenates all local grad shards into one flat bundle
    # before the data-axis psum, and the partition model cannot see
    # through the concat/slice round trip, so the second bias's grad
    # conservatively reports varies(model) even though the psum made
    # the whole bundle agree along data and nothing mixed model ranks
    ep.expect.setdefault("sharding", {
        "mesh_axes": {"data": 2, "model": 4},
        "divergent_outputs": 1})
    ep.expect.setdefault("resharding",
                         parallel.plan_resharding_expectations(plan))
    mapped = jax.shard_map(step, mesh=mesh,
                           in_specs=(specs, P("data"), P("data")),
                           out_specs=specs, check_vma=False)
    return Graph(trace=_scoped(
        _no_policy(), lambda: jax.make_jaxpr(mapped)(params, x, y)))


register_entry_point(
    "tp_mlp_train_step", tags=("training", "tp"),
    description="DP x TP (2x4) ParallelMLP train step — Megatron "
                "psum pattern + DDP grad bucket")(
    _tp_train_step_graph)
