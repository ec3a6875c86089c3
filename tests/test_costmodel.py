"""Cost-model observability (PR 8): the analytic FLOPs/bytes model is
pinned against XLA's own counts on the real hot graphs, the memory
plans/liveness/live-array census behave, and the MFU + peak-TFLOPs
surface is consistent.

The acceptance pin lives here: analytic FLOPs match XLA within 5% on
the resnet18 O2 and GPT O2 entry points.  The cross-check runs at the
``Lowered`` stage (pre-optimization HLO, structurally 1:1 with the
jaxpr — jax's own DCE applied on both sides) AND against
``Compiled.cost_analysis()`` on a fwd+bwd core the way test_remat.py
consumes it.  Post-optimization counts on flat-optimizer graphs are
deliberately NOT compared: XLA's fused-producer duplication bills the
11M-element Adam update once per param-leaf slice there (~8x over —
see the costmodel module docstring)."""

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from apex_tpu import amp, models, optimizers
from apex_tpu.nn import functional as F
from apex_tpu.observability import costmodel, memory, exporters


def _lower_jaxpr(closed):
    """Re-stage a traced jaxpr for XLA cost analysis (same trick
    analysis.Graph.compiled uses for trace-only entry points)."""
    args = [jax.ShapeDtypeStruct(v.aval.shape, v.aval.dtype)
            for v in closed.jaxpr.invars]
    fn = jax.jit(lambda *xs: jax.core.eval_jaxpr(
        closed.jaxpr, closed.consts, *xs))
    return fn.lower(*args)


# -- acceptance: analytic vs XLA on the real entry points ------------------

@pytest.mark.parametrize("ep_name", ["ddp_resnet18_o2",
                                     "gpt_o2_train_step"])
def test_analytic_flops_match_xla_on_entry_points(ep_name):
    """THE acceptance pin: the analytic model prices the full DDP train
    step — convs (valid-position counting incl. dgrad dilation), dots,
    elementwise, reductions, collectives, the optimizer cond — within
    5% of XLA's HloCostAnalysis on the same graph.  Actual agreement
    is ~0.1%; 5% is the contract."""
    from apex_tpu import analysis
    ep = analysis.get(ep_name)
    cost = costmodel.jaxpr_cost(ep.graph().jaxpr, xla_parity=True)
    xla = costmodel.xla_cost(_lower_jaxpr(ep.graph().jaxpr))
    assert xla["flops"] > 0
    rel = abs(cost.flops - xla["flops"]) / xla["flops"]
    assert rel < 0.05, (ep_name, cost.flops, xla["flops"], rel)
    # transcendentals ride the same ledger split XLA uses
    if xla["transcendentals"]:
        rel_t = (abs(cost.transcendentals - xla["transcendentals"])
                 / xla["transcendentals"])
        assert rel_t < 0.05
    # the cached surface returns the honest-mode count, once
    assert ep.cost() is ep.cost()
    assert ep.cost().flops > 0


def test_analytic_matches_compiled_cost_analysis():
    """Cross-validation against ``Compiled.cost_analysis()`` the way
    tests/test_remat.py consumes it — on a dot-dominated MLP fwd+bwd
    where XLA's post-fusion counter has no duplicated producers to
    overbill (the flat-optimizer / BN-heavy graphs are cross-checked
    at the Lowered stage instead; see the costmodel docstring)."""
    w1 = jnp.ones((256, 512), jnp.bfloat16)
    w2 = jnp.ones((512, 256), jnp.bfloat16)
    x = jnp.ones((64, 256), jnp.bfloat16)

    def loss(w1, w2):
        h = jnp.maximum(x @ w1, 0)
        return (h @ w2).astype(jnp.float32).sum()

    def fwdbwd(w1, w2):
        return jax.grad(loss, argnums=(0, 1))(w1, w2)

    cost = costmodel.jaxpr_cost(jax.make_jaxpr(fwdbwd)(w1, w2),
                                xla_parity=True)
    compiled = jax.jit(fwdbwd).lower(w1, w2).compile()
    xla = costmodel.xla_cost(compiled)
    rel = abs(cost.flops - xla["flops"]) / xla["flops"]
    assert rel < 0.05, (cost.flops, xla["flops"], rel)
    # dot-dominated: the matmul family carries nearly all the work.
    # 4 dots survive DCE: fwd h = x@w1 (kept for dw2), then dh = g@w2^T,
    # dw2 = h^T@g, dw1 = x^T@dh — the fwd OUTPUT dot h@w2 is dead under
    # grad-of-sum (cotangent is ones) and neither ledger counts it
    assert cost.matmul_flops > 0.9 * cost.flops
    one_dot = 2 * 64 * 256 * 512
    assert cost.matmul_flops == pytest.approx(4 * one_dot, rel=0.01)


def test_conv_flops_valid_position_counting():
    """The conv formula is XLA's: padding taps don't count, and the
    dgrad of a strided conv (dilated input) costs the same as its
    forward — NOT kernel-size times more."""
    x = jnp.ones((1, 64, 8, 8), jnp.bfloat16)
    w = jnp.ones((64, 64, 3, 3), jnp.bfloat16)

    def conv(x, w):
        return jax.lax.conv_general_dilated(
            x, w, (2, 2), "SAME",
            dimension_numbers=("NCHW", "OIHW", "NCHW"))

    fwd = jax.make_jaxpr(conv)(x, w)
    (conv_eqn,) = [e for e in fwd.jaxpr.eqns
                   if e.primitive.name == "conv_general_dilated"]
    f_fwd = costmodel.conv_flops(conv_eqn)
    assert f_fwd == costmodel.xla_cost(_lower_jaxpr(fwd))["flops"]

    dgrad = jax.make_jaxpr(
        lambda x, w: jax.grad(
            lambda x: conv(x, w).astype(jnp.float32).sum())(x))(x, w)
    bwd_convs = [e for e in dgrad.jaxpr.eqns
                 if e.primitive.name == "conv_general_dilated"]
    # the dgrad conv (dilated lhs) prices like the forward
    dg = [e for e in bwd_convs if e.params.get("lhs_dilation",
                                               (1, 1)) != (1, 1)]
    assert dg and costmodel.conv_flops(dg[0]) == f_fwd
    # naive out*cin*k^2 counting would claim stride^2 = 4x more
    naive = 2 * 1 * 64 * 8 * 8 * 64 * 9
    assert costmodel.conv_flops(dg[0]) < naive / 2


def test_scan_honest_vs_parity_and_dce():
    """Honest mode multiplies scan bodies by trip count (a K-tick
    decode window costs K ticks); parity mode counts once like XLA's
    while lowering.  Dead eqns never count in either mode."""
    def stepped(x):
        def body(c, _):
            dead = jnp.tanh(c) * 3.0          # unused: DCE fodder
            del dead
            return c * 2.0 + 1.0, ()
        return jax.lax.scan(body, x, None, length=8)[0]

    closed = jax.make_jaxpr(stepped)(jnp.ones((100,)))
    honest = costmodel.jaxpr_cost(closed)
    parity = costmodel.jaxpr_cost(closed, xla_parity=True)
    assert honest.flops == 8 * parity.flops == 8 * 200
    assert honest.transcendentals == 0        # tanh chain is dead

    xla = costmodel.xla_cost(_lower_jaxpr(closed))
    # XLA's while lowering adds a couple of loop-counter flops
    assert abs(parity.flops - xla["flops"]) <= 8


def test_fp32_matmul_fraction():
    def mixed(a16, b16, a32, b32):
        return (a16 @ b16).astype(jnp.float32).sum() + (a32 @ b32).sum()

    a16 = jnp.ones((32, 32), jnp.bfloat16)
    a32 = jnp.ones((32, 32), jnp.float32)
    c = costmodel.jaxpr_cost(jax.make_jaxpr(mixed)(a16, a16, a32, a32))
    assert c.fp32_matmul_fraction() == pytest.approx(0.5)
    assert c.dominant_matmul_dtype in ("bfloat16", "float32")
    c16 = costmodel.jaxpr_cost(
        jax.make_jaxpr(lambda a, b: a @ b)(a16, a16))
    assert c16.fp32_matmul_fraction() == 0.0
    assert c16.dominant_matmul_dtype == "bfloat16"


def test_roofline_r5_flops_accounting_corrected():
    """The round-5 hand roofline math, now machine-checked — and
    CORRECTED: the hand-rolled roofline priced a resnet50 224^2
    forward at "4.1 GFLOP/img", which is the published ~4.1 GMACs
    quoted in the 2-flops-per-MAC convention the peak table uses, so
    the real forward is ~7.9 GFLOP (XLA agrees to 0.01%).  The
    hand-derived 11.4%-MFU headline divided MAC-counted work by a
    FLOP-counted peak — the measured step was actually ~2x that MFU.
    This is exactly the class of folklore error the analytic model
    exists to kill."""
    model = models.resnet50()
    params, bn = model.init(jax.random.PRNGKey(0))
    x = jnp.ones((1, 3, 224, 224))

    def fwd(p):
        out, _ = model.apply(p, x, state=bn, train=False)
        return out.sum()

    closed = jax.make_jaxpr(fwd)(params)
    c = costmodel.jaxpr_cost(closed, xla_parity=True)
    assert 7.5e9 < c.flops < 8.5e9            # ~2x the MAC count
    assert c.matmul_flops > 0.95 * c.flops
    xla = costmodel.xla_cost(_lower_jaxpr(closed))
    assert abs(c.flops - xla["flops"]) / xla["flops"] < 0.01


# -- memory plans and liveness --------------------------------------------

def test_memory_plan_fields_and_reassembly():
    f = jax.jit(lambda a, b: jnp.tanh(a @ b).sum())
    compiled = f.lower(jnp.ones((64, 64)), jnp.ones((64, 64))).compile()
    plan = memory.memory_plan(compiled)
    for key in memory.MEMORY_PLAN_FIELDS:
        assert plan[key] >= 0
    assert plan["argument_bytes"] == 2 * 64 * 64 * 4
    assert plan["peak_bytes"] == (
        plan["argument_bytes"] + plan["output_bytes"]
        + plan["temp_bytes"] + plan["generated_code_bytes"]
        - plan["alias_bytes"])


def test_memory_plan_donation_alias_credit():
    """A donated buffer's output shares its argument's storage: the
    alias credit shows up and lowers the peak."""
    def bump(c):
        return jax.tree_util.tree_map(lambda x: x + 1.0, c)

    cache = {"k": jnp.zeros((64, 64)), "v": jnp.zeros((64, 64))}
    plain = jax.jit(bump).lower(cache).compile()
    donated = jax.jit(bump, donate_argnums=(0,)).lower(cache).compile()
    p0 = memory.memory_plan(plain)
    p1 = memory.memory_plan(donated)
    assert p0["alias_bytes"] == 0
    assert p1["alias_bytes"] == 2 * 64 * 64 * 4
    assert p1["peak_bytes"] < p0["peak_bytes"]


def test_jaxpr_live_bytes_sees_through_shard_map_and_finds_peak():
    from jax.sharding import Mesh, PartitionSpec as P

    def body(x):
        big = jnp.concatenate([x, x, x])      # 3x temp, then reduced
        return big.sum(keepdims=True)

    mesh = Mesh(np.array(jax.devices()[:8]), ("data",))
    mapped = jax.shard_map(body, mesh=mesh, in_specs=(P("data"),),
                           out_specs=P("data"), check_vma=False)
    lb = memory.jaxpr_live_bytes(
        jax.make_jaxpr(mapped)(jnp.ones((8, 1024))))
    # per-device: 1024-elem arg + the 3072-elem concat temp
    assert lb["argument_bytes"] == 1024 * 4
    assert lb["peak_temp_bytes"] >= 3 * 1024 * 4
    assert lb["peak_temp_bytes_by_dtype"]["float32"] \
        == lb["peak_temp_bytes"]


def test_jaxpr_live_bytes_fp32_upcast_doubles_fp32_temps():
    """The static signal MemoryBudgetRule's upcast mutation rides: the
    same pipeline with an fp32 upcast multiplies fp32 temp bytes while
    the bf16 version keeps them near zero."""
    w = jnp.ones((256, 256), jnp.bfloat16)

    def clean(x):
        h = jnp.maximum(x @ w, 0)
        return (h @ w).astype(jnp.float32).sum()

    def upcast(x):
        h = jnp.maximum((x.astype(jnp.float32) @ w.astype(jnp.float32)),
                        0)
        return (h @ w.astype(jnp.float32)).sum()

    x = jnp.ones((64, 256), jnp.bfloat16)
    lb_clean = memory.jaxpr_live_bytes(jax.make_jaxpr(clean)(x))
    lb_up = memory.jaxpr_live_bytes(jax.make_jaxpr(upcast)(x))
    f32_clean = lb_clean["peak_temp_bytes_by_dtype"].get("float32", 0)
    f32_up = lb_up["peak_temp_bytes_by_dtype"].get("float32", 0)
    assert f32_up > 2 * max(f32_clean, 1)


def test_live_array_census_and_gauges():
    from apex_tpu.observability import MetricsRegistry
    keep = jnp.ones((1024,), jnp.float32)     # noqa: F841 — stays live
    census = memory.live_array_bytes()
    assert census["bytes"] >= 4096 and census["arrays"] >= 1
    reg = MetricsRegistry()
    out = memory.record_live_arrays(reg)
    assert reg.gauge("device_live_bytes").value == out["bytes"]
    assert reg.gauge("device_live_arrays").value == out["arrays"]
    del keep


# -- entry-point surface + records ----------------------------------------

def test_entry_point_memory_plan_and_record_schema():
    """engine_prefill_slot (real lowering, donation) gives a memory
    plan with a non-zero alias credit, and the shared record builder
    emits a schema-valid ``kind: memory`` record."""
    from apex_tpu import analysis
    ep = analysis.get("engine_prefill_slot")
    plan = ep.memory_plan()
    assert plan["alias_bytes"] > 0            # donated cache aliases
    assert plan["peak_bytes"] > 0
    assert plan["analytic_live_bytes"] > 0
    assert ep.memory_plan() is plan           # cached per process

    rec = exporters.JsonlExporter.enrich(
        analysis.entry_point_memory_record(ep))
    assert exporters.validate_memory_record(rec) == []
    assert exporters.validate_telemetry_record(rec) == []
    assert rec["entry_point"] == "engine_prefill_slot"
    assert rec["flops"] > 0
