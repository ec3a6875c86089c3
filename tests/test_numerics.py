"""Numerics observability (PR 9): device-resident gradient-health
telemetry, overflow attribution, cross-replica divergence digests, and
the per-bucket / compression-error accounting riding the DDP allreduce.

The jaxpr-level pins (zero host transfers when enabled, byte-identical
step when disabled, plan-exact collective delta) live in
tests/test_step_graph_audit.py on the real entry points; here we test
the arithmetic, the attribution, the flight-ring trail, the record
schema, and the seeded fault scenarios the ISSUE's acceptance criteria
name: a NaN injected into ONE layer's gradients produces a scaler skip
whose flight event and ``kind: numerics`` record name that layer, and
a perturbed replica trips the divergence digest within one step while
an undisturbed run stays clean for the full run.
"""

import numpy as np
import pytest
import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from apex_tpu import amp, optimizers, parallel
from apex_tpu import observability as obs
from apex_tpu.observability import numerics as N
from apex_tpu.observability.exporters import (JsonlExporter,
                                              validate_numerics_record,
                                              validate_telemetry_record)


@pytest.fixture
def mesh():
    return Mesh(np.array(jax.devices()[:8]), ("data",))


def _params():
    rng = np.random.RandomState(0)
    return {"layer0": jnp.asarray(rng.randn(4, 3), jnp.float32),
            "layer1": jnp.asarray(rng.randn(6), jnp.float32),
            "layer2": jnp.asarray(rng.randn(2, 2), jnp.float32)}


# -- leaf stats arithmetic -------------------------------------------------

def test_leaf_stats_counts_nonfinite_absmax_underflow():
    """nonfinite counted per layer, magnitudes computed on the FINITE
    values only (one inf must not erase the abs-max next to it),
    abs_max/sq_sum reported UNSCALED, underflow = nonzero scaled
    magnitudes below the half dtype's smallest normal."""
    g = {"a": jnp.asarray([8.0, -16.0, jnp.inf, jnp.nan]),
         "b": jnp.asarray([0.0, 1e-9, 4.0])}
    nm = N.NumericsMonitor(g, half_dtype="float16")
    st = nm.leaf_stats(g, 2.0)
    assert list(nm.names) == ["a", "b"]
    np.testing.assert_allclose(np.asarray(st["nonfinite"]), [2.0, 0.0])
    # unscaled: max |finite| / scale
    np.testing.assert_allclose(np.asarray(st["abs_max"]), [8.0, 2.0])
    np.testing.assert_allclose(np.asarray(st["sq_sum"]),
                               [80.0, 4.0], rtol=1e-5)
    # 1e-9 is a nonzero scaled value below fp16 tiny (6.1e-5); the
    # exact zero is not an underflow
    np.testing.assert_allclose(np.asarray(st["underflow"]), [0.0, 1.0])


def test_monitor_flush_is_one_device_get(monkeypatch):
    g = _params()
    reg = obs.MetricsRegistry()
    nm = N.NumericsMonitor(g, half_dtype="bfloat16", registry=reg)
    tele = nm.init()
    tele = nm.update(tele, grad_stats=nm.leaf_stats(g, 1.0),
                     found_inf=jnp.zeros(()), loss_scale=1.0)
    calls = []
    real = jax.device_get
    monkeypatch.setattr(jax, "device_get",
                        lambda x: calls.append(1) or real(x))
    out = nm.flush(tele)
    assert len(calls) == 1
    assert out["steps"] == 1 and out["overflow_steps"] == 0
    assert out["culprit"] is None
    # registry fold: per-layer children + the totals
    assert reg.counter("numerics_overflow_steps_total").value == 0
    amax = reg.gauge("numerics_abs_max")
    assert amax.labels(layer="layer0").value > 0


def test_disabled_monitor_is_inert_and_leafless():
    g = _params()
    nm = N.NumericsMonitor(g, enabled=False, digest=True,
                           axis_name="data")
    tele = nm.init()
    assert tele == {} and jax.tree_util.tree_leaves(tele) == []
    assert nm.update(tele) == {}
    fl = nm.flush(tele)
    assert fl["enabled"] is False and fl["culprit"] is None
    # an instrumented-but-disabled function traces byte-identical
    def base(x):
        return x * 2.0

    def instrumented(x):
        t = nm.update(nm.init())
        del t
        return x * 2.0

    assert str(jax.make_jaxpr(base)(jnp.ones(4))) == \
        str(jax.make_jaxpr(instrumented)(jnp.ones(4)))


def test_monitor_validation_errors():
    g = _params()
    with pytest.raises(ValueError, match="exactly one"):
        N.NumericsMonitor(g, names=("a",))
    with pytest.raises(ValueError, match="half_dtype"):
        N.NumericsMonitor(g, half_dtype="float32")
    with pytest.raises(ValueError, match="axis_name"):
        N.NumericsMonitor(g, digest=True)
    nm = N.NumericsMonitor(g)
    with pytest.raises(ValueError, match="leaves"):
        nm.leaf_stats({"only": jnp.ones(3)}, 1.0)
    with pytest.raises(ValueError, match="bucket_labels"):
        nm.update(nm.init(), bucket_stats=[{}])
    with pytest.raises(ValueError, match="digest=False"):
        nm.update(nm.init(), sync_tree=g)
    nmb = N.NumericsMonitor(g, bucket_labels=("b0", "b1"))
    with pytest.raises(ValueError, match="bucket stats"):
        nmb.update(nmb.init(), bucket_stats=[{
            "nonfinite": jnp.zeros(()), "abs_max": jnp.zeros(()),
            "sq_sum": jnp.zeros(())}])


# -- the acceptance pin: seeded NaN injection names the poisoned layer ----

def test_nan_injection_attribution_names_poisoned_layer():
    """Inject NaN into ONE layer's gradients: the (fp16-dynamic)
    scaler skips the step, and the culprit the monitor flushes — the
    flight-ring ``overflow_attribution`` event, the ``scaler_skip``
    event via ``record_scaler(numerics=...)``, and the
    ``kind: numerics`` record — all name that layer."""
    from apex_tpu.amp._process_optimizer import AmpOptimizer
    from apex_tpu.amp.scaler import LossScaler

    params = _params()
    opt = AmpOptimizer(optimizers.FusedAdam(1e-3),
                       LossScaler("dynamic"), master_weights=True)
    ost = opt.init(params)
    nm_ring = obs.EventRing()
    nm = N.NumericsMonitor(params, half_dtype="float16", ring=nm_ring)
    tele = nm.init()

    @jax.jit
    def step(params, ost, tele, g):
        params, ost, info = opt.step(params, ost, g, grad_health=nm)
        tele = nm.update(tele, grad_stats=info["grad_health"],
                         found_inf=info["found_inf"],
                         loss_scale=info["loss_scale"])
        return params, ost, tele

    scale = float(amp.scaler_state(ost).loss_scale)
    clean = jax.tree_util.tree_map(
        lambda p: jnp.full_like(p, 0.5) * scale, params)
    poisoned = dict(clean)
    poisoned["layer1"] = clean["layer1"].at[2].set(jnp.nan)

    p1, ost1, tele = step(params, ost, tele, poisoned)
    # the skip: params and loss scale react, the step is dropped
    assert amp.steps_skipped(ost1) == 1
    assert amp.current_loss_scale(ost1) == scale / 2
    np.testing.assert_array_equal(np.asarray(p1["layer1"]),
                                  np.asarray(params["layer1"]))
    # a clean step after it is applied normally
    p2, ost2, tele = step(p1, ost1, tele, clean)
    assert amp.steps_skipped(ost2) == 1
    assert not np.allclose(np.asarray(p2["layer1"]),
                           np.asarray(p1["layer1"]))

    flushed = nm.flush(tele)
    assert flushed["steps"] == 2 and flushed["overflow_steps"] == 1
    assert flushed["culprit"] == "layer1"
    assert flushed["culprit_nonfinite"] == 1
    by_name = {l["name"]: l for l in flushed["layers"]}
    assert by_name["layer1"]["nonfinite"] == 1
    assert by_name["layer0"]["nonfinite"] == 0
    # flight-ring attribution event
    (ev,) = nm_ring.snapshot("overflow_attribution")
    assert ev["culprit"] == "layer1" and ev["overflow_steps"] == 1
    # record_scaler(numerics=...) puts the culprit on the skip event
    ring = obs.EventRing()
    prev = obs.set_ring(ring)
    try:
        reg = obs.MetricsRegistry()
        amp.record_scaler(ost2, registry=reg, numerics=flushed)
        (skip_ev,) = ring.snapshot("scaler_skip")
        assert skip_ev["culprit"] == "layer1"
        assert skip_ev["culprit_nonfinite"] == 1
    finally:
        obs.set_ring(prev)
    # the kind: numerics record names the layer and validates
    rec = JsonlExporter.enrich(nm.to_record(flushed, metric="inject"))
    assert rec["culprit"] == "layer1"
    assert validate_numerics_record(rec) == []
    assert validate_telemetry_record(rec) == []   # dispatch by kind


# -- the acceptance pin: divergence digest --------------------------------

def test_divergence_digest_perturbed_replica_trips_clean_run_stays(mesh):
    """A replica whose state drifts by 1e-3 on one leaf trips the
    digest WITHIN the step that saw it; an undisturbed run stays
    in-sync for the full run (replicated state is bitwise identical,
    so the 8-way psum matches world*local exactly)."""
    params = _params()
    nm_ring = obs.EventRing()
    nm = N.NumericsMonitor(params, digest=True, axis_name="data",
                           ring=nm_ring)

    def step(tele, p, poison):
        idx = lax.axis_index("data")
        bump = jnp.where((idx == 3) & poison, 1e-3, 0.0)
        p = {**p, "layer1": p["layer1"] + bump}
        return nm.update(tele, sync_tree=p)

    mapped = jax.jit(jax.shard_map(
        step, mesh=mesh, in_specs=(P(), P(), P()), out_specs=P(),
        check_vma=False))

    # undisturbed: a full multi-step run stays clean
    tele = nm.init()
    for _ in range(6):
        tele = mapped(tele, params, jnp.asarray(False))
    fl = nm.flush(tele)
    assert fl["divergence"]["desync_steps"] == 0
    assert fl["divergence"]["in_sync"] is True
    assert fl["divergence"]["max_rel_dev"] <= N.DEFAULT_DIGEST_TOL
    assert nm_ring.snapshot("replica_desync") == []

    # perturbed: trips in ONE step, and the worst leaf is named
    tele = mapped(tele, params, jnp.asarray(True))
    fl = nm.flush(tele)
    assert fl["divergence"]["desync_steps"] == 1
    assert fl["divergence"]["in_sync"] is False
    assert fl["divergence"]["max_rel_dev"] > N.DEFAULT_DIGEST_TOL
    assert fl["divergence"]["worst_leaf"] == "layer1"
    (ev,) = nm_ring.snapshot("replica_desync")
    assert ev["worst_leaf"] == "layer1"

    # a replica that RE-SYNCS after the desync (the elastic-fleet
    # recovery flow) must not rewrite the attribution: worst_leaf is
    # pinned at the step that set max_rel_dev, not the last step's
    # noise floor
    tele = mapped(tele, params, jnp.asarray(False))
    fl = nm.flush(tele)
    assert fl["divergence"]["desync_steps"] == 1
    assert fl["divergence"]["worst_leaf"] == "layer1"


def test_worst_leaf_none_before_any_digest():
    params = _params()
    nm = N.NumericsMonitor(params, digest=True, axis_name="data")
    fl = nm.flush(nm.init())
    assert fl["divergence"]["worst_leaf"] is None


def test_underflow_fraction_not_diluted_by_healthless_updates():
    """grad_steps (updates that carried grad_stats), not steps, is
    the underflow denominator — a caller folding grad health every
    other step keeps the true per-element fraction."""
    g = {"w": jnp.asarray([1e-9, 1e-9, 1.0, 2.0])}   # 2/4 underflow
    nm = N.NumericsMonitor(g, half_dtype="float16")
    tele = nm.init()
    for _ in range(3):
        tele = nm.update(tele, grad_stats=nm.leaf_stats(g, 1.0))
        tele = nm.update(tele)           # health-less step
    fl = nm.flush(tele)
    assert fl["steps"] == 6
    (lyr,) = fl["layers"]
    assert lyr["underflow_fraction"] == pytest.approx(0.5)


def test_divergence_check_nonfinite_state_is_maximal(mesh):
    """A replica holding NaN state is maximal divergence (rel clamps
    to 1.0), not an unmeasurable NaN verdict."""
    def f(x):
        idx = lax.axis_index("data")
        t = {"w": x + jnp.where(idx == 0, jnp.nan, 0.0)}
        chk = N.divergence_check(t, "data")
        return jnp.reshape(chk["max_rel_dev"], (1,))

    out = jax.jit(jax.shard_map(
        f, mesh=mesh, in_specs=(P(),), out_specs=P("data"),
        check_vma=False))(jnp.ones(8))
    assert np.all(np.isfinite(np.asarray(out)))
    assert np.all(np.asarray(out) == 1.0)


def test_digest_comm_plan_matches_traced_collectives(mesh):
    """The digest's planned collective census is exactly what the
    traced check contains: ONE psum of the (L, 2) fp32 digest."""
    params = _params()
    (b,) = N.digest_comm_plan(params)
    assert b["eqns"] == {"psum": 1}
    assert b["eqn_payload_bytes"]["psum"] == 3 * 2 * 4
    from apex_tpu import analysis
    jaxpr = jax.make_jaxpr(jax.shard_map(
        lambda p: N.divergence_check(p, "data")["max_rel_dev"],
        mesh=mesh, in_specs=(P(),), out_specs=P(),
        check_vma=False))(params)
    eqns = analysis.collective_eqns(jaxpr)
    assert len(eqns) == 1 and eqns[0].primitive.name == "psum"
    assert analysis.eqn_payload_bytes(eqns[0]) == b["wire_bytes"]


# -- per-bucket stats on the DDP allreduce --------------------------------

def test_allreduce_numerics_out_bucket_stats(mesh):
    """numerics_out rides the bucket structure: per-bucket nonfinite /
    abs-max / sq-sum device scalars in plan order, foldable into the
    monitor; a seeded inf in the bf16 bucket is counted there and
    nowhere else."""
    grads = {"a": jnp.ones((300,), jnp.float32),
             "b": jnp.full((10,), 2.0, jnp.bfloat16)}
    grads["b"] = grads["b"].at[3].set(jnp.inf)
    plan = parallel.allreduce_comm_plan(
        {k: jax.ShapeDtypeStruct(v.shape, v.dtype)
         for k, v in grads.items()})
    labels = N.bucket_labels(plan)
    nm = N.NumericsMonitor(names=labels, bucket_labels=labels)
    ddp = parallel.DistributedDataParallel()

    def step(tele, g):
        nout = []
        out = ddp.allreduce_grads(g, numerics_out=nout)
        assert all("compression_sq_error" not in b for b in nout)
        return nm.update(tele, bucket_stats=nout), out

    tele, _ = jax.jit(jax.shard_map(
        step, mesh=mesh, in_specs=(P(), P()), out_specs=(P(), P()),
        check_vma=False))(nm.init(), grads)
    fl = nm.flush(tele)
    by_label = {b["label"]: b for b in fl["buckets"]}
    f32 = by_label[next(l for l in labels if "float32" in l)]
    bf16 = by_label[next(l for l in labels if "bfloat16" in l)]
    assert f32["nonfinite"] == 0 and bf16["nonfinite"] == 1
    assert f32["abs_max"] == 1.0 and bf16["abs_max"] == 2.0


def test_hierarchical_compression_error_telemetry(mesh):
    """The bf16 DCN hop reports its own quantization loss: zero when
    the shard values are exactly bf16-representable, positive
    otherwise — the cost side of the PR 5 wire savings."""
    ddp = parallel.DistributedDataParallel(
        comm_topology="hierarchical", ici_size=4,
        allreduce_compress_bf16=True)
    plan = parallel.allreduce_comm_plan(
        {"w": jax.ShapeDtypeStruct((400,), jnp.float32)},
        comm_topology="hierarchical", allreduce_compress_bf16=True,
        ici_size=4, world=8)
    labels = N.bucket_labels(plan)
    nm = N.NumericsMonitor(names=labels, bucket_labels=labels)

    def step(tele, g):
        nout = []
        out = ddp.allreduce_grads(g, numerics_out=nout)
        return nm.update(tele, bucket_stats=nout), out

    run = jax.jit(jax.shard_map(
        step, mesh=mesh, in_specs=(P(), P()), out_specs=(P(), P()),
        check_vma=False))

    # exactly representable: ones psum_scatter to 4.0 per element
    tele, _ = run(nm.init(), {"w": jnp.ones((400,), jnp.float32)})
    fl = nm.flush(tele)
    assert fl["buckets"][0]["compression_sq_error"] == 0.0

    # generic values: the bf16 round-trip loses bits
    tele, _ = run(nm.init(), {"w": jnp.linspace(0.0, 1.0, 400)})
    fl = nm.flush(tele)
    assert fl["buckets"][0]["compression_sq_error"] > 0.0


# -- record schema ---------------------------------------------------------

def _good_record():
    return JsonlExporter.enrich({
        "kind": "numerics", "metric": "unit", "steps": 10,
        "overflow_steps": 2, "loss_scale": 1024.0,
        "half_dtype": "float16", "tiny": 6.1e-5, "grad_norm": 3.5,
        "layers": [
            {"name": "w1", "nonfinite": 4, "abs_max": 2.0,
             "grad_norm": 1.5, "underflow_fraction": 0.25},
            {"name": "w2", "nonfinite": 0, "abs_max": 0.5,
             "grad_norm": 0.5, "underflow_fraction": 0.0}],
        "culprit": "w1", "culprit_nonfinite": 4,
        "buckets": [{"label": "float32/b0", "nonfinite": 4,
                     "abs_max": 2.0, "grad_norm": 1.6,
                     "compression_sq_error": 0.001}],
        "divergence": {"max_rel_dev": 0.0, "desync_steps": 0,
                       "tol": 1e-6, "in_sync": True}})


def test_numerics_record_schema_accepts_good_and_flags_mutations():
    assert validate_numerics_record(_good_record()) == []
    cases = [
        (lambda r: r.pop("layers"), "layers"),
        (lambda r: r.update(layers=[]), "non-empty"),
        (lambda r: r.update(overflow_steps=11), "exceeds steps"),
        (lambda r: r.update(culprit="nope"), "not one of"),
        (lambda r: r.update(overflow_steps=0, culprit="w1"),
         "never happened"),
        (lambda r: r["layers"][0].update(underflow_fraction=1.5),
         "underflow_fraction"),
        (lambda r: r["layers"][0].update(abs_max=float("nan")),
         "abs_max"),
        (lambda r: r["divergence"].update(in_sync=False),
         "inconsistent"),
        (lambda r: r["buckets"][0].update(nonfinite=-1), "nonfinite"),
        (lambda r: r.pop("metric"), "metric"),
        (lambda r: r.update(kind="bench"), "kind"),
        (lambda r: r.update(half_dtype="fp8"), "half_dtype"),
    ]
    for mutate, frag in cases:
        rec = _good_record()
        mutate(rec)
        errs = validate_numerics_record(rec)
        assert errs and any(frag in e for e in errs), (frag, errs)
    # dispatch: the telemetry validator routes on kind
    assert validate_telemetry_record(_good_record()) == []
    bad = _good_record()
    bad["layers"] = []
    assert validate_telemetry_record(bad)


