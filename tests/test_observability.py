"""Telemetry subsystem: metrics registry (host + device-resident),
span tracing / Chrome-trace export, exporters, engine stats, and the
bench JSONL schema."""

import json
import threading

import numpy as np
import pytest
import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from apex_tpu import models, observability as obs, serving
from apex_tpu.observability import exporters


# -- host metrics ---------------------------------------------------------

def test_counter_gauge_basics():
    reg = obs.MetricsRegistry()
    c = reg.counter("c_total")
    c.inc()
    c.inc(2.5)
    assert c.value == 3.5
    with pytest.raises(ValueError):
        c.inc(-1)
    g = reg.gauge("g")
    g.set(7.0)
    assert g.value == 7.0
    # get-or-create returns the same object; kind clash raises
    assert reg.counter("c_total") is c
    with pytest.raises(ValueError, match="already registered"):
        reg.gauge("c_total")


def test_counter_labels_accumulate_separately():
    reg = obs.MetricsRegistry()
    c = reg.counter("bytes_total")
    c.labels(dtype="float32").inc(100)
    c.labels(dtype="bfloat16").inc(7)
    c.labels(dtype="float32").inc(1)
    assert c.labels(dtype="float32").value == 101
    assert c.labels(dtype="bfloat16").value == 7


def test_histogram_bucket_edges_le_semantics():
    """Prometheus ``le``: an observation exactly on an edge lands in
    that edge's bucket, strictly-greater goes to the next."""
    h = obs.Histogram("h", buckets=(1.0, 2.0, 5.0))
    for v in (0.5, 1.0, 1.0000001, 2.0, 5.0, 5.1):
        h.observe(v)
    cum = h.cumulative()
    assert cum["1.0"] == 2          # 0.5 and exactly-1.0
    assert cum["2.0"] == 4          # + 1.0000001 and exactly-2.0
    assert cum["5.0"] == 5          # + exactly-5.0
    assert cum["+Inf"] == 6         # + 5.1 overflow
    assert h.count == 6
    assert h.sum == pytest.approx(14.6000001)
    s = h.summary()
    assert s["count"] == 6 and s["mean"] == pytest.approx(h.sum / 6)
    assert h.percentile(0.0) <= h.percentile(0.99) <= 5.0
    with pytest.raises(ValueError, match="increasing"):
        obs.Histogram("bad", buckets=(2.0, 1.0))


def test_histogram_empty_summary():
    h = obs.Histogram("h")
    assert h.summary() == {"count": 0, "sum": 0.0, "mean": None,
                           "p50": None, "p99": None}


def test_registry_thread_safety():
    reg = obs.MetricsRegistry()
    c = reg.counter("n")
    h = reg.histogram("lat", buckets=(0.5,))

    def work():
        for _ in range(1000):
            c.inc()
            h.observe(0.1)

    threads = [threading.Thread(target=work) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert c.value == 8000
    assert h.count == 8000 and h.cumulative()["0.5"] == 8000


# -- device metrics -------------------------------------------------------

def test_device_counters_accumulate_under_jit_single_fetch(monkeypatch):
    dm = obs.DeviceMetrics(counters=("steps", "overflows"),
                           gauges=("scale",))
    st = dm.init()

    @jax.jit
    def step(st, ovf):
        st = dm.inc(st, "steps")
        st = dm.inc(st, "overflows", ovf)
        st = dm.set(st, "scale", 2.0 ** 10)
        return st

    for i in range(5):
        st = step(st, jnp.asarray(float(i == 2)))

    # counters stay on device until flush...
    assert all(isinstance(v, jax.Array) for v in st.values())
    # ...which is ONE device_get of the whole tree
    calls = []
    real = jax.device_get
    monkeypatch.setattr(jax, "device_get",
                        lambda x: calls.append(1) or real(x))
    reg = obs.MetricsRegistry()
    vals = dm.flush(st, reg)
    assert len(calls) == 1
    assert vals["steps"] == 5.0 and vals["overflows"] == 1.0
    assert vals["scale"] == 2.0 ** 10
    # host registry now mirrors the device totals; repeated flushes are
    # idempotent (set_total, not +=)
    assert reg.counter("steps").value == 5.0
    dm.flush(st, reg)
    assert reg.counter("steps").value == 5.0


def test_device_metrics_jaxpr_is_host_transfer_free():
    dm = obs.DeviceMetrics(counters=("n",), histograms={"h": (1.0, 2.0)})
    st = dm.init()

    def step(st):
        st = dm.inc(st, "n", 3.0)
        st = dm.observe(st, "h", 1.5)
        return st

    jpr = jax.make_jaxpr(step)(st)
    prims = {e.primitive.name for e in jpr.jaxpr.eqns}
    assert not prims & {"pure_callback", "io_callback", "debug_callback",
                        "outfeed", "infeed", "device_put"}


def test_device_metrics_under_shard_map():
    """Per-device increments + an in-graph psum: the flushed counter is
    the global total, with the state replicated across the mesh."""
    dm = obs.DeviceMetrics(counters=("tokens",))
    mesh = Mesh(np.array(jax.devices()[:8]), ("data",))

    def step(st, x):
        return dm.inc(st, "tokens", lax.psum(jnp.sum(x), "data"))

    mapped = jax.jit(jax.shard_map(
        step, mesh=mesh, in_specs=(P(), P("data")), out_specs=P(),
        check_vma=False))
    st = dm.init()
    x = jnp.ones((8, 4), jnp.float32)
    for _ in range(3):
        st = mapped(st, x)
    assert dm.flush(st, obs.MetricsRegistry())["tokens"] == 3 * 32


def test_device_histogram_buckets():
    dm = obs.DeviceMetrics(histograms={"lat": (1.0, 2.0, 5.0)})
    st = dm.init()

    @jax.jit
    def step(st, v):
        return dm.observe(st, "lat", v)

    for v in (0.5, 1.0, 3.0, 100.0):
        st = step(st, jnp.asarray(v))
    reg = obs.MetricsRegistry()
    dm.flush(st, reg)
    h = reg.histogram("lat", buckets=(1.0, 2.0, 5.0))
    assert h.cumulative() == {"1.0": 2, "2.0": 2, "5.0": 3, "+Inf": 4}
    assert h.sum == pytest.approx(104.5)


def test_device_metrics_name_validation():
    dm = obs.DeviceMetrics(counters=("a",), gauges=("b",))
    st = dm.init()
    with pytest.raises(KeyError):
        dm.inc(st, "b")           # gauge is not a counter
    with pytest.raises(KeyError):
        dm.set(st, "nope", 1.0)
    with pytest.raises(ValueError, match="duplicate"):
        obs.DeviceMetrics(counters=("x",), gauges=("x",))


# -- tracing --------------------------------------------------------------

def test_chrome_trace_export_well_formed(tmp_path):
    rec = obs.SpanRecorder()
    with rec.span("outer", phase="test"):
        with rec.span("inner"):
            pass
    rec.event("mark", step=3)
    path = str(tmp_path / "trace.json")
    rec.export_chrome_trace(path)
    with open(path) as f:
        doc = json.load(f)
    evs = doc["traceEvents"]
    assert doc["displayTimeUnit"] == "ms"
    assert [e["name"] for e in evs] == ["inner", "outer", "mark"]
    for e in evs:
        assert e["ph"] in ("X", "i")
        assert isinstance(e["ts"], float) and e["ts"] >= 0
        assert isinstance(e["pid"], int) and isinstance(e["tid"], int)
        if e["ph"] == "X":
            assert e["dur"] >= 0
    outer = evs[1]
    inner = evs[0]
    # nesting: inner lies within outer's span
    assert outer["ts"] <= inner["ts"]
    assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1e-3
    assert outer["args"] == {"phase": "test"}
    assert evs[2]["args"] == {"step": 3}


def test_jsonl_event_export(tmp_path):
    rec = obs.SpanRecorder()
    with rec.span("a"):
        pass
    rec.event("b")
    path = str(tmp_path / "events.jsonl")
    rec.export_jsonl(path)
    with open(path) as f:
        lines = [json.loads(ln) for ln in f if ln.strip()]
    assert [ln["name"] for ln in lines] == ["a", "b"]
    rec.clear()
    assert rec.events() == []


def test_span_exception_safe():
    rec = obs.SpanRecorder()
    with pytest.raises(RuntimeError):
        with rec.span("boom"):
            raise RuntimeError("x")
    assert [e["name"] for e in rec.events()] == ["boom"]


# -- distributed-trace context (PR 6) --------------------------------------

def test_trace_ids_unique_and_span_ids_causal():
    ids = {obs.new_trace_id() for _ in range(100)}
    assert len(ids) == 100
    rec = obs.SpanRecorder()
    tid = obs.new_trace_id()
    with rec.span("root", trace_id=tid):
        with rec.span("child"):              # adopts ambient trace
            rec.event("leaf")
    evs = rec.trace(tid)
    assert [e["name"] for e in evs] == ["root", "child", "leaf"]
    root, child, leaf = evs
    # allocation order IS causal order: parent id < child id, and the
    # parent chain is exactly root <- child <- leaf
    assert root["span_id"] < child["span_id"] < leaf["span_id"]
    assert "parent_id" not in root
    assert child["parent_id"] == root["span_id"]
    assert leaf["parent_id"] == child["span_id"]
    assert all(e["trace_id"] == tid for e in evs)
    # trace() sorts causally even though the recorder appended the
    # parent's complete event AFTER its children
    raw = [e["name"] for e in rec.events()]
    assert raw == ["leaf", "child", "root"]


def test_explicit_trace_does_not_adopt_foreign_parent():
    """A new root with an explicit trace_id opened INSIDE another
    trace's span must stay parentless — adopting the ambient span
    would stitch two unrelated traces together."""
    rec = obs.SpanRecorder()
    with rec.span("outer", trace_id="trace-a"):
        with rec.span("rootb", trace_id="trace-b"):
            pass
    (b,) = rec.trace("trace-b")
    assert "parent_id" not in b
    # and events chained by explicit parent_id override the ambient
    with rec.span("outer2", trace_id="trace-a"):
        first = rec.event("e1", trace_id="trace-c")
        rec.event("e2", trace_id="trace-c", parent_id=first)
    e1, e2 = rec.trace("trace-c")
    assert "parent_id" not in e1
    assert e2["parent_id"] == e1["span_id"]


def test_span_parentage_thread_correct_under_pool():
    """Satellite 1 regression: spans emitted from ThreadPoolExecutor
    workers must parent on THEIR activated context, never on whatever
    span another worker has open concurrently (the ambient context is
    per-thread and reset on exit, so reused pool threads cannot
    inherit a stale parent)."""
    from concurrent.futures import ThreadPoolExecutor
    rec = obs.SpanRecorder()
    barrier = threading.Barrier(4, timeout=10)

    def work(k):
        tid = f"trace-{k}"
        root = rec.event("root", trace_id=tid)
        with rec.activate(tid, root):
            barrier.wait()               # all workers inside at once
            with rec.span("outer", item=k):
                with rec.span("inner", item=k):
                    rec.event("mark", item=k)
        return tid

    with ThreadPoolExecutor(max_workers=4) as pool:
        tids = list(pool.map(work, range(4)))
    for k, tid in enumerate(tids):
        evs = rec.trace(tid)
        assert [e["name"] for e in evs] == ["root", "outer", "inner",
                                            "mark"]
        ids = {e["span_id"] for e in evs}
        root, outer, inner, mark = evs
        # parentage stays inside the trace and follows the nesting
        assert outer["parent_id"] == root["span_id"]
        assert inner["parent_id"] == outer["span_id"]
        assert mark["parent_id"] == inner["span_id"]
        assert all(e.get("parent_id", root["span_id"]) in ids
                   for e in evs)
        assert all(e.get("args", {}).get("item", k) == k for e in evs)
    # pool threads are reused: after the activations exit, a span on
    # a reused worker has NO ambient trace
    with ThreadPoolExecutor(max_workers=1) as pool:
        pool.submit(lambda: None).result()
        assert pool.submit(obs.current_trace).result() is None


def test_maybe_span_gated_by_ambient_context():
    rec = obs.SpanRecorder()
    prev = obs.set_recorder(rec)
    try:
        with obs.maybe_span("hot"):          # no ambient: records nothing
            pass
        assert obs.maybe_event("tick") is None
        assert rec.events() == []
        with rec.activate("t-1", None):
            with obs.maybe_span("hot"):
                pass
            assert isinstance(obs.maybe_event("tick"), int)
        assert [e["name"] for e in rec.trace("t-1")] == ["hot", "tick"]
    finally:
        obs.set_recorder(prev)


def test_maybe_event_records_into_ambient_owner_recorder():
    """Span ids are PER-RECORDER: an ambient context minted by a
    private recorder must route maybe_span/maybe_event into THAT
    recorder — recording them into the default recorder would stamp a
    foreign parent id into its id space (dangling, or colliding with
    an unrelated span that happens to hold the same id)."""
    priv = obs.SpanRecorder()
    rec = obs.SpanRecorder()
    prev = obs.set_recorder(rec)
    try:
        rec.event("noise")                   # default id space advances
        with priv.span("outer", trace_id="t-priv"):
            sid = obs.maybe_event("inner")
        assert rec.events() == [  # default recorder: only its own noise
            e for e in rec.events() if e["name"] == "noise"]
        evs = priv.trace("t-priv")
        assert [e["name"] for e in evs] == ["outer", "inner"]
        inner = next(e for e in evs if e["name"] == "inner")
        outer = next(e for e in evs if e["name"] == "outer")
        assert inner["span_id"] == sid
        assert inner["parent_id"] == outer["span_id"]
        from apex_tpu.observability.exporters import (JsonlExporter,
                                                      validate_trace_record)
        assert validate_trace_record(
            JsonlExporter.enrich(priv.trace_record("t-priv"))) == []
        # and the default recorder's explicit event() never adopts a
        # foreign recorder's ambient parent
        with priv.span("outer2", trace_id="t-priv2"):
            rec.event("standalone")
        ev = [e for e in rec.events() if e["name"] == "standalone"][0]
        assert "parent_id" not in ev and "trace_id" not in ev
    finally:
        obs.set_recorder(prev)


def test_span_recorder_bounded_buffer():
    rec = obs.SpanRecorder(max_events=3)
    for i in range(10):
        rec.event(f"e{i}")
    assert [e["name"] for e in rec.events()] == ["e7", "e8", "e9"]
    # the process DEFAULT recorder is bounded too (flight-recorder
    # discipline: a fleet traces every request by default, and a
    # weeks-long process must hold the last N spans, not all of them)
    assert (obs.get_recorder()._events.maxlen
            == obs.tracing.DEFAULT_MAX_EVENTS)


# -- flight-recorder event ring (PR 6) -------------------------------------

def test_event_ring_bounded_seq_and_dump(tmp_path):
    ring = obs.EventRing(capacity=4)
    for i in range(7):
        ring.append("kind_a" if i % 2 == 0 else "kind_b", i=i)
    assert len(ring) == 4
    assert ring.total == 7 and ring.dropped == 3
    evs = ring.snapshot()
    # oldest-first, seq survives wraparound
    assert [e["seq"] for e in evs] == [3, 4, 5, 6]
    assert [e["i"] for e in ring.snapshot("kind_a")] == [4, 6]
    assert all(e["t"] >= 0 for e in evs)
    path = str(tmp_path / "flight.jsonl")
    ring.dump(path)
    with open(path) as f:
        lines = [json.loads(ln) for ln in f if ln.strip()]
    assert lines[0] == {"kind": "flight_ring", "capacity": 4,
                        "total": 7, "dropped": 3}
    assert [ln["seq"] for ln in lines[1:]] == [3, 4, 5, 6]
    ring.clear()
    assert len(ring) == 0 and ring.total == 7    # seq keeps counting
    with pytest.raises(ValueError, match="capacity"):
        obs.EventRing(capacity=0)
    # process-default ring plumbing
    prev = obs.set_ring(obs.EventRing(capacity=2))
    try:
        from apex_tpu.observability import flightrec
        flightrec.record("x", a=1)
        assert obs.get_ring().snapshot()[0]["kind"] == "x"
    finally:
        obs.set_ring(prev)


def test_event_ring_thread_safe_appends():
    ring = obs.EventRing(capacity=10_000)
    def work():
        for i in range(500):
            ring.append("k", i=i)
    threads = [threading.Thread(target=work) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert ring.total == 4000
    assert sorted(e["seq"] for e in ring.snapshot()) == list(range(4000))


def test_amp_scaler_skip_lands_in_flight_ring():
    """A scaler skip (overflow -> step dropped) is a flight-recorder
    transition: record_scaler appends it to the process ring exactly
    once per newly observed skip."""
    from apex_tpu import amp, optimizers as opts
    from apex_tpu import nn

    class Lin(nn.Module):
        def init(self, key):
            return {"w": jnp.ones((4,), jnp.float32)}, ()

        def apply(self, p, x, state=(), train=False):
            return x * p["w"], state

    model, opt = amp.initialize(Lin(), opts.FusedAdam(1e-3),
                                opt_level="O2", half_dtype="float16",
                                verbosity=0)
    params, _ = model.init(jax.random.PRNGKey(0))
    ost = opt.init(params)
    ring = obs.EventRing()
    prev = obs.set_ring(ring)
    try:
        reg = obs.MetricsRegistry()
        amp.record_scaler(ost, registry=reg, step=0)
        assert ring.snapshot("scaler_skip") == []      # no skip yet
        g = jax.tree_util.tree_map(
            lambda p: jnp.full(p.shape, jnp.inf, jnp.float32), params)
        _, ost2, _ = opt.step(params, ost, g)
        amp.record_scaler(ost2, registry=reg, step=1)
        (ev,) = ring.snapshot("scaler_skip")
        assert ev["steps_skipped"] == 1 and ev["step"] == 1
        assert ev["loss_scale"] == 2.0 ** 15
        # re-recording the SAME skip count appends nothing
        amp.record_scaler(ost2, registry=reg, step=2)
        assert len(ring.snapshot("scaler_skip")) == 1
        # a FRESH registry re-reports the cumulative total once — the
        # documented tradeoff: dedup is per registry, because any
        # process-global gate on totals would suppress a SECOND
        # optimizer's first skips (worse than a duplicate event)
        amp.record_scaler(ost2, registry=obs.MetricsRegistry(), step=3)
        evs = ring.snapshot("scaler_skip")
        assert len(evs) == 2 and evs[-1]["steps_skipped"] == 1
    finally:
        obs.set_ring(prev)


# -- step-time attribution (PR 6) ------------------------------------------

def test_steptime_attribution_decomposition_and_schema():
    """attribute_step on deterministic sleepers: the decomposition's
    internal identities (comm = step - compute, clamped; per-level
    times reassemble the isolated comm time; overlap in [0, 1]) hold
    and the resulting bench record passes the validator."""
    from apex_tpu.observability import steptime

    def sleeper(s):
        def fn():
            import time as _t
            _t.sleep(s)
            return jnp.ones((4,))
        return fn

    plan = [{"topology": "hierarchical", "comm_dtype": "float32",
             "ici_wire_bytes": 3000, "dcn_wire_bytes": 1000,
             "wire_bytes": 4000},
            {"topology": "flat", "wire_bytes": 4000}]
    att = steptime.attribute_step(sleeper(0.03), sleeper(0.018),
                                  sleeper(0.012), args=(), plan=plan,
                                  iters=2, warmup=0)
    for k in steptime.ATTRIBUTION_FIELDS:
        assert isinstance(att[k], float) and att[k] >= 0.0, k
    assert 0.0 <= att["overlap_fraction"] <= 1.0
    assert att["comm_ms"] == pytest.approx(
        max(att["step_ms"] - att["compute_ms"], 0.0), abs=2e-4)
    # the per-level split reassembles the isolated measurement and
    # follows the plan's byte weights (3000+4000 ici vs 1000 dcn);
    # fields are rounded to 4 decimals, hence the absolute tolerance
    assert att["ici_ms"] + att["dcn_ms"] == pytest.approx(
        att["comm_isolated_ms"], abs=2e-4)
    assert att["dcn_ms"] == pytest.approx(
        att["comm_isolated_ms"] * 1000 / 8000, abs=2e-4)
    assert len(att["buckets"]) == 2
    assert att["buckets"][1]["dcn_ms"] == 0.0    # flat bucket: all ici
    rec = exporters.JsonlExporter.enrich(
        {"metric": "train_step_attribution_hier", "value": att["step_ms"],
         "unit": "ms", "vs_baseline": None, "backend": "cpu", "ndev": 8,
         "arch": "cpu",
         **{k: att[k] for k in steptime.ATTRIBUTION_FIELDS},
         **{k: att[k] for k in steptime.OVERLAP_SCHEDULE_FIELDS}})
    assert exporters.validate_bench_record(rec) == []
    with pytest.raises(ValueError, match="iters"):
        steptime.blocked_time(sleeper(0.0), iters=0)


def test_attribution_measured_ici_step_zero_weight_level_folds():
    """A measured ici_step under a plan whose buckets carry no DCN
    bytes (single-fabric): the measured non-ici residue folds into the
    ici column instead of silently vanishing (a zero byte weight can't
    absorb time), so the record still reassembles comm_isolated_ms and
    passes the validator."""
    from apex_tpu.observability import steptime

    def sleeper(s):
        def fn():
            import time as _t
            _t.sleep(s)
            return jnp.ones((4,))
        return fn

    plan = [{"topology": "flat", "wire_bytes": 100}]
    att = steptime.attribute_step(sleeper(0.02), sleeper(0.012),
                                  sleeper(0.008), args=(), plan=plan,
                                  iters=2, warmup=0,
                                  ici_step=sleeper(0.003))
    assert att["dcn_ms"] == 0.0
    assert att["ici_ms"] == pytest.approx(att["comm_isolated_ms"],
                                          abs=2e-4)
    rec = exporters.JsonlExporter.enrich(
        {"metric": "train_step_attribution_flat", "value": att["step_ms"],
         "unit": "ms", "vs_baseline": None, "backend": "cpu", "ndev": 8,
         "arch": "cpu",
         **{k: att[k] for k in steptime.ATTRIBUTION_FIELDS},
         **{k: att[k] for k in steptime.OVERLAP_SCHEDULE_FIELDS}})
    assert exporters.validate_bench_record(rec) == []


def test_attribution_zero_weight_plan_still_reassembles():
    """A plan whose buckets carry NO recognized byte weight (no
    wire_bytes/bytes, or zero) can't label the per-level split — the
    fallback attributes everything to the ici column so ici+dcn still
    reassembles comm_isolated_ms and the record passes its own
    schema, instead of emitting ici=dcn=0 and failing it."""
    from apex_tpu.observability import steptime

    def sleeper(s):
        def fn():
            import time as _t
            _t.sleep(s)
            return jnp.ones((4,))
        return fn

    for plan in ([{"topology": "flat", "payload_bytes": 100}],
                 [{"topology": "flat", "wire_bytes": 0}]):
        att = steptime.attribute_step(sleeper(0.02), sleeper(0.012),
                                      sleeper(0.008), args=(),
                                      plan=plan, iters=2, warmup=0)
        assert att["dcn_ms"] == 0.0
        assert att["ici_ms"] == pytest.approx(att["comm_isolated_ms"],
                                              abs=2e-4)
        rec = exporters.JsonlExporter.enrich(
            {"metric": "train_step_attribution_flat",
             "value": att["step_ms"], "unit": "ms", "vs_baseline": None,
             "backend": "cpu", "ndev": 8, "arch": "cpu",
             **{k: att[k] for k in steptime.ATTRIBUTION_FIELDS},
             **{k: att[k]
                for k in steptime.OVERLAP_SCHEDULE_FIELDS}})
        assert exporters.validate_bench_record(rec) == []


def test_attribution_record_schema_mutations():
    """A record carrying overlap_fraction must be internally
    consistent: compute+comm reassemble the step, the level times
    reassemble the isolated comm, the fraction is a fraction."""
    base = exporters.JsonlExporter.enrich(
        {"metric": "train_step_attribution_flat", "value": 10.0,
         "unit": "ms", "vs_baseline": None, "backend": "cpu", "ndev": 8,
         "arch": "cpu", "step_ms": 10.0, "compute_ms": 6.0,
         "comm_ms": 4.0, "comm_isolated_ms": 5.0,
         "overlap_fraction": 0.2, "ici_ms": 4.0, "dcn_ms": 1.0,
         "overlap_mode": "reduce_after_backward", "n_stages": 1,
         "issue_order": [0]})
    assert exporters.validate_bench_record(base) == []
    bad = dict(base, overlap_fraction=1.5)
    assert any("overlap_fraction" in e
               for e in exporters.validate_bench_record(bad))
    bad = dict(base, comm_ms=-1.0)
    assert any(">= 0" in e for e in exporters.validate_bench_record(bad))
    bad = dict(base, compute_ms=1.0)       # 1 + 4 != 10
    assert any("inconsistent with step_ms" in e
               for e in exporters.validate_bench_record(bad))
    bad = dict(base, ici_ms=1.0)           # 1 + 1 != 5
    assert any("reassemble" in e
               for e in exporters.validate_bench_record(bad))
    missing = {k: v for k, v in base.items() if k != "dcn_ms"}
    assert any("dcn_ms" in e
               for e in exporters.validate_bench_record(missing))


def test_ddp_comm_enabled_compute_twin_is_collective_free():
    """comm_enabled=False (the step-time compute twin) elides every
    gradient collective while keeping the local average, so the twin
    graph is collective-free and its values are the local mean."""
    from apex_tpu import parallel
    mesh = Mesh(np.array(jax.devices()[:8]), ("data",))
    ddp = parallel.DistributedDataParallel()
    ddp.comm_enabled = False
    grads = {"a": jnp.ones((64,), jnp.float32)}

    def step(g):
        return ddp.allreduce_grads(g)

    mapped = jax.shard_map(step, mesh=mesh, in_specs=(P(),),
                           out_specs=P(), check_vma=False)
    txt = str(jax.make_jaxpr(mapped)(grads))
    assert not any(p in txt for p in ("psum", "all_gather",
                                      "reduce_scatter", "all_to_all",
                                      "ppermute")), txt
    out = jax.jit(mapped)(grads)
    # local gradient averaged by the axis size, no cross-replica sum
    assert float(out["a"][0]) == pytest.approx(1.0 / 8)
    assert ddp.last_comm_stats == []


def test_validate_trace_record_pins_causal_shape():
    """kind: trace records — the per-request flight record — must hold
    the causal invariants: unique positive span ids, parents strictly
    earlier, every span in the record's trace.  A violated parent
    order is exactly the worker-thread interleaving bug the schema
    exists to catch."""
    rec = obs.SpanRecorder()
    tid = obs.new_trace_id()
    root = rec.event("submit", trace_id=tid)
    with rec.activate(tid, root):
        with rec.span("dispatch"):
            rec.event("tick")
    good = exporters.JsonlExporter.enrich(rec.trace_record(tid))
    assert exporters.validate_trace_record(good) == []
    assert exporters.validate_telemetry_record(good) == []  # dispatch
    assert good["span_count"] == 3

    def bad(**mut):
        return exporters.validate_trace_record({**good, **mut})

    assert any("kind" in e for e in bad(kind="bench"))
    assert any("trace_id" in e for e in bad(trace_id=""))
    assert any("non-empty" in e for e in bad(spans=[], span_count=0))
    assert any("span_count" in e for e in bad(span_count=7))
    # a span whose parent is NOT causally earlier (the lost-chain bug)
    spans = [dict(s) for s in good["spans"]]
    spans[1]["parent_id"] = spans[2]["span_id"] + 5
    assert any("causally earlier" in e for e in bad(spans=spans))
    # duplicate span ids
    spans = [dict(s) for s in good["spans"]]
    spans[2]["span_id"] = spans[0]["span_id"]
    errs = bad(spans=spans)
    assert any("duplicate" in e or "causally" in e for e in errs)
    # a span smuggled in from another trace
    spans = [dict(s) for s in good["spans"]]
    spans[1]["trace_id"] = "other-trace"
    assert any("belongs to trace" in e for e in bad(spans=spans))
    spans = [dict(s) for s in good["spans"]]
    spans[0]["ph"] = "Z"
    assert any("ph" in e for e in bad(spans=spans))
    # the chain's head evicted (bounded recorder): the orphaned child
    # parents on a span that is NOT in the record — incomplete trace
    spans = [dict(s) for s in good["spans"][1:]]
    assert any("not in this record" in e
               for e in bad(spans=spans, span_count=len(spans)))
    assert exporters.validate_trace_record("nope") != []


def test_histogram_summary_cached_between_writes():
    """Satellite 2 pin: summary() memoizes until the next observation —
    a router reading Engine.stats() every tick pays the bucket-walk
    quantiles once per write, not once per read."""
    h = obs.Histogram("lat", buckets=(1.0, 2.0, 5.0))
    assert h._summary_computes == 0
    for v in (0.5, 1.5, 3.0):
        h.observe(v)
    first = h.summary()
    for _ in range(50):
        assert h.summary() == first
    assert h._summary_computes == 1          # 51 reads, ONE compute
    h.observe(4.0)                           # write invalidates
    s2 = h.summary()
    assert s2["count"] == 4 and s2 != first
    for _ in range(10):
        h.summary()
    assert h._summary_computes == 2
    # the cache returns copies — mutating a reader's dict is safe
    s2["p50"] = -1
    assert h.summary()["p50"] != -1
    assert h._summary_computes == 2
    # percentile() still answers directly (uncached path unchanged)
    assert h.percentile(0.5) == h.summary()["p50"]
    # _restore (DeviceMetrics flush) also invalidates
    h._restore([1, 0, 0, 0], 0.5)
    assert h.summary()["count"] == 1
    assert h._summary_computes == 3


# -- exporters ------------------------------------------------------------

def test_prometheus_text_exposition():
    reg = obs.MetricsRegistry()
    reg.counter("req_total", help="requests").inc(3)
    reg.gauge("depth").set(2)
    h = reg.histogram("lat", buckets=(0.1, 1.0))
    h.observe(0.05)
    h.observe(5.0)
    b = reg.counter("bytes_total")
    b.labels(dtype="float32").inc(64)
    text = exporters.prometheus_text(reg)
    assert "# HELP req_total requests" in text
    assert "# TYPE req_total counter" in text
    assert "req_total 3.0" in text
    assert "depth 2.0" in text
    assert 'lat_bucket{le="0.1"} 1' in text
    assert 'lat_bucket{le="+Inf"} 2' in text
    assert "lat_count 2" in text
    assert 'bytes_total{dtype="float32"} 64.0' in text


def test_jsonl_exporter_enrich_and_emit(tmp_path):
    path = str(tmp_path / "out.jsonl")
    with exporters.JsonlExporter(path=path) as ex:
        line = ex.emit({"metric": "m", "value": 1.0, "unit": "x"})
        # replayed record keeps its own provenance
        replay = ex.emit({"metric": "m2", "value": 2.0, "stale": True,
                          "host": {"hostname": "cap", "pid": 1}})
    assert line["schema_version"] == exporters.SCHEMA_VERSION
    assert line["stale"] is False
    assert line["host"]["hostname"]
    assert replay["stale"] is True
    assert replay["host"] == {"hostname": "cap", "pid": 1}
    with open(path) as f:
        assert len(f.readlines()) == 2


def test_bench_record_schema_validation():
    good = exporters.JsonlExporter.enrich(
        {"metric": "m", "value": 1.5, "unit": "x", "vs_baseline": None,
         "backend": "cpu", "ndev": 8, "arch": "cpu"})
    assert exporters.validate_bench_record(good) == []
    # error lines (value null) are valid
    err_line = exporters.JsonlExporter.enrich(
        {"metric": "m", "value": None, "unit": None, "vs_baseline": None,
         "backend": "cpu", "ndev": 8, "arch": "cpu", "error": "boom"})
    assert exporters.validate_bench_record(err_line) == []
    # missing stale / wrong types are caught
    bad = dict(good)
    del bad["stale"]
    assert any("stale" in e for e in exporters.validate_bench_record(bad))
    bad = dict(good, value="fast")
    assert any("value" in e for e in exporters.validate_bench_record(bad))
    bad = dict(good, schema_version=0)
    assert any("schema_version" in e
               for e in exporters.validate_bench_record(bad))
    assert exporters.validate_bench_record([1, 2]) != []


def test_bench_record_schema_serving_decode_window_fields():
    """Fresh engine-decode lines must carry the decode-window fields
    (PR 2); stale replays of pre-window records and error lines stay
    valid without them."""
    base = {"metric": "gpt_tiny_engine_decode_throughput", "value": 9.0,
            "unit": "tokens/sec/chip", "vs_baseline": None,
            "backend": "cpu", "ndev": 8, "arch": "cpu",
            "kv_cache_bytes": 16384,    # required fresh at schema v3
            # required fresh at schema v8 (KV fragmentation pair)
            "kv_waste_bytes": 4096, "kv_utilization": 0.75,
            # required fresh at schema v10 (compile-plane triple)
            "cold_compile_ms": 350.0, "compiles_total": 2,
            "steady_state_retraces": 0,
            # required fresh at schema v12 (paged serving plane)
            "admission_mode": "fixed_slot"}
    good = exporters.JsonlExporter.enrich(
        dict(base, window=8, tokens_per_sync=7.5))
    assert exporters.validate_bench_record(good) == []
    # missing window on a fresh decode line is a schema violation
    missing = exporters.JsonlExporter.enrich(dict(base))
    assert any("window" in e
               for e in exporters.validate_bench_record(missing))
    # missing kv_cache_bytes on a fresh v3 decode line too (PR 8)
    nokv = {k: v for k, v in base.items() if k != "kv_cache_bytes"}
    assert any("kv_cache_bytes" in e
               for e in exporters.validate_bench_record(
                   exporters.JsonlExporter.enrich(dict(nokv, window=8))))
    # missing the fragmentation pair on a fresh v8 decode line (PR 13)
    for key in ("kv_waste_bytes", "kv_utilization"):
        nofrag = {k: v for k, v in base.items() if k != key}
        assert any(key in e
                   for e in exporters.validate_bench_record(
                       exporters.JsonlExporter.enrich(
                           dict(nofrag, window=8)))), key
    # ...but an archived v7 line without the pair stays valid at its
    # declared version, as does an archived v2 line without any of it
    v7 = exporters.JsonlExporter.enrich(
        dict({k: v for k, v in base.items()
              if k not in ("kv_waste_bytes", "kv_utilization")},
             window=8))
    v7["schema_version"] = 7
    assert exporters.validate_bench_record(v7) == []
    v2 = exporters.JsonlExporter.enrich(dict(nokv, window=8))
    v2["schema_version"] = 2
    assert exporters.validate_bench_record(v2) == []
    # wrong types / values are caught wherever the field appears
    for w in (0, -2, 1.5, True, "8"):
        bad = exporters.JsonlExporter.enrich(dict(base, window=w))
        assert any("window" in e
                   for e in exporters.validate_bench_record(bad)), w
    bad = exporters.JsonlExporter.enrich(
        dict(base, window=8, tokens_per_sync="lots"))
    assert any("tokens_per_sync" in e
               for e in exporters.validate_bench_record(bad))
    bad = exporters.JsonlExporter.enrich(
        dict(base, window=8, kv_cache_bytes=-5))
    assert any("kv_cache_bytes" in e
               for e in exporters.validate_bench_record(bad))
    bad = exporters.JsonlExporter.enrich(
        dict(base, window=8, kv_waste_bytes=999_999))   # > cache
    assert any("kv_waste_bytes" in e
               for e in exporters.validate_bench_record(bad))
    bad = exporters.JsonlExporter.enrich(
        dict(base, window=8, kv_utilization=1.2))
    assert any("kv_utilization" in e
               for e in exporters.validate_bench_record(bad))
    # a windowed line must report tokens/sec
    bad = exporters.JsonlExporter.enrich(
        dict(base, window=8, unit="steps/sec"))
    assert any("tokens/sec" in e
               for e in exporters.validate_bench_record(bad))
    # stale replay of an old (pre-window) record: exempt
    stale = exporters.JsonlExporter.enrich(dict(base), stale=True)
    assert exporters.validate_bench_record(stale) == []
    # error line for a hung decode config: exempt
    err = exporters.JsonlExporter.enrich(
        {"metric": "gpt_tiny_engine_decode_throughput", "value": None,
         "unit": None, "vs_baseline": None, "backend": "cpu",
         "ndev": 8, "arch": "cpu", "error": "config hung"})
    assert exporters.validate_bench_record(err) == []


def test_bench_emits_schema_valid_jsonl():
    """A fresh train-throughput line as bench.py's emit enriches it is
    schema-valid, and the v3 cost-model requirement bites."""
    fresh = exporters.JsonlExporter.enrich(
        {"metric": "resnet50_amp_o2_ddp_train_throughput",
         "value": 1830.0,
         "unit": "images/sec/chip", "vs_baseline": 11.7,
         "backend": "tpu", "ndev": 1, "arch": "TPU v5 lite",
         # schema-v3 cost-model fields every fresh train line carries
         "flops_per_step": 3.15e12, "achieved_tflops": 45.0,
         "mfu": 0.228, "peak_bytes": 9_000_000_000,
         # schema-v10 compile-plane triple (fresh train lines)
         "cold_compile_ms": 5400.0, "compiles_total": 1,
         "steady_state_retraces": 0})
    assert exporters.validate_bench_record(fresh) == []
    # the v3 requirement bites: a fresh train line without them flags
    bare = {k: v for k, v in fresh.items()
            if k not in ("flops_per_step", "achieved_tflops", "mfu",
                         "peak_bytes")}
    assert any("flops_per_step" in e
               for e in exporters.validate_bench_record(bare))
    # archived v2 train lines (and stale replays) stay valid
    v2 = dict(bare)
    v2["schema_version"] = 2
    assert exporters.validate_bench_record(v2) == []
    assert exporters.validate_bench_record(dict(bare, stale=True)) == []


def test_check_bench_schema_cli(tmp_path):
    """The tests/ci gate accepts a valid stream and rejects a broken
    one."""
    import subprocess
    import sys
    import os
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    script = os.path.join(root, "tests", "ci", "check_bench_schema.py")
    good = json.dumps(exporters.JsonlExporter.enrich(
        {"metric": "m", "value": 1.0, "unit": "x", "backend": "cpu",
         "ndev": 8, "arch": "cpu"}))
    r = subprocess.run([sys.executable, script], input=good + "\n",
                       capture_output=True, text=True)
    assert r.returncode == 0, r.stderr
    r = subprocess.run([sys.executable, script],
                       input='{"metric": "m"}\n',
                       capture_output=True, text=True)
    assert r.returncode == 1


def _trend_round(tmp_path, name, lines):
    """One BENCH_r*.json runbook wrapper holding ``lines`` as its
    JSONL tail (what check_bench_trend.py parses)."""
    doc = {"n": name, "cmd": "python bench.py", "rc": 0,
           "tail": "\n".join(json.dumps(ln) for ln in lines)}
    with open(str(tmp_path / name), "w") as f:
        json.dump(doc, f)


def _run_trend(args):
    import subprocess
    import sys
    import os
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    script = os.path.join(root, "tests", "ci", "check_bench_trend.py")
    return subprocess.run([sys.executable, script] + args,
                          capture_output=True, text=True)


def test_check_bench_trend_gate(tmp_path):
    """The trend gate (acceptance pin): exit 0 on the BENCH history at
    the repo root, nonzero on a synthetic history where a fresh
    accelerator metric regresses past tolerance — and a record marked
    ``stale: true`` is partitioned out of the trend."""
    r = _run_trend([])
    assert r.returncode == 0, r.stderr
    assert "stale replays partitioned out" in r.stderr

    def tpu(value, **kw):
        return exporters.JsonlExporter.enrich(
            {"metric": "resnet18_fwd_bwd_throughput", "value": value,
             "unit": "images/sec/chip", "vs_baseline": None,
             "backend": "tpu", "ndev": 1, "arch": "TPU v5 lite", **kw})

    # fresh-vs-fresh accelerator regression past tolerance -> error
    d3 = tmp_path / "case3"
    d3.mkdir()
    _trend_round(d3, "BENCH_r01.json", [tpu(1000.0)])
    _trend_round(d3, "BENCH_r02.json", [tpu(600.0)])   # -40%
    r = _run_trend(["--dir", str(d3)])
    assert r.returncode == 1 and "regressed" in r.stderr
    # ...within tolerance passes
    r = _run_trend(["--dir", str(d3), "--tol", "0.8"])
    assert r.returncode == 0
    # change is relative to the PREVIOUS value in both directions: a
    # 21% rate drop is under the 25% default tol and must not gate
    d3b = tmp_path / "case3b"
    d3b.mkdir()
    _trend_round(d3b, "BENCH_r01.json", [tpu(1000.0)])
    _trend_round(d3b, "BENCH_r02.json", [tpu(790.0)])  # -21%
    r = _run_trend(["--dir", str(d3b)])
    assert r.returncode == 0, r.stderr

    # a record marked stale: partitioned out, clean — and it must NOT
    # count as progress (no fresh line to compare)
    d4 = tmp_path / "case4"
    d4.mkdir()
    _trend_round(d4, "BENCH_r01.json", [tpu(500.0)])
    _trend_round(d4, "BENCH_r02.json", [tpu(1830.0, stale=True)])
    r = _run_trend(["--dir", str(d4)])
    assert r.returncode == 0
    assert "1 stale replays partitioned out" in r.stderr

    # CPU smoke regressions warn but do not gate... unless --strict-cpu
    d5 = tmp_path / "case5"
    d5.mkdir()

    def cpu(value):
        return exporters.JsonlExporter.enrich(
            {"metric": "fused_lamb_step_time", "value": value,
             "unit": "ms", "vs_baseline": None, "backend": "cpu",
             "ndev": 8, "arch": "cpu"})
    _trend_round(d5, "BENCH_r01.json", [cpu(10.0)])
    _trend_round(d5, "BENCH_r02.json", [cpu(47.0)])
    r = _run_trend(["--dir", str(d5)])
    assert r.returncode == 0 and "WARNING" in r.stderr
    r = _run_trend(["--dir", str(d5), "--strict-cpu"])
    assert r.returncode == 1


def test_check_bench_trend_overlap_fields_gate(tmp_path):
    """The PR 14 trend columns: a fresh accelerator line whose
    overlap_fraction / measured_overlap_fraction DROPS past --tol (or
    whose comm_visible_ms GROWS past it) gates; CPU smoke warns; and a
    zero baseline — the reduce-after-backward world — never trends (no
    overlap yet means nothing to lose)."""
    def attr(backend, value, frac, visible):
        return exporters.JsonlExporter.enrich(
            {"metric": "train_step_attribution_overlap",
             "value": value, "unit": "ms", "vs_baseline": None,
             "backend": backend, "ndev": 8,
             "arch": "TPU v5 lite" if backend == "tpu" else "cpu",
             "overlap_fraction": frac, "comm_visible_ms": visible,
             "overlap_mode": "overlapped", "n_stages": 4,
             "issue_order": [3, 2, 1, 0]})

    # accelerator overlap_fraction drop past tol -> error
    d1 = tmp_path / "ovl1"
    d1.mkdir()
    _trend_round(d1, "BENCH_r01.json", [attr("tpu", 10.0, 0.8, 1.0)])
    _trend_round(d1, "BENCH_r02.json", [attr("tpu", 10.1, 0.3, 1.0)])
    r = _run_trend(["--dir", str(d1)])
    assert r.returncode == 1
    assert "overlap_fraction dropped" in r.stderr
    # ...within tolerance passes
    r = _run_trend(["--dir", str(d1), "--tol", "0.7"])
    assert r.returncode == 0, r.stderr

    # accelerator comm_visible_ms growth past tol -> error
    d2 = tmp_path / "ovl2"
    d2.mkdir()
    _trend_round(d2, "BENCH_r01.json", [attr("tpu", 10.0, 0.8, 1.0)])
    _trend_round(d2, "BENCH_r02.json", [attr("tpu", 10.1, 0.8, 2.0)])
    r = _run_trend(["--dir", str(d2)])
    assert r.returncode == 1
    assert "comm_visible_ms grew" in r.stderr

    # CPU smoke: warns only, unless --strict-cpu
    d3 = tmp_path / "ovl3"
    d3.mkdir()
    _trend_round(d3, "BENCH_r01.json", [attr("cpu", 10.0, 0.8, 1.0)])
    _trend_round(d3, "BENCH_r02.json", [attr("cpu", 10.1, 0.3, 1.0)])
    r = _run_trend(["--dir", str(d3)])
    assert r.returncode == 0 and "WARNING" in r.stderr
    r = _run_trend(["--dir", str(d3), "--strict-cpu"])
    assert r.returncode == 1

    # zero baseline never trends: 0.0 -> 0.0 is today's world, and a
    # fraction appearing off zero is progress, not regression
    d4 = tmp_path / "ovl4"
    d4.mkdir()
    _trend_round(d4, "BENCH_r01.json", [attr("tpu", 10.0, 0.0, 1.0)])
    _trend_round(d4, "BENCH_r02.json", [attr("tpu", 10.1, 0.0, 1.0)])
    _trend_round(d4, "BENCH_r03.json", [attr("tpu", 10.0, 0.6, 1.0)])
    r = _run_trend(["--dir", str(d4)])
    assert r.returncode == 0, r.stderr

    # ...but a LOWER-is-better time at 0 is the success state: comm
    # returning from fully hidden to measurably visible is the worst
    # regression the column exists for — gates even from a zero
    # baseline (rounding-noise returns under 0.05 ms do not)
    d4b = tmp_path / "ovl4b"
    d4b.mkdir()
    _trend_round(d4b, "BENCH_r01.json", [attr("tpu", 10.0, 0.9, 0.0)])
    _trend_round(d4b, "BENCH_r02.json", [attr("tpu", 10.1, 0.9, 4.0)])
    r = _run_trend(["--dir", str(d4b)])
    assert r.returncode == 1
    assert "returned from a zero baseline" in r.stderr
    d4c = tmp_path / "ovl4c"
    d4c.mkdir()
    _trend_round(d4c, "BENCH_r01.json", [attr("tpu", 10.0, 0.9, 0.0)])
    _trend_round(d4c, "BENCH_r02.json", [attr("tpu", 10.1, 0.9, 0.01)])
    r = _run_trend(["--dir", str(d4c)])
    assert r.returncode == 0, r.stderr

    # measured_overlap_fraction (profile metric lines) follows the
    # same policy
    def prof(value, frac):
        return exporters.JsonlExporter.enrich(
            {"metric": "comm_profile_overlap_comm_visible_ms",
             "value": value, "unit": "ms", "vs_baseline": None,
             "backend": "tpu", "ndev": 8, "arch": "TPU v5 lite",
             "measured_overlap_fraction": frac})
    d5 = tmp_path / "ovl5"
    d5.mkdir()
    _trend_round(d5, "BENCH_r01.json", [prof(1.0, 0.9)])
    _trend_round(d5, "BENCH_r02.json", [prof(1.05, 0.2)])
    r = _run_trend(["--dir", str(d5)])
    assert r.returncode == 1
    assert "measured_overlap_fraction dropped" in r.stderr


def test_check_bench_trend_memory_and_mfu_gate(tmp_path):
    """The PR 8 trend columns: peak-memory growth past --mem-tol gates
    on EVERY backend (the compiled plan is deterministic — CPU noise
    is no excuse), stale replays stay partitioned out, kind: memory
    records trend by entry point, and MFU drops follow the same
    accelerator-gates / CPU-warns policy as throughput."""

    def train(value, peak, mfu=None, backend="cpu", **kw):
        rec = {"metric": "resnet18_train_throughput", "value": value,
               "unit": "images/sec/chip", "vs_baseline": None,
               "backend": backend, "ndev": 8, "arch": backend,
               "peak_bytes": peak}
        if mfu is not None:
            rec["mfu"] = mfu
        return exporters.JsonlExporter.enrich({**rec, **kw})

    # peak-memory regression on a CPU backend: throughput noise warns,
    # but the 40% plan growth is an error
    d1 = tmp_path / "mem1"
    d1.mkdir()
    _trend_round(d1, "BENCH_r01.json", [train(100.0, 1_000_000)])
    _trend_round(d1, "BENCH_r02.json", [train(101.0, 1_400_000)])
    r = _run_trend(["--dir", str(d1)])
    assert r.returncode == 1
    assert "peak memory grew 40%" in r.stderr
    # ...within a loosened --mem-tol it passes
    r = _run_trend(["--dir", str(d1), "--mem-tol", "0.5"])
    assert r.returncode == 0, r.stderr

    # a stale replay carrying a bigger peak is partitioned out
    d2 = tmp_path / "mem2"
    d2.mkdir()
    _trend_round(d2, "BENCH_r01.json", [train(100.0, 1_000_000)])
    _trend_round(d2, "BENCH_r02.json",
                 [train(100.0, 9_000_000, stale=True)])
    r = _run_trend(["--dir", str(d2)])
    assert r.returncode == 0, r.stderr

    # kind: memory records trend by entry point
    def memrec(peak):
        return exporters.JsonlExporter.enrich(
            {"kind": "memory", "entry_point": "engine_step_k",
             "source": "compiled", "flops": 1e6, "backend": "cpu",
             "peak_bytes": peak})

    d3 = tmp_path / "mem3"
    d3.mkdir()
    _trend_round(d3, "BENCH_r01.json", [memrec(1_000_000)])
    _trend_round(d3, "BENCH_r02.json", [memrec(1_500_000)])
    r = _run_trend(["--dir", str(d3)])
    assert r.returncode == 1 and "engine_step_k" in r.stderr
    # identical plans across rounds are the normal case: clean
    d3b = tmp_path / "mem3b"
    d3b.mkdir()
    _trend_round(d3b, "BENCH_r01.json", [memrec(1_000_000)])
    _trend_round(d3b, "BENCH_r02.json", [memrec(1_000_000)])
    assert _run_trend(["--dir", str(d3b)]).returncode == 0

    # MFU: accelerator drop past tol gates, CPU drop warns
    d4 = tmp_path / "mfu1"
    d4.mkdir()
    _trend_round(d4, "BENCH_r01.json",
                 [train(1000.0, 1_000_000, mfu=0.20, backend="tpu",
                        arch="TPU v5 lite")])
    _trend_round(d4, "BENCH_r02.json",
                 [train(990.0, 1_000_000, mfu=0.10, backend="tpu",
                        arch="TPU v5 lite")])
    r = _run_trend(["--dir", str(d4)])
    assert r.returncode == 1 and "MFU regressed" in r.stderr
    d5 = tmp_path / "mfu2"
    d5.mkdir()
    _trend_round(d5, "BENCH_r01.json", [train(100.0, 1_000_000,
                                              mfu=0.02)])
    _trend_round(d5, "BENCH_r02.json", [train(99.0, 1_000_000,
                                              mfu=0.01)])
    r = _run_trend(["--dir", str(d5)])
    assert r.returncode == 0 and "MFU regressed" in r.stderr \
        and "WARNING" in r.stderr
    assert _run_trend(["--dir", str(d5), "--strict-cpu"]).returncode == 1


def test_check_bench_trend_zero_peak_memory_ratchet(tmp_path):
    """The ZeRO memory ratchet on the --comm zero legs: a stage
    landing DROPS the leg's compiled peak_bytes and the trend accepts
    the new floor without ceremony; the next round regressing back
    toward the unsharded peak gates at --mem-tol on EVERY backend —
    the compiled plan is deterministic, so CPU noise is no excuse
    (same policy as the replication-ledger gate)."""

    def zleg(peak, stage=3):
        return exporters.JsonlExporter.enrich(
            {"metric": f"ddp_mlp_zero{stage}_train_throughput",
             "value": 5000.0, "unit": "samples/sec/chip",
             "vs_baseline": None, "backend": "cpu", "ndev": 8,
             "arch": "cpu", "peak_bytes": peak, "zero_stage": stage,
             "flops_per_step": 1e6, "achieved_tflops": 0.001,
             "mfu": None, "cold_compile_ms": 10.0,
             "compiles_total": 1, "steady_state_retraces": 0})

    # ratchet DOWN: the stage-3 peak collapse vs last round is clean
    d1 = tmp_path / "zmem1"
    d1.mkdir()
    _trend_round(d1, "BENCH_r01.json", [zleg(151_000_000)])
    _trend_round(d1, "BENCH_r02.json", [zleg(128_000_000)])
    r = _run_trend(["--dir", str(d1), "--mem-tol", "0.05"])
    assert r.returncode == 0, r.stderr

    # ...and the ratcheted-down floor HOLDS: regressing back up past
    # --mem-tol gates, even on the CPU backend
    d2 = tmp_path / "zmem2"
    d2.mkdir()
    _trend_round(d2, "BENCH_r01.json", [zleg(128_000_000)])
    _trend_round(d2, "BENCH_r02.json", [zleg(145_000_000)])  # +13%
    r = _run_trend(["--dir", str(d2), "--mem-tol", "0.1"])
    assert r.returncode == 1
    assert "peak memory grew" in r.stderr
    # the same growth inside a loosened tolerance passes
    r = _run_trend(["--dir", str(d2), "--mem-tol", "0.25"])
    assert r.returncode == 0, r.stderr


def test_check_bench_trend_partitions_numerics_records(tmp_path):
    """kind: numerics gradient-health dumps (PR 9) are per-run
    diagnostics, not a cross-round trend: fresh ones pass through
    without entering the measurement trend, stale replays count
    toward the partition tally like every other record family."""
    def numrec(overflow, **kw):
        return exporters.JsonlExporter.enrich(
            {"kind": "numerics", "metric": "resnet18_o2_ddp_numerics",
             "steps": 10, "overflow_steps": overflow,
             "backend": "cpu",
             "layers": [{"name": "w", "nonfinite": 0, "abs_max": 1.0,
                         "grad_norm": 1.0,
                         "underflow_fraction": 0.0}], **kw})

    d = tmp_path / "num1"
    d.mkdir()
    _trend_round(d, "BENCH_r01.json", [numrec(0)])
    # a later round with MORE overflows must not read as a metric
    # regression — numerics records carry no trend value
    _trend_round(d, "BENCH_r02.json", [numrec(5),
                                       numrec(0, stale=True)])
    r = _run_trend(["--dir", str(d)])
    assert r.returncode == 0, r.stderr
    assert "0 fresh measurements counted" in r.stderr
    assert "1 stale replays partitioned out" in r.stderr


# -- engine telemetry -----------------------------------------------------

def _gpt(seed=0):
    m = models.GPT(models.GPTConfig(vocab_size=64, block_size=24,
                                    n_layer=2, n_head=4, n_embd=32,
                                    dropout=0.0, n_kv_head=2))
    params, _ = m.init(jax.random.PRNGKey(seed))
    return m, params


def test_engine_stats_enriched_fields():
    m, params = _gpt()
    eng = serving.Engine(m, params, slots=2, buf_len=24)
    rng = np.random.RandomState(0)
    rids = [eng.submit(list(rng.randint(0, 64, 5)), max_new_tokens=4)
            for _ in range(3)]                  # 3rd queues (2 slots)
    s = eng.stats()
    assert s["queue_depth"] == s["waiting"] == 1
    assert s["occupancy"] == 1.0 and s["slots"] == 2
    assert s["admitted"] == 2
    assert s["prefill_latency"]["count"] == 2
    while eng.live() or eng.stats()["waiting"]:
        eng.step()
    s = eng.stats()
    assert s["finished"] == 3 and s["admitted"] == 3
    assert s["tokens_generated"] == 12
    assert s["decode_steps"] == s["decode_step_latency"]["count"] > 0
    assert s["ttft"]["count"] == 3 and s["ttft"]["mean"] > 0
    assert s["request_tokens_per_sec"]["count"] == 3
    assert s["queue_wait"]["count"] == 3
    assert s["prefix_hits"] == 0 and s["prefix_hit_rate"] == 0.0
    for rid in rids:
        assert len(eng.result(rid)) == 4


def test_engine_stats_memory_fields():
    """Engine.stats() memory surface (PR 8): kv_cache_bytes recomputed
    from the live cache buffers, the live-array census, the
    engine_kv_cache_bytes gauge, and HBM fields None on a CPU-style
    backend (no fabricated occupancy)."""
    m, params = _gpt()
    eng = serving.Engine(m, params, slots=2, buf_len=24)
    s = eng.stats()
    expect_kv = sum(leaf.nbytes
                    for leaf in jax.tree_util.tree_leaves(eng.cache))
    assert s["kv_cache_bytes"] == expect_kv > 0
    assert eng.kv_cache_bytes() == expect_kv
    assert eng.metrics.gauge("engine_kv_cache_bytes").value == expect_kv
    # the census sees at least this engine's cache + params
    assert s["device_live_bytes"] >= expect_kv
    assert eng.metrics.gauge("device_live_bytes").value \
        == s["device_live_bytes"]
    # CPU backend reports no hardware memory stats — fields are None,
    # not a made-up ratio
    assert s["hbm_bytes_in_use"] is None
    assert s["hbm_bytes_limit"] is None
    assert s["hbm_occupancy"] is None
    # a prefix pool adds its rows to the engine's KV footprint
    pooled = serving.Engine(m, params, slots=2, buf_len=24,
                            prefix_pool=1)
    assert pooled.kv_cache_bytes() > expect_kv


def test_seq2seq_engine_stats_memory_fields():
    model = models.T5(models.T5Config(
        vocab_size=64, d_model=32, d_kv=8, d_ff=64, num_layers=1,
        num_heads=4, dropout_rate=0.0, relative_attention_num_buckets=8,
        relative_attention_max_distance=16))
    t5p, _ = model.init(jax.random.PRNGKey(0))
    eng = serving.Seq2SeqEngine(model, t5p, slots=2, src_len=8,
                                max_new_cap=8)
    s = eng.stats()
    expect = sum(leaf.nbytes
                 for leaf in jax.tree_util.tree_leaves(eng.state))
    assert s["kv_cache_bytes"] == expect > 0


def test_engine_stats_prefix_cache_hit_rate():
    m, params = _gpt(1)
    eng = serving.Engine(m, params, slots=2, buf_len=24, prefix_pool=1)
    rng = np.random.RandomState(1)
    pref = list(rng.randint(0, 64, 8))
    eng.register_prefix(pref)
    eng.add_request(pref + list(rng.randint(0, 64, 3)), max_new_tokens=2)
    eng.add_request(list(rng.randint(0, 64, 6)), max_new_tokens=2)
    while eng.live():
        eng.step()
    s = eng.stats()
    assert s["prefix_hits"] == 1 and s["admitted"] == 2
    assert s["prefix_hit_rate"] == 0.5
    assert eng.metrics.counter("engine_prefix_hits_total").value == 1


def test_engine_stats_rolling_mode():
    cfg = models.LlamaConfig(
        vocab_size=64, hidden_size=32, intermediate_size=64,
        num_hidden_layers=2, num_attention_heads=4,
        num_key_value_heads=2, max_position_embeddings=16,
        sliding_window=6, tie_word_embeddings=True)
    m = models.Llama(cfg)
    params, _ = m.init(jax.random.PRNGKey(0))
    eng = serving.Engine(m, params, slots=2, buf_len=16, rolling=True)
    rng = np.random.RandomState(0)
    eng.add_request(list(rng.randint(0, 64, 4)), max_new_tokens=3)
    while eng.live():
        eng.step()
    s = eng.stats()
    assert s["finished"] == 1 and s["tokens_generated"] == 3
    assert s["prefill_latency"]["count"] == 1
    assert s["ttft"]["count"] == 1


def test_seq2seq_engine_stats():
    cfg = models.T5Config(vocab_size=64, d_model=32, d_kv=8, d_ff=64,
                          num_layers=2, num_heads=4, dropout_rate=0.0,
                          relative_attention_num_buckets=8,
                          relative_attention_max_distance=16)
    m = models.T5(cfg)
    params, _ = m.init(jax.random.PRNGKey(0))
    eng = serving.Seq2SeqEngine(m, params, slots=1, src_len=8,
                                max_new_cap=4)
    eng.submit([3, 4, 5], max_new_tokens=3)
    eng.submit([6, 7], max_new_tokens=2)       # queues behind slot 0
    while eng.live() or eng.stats()["waiting"]:
        eng.step()
    s = eng.stats()
    assert s["finished"] == 2 and s["tokens_generated"] == 5
    assert s["ttft"]["count"] == 2
    assert s["queue_wait"]["count"] == 2
    # the queued request waited at least one decode tick
    assert s["queue_wait"]["sum"] > 0


def test_engine_custom_metrics_registry():
    m, params = _gpt(2)
    reg = obs.MetricsRegistry()
    eng = serving.Engine(m, params, slots=1, buf_len=24, metrics=reg)
    eng.add_request([1, 2, 3], max_new_tokens=2)
    while eng.live():
        eng.step()
    assert eng.metrics is reg
    assert reg.counter("engine_tokens_total").value == 2


# -- amp / optimizer / profiler satellites --------------------------------

def test_amp_scaler_introspection():
    from apex_tpu import amp, optimizers as opts
    from apex_tpu import nn

    class Lin(nn.Module):
        def init(self, key):
            return {"w": jnp.ones((4, 4), jnp.float32)}, ()

        def apply(self, p, x, state=(), train=False):
            return x @ p["w"], state

    model, opt = amp.initialize(Lin(), opts.FusedAdam(1e-3),
                                opt_level="O2", half_dtype="float16",
                                verbosity=0)
    params, _ = model.init(jax.random.PRNGKey(0))
    ost = opt.init(params)
    assert amp.current_loss_scale(ost) == 2.0 ** 16
    assert amp.steps_skipped(ost) == 0
    st = amp.amp_stats(ost)
    assert st["num_losses"] == 1
    assert st["per_loss"][0]["loss_scale"] == 2.0 ** 16
    # overflow: scale halves, skip count exposed through the frontend
    g = jax.tree_util.tree_map(
        lambda p: jnp.full(p.shape, jnp.inf, jnp.float32), params)
    _, ost2, info = opt.step(params, ost, g)
    assert amp.steps_skipped(ost2) == 1
    assert amp.current_loss_scale(ost2) == 2.0 ** 15
    # registry recording (loss-scale timeline point)
    reg = obs.MetricsRegistry()
    rec = obs.SpanRecorder()
    prev = obs.set_recorder(rec)
    try:
        amp.record_scaler(ost2, registry=reg, step=1, emit_event=True)
    finally:
        obs.set_recorder(prev)
    assert reg.gauge("amp_loss_scale").value == 2.0 ** 15
    assert reg.counter("amp_steps_skipped_total").value == 1
    ev = rec.events()[-1]
    assert ev["name"] == "amp_loss_scale" and ev["args"]["step"] == 1
    with pytest.raises(TypeError):
        amp.amp_stats({"not": "an opt state"})


def test_step_info_grad_norm():
    from apex_tpu import amp, optimizers as opts
    from apex_tpu import nn

    class Lin(nn.Module):
        def init(self, key):
            return {"w": jnp.ones((3,), jnp.float32)}, ()

        def apply(self, p, x, state=(), train=False):
            return x * p["w"], state

    model, opt = amp.initialize(Lin(), opts.FusedAdam(1e-3),
                                opt_level="O2", verbosity=0)
    params, _ = model.init(jax.random.PRNGKey(0))
    ost = opt.init(params)
    g = {"w": jnp.asarray([3.0, 4.0, 0.0], jnp.bfloat16)}
    _, _, info = opt.step(params, ost, g)
    assert float(info["grad_norm"]) == pytest.approx(5.0, rel=1e-3)
    assert float(opts.global_grad_norm(
        {"a": jnp.asarray([3.0]), "b": jnp.asarray([4.0])})) == \
        pytest.approx(5.0)
    assert float(opts.global_grad_norm({})) == 0.0


def test_profiler_nesting_and_threads(monkeypatch):
    """Nested profile() must not stop the outer window; concurrent
    start/stop must produce exactly one start_trace/stop_trace pair."""
    from apex_tpu.utils import profiler
    calls = []
    monkeypatch.setattr(jax.profiler, "start_trace",
                        lambda d: calls.append("start"))
    monkeypatch.setattr(jax.profiler, "stop_trace",
                        lambda: calls.append("stop"))
    with profiler.profile("/tmp/x"):
        assert profiler.profiling_active()
        with profiler.profile("/tmp/x"):   # nested: must no-op cleanly
            assert calls == ["start"]
        assert calls == ["start"]          # inner exit didn't stop it
        assert profiler.profiling_active()
    assert calls == ["start", "stop"]
    assert not profiler.profiling_active()
    profiler.stop_profile()                # unmatched stop: no-op
    assert calls == ["start", "stop"]

    # hammer it from 8 threads: starts/stops stay balanced, never nested
    calls.clear()
    def work():
        for _ in range(50):
            with profiler.profile("/tmp/x"):
                pass
    threads = [threading.Thread(target=work) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not profiler.profiling_active()
    assert calls.count("start") == calls.count("stop")
    depth = 0
    for c in calls:
        depth += 1 if c == "start" else -1
        assert depth in (0, 1)             # never two open windows
    assert depth == 0


def test_data_loader_records_wait_times():
    from apex_tpu.data import DataLoader
    reg = obs.MetricsRegistry()
    rng = np.random.RandomState(0)
    imgs = rng.randint(0, 255, (16, 8, 8, 3)).astype(np.uint8)
    lbls = rng.randint(0, 10, 16)
    dl = DataLoader(imgs, lbls, batch_size=4, shuffle=False, native=False,
                    metrics=reg)
    for _ in range(3):
        dl.next_batch()
    s = dl.stats()
    assert s["batches"] == 3
    assert s["load_wait"]["count"] == 3 and s["load_wait"]["sum"] >= 0
    assert reg.counter("data_batches_total").value == 3


def test_ddp_comm_stats_recorded():
    from apex_tpu import parallel
    ddp = parallel.DistributedDataParallel(message_size=100)
    mesh = Mesh(np.array(jax.devices()[:8]), ("data",))
    grads = {"a": jnp.ones((300,), jnp.float32),
             "b": jnp.ones((10,), jnp.bfloat16)}

    def step(g):
        return ddp.allreduce_grads(g)

    out = jax.jit(jax.shard_map(
        step, mesh=mesh, in_specs=(P(),), out_specs=P(),
        check_vma=False))(grads)
    assert float(out["a"][0]) == 1.0    # psum(1)*8 / world (averaged)
    by_dtype = {b["dtype"]: b for b in ddp.last_comm_stats}
    assert by_dtype["float32"]["cause"] == "chunked"
    assert by_dtype["float32"]["chunks"] == 3
    # TRUE on-wire bytes: the chunked path pads to chunks*message_size
    # (here 300 fits 3x100 exactly — padded_elements pins that)
    assert by_dtype["float32"]["bytes"] == 300 * 4
    assert by_dtype["float32"]["wire_elements"] == 300
    assert by_dtype["float32"]["padded_elements"] == 0
    assert by_dtype["float32"]["topology"] == "flat"
    assert by_dtype["bfloat16"]["cause"] == "single"
    assert by_dtype["bfloat16"]["bytes"] == 10 * 2
    # folded into the process registry under (dtype, cause) labels
    reg = obs.get_registry()
    c = reg.counter("ddp_allreduce_buckets_total")
    assert c.labels(dtype="float32", cause="chunked").value >= 1
    assert reg.counter("ddp_allreduce_bytes_total").labels(
        dtype="float32").value >= 1200
    # per-fabric-level accounting: flat psums count fully on both
    lvl = reg.counter("ddp_allreduce_level_bytes_total")
    assert lvl.labels(level="dcn", dtype="float32").value >= 1200
    assert lvl.labels(level="ici", dtype="float32").value >= 1200


def test_ddp_comm_stats_hierarchical_levels():
    """The hierarchical topology's trace-time stats split the wire
    bytes per fabric level, and the registry's level counter sees the
    DCN hop at 1/ici of the bucket."""
    from apex_tpu import parallel
    ddp = parallel.DistributedDataParallel(
        comm_topology="hierarchical", ici_size=4)
    mesh = Mesh(np.array(jax.devices()[:8]), ("data",))
    grads = {"a": jnp.ones((400,), jnp.float32)}

    base = obs.get_registry().counter(
        "ddp_allreduce_level_bytes_total").labels(
        level="dcn", dtype="float32").value
    jax.jit(jax.shard_map(
        lambda g: ddp.allreduce_grads(g), mesh=mesh, in_specs=(P(),),
        out_specs=P(), check_vma=False))(grads)
    (b,) = ddp.last_comm_stats
    assert b["topology"] == "hierarchical"
    assert b["dcn_wire_bytes"] == 100 * 4          # 1/ici of the bucket
    assert b["ici_wire_bytes"] == 400 * 4 + 100 * 4
    assert b["bytes"] == b["ici_wire_bytes"] + b["dcn_wire_bytes"]
    after = obs.get_registry().counter(
        "ddp_allreduce_level_bytes_total").labels(
        level="dcn", dtype="float32").value
    assert after - base == 400


# -- Prometheus exposition conformance (PR 10, satellite) ------------------

def test_prometheus_text_escapes_and_roundtrips():
    """Exposition-format conformance: HELP/TYPE lines, label-value
    escaping (backslash / quote / newline), the +Inf histogram bucket
    — and the parser round-trip recovers the registry's exact label
    values and sample values."""
    reg = obs.MetricsRegistry()
    c = reg.counter("esc_total", help="counts with a \\ slash\nnewline")
    c.labels(path='/v1/"gen"\nx', shard="a\\b").inc(4)
    g = reg.gauge("esc_gauge")
    g.set(2.5)
    h = reg.histogram("esc_seconds", help="latency", buckets=(0.1, 1.0))
    for v in (0.05, 0.5, 9.0):
        h.observe(v)
    text = exporters.prometheus_text(reg)
    # conformance checker: no violations
    assert exporters.validate_prometheus_text(text) == []
    # HELP newline is escaped on the wire (single physical line)
    (help_line,) = [ln for ln in text.splitlines()
                    if ln.startswith("# HELP esc_total")]
    assert "\\n" in help_line and "\n" not in help_line[1:]
    # parser round-trip: the gnarly label values come back EXACTLY
    fams = exporters.parse_prometheus_text(text)
    assert fams["esc_total"]["type"] == "counter"
    (name, labels, value), = fams["esc_total"]["samples"]
    assert labels == {"path": '/v1/"gen"\nx', "shard": "a\\b"}
    assert value == 4.0
    assert fams["esc_total"]["help"].endswith("\\nnewline")
    # histogram: +Inf bucket present, cumulative counts monotone,
    # _count == +Inf, _sum == the observed sum
    hs = {n: (lab, v) for n, lab, v in fams["esc_seconds"]["samples"]}
    buckets = {lab["le"]: v for n, lab, v
               in fams["esc_seconds"]["samples"]
               if n == "esc_seconds_bucket"}
    assert buckets == {"0.1": 1.0, "1": 2.0, "+Inf": 3.0}
    assert hs["esc_seconds_count"][1] == 3.0
    assert hs["esc_seconds_sum"][1] == pytest.approx(9.55)


def test_prometheus_labeled_histogram_exposition_roundtrips():
    """PR 16 satellite: labeled HISTOGRAM children expose correctly —
    each label set's buckets merge the child labels with ``le=``, keep
    their own cumulative +Inf/_count invariants, and user-supplied
    tenant label values (quotes, backslashes, newlines) survive the
    escape round-trip on every bucket line."""
    reg = obs.MetricsRegistry()
    h = reg.histogram("tenant_wait_seconds", help="queue wait",
                      buckets=(0.1, 1.0))
    nasty = 'acme "prod"\nv\\2'
    h.labels(tenant="batch").observe(0.05)
    h.labels(tenant="batch").observe(0.5)
    h.labels(tenant=nasty).observe(9.0)
    text = exporters.prometheus_text(reg)
    assert exporters.validate_prometheus_text(text) == []
    fams = exporters.parse_prometheus_text(text)
    f = fams["tenant_wait_seconds"]
    assert f["type"] == "histogram"
    # untouched parent suppressed: every sample carries the tenant
    assert f["samples"] and all("tenant" in lab
                                for _, lab, _ in f["samples"])
    per = {}
    for name, lab, value in f["samples"]:
        s = per.setdefault(lab["tenant"], {})
        if name.endswith("_bucket"):
            s[lab["le"]] = value
        else:
            s[name.rsplit("_", 1)[-1]] = value
    # per-label-set cumulative buckets, each with its own +Inf==_count
    assert per["batch"] == {"0.1": 1.0, "1": 2.0, "+Inf": 2.0,
                            "sum": pytest.approx(0.55), "count": 2.0}
    # the gnarly tenant value came back EXACTLY, buckets intact
    assert per[nasty]["+Inf"] == 1.0 and per[nasty]["count"] == 1.0
    assert per[nasty]["sum"] == 9.0
    # a parent observed DIRECTLY as well exposes both series
    h.observe(0.05)
    fams = exporters.parse_prometheus_text(
        exporters.prometheus_text(reg))
    bare = [lab for n, lab, _ in fams["tenant_wait_seconds"]["samples"]
            if n.endswith("_count") and "tenant" not in lab]
    assert bare == [{}]
    assert exporters.validate_prometheus_text(
        exporters.prometheus_text(reg)) == []


def test_registry_label_cardinality_cap_folds_and_counts():
    """PR 16 tentpole guard: a metric flooded with more distinct label
    values than ``max_label_sets`` stays bounded — overflow folds into
    the shared ``other`` child, the fold is counted on
    ``labels_dropped``, totals are conserved, and the exposition stays
    conformant mid-fold."""
    from apex_tpu.observability.metrics import (DEFAULT_MAX_LABEL_SETS,
                                                OVERFLOW_LABEL_VALUE)
    reg = obs.MetricsRegistry()
    c = reg.counter("flood_total")
    assert c.max_label_sets == DEFAULT_MAX_LABEL_SETS
    c.max_label_sets = 3
    for i in range(8):
        c.labels(tenant=f"t{i}").inc()
    kids = c.children()
    assert {dict(k)["tenant"] for k in kids} == \
        {"t0", "t1", "t2", OVERFLOW_LABEL_VALUE}
    assert c.labels_dropped == 5
    # conserved: the folded increments landed on the overflow child
    assert c.labels(tenant=OVERFLOW_LABEL_VALUE).value == 5
    assert sum(ch.value for ch in kids.values()) == 8
    # a REPEATED over-cap id keeps folding (per-call drop accounting)
    c.labels(tenant="t7").inc()
    assert c.labels_dropped == 6
    assert c.labels(tenant=OVERFLOW_LABEL_VALUE).value == 6
    # an id that got under the cap is unaffected
    assert c.labels(tenant="t1").value == 1
    assert exporters.validate_prometheus_text(
        exporters.prometheus_text(reg)) == []


def test_validate_prometheus_text_catches_violations():
    # missing +Inf bucket
    bad = ("# TYPE h histogram\n"
           'h_bucket{le="1"} 2\nh_sum 1.0\nh_count 2\n')
    assert any("+Inf" in e
               for e in exporters.validate_prometheus_text(bad))
    # non-monotone cumulative buckets
    bad = ("# TYPE h histogram\n"
           'h_bucket{le="1"} 5\nh_bucket{le="+Inf"} 3\n'
           "h_sum 1.0\nh_count 3\n")
    assert any("decrease" in e
               for e in exporters.validate_prometheus_text(bad))
    # _count disagreeing with the +Inf bucket
    bad = ("# TYPE h histogram\n"
           'h_bucket{le="+Inf"} 3\nh_sum 1.0\nh_count 4\n')
    assert any("_count" in e
               for e in exporters.validate_prometheus_text(bad))
    # sample with no TYPE declaration
    assert any("no # TYPE" in e
               for e in exporters.validate_prometheus_text("x 1.0\n"))
    # negative counter
    bad = "# TYPE c counter\nc -1.0\n"
    assert any("negative" in e
               for e in exporters.validate_prometheus_text(bad))
    # unparseable line
    assert exporters.validate_prometheus_text("{broken 1.0\n")
    # labeled-histogram invariants hold PER label set: one tenant's
    # series missing its +Inf (or disagreeing with _count) is caught
    # even when a sibling series is clean
    bad = ("# TYPE h histogram\n"
           'h_bucket{tenant="ok",le="+Inf"} 2\n'
           'h_sum{tenant="ok"} 1.0\nh_count{tenant="ok"} 2\n'
           'h_bucket{tenant="sick",le="1"} 1\n'
           'h_sum{tenant="sick"} 0.5\nh_count{tenant="sick"} 1\n')
    errs = exporters.validate_prometheus_text(bad)
    assert any("+Inf" in e and "sick" in e for e in errs)
    assert not any("'ok'" in e for e in errs)
    bad = ("# TYPE h histogram\n"
           'h_bucket{tenant="a",le="+Inf"} 3\n'
           'h_sum{tenant="a"} 1.0\nh_count{tenant="a"} 4\n')
    assert any("_count" in e
               for e in exporters.validate_prometheus_text(bad))


# -- EventRing.dump under concurrent appends (PR 10, satellite) -----------

def test_event_ring_dump_consistent_under_concurrent_appends(tmp_path):
    """dump() taken WHILE writers hammer the ring must be internally
    consistent: the header's drop accounting is exact for the snapshot
    it describes, retained events are a contiguous seq window in order
    (timestamps non-decreasing with seq — the clock is read under the
    lock), and no event is torn or duplicated."""
    ring = obs.EventRing(capacity=64)
    stop = threading.Event()

    def writer(wid):
        i = 0
        while not stop.is_set():
            ring.append("w", wid=wid, i=i)
            i += 1

    threads = [threading.Thread(target=writer, args=(w,))
               for w in range(4)]
    for t in threads:
        t.start()
    try:
        for k in range(20):
            path = str(tmp_path / f"dump_{k}.jsonl")
            ring.dump(path)
            with open(path) as f:
                lines = [json.loads(ln) for ln in f]
            header, events = lines[0], lines[1:]
            assert header["kind"] == "flight_ring"
            assert header["capacity"] == 64
            # exact accounting FOR THIS snapshot
            assert header["dropped"] == header["total"] - len(events)
            assert len(events) <= 64
            seqs = [e["seq"] for e in events]
            # contiguous window ending at total-1, oldest first
            assert seqs == list(range(header["total"] - len(events),
                                      header["total"]))
            # time order can never disagree with seq order
            ts = [e["t"] for e in events]
            assert ts == sorted(ts)
            # no torn event: every record carries its full payload
            assert all("wid" in e and "i" in e for e in events)
    finally:
        stop.set()
        for t in threads:
            t.join()
    # quiesced: final dump's total equals appended count exactly
    final = str(tmp_path / "final.jsonl")
    ring.dump(final)
    with open(final) as f:
        header = json.loads(f.readline())
    assert header["total"] == ring.total
    assert header["dropped"] == ring.total - len(ring)


# -- kind: run records (PR 10) --------------------------------------------

def test_validate_run_record_edges():
    def rec(**kw):
        base = {"kind": "run", "run": "r", "verdict": "ok",
                "observations": 5, "watermark": 4,
                "anomaly_counts": {"stall": 0, "nan": 0},
                "anomalies": [],
                "loss": {"last": 1.0, "ewma": 1.0},
                "checkpoints": 0, "duration_s": 1.5}
        base.update(kw)
        return exporters.JsonlExporter.enrich(base)

    assert exporters.validate_run_record(rec()) == []
    # null watermark (nothing observed yet) is legal
    assert exporters.validate_run_record(rec(watermark=None)) == []
    # verdict/count consistency both ways
    assert any("inconsistent" in e for e in exporters.
               validate_run_record(rec(verdict="attention")))
    assert any("inconsistent" in e for e in exporters.
               validate_run_record(rec(anomaly_counts={"nan": 2})))
    # unknown anomaly kind
    assert any("unknown kind" in e for e in exporters.
               validate_run_record(rec(anomaly_counts={"gremlin": 1},
                                       verdict="attention")))
    # detail list exceeding its count
    assert any("can never exceed" in e for e in exporters.
               validate_run_record(rec(
                   verdict="attention",
                   anomaly_counts={"nan": 1},
                   anomalies=[{"kind": "nan", "observation": 1},
                              {"kind": "nan", "observation": 2}])))
    # NaN smuggled into the loss summary
    assert any("finite" in e for e in exporters.validate_run_record(
        rec(loss={"last": float("nan")})))
    # bad verdict / run / observations
    assert exporters.validate_run_record(rec(verdict="fine"))
    assert exporters.validate_run_record(rec(run=""))
    assert exporters.validate_run_record(rec(observations=-1))
    assert exporters.validate_run_record(rec(duration_s=-2))


def test_check_bench_trend_partitions_run_records(tmp_path):
    """kind: run supervisor verdicts are per-run diagnostics, not a
    cross-round trend: a later round's anomalous run must not read as
    a regression, stale replays count toward the partition tally —
    while the run_supervisor_overhead METRIC lines do trend."""
    def runrec(n_nan, **kw):
        return exporters.JsonlExporter.enrich(
            {"kind": "run", "run": "resnet18_o2_ddp",
             "verdict": "attention" if n_nan else "ok",
             "observations": 10, "watermark": 9,
             "anomaly_counts": {"nan": n_nan}, "anomalies": [],
             "backend": "cpu", **kw})

    d = tmp_path / "run1"
    d.mkdir()
    _trend_round(d, "BENCH_r01.json", [runrec(0)])
    _trend_round(d, "BENCH_r02.json", [runrec(5),
                                       runrec(0, stale=True)])
    r = _run_trend(["--dir", str(d)])
    assert r.returncode == 0, r.stderr
    assert "1 stale replays partitioned out" in r.stderr

    # the overhead metric lines DO trend (tpu backend gates)
    def ov(value, **kw):
        return exporters.JsonlExporter.enrich(
            {"metric": "run_supervisor_overhead_o2", "value": value,
             "unit": "ms", "vs_baseline": None, "backend": "tpu",
             "ndev": 1, "arch": "TPU v5 lite",
             "step_ms_on": 10.0 + value, "step_ms_off": 10.0, **kw})

    d2 = tmp_path / "run2"
    d2.mkdir()
    _trend_round(d2, "BENCH_r01.json", [ov(1.0)])
    _trend_round(d2, "BENCH_r02.json", [ov(2.0)])   # 100% worse (ms)
    r = _run_trend(["--dir", str(d2)])
    assert r.returncode == 1
    assert "regressed" in r.stderr


def test_v5_requirements_gate_on_declared_version():
    """Schema v5's run_supervisor_overhead both-sides requirement (and
    the run-record family itself) gate on the record's DECLARED
    schema_version — archived v4-and-earlier streams re-validate
    clean."""
    line = {"metric": "run_supervisor_overhead_o2", "value": 1.0,
            "unit": "ms", "vs_baseline": None, "backend": "cpu",
            "ndev": 8, "arch": "cpu"}
    # fresh v5 line WITHOUT the on/off pair: error
    v5 = exporters.JsonlExporter.enrich(dict(line))
    assert v5["schema_version"] >= 5
    errs = exporters.validate_bench_record(v5)
    assert any("step_ms_on" in e for e in errs)
    # the same line declaring v4 (an archived pre-supervisor stream):
    # clean — v4 never defined the metric, so no requirement applies
    v4 = exporters.JsonlExporter.enrich(
        {**line, "schema_version": 4})
    assert exporters.validate_bench_record(v4) == []
    # and the complete v5 line is clean
    full = exporters.JsonlExporter.enrich(
        {**line, "step_ms_on": 11.0, "step_ms_off": 10.0})
    assert exporters.validate_bench_record(full) == []
    # v4 numerics_overhead contract unchanged by the bump
    num = exporters.JsonlExporter.enrich(
        {"metric": "numerics_overhead_o2", "value": 1.0, "unit": "ms",
         "vs_baseline": None, "backend": "cpu", "ndev": 8,
         "arch": "cpu", "schema_version": 4})
    assert any("step_ms_on" in e
               for e in exporters.validate_bench_record(num))


def test_v7_requirements_gate_on_declared_version():
    """Schema v7: fresh chaos_preempt* lines must carry the resume
    they measured (mttr_s / resume_overhead_s / resumed_step);
    recovery records validate cause/preempted/data_state whenever
    present.  Archived v6-and-earlier streams re-validate clean."""
    line = {"metric": "chaos_preempt_resume", "value": 0.01,
            "unit": "s", "vs_baseline": None, "backend": "cpu",
            "ndev": 1, "arch": "cpu"}
    v7 = exporters.JsonlExporter.enrich(dict(line))
    assert v7["schema_version"] >= 7
    errs = exporters.validate_bench_record(v7)
    assert any("mttr_s" in e for e in errs)
    assert any("resumed_step" in e for e in errs)
    # the same line declaring v6 (an archived pre-preemption stream):
    # clean — v6 never defined the metric
    v6 = exporters.JsonlExporter.enrich({**line, "schema_version": 6})
    assert exporters.validate_bench_record(v6) == []
    # and the complete v7 line is clean
    full = exporters.JsonlExporter.enrich(
        {**line, "mttr_s": 0.02, "resume_overhead_s": 0.01,
         "resumed_step": 7})
    assert exporters.validate_bench_record(full) == []

    # recovery-record preemption fields, validated whenever present
    base = {"kind": "recovery", "role": "training", "subject": "run",
            "episodes": 0, "actions_total": 0,
            "max_actions_in_episode": 0, "actions": [],
            "mttr_s": {"last": None, "mean": None, "count": 0},
            "in_flight": False, "duration_s": 1.0}
    ok = exporters.JsonlExporter.enrich(
        {**base, "cause": "preemption", "preempted": True,
         "data_state": {"samples_consumed": 80, "epoch": 1,
                        "cursor": 16, "shard_id": 0,
                        "num_shards": 4}})
    assert exporters.validate_recovery_record(ok) == []
    bad_cause = exporters.JsonlExporter.enrich(
        {**base, "cause": "cosmic_rays"})
    assert any("cause" in e for e in
               exporters.validate_recovery_record(bad_cause))
    bad_ds = exporters.JsonlExporter.enrich(
        {**base, "data_state": {"samples_consumed": -1}})
    assert any("samples_consumed" in e for e in
               exporters.validate_recovery_record(bad_ds))
    bad_shard = exporters.JsonlExporter.enrich(
        {**base, "data_state": {"shard_id": 5, "num_shards": 4}})
    assert any("shard_id" in e for e in
               exporters.validate_recovery_record(bad_shard))
    bad_pre = exporters.JsonlExporter.enrich(
        {**base, "preempted": "yes"})
    assert any("preempted" in e for e in
               exporters.validate_recovery_record(bad_pre))
    # the new action kind is known to the validator
    act = exporters.JsonlExporter.enrich(
        {**base, "episodes": 1, "actions_total": 1,
         "max_actions_in_episode": 1,
         "actions": [{"kind": "preempt_snapshot", "episode": 1,
                      "t_s": 0.5}]})
    assert exporters.validate_recovery_record(act) == []


def test_v8_profile_records_and_version_gating():
    """Schema v8: ``kind: profile`` records dispatch to their own
    validator, and the engine-decode kv-fragmentation requirement
    gates on the DECLARED version — archived v7-and-earlier streams
    re-validate clean (the full archived-stream sweep rides
    test_check_bench_trend_gate's real BENCH_r*.json files through
    check_bench_schema)."""
    prof = exporters.JsonlExporter.enrich(
        {"kind": "profile", "metric": "resnet18_o2_ddp_flat_profile",
         "span_ms": 10.0, "device_busy_ms": 8.0, "compute_ms": 7.0,
         "collective_ms": 3.0, "gap_ms": 2.0, "overlap_ms": 2.0,
         "measured_overlap_fraction": 0.6667, "kernel_count": 42,
         "lane_count": 8, "steps": 3,
         "top_kernels": [{"name": "all-reduce", "kind": "collective",
                          "count": 24, "total_ms": 3.0}]})
    assert prof["schema_version"] >= 8
    assert exporters.validate_profile_record(prof) == []
    # the dispatcher routes on kind — the same record through the
    # telemetry validator hits the profile schema, not the bench one
    assert exporters.validate_telemetry_record(prof) == []
    broken = dict(prof, device_busy_ms=99.0)
    assert exporters.validate_telemetry_record(broken) != []
    # a mixed stream with a profile line stays check_bench_schema clean
    bench_line = exporters.JsonlExporter.enrich(
        {"metric": "m", "value": 1.0, "unit": "x", "vs_baseline": None,
         "backend": "cpu", "ndev": 8, "arch": "cpu"})
    assert exporters.validate_telemetry_jsonl(
        [json.dumps(prof), json.dumps(bench_line)]) == []


def test_check_bench_trend_partitions_profile_records(tmp_path):
    """kind: profile device-timeline attributions are per-capture
    stories, not a cross-round trend: a later round's worse split
    must not read as a metric regression, and stale replays count
    toward the partition tally (the numerics/run/recovery rule)."""
    def profrec(busy, **kw):
        return exporters.JsonlExporter.enrich(
            {"kind": "profile", "metric": "resnet18_o2_ddp_profile",
             "backend": "cpu", "span_ms": busy + 1.0,
             "device_busy_ms": busy, "compute_ms": busy,
             "collective_ms": 0.0, "gap_ms": 1.0, "overlap_ms": 0.0,
             "measured_overlap_fraction": 0.0, **kw})

    d = tmp_path / "prof1"
    d.mkdir()
    _trend_round(d, "BENCH_r01.json", [profrec(5.0)])
    _trend_round(d, "BENCH_r02.json", [profrec(50.0),
                                       profrec(5.0, stale=True)])
    r = _run_trend(["--dir", str(d)])
    assert r.returncode == 0, r.stderr
    assert "0 fresh measurements counted" in r.stderr
    assert "1 stale replays partitioned out" in r.stderr


# -- PR 15: the compilation plane ------------------------------------------

def test_v10_compile_fields_and_version_gating():
    """Schema v10 (the compilation plane): fresh train-throughput and
    engine-decode lines must carry the compile-plane triple
    (cold_compile_ms / compiles_total / steady_state_retraces); the
    fields are value-checked wherever they appear; archived v1-v9
    streams re-validate clean at their declared versions."""
    assert exporters.SCHEMA_VERSION >= 10
    base = {"metric": "resnet18_o2_train_throughput", "value": 100.0,
            "unit": "images/sec/chip", "vs_baseline": None,
            "backend": "tpu", "ndev": 1, "arch": "TPU v5 lite",
            "flops_per_step": 1e12, "achieved_tflops": 10.0,
            "mfu": 0.1, "peak_bytes": 1_000_000,
            "cold_compile_ms": 1234.5, "compiles_total": 1,
            "steady_state_retraces": 0}
    assert exporters.validate_bench_record(
        exporters.JsonlExporter.enrich(dict(base))) == []
    # fresh v10 train line missing any of the triple flags
    for key in exporters.COMPILE_FIELDS:
        rec = exporters.JsonlExporter.enrich(
            {k: v for k, v in base.items() if k != key})
        assert any(key in e
                   for e in exporters.validate_bench_record(rec)), key
    # ...but the same line DECLARING v9 (an archived stream) is valid
    v9 = exporters.JsonlExporter.enrich(
        {k: v for k, v in base.items()
         if k not in exporters.COMPILE_FIELDS})
    v9["schema_version"] = 9
    assert exporters.validate_bench_record(v9) == []
    # stale replays and error lines stay exempt
    stale = exporters.JsonlExporter.enrich(
        {k: v for k, v in base.items()
         if k not in exporters.COMPILE_FIELDS}, stale=True)
    assert exporters.validate_bench_record(stale) == []
    err = exporters.JsonlExporter.enrich(
        {"metric": "resnet18_o2_train_throughput", "value": None,
         "unit": None, "vs_baseline": None, "backend": "tpu",
         "ndev": 1, "arch": "TPU v5 lite", "error": "hung"})
    assert exporters.validate_bench_record(err) == []
    # field VALUES are checked wherever the fields appear (any metric)
    plain = {"metric": "m", "value": 1.0, "unit": "x",
             "vs_baseline": None, "backend": "cpu", "ndev": 8,
             "arch": "cpu"}
    for key, bad in (("cold_compile_ms", -1.0),
                     ("cold_compile_ms", "slow"),
                     ("compiles_total", -1),
                     ("compiles_total", 1.5),
                     ("compiles_total", True),
                     ("steady_state_retraces", -2),
                     ("steady_state_retraces", "none")):
        rec = exporters.JsonlExporter.enrich(dict(plain, **{key: bad}))
        assert any(key in e
                   for e in exporters.validate_bench_record(rec)), \
            (key, bad)
    # a nonzero steady-state retrace count is schema-VALID (the record
    # is honest about it) — gating it is the trend checker's job
    assert exporters.validate_bench_record(
        exporters.JsonlExporter.enrich(
            dict(plain, steady_state_retraces=3))) == []


def test_compile_fields_pinned_to_compilation_module():
    """exporters.COMPILE_FIELDS is the stdlib-side duplicate of
    compilation.BENCH_COMPILE_FIELDS (both modules must stay
    importable without jax) — pinned equal so the two cannot drift."""
    from apex_tpu.observability import compilation
    assert exporters.COMPILE_FIELDS == compilation.BENCH_COMPILE_FIELDS


def test_check_bench_trend_compile_gate(tmp_path):
    """The compile-plane trend gates: a fresh line with a nonzero
    steady_state_retraces errors on EVERY backend (the ledger count is
    deterministic — the timed loop included a recompile), and
    cold_compile_ms growth past --tol gates on accelerators / warns on
    CPU smoke like every timing-derived column."""
    def line(backend, value, cold_ms, retraces=0):
        return exporters.JsonlExporter.enrich(
            {"metric": "gpt_tiny_engine_decode_throughput",
             "value": value, "unit": "tokens/sec/chip",
             "vs_baseline": None, "backend": backend, "ndev": 8,
             "arch": "TPU v5 lite" if backend == "tpu" else "cpu",
             "window": 8, "tokens_per_sync": 7.5,
             "admission_mode": "fixed_slot",
             "kv_cache_bytes": 16384, "kv_waste_bytes": 4096,
             "kv_utilization": 0.75,
             "cold_compile_ms": cold_ms, "compiles_total": 2,
             "steady_state_retraces": retraces})

    # nonzero steady-state retraces: error even on CPU smoke
    d1 = tmp_path / "comp1"
    d1.mkdir()
    _trend_round(d1, "BENCH_r01.json", [line("cpu", 100.0, 300.0,
                                             retraces=2)])
    r = _run_trend(["--dir", str(d1)])
    assert r.returncode == 1
    assert "steady-state retrace" in r.stderr
    # accelerator cold_compile_ms growth past tol: error
    d2 = tmp_path / "comp2"
    d2.mkdir()
    _trend_round(d2, "BENCH_r01.json", [line("tpu", 100.0, 1000.0)])
    _trend_round(d2, "BENCH_r02.json", [line("tpu", 100.0, 2000.0)])
    r = _run_trend(["--dir", str(d2)])
    assert r.returncode == 1
    assert "cold_compile_ms" in r.stderr
    # the same growth on CPU smoke: warning only (strict-cpu gates)
    d3 = tmp_path / "comp3"
    d3.mkdir()
    _trend_round(d3, "BENCH_r01.json", [line("cpu", 100.0, 1000.0)])
    _trend_round(d3, "BENCH_r02.json", [line("cpu", 100.0, 2000.0)])
    r = _run_trend(["--dir", str(d3)])
    assert r.returncode == 0 and "cold_compile_ms" in r.stderr
    r = _run_trend(["--dir", str(d3), "--strict-cpu"])
    assert r.returncode == 1
    # growth inside tol, zero retraces: clean
    d4 = tmp_path / "comp4"
    d4.mkdir()
    _trend_round(d4, "BENCH_r01.json", [line("tpu", 100.0, 1000.0)])
    _trend_round(d4, "BENCH_r02.json", [line("tpu", 101.0, 1100.0)])
    r = _run_trend(["--dir", str(d4)])
    assert r.returncode == 0, r.stderr
    # a STALE replay carrying old compile fields never trends
    d5 = tmp_path / "comp5"
    d5.mkdir()
    _trend_round(d5, "BENCH_r01.json", [line("tpu", 100.0, 1000.0)])
    _trend_round(d5, "BENCH_r02.json",
                 [dict(line("tpu", 100.0, 9000.0, retraces=5),
                       stale=True)])
    r = _run_trend(["--dir", str(d5)])
    assert r.returncode == 0, r.stderr


def test_v11_tenant_fields_and_version_gating():
    """Schema v11 (the tenant plane): fresh per-tenant goodput lines
    must carry ``tenant`` + ``slo_attainment``, the parity line its
    token counts (arithmetically consistent); archived v10 streams
    re-validate clean at their declared version; TENANT_COUNTS is
    pinned to the SLO tracker's actual bucket keys so the validator
    and the producer cannot drift."""
    assert exporters.SCHEMA_VERSION >= 11
    from apex_tpu.fleet import slo as fleet_slo
    assert exporters.TENANT_COUNTS == tuple(
        k for k in fleet_slo._new_tenant_bucket()
        if k not in ("t_first", "t_last", "tenant"))

    tline = {"metric": "gpt_tiny_fleet2_tenant_interactive_goodput",
             "value": 42.0, "unit": "tokens/sec", "vs_baseline": None,
             "backend": "cpu", "ndev": 1, "arch": "cpu",
             "tenant": "interactive", "slo_attainment": 1.0}
    assert exporters.validate_bench_record(
        exporters.JsonlExporter.enrich(dict(tline))) == []
    # fresh v11 tenant-goodput line missing either required field
    for key in ("tenant", "slo_attainment"):
        rec = exporters.JsonlExporter.enrich(
            {k: v for k, v in tline.items() if k != key})
        assert any(key in e
                   for e in exporters.validate_bench_record(rec)), key
    # ...but the same line DECLARING v10 (archived) is valid
    v10 = exporters.JsonlExporter.enrich(
        {k: v for k, v in tline.items()
         if k not in ("tenant", "slo_attainment")})
    v10["schema_version"] = 10
    assert exporters.validate_bench_record(v10) == []
    # null attainment (no deadlined request resolved) is valid
    assert exporters.validate_bench_record(exporters.JsonlExporter
        .enrich(dict(tline, slo_attainment=None))) == []
    # field VALUES checked wherever they appear
    for key, bad in (("slo_attainment", 1.5),
                     ("slo_attainment", -0.1),
                     ("tenant", ""), ("tenant", 7)):
        rec = exporters.JsonlExporter.enrich(dict(tline, **{key: bad}))
        assert any(key in e
                   for e in exporters.validate_bench_record(rec)), \
            (key, bad)

    pline = {"metric": "gpt_tiny_fleet2_tenant_parity", "value": 1.0,
             "unit": "ratio", "vs_baseline": None, "backend": "cpu",
             "ndev": 1, "arch": "cpu",
             "tenants_goodput_tokens": 120, "tokens_within_slo": 120}
    assert exporters.validate_bench_record(
        exporters.JsonlExporter.enrich(dict(pline))) == []
    # the ratio must reassemble from its own counts
    assert any("tenants_goodput_tokens" in e or "reassemble" in e
               for e in exporters.validate_bench_record(
                   exporters.JsonlExporter.enrich(
                       dict(pline, value=0.9))))
    # fresh v11 parity line missing its counts
    for key in ("tenants_goodput_tokens", "tokens_within_slo"):
        rec = exporters.JsonlExporter.enrich(
            {k: v for k, v in pline.items() if k != key})
        assert any(key in e
                   for e in exporters.validate_bench_record(rec)), key
    # archived v10 parity-free streams unaffected; stale exempt
    stale = exporters.JsonlExporter.enrich(
        {k: v for k, v in pline.items()
         if k not in ("tenants_goodput_tokens", "tokens_within_slo")},
        stale=True)
    assert exporters.validate_bench_record(stale) == []


def test_check_bench_trend_tenant_gate(tmp_path):
    """The tenant-plane trend gates: a fresh parity line off 1.0 by
    more than 1% errors on EVERY backend (exact token accounting — the
    leg tags every request), while a per-tenant slo_attainment drop
    past --tol follows the accelerator-gates / CPU-warns policy like
    every timing-derived column; stale replays never trend."""
    def tline(backend, attain):
        return exporters.JsonlExporter.enrich(
            {"metric": "gpt_tiny_fleet2_tenant_interactive_goodput",
             "value": 50.0, "unit": "tokens/sec", "vs_baseline": None,
             "backend": backend, "ndev": 1,
             "arch": "TPU v5 lite" if backend == "tpu" else "cpu",
             "tenant": "interactive", "slo_attainment": attain})

    def parity(backend, value, tg, tw):
        return exporters.JsonlExporter.enrich(
            {"metric": "gpt_tiny_fleet2_tenant_parity", "value": value,
             "unit": "ratio", "vs_baseline": None, "backend": backend,
             "ndev": 1,
             "arch": "TPU v5 lite" if backend == "tpu" else "cpu",
             "tenants_goodput_tokens": tg, "tokens_within_slo": tw})

    # parity off 1.0: error even on CPU smoke, first round
    d1 = tmp_path / "ten1"
    d1.mkdir()
    _trend_round(d1, "BENCH_r01.json", [parity("cpu", 0.9, 90, 100)])
    r = _run_trend(["--dir", str(d1)])
    assert r.returncode == 1
    assert "parity" in r.stderr
    # accelerator attainment drop past tol: error
    d2 = tmp_path / "ten2"
    d2.mkdir()
    _trend_round(d2, "BENCH_r01.json", [tline("tpu", 1.0)])
    _trend_round(d2, "BENCH_r02.json", [tline("tpu", 0.5)])
    r = _run_trend(["--dir", str(d2)])
    assert r.returncode == 1
    assert "slo_attainment" in r.stderr
    # same drop on CPU smoke: warning only (strict-cpu gates)
    d3 = tmp_path / "ten3"
    d3.mkdir()
    _trend_round(d3, "BENCH_r01.json", [tline("cpu", 1.0)])
    _trend_round(d3, "BENCH_r02.json", [tline("cpu", 0.5)])
    r = _run_trend(["--dir", str(d3)])
    assert r.returncode == 0 and "slo_attainment" in r.stderr
    r = _run_trend(["--dir", str(d3), "--strict-cpu"])
    assert r.returncode == 1
    # steady attainment + exact parity: clean
    d4 = tmp_path / "ten4"
    d4.mkdir()
    _trend_round(d4, "BENCH_r01.json",
                 [tline("tpu", 1.0), parity("tpu", 1.0, 100, 100)])
    _trend_round(d4, "BENCH_r02.json",
                 [tline("tpu", 1.0), parity("tpu", 1.0, 120, 120)])
    r = _run_trend(["--dir", str(d4)])
    assert r.returncode == 0, r.stderr
    # a STALE replay with broken parity / cratered attainment: ignored
    d5 = tmp_path / "ten5"
    d5.mkdir()
    _trend_round(d5, "BENCH_r01.json", [tline("tpu", 1.0)])
    _trend_round(d5, "BENCH_r02.json",
                 [dict(tline("tpu", 0.1), stale=True),
                  dict(parity("tpu", 0.5, 50, 100), stale=True)])
    r = _run_trend(["--dir", str(d5)])
    assert r.returncode == 0, r.stderr


def test_v12_block_pool_fields_and_version_gating():
    """Schema v12 (the paged serving plane): fresh engine-decode lines
    must say which allocator produced them (``admission_mode``), paged
    lines must expose the block pool, field VALUES are checked
    wherever they appear, and archived v11 streams re-validate clean
    at their declared version."""
    assert exporters.SCHEMA_VERSION >= 12
    assert exporters.ADMISSION_MODES == ("fixed_slot", "paged")
    from apex_tpu import serving
    assert serving.Engine.admission_mode in exporters.ADMISSION_MODES
    assert serving.PagedEngine.admission_mode in exporters.ADMISSION_MODES

    base = {"metric": "gpt_tiny_engine_decode_paged_throughput",
            "value": 9.0, "unit": "tokens/sec/chip",
            "vs_baseline": None, "backend": "cpu", "ndev": 8,
            "arch": "cpu", "window": 8, "tokens_per_sync": 7.5,
            "kv_cache_bytes": 16384, "kv_waste_bytes": 4096,
            "kv_utilization": 0.75, "cold_compile_ms": 350.0,
            "compiles_total": 2, "steady_state_retraces": 0,
            "admission_mode": "paged", "block_size": 8,
            "blocks_total": 16, "blocks_free": 5}
    assert exporters.validate_bench_record(
        exporters.JsonlExporter.enrich(dict(base))) == []
    # fresh v12 engine line without admission_mode
    rec = exporters.JsonlExporter.enrich(
        {k: v for k, v in base.items() if k != "admission_mode"})
    assert any("admission_mode" in e
               for e in exporters.validate_bench_record(rec))
    # a fixed-slot line needs no block fields
    fixed = exporters.JsonlExporter.enrich(
        {k: v for k, v in base.items()
         if k not in ("block_size", "blocks_total", "blocks_free")}
        | {"admission_mode": "fixed_slot"})
    assert exporters.validate_bench_record(fixed) == []
    # ...but a paged line missing any of them fails
    for key in ("block_size", "blocks_total", "blocks_free"):
        rec = exporters.JsonlExporter.enrich(
            {k: v for k, v in base.items() if k != key})
        assert any(key in e
                   for e in exporters.validate_bench_record(rec)), key
    # archived v11 stream without any of it: valid at its version
    v11 = exporters.JsonlExporter.enrich(
        {k: v for k, v in base.items()
         if k not in ("admission_mode", "block_size", "blocks_total",
                      "blocks_free")})
    v11["schema_version"] = 11
    assert exporters.validate_bench_record(v11) == []
    # field VALUES checked wherever they appear
    for key, bad in (("admission_mode", "slab"), ("admission_mode", 3),
                     ("block_size", 0), ("block_size", 8.5),
                     ("blocks_total", -1), ("blocks_free", True)):
        rec = exporters.JsonlExporter.enrich(dict(base, **{key: bad}))
        assert any(key in e
                   for e in exporters.validate_bench_record(rec)), \
            (key, bad)
    # blocks_free beyond the pool is an accounting bug
    rec = exporters.JsonlExporter.enrich(dict(base, blocks_free=99))
    assert any("blocks_free" in e
               for e in exporters.validate_bench_record(rec))
    # stale replay of a pre-paged record: exempt
    stale = exporters.JsonlExporter.enrich(
        {k: v for k, v in base.items()
         if k not in ("admission_mode", "block_size", "blocks_total",
                      "blocks_free")}, stale=True)
    assert exporters.validate_bench_record(stale) == []


def test_check_bench_trend_kv_gate(tmp_path):
    """The KV-plane trend gates: kv_waste_bytes growth past --tol
    errors on accelerators / warns on CPU smoke (the sampled waste is
    timing-adjacent), waste returning from a ZERO baseline gates like
    comm coming back onto the critical path, waste dropping (the paged
    engine's whole purpose) is clean, and the v12 field contract —
    fresh engine lines must carry admission_mode — gates on every
    backend while archived v11 rounds stay exempt."""
    def kline(backend, waste, **kw):
        return exporters.JsonlExporter.enrich(
            {"metric": "gpt_tiny_engine_decode_throughput",
             "value": 100.0, "unit": "tokens/sec/chip",
             "vs_baseline": None, "backend": backend, "ndev": 8,
             "arch": "TPU v5 lite" if backend == "tpu" else "cpu",
             "window": 8, "tokens_per_sync": 7.5,
             "admission_mode": "fixed_slot",
             "kv_cache_bytes": 16384, "kv_waste_bytes": waste,
             "kv_utilization": 0.75, "cold_compile_ms": 300.0,
             "compiles_total": 2, "steady_state_retraces": 0, **kw})

    # accelerator waste growth past tol: error
    d1 = tmp_path / "kv1"
    d1.mkdir()
    _trend_round(d1, "BENCH_r01.json", [kline("tpu", 4096)])
    _trend_round(d1, "BENCH_r02.json", [kline("tpu", 9000)])
    r = _run_trend(["--dir", str(d1)])
    assert r.returncode == 1
    assert "kv_waste_bytes" in r.stderr
    # the same growth on CPU smoke: warning only (strict-cpu gates)
    d2 = tmp_path / "kv2"
    d2.mkdir()
    _trend_round(d2, "BENCH_r01.json", [kline("cpu", 4096)])
    _trend_round(d2, "BENCH_r02.json", [kline("cpu", 9000)])
    r = _run_trend(["--dir", str(d2)])
    assert r.returncode == 0 and "kv_waste_bytes" in r.stderr
    r = _run_trend(["--dir", str(d2), "--strict-cpu"])
    assert r.returncode == 1
    # waste DROPPING (the paged win) is clean
    d3 = tmp_path / "kv3"
    d3.mkdir()
    _trend_round(d3, "BENCH_r01.json", [kline("tpu", 4096)])
    _trend_round(d3, "BENCH_r02.json", [kline("tpu", 128)])
    r = _run_trend(["--dir", str(d3)])
    assert r.returncode == 0, r.stderr
    # waste returning from a zero baseline: the leak signature
    d4 = tmp_path / "kv4"
    d4.mkdir()
    _trend_round(d4, "BENCH_r01.json", [kline("tpu", 0)])
    _trend_round(d4, "BENCH_r02.json", [kline("tpu", 2048)])
    r = _run_trend(["--dir", str(d4)])
    assert r.returncode == 1
    assert "zero baseline" in r.stderr
    # fresh v12 line without admission_mode: error on every backend
    d5 = tmp_path / "kv5"
    d5.mkdir()
    noam = kline("cpu", 4096)
    del noam["admission_mode"]
    _trend_round(d5, "BENCH_r01.json", [noam])
    r = _run_trend(["--dir", str(d5)])
    assert r.returncode == 1
    assert "admission_mode" in r.stderr
    # a paged line missing its block fields: error
    d6 = tmp_path / "kv6"
    d6.mkdir()
    _trend_round(d6, "BENCH_r01.json",
                 [kline("cpu", 4096, admission_mode="paged")])
    r = _run_trend(["--dir", str(d6)])
    assert r.returncode == 1
    assert "block" in r.stderr
    # ...but an archived round DECLARING v11 is exempt, and a stale
    # replay with cratered waste never trends
    d7 = tmp_path / "kv7"
    d7.mkdir()
    old = kline("tpu", 4096)
    del old["admission_mode"]
    old["schema_version"] = 11
    _trend_round(d7, "BENCH_r01.json", [old])
    _trend_round(d7, "BENCH_r02.json",
                 [dict(kline("tpu", 999999), stale=True)])
    r = _run_trend(["--dir", str(d7)])
    assert r.returncode == 0, r.stderr


def _ledger_rec(entry_point="ddp_resnet18_o2", repl=7000, **kw):
    """A schema-complete v13 replication-ledger record (what bench.py
    --graph-lint and the --sharding CLI emit)."""
    arg = 1000
    return exporters.JsonlExporter.enrich({
        "kind": "sharding", "entry_point": entry_point,
        "source": "jaxpr", "world": 8, "mesh_axes": {"data": 8},
        "shard_maps": 1, "argument_bytes": arg,
        "unique_bytes": 8 * arg - repl, "replicated_bytes": repl,
        "replicated_bytes_by_dtype": {"float32": repl} if repl else {},
        "replicated_fraction": repl / (8 * arg),
        "top_replicated": [], "resharding_eqns": {}, **kw})


def test_v13_sharding_records_and_version_gating():
    """Schema v13 (the sharding plane): ``kind: sharding`` records
    dispatch to their own validator, the ledger identity must
    reassemble, and archived streams declaring v1..v12 — which never
    carry the kind — re-validate clean at their declared versions."""
    assert exporters.SCHEMA_VERSION >= 13
    good = _ledger_rec()
    assert exporters.validate_sharding_record(good) == []
    assert exporters.validate_telemetry_record(good) == []
    # the identity every record must satisfy:
    # unique + replicated == world * argument
    assert any("reassemble" in e for e in
               exporters.validate_sharding_record(
                   dict(good, replicated_bytes=6999,
                        replicated_bytes_by_dtype={"float32": 6999})))
    # archived pre-v13 records of every enveloped kind stay valid at
    # their declared version after the bump
    old_kinds = [
        exporters.JsonlExporter.enrich(
            {"metric": "m", "value": 1.0, "unit": "x",
             "backend": "cpu", "ndev": 8, "arch": "cpu"}),
        exporters.JsonlExporter.enrich(
            {"kind": "graph_lint", "rule": "donation",
             "severity": "error", "entry_point": "e", "message": "m"}),
    ]
    for rec in old_kinds:
        for v in range(1, 13):
            archived = dict(rec, schema_version=v)
            assert exporters.validate_telemetry_record(archived) == [], v


def test_check_bench_trend_sharding_gate(tmp_path):
    """The replication-ledger trend gate (schema v13): duplicate-bytes
    growth past --mem-tol gates on EVERY backend (the ledger is
    statically derived, the peak_bytes rule), a zero baseline
    returning to nonzero is the un-sharded signature, shrinkage (the
    ZeRO direction) is clean, and stale replays partition out."""
    # growth past mem-tol on CPU smoke still errors — no noise excuse
    d1 = tmp_path / "sh1"
    d1.mkdir()
    _trend_round(d1, "BENCH_r01.json",
                 [_ledger_rec(repl=7000, backend="cpu")])
    _trend_round(d1, "BENCH_r02.json",
                 [_ledger_rec(repl=7900, backend="cpu")])  # +13%
    r = _run_trend(["--dir", str(d1)])
    assert r.returncode == 0, r.stderr          # within default 25%
    r = _run_trend(["--dir", str(d1), "--mem-tol", "0.1"])
    assert r.returncode == 1
    assert "replicated_bytes" in r.stderr
    # shrinking the duplicate bytes (a ZeRO shard landing) is clean
    d2 = tmp_path / "sh2"
    d2.mkdir()
    _trend_round(d2, "BENCH_r01.json", [_ledger_rec(repl=7000)])
    _trend_round(d2, "BENCH_r02.json", [_ledger_rec(repl=1000)])
    r = _run_trend(["--dir", str(d2), "--mem-tol", "0.1"])
    assert r.returncode == 0, r.stderr
    # a fully-sharded (zero) baseline returning to replication gates
    d3 = tmp_path / "sh3"
    d3.mkdir()
    _trend_round(d3, "BENCH_r01.json", [_ledger_rec(repl=0)])
    _trend_round(d3, "BENCH_r02.json", [_ledger_rec(repl=2048)])
    r = _run_trend(["--dir", str(d3)])
    assert r.returncode == 1
    assert "zero baseline" in r.stderr
    # distinct entry points trend independently; a stale replay with
    # inflated bytes never enters the trend
    d4 = tmp_path / "sh4"
    d4.mkdir()
    _trend_round(d4, "BENCH_r01.json",
                 [_ledger_rec("ep_a", 7000), _ledger_rec("ep_b", 100)])
    _trend_round(d4, "BENCH_r02.json",
                 [_ledger_rec("ep_a", 7000),
                  dict(_ledger_rec("ep_b", 999999), stale=True)])
    r = _run_trend(["--dir", str(d4), "--mem-tol", "0.01"])
    assert r.returncode == 0, r.stderr
    assert "stale replays partitioned" in r.stderr


def test_v15_zero_stage_records_and_version_gating():
    """Schema v15 (the ZeRO weight-update plane): fresh zero
    train-throughput lines and zero-EP sharding ledgers must carry
    ``zero_stage`` in {1, 2, 3}; the field is value-checked wherever
    it appears; archived v1..v14 streams re-validate clean at their
    declared versions."""
    assert exporters.SCHEMA_VERSION == 15
    base = {"metric": "ddp_resnet18_o2_zero3_train_throughput",
            "value": 100.0, "unit": "images/sec/chip",
            "vs_baseline": None, "backend": "cpu", "ndev": 8,
            "arch": "cpu", "flops_per_step": 1e12,
            "achieved_tflops": 10.0, "mfu": None,
            "peak_bytes": 1_000_000, "cold_compile_ms": 10.0,
            "compiles_total": 1, "steady_state_retraces": 0,
            "zero_stage": 3}
    assert exporters.validate_bench_record(
        exporters.JsonlExporter.enrich(dict(base))) == []
    # fresh v15 zero line without the stage tag gates
    rec = exporters.JsonlExporter.enrich(
        {k: v for k, v in base.items() if k != "zero_stage"})
    assert any("zero_stage" in e for e in
               exporters.validate_bench_record(rec))
    # ...but the same record declaring v14 rolls back clean
    v14 = dict(rec, schema_version=14)
    assert exporters.validate_bench_record(v14) == []
    # non-zero train lines never need the tag
    plain = exporters.JsonlExporter.enrich(
        dict({k: v for k, v in base.items() if k != "zero_stage"},
             metric="ddp_resnet18_o2_train_throughput"))
    assert exporters.validate_bench_record(plain) == []
    # the stage is value-checked wherever it appears (any metric)
    for bad in (0, 4, True, "3", 2.0):
        rec = exporters.JsonlExporter.enrich(
            {"metric": "m", "value": 1.0, "unit": "x",
             "vs_baseline": None, "backend": "cpu", "ndev": 8,
             "arch": "cpu", "zero_stage": bad})
        assert any("zero_stage" in e for e in
                   exporters.validate_bench_record(rec)), bad

    # sharding plane: fresh v15 ledgers for zero EPs carry the stage
    zled = _ledger_rec("ddp_resnet18_o2_zero2", zero_stage=2)
    assert exporters.validate_sharding_record(zled) == []
    missing = {k: v for k, v in zled.items() if k != "zero_stage"}
    assert any("zero_stage" in e for e in
               exporters.validate_sharding_record(missing))
    archived = dict(missing, schema_version=14)
    assert exporters.validate_sharding_record(archived) == []
    assert any("zero_stage" in e for e in
               exporters.validate_sharding_record(
                   dict(zled, zero_stage=7)))
    # non-zero EPs stay exempt at v15
    assert exporters.validate_sharding_record(_ledger_rec()) == []


def test_check_bench_trend_skips_twin_anomaly_overlap_records(tmp_path):
    """A record whose attribution flagged its own compute twin as
    slower than the step (compute_twin_excess_ms > 0) carries CLAMPED
    perfect-overlap numbers (comm_ms=0, overlap_fraction=1.0) — it
    must not seed the overlap trend, or the next HEALTHY round gates
    as a phantom regression."""
    def attr(frac, visible, **kw):
        return exporters.JsonlExporter.enrich(
            {"metric": "train_step_attribution_overlap",
             "value": 5.0, "unit": "ms", "vs_baseline": None,
             "backend": "tpu", "ndev": 8, "arch": "TPU v5 lite",
             "overlap_fraction": frac, "comm_visible_ms": visible,
             "overlap_mode": "overlapped", "n_stages": 4,
             "issue_order": [3, 2, 1, 0], **kw})

    d = tmp_path / "twin1"
    d.mkdir()
    # round 1: the twin anomaly (clamped to perfect overlap)
    _trend_round(d, "BENCH_r01.json",
                 [attr(1.0, 0.0, compute_twin_excess_ms=2.5)])
    # round 2: a healthy real measurement — must NOT gate against the
    # clamped 1.0/0.0 baseline
    _trend_round(d, "BENCH_r02.json", [attr(0.5, 1.2)])
    r = _run_trend(["--dir", str(d)])
    assert r.returncode == 0, r.stderr
    # sanity: without the anomaly marker the same pair DOES gate
    d2 = tmp_path / "twin2"
    d2.mkdir()
    _trend_round(d2, "BENCH_r01.json", [attr(1.0, 0.0)])
    _trend_round(d2, "BENCH_r02.json", [attr(0.5, 1.2)])
    r = _run_trend(["--dir", str(d2)])
    assert r.returncode == 1
