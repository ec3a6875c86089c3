"""Telemetry subsystem: metrics registry (host + device-resident),
span tracing / Chrome-trace export, exporters, engine stats, and the
record schemas."""

import functools
import json
import threading

import numpy as np
import pytest
import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from apex_tpu import analysis, models, observability as obs, serving
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


def test_check_telemetry_schema_cli(tmp_path):
    """The tests/ci gate accepts a valid lint stream and rejects a
    broken record and a record without a known ``kind``."""
    import subprocess
    import sys
    import os
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    script = os.path.join(root, "tests", "ci",
                          "check_telemetry_schema.py")
    finding = exporters.JsonlExporter.enrich(
        {"kind": "graph_lint", "rule": "donation", "severity": "error",
         "entry_point": "e", "message": "m"})
    summary = exporters.JsonlExporter.enrich(
        {"kind": "graph_lint_summary", "entry_points": 1, "rules": 1,
         "findings": 1, "errors": 1, "warnings": 0})
    good = json.dumps(finding) + "\n" + json.dumps(summary) + "\n"
    r = subprocess.run([sys.executable, script], input=good,
                       capture_output=True, text=True)
    assert r.returncode == 0, r.stderr
    assert "2 records OK" in r.stdout
    for broken in (dict(finding, severity="loud"),
                   {k: v for k, v in finding.items() if k != "kind"},
                   dict(finding, kind="bench")):
        r = subprocess.run([sys.executable, script],
                           input=json.dumps(broken) + "\n",
                           capture_output=True, text=True)
        assert r.returncode == 1, broken
    path = tmp_path / "records.jsonl"
    path.write_text(good)
    r = subprocess.run([sys.executable, script, str(path)],
                       capture_output=True, text=True)
    assert r.returncode == 0, r.stderr


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


def test_profiler_unique_capture_dirs(tmp_path):
    """Repeated captures into ONE logdir land in distinct
    subdirectories, each holding its own trace file — start_trace names
    sessions by wall-clock second, so two captures in one second used
    to overwrite each other."""
    import glob
    import os
    from apex_tpu.utils import profiler
    f = jax.jit(lambda a: (a @ a).sum())
    x = jnp.ones((16, 16))
    f(x).block_until_ready()
    dirs = []
    for _ in range(2):
        with profiler.profile(str(tmp_path)) as cap:
            assert profiler.current_capture_dir() == cap
            f(x).block_until_ready()
        dirs.append(cap)
    assert dirs[0] != dirs[1]
    assert all(d.startswith(str(tmp_path)) for d in dirs)
    assert profiler.current_capture_dir() is None
    assert profiler.last_capture_dir() == dirs[1]
    # both captures kept their own trace file — nothing overwritten
    traces = [glob.glob(os.path.join(d, "plugins", "profile", "*",
                                     "*.xplane.pb")) for d in dirs]
    assert all(len(t) == 1 for t in traces), traces
    assert traces[0] != traces[1]
    # nested profile() joins the outer window: same dir, refcount
    # semantics preserved (the nesting test above monkeypatches the
    # trace calls; this one exercises the real window)
    with profiler.profile(str(tmp_path)) as outer:
        with profiler.profile(str(tmp_path / "inner")) as inner:
            assert inner == outer
            assert profiler.profiling_active()
        assert profiler.profiling_active()
    assert not profiler.profiling_active()


def test_failed_start_trace_leaves_no_orphan_dir(tmp_path, monkeypatch):
    """A foreign trace already active makes start_trace raise; the
    pre-created unique capture dir must not be left behind (a caller
    retrying would otherwise grow one orphan per attempt) and the
    refcount must stay clean."""
    import os
    from apex_tpu.utils import profiler

    def boom(d):
        raise RuntimeError("Only one profile may be run at a time.")
    monkeypatch.setattr(jax.profiler, "start_trace", boom)
    with pytest.raises(RuntimeError, match="one profile"):
        profiler.start_profile(str(tmp_path))
    assert os.listdir(str(tmp_path)) == []
    assert not profiler.profiling_active()


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


_RECOVERY_BASE = {"kind": "recovery", "role": "training",
                  "subject": "run", "episodes": 0, "actions_total": 0,
                  "max_actions_in_episode": 0, "actions": [],
                  "mttr_s": {"last": None, "mean": None, "count": 0},
                  "in_flight": False, "duration_s": 1.0}


@pytest.mark.parametrize("extra, named", [
    ({"cause": "preemption", "preempted": True,
      "data_state": {"samples_consumed": 80, "epoch": 1, "cursor": 16,
                     "shard_id": 0, "num_shards": 4}}, None),
    # the snapshot action kind is known to the validator
    ({"episodes": 1, "actions_total": 1, "max_actions_in_episode": 1,
      "actions": [{"kind": "preempt_snapshot", "episode": 1,
                   "t_s": 0.5}]}, None),
    ({"cause": "cosmic_rays"}, "cause"),
    ({"data_state": {"samples_consumed": -1}}, "samples_consumed"),
    ({"data_state": {"shard_id": 5, "num_shards": 4}}, "shard_id"),
    ({"preempted": "yes"}, "preempted"),
], ids=["preempted_ok", "preempt_snapshot_ok", "bad_cause",
        "negative_samples", "shard_out_of_range", "preempted_not_bool"])
def test_recovery_preemption_fields_are_value_checked(extra, named):
    """Recovery records validate cause / preempted / data_state
    whenever present, and know the ``preempt_snapshot`` action kind."""
    rec = exporters.JsonlExporter.enrich({**_RECOVERY_BASE, **extra})
    errs = exporters.validate_recovery_record(rec)
    if named is None:
        assert errs == []
    else:
        assert any(named in e for e in errs), errs


# -- PR 15: the compilation plane ------------------------------------------


def test_tenant_counts_are_the_slo_trackers_bucket_keys():
    """The tenant plane: TENANT_COUNTS is pinned to the SLO tracker's
    actual bucket keys so the fleet-record validator and the producer
    cannot drift."""
    from apex_tpu.fleet import slo as fleet_slo
    assert exporters.TENANT_COUNTS == tuple(
        k for k in fleet_slo._new_tenant_bucket()
        if k not in ("t_first", "t_last", "tenant"))


def _ledger_rec(entry_point="ddp_resnet18_o2", repl=7000, **kw):
    """A schema-complete replication-ledger record (what the
    --sharding CLI emits)."""
    arg = 1000
    return exporters.JsonlExporter.enrich({
        "kind": "sharding", "entry_point": entry_point,
        "source": "jaxpr", "world": 8, "mesh_axes": {"data": 8},
        "shard_maps": 1, "argument_bytes": arg,
        "unique_bytes": 8 * arg - repl, "replicated_bytes": repl,
        "replicated_bytes_by_dtype": {"float32": repl} if repl else {},
        "replicated_fraction": repl / (8 * arg),
        "top_replicated": [], "resharding_eqns": {}, **kw})


def test_sharding_records_reassemble_and_dispatch():
    """The sharding plane: ``kind: sharding`` records dispatch to
    their own validator, alone and in a mixed stream, and the ledger
    identity must reassemble."""
    good = _ledger_rec()
    assert exporters.validate_sharding_record(good) == []
    assert exporters.validate_telemetry_record(good) == []
    # the identity every record must satisfy:
    # unique + replicated == world * argument
    assert any("reassemble" in e for e in
               exporters.validate_sharding_record(
                   dict(good, replicated_bytes=6999,
                        replicated_bytes_by_dtype={"float32": 6999})))
    # a mixed stream stays clean; a line without a kind in it does not
    lint = exporters.JsonlExporter.enrich(
        {"kind": "graph_lint", "rule": "donation",
         "severity": "error", "entry_point": "e", "message": "m"})
    assert exporters.validate_telemetry_jsonl(
        [json.dumps(good), json.dumps(lint)]) == []
    no_kind = exporters.JsonlExporter.enrich(
        {"metric": "m", "value": 1.0, "unit": "x"})
    errs = exporters.validate_telemetry_jsonl(
        [json.dumps(good), json.dumps(no_kind)])
    assert len(errs) == 1 and "line 2" in errs[0] and "kind" in errs[0]


def test_zero_ledgers_carry_their_stage():
    """The ZeRO weight-update plane: zero-EP sharding ledgers must
    carry ``zero_stage`` in {1, 2, 3}; the field is value-checked."""
    zled = _ledger_rec("ddp_resnet18_o2_zero2", zero_stage=2)
    assert exporters.validate_sharding_record(zled) == []
    missing = {k: v for k, v in zled.items() if k != "zero_stage"}
    assert any("zero_stage" in e for e in
               exporters.validate_sharding_record(missing))
    assert any("zero_stage" in e for e in
               exporters.validate_sharding_record(
                   dict(zled, zero_stage=7)))
    # non-zero EPs stay exempt
    assert exporters.validate_sharding_record(_ledger_rec()) == []




# -- one validator per kind, each fed by the library's own producer --------

def _tiny_sharded_entry_point(name, body):
    """A synthetic analysis entry point over an 8-way shard_map of
    ``body``, with a real lowering (the memory record compiles it)."""
    from apex_tpu.analysis.entry_points import EntryPoint
    from apex_tpu.analysis.graphs import Graph
    mesh = Mesh(np.array(jax.devices()[:8]), ("data",))
    mapped = jax.shard_map(body, mesh=mesh, in_specs=(P(),),
                           out_specs=P(), check_vma=False)
    x = jnp.ones((1024,))
    return EntryPoint(name, lambda ep: Graph(
        trace=lambda: jax.make_jaxpr(mapped)(x),
        lower=lambda: jax.jit(mapped).lower(x)))


def _lint_records():
    def leaky(x):
        return jax.pure_callback(
            lambda a: np.asarray(a),
            jax.ShapeDtypeStruct(x.shape, x.dtype), x)

    ep = _tiny_sharded_entry_point("producer_lint", leaky)
    out = []
    analysis.run_lint(entry_points=[ep], rules=["host-transfer"],
                      emit=out.append)
    (finding, summary) = out
    return finding, summary


def _ledger_record(build):
    ep = _tiny_sharded_entry_point("producer_ledger",
                                   lambda x: lax.psum(x, "data"))
    return build(ep)


def _fleet_record():
    from apex_tpu.fleet import Fleet
    m, p = _gpt()
    fl = Fleet([serving.Engine(m, p, slots=2, buf_len=24)],
               step_workers=1)
    try:
        fl.submit([1, 2, 3], max_new_tokens=2, tenant="a")
        while fl.live():
            fl.step()
        return fl.record()
    finally:
        fl.close()


def _trace_record():
    rec = obs.SpanRecorder()
    tid = obs.new_trace_id()
    root = rec.event("submit", trace_id=tid)
    with rec.activate(tid, root):
        with rec.span("dispatch"):
            rec.event("tick")
    return rec.trace_record(tid)


def _numerics_record():
    from apex_tpu.observability import numerics
    g = {"a": jnp.asarray([8.0, -16.0]), "b": jnp.asarray([4.0])}
    nm = numerics.NumericsMonitor(g, half_dtype="bfloat16",
                                  registry=obs.MetricsRegistry())
    tele = nm.update(nm.init(), grad_stats=nm.leaf_stats(g, 1.0),
                     found_inf=jnp.zeros(()), loss_scale=1.0)
    return nm.to_record(nm.flush(tele), metric="producer")


def _run_record():
    sup = obs.RunSupervisor(
        "t", ring=obs.EventRing(capacity=64),
        registry=obs.MetricsRegistry(),
        config=obs.SupervisorConfig(stall_observations=4,
                                    warmup_observations=3))
    for i in range(4):
        sup.observe_step(step=i, loss=1.0, step_time_s=0.01)
    sup.observe_step(step=4, loss=float("nan"))
    return sup.record(metric="producer")


def _recovery_record():
    from apex_tpu.fleet.recovery import RecoveryLog
    log = RecoveryLog("serving", "t", ring=obs.EventRing(64))
    log.open_episode("spike")
    log.action("admission_tighten", max_queue_from=8, max_queue_to=4)
    log.close_episode(mttr_s=3.0)
    return log.record()


# kind -> the library's own producer of it, at the smallest input that
# producer's own test builds
_PRODUCERS = {
    "graph_lint": lambda: _lint_records()[0],
    "graph_lint_summary": lambda: _lint_records()[1],
    "fleet": _fleet_record,
    "trace": _trace_record,
    "memory": lambda: _ledger_record(analysis.entry_point_memory_record),
    "numerics": _numerics_record,
    "run": _run_record,
    "recovery": _recovery_record,
    "sharding": lambda: _ledger_record(
        analysis.entry_point_sharding_record),
}


@functools.lru_cache(maxsize=None)
def _produced_json(kind):
    return json.dumps(exporters.JsonlExporter.enrich(_PRODUCERS[kind]()))


def _produced(kind):
    """The enriched record of ``kind``, made once a process."""
    return json.loads(_produced_json(kind))


@pytest.mark.parametrize("kind", list(_PRODUCERS))
def test_library_producer_passes_its_validator(kind):
    """Each record kind the library produces validates through the
    dispatcher as its producer makes it, and the same record with
    ``kind`` removed or misspelt is rejected by name: the dispatcher
    has no default schema."""
    rec = _produced(kind)
    assert rec["kind"] == kind
    assert exporters.validate_telemetry_record(rec) == []
    assert json.loads(json.dumps(rec)) == rec      # a JSONL line
    for broken in ({k: v for k, v in rec.items() if k != "kind"},
                   dict(rec, kind=kind + "s"), dict(rec, kind=None)):
        errs = exporters.validate_telemetry_record(broken)
        assert len(errs) == 1 and "'kind'" in errs[0], errs


@pytest.mark.parametrize("kind", list(_PRODUCERS))
def test_a_record_of_another_schema_version_is_refused(kind):
    """One schema: the record its producer makes, declaring the
    version before the current one, gets exactly one error, and that
    error names both versions.  Nothing else about it is judged more
    leniently or more strictly."""
    cur = exporters.SCHEMA_VERSION
    rec = _produced(kind)
    assert rec["schema_version"] == cur
    for other in (cur - 1, cur + 1):
        errs = exporters.validate_telemetry_record(
            dict(rec, schema_version=other))
        assert len(errs) == 1, errs
        assert str(other) in errs[0] and str(cur) in errs[0], errs


def test_dispatcher_knows_exactly_the_produced_kinds():
    """No schema is reachable without a producer above, none of the
    producers lacks one, and what is not a record of a known kind is
    an error that names ``kind``."""
    assert set(exporters._VALIDATORS) == set(_PRODUCERS)
    for rec in ({}, {"kind": "bench"}, {"kind": ["fleet"]},
                exporters.JsonlExporter.enrich(
                    {"metric": "m", "value": 1.0, "unit": "x"})):
        errs = exporters.validate_telemetry_record(rec)
        assert len(errs) == 1 and "'kind'" in errs[0], errs
    assert exporters.validate_telemetry_record([1, 2]) != []
    assert exporters.validate_telemetry_jsonl([]) == ["no records found"]
    assert any("not JSON" in e
               for e in exporters.validate_telemetry_jsonl(["{oops"]))


def _lint_finding():
    return exporters.JsonlExporter.enrich(
        {"kind": "graph_lint", "rule": "donation", "severity": "error",
         "entry_point": "e", "message": "m"})


@pytest.mark.parametrize("mutate, named", [
    (lambda r: r.pop("stale"), "stale"),
    (lambda r: r.update(stale="no"), "stale"),
    (lambda r: r.pop("schema_version"), "schema_version"),
    (lambda r: r.update(schema_version=0), "schema_version"),
    (lambda r: r.update(schema_version=True), "schema_version"),
    (lambda r: r.pop("host"), "host"),
    (lambda r: r.update(host={"hostname": 7, "pid": 1}), "host.hostname"),
    (lambda r: r.update(host={"hostname": "h", "pid": "1"}), "host.pid"),
], ids=["no_stale", "stale_not_bool", "no_version", "version_zero",
        "version_bool", "no_host", "hostname_not_str", "pid_not_int"])
def test_record_envelope_is_required(mutate, named):
    """The envelope every kind shares (schema_version / capture host /
    boolean ``stale``), held on a lint record: ``_check_envelope`` is
    one implementation for all nine kinds."""
    rec = _lint_finding()
    assert exporters.validate_telemetry_record(rec) == []
    mutate(rec)
    errs = exporters.validate_telemetry_record(rec)
    assert any(named in e for e in errs), errs
