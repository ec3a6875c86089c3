"""Device-timeline attribution (PR 13): the stdlib chrome-trace parser
on hand-built synthetic traces (overlap / gap / collective
classification pinned without a capture), the real-capture path on the
8-virtual-device CPU mesh (jax.profiler writes it, we parse it), the
unique per-capture directory contract, and the ``kind: profile`` record
schema."""

import gzip
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from apex_tpu.observability import exporters, timeline
from apex_tpu.utils import profiler


# -- synthetic-trace unit suite (no capture needed) ------------------------

def _trace(events):
    """A minimal chrome-trace document: the given X events plus the
    host-frame noise a real capture interleaves (python tracer events
    without hlo_op, metadata rows) that the parser must drop."""
    noise = [
        {"ph": "M", "pid": 1, "tid": 1, "name": "thread_name",
         "args": {"name": "python"}},
        {"ph": "X", "pid": 1, "tid": 1, "ts": 0.0, "dur": 999.0,
         "name": "$builtins isinstance"},          # no args at all
        {"ph": "X", "pid": 1, "tid": 1, "ts": 0.0, "dur": 999.0,
         "name": "host frame", "args": {"not_hlo": 1}},
        {"ph": "i", "pid": 1, "tid": 1, "ts": 5.0, "name": "instant",
         "args": {"hlo_op": "ignored"}},           # wrong phase
    ]
    return {"displayTimeUnit": "ns", "traceEvents": noise + events}


def _kernel(name, ts, dur, tid=2, module="jit_step", op=None):
    return {"ph": "X", "pid": 7, "tid": tid, "ts": ts, "dur": dur,
            "name": name,
            "args": {"hlo_op": op or name, "hlo_module": module}}


def test_classify_kernel_patterns():
    for name in ("all-reduce.1", "all-gather.3", "reduce-scatter",
                 "collective-permute.2", "all-to-all",
                 "fused-all-reduce-start.1"):
        assert timeline.classify_kernel(name) == "collective", name
    for name in ("dot.3", "fusion.12", "tanh", "reduce-window",
                 "convolution.1", "copy"):
        assert timeline.classify_kernel(name) == "compute", name
    # the exporters validator duplicates the field tuple (stdlib CI
    # loader discipline) — pin the pairs equal so they cannot drift
    assert exporters.PROFILE_TIME_FIELDS + (
        "measured_overlap_fraction",) == timeline.PROFILE_FIELDS


def test_merge_and_overlap_primitives():
    merged = timeline.merge_intervals(
        [(0, 10), (5, 15), (20, 30), (30, 31), (40, 40)])
    assert merged == [(0, 15), (20, 31)]
    assert timeline.overlap_us([(0, 10), (20, 30)],
                               [(5, 25)]) == pytest.approx(10.0)
    assert timeline.overlap_us([], [(0, 5)]) == 0.0


def test_synthetic_full_overlap():
    """A collective fully hidden under compute: overlap == collective,
    measured fraction 1.0."""
    doc = _trace([
        _kernel("dot.1", ts=0.0, dur=100.0),
        _kernel("all-reduce.1", ts=20.0, dur=50.0, tid=3),
    ])
    att = timeline.attribute_timeline(timeline.device_events(doc))
    assert att["span_ms"] == pytest.approx(0.1)
    assert att["device_busy_ms"] == pytest.approx(0.1)   # union
    assert att["compute_ms"] == pytest.approx(0.1)
    assert att["collective_ms"] == pytest.approx(0.05)
    assert att["overlap_ms"] == pytest.approx(0.05)
    assert att["measured_overlap_fraction"] == pytest.approx(1.0)
    assert att["gap_ms"] == 0.0
    assert att["kernel_count"] == 2 and att["lane_count"] == 2


def test_synthetic_no_overlap_reduce_after_backward():
    """The reduce-after-backward shape: compute then collective,
    disjoint — fraction 0.0, exactly today's baseline."""
    doc = _trace([
        _kernel("fusion.1", ts=0.0, dur=100.0),
        _kernel("all-reduce.1", ts=100.0, dur=40.0),
    ])
    att = timeline.attribute_timeline(timeline.device_events(doc))
    assert att["measured_overlap_fraction"] == 0.0
    assert att["overlap_ms"] == 0.0
    assert att["collective_ms"] == pytest.approx(0.04)
    assert att["device_busy_ms"] == pytest.approx(0.14)
    assert att["gap_ms"] == 0.0


def test_synthetic_gap_and_partial_overlap():
    """Gap = span minus busy; overlap counts only the covered part of
    the collective."""
    doc = _trace([
        _kernel("dot.1", ts=0.0, dur=100.0),
        # idle 100..200, then a collective whose first half overlaps
        # the next compute kernel
        _kernel("all-reduce.2", ts=200.0, dur=100.0, tid=3),
        _kernel("fusion.7", ts=200.0, dur=50.0),
    ])
    att = timeline.attribute_timeline(timeline.device_events(doc))
    assert att["span_ms"] == pytest.approx(0.3)
    assert att["device_busy_ms"] == pytest.approx(0.2)
    assert att["gap_ms"] == pytest.approx(0.1)
    assert att["overlap_ms"] == pytest.approx(0.05)
    assert att["measured_overlap_fraction"] == pytest.approx(0.5)
    # the record built from it is schema-valid
    rec = exporters.JsonlExporter.enrich(
        timeline.profile_record(att, metric="synthetic"))
    assert exporters.validate_profile_record(rec) == []
    assert exporters.validate_telemetry_record(rec) == []


def test_synthetic_module_filter_and_topk():
    doc = _trace([
        _kernel("dot.1", ts=0.0, dur=10.0),
        _kernel("dot.2", ts=10.0, dur=30.0),
        _kernel("tanh.1", ts=40.0, dur=5.0),
        _kernel("sum.1", ts=0.0, dur=500.0, module="jit__multi_slice"),
    ])
    ev = timeline.device_events(doc, modules=("jit_step",))
    assert {e["name"] for e in ev} == {"dot.1", "dot.2", "tanh.1"}
    att = timeline.attribute_timeline(ev, top_k=1)
    # ``.N`` instance suffixes aggregate: dot.1 + dot.2 -> one line
    assert att["top_kernels"] == [
        {"name": "dot", "kind": "compute", "count": 2,
         "total_ms": pytest.approx(0.04)}]
    # no collectives at all: fraction pins to 0.0, not NaN
    assert att["measured_overlap_fraction"] == 0.0
    # empty event list attributes to all-zeros (a capture of an idle
    # process must produce a valid record, /profilez relies on it)
    empty = timeline.attribute_timeline([])
    rec = exporters.JsonlExporter.enrich(
        timeline.profile_record(empty, metric="idle"))
    assert exporters.validate_profile_record(rec) == []
    assert empty["span_ms"] == empty["device_busy_ms"] == 0.0


def test_load_trace_plain_and_gz(tmp_path):
    doc = _trace([_kernel("dot.1", ts=0.0, dur=10.0)])
    plain = tmp_path / "a.trace.json"
    plain.write_text(json.dumps(doc))
    with gzip.open(str(tmp_path / "b.trace.json.gz"), "wt") as f:
        json.dump(doc, f)
    for p in (str(plain), str(tmp_path / "b.trace.json.gz")):
        loaded = timeline.load_trace(p)
        assert len(timeline.device_events(loaded)) == 1
    bad = tmp_path / "c.trace.json"
    bad.write_text(json.dumps({"nope": 1}))
    with pytest.raises(ValueError, match="traceEvents"):
        timeline.load_trace(str(bad))


def test_find_trace_file_resolves_newest_session(tmp_path):
    """The jax layout (plugins/profile/<session>/host.trace.json.gz)
    resolves; with two sessions the newest wins; a missing capture
    raises FileNotFoundError instead of parsing stale garbage."""
    with pytest.raises(FileNotFoundError):
        timeline.find_trace_file(str(tmp_path))
    s1 = tmp_path / "plugins" / "profile" / "2026_01_01_00_00_00"
    s1.mkdir(parents=True)
    p1 = s1 / "host.trace.json.gz"
    with gzip.open(str(p1), "wt") as f:
        json.dump(_trace([]), f)
    assert timeline.find_trace_file(str(tmp_path)) == str(p1)
    s2 = tmp_path / "plugins" / "profile" / "2026_01_02_00_00_00"
    s2.mkdir(parents=True)
    p2 = s2 / "host.trace.json"
    p2.write_text(json.dumps(_trace([])))
    os.utime(str(p1), (1, 1))              # force p2 newer
    assert timeline.find_trace_file(str(tmp_path)) == str(p2)
    # a direct file path passes through
    assert timeline.find_trace_file(str(p2)) == str(p2)


def test_profile_record_schema_mutations():
    """validate_profile_record catches the hand-built-record
    mistakes: busy above span, gap not reassembling, overlap escaping
    its intersection bound, fraction inconsistent with its own sides,
    unknown kernel kinds, and bad KV fields."""
    att = timeline.attribute_timeline(timeline.device_events(_trace([
        _kernel("dot.1", ts=0.0, dur=100.0),
        _kernel("all-reduce.1", ts=50.0, dur=100.0, tid=3),
    ])))
    good = exporters.JsonlExporter.enrich(timeline.profile_record(
        att, metric="m", kv_cache_bytes=1000, kv_waste_bytes=400,
        kv_utilization=0.6))
    assert exporters.validate_profile_record(good) == []
    assert any("kind" in e for e in exporters.validate_profile_record(
        {**good, "kind": "bench"}))
    assert any("metric" in e[:40] or "entry_point" in e
               for e in exporters.validate_profile_record(
                   {k: v for k, v in good.items() if k != "metric"}))
    assert any("device_busy_ms" in e
               for e in exporters.validate_profile_record(
                   {**good, "device_busy_ms": good["span_ms"] + 5.0}))
    assert any("gap_ms" in e
               for e in exporters.validate_profile_record(
                   {**good, "gap_ms": good["gap_ms"] + 3.0}))
    assert any("overlap_ms" in e
               for e in exporters.validate_profile_record(
                   {**good, "overlap_ms": good["collective_ms"] + 1.0}))
    assert any("measured_overlap_fraction" in e
               for e in exporters.validate_profile_record(
                   {**good, "measured_overlap_fraction": 0.0}))
    assert any("collective_ms" in e
               for e in exporters.validate_profile_record(
                   {**good, "collective_ms": -1.0}))
    assert any("top_kernels" in e
               for e in exporters.validate_profile_record(
                   {**good, "top_kernels": [
                       {"name": "dot", "kind": "magic", "count": 1,
                        "total_ms": 1.0}]}))
    assert any("kv_waste_bytes" in e
               for e in exporters.validate_profile_record(
                   {**good, "kv_waste_bytes": 2000}))   # > cache
    assert any("kv_utilization" in e
               for e in exporters.validate_profile_record(
                   {**good, "kv_utilization": 1.5}))
    assert any("steps" in e for e in exporters.validate_profile_record(
        {**good, "steps": 0}))


# -- real captures on the CPU mesh ----------------------------------------

def _psum_step():
    mesh = Mesh(np.array(jax.devices()[:8]), ("data",))

    def step(x):
        y = jnp.tanh(x @ x.T)
        return jax.lax.psum(y.sum(), "data")

    return jax.jit(jax.shard_map(step, mesh=mesh,
                                 in_specs=(P("data"),), out_specs=P(),
                                 check_vma=False))


def test_real_capture_parses_with_collectives(tmp_path):
    """One jitted psum step captured under profile(): the parser finds
    the trace jax actually wrote, the all-reduce classifies as a
    collective, and the per-step attribution is schema-valid."""
    f = _psum_step()
    x = jnp.ones((8 * 16, 16))
    f(x).block_until_ready()               # compile outside the window
    att = timeline.capture(f, x, iters=2, logdir=str(tmp_path),
                           modules=("jit_step",))
    assert att["steps"] == 2
    assert att["trace_path"].startswith(str(tmp_path))
    assert att["kernel_count"] > 0
    assert att["device_busy_ms"] > 0
    names = {k["name"] for k in att["top_kernels"]}
    assert any(k["kind"] == "collective" for k in att["top_kernels"]), \
        names
    rec = exporters.JsonlExporter.enrich(
        timeline.profile_record(att, metric="psum_step"))
    assert exporters.validate_profile_record(rec) == []


def test_profiler_unique_capture_dirs(tmp_path):
    """The capture-reuse fix: repeated captures into ONE logdir land
    in distinct subdirectories, each holding its own trace file —
    start_trace names sessions by wall-clock second, so two captures
    in one second used to overwrite each other."""
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
    traces = [timeline.find_trace_file(d) for d in dirs]
    assert traces[0] != traces[1]
    for t in traces:
        assert timeline.load_trace(t)["traceEvents"] is not None
    # nested profile() joins the outer window: same dir, refcount
    # semantics preserved (the existing nesting test monkeypatches the
    # trace calls; this one exercises the real window)
    with profiler.profile(str(tmp_path)) as outer:
        with profiler.profile(str(tmp_path / "inner")) as inner:
            assert inner == outer
            assert profiler.profiling_active()
        assert profiler.profiling_active()
    assert not profiler.profiling_active()


def test_failed_start_trace_leaves_no_orphan_dir(tmp_path, monkeypatch):
    """A foreign trace already active makes start_trace raise; the
    pre-created unique capture dir must not be left behind (a monitor
    retrying /profilez would otherwise grow one orphan per attempt) and
    the refcount must stay clean."""
    def boom(d):
        raise RuntimeError("Only one profile may be run at a time.")
    monkeypatch.setattr(jax.profiler, "start_trace", boom)
    with pytest.raises(RuntimeError, match="one profile"):
        profiler.start_profile(str(tmp_path))
    assert os.listdir(str(tmp_path)) == []
    assert not profiler.profiling_active()
