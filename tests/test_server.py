"""Introspection server: every endpoint live, exposition conformance
on /metricsz, error isolation on /statusz (and /tenantz), 503 on a
sick run, and the tentpole acceptance pin — a server attached to a
RUNNING fleet serves every endpoint while traffic is in flight, with
the scraped numbers (including the per-tenant rollup) agreeing with
the fleet's own stats.

The HTTP layer is exercised for real (ephemeral ports, urllib), never
mocked: the contract is that an operator can point curl at a live
process."""

import json
import threading
import urllib.error
import urllib.request

import pytest

from apex_tpu.fleet import Fleet
from apex_tpu.observability import (EventRing, MetricsRegistry,
                                    RunSupervisor, SpanRecorder,
                                    exporters, server)


def _get(url, timeout=10):
    try:
        with urllib.request.urlopen(url, timeout=timeout) as r:
            return r.status, r.headers.get("Content-Type", ""), r.read()
    except urllib.error.HTTPError as e:
        return e.code, e.headers.get("Content-Type", ""), e.read()


def _get_json(url):
    code, _, body = _get(url)
    return code, json.loads(body)


class _StubReplica:
    """Minimal scheduler-surface replica (the test_fleet stub's
    shape): deterministic token stream, content-free."""

    def __init__(self, slots=2):
        self.slots = slots
        self._free = list(range(slots))
        self._live = {}
        self._waiting = []
        self._finished = {}
        self._next_rid = 0

    def submit(self, prompt, max_new_tokens, eos_token_id=None,
               seed=None, temperature=None):
        rid = self._next_rid
        self._next_rid += 1
        if self._free and not self._waiting:
            self._free.pop()
            self._live[rid] = [list(prompt), max_new_tokens, []]
        else:
            self._waiting.append((rid, list(prompt), max_new_tokens))
        return rid

    def step(self):
        out = {}
        for rid, rec in list(self._live.items()):
            prompt, max_new, got = rec
            tok = 100 * len(prompt) + len(got)
            got.append(tok)
            out[rid] = [tok]
            if len(got) >= max_new:
                del self._live[rid]
                self._free.append(0)
                self._finished[rid] = got
        while self._free and self._waiting:
            rid, prompt, max_new = self._waiting.pop(0)
            self._free.pop()
            self._live[rid] = [prompt, max_new, []]
        return out

    def live(self):
        return len(self._live)

    def free_slots(self):
        return len(self._free)

    def queue_depth(self):
        return len(self._waiting)

    def is_finished(self, rid):
        return rid in self._finished

    def result(self, rid):
        return self._finished[rid]

    def cancel(self, rid):
        self._live.pop(rid, None)

    def take_waiting(self):
        out, self._waiting = self._waiting, []
        return out

    def stats(self):
        return {"live": len(self._live), "slots": self.slots,
                "occupancy": len(self._live) / self.slots,
                "queue_depth": len(self._waiting)}


@pytest.fixture
def basic_server():
    reg = MetricsRegistry()
    reg.counter("t_total", help="c").inc(2)
    h = reg.histogram("t_seconds", buckets=(0.1, 1.0))
    h.observe(0.5)
    ring = EventRing(capacity=16)
    ring.append("boot")
    rec = SpanRecorder()
    srv = server.ObservabilityServer(registry=reg, ring=ring,
                                     recorder=rec).start()
    try:
        yield srv, reg, ring, rec
    finally:
        srv.stop()


def test_all_endpoints_respond(basic_server):
    srv, *_ = basic_server
    for ep in server.ENDPOINTS:
        code, ctype, _ = _get(srv.url + ep)
        assert code == 200, ep
        want = "text/plain" if ep == "/metricsz" else "application/json"
        assert ctype.startswith(want), (ep, ctype)
    code, idx = _get_json(srv.url + "/")
    assert code == 200 and set(idx["endpoints"]) == set(server.ENDPOINTS)
    code, err = _get_json(srv.url + "/nope")
    assert code == 404 and "endpoints" in err


def test_metricsz_is_conformant_and_live(basic_server):
    srv, reg, *_ = basic_server
    _, _, body = _get(srv.url + "/metricsz")
    assert exporters.validate_prometheus_text(body.decode()) == []
    # LIVE registry, not a snapshot at attach time
    reg.counter("t_total").inc(5)
    _, _, body = _get(srv.url + "/metricsz")
    fams = exporters.parse_prometheus_text(body.decode())
    (name, labels, value), = fams["t_total"]["samples"]
    assert value == 7.0


def test_flightz_reflects_ring_and_filters(basic_server):
    srv, _, ring, _ = basic_server
    ring.append("failover", replica=1)
    ring.append("shed", queue_depth=3)
    code, fz = _get_json(srv.url + "/flightz")
    assert code == 200
    assert fz["total"] == 3 and fz["dropped"] == 0
    assert [e["kind"] for e in fz["events"]] == ["boot", "failover",
                                                "shed"]
    _, fz = _get_json(srv.url + "/flightz?kind=failover")
    assert [e["kind"] for e in fz["events"]] == ["failover"]
    assert fz["total"] == 3                  # header stays global


def test_tracez_index_and_record(basic_server):
    srv, _, _, rec = basic_server
    from apex_tpu.observability import tracing
    tid = tracing.new_trace_id("srvtest")
    root = rec.event("submit", trace_id=tid)
    rec.event("result", trace_id=tid, parent_id=root)
    code, tz = _get_json(srv.url + "/tracez")
    assert code == 200 and tid in tz["traces"]
    code, trec = _get_json(srv.url + f"/tracez?trace_id={tid}")
    assert code == 200
    assert exporters.validate_trace_record(trec) == []
    assert trec["span_count"] == 2
    code, _ = _get_json(srv.url + "/tracez?trace_id=unknown")
    assert code == 404


def test_healthz_turns_503_when_check_fails():
    flag = {"ok": True}
    srv = server.ObservabilityServer(
        registry=MetricsRegistry(),
        health={"custom": lambda: (flag["ok"], "detail here")}).start()
    try:
        code, hz = _get_json(srv.url + "/healthz")
        assert code == 200 and hz["status"] == "ok"
        flag["ok"] = False
        code, hz = _get_json(srv.url + "/healthz")
        assert code == 503 and hz["status"] == "unhealthy"
        assert hz["checks"]["custom"]["ok"] is False
    finally:
        srv.stop()


def test_statusz_isolates_raising_source():
    def boom():
        raise RuntimeError("seeded")

    srv = server.ObservabilityServer(
        registry=MetricsRegistry(),
        status={"good": lambda: {"x": 1}, "bad": boom}).start()
    try:
        code, st = _get_json(srv.url + "/statusz")
        assert code == 200
        assert st["good"] == {"x": 1}
        assert "seeded" in st["bad"]["error"]
    finally:
        srv.stop()


def test_serve_supervisor_wires_health_and_status():
    sup = RunSupervisor("srv_run", ring=EventRing(),
                        registry=MetricsRegistry())
    sup.observe_step(step=0, loss=1.0)
    srv = server.serve(supervisor=sup, registry=MetricsRegistry())
    try:
        code, st = _get_json(srv.url + "/statusz")
        assert st["run"]["run"] == "srv_run"
        code, hz = _get_json(srv.url + "/healthz")
        assert code == 200
        sup.observe_step(step=1, loss=float("nan"))
        code, hz = _get_json(srv.url + "/healthz")
        assert code == 503 and "nan" in hz["checks"]["run"]["detail"]
    finally:
        srv.stop()


def test_server_restarts_on_fresh_port(basic_server):
    srv, *_ = basic_server
    first = srv.port
    srv.stop()
    assert srv.url is None
    srv.start()
    assert srv.port is not None
    code, _, _ = _get(srv.url + "/healthz")
    assert code == 200


# -- the tentpole acceptance: live scrape of a running fleet ---------------

def test_live_scrape_of_running_fleet_during_traffic():
    """server.serve(fleet=...) attached to a Fleet actively stepping
    tenant-tagged traffic: every endpoint serves concurrently with the
    step loop, /metricsz stays exposition-conformant mid-flight,
    /tenantz serves a schema-shaped rollup mid-flight, /statusz's
    fleet numbers agree with Fleet.stats(), /flightz shows the fleet's
    ring, and /tracez returns a schema-clean kind: trace record for a
    real request."""
    ring = EventRing(capacity=256)
    fleet = Fleet([_StubReplica(slots=2) for _ in range(3)],
                  policy="least_loaded", max_queue=64,
                  step_workers=1, ring=ring)
    srv = server.serve(fleet=fleet)
    stop = threading.Event()
    errors = []

    def traffic():
        try:
            for wave in range(6):
                rids = [fleet.submit([1, 2, 3], max_new_tokens=6,
                                     deadline=30.0,
                                     tenant=("interactive" if i % 2
                                             else "batch"),
                                     priority=0 if i % 2 else 1)
                        for i in range(6)]
                while fleet.live():
                    fleet.step()
                for r in rids:
                    assert fleet.result(r) == [300 + j
                                               for j in range(6)]
        except Exception as e:          # noqa: BLE001
            errors.append(e)
        finally:
            stop.set()

    t = threading.Thread(target=traffic)
    t.start()
    scrapes = 0
    try:
        # at least one full scrape round runs regardless of how fast
        # the stub traffic drains (do-while: check stop AFTER a round)
        while True:
            for ep in server.ENDPOINTS:
                code, ctype, body = _get(srv.url + ep)
                assert code == 200, ep
                if ep == "/metricsz":
                    assert exporters.validate_prometheus_text(
                        body.decode()) == []
                if ep == "/tenantz":
                    # a schema-shaped rollup MID-FLIGHT, not only
                    # after the traffic drains
                    tz = json.loads(body)
                    assert tz["kind"] == "tenants"
                    assert "fleet" in tz["by_source"]
                scrapes += 1
            if stop.is_set():
                break
        t.join()
    finally:
        stop.set()
        t.join(timeout=10)
        srv.stop()
        fleet.close()
    assert not errors, errors
    assert scrapes >= len(server.ENDPOINTS)   # scraped during traffic

    # post-traffic consistency: scraped status == fleet.stats()
    srv2 = server.serve(fleet=fleet)
    try:
        _, st = _get_json(srv2.url + "/statusz")
        s = fleet.stats()
        assert st["fleet"]["submitted"] == s["submitted"] == 36
        assert st["fleet"]["finished"] == s["finished"] == 36
        assert st["fleet"]["goodput_tokens_per_s"] > 0
        assert st["fleet"]["slo"]["slo_attainment"] == 1.0
        # /flightz serves the FLEET's ring (explicit, not process)
        _, fz = _get_json(srv2.url + "/flightz")
        assert fz["total"] == ring.total
        # /tracez: one real request's flight record validates
        tid = fleet.request_trace_id(0)
        _, trec = _get_json(srv2.url + f"/tracez?trace_id={tid}")
        assert exporters.validate_trace_record(trec) == []
        names = [sp["name"] for sp in trec["spans"]]
        assert names[0] == "fleet_submit"
        assert "fleet_dispatch" in names and "fleet_result" in names
        # rid 0 was tagged tenant "batch": EVERY hop of its trace
        # carries the stamp (filtering by args.tenant yields the
        # tenant's complete story)
        assert all(sp.get("args", {}).get("tenant") == "batch"
                   for sp in trec["spans"])
        # /healthz: replicas check wired by serve(fleet=)
        code, hz = _get_json(srv2.url + "/healthz")
        assert code == 200 and hz["checks"]["replicas"]["ok"]
        # /tenantz: the per-tenant rollup of the tagged traffic,
        # exact under the sum-over-tenants rule (every request tagged)
        code, tz = _get_json(srv2.url + "/tenantz")
        assert code == 200
        assert tz["tenant_names"] == ["batch", "interactive"]
        tb = tz["by_source"]["fleet"]["tenants"]
        assert (tb["batch"]["submitted"]
                + tb["interactive"]["submitted"]) == 36
        assert tb["interactive"]["slo_attainment"] == 1.0
        assert tb["batch"]["finished"] == tb["batch"]["submitted"]
        code, tzf = _get_json(srv2.url + "/tenantz?tenant=batch")
        assert code == 200
        assert list(tzf["by_source"]["fleet"]["tenants"]) == ["batch"]
        code, _ = _get_json(srv2.url + "/tenantz?tenant=nope")
        assert code == 404
        # the fleet's record (per-tenant block included) is
        # schema-clean end to end
        rec = exporters.JsonlExporter.enrich(fleet.record())
        assert rec["schema_version"] == exporters.SCHEMA_VERSION
        assert exporters.validate_fleet_record(rec) == []
    finally:
        srv2.stop()


def test_ci_server_smoke_gate():
    """The tier-1 wiring of tests/ci/server_smoke.py (like the trend
    gate): the jax-free smoke script boots the server, scrapes all
    seven endpoints (incl. the /compilez
    ledger snapshot with a seeded retrace verdict, and the /tenantz
    empty shape + seeded per-tenant rollup), and validates exposition
    + JSON schemas."""
    import os
    import subprocess
    import sys
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    script = os.path.join(root, "tests", "ci", "server_smoke.py")
    r = subprocess.run([sys.executable, script], capture_output=True,
                       text=True, timeout=120)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "all 7 endpoints OK" in r.stdout


def test_compilez_live_ledger():
    """/compilez against the live process ledger: an instrumented jit
    call lands in the snapshot (entry, trace count, cache attribution
    column) and the ?entry= filter narrows/404s."""
    import jax.numpy as jnp
    from apex_tpu.observability import compilation

    led = compilation.CompilationLedger()
    f = compilation.instrumented_jit(
        lambda x: x * 2, "smoke.double", ledger=led,
        arg_names=("x",))
    f(jnp.ones((3,), jnp.float32))
    f(jnp.ones((4,), jnp.float32))       # shape retrace
    srv = server.ObservabilityServer(ledger=led).start()
    try:
        code, body = _get_json(srv.url + "/compilez")
        assert code == 200 and body["kind"] == "compilation"
        ent = body["entries"]["smoke.double"]
        assert ent["traces"] == 2 and ent["retraces"] == 1
        assert ent["last_retrace"]["culprit"] == "x"
        assert ent["compiles"] == 2
        assert ent["cache"]  # hit/miss/uncached tallies present
        code, body = _get_json(srv.url
                               + "/compilez?entry=smoke.double")
        assert code == 200 and list(body["entries"]) == ["smoke.double"]
        code, body = _get_json(srv.url + "/compilez?entry=nope")
        assert code == 404
    finally:
        srv.stop()
