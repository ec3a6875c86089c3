"""Serving SLO-feedback controller (fleet/autoscale.py) + the shared
recovery bookkeeping (fleet/recovery.py RecoveryLog) + fleet MTTR.

All jax-free: the controller reads tracker deltas and actuates host
knobs, so a stub replica + an injected tick clock make every timeline
exact — the same discipline as the breaker/retry tests in
test_fleet.py.  The end-to-end seeded chaos schedule (death + stall +
spike, baseline vs controller, plus the stub elastic-training run)
lives in tests/ci/chaos_smoke.py and is wired into tier-1 here by
subprocess, like the server_smoke gate."""

import os
import subprocess
import sys

import numpy as np
import pytest

from apex_tpu.fleet import (DEAD, AutoscaleConfig, FaultyReplica,
                            Fleet, FleetOverloaded, HealthConfig,
                            RecoveryLog, RetryPolicy, SloController)
from apex_tpu.fleet.recovery import (RECOVERY_ACTION_KINDS,
                                     RECOVERY_CAUSES, RECOVERY_ROLES)
from apex_tpu import observability as obs
from apex_tpu.observability import exporters
from apex_tpu.observability.exporters import (JsonlExporter,
                                              validate_recovery_record,
                                              validate_fleet_record,
                                              validate_telemetry_record)


class _Stub:
    """Scheduler-surface stub: one deterministic token per live
    request per step (test_fleet discipline) + the duck-typed
    ``set_window`` the controller's window actuator targets."""

    def __init__(self, slots=2, window=8):
        self.slots = slots
        self.window = window
        self.base_window = window
        self._free = list(range(slots))
        self._live = {}
        self._waiting = []
        self._finished = {}
        self._next_rid = 0

    def set_window(self, k):
        self.window = int(k)

    @staticmethod
    def expected_tokens(plen, max_new):
        return [100 * plen + j for j in range(max_new)]

    def _admit(self, rid, prompt, max_new):
        self._free.pop()
        self._live[rid] = [list(prompt), max_new, []]

    def add_request(self, prompt, max_new_tokens, eos_token_id=None,
                    seed=None, temperature=None):
        if not self._free:
            raise RuntimeError("no free slot")
        rid = self._next_rid
        self._next_rid += 1
        self._admit(rid, prompt, max_new_tokens)
        return rid

    def submit(self, prompt, max_new_tokens, eos_token_id=None,
               seed=None, temperature=None):
        if self._free and not self._waiting:
            return self.add_request(prompt, max_new_tokens)
        rid = self._next_rid
        self._next_rid += 1
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
            self._admit(rid, prompt, max_new)
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
        return list(self._finished[rid])

    def cancel(self, rid):
        for i, item in enumerate(self._waiting):
            if item[0] == rid:
                del self._waiting[i]
                return True
        if rid in self._live:
            del self._live[rid]
            self._free.append(0)
            return True
        return False

    def take_waiting(self):
        taken, self._waiting = self._waiting, []
        return taken

    def stats(self):
        return {"occupancy": len(self._live) / self.slots,
                "queue_depth": len(self._waiting)}


class _Clock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


def _fleet(n=2, slots=2, max_queue=64, clock=None, window=8, **kw):
    reps = [_Stub(slots=slots, window=window) for _ in range(n)]
    fl = Fleet(reps, policy="least_loaded", max_queue=max_queue,
               retry=RetryPolicy(max_attempts=8), step_workers=1,
               clock=clock, **kw)
    return fl, reps


def _drive(fl, ctrl, clock, *, waves, ticks, deadline=None,
           ctrl_every=2, max_new=4):
    """Seeded workload: ``waves[tick]`` submissions per tick; one
    controller tick every ``ctrl_every`` fleet steps; the clock
    advances exactly one unit per fleet step."""
    shed = 0
    rids = []
    for tick in range(ticks):
        for _ in range(waves.get(tick, 0)):
            try:
                rids.append(fl.submit([1, 2, 3],
                                      max_new_tokens=max_new,
                                      deadline=deadline))
            except FleetOverloaded:
                shed += 1
        fl.step()
        clock.t += 1.0
        if ctrl is not None and tick % ctrl_every == ctrl_every - 1:
            ctrl.tick()
    guard = 0
    while fl.live() and guard < 300:
        fl.step()
        clock.t += 1.0
        if ctrl is not None:
            ctrl.tick()
        guard += 1
    assert not fl.live()
    return rids, shed


# -- constants pinned across the stdlib/package boundary -----------------

def test_action_kinds_pinned_to_exporters():
    assert RECOVERY_ACTION_KINDS == exporters.RECOVERY_ACTION_KINDS
    assert RECOVERY_ROLES == exporters.RECOVERY_ROLES
    assert RECOVERY_CAUSES == exporters.RECOVERY_CAUSES


def test_recovery_log_rejects_negative_t_s_at_append():
    """The PR 11 gotcha guarded AT THE SOURCE: a log whose t0 predates
    the current clock (fleet/controller built before an injected tick
    clock was reset) fails at action() time with the remedy, instead
    of the finished record failing validate_recovery_record later."""
    t = {"v": 100.0}
    log = RecoveryLog("serving", "clockskew", clock=lambda: t["v"])
    t["v"] = 10.0                       # clock reset AFTER construction
    with pytest.raises(ValueError, match="[Rr]eset the clock"):
        log.action("undrain")
    # a healthy clock still appends
    t["v"] = 101.0
    ev = log.action("undrain")
    assert ev["t_s"] == pytest.approx(1.0)


# -- RecoveryLog bookkeeping ---------------------------------------------

def test_recovery_log_episode_action_mttr_accounting():
    clk = _Clock()
    ring = obs.EventRing(64)
    log = RecoveryLog("serving", "t", clock=clk, ring=ring)
    assert not log.in_flight
    log.open_episode("spike")
    log.open_episode("spike again")      # idempotent while open
    assert log.episodes == 1
    log.action("admission_tighten", max_queue_from=8, max_queue_to=4)
    clk.t = 3.0
    log.close_episode(mttr_s=3.0)
    assert not log.in_flight
    # relax OUTSIDE the episode: counted in the total, excluded from
    # the per-episode oscillation bound
    log.action("admission_relax", max_queue_from=4, max_queue_to=8)
    assert log.actions_total == 2
    assert log.max_actions_in_episode == 1
    assert log.mttr() == {"last": 3.0, "mean": 3.0, "count": 1}
    with pytest.raises(ValueError):
        log.action("reboot_the_universe")
    with pytest.raises(ValueError):
        RecoveryLog("mystery", "t")
    kinds = [ev["kind"] for ev in ring.snapshot()]
    assert kinds == ["recovery_started", "recovery_action",
                     "recovery_done", "recovery_action"]
    rec = JsonlExporter.enrich(log.record())
    assert validate_recovery_record(rec) == []
    assert validate_telemetry_record(rec) == []


def test_recovery_record_validator_rejects_mutations():
    log = RecoveryLog("training", "r")
    log.open_episode("death")
    log.action("world_shrink", world_from=8, world_to=4)
    log.close_episode(mttr_s=0.5)
    good = JsonlExporter.enrich(log.record(world=4, recoveries=1))
    assert validate_recovery_record(good) == []
    cases = {
        "unknown role": {"role": "parking"},
        "empty subject": {"subject": ""},
        "negative episodes": {"episodes": -1},
        "details exceed total": {"actions_total": 0},
        "max exceeds total": {"max_actions_in_episode": 99},
        "bad world": {"world": 0},
        "mttr inconsistent": {"mttr_s": {"last": None, "mean": None,
                                         "count": 3}},
        "mttr nan": {"mttr_s": {"last": float("nan"), "mean": 0.5,
                                "count": 1}},
    }
    for label, patch in cases.items():
        bad = {**good, **patch}
        assert validate_recovery_record(bad), label
    bad_action = dict(good)
    bad_action["actions"] = [dict(good["actions"][0], kind="reboot")]
    assert validate_recovery_record(bad_action)
    bad_ep = dict(good)
    bad_ep["actions"] = [dict(good["actions"][0], episode=7)]
    assert validate_recovery_record(bad_ep)


# -- controller behavior --------------------------------------------------

def test_stable_load_no_actuation():
    clk = _Clock()
    fl, _ = _fleet(clock=clk)
    ctrl = SloController(fl, AutoscaleConfig(), clock=clk)
    waves = {t: 1 for t in range(0, 40, 6)}     # well under capacity
    _drive(fl, ctrl, clk, waves=waves, ticks=48, deadline=30.0)
    rec = ctrl.record()
    assert rec["episodes"] == 0
    assert rec["actions_total"] == 0
    assert fl.max_queue == ctrl.base_max_queue
    assert JsonlExporter.enrich(rec) and \
        validate_recovery_record(JsonlExporter.enrich(rec)) == []


def test_spike_tightens_admission_then_relaxes_back():
    clk = _Clock()
    fl, _ = _fleet(max_queue=64, clock=clk)
    cfg = AutoscaleConfig(min_queue=4, backlog_factor=2.0,
                          cooldown_ticks=1, relax_after_ticks=4,
                          max_actions_per_episode=6)
    ctrl = SloController(fl, cfg, clock=clk)
    waves = {0: 1, 10: 30}                       # the spike
    _drive(fl, ctrl, clk, waves=waves, ticks=80, deadline=12.0)
    rec = ctrl.record()
    kinds = [a["kind"] for a in rec["actions"]]
    assert "admission_tighten" in kinds
    assert "admission_relax" in kinds
    # converged: bounded per episode, episode closed, admission back
    # at its base once the spike drained and health held
    assert rec["max_actions_in_episode"] <= cfg.max_actions_per_episode
    assert not rec["in_flight"]
    assert fl.max_queue == ctrl.base_max_queue
    assert validate_recovery_record(JsonlExporter.enrich(rec)) == []


def test_controller_beats_baseline_on_seeded_spike():
    """The acceptance pin at the unit level: identical seeded TWO-wave
    spike, deterministic stub service times — the controller must hold
    attainment above the no-controller baseline.  Wave 1 is absorbed
    by both (already admitted before any feedback can act); wave 2 is
    where feedback pays: it hits the pre-tightened admission bound and
    the doomed tail sheds at the door instead of expiring as misses.
    min_queue is sized to the makeable backlog (deadline / per-request
    service x slots), so goodput stays within a whisker of the
    baseline — the exact-parity pin under saturation lives in
    bench --chaos and tests/ci/chaos_smoke.py."""
    waves = {t: 1 for t in range(0, 90, 6)}
    waves[10] = waves.get(10, 0) + 24
    waves[50] = waves.get(50, 0) + 24

    def run(with_ctrl):
        clk = _Clock()
        fl, _ = _fleet(max_queue=64, clock=clk)
        ctrl = (SloController(
            fl, AutoscaleConfig(min_queue=12, backlog_factor=2.0,
                                cooldown_ticks=1,
                                relax_after_ticks=10,
                                max_actions_per_episode=6),
            clock=clk) if with_ctrl else None)
        _drive(fl, ctrl, clk, waves=waves, ticks=110, deadline=24.0,
               max_new=8)
        return fl.record()

    base, ctrl = run(False), run(True)
    assert base["slo_attainment"] is not None
    assert ctrl["slo_attainment"] > base["slo_attainment"]
    assert (ctrl["goodput_tokens_per_s"]
            >= 0.9 * base["goodput_tokens_per_s"])
    for rec in (base, ctrl):
        assert validate_fleet_record(JsonlExporter.enrich(rec)) == []


def test_undrain_is_first_resort_under_backlog():
    clk = _Clock()
    fl, _ = _fleet(n=3, clock=clk)
    fl.drain(2)
    assert fl.states()[2] == "drained"
    ctrl = SloController(fl, AutoscaleConfig(backlog_factor=1.0,
                                             cooldown_ticks=1),
                         clock=clk)
    # pile a backlog: 20 queued against 4 steppable slots
    for _ in range(20):
        fl.submit([1, 2, 3], max_new_tokens=4)
    fl.step()
    clk.t += 1.0
    acts = ctrl.tick()
    assert [a["kind"] for a in acts] == ["undrain"]
    assert fl.states()[2] == "healthy"
    # capacity came back BEFORE any admission tightening
    assert fl.max_queue == ctrl.base_max_queue


def test_cooldown_shortened_for_open_breaker_under_pressure():
    clk = _Clock()
    reps = [_Stub(slots=2), _Stub(slots=2)]
    sick = FaultyReplica(reps[0], raise_on_step=(0, None))
    fl = Fleet([sick, reps[1]], policy="least_loaded", max_queue=64,
               retry=RetryPolicy(max_attempts=8),
               health=HealthConfig(cooldown_steps=32,
                                   dead_consecutive=2),
               step_workers=1, clock=clk)
    ctrl = SloController(fl, AutoscaleConfig(backlog_factor=1.0,
                                             cooldown_ticks=1,
                                             probe_cooldown_steps=1),
                         clock=clk)
    for _ in range(12):
        fl.submit([1, 2, 3], max_new_tokens=4)
    # step until the breaker opens on the sick replica
    for _ in range(4):
        fl.step()
        clk.t += 1.0
    h = fl.health[0]
    assert h.circuit == "open" and h.cooldown_left > 1
    acts = ctrl.tick()
    assert any(a["kind"] == "cooldown_shorten" for a in acts)
    assert h.cooldown_left == 1
    ring_kinds = [ev["kind"] for ev in fl.ring.snapshot()]
    assert "cooldown_set" in ring_kinds


def test_window_actuated_when_other_knobs_exhausted():
    clk = _Clock()
    fl, reps = _fleet(max_queue=16, clock=clk, window=8)
    cfg = AutoscaleConfig(min_queue=16, backlog_factor=1.0,
                          cooldown_ticks=1, relax_after_ticks=2,
                          window_bounds=(2, 8),
                          max_actions_per_episode=8)
    ctrl = SloController(fl, cfg, clock=clk)
    # max_queue already at min (== min_queue), nothing drained, no
    # breaker open: the only knob left under backlog is the decode
    # window.  16 submits leave 8 queued past the 4 slots + 4
    # replica-queue seats after one dispatch tick.
    for _ in range(16):
        fl.submit([1, 2, 3], max_new_tokens=4)
    fl.step()
    clk.t += 1.0
    acts = ctrl.tick()
    assert [a["kind"] for a in acts] == ["window_shrink"]
    assert reps[0].window == 4
    # recovery grows it back toward the base window
    while fl.live():
        fl.step()
        clk.t += 1.0
    for _ in range(6):
        clk.t += 1.0
        ctrl.tick()
    assert any(a["kind"] == "window_grow"
               for a in ctrl.record()["actions"])
    assert reps[0].window == 8


def test_bounded_actuation_under_persistent_overload():
    """A hopeless overload (capacity can never meet the deadline) must
    not make the controller thrash: one episode, at most
    max_actions_per_episode actuations, then it stops and leaves the
    episode for a human."""
    clk = _Clock()
    fl, _ = _fleet(n=1, slots=1, max_queue=64, clock=clk)
    cfg = AutoscaleConfig(min_queue=2, backlog_factor=1.0,
                          cooldown_ticks=1, relax_after_ticks=50,
                          max_actions_per_episode=3)
    ctrl = SloController(fl, cfg, clock=clk)
    waves = {t: 3 for t in range(0, 60, 2)}     # 3x capacity forever
    _drive(fl, ctrl, clk, waves=waves, ticks=60, deadline=4.0)
    rec = ctrl.record()
    assert rec["episodes"] >= 1
    assert rec["max_actions_in_episode"] <= 3
    assert validate_recovery_record(JsonlExporter.enrich(rec)) == []


# -- fleet MTTR accounting ------------------------------------------------

def test_fleet_mttr_measures_failover_to_reclaimed_progress():
    clk = _Clock()
    stub = _Stub(slots=2)
    sick = FaultyReplica(stub, raise_on_step=(2, 3))
    fl = Fleet([sick, _Stub(slots=2)], policy="round_robin",
               max_queue=16, retry=RetryPolicy(max_attempts=8),
               step_workers=1, clock=clk)
    rids = [fl.submit([1, 2, 3], max_new_tokens=4) for _ in range(4)]
    assert fl.mttr() == {"last": None, "mean": None, "count": 0}
    guard = 0
    while fl.live() and guard < 100:
        fl.step()
        clk.t += 1.0
        guard += 1
    m = fl.mttr()
    assert m["count"] == 1
    # deterministic timeline: failover at the fault tick, re-dispatch
    # next tick into the survivor's (full) slots, first reclaimed
    # token one tick later -> exactly 2 ticks
    assert m["last"] == 2.0
    for r in rids:
        assert fl.result(r) == _Stub.expected_tokens(3, 4)
    kinds = [ev["kind"] for ev in fl.ring.snapshot()]
    assert "failover" in kinds and "recovery_done" in kinds
    rec = JsonlExporter.enrich(fl.record())
    assert validate_fleet_record(rec) == []
    assert rec["mttr"]["count"] == 1


def test_fleet_record_mttr_field_validated():
    good = {"kind": "fleet", "trace_id": "t", "replicas": 1,
            "policy": "p", "healthy": 1, "degraded": 0, "dead": 0,
            "queue_depth": 0, "submitted": 0, "finished": 0,
            "failed": 0, "shed": 0, "retries": 0, "failovers": 0,
            "drains": 0, "tokens": 0, "deadline_exceeded": 0,
            "tenants": {}, "tenants_dropped": 0,  # required
            "classes": {}, "preemptions": 0,      # required
            "mttr": {"last": None, "mean": None, "count": 0}}
    assert validate_fleet_record(JsonlExporter.enrich(good)) == []
    bad = dict(good, mttr={"last": -1.0, "mean": 1.0, "count": 1})
    assert validate_fleet_record(JsonlExporter.enrich(bad))
    bad2 = dict(good, mttr="fast")
    assert validate_fleet_record(JsonlExporter.enrich(bad2))


# -- recovering is degraded-but-live on /healthz --------------------------

def test_healthz_reports_recovering_not_503_during_world_shrink():
    clk = _Clock()
    reps = [FaultyReplica(_Stub(), raise_on_step=(0, None))]
    fl = Fleet(reps, step_workers=1, clock=clk,
               health=HealthConfig(dead_consecutive=1))
    fl.submit([1, 2], max_new_tokens=2)
    for _ in range(3):
        fl.step()
        clk.t += 1.0
    assert fl.states() == [DEAD]
    srv = obs.server.serve(fleet=fl, start=False)
    code, payload = srv.healthz()
    assert code == 503                      # dead fleet, no recovery
    fl.begin_recovery("intentional world shrink")
    code, payload = srv.healthz()
    assert code == 200                      # degraded-but-LIVE
    assert "recovering" in payload["checks"]["replicas"]["detail"]
    kinds = [ev["kind"] for ev in fl.ring.snapshot()]
    assert "fleet_recovery_begin" in kinds
    fl.end_recovery()
    code, _ = srv.healthz()
    assert code == 503                      # still dead, not handled
    assert fl.stats()["recovery_in_flight"] is False


# -- the tier-1 chaos gate ------------------------------------------------

def test_chaos_smoke_gate():
    script = os.path.join(os.path.dirname(__file__), "ci",
                          "chaos_smoke.py")
    proc = subprocess.run(
        [sys.executable, script], capture_output=True, text=True,
        timeout=240,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 0, (proc.stdout + proc.stderr)
    assert "all checks passed" in proc.stdout
