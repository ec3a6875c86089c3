"""Serving fleet: routing, health/breaker, drain, backpressure, and —
the pin that matters — failover EXACTNESS: a request reclaimed from a
replica killed mid-decode and restarted on a survivor must produce
token-for-token the output of an undisturbed single engine.

Two layers of coverage: the orchestration machinery (breaker
transitions, retry backoff, shed, drain, deadlines, watchdog) runs
against a jax-free stub replica wrapped by the seeded fault harness —
every schedule is exact and instant; the exactness and prefix-affinity
contracts run against real Engines on the tiny GPT config."""

import json

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from apex_tpu import models, serving
from apex_tpu.fleet import (DEAD, DEGRADED, DRAINED, DRAINING, HEALTHY,
                            FaultyReplica, Fleet, FleetOverloaded,
                            HealthConfig, LeastLoaded, PrefixAffinity,
                            ReplicaFault, RetryPolicy, RoundRobin,
                            make_policy)
from apex_tpu import observability as obs
from apex_tpu.observability.exporters import (JsonlExporter,
                                              validate_fleet_record,
                                              validate_telemetry_record,
                                              validate_trace_record)


# -- jax-free stub replica: the scheduler surface, deterministic tokens ---

class _StubReplica:
    """Minimal scheduler-surface replica: request k's token number j is
    ``100 * (len(prompt)) + j`` — content-free but fully deterministic,
    so restart-exactness holds by construction and the tests can focus
    on the orchestration."""

    def __init__(self, slots=2):
        self.slots = slots
        self._free = list(range(slots))
        self._live = {}                  # rid -> [prompt, max_new, done]
        self._waiting = []
        self._finished = {}
        self._next_rid = 0

    @staticmethod
    def expected(prompt, max_new):
        return [100 * len(prompt) + j for j in range(max_new)]

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
        self._waiting.append((rid, list(prompt), max_new_tokens,
                              eos_token_id, seed, temperature))
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
            rid, prompt, max_new, *_ = self._waiting.pop(0)
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
        return {"live": len(self._live), "slots": self.slots,
                "occupancy": len(self._live) / self.slots,
                "queue_depth": len(self._waiting),
                "free": len(self._free)}


def _drive(fl, limit=200):
    n = 0
    while fl.live():
        fl.step()
        n += 1
        assert n < limit, "fleet failed to converge"
    return n


# -- orchestration machinery (stub replicas) -------------------------------

def test_policies_route_and_validate():
    fl = Fleet([_StubReplica(), _StubReplica(), _StubReplica()],
               policy="round_robin", step_workers=1)
    for _ in range(3):
        fl.submit([1, 2], max_new_tokens=2)
    fl.step()
    # round robin spread one request per replica
    assert [r.live() + len(r._finished) for r in fl.replicas] == [1, 1, 1]

    # least-loaded prefers the emptiest replica
    a, b = _StubReplica(slots=4), _StubReplica(slots=4)
    fl2 = Fleet([a, b], policy="least_loaded", step_workers=1)
    a._free = [0]                        # a is 3/4 full
    a._live = {100 + i: [[1], 1, []] for i in range(3)}
    fl2.submit([1, 2, 3], max_new_tokens=1)
    fl2.step()
    assert b.live() + len(b._finished) == 1

    assert isinstance(make_policy("least_loaded"), LeastLoaded)
    assert isinstance(make_policy("round_robin"), RoundRobin)
    assert isinstance(make_policy("prefix_affinity"), PrefixAffinity)
    with pytest.raises(ValueError, match="unknown routing policy"):
        make_policy("wat")
    with pytest.raises(TypeError, match="select"):
        make_policy(object())
    with pytest.raises(ValueError, match="at least one replica"):
        Fleet([])


def test_results_exact_and_threaded_equals_serial():
    prompts = [[1] * (1 + i % 4) for i in range(8)]
    outs = []
    for workers in (1, 4):
        fl = Fleet([_StubReplica(), _StubReplica()],
                   step_workers=workers)
        rids = [fl.submit(p, max_new_tokens=3) for p in prompts]
        _drive(fl)
        outs.append([fl.result(r) for r in rids])
    assert outs[0] == outs[1]
    assert outs[0] == [_StubReplica.expected(p, 3) for p in prompts]


def test_backpressure_bounded_queue_sheds():
    """The fleet queue is BOUNDED: overflow raises the retriable
    FleetOverloaded instead of growing some _waiting list forever."""
    fl = Fleet([_StubReplica(slots=1)], max_queue=2,
               replica_queue_cap=0, step_workers=1,
               ring=obs.EventRing(capacity=64))
    fl.submit([1], max_new_tokens=50)
    fl.step()                            # occupy the only slot
    fl.submit([1, 2], max_new_tokens=1)  # queued (fleet level)
    fl.submit([1, 2, 3], max_new_tokens=1)
    with pytest.raises(FleetOverloaded) as ei:
        fl.submit([1, 2, 3, 4], max_new_tokens=1)
    assert ei.value.queue_depth == 2 and ei.value.max_queue == 2
    # sustained overload is ONE ring episode, not one event per
    # rejected submit — the counter carries the volume while the
    # bounded ring keeps room for breaker/failover history
    for _ in range(5):
        with pytest.raises(FleetOverloaded):
            fl.submit([9], max_new_tokens=1)
    s = fl.stats()
    assert s["shed"] == 6 and s["queue_depth"] == 2
    assert fl.metrics.counter("fleet_shed_total").value == 6.0
    assert len(fl.ring.snapshot("shed")) == 1
    # shed is retriable: capacity comes back as requests finish
    _drive(fl)
    fl.submit([1, 2, 3, 4], max_new_tokens=1)  # admitted: episode ends
    _drive(fl)
    assert fl.stats()["failed"] == 0
    # a NEW overload after an admitted submit is a NEW episode
    fl.submit([1], max_new_tokens=50)
    fl.step()
    fl.submit([1, 2], max_new_tokens=1)
    fl.submit([1, 2, 3], max_new_tokens=1)
    with pytest.raises(FleetOverloaded):
        fl.submit([7, 7], max_new_tokens=1)
    assert len(fl.ring.snapshot("shed")) == 2


def test_default_ring_resolves_per_append_across_set_ring_swap():
    """A fleet built WITHOUT an explicit ring follows obs.set_ring
    swaps: every producer (fleet events, breaker notes, injected
    faults) resolves the process ring per append, so one swap moves
    the WHOLE story to the new ring instead of splitting it."""
    rep = FaultyReplica(_StubReplica(), raise_on_step=(0, 1))
    fl = Fleet([rep, _StubReplica()], policy="round_robin",
               health=HealthConfig(dead_consecutive=1,
                                   cooldown_steps=100),
               retry=RetryPolicy(max_attempts=6, jitter=0.0),
               step_workers=1)
    fresh = obs.EventRing(capacity=64)
    prev = obs.set_ring(fresh)
    try:
        fl.submit([1, 2], max_new_tokens=2)
        _drive(fl)
        assert fl.stats()["failovers"] == 1
        kinds = {e["kind"] for e in fresh.snapshot()}
        assert {"fault_injected", "failover", "breaker_open"} <= kinds
    finally:
        obs.set_ring(prev)


def test_dispatch_retry_backoff_then_success():
    """Prefill faults burn attempts on an exponential step schedule
    (jitter 0 → exact), then the request lands and completes."""
    rep = FaultyReplica(_StubReplica(), raise_on_prefill=(0, None))
    fl = Fleet([rep], retry=RetryPolicy(max_attempts=5,
                                        base_delay_steps=1, backoff=2.0,
                                        jitter=0.0),
               step_workers=1)
    rid = fl.submit([1, 2], max_new_tokens=2)
    # prefill faults key off the wrapper's step counter, which only
    # advances when the replica is stepped; with no live work the fleet
    # never steps it, so the fault window is effectively permanent
    # until we lift it
    for _ in range(4):
        fl.step()
    assert fl.status(rid) == "queued"
    assert fl.stats()["retries"] >= 1
    # attempts 1..k fire at steps 1, 2, 4, 8 (backoff 2, no jitter)
    req = fl._pending[0]
    assert req.next_attempt_step > fl._step_no
    rep._raise_on_prefill = ()           # heal the replica
    _drive(fl, limit=40)
    assert fl.result(rid) == _StubReplica.expected([1, 2], 2)
    assert fl.metrics.counter("fleet_retries_total").value >= 1.0


def test_retry_exhaustion_fails_request():
    rep = FaultyReplica(_StubReplica(), raise_on_prefill=(0, None))
    fl = Fleet([rep], retry=RetryPolicy(max_attempts=2, jitter=0.0),
               step_workers=1)
    rid = fl.submit([1], max_new_tokens=1)
    for _ in range(6):
        fl.step()
    assert fl.status(rid) == "failed"
    with pytest.raises(RuntimeError, match="dispatch failed after 2"):
        fl.result(rid)
    assert fl.stats()["failed"] == 1
    # a shape-invalid request fails immediately, without blaming health
    class _Picky(_StubReplica):
        def submit(self, prompt, *a, **kw):
            raise ValueError("prompt length bad")
    fl2 = Fleet([_Picky()], step_workers=1)
    bad = fl2.submit([1] * 99, max_new_tokens=1)
    fl2.step()
    with pytest.raises(RuntimeError, match="rejected at dispatch"):
        fl2.result(bad)
    assert fl2.health[0].errors_total == 0


def test_circuit_breaker_dead_halfopen_recovery():
    """Two consecutive step faults open the breaker; the replica is
    not stepped during cooldown; the half-open probe closes it and the
    reclaimed request still finishes exactly."""
    rep = FaultyReplica(_StubReplica(), raise_on_step=(0, 2))
    fl = Fleet([rep],
               health=HealthConfig(dead_consecutive=2, cooldown_steps=4),
               retry=RetryPolicy(max_attempts=10, jitter=0.0),
               step_workers=1)
    rid = fl.submit([1, 2, 3], max_new_tokens=4)
    fl.step()                            # fault 1 -> failover, requeue
    assert fl.states()[0] != DEAD        # one error: not dead yet
    fl.step()                            # re-dispatch, fault 2 -> DEAD
    assert fl.states() == [DEAD]
    assert fl.health[0].circuit == "open"
    steps_before = rep.steps
    for _ in range(3):                   # cooldown: never stepped
        fl.step()
    assert rep.steps == steps_before
    assert fl.health[0].circuit == "open"
    fl.step()          # cooldown elapses -> half-open probe fires NOW
    assert rep.steps == steps_before + 1
    assert fl.health[0].circuit == "closed"   # clean probe closed it
    _drive(fl, limit=20)
    assert fl.states() == [HEALTHY]
    assert fl.result(rid) == _StubReplica.expected([1, 2, 3], 4)
    assert fl.stats()["failovers"] == 2


def test_half_open_probe_dispatches_despite_healthy_capacity():
    """Recovery must not starve: even when a healthy replica could
    absorb every request, the half-open replica still receives its
    one probe — otherwise it idles degraded forever and the fleet
    permanently runs at reduced capacity."""
    rep = FaultyReplica(_StubReplica(), raise_on_step=(0, 1))
    ok = _StubReplica(slots=8)
    fl = Fleet([rep, ok], policy="least_loaded",
               health=HealthConfig(dead_consecutive=1, cooldown_steps=2),
               retry=RetryPolicy(max_attempts=10, jitter=0.0),
               step_workers=1)
    rids = [fl.submit([1], max_new_tokens=2) for _ in range(2)]
    fl.step()                            # replica 0 raises once -> DEAD
    assert fl.states()[0] == DEAD
    recovered_at = None
    for i in range(10):                  # trickle: ok never saturates
        fl.submit([2, 3], max_new_tokens=1)
        fl.step()
        if fl.health[0].circuit == "closed":
            recovered_at = i
            break
    assert recovered_at is not None      # the probe DID dispatch
    _drive(fl, limit=40)
    assert fl.stats()["failed"] == 0
    assert all(fl.result(r) == _StubReplica.expected([1], 2)
               for r in rids)


def test_failed_probe_doubles_cooldown():
    rep = FaultyReplica(_StubReplica(), raise_on_step=(0, 3))
    fl = Fleet([rep],
               health=HealthConfig(dead_consecutive=2, cooldown_steps=2,
                                   cooldown_backoff=2.0),
               retry=RetryPolicy(max_attempts=20, jitter=0.0),
               step_workers=1)
    fl.submit([1], max_new_tokens=2)
    fl.step()
    fl.step()                            # 2 faults -> open, cooldown 2
    assert fl.health[0].circuit == "open"
    fl.step()                            # cooling
    fl.step()          # half-open this step; probe raises (3rd fault)
    assert fl.health[0].circuit == "open"
    assert fl.health[0]._cooldown == 4   # doubled
    _drive(fl, limit=40)                 # window over: recovers, finishes
    assert fl.stats()["finished"] == 1


def test_stall_watchdog_fails_over_silent_replica():
    """A stalled replica (returns {} without stepping — never raises)
    is caught by the no-progress watchdog and its work restarts on the
    survivor, exact."""
    stalled = FaultyReplica(_StubReplica(), stall=(0, None))
    ok = _StubReplica()
    fl = Fleet([stalled, ok], policy="round_robin",
               health=HealthConfig(stall_steps=3, dead_consecutive=2),
               retry=RetryPolicy(max_attempts=6, jitter=0.0),
               step_workers=1)
    rids = [fl.submit([1, 2], max_new_tokens=3) for _ in range(2)]
    _drive(fl, limit=60)
    assert all(fl.result(r) == _StubReplica.expected([1, 2], 3)
               for r in rids)
    assert fl.stats()["failovers"] >= 1
    assert fl.health[0].errors_total >= 1
    # drop_results is the same silence with internal progress — the
    # watchdog treats it identically
    dropper = FaultyReplica(_StubReplica(), drop_results=(0, None))
    fl2 = Fleet([dropper, _StubReplica()], policy="round_robin",
                health=HealthConfig(stall_steps=3, dead_consecutive=2),
                retry=RetryPolicy(max_attempts=6, jitter=0.0),
                step_workers=1)
    r2 = [fl2.submit([3], max_new_tokens=8) for _ in range(2)]
    _drive(fl2, limit=80)
    assert all(fl2.result(r) == _StubReplica.expected([3], 8)
               for r in r2)


def test_faulty_replica_arm_after_warmup_and_fleet_close():
    """arm() programs fault windows RELATIVE to the current step
    counter — 'die k steps from now', the post-warmup idiom — and
    Fleet.close() joins the worker pool without
    retiring the fleet."""
    rep = FaultyReplica(_StubReplica())
    fl = Fleet([rep, _StubReplica()], policy="round_robin",
               health=HealthConfig(dead_consecutive=2),
               retry=RetryPolicy(max_attempts=6, jitter=0.0))
    for _ in range(2):
        fl.submit([1], max_new_tokens=2)
    _drive(fl)                           # warmup: no faults fire
    assert rep.faults_fired == 0 and rep.steps >= 2
    base = rep.steps
    rep.arm(raise_on_step=(1, None))     # die 1 step from NOW
    assert rep._raise_on_step == ((base + 1, None),)
    rids = [fl.submit([1, 2], max_new_tokens=3) for _ in range(2)]
    _drive(fl, limit=80)
    assert rep.faults_fired >= 1
    assert all(fl.result(r) == _StubReplica.expected([1, 2], 3)
               for r in rids)
    with pytest.raises(TypeError, match="unknown fault kind"):
        rep.arm(explode=(0, None))
    rep.arm(raise_on_step=())            # clear the fault
    assert rep._raise_on_step == ()
    fl.close()                           # idempotent; step() revives
    fl.close()
    assert fl._pool is None
    fl.undrain(0)                        # fresh record for replica 0
    r = fl.submit([3], max_new_tokens=1)
    _drive(fl, limit=20)
    assert fl.result(r) == _StubReplica.expected([3], 1)


def test_drain_reenqueues_waiting_finishes_inflight():
    a, b = _StubReplica(slots=1), _StubReplica(slots=1)
    fl = Fleet([a, b], policy="round_robin", replica_queue_cap=1,
               step_workers=1)
    rids = [fl.submit([1] * (i + 1), max_new_tokens=4)
            for i in range(4)]
    fl.step()   # a: slot+queue, b: slot+queue
    assert a.queue_depth() == 1 and b.queue_depth() == 1
    fl.drain(0)
    # a's queued request went back to the fleet; its in-flight stays
    assert a.queue_depth() == 0
    assert fl.states()[0] == DRAINING and a.live() == 1
    assert fl.stats()["drains"] == 1
    _drive(fl, limit=60)
    assert fl.states()[0] == DRAINED
    for i, r in enumerate(rids):
        assert fl.result(r) == _StubReplica.expected([1] * (i + 1), 4)
    # drained replicas take no new work...
    r5 = fl.submit([9], max_new_tokens=1)
    _drive(fl, limit=20)
    assert len(a._finished) == 1         # only its pre-drain request
    # ...until re-enlisted
    fl.undrain(0)
    assert fl.states()[0] == HEALTHY
    fl.submit([8], max_new_tokens=1)
    fl.submit([7], max_new_tokens=1)
    _drive(fl, limit=20)
    assert fl.stats()["failed"] == 0 and fl.result(r5) == [100]


def test_deadline_exceeded_fails_pending_and_inflight():
    t = [0.0]
    stub = _StubReplica(slots=2)
    fl = Fleet([stub], clock=lambda: t[0],
               replica_queue_cap=0, step_workers=1,
               ring=obs.EventRing(capacity=64))
    slow = fl.submit([1], max_new_tokens=100)
    fl.step()                            # occupies slot 0
    # submission order: `inflight` grabs the last slot, `queued` stays
    # in the fleet queue — one deadline fires in each state
    inflight = fl.submit([1, 2, 3], max_new_tokens=200, deadline=8.0)
    queued = fl.submit([1, 2], max_new_tokens=1, deadline=5.0)
    with pytest.raises(ValueError, match="deadline"):
        fl.submit([1], max_new_tokens=1, deadline=0.0)
    fl.step()
    assert fl.status(inflight) == "inflight"
    assert fl.status(queued) == "queued"
    t[0] = 6.0                           # past queued's deadline
    fl.step()
    assert fl.status(queued) == "failed"
    with pytest.raises(RuntimeError, match="deadline exceeded"):
        fl.result(queued)
    t[0] = 9.0                           # past inflight's deadline
    fl.step()
    assert fl.status(inflight) == "failed"
    assert stub.live() == 1              # cancelled off the replica
    assert fl.stats()["deadline_exceeded"] == 2
    assert fl.status(slow) == "inflight"  # no deadline: untouched
    # ring events aggregate per sweep (one per _check_deadlines pass
    # that expired anything), with the counter carrying the volume —
    # a deadline storm must not wheel the ring
    evs = fl.ring.snapshot("deadline_exceeded")
    assert len(evs) == 2                 # two sweeps expired something
    assert [e["count"] for e in evs] == [1, 1]
    assert evs[0]["rids"] == [queued] and evs[1]["rids"] == [inflight]
    with pytest.raises(KeyError):
        fl.status(12345)


def test_prefix_owner_longest_match_on_stub():
    fl = Fleet([_StubReplica(), _StubReplica()], step_workers=1)
    fl._prefix_map[(1, 2)] = 0
    fl._prefix_map[(1, 2, 3)] = 1
    assert fl.prefix_owner([1, 2, 3, 4]) == 1    # longest wins
    assert fl.prefix_owner([1, 2, 9]) == 0
    assert fl.prefix_owner([2, 1]) is None


def test_fleet_record_schema_and_gauges():
    fl = Fleet([_StubReplica(), _StubReplica()], step_workers=1)
    rids = [fl.submit([1, 2], max_new_tokens=2) for _ in range(3)]
    _drive(fl)
    rec = JsonlExporter.enrich(fl.record())
    assert validate_fleet_record(rec) == []
    assert validate_telemetry_record(rec) == []   # kind-dispatch
    assert rec["finished"] == 3 and rec["replicas"] == 2
    # mutations the validator must catch
    assert validate_fleet_record({**rec, "kind": "wat"})
    assert validate_fleet_record({**rec, "policy": ""})
    assert validate_fleet_record({**rec, "failovers": -1})
    assert validate_fleet_record({**rec, "healthy": 3})   # > replicas
    assert validate_fleet_record({**rec, "finished": 9})  # > submitted
    assert validate_fleet_record(
        {k: v for k, v in rec.items() if k != "shed"})
    # trace_id is required: the join key to the request traces
    assert any("trace_id" in e for e in validate_fleet_record(
        {k: v for k, v in rec.items() if k != "trace_id"}))
    # a malformed schema_version reports, never raises
    assert validate_fleet_record({**rec, "schema_version": None})
    assert validate_fleet_record({**rec, "schema_version": "2"})
    # per-replica labeled gauges exist and carry the final state
    st = fl.metrics.gauge("fleet_replica_state_code")
    assert set(st.children()) == {(("replica", "0"),),
                                  (("replica", "1"),)}
    assert fl.metrics.gauge("fleet_queue_depth").value == 0.0
    assert fl.metrics.counter("fleet_finished_total").value == 3.0
    assert len(rids) == 3


# -- real engines: exactness + prefix affinity -----------------------------

def _gpt(seed=0):
    m = models.GPT(models.GPTConfig(vocab_size=64, block_size=24,
                                    n_layer=2, n_head=4, n_embd=32,
                                    dropout=0.0, n_kv_head=2))
    params, _ = m.init(jax.random.PRNGKey(seed))
    return m, params


def _solo(m, params, prompt, n):
    buf = jnp.zeros((1, 24), jnp.int32).at[0, :len(prompt)].set(
        jnp.asarray(prompt))
    out, flen = m.generate_cached(params, buf, len(prompt), n)
    return list(np.asarray(out[0, len(prompt):int(flen[0])]))


def test_fleet_of_engines_matches_solo_decoding():
    m, params = _gpt()
    fl = Fleet([serving.Engine(m, params, slots=2, buf_len=24)
                for _ in range(2)], policy="least_loaded")
    rng = np.random.RandomState(0)
    prompts = [list(rng.randint(0, 64, int(rng.randint(3, 9))))
               for _ in range(5)]
    rids = [fl.submit(p, max_new_tokens=6) for p in prompts]
    _drive(fl)
    for r, p in zip(rids, prompts):
        assert fl.result(r) == _solo(m, params, p, 6)
    s = fl.stats()
    assert s["finished"] == 5 and s["failed"] == 0
    assert s["healthy"] == 2


def test_failover_exactness_replica_killed_mid_decode():
    """THE acceptance pin: a seeded fault kills replica 0 after its
    3rd step — mid-decode for whatever it was running.  Every accepted
    request's final tokens must be identical to an undisturbed
    single-engine run (same prompts, same seeds)."""
    m, params = _gpt()
    rng = np.random.RandomState(1)
    prompts = [list(rng.randint(0, 64, int(rng.randint(3, 9))))
               for _ in range(6)]

    # undisturbed single engine, the ground truth
    single = serving.Engine(m, params, slots=2, buf_len=24)
    expected = {}
    srids = [single.submit(p, max_new_tokens=7) for p in prompts]
    while single.live() or single.queue_depth():
        single.step()
    for r, p in zip(srids, prompts):
        expected[tuple(p)] = single.result(r)
        assert single.result(r) == _solo(m, params, p, 7)

    bad = FaultyReplica(serving.Engine(m, params, slots=2, buf_len=24),
                        raise_on_step=(3, None))
    fl = Fleet([bad, serving.Engine(m, params, slots=2, buf_len=24)],
               policy="round_robin",
               health=HealthConfig(dead_consecutive=2, cooldown_steps=50),
               retry=RetryPolicy(max_attempts=6, jitter=0.0))
    rids = [fl.submit(p, max_new_tokens=7) for p in prompts]
    _drive(fl, limit=300)
    s = fl.stats()
    assert s["failovers"] >= 1            # the fault actually fired
    assert s["failed"] == 0               # ...and nobody was lost
    assert s["dead"] == 1                 # breaker opened, stayed open
    for r, p in zip(rids, prompts):
        assert fl.result(r) == expected[tuple(p)]


def test_failover_exactness_paged_replicas():
    """PR 17: the failover pin holds through the paged engine — a
    block-pool replica killed mid-decode hands its requests to a
    paged survivor, and every result() is token-for-token the
    undisturbed single-PagedEngine run (greedy AND explicitly-seeded
    sampled: the stream is request-intrinsic, never pool-layout-
    dependent)."""
    m, params = _gpt(4)
    rng = np.random.RandomState(4)
    prompts = [list(rng.randint(0, 64, int(rng.randint(3, 9))))
               for _ in range(6)]

    def paged_engine():
        return serving.PagedEngine(m, params, slots=2, buf_len=24,
                                   block_size=8, window=2,
                                   temperature=0.8, top_k=8,
                                   rng=jax.random.PRNGKey(7))

    # half greedy (temperature=0 override), half seeded-sampled
    kws = [dict(temperature=0.0) if i % 2 == 0 else dict(seed=100 + i)
           for i in range(len(prompts))]
    single = paged_engine()
    srids = [single.submit(p, max_new_tokens=7, **kw)
             for p, kw in zip(prompts, kws)]
    while single.live() or single.queue_depth():
        single.step()
    expected = [single.result(r) for r in srids]
    for toks, p, kw in zip(expected, prompts, kws):
        if kw.get("temperature") == 0.0:
            assert toks == _solo(m, params, p, 7)

    bad = FaultyReplica(paged_engine(), raise_on_step=(3, None))
    fl = Fleet([bad, paged_engine()], policy="round_robin",
               health=HealthConfig(dead_consecutive=2,
                                   cooldown_steps=50),
               retry=RetryPolicy(max_attempts=6, jitter=0.0))
    rids = [fl.submit(p, max_new_tokens=7, **kw)
            for p, kw in zip(prompts, kws)]
    _drive(fl, limit=300)
    s = fl.stats()
    assert s["failovers"] >= 1            # the fault actually fired
    assert s["failed"] == 0
    assert [fl.result(r) for r in rids] == expected


def test_failover_exactness_sampled_with_explicit_seeds():
    """Same pin through the sampled tick: explicit seeds make the
    stream request-intrinsic, so a failed-over sampled request
    re-draws exactly its single-engine tokens."""
    m, params = _gpt(2)
    rng = np.random.RandomState(2)
    prompts = [list(rng.randint(0, 64, 5)) for _ in range(4)]

    def sampled_engine():
        return serving.Engine(m, params, slots=2, buf_len=24,
                              temperature=0.8, top_k=8,
                              rng=jax.random.PRNGKey(7))

    single = sampled_engine()
    srids = [single.submit(p, max_new_tokens=6, seed=100 + i)
             for i, p in enumerate(prompts)]
    while single.live() or single.queue_depth():
        single.step()
    expected = [single.result(r) for r in srids]

    bad = FaultyReplica(sampled_engine(), raise_on_step=(2, None))
    fl = Fleet([bad, sampled_engine()], policy="round_robin",
               health=HealthConfig(dead_consecutive=2,
                                   cooldown_steps=50),
               retry=RetryPolicy(max_attempts=6, jitter=0.0))
    rids = [fl.submit(p, max_new_tokens=6, seed=100 + i)
            for i, p in enumerate(prompts)]
    _drive(fl, limit=300)
    assert fl.stats()["failovers"] >= 1
    assert [fl.result(r) for r in rids] == expected


def test_prefix_affinity_routes_to_owner_and_splices():
    m, params = _gpt()
    rng = np.random.RandomState(3)
    prefix = list(rng.randint(0, 64, 6))

    def eng():
        return serving.Engine(m, params, slots=2, buf_len=24,
                              prefix_pool=1)

    fl = Fleet([eng(), eng()], policy="prefix_affinity")
    owner = fl.register_prefix(prefix, replica=1)
    assert owner == 1
    suffix = list(rng.randint(0, 64, 4))
    rid = fl.submit(prefix + suffix, max_new_tokens=5)
    other = fl.submit(list(rng.randint(0, 64, 5)), max_new_tokens=5)
    _drive(fl)
    # the matching prompt landed on the owner and admitted by splice
    assert fl.replicas[1].prefix_hits == 1
    assert fl.replicas[0].prefix_hits == 0
    assert fl.result(rid) == _solo(m, params, prefix + suffix, 5)
    assert fl.result(other) == _solo(
        m, params, fl._results[other].prompt, 5)


def test_engine_queue_bookkeeping_under_shed_drain_reenqueue():
    """Satellite pin: engine_queue_depth (gauge) and
    stats()['queue_depth'] stay correct through every fleet-era queue
    mutation — submit-past-capacity, take_waiting (drain/failover
    re-enqueue), cancel of a queued request, and re-submission onto
    another replica."""
    m, params = _gpt()

    def gauge(e):
        return e.metrics.gauge("engine_queue_depth").value

    a = serving.Engine(m, params, slots=1, buf_len=24)
    b = serving.Engine(m, params, slots=1, buf_len=24)
    rng = np.random.RandomState(4)
    p = [list(rng.randint(0, 64, 4)) for _ in range(4)]
    a.submit(p[0], max_new_tokens=3)     # direct admit
    q1 = a.submit(p[1], max_new_tokens=3)
    a.submit(p[2], max_new_tokens=3)
    assert a.stats()["queue_depth"] == 2 and gauge(a) == 2.0
    # cancel one queued request
    assert a.cancel(q1)
    assert a.stats()["queue_depth"] == 1 and gauge(a) == 1.0
    # drain-style take: the queue empties and the gauge follows
    taken = a.take_waiting()
    assert [t[0] for t in taken] == [a._next_rid - 1]
    assert a.stats()["queue_depth"] == 0 and gauge(a) == 0.0
    # re-enqueue the taken request onto ANOTHER replica
    b.submit(p[3], max_new_tokens=3)     # occupy b's slot
    rb = b.submit(taken[0][1], taken[0][2], taken[0][3])
    assert b.stats()["queue_depth"] == 1 and gauge(b) == 1.0
    while b.live() or b.queue_depth():
        b.step()
    assert gauge(b) == 0.0
    assert b.result(rb) == _solo(m, params, taken[0][1], 3)
    # cancel a LIVE request: slot frees, the engine stays consistent
    while a.live() or a.queue_depth():   # finish a's original request
        a.step()
    live_rid = a.submit(p[0], max_new_tokens=5)
    assert a.cancel(live_rid) and a.live() == 0
    assert not a.cancel(live_rid)        # unknown now
    r2 = a.submit(p[1], max_new_tokens=3)
    while a.live() or a.queue_depth():
        a.step()
    assert a.result(r2) == _solo(m, params, p[1], 3)


def test_cancel_frees_slot_and_queued_requests_still_run():
    """cancel() on a full engine must not strand the waiting queue:
    step() admits the queued work even though no slot is live."""
    m, params = _gpt()
    e = serving.Engine(m, params, slots=1, buf_len=24)
    rng = np.random.RandomState(5)
    pa, pb = list(rng.randint(0, 64, 4)), list(rng.randint(0, 64, 5))
    ra = e.submit(pa, max_new_tokens=4)
    rb = e.submit(pb, max_new_tokens=4)
    assert e.cancel(ra)
    assert e.live() == 0 and e.queue_depth() == 1
    while e.live() or e.queue_depth():
        e.step()
    assert e.result(rb) == _solo(m, params, pb, 4)
    with pytest.raises(KeyError):
        e.result(ra)                     # cancelled: no result ever


# -- flight recorder: per-request distributed tracing (PR 6) ---------------

def test_failover_trace_reconstructs_causal_chain(tmp_path):
    """THE flight-recorder acceptance pin: a seeded mid-run replica
    death (``FaultyReplica.raise_on_step``) produces ONE trace whose
    spans reconstruct the request's full causal chain — submit, route,
    dispatch, fault, reclaim, re-dispatch on the survivor, result —
    each hop parenting on the previous one, schema-valid as a
    ``kind: trace`` record; the injected fault, the failover, and the
    breaker transition it provoked sit in causal order in the event
    ring, and the ring is dumped to ``flight_dump_path`` the moment
    the replica fails."""
    ring = obs.EventRing(capacity=64)
    rec = obs.SpanRecorder()
    prev = obs.set_recorder(rec)
    dump = str(tmp_path / "flight.jsonl")
    try:
        bad = FaultyReplica(_StubReplica(), raise_on_step=(2, None),
                            ring=ring)
        fl = Fleet([bad, _StubReplica()], policy="round_robin",
                   health=HealthConfig(dead_consecutive=1,
                                       cooldown_steps=100),
                   retry=RetryPolicy(max_attempts=6, jitter=0.0),
                   step_workers=1, ring=ring, flight_dump_path=dump)
        r0 = fl.submit([1, 2, 3], max_new_tokens=6)
        r1 = fl.submit([4, 5], max_new_tokens=3)
        _drive(fl)

        # failover happened and exactness held regardless
        assert fl.stats()["failovers"] == 1
        assert fl.result(r0) == _StubReplica.expected([1, 2, 3], 6)
        assert fl.result(r1) == _StubReplica.expected([4, 5], 3)

        # the faulted request's trace, span by span
        evs = rec.trace(fl.request_trace_id(r0))
        names = [e["name"] for e in evs]
        assert names == ["fleet_submit", "fleet_route",
                         "fleet_dispatch", "fleet_fault",
                         "fleet_reclaim", "fleet_route",
                         "fleet_dispatch", "fleet_result"]
        # one unbroken causal chain: every hop parents on the previous
        assert "parent_id" not in evs[0]          # submit is the root
        for prev_ev, ev in zip(evs, evs[1:]):
            assert ev["parent_id"] == prev_ev["span_id"]
        args = [e.get("args", {}) for e in evs]
        assert args[1]["replica"] == 0            # routed to the bad one
        assert args[1]["policy"] == "round_robin"
        assert "decision" in args[1]              # router said why
        assert args[2]["replica"] == 0
        assert args[3]["replica"] == 0            # the fault hop
        assert "injected step fault" in args[3]["reason"]
        assert args[4]["restarts"] == 1           # reclaimed once
        assert args[5]["replica"] == 1            # survivor re-route
        assert args[6]["replica"] == 1
        assert args[7]["tokens"] == 6 and args[7]["restarts"] == 1

        # the undisturbed request's trace has no failure hop
        evs1 = rec.trace(fl.request_trace_id(r1))
        assert [e["name"] for e in evs1] == [
            "fleet_submit", "fleet_route", "fleet_dispatch",
            "fleet_result"]
        assert evs1[1]["args"]["replica"] == 1

        # schema-valid kind: trace records, kind-dispatched
        for r in (r0, r1):
            tr = JsonlExporter.enrich(fl.trace_record(r))
            assert validate_trace_record(tr) == []
            assert validate_telemetry_record(tr) == []
        # fleet record cross-references the fleet-run trace id
        frec = JsonlExporter.enrich(fl.record())
        assert validate_fleet_record(frec) == []
        assert frec["trace_id"] == fl.trace_id
        assert fl.request_trace_id(r0).startswith(fl.trace_id + "/r")

        # the event ring holds the post-mortem story in causal order:
        # injected fault -> failover -> breaker open
        kinds = [e["kind"] for e in ring.snapshot()]
        for k in ("fault_injected", "failover", "breaker_open"):
            assert k in kinds, kinds
        assert kinds.index("fault_injected") < kinds.index("failover")
        fo = ring.snapshot("failover")[0]
        assert fo["replica"] == 0 and fo["reclaimed"] == 1
        assert "injected step fault" in fo["reason"]
        # breaker events carry the SAME (int) replica join key as the
        # fleet's own events — a post-mortem groups one replica's
        # story with ev["replica"] == i across both producers
        bo = ring.snapshot("breaker_open")[0]
        assert bo["replica"] == 0

        # ...and was dumped the moment the replica failed
        with open(dump) as f:
            lines = [json.loads(ln) for ln in f]
        assert lines[0]["kind"] == "flight_ring"
        assert lines[0]["dropped"] == 0
        assert any(ln["kind"] == "fault_injected" for ln in lines[1:])
    finally:
        obs.set_recorder(prev)


def test_traced_fleet_step_workers_threads_keep_span_parentage():
    """Satellite 1 at the fleet level: with ``step_workers=2`` the
    replica step dispatches overlap on pool workers, and worker-thread
    spans (window decode) must nest under their OWN replica's
    ``fleet_replica_step`` span in the fleet trace — never under
    another worker's span, never inside a request's lifecycle trace
    (the PR 1 recorder interleaved exactly here)."""
    m, params = _gpt()
    rec = obs.SpanRecorder()
    prev = obs.set_recorder(rec)
    try:
        with Fleet([serving.Engine(m, params, slots=2, buf_len=24)
                    for _ in range(2)], policy="least_loaded",
                   step_workers=2) as fl:
            rng = np.random.RandomState(7)
            prompts = [list(rng.randint(0, 64, 5)) for _ in range(4)]
            rids = [fl.submit(p, max_new_tokens=5) for p in prompts]
            _drive(fl)
            for r, p in zip(rids, prompts):
                assert fl.result(r) == _solo(m, params, p, 5)
            for r in rids:
                evs = rec.trace(fl.request_trace_id(r))
                names = [e["name"] for e in evs]
                assert names[0] == "fleet_submit"
                assert names[-1] == "fleet_result"
                d = evs[names.index("fleet_dispatch")]
                # the engine admission hop (prefill span or queue
                # event) recorded under the dispatch activation
                eng = [e for e in evs if e["name"] in
                       ("engine_prefill", "engine_queue")]
                assert eng and all(e["parent_id"] == d["span_id"]
                                   for e in eng)
                # closed under parentage: no span adopted a foreign
                # parent
                ids = {e["span_id"] for e in evs}
                assert all(e["parent_id"] in ids for e in evs
                           if "parent_id" in e)
                assert validate_trace_record(JsonlExporter.enrich(
                    fl.trace_record(r))) == []
            # fleet trace: every window-decode span nests under a
            # fleet_replica_step span recorded on the SAME worker
            # thread with the replica label
            fevs = rec.trace(fl.trace_id)
            steps = {e["span_id"]: e for e in fevs
                     if e["name"] == "fleet_replica_step"}
            decodes = [e for e in fevs
                       if e["name"] == "engine_window_decode"]
            assert steps and decodes
            for e in decodes:
                assert e["parent_id"] in steps
                assert e["tid"] == steps[e["parent_id"]]["tid"]
            # request lifecycle events never leak into the fleet trace
            assert not [e for e in fevs
                        if e["name"].startswith("fleet_sub")]
    finally:
        obs.set_recorder(prev)


# -- SLO / goodput accounting (PR 10) -------------------------------------

def test_slo_goodput_counts_only_within_deadline_tokens():
    """Goodput = tokens from requests that finished within their
    deadline: a pre-expired request's would-be tokens are excluded,
    attainment reflects the miss, and the deadline-sweep aggregate
    (count + first rids) surfaces through stats()/record() — not only
    the flight ring."""
    t = [0.0]
    fl = Fleet([_StubReplica(slots=4)], clock=lambda: t[0],
               step_workers=1, ring=obs.EventRing(capacity=64))
    ok1 = fl.submit([1, 2], max_new_tokens=3, deadline=100.0)
    ok2 = fl.submit([1, 2], max_new_tokens=3, deadline=100.0)
    free = fl.submit([1, 2], max_new_tokens=3)          # no SLO
    hopeless = fl.submit([1, 2], max_new_tokens=3, deadline=4.0)
    t[0] = 5.0                         # hopeless expires on first sweep
    steps = 0
    while fl.live():
        fl.step()
        t[0] += 1.0
        steps += 1
        assert steps < 50
    assert fl.status(hopeless) == "failed"
    s = fl.stats()
    # 2 of 3 deadlined requests resolved in time
    assert s["slo"]["with_deadline"] == 3
    assert s["slo"]["within_deadline"] == 2
    assert s["slo"]["slo_attainment"] == pytest.approx(2 / 3)
    # goodput: the two deadlined finishers + the no-SLO request
    assert s["slo"]["goodput_tokens"] == 9
    assert s["tokens_generated"] == 9
    assert s["goodput_tokens_per_s"] > 0
    # the sweep aggregate matches the ring event
    assert s["deadline_exceeded"] == 1
    assert s["deadline_last_sweep"]["count"] == 1
    assert s["deadline_last_sweep"]["rids"] == [hopeless]
    (ev,) = fl.ring.snapshot("deadline_exceeded")
    assert ev["count"] == 1 and ev["rids"] == [hopeless]
    # registry metrics mirror the fleet-local numbers
    assert fl.metrics.get("fleet_goodput_tokens_total").value == 9
    assert fl.metrics.get("fleet_slo_miss_total").value == 1
    assert fl.metrics.get("fleet_slo_attainment").value == \
        pytest.approx(2 / 3)
    # result() for the winners is unaffected
    assert fl.result(ok1) == _StubReplica.expected([1, 2], 3)
    assert fl.result(ok2) == _StubReplica.expected([1, 2], 3)
    assert fl.result(free) == _StubReplica.expected([1, 2], 3)


def test_fleet_record_carries_slo_fields_and_validator_pins_them():
    t = [0.0]
    fl = Fleet([_StubReplica(slots=2)], clock=lambda: t[0],
               step_workers=1, ring=obs.EventRing(capacity=64))
    fl.submit([1, 2], max_new_tokens=2, deadline=50.0)
    while fl.live():
        fl.step()
        t[0] += 1.0
    rec = JsonlExporter.enrich(fl.record())
    assert validate_fleet_record(rec) == []
    assert rec["goodput_tokens_per_s"] > 0
    assert rec["slo_attainment"] == 1.0
    assert rec["tokens_within_slo"] == 2
    assert rec["deadline_exceeded"] == 0
    assert rec["deadline_last_sweep"] == {"count": 0, "rids": [],
                                          "fleet_step": None}
    # mutations the validator must catch
    assert validate_fleet_record({**rec, "goodput_tokens_per_s": -1})
    assert validate_fleet_record({**rec, "slo_attainment": 1.5})
    assert validate_fleet_record({**rec, "tokens_within_slo": -2})
    assert validate_fleet_record(
        {**rec, "tokens_within_slo": rec["tokens"] + 1})
    assert validate_fleet_record({**rec, "deadline_exceeded": -1})
    assert validate_fleet_record(
        {**rec, "deadline_last_sweep": {"count": 0, "rids": [1, 2],
                                        "fleet_step": None}})
    assert validate_fleet_record(
        {**rec, "deadline_last_sweep": "yesterday"})
    # null attainment (no deadlined request resolved yet) is valid
    assert validate_fleet_record({**rec, "slo_attainment": None}) == []
    # archived records WITHOUT the optional fields stay clean
    stripped = {k: v for k, v in rec.items()
                if k not in ("goodput_tokens_per_s", "slo_attainment",
                             "tokens_within_slo", "deadline_exceeded",
                             "deadline_last_sweep")}
    assert validate_fleet_record(stripped) == []


def test_queue_wait_service_split_matches_trace_spans():
    """The SLO tracker's queue-wait/service split is fed at the same
    instants the request's trace spans record — so the split derived
    from the kind: trace record (fleet.slo.split_from_trace) must
    agree with the tracker's histograms.  One replica, one slot, two
    requests: the second genuinely queues behind the first."""
    from apex_tpu.fleet import slo as fleet_slo

    rec = obs.SpanRecorder()
    prev = obs.set_recorder(rec)
    try:
        fl = Fleet([_StubReplica(slots=1)], replica_queue_cap=0,
                   step_workers=1, ring=obs.EventRing(capacity=64))
        first = fl.submit([1, 2], max_new_tokens=3)
        second = fl.submit([1, 2], max_new_tokens=3)
        _drive(fl)
        assert fl.result(second) == _StubReplica.expected([1, 2], 3)
        qw = fl.stats()["slo"]["queue_wait"]
        sv = fl.stats()["slo"]["service_time"]
        assert qw["count"] == 2 and sv["count"] == 2
        for rid in (first, second):
            split = fleet_slo.split_from_trace(fl.trace_record(rid))
            assert split is not None
            assert split["total_s"] == pytest.approx(
                fl.latency(rid), abs=0.05)
        # the queued request's span-derived queue wait exceeds the
        # immediately-dispatched one's (it sat behind a full slot)
        s1 = fleet_slo.split_from_trace(fl.trace_record(first))
        s2 = fleet_slo.split_from_trace(fl.trace_record(second))
        assert s2["queue_wait_s"] > s1["queue_wait_s"]
        # tracker histogram sum ~ sum of span-derived waits
        assert qw["sum"] == pytest.approx(
            s1["queue_wait_s"] + s2["queue_wait_s"], abs=0.1)
    finally:
        obs.set_recorder(prev)


def test_failed_dispatch_request_counts_as_slo_miss():
    """A deadlined request that FAILS (rejected at dispatch) is an SLO
    miss — it delivered nothing within its promise — while a failed
    no-deadline request is not (no promise existed)."""
    class _Rejecting(_StubReplica):
        def submit(self, prompt, *a, **kw):
            raise ValueError("seeded shape rejection")

    fl = Fleet([_Rejecting()], step_workers=1,
               ring=obs.EventRing(capacity=64))
    with_slo = fl.submit([1], max_new_tokens=1, deadline=100.0)
    without = fl.submit([1], max_new_tokens=1)
    fl.step()
    assert fl.status(with_slo) == "failed"
    assert fl.status(without) == "failed"
    s = fl.stats()["slo"]
    assert s["with_deadline"] == 1 and s["within_deadline"] == 0
    assert s["slo_attainment"] == 0.0
    assert fl.metrics.get("fleet_slo_miss_total").value == 1


# -- PR 16: the tenant plane ----------------------------------------------

def test_tenant_tag_survives_failover():
    """Satellite 4: a tagged request reclaimed from a dead replica and
    restarted on the survivor keeps its tenant on EVERY surface — each
    span of the fault/reclaim/re-dispatch chain, the failover and
    recovery_done aggregates on the flight ring (list membership, the
    ``?tenant=`` filter rule), and the per-tenant SLO tallies."""
    ring = obs.EventRing(capacity=64)
    rec = obs.SpanRecorder()
    prev = obs.set_recorder(rec)
    try:
        bad = FaultyReplica(_StubReplica(), raise_on_step=(2, None),
                            ring=ring)
        fl = Fleet([bad, _StubReplica()], policy="round_robin",
                   health=HealthConfig(dead_consecutive=1,
                                       cooldown_steps=100),
                   retry=RetryPolicy(max_attempts=6, jitter=0.0),
                   step_workers=1, ring=ring)
        r0 = fl.submit([1, 2, 3], max_new_tokens=6,
                       tenant="interactive", priority=0)
        r1 = fl.submit([4, 5], max_new_tokens=3,
                       tenant="batch", priority=1)
        _drive(fl)
        assert fl.stats()["failovers"] == 1
        assert fl.result(r0) == _StubReplica.expected([1, 2, 3], 6)

        # the reclaimed request's FULL chain is tenant-stamped — the
        # hops after the fault (reclaim, survivor re-route/re-dispatch,
        # result) included, not only the pre-fault ones
        evs = rec.trace(fl.request_trace_id(r0))
        assert [e["name"] for e in evs] == [
            "fleet_submit", "fleet_route", "fleet_dispatch",
            "fleet_fault", "fleet_reclaim", "fleet_route",
            "fleet_dispatch", "fleet_result"]
        for e in evs:
            assert e["args"]["tenant"] == "interactive", e["name"]
            assert e["args"]["priority"] == 0, e["name"]
        # the undisturbed request's spans carry ITS tag
        for e in rec.trace(fl.request_trace_id(r1)):
            assert e["args"]["tenant"] == "batch"

        # ring aggregates name the suffering tenant (lists — only the
        # reclaimed request's tenant, not every tenant in flight)
        (fo,) = ring.snapshot("failover")
        assert fo["tenants"] == ["interactive"]
        (rd,) = ring.snapshot("recovery_done")
        assert rd["tenants"] == ["interactive"]
        # the /flightz?tenant= membership rule finds both aggregates
        kinds = {e["kind"] for e in
                 ring.snapshot(tenant="interactive")}
        assert {"failover", "recovery_done"} <= kinds
        assert not {"failover", "recovery_done"} & {
            e["kind"] for e in ring.snapshot(tenant="batch")}

        # SLO accounting followed the request across the failover
        ts = fl.slo.tenant_stats()
        assert ts["interactive"]["submitted"] == 1
        assert ts["interactive"]["finished"] == 1
        assert ts["interactive"]["goodput_tokens"] == 6
        assert ts["batch"]["goodput_tokens"] == 3
        # ...and so did the tenant-labeled registry child
        assert fl.metrics.get("fleet_goodput_tokens_total").labels(
            tenant="interactive").value == 6
    finally:
        obs.set_recorder(prev)


def test_tenant_sums_equal_untagged_totals_under_concurrency():
    """THE exactness pin: with every request tagged, the sum over
    tenants of goodput tokens / sheds / deadline misses / finishes
    equals the untagged fleet totals EXACTLY — per-tenant accounting
    is a partition of the same counters, not a parallel estimate —
    including with threaded replica steps (``step_workers=2``)."""
    t = [0.0]
    fl = Fleet([_StubReplica(slots=1), _StubReplica(slots=1)],
               max_queue=2, replica_queue_cap=0, step_workers=2,
               clock=lambda: t[0], ring=obs.EventRing(capacity=64))
    # occupy both slots with long decodes, one tenant each
    fl.submit([1], max_new_tokens=6, tenant="acme")
    fl.submit([1, 2], max_new_tokens=6, tenant="zeta")
    fl.step()
    t[0] += 1.0
    # fill the fleet queue with deadlined requests that will expire
    d1 = fl.submit([1], max_new_tokens=1, deadline=2.0, tenant="acme")
    d2 = fl.submit([1, 2], max_new_tokens=1, deadline=2.0,
                   tenant="zeta")
    # overload: sheds are tenant-attributed BEFORE rid allocation
    for tn in ("acme", "acme", "zeta"):
        with pytest.raises(FleetOverloaded):
            fl.submit([9], max_new_tokens=1, tenant=tn)
    t[0] = 5.0                    # both queued deadlines now hopeless
    steps = 0
    while fl.live():
        fl.step()
        t[0] += 1.0
        steps += 1
        assert steps < 50
    assert fl.status(d1) == "failed" and fl.status(d2) == "failed"

    s = fl.stats()
    ts = s["tenants"]
    assert sorted(ts) == ["acme", "zeta"]
    for key, total in (("shed", s["shed"]),
                       ("deadline_exceeded", s["deadline_exceeded"]),
                       ("goodput_tokens", s["slo"]["goodput_tokens"]),
                       ("submitted", s["submitted"]),
                       ("finished", s["finished"]),
                       ("failed", s["failed"])):
        assert sum(b[key] for b in ts.values()) == total, key
    assert s["shed"] == 3 and ts["acme"]["shed"] == 2
    assert s["deadline_exceeded"] == 2
    assert s["slo"]["goodput_tokens"] == 12    # the two occupiers
    # both tenants missed their one deadlined request
    assert ts["acme"]["slo_attainment"] == 0.0
    assert ts["zeta"]["slo_attainment"] == 0.0
    # the record carries the same partition and validates
    rec = JsonlExporter.enrich(fl.record())
    assert validate_fleet_record(rec) == []
    assert sum(b["goodput_tokens"] for b in rec["tenants"].values()) \
        == rec["tokens_within_slo"]
    # ...and the validator catches a partition that over-counts
    broken = {**rec, "tenants": {
        **rec["tenants"],
        "acme": {**rec["tenants"]["acme"],
                 "goodput_tokens": rec["tokens_within_slo"] + 1}}}
    assert validate_fleet_record(broken)
    # a record WITHOUT the tenant block is rejected
    stripped = {k: v for k, v in rec.items()
                if k not in ("tenants", "tenants_dropped")}
    assert any("tenants" in e
               for e in validate_fleet_record(stripped))


def test_tenant_cardinality_flood_stays_bounded_and_conserved():
    """A flood of distinct tenant ids must not grow unbounded state:
    past ``max_tenants`` new ids fold into the shared ``other`` bucket
    on EVERY surface (SLO buckets, span stamps, registry label
    children), the fold is counted on ``tenants_dropped``, and the
    totals stay conserved — folding loses attribution, never tokens."""
    fl = Fleet([_StubReplica(slots=4)], step_workers=1,
               ring=obs.EventRing(capacity=64))
    fl.slo.max_tenants = 3
    rec = obs.SpanRecorder()
    prev = obs.set_recorder(rec)
    try:
        rids = [fl.submit([1, 2], max_new_tokens=2, tenant=f"t{i}")
                for i in range(8)]
        _drive(fl)
        s = fl.stats()
        ts = s["tenants"]
        # bounded: 3 real buckets + the overflow, 5 folds accounted
        assert sorted(ts) == ["other", "t0", "t1", "t2"]
        assert fl.slo.tenants_dropped == 5
        assert s["tenants_dropped"] == 5
        assert ts["other"]["submitted"] == 5
        # conserved: the fold moved tokens, it didn't drop them
        assert sum(b["goodput_tokens"] for b in ts.values()) == 16
        assert s["slo"]["goodput_tokens"] == 16
        # the fold happens ONCE at submit, so spans agree with stats
        for e in rec.trace(fl.request_trace_id(rids[7])):
            assert e["args"]["tenant"] == "other"
        # registry children bounded to the same fold
        goodput = fl.metrics.get("fleet_goodput_tokens_total")
        vals = {dict(k)["tenant"] for k in goodput.children()}
        assert vals == {"other", "t0", "t1", "t2"}
        assert goodput.labels(tenant="other").value == 10
        # slo folds BEFORE the registry sees the label, so no metric
        # hit its own cap — the fleet surface reports no label drops
        assert fl.tenant_stats()["label_sets_dropped"] == {}
        # the record stays schema-valid mid-fold
        out = JsonlExporter.enrich(fl.record())
        assert validate_fleet_record(out) == []
        assert out["tenants_dropped"] == 5
        assert sorted(out["tenants"]) == ["other", "t0", "t1", "t2"]
    finally:
        obs.set_recorder(prev)


# -- PR 15: the compilation plane ------------------------------------------

def test_fleet_warmup_precompiles_every_replica():
    """Fleet.warmup() pays each replica's per-instance re-jit up
    front (the PR 4 cold-fleet-measures-N-compiles gotcha, fixed at
    the source): after warmup, a full traffic pass adds ZERO traces.
    Stub replicas without a warmup() method are skipped, so the stub
    suites keep working unchanged."""
    from apex_tpu.observability import compilation
    m, params = _gpt()
    led = compilation.get_ledger()
    t0 = led.total_traces()
    fl = Fleet([serving.Engine(m, params, slots=2, buf_len=24)
                for _ in range(2)], policy="least_loaded")
    fl.warmup()
    # 2 replicas x (prefill + step) — each instance re-jits its own
    assert led.total_traces() - t0 == 4
    t1 = led.total_traces()
    rng = np.random.RandomState(0)
    rids = [fl.submit(list(rng.randint(0, 64, int(rng.randint(3, 9)))),
                      max_new_tokens=5) for _ in range(6)]
    _drive(fl)
    assert all(fl.status(r) == "finished" for r in rids)
    assert led.total_traces() - t1 == 0
    # duck-typing: a stub fleet warms to a no-op instead of crashing
    Fleet([_StubReplica(), _StubReplica()]).warmup()


def test_failover_survivors_recompile_nothing():
    """The fleet-level zero-retrace pin: a warmed fleet loses a
    replica mid-run; the reclaimed requests RESTART from their
    prompts on the survivor with ledger delta == 0 — failover rides
    entirely on executables the survivor already owns (restarted
    prompts are new buffer values, not new signatures)."""
    from apex_tpu.observability import compilation
    m, params = _gpt()
    bad = FaultyReplica(serving.Engine(m, params, slots=2, buf_len=24),
                        raise_on_step=(3, None))
    fl = Fleet([bad, serving.Engine(m, params, slots=2, buf_len=24)],
               policy="round_robin",
               health=HealthConfig(dead_consecutive=2,
                                   cooldown_steps=50),
               retry=RetryPolicy(max_attempts=6, jitter=0.0))
    fl.warmup()                       # incl. the wrapped replica
    led = compilation.get_ledger()
    t0 = led.total_traces()
    rng = np.random.RandomState(1)
    prompts = [list(rng.randint(0, 64, int(rng.randint(3, 9))))
               for _ in range(6)]
    rids = [fl.submit(p, max_new_tokens=7) for p in prompts]
    _drive(fl, limit=300)
    s = fl.stats()
    assert s["failovers"] >= 1        # the death actually fired
    assert s["failed"] == 0           # every request survived
    for r in rids:
        assert fl.status(r) == "finished"
    assert led.total_traces() - t0 == 0   # survivors compiled NOTHING
