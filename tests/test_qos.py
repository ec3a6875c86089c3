"""Multi-tenant QoS (PR 19): priority classes, weighted-fair queuing,
and paged decode preemption.

Coverage mirrors the fleet-test discipline — the scheduling machinery
(stride order, per-class quotas, preemption bookkeeping, controller
actuation) runs against jax-free stubs where every schedule is exact
and instant; the pins that justify the subsystem run against real
engines on the tiny GPT config:

- preemption EXACTNESS: a request evicted mid-decode from a paged
  replica and readmitted later must produce token-for-token the output
  of an undisturbed solo engine (greedy AND explicitly-seeded sampled),
- zero retraces: a warmed fleet runs a whole preemption episode with
  compilation-ledger delta == 0,
- composition with failover: a replica dying while holding a
  preempted-then-readmitted request still converges to exact results,
  exactly once, with the recovery ring naming the right tenants.
"""

import json

import numpy as np
import pytest
import jax

from apex_tpu import models, serving
from apex_tpu.fleet import (AutoscaleConfig, FaultyReplica, Fleet,
                            FleetOverloaded, HealthConfig, RetryPolicy,
                            SloController)
from apex_tpu.fleet.qos import (DEFAULT_CLASS, STRIDE_SCALE, QosClass,
                                QosPolicy, WfqQueue)
from apex_tpu.fleet import slo as fleet_slo
from apex_tpu.fleet.recovery import RECOVERY_ACTION_KINDS
from apex_tpu import observability as obs
from apex_tpu.observability import exporters
from apex_tpu.observability.flightrec import (EventRing,
                                              event_matches_tenant)


# -- jax-free stub replica (the test_fleet scheduler surface) -------------

class _StubReplica:
    """Deterministic scheduler-surface replica: request k's token j is
    ``100 * len(prompt) + j`` — restart/preemption exactness holds by
    construction, so these tests pin the ORCHESTRATION."""

    def __init__(self, slots=2):
        self.slots = slots
        self._free = list(range(slots))
        self._live = {}
        self._waiting = []
        self._finished = {}
        self._next_rid = 0

    @staticmethod
    def expected(prompt, max_new):
        return [100 * len(prompt) + j for j in range(max_new)]

    def _admit(self, rid, prompt, max_new):
        self._free.pop()
        self._live[rid] = [list(prompt), max_new, []]

    def submit(self, prompt, max_new_tokens, eos_token_id=None,
               seed=None, temperature=None):
        rid = self._next_rid
        self._next_rid += 1
        if self._free and not self._waiting:
            self._admit(rid, prompt, max_new_tokens)
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


def _drive(fl, limit=300):
    n = 0
    while fl.live():
        fl.step()
        n += 1
        assert n < limit, "fleet failed to converge"
    return n


def _two_class(**kw):
    """The canonical two-class policy: interactive (weight 8, never
    evicted) over batch (weight 1, preemptible), tenants mapped 1:1."""
    return QosPolicy(
        [QosClass("interactive", weight=8, preemptible=False),
         QosClass("batch", weight=1, **kw)],
        tenant_class={"alice": "interactive", "bob": "batch"})


class _Tagged:
    """Minimal request-shaped object for driving WfqQueue directly."""

    def __init__(self, rid, qos_class):
        self.rid = rid
        self.qos_class = qos_class

    def __repr__(self):
        return f"<{self.qos_class}:{self.rid}>"


# -- QosPolicy: validation and class resolution ---------------------------

def test_policy_validation_and_resolution():
    with pytest.raises(ValueError):
        QosClass("", weight=1)
    with pytest.raises(ValueError):
        QosClass("x", weight=0)
    with pytest.raises(ValueError):
        QosClass("x", weight=True)          # bools are not weights
    with pytest.raises(ValueError):
        QosClass("x", deadline_s=0.0)
    with pytest.raises(ValueError):
        QosClass("x", queue_share=0.0)
    with pytest.raises(ValueError):
        QosPolicy([])
    with pytest.raises(ValueError):
        QosPolicy([QosClass("a"), QosClass("a")])
    with pytest.raises(ValueError):
        QosPolicy([QosClass("a")], tenant_class={"t": "nope"})
    with pytest.raises(ValueError):
        QosPolicy([QosClass("a")], default_class="nope")

    pol = _two_class()
    # precedence: explicit priority naming a known class > tenant map
    # > default (the LAST class — anonymous traffic never outranks
    # tagged interactive requests)
    assert pol.resolve(tenant="alice") == "interactive"
    assert pol.resolve(tenant="alice", priority="batch") == "batch"
    assert pol.resolve(tenant="nobody") == "batch"
    assert pol.resolve() == "batch"
    assert pol.resolve(priority="made-up") == "batch"   # total, no raise
    assert pol.rank("interactive") == 0
    assert pol.rank("batch") == 1
    assert pol.rank("made-up") == 2          # unknown ranks below all
    assert not pol.preemptible("interactive")
    assert pol.preemptible("batch")
    # queue_share caps never round a tiny share to an un-admittable 0
    capped = QosPolicy([QosClass("a"), QosClass("b", queue_share=0.01)])
    assert capped.cap("b", 10) == 1
    assert capped.cap("a", 10) == 10         # None share = whole queue
    # the implicit single-class policy of a QoS-less fleet
    single = QosPolicy.single()
    assert list(single.classes) == [DEFAULT_CLASS]
    assert single.resolve(tenant="anyone") == DEFAULT_CLASS


# -- WfqQueue: FIFO degeneracy, weighted interleave, no starvation --------

def test_wfq_single_class_is_exact_fifo():
    """Under the implicit single-class policy the WFQ order IS
    submission order — the queue is a drop-in for the old list,
    including the failover front-requeue idiom."""
    q = WfqQueue()
    reqs = [_Tagged(i, None) for i in range(6)]
    for r in reqs:
        q.append(r)
    assert list(q) == reqs
    assert q[0] is reqs[0] and len(q) == 6 and bool(q)
    q.remove(reqs[2])
    assert list(q) == [reqs[0], reqs[1], reqs[3], reqs[4], reqs[5]]
    # front-requeue puts the reclaimed requests back at the head in
    # their original relative order
    q[:0] = [reqs[2]]
    assert q[0] is reqs[2]
    with pytest.raises(TypeError):
        q[0] = reqs[1]                      # only q[:0] = [...] allowed


def _dequeue_order(pol, items):
    q = WfqQueue(pol)
    for it in items:
        q.append(it)
    order = []
    while q:
        head = q[0]
        q.remove(head)
        order.append(head)
    return order


def test_wfq_weighted_interleave_deterministic_no_starvation():
    """Stride scheduling, both starvation directions: a batch flood
    cannot starve the interactive trickle (interactive dequeues ~8x
    as often), and an interactive flood cannot starve batch (its pass
    catches up — the max gap between batch dequeues is bounded by the
    weight ratio).  The order is a pure function of the submissions:
    two identical runs produce the identical sequence."""
    pol = _two_class()
    # batch flood + interactive trickle: every interactive request is
    # served within the first few dequeues despite 20 queued batch
    flood = [_Tagged(i, "batch") for i in range(20)]
    trickle = [_Tagged(100 + i, "interactive") for i in range(3)]
    order = _dequeue_order(pol, flood + trickle)
    inter_pos = [i for i, r in enumerate(order)
                 if r.qos_class == "interactive"]
    assert max(inter_pos) <= 4, order
    # interactive flood + batch trickle: batch still drains — first
    # batch dequeue lands within one stride round (weight ratio 8),
    # and consecutive batch dequeues are never more than a round apart
    flood_i = [_Tagged(i, "interactive") for i in range(20)]
    trickle_b = [_Tagged(100 + i, "batch") for i in range(3)]
    order2 = _dequeue_order(pol, flood_i + trickle_b)
    batch_pos = [i for i, r in enumerate(order2)
                 if r.qos_class == "batch"]
    assert batch_pos[0] <= 2, order2
    gaps = [b - a for a, b in zip(batch_pos, batch_pos[1:])]
    assert all(g <= 9 for g in gaps), order2
    # determinism: the same submissions give the same schedule
    assert [r.rid for r in _dequeue_order(pol, flood + trickle)] \
        == [r.rid for r in order]
    # FIFO within one class is preserved by the merge
    assert [r.rid for r in order if r.qos_class == "batch"] \
        == sorted(r.rid for r in flood)


def test_wfq_waking_class_inherits_live_pass():
    """A class waking from empty inherits the minimum live pass: its
    idle time is not credit, so it cannot monopolize the queue on
    arrival — the very next dequeues still interleave."""
    pol = _two_class()
    q = WfqQueue(pol)
    batch = [_Tagged(i, "batch") for i in range(6)]
    for r in batch:
        q.append(r)
    for _ in range(3):                      # serve batch alone a while
        head = q[0]
        q.remove(head)
    woken = [_Tagged(100 + i, "interactive") for i in range(4)]
    for r in woken:
        q.append(r)
    order = list(q)
    # interactive wins the tie at the inherited pass (rank tiebreak)
    # but batch is NOT pushed to the back of the whole schedule
    assert order[0].qos_class == "interactive"
    assert order[1].qos_class == "batch"


# -- per-class admission: quota shed with class accounting ----------------

def test_per_class_quota_sheds_with_class_accounting():
    """A batch flood sheds against its OWN queue_share quota while the
    interactive class keeps admitting; the FleetOverloaded, the ring
    shed episode, and the per-class tallies all name the class."""
    ring = obs.EventRing(capacity=64)
    fl = Fleet([_StubReplica(slots=1)], max_queue=8,
               replica_queue_cap=0, step_workers=1, ring=ring,
               qos=_two_class(queue_share=0.25))    # batch cap = 2
    fl.submit([1], max_new_tokens=30, tenant="bob")
    fl.step()                                # batch occupies the slot
    fl.submit([1, 2], max_new_tokens=1, tenant="bob")
    fl.submit([1, 2, 3], max_new_tokens=1, tenant="bob")
    with pytest.raises(FleetOverloaded) as ei:
        fl.submit([1, 2, 3, 4], max_new_tokens=1, tenant="bob")
    assert ei.value.qos_class == "batch"
    # the interactive class still has the rest of the queue
    hi = fl.submit([5, 6], max_new_tokens=1, tenant="alice")
    s = fl.stats()
    assert s["shed"] == 1
    assert s["classes"]["batch"]["shed"] == 1
    assert s["classes"]["interactive"]["shed"] == 0
    sheds = ring.snapshot("shed")
    assert len(sheds) == 1 and sheds[0]["qos_class"] == "batch"
    _drive(fl)
    assert fl.status(hi) == "finished"


# -- decode preemption: bookkeeping on stubs ------------------------------

def test_preemption_evicts_lower_class_and_stays_exact():
    """No candidates (slot busy, no replica queue): an interactive
    submit evicts the in-flight batch request.  The ring event names
    both parties and both tenants, the per-class tallies count the
    eviction, and the evictee restarts from its prompt to its exact
    undisturbed tokens."""
    ring = obs.EventRing(capacity=64)
    fl = Fleet([_StubReplica(slots=1)], max_queue=8,
               replica_queue_cap=0, step_workers=1, ring=ring,
               qos=_two_class())
    vic = fl.submit([1, 2], max_new_tokens=4, tenant="bob")
    fl.step()                                # batch decoding in the slot
    hi = fl.submit([3, 4, 5], max_new_tokens=2, tenant="alice")
    fl.step()                                # preempt fires at dispatch
    evs = ring.snapshot("preemption")
    assert len(evs) == 1
    ev = evs[0]
    assert ev["evicted_rid"] == vic and ev["evicted_class"] == "batch"
    assert ev["admitted_rid"] == hi
    assert ev["admitted_class"] == "interactive"
    assert ev["tenants"] == ["alice", "bob"]
    _drive(fl)
    s = fl.stats()
    assert s["preemptions"] == 1
    assert s["classes"]["batch"]["preempted"] == 1
    assert s["classes"]["interactive"]["preempted"] == 0
    assert s["failed"] == 0
    # exactness by construction: the evictee restarted from its prompt
    assert fl.result(vic) == _StubReplica.expected([1, 2], 4)
    assert fl.result(hi) == _StubReplica.expected([3, 4, 5], 2)
    # a preemption is not a failure: no retry budget consumed
    assert s["retries"] == 0 and s["failovers"] == 0


def test_preemption_victim_selection_deterministic():
    """Among equal-rank victims the YOUNGEST (fewest harvested tokens,
    then highest rid) is evicted — the least sunk work to redo."""
    ring = obs.EventRing(capacity=64)
    fl = Fleet([_StubReplica(slots=2)], max_queue=8,
               replica_queue_cap=0, step_workers=1, ring=ring,
               qos=_two_class())
    fl.submit([1, 2], max_new_tokens=6, tenant="bob")
    b2 = fl.submit([1, 2, 3], max_new_tokens=6, tenant="bob")
    fl.step()                                # both batch slots busy
    fl.submit([9], max_new_tokens=1, tenant="alice")
    fl.step()
    evs = ring.snapshot("preemption")
    assert len(evs) == 1 and evs[0]["evicted_rid"] == b2
    _drive(fl)
    assert fl.stats()["failed"] == 0


def test_preemption_fires_over_queue_behind_busy_slots():
    """The priority-inversion path: every candidate replica has queue
    room but NO free slot — a high-class request must evict a
    lower-class decode instead of queueing behind it (the paged
    regression: a paged replica's internal queue kept it a candidate
    forever, so preemption never fired)."""
    ring = obs.EventRing(capacity=64)
    fl = Fleet([_StubReplica(slots=1)], max_queue=8,
               replica_queue_cap=4, step_workers=1, ring=ring,
               qos=_two_class())
    vic = fl.submit([1, 2], max_new_tokens=6, tenant="bob")
    fl.step()
    hi = fl.submit([3, 4], max_new_tokens=2, tenant="alice")
    fl.step()
    evs = ring.snapshot("preemption")
    assert len(evs) == 1 and evs[0]["evicted_rid"] == vic
    _drive(fl)
    assert fl.result(vic) == _StubReplica.expected([1, 2], 6)
    assert fl.result(hi) == _StubReplica.expected([3, 4], 2)
    # a non-preemptible or same-class victimless queue does NOT evict:
    # batch-on-batch contention just queues
    fl2 = Fleet([_StubReplica(slots=1)], max_queue=8,
                replica_queue_cap=4, step_workers=1,
                ring=obs.EventRing(capacity=16), qos=_two_class())
    fl2.submit([1], max_new_tokens=4, tenant="bob")
    fl2.step()
    fl2.submit([2], max_new_tokens=1, tenant="bob")
    fl2.step()
    assert fl2.stats()["preemptions"] == 0
    _drive(fl2)


def test_single_class_fleet_never_preempts():
    """A QoS-less fleet (implicit single-class policy) keeps the
    pre-QoS surfaces byte-identical: no preemption machinery, zero
    class counters on the quiet default class."""
    fl = Fleet([_StubReplica(slots=1)], max_queue=8,
               replica_queue_cap=0, step_workers=1,
               ring=obs.EventRing(capacity=16))
    fl.submit([1, 2], max_new_tokens=4)
    fl.step()
    fl.submit([3], max_new_tokens=1, priority=0)   # legacy int tag
    _drive(fl)
    s = fl.stats()
    assert s["preemptions"] == 0
    assert list(s["classes"]) == [DEFAULT_CLASS]
    assert s["classes"][DEFAULT_CLASS]["preempted"] == 0
    assert len(fl.ring.snapshot("preemption")) == 0


# -- flightrec membership: ONE rule for snapshot and /flightz -------------

def test_event_matches_tenant_both_directions():
    """The shared membership rule (PR 16 extraction): a per-request
    ``tenant:`` stamp matches, an aggregate ``tenants: [...]`` list
    matches, and absence of both never matches."""
    assert event_matches_tenant({"tenant": "acme"}, "acme")
    assert not event_matches_tenant({"tenant": "acme"}, "zeta")
    assert event_matches_tenant({"tenants": ["acme", "zeta"]}, "zeta")
    assert not event_matches_tenant({"tenants": ["acme"]}, "zeta")
    assert not event_matches_tenant({"kind": "shed"}, "acme")
    assert not event_matches_tenant({"tenants": None}, "acme")
    ring = EventRing(capacity=16)
    ring.append("shed", tenant="acme")
    ring.append("failover", tenants=["acme", "zeta"], reclaimed=2)
    ring.append("preemption", tenants=["zeta"])
    ring.append("breaker_open", replica=0)
    acme = ring.snapshot(tenant="acme")
    assert [e["kind"] for e in acme] == ["shed", "failover"]
    zeta = ring.snapshot(tenant="zeta")
    assert [e["kind"] for e in zeta] == ["failover", "preemption"]
    assert ring.snapshot(tenant="nobody") == []


# -- per-class controller actuation ---------------------------------------

def test_controller_tightens_batch_class_never_interactive():
    """Under overload the controller halves the LOWEST-priority
    class's queue quota — the interactive class's admission is never
    touched — and after sustained health relaxes it back to exactly
    the baseline share."""
    pol = _two_class(queue_share=0.5)
    reps = [_StubReplica(slots=1)]
    clk = [0.0]
    fl = Fleet(reps, max_queue=16, replica_queue_cap=0,
               step_workers=1, clock=lambda: clk[0],
               ring=obs.EventRing(capacity=64), qos=pol)
    cfg = AutoscaleConfig(backlog_factor=1.0, min_queue=2,
                          relax_after_ticks=1, cooldown_ticks=1)
    ctrl = SloController(fl, cfg, clock=lambda: clk[0])
    base_cap = pol.cap("batch", fl.max_queue)
    assert base_cap == 8
    # flood the batch class to build a real backlog signal
    fl.submit([1], max_new_tokens=40, tenant="bob")
    fl.step()
    for k in range(7):
        fl.submit([1, k], max_new_tokens=1, tenant="bob")
    acts = []
    for _ in range(6):
        fl.step()
        clk[0] += 1.0
        acts += ctrl.tick()
    kinds = [a["kind"] for a in acts]
    assert "class_admission_tighten" in kinds, kinds
    tight = next(a for a in acts
                 if a["kind"] == "class_admission_tighten")
    assert tight["qos_class"] == "batch"
    assert pol.cap("batch", fl.max_queue) < base_cap
    # the top class was never tightened: its cap is still the whole
    # queue and no action ever names it
    assert pol.cap("interactive", fl.max_queue) == fl.max_queue
    assert all(a.get("qos_class") != "interactive" for a in acts)
    assert fl.max_queue == 16               # global knob untouched
    # drain, then sustained health relaxes back to the exact baseline
    _drive(fl)
    relax_acts = []
    for _ in range(30):
        fl.step()
        clk[0] += 1.0
        relax_acts += ctrl.tick()
        if pol.cap("batch", fl.max_queue) == base_cap:
            break
    assert any(a["kind"] == "class_admission_relax"
               for a in relax_acts)
    assert pol.cap("batch", fl.max_queue) == base_cap
    assert pol.classes["batch"].queue_share == 0.5


def test_class_action_kinds_registered():
    """The per-class actuation kinds exist in BOTH registries (the
    stdlib-side recovery log and the exporter validator) — the same
    two-tuple pin the other recovery kinds live under."""
    for kind in ("class_admission_tighten", "class_admission_relax"):
        assert kind in RECOVERY_ACTION_KINDS
        assert kind in exporters.RECOVERY_ACTION_KINDS
    assert RECOVERY_ACTION_KINDS == exporters.RECOVERY_ACTION_KINDS


# -- the validator knows the class plane ---------------------------------

def _fleet_record():
    """A real multi-class fleet record off the stub fleet."""
    fl = Fleet([_StubReplica(slots=2)], max_queue=8,
               replica_queue_cap=0, step_workers=1,
               ring=obs.EventRing(capacity=16), qos=_two_class())
    fl.submit([1, 2], max_new_tokens=3, tenant="bob")
    fl.submit([2, 3], max_new_tokens=2, tenant="alice")
    _drive(fl)
    return exporters.JsonlExporter.enrich(fl.record())


def test_class_plane_fleet_record_validates_and_mutations_reject():
    # CLASS_COUNTS is the class bucket minus its window timestamps —
    # pinned across the package boundary like TENANT_COUNTS
    assert exporters.CLASS_COUNTS == tuple(
        k for k in fleet_slo._new_class_bucket()
        if k not in ("t_first", "t_last"))
    good = _fleet_record()
    assert good["schema_version"] == exporters.SCHEMA_VERSION
    assert set(good["classes"]) == {"interactive", "batch"}
    assert exporters.validate_fleet_record(good) == []
    assert exporters.validate_telemetry_record(good) == []

    # fleet records REQUIRE the class plane
    for missing in ("classes", "preemptions"):
        bad = {k: v for k, v in good.items() if k != missing}
        assert any(missing in e for e in
                   exporters.validate_fleet_record(bad)), missing

    def mutated(**kw):
        rec = json.loads(json.dumps(good))
        cls = rec["classes"]["batch"]
        for k, v in kw.items():
            if k == "preemptions":
                rec[k] = v
            else:
                cls[k] = v
        return rec

    assert any("preemptions" in e for e in
               exporters.validate_fleet_record(
                   mutated(preemptions=-1)))
    assert any("preempted" in e for e in
               exporters.validate_fleet_record(mutated(preempted=-2)))
    # per-class evictions cannot exceed the fleet preemption total
    assert exporters.validate_fleet_record(
        mutated(preempted=5, preemptions=1)) != []
    assert any("slo_attainment" in e for e in
               exporters.validate_fleet_record(
                   mutated(slo_attainment=1.5)))
    assert any("weight" in e for e in
               exporters.validate_fleet_record(mutated(weight=0)))


# -- the engine-backed pins: exactness, zero retraces, failover -----------

def _gpt(seed=0):
    m = models.GPT(models.GPTConfig(vocab_size=64, block_size=24,
                                    n_layer=2, n_head=4, n_embd=32,
                                    dropout=0.0, n_kv_head=2))
    params, _ = m.init(jax.random.PRNGKey(seed))
    return m, params


def test_preemption_exactness_paged_replicas():
    """THE acceptance pin: a batch request evicted mid-decode from a
    paged replica (KV blocks recycled) and readmitted later produces
    token-for-token the undisturbed solo-engine output — greedy AND
    explicitly-seeded sampled, so the stream is request-intrinsic,
    never pool-layout-dependent."""
    m, params = _gpt(4)
    rng = np.random.RandomState(4)
    prompts = [list(rng.randint(0, 64, int(rng.randint(3, 9))))
               for _ in range(3)]
    # victim candidates: one greedy, one seeded-sampled; the admitted
    # interactive request is greedy
    kws = [dict(temperature=0.0), dict(seed=107), dict(temperature=0.0)]

    def paged_engine():
        return serving.PagedEngine(m, params, slots=2, buf_len=24,
                                   block_size=8, window=2,
                                   temperature=0.8, top_k=8,
                                   rng=jax.random.PRNGKey(7))

    # the batch decodes are LONG (10 tokens at window=2 ~ 5 steps) so
    # they are still mid-decode when the interactive request arrives
    new = [10, 10, 4]
    single = paged_engine()
    srids = [single.submit(p, max_new_tokens=n, **kw)
             for p, n, kw in zip(prompts, new, kws)]
    while single.live() or single.queue_depth():
        single.step()
    expected = [single.result(r) for r in srids]

    fl = Fleet([paged_engine()], max_queue=16, replica_queue_cap=0,
               retry=RetryPolicy(max_attempts=8, jitter=0.0),
               step_workers=1, ring=obs.EventRing(capacity=64),
               qos=_two_class())
    rids = [fl.submit(prompts[0], max_new_tokens=new[0], tenant="bob",
                      **kws[0]),
            fl.submit(prompts[1], max_new_tokens=new[1], tenant="bob",
                      **kws[1])]
    fl.step()                           # both batch decodes underway
    rids.append(fl.submit(prompts[2], max_new_tokens=new[2],
                          tenant="alice", **kws[2]))
    _drive(fl)
    s = fl.stats()
    assert s["preemptions"] >= 1        # the eviction actually fired
    assert s["failed"] == 0
    assert [fl.result(r) for r in rids] == expected
    evs = fl.ring.snapshot("preemption")
    assert evs and evs[0]["evicted_class"] == "batch"
    assert "alice" in evs[0]["tenants"] and "bob" in evs[0]["tenants"]


def test_warmed_fleet_preemption_episode_zero_retraces():
    """A warmed paged fleet runs a whole preemption episode —
    eviction, KV-block recycling, readmission, restart — with
    compilation-ledger delta == 0: eviction rides the eager host-side
    freeze path, never a new traced shape."""
    from apex_tpu.observability import compilation
    m, params = _gpt(5)
    fl = Fleet([serving.PagedEngine(m, params, slots=2, buf_len=24,
                                    block_size=8, window=2,
                                    temperature=0.0)],
               max_queue=16, replica_queue_cap=0,
               retry=RetryPolicy(max_attempts=8, jitter=0.0),
               step_workers=1, ring=obs.EventRing(capacity=64),
               qos=_two_class())
    fl.warmup()
    # settle one request end to end so every steady-state shape is
    # traced before the watermark (the warm-up discipline)
    settle = fl.submit([1, 2, 3], max_new_tokens=4, tenant="bob")
    _drive(fl)
    assert fl.status(settle) == "finished"
    led = compilation.get_ledger()
    t0 = led.total_traces()
    rng = np.random.RandomState(5)
    lo = [fl.submit(list(rng.randint(0, 64, 3)), max_new_tokens=8,
                    tenant="bob") for _ in range(2)]
    fl.step()
    hi = fl.submit(list(rng.randint(0, 64, 3)), max_new_tokens=4,
                   tenant="alice")
    _drive(fl)
    s = fl.stats()
    assert s["preemptions"] >= 1
    assert s["failed"] == 0
    assert fl.status(hi) == "finished"
    assert all(fl.status(r) == "finished" for r in lo)
    assert led.total_traces() - t0 == 0     # zero retraces, the pin


def test_preemption_composed_with_failover_stays_exact():
    """Composition: a replica dies while the preempted-then-readmitted
    request is back in flight.  Every request still converges to its
    exact undisturbed tokens, result() lands exactly once per rid, and
    the recovery ring's preemption/failover events both carry the
    affected tenants."""
    m, params = _gpt(6)
    rng = np.random.RandomState(6)
    prompts = [list(rng.randint(0, 64, int(rng.randint(3, 8))))
               for _ in range(5)]
    new = [3, 10, 10, 10, 4]            # batch rid 0 frees a slot early

    def paged_engine():
        return serving.PagedEngine(m, params, slots=2, buf_len=24,
                                   block_size=8, window=2,
                                   temperature=0.0)

    single = paged_engine()
    srids = [single.submit(p, max_new_tokens=n)
             for p, n in zip(prompts, new)]
    while single.live() or single.queue_depth():
        single.step()
    expected = [single.result(r) for r in srids]

    bad = FaultyReplica(paged_engine(), raise_on_step=(6, None))
    fl = Fleet([bad, paged_engine()], policy="round_robin",
               max_queue=16, replica_queue_cap=0,
               health=HealthConfig(dead_consecutive=2,
                                   cooldown_steps=50),
               retry=RetryPolicy(max_attempts=8, jitter=0.0),
               step_workers=1, ring=obs.EventRing(capacity=128),
               qos=_two_class())
    # four batch requests fill all four slots; the interactive submit
    # then evicts the youngest batch one, which readmits when rid 0's
    # short decode frees a slot on replica 0 — and is in flight again
    # there when the armed fault fires at step 6
    rids = [fl.submit(p, max_new_tokens=n, tenant="bob")
            for p, n in zip(prompts[:4], new[:4])]
    fl.step()
    rids.append(fl.submit(prompts[4], max_new_tokens=new[4],
                          tenant="alice"))
    _drive(fl)
    s = fl.stats()
    assert s["preemptions"] >= 1        # the eviction fired...
    assert s["failovers"] >= 1          # ...and so did the death
    assert s["failed"] == 0
    # exactly once: every rid reports finished and yields its exact
    # tokens (repeat reads are stable, not re-executions)
    for r, exp in zip(rids, expected):
        assert fl.status(r) == "finished"
        assert fl.result(r) == exp
        assert fl.result(r) == exp
    pre = fl.ring.snapshot("preemption")
    assert pre and pre[0]["tenants"] == ["alice", "bob"]
    fo = fl.ring.snapshot("failover")
    assert fo and fo[0]["tenants"]      # the reclaimed work is named
    assert set(fo[0]["tenants"]) <= {"alice", "bob"}
    # the membership rule finds the story from EITHER side
    assert any(e["kind"] == "preemption"
               for e in fl.ring.snapshot(tenant="alice"))
    assert any(e["kind"] == "preemption"
               for e in fl.ring.snapshot(tenant="bob"))
