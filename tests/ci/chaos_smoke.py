#!/usr/bin/env python
"""CI gate: the self-healing controllers under a seeded fault schedule.

Runs a short deterministic chaos scenario — replica DEATH, silent
STALL, and a traffic SPIKE — through jax-light stub replicas (no model,
no tracing, no device work: the fleet/controller layer is pure host
orchestration) plus a stub elastic-training run, and asserts the
telemetry→action loop CONVERGES:

1. the serving SLO controller's actuation is bounded per overload
   episode (``max_actions_in_episode <= config.max_actions_per_episode``
   — the no-oscillation contract) and the controlled run's deadline
   attainment beats the no-controller baseline on the identical seeded
   workload with goodput no worse;
2. the fleet survives the death and the stall (every surviving request
   resolves; MTTR — failover to first post-recovery progress of
   reclaimed work — is measured and non-negative, in the injected
   tick clock's units);
3. the training controller survives a mid-step replica death AND a
   torn snapshot: it shrinks the world, skips the torn write, resumes
   from the previous durable snapshot, and finishes the run;
4. a PLANNED preemption (the ``TrainingFaults.preemption`` window
   firing into a ``PreemptionGuard``, the programmatic twin of the
   real SIGTERM) is honored at the next step boundary: coordinated
   emergency snapshot (numpy tree + DataLoader cursor under one
   checksum), clean ``preempted`` verdict, and a fresh trainer +
   fresh loader resume to a loss trajectory and consumed-sample-index
   sequence IDENTICAL to an undisturbed run (exactly-once accounting
   across the preemption);
5. every ``kind: recovery`` record the controllers emit — and every
   ``kind: fleet`` record with the new ``mttr`` aggregate — validates
   against the schema (``exporters.validate_telemetry_record``).

Exit 0 = converged and schema-clean; 1 = any violation (each printed).
Wired into tier-1 by tests/test_autoscale.py (subprocess), like the
server_smoke gate.
"""

import os
import sys
import tempfile

_ROOT = os.path.abspath(os.path.join(os.path.dirname(
    os.path.abspath(__file__)), os.pardir, os.pardir))
sys.path.insert(0, _ROOT)

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np  # noqa: E402

from apex_tpu.data import DataLoader  # noqa: E402
from apex_tpu.fleet import (AutoscaleConfig, ElasticConfig,  # noqa: E402
                            ElasticTrainer, FaultyReplica, Fleet,
                            FleetOverloaded, PreemptionGuard,
                            RetryPolicy, SloController, TrainingFaults)
from apex_tpu.observability.exporters import (  # noqa: E402
    JsonlExporter, validate_telemetry_record)

VIOLATIONS = []


def check(ok, msg):
    status = "ok" if ok else "VIOLATION"
    print(f"chaos_smoke: [{status}] {msg}")
    if not ok:
        VIOLATIONS.append(msg)


def check_record(rec, label):
    errs = validate_telemetry_record(JsonlExporter.enrich(rec))
    check(not errs, f"{label} record schema-clean"
          + (f": {errs}" if errs else ""))


class StubReplica:
    """Deterministic scheduler-surface replica (test_fleet discipline):
    request k's j-th token is ``100*len(prompt)+j``; one token per live
    request per step.  ``set_window`` exists so the controller's
    decode-window actuator has a real target."""

    def __init__(self, slots=2, window=4):
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


class Tick:
    t = 0.0


def clock():
    return Tick.t


# ---------------------------------------------------------------------------
# serving: seeded spike + death + stall, baseline vs controller
# ---------------------------------------------------------------------------

MAX_NEW = 8
DEADLINE = 16.0
# seeded schedule: steady trickle + one 24-request spike at tick 10;
# the death (replica 0 raises from tick 20) and the stall (replica 1
# goes silent on live work, ticks 44-56) land mid-run
WAVES = {t: 1 for t in range(0, 70, 6)}
WAVES[10] = WAVES.get(10, 0) + 24


def drive(fl, controller=None, ticks=90):
    rng = np.random.RandomState(0)
    rids, shed = [], 0
    for tick in range(ticks):
        for _ in range(WAVES.get(tick, 0)):
            try:
                rids.append(fl.submit(
                    list(rng.randint(0, 100, 4)),
                    max_new_tokens=MAX_NEW, deadline=DEADLINE))
            except FleetOverloaded:
                shed += 1
        fl.step()
        Tick.t += 1.0
        if controller is not None and tick % 2 == 1:
            controller.tick()
    guard = 0
    while fl.live() and guard < 500:
        fl.step()
        Tick.t += 1.0
        if controller is not None:
            controller.tick()
        guard += 1
    check(not fl.live(), "fleet drained to completion")
    return rids, shed


def build_fleet(with_faults):
    reps = [StubReplica(slots=2), StubReplica(slots=2)]
    if with_faults:
        reps[0] = FaultyReplica(reps[0], raise_on_step=(20, 24))
        reps[1] = FaultyReplica(reps[1], stall=(44, 56))
    return Fleet(reps, policy="least_loaded", max_queue=64,
                 retry=RetryPolicy(max_attempts=8), step_workers=1,
                 clock=clock)


def serving_scenario():
    cfg = AutoscaleConfig(target_attainment=0.9, min_queue=4,
                          backlog_factor=2.0, cooldown_ticks=1,
                          relax_after_ticks=6,
                          max_actions_per_episode=6)

    Tick.t = 0.0
    base = build_fleet(with_faults=True)
    drive(base)
    rec_b = base.record()
    check_record(rec_b, "baseline fleet")

    Tick.t = 0.0
    fl = build_fleet(with_faults=True)
    ctrl = SloController(fl, cfg, clock=clock)
    drive(fl, controller=ctrl)
    rec_c = fl.record()
    check_record(rec_c, "controlled fleet")
    rec_ctrl = ctrl.record()
    check_record(rec_ctrl, "serving controller recovery")

    # convergence: bounded actuation per episode, episode closed
    check(rec_ctrl["max_actions_in_episode"]
          <= cfg.max_actions_per_episode,
          f"actuation bounded per episode "
          f"({rec_ctrl['max_actions_in_episode']} <= "
          f"{cfg.max_actions_per_episode})")
    check(not rec_ctrl["in_flight"],
          "controller episode closed by end of run")
    check(rec_ctrl["episodes"] >= 1,
          f"controller saw the overload "
          f"({rec_ctrl['episodes']} episode(s))")

    # the death + stall were survived on both sides; MTTR measured
    for label, rec in (("baseline", rec_b), ("controlled", rec_c)):
        check(rec["failovers"] >= 1,
              f"{label}: failover happened "
              f"({rec['failovers']} reclaims)")
        m = rec["mttr"]
        check(m["count"] >= 1 and m["last"] is not None
              and m["last"] >= 0,
              f"{label}: MTTR measured ({m})")

    # the SLO verdict: attainment up, goodput no worse (identical
    # seeded workload, deterministic stub service times)
    att_b, att_c = rec_b["slo_attainment"], rec_c["slo_attainment"]
    check(att_b is not None and att_c is not None
          and att_c > att_b,
          f"controller holds attainment above baseline "
          f"({att_b if att_b is None else round(att_b, 3)} -> "
          f"{att_c if att_c is None else round(att_c, 3)})")
    gp_b, gp_c = (rec_b["goodput_tokens_per_s"],
                  rec_c["goodput_tokens_per_s"])
    check(gp_c >= 0.95 * gp_b,
          f"goodput no worse under control "
          f"({round(gp_b, 3)} -> {round(gp_c, 3)} tokens/tick)")


# ---------------------------------------------------------------------------
# training: stub elastic run — death + torn snapshot, world shrink
# ---------------------------------------------------------------------------

def training_scenario():
    with tempfile.TemporaryDirectory() as d:
        # a "training run" whose step is plain numpy (the controller
        # never looks inside the step; jax enters only through the
        # npz checkpointer, which traces nothing)
        def build_step(world):
            def step(state, batch):
                w = state["w"] - 0.1 * (state["w"] - batch)
                loss = float(np.sum((w - batch) ** 2)) + 1.0 / world
                return {"w": w, "steps": state["steps"] + 1}, loss
            return step

        faults = TrainingFaults(replica_death=(5, 6),
                                torn_checkpoint=(4, 5), seed=0)
        trainer = ElasticTrainer(
            build_step,
            {"w": np.zeros(4, np.float32), "steps": np.int32(0)},
            world=4, ckpt_dir=d, faults=faults,
            config=ElasticConfig(checkpoint_every=2, min_world=1,
                                 max_recoveries=3),
            run="chaos_smoke")
        rng = np.random.RandomState(1)
        batches = [rng.randn(4).astype(np.float32)
                   for _ in range(12)]
        history = trainer.run(10, lambda i: batches[i])
        check(trainer.world == 2,
              f"world shrank 4 -> {trainer.world} on replica death")
        check(trainer.recoveries == 1,
              f"exactly one recovery ({trainer.recoveries})")
        # the snapshot at step 4 was torn (observed-step window 4 is
        # the save after committed step 4): resume fell back to the
        # previous durable snapshot at step 2
        check(trainer.resumed_step == 2,
              f"torn snapshot skipped, resumed at step "
              f"{trainer.resumed_step} (durable), not 4 (torn)")
        check(len(faults.torn_paths) == 1,
              f"the torn-write fault fired ({faults.torn_paths})")
        steps_seen = [row[0] for row in trainer.history]
        check(trainer.history[-1][0] == 9 and len(history) >= 10,
              f"run completed through step 9 (saw {steps_seen})")
        rec = trainer.record()
        check_record(rec, "training controller recovery")
        m = rec["mttr_s"]
        check(m["count"] == 1 and m["last"] is not None
              and m["last"] >= 0,
              f"training MTTR measured ({m})")


# ---------------------------------------------------------------------------
# training: planned preemption — emergency snapshot, deterministic resume
# ---------------------------------------------------------------------------

def preemption_scenario():
    rng = np.random.RandomState(7)
    images = rng.randint(0, 256, (64, 4, 4, 3), np.uint8)
    labels = np.arange(64, dtype=np.int32)
    total_steps = 12

    def make_loader():
        # the portable (checkpointable) stream — jax-light like the
        # rest of this gate; only the npz checkpointer touches jax
        return DataLoader(images, labels, batch_size=8, shuffle=True,
                          seed=11, native=False)

    def build_step(world):
        def step(state, batch):
            imgs, lbls = batch
            g = imgs.mean(axis=(0, 2, 3)).astype(np.float32)
            w = state["w"] - 0.1 * (state["w"] - g)
            loss = float(np.mean((w - g) ** 2)) + 1.0 / world
            return {"w": w}, loss
        return step

    def run_one(d, loader, log, *, guard=None, faults=None,
                resume=False, name="preempt"):
        def data_fn(i):
            imgs, lbls, _ = loader.next_batch()
            log.append([int(v) for v in lbls])
            return imgs, lbls
        tr = ElasticTrainer(
            build_step, {"w": np.zeros(3, np.float32)}, world=4,
            ckpt_dir=d, data=loader, guard=guard, faults=faults,
            resume=resume,
            # keep the numpy step in numpy after a restore (the
            # checkpointer hands back jnp leaves)
            from_host=lambda tree, w: {
                k: np.asarray(v) for k, v in tree.items()},
            config=ElasticConfig(checkpoint_every=4, min_world=1),
            run=name)
        tr.run(total_steps, data_fn)
        return tr

    with tempfile.TemporaryDirectory() as d_und, \
            tempfile.TemporaryDirectory() as d_pre:
        und_log, pre_log = [], []
        und = run_one(d_und, make_loader(), und_log, name="und")
        guard = PreemptionGuard(grace_s=60.0)
        faults = TrainingFaults(preemption=(6, 7), seed=0)
        pre = run_one(d_pre, make_loader(), pre_log, guard=guard,
                      faults=faults, name="preempted")
        check(pre.verdict == "preempted",
              f"preemption honored at the step boundary "
              f"(verdict {pre.verdict!r})")
        check(len(pre.history) == 7,
              f"step 6 still committed before the exit "
              f"({[r[0] for r in pre.history]})")
        rec = pre.record()
        check_record(rec, "preempted trainer recovery")
        check(rec.get("cause") == "preemption"
              and rec.get("preempted") is True,
              f"record names the cause (cause={rec.get('cause')!r})")
        check(rec.get("data_state", {}).get(
            "samples_consumed") == 7 * 8,
            f"record carries the data census "
            f"({rec.get('data_state')})")

        res = run_one(d_pre, make_loader(), pre_log, resume=True,
                      name="resumed")
        check(res.resumed_step == 7,
              f"resumed from the emergency snapshot "
              f"(step {res.resumed_step})")
        res_losses = [l for _, l, _ in pre.history + res.history]
        und_losses = [l for _, l, _ in und.history]
        check(res_losses == und_losses,
              "preempt-resume loss trajectory identical to the "
              "undisturbed run")
        check(pre_log == und_log,
              "consumed-sample-index sequence identical (exactly-once "
              "across the preemption)")
        check(res.resume_overhead_s is not None
              and res.resume_overhead_s >= 0,
              f"resume overhead accounted "
              f"({res.resume_overhead_s})")
        check_record(res.record(), "resumed trainer recovery")


def main():
    serving_scenario()
    training_scenario()
    preemption_scenario()
    if VIOLATIONS:
        print(f"chaos_smoke: {len(VIOLATIONS)} violation(s)")
        return 1
    print("chaos_smoke: all checks passed")
    return 0


if __name__ == "__main__":
    sys.exit(main())
