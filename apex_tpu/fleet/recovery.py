"""Self-healing training: the telemetry→action loop, training side.

The observability plane (PRs 6–10) *reports* — RunSupervisor verdicts,
``checkpoint_saved`` durability watermarks, flight-ring fault events.
This module closes the loop: :class:`ElasticTrainer` drives a
data-parallel training run that SURVIVES a replica death mid-step
instead of 503ing until a human arrives.  On a
:class:`~apex_tpu.fleet.faults.ReplicaFault` (or a configured
supervisor verdict — NaN, stall, divergence) it

1. **shrinks the data axis** to the surviving world size and re-jits
   the step there (``build_step(world)`` — the caller's closure builds
   the mesh over the survivors; ``predivide_factors`` and the DDP
   comm plan rescale automatically at trace time because both read
   the mapped axis size, and the ``ddp_resnet18_o2_hier_world4``
   analysis entry point pins that the shrunk step's collectives lint
   clean against the plan recomputed at the new world);
2. **redistributes ZeRO-1 optimizer shards** onto the survivors
   (:func:`reshard_flat_state`: every flat shard buffer padded for
   the old world is sliced back to its logical length and re-padded
   for the new one);
3. **resumes from the last durable snapshot** — candidates newest
   first, each verified by its content checksum
   (:class:`~apex_tpu.utils.checkpoint.CheckpointCorrupt` skips a
   torn write and falls back), so the ``checkpoint_saved`` events the
   supervisor watermarks are exactly the resume-point oracle;
4. accounts **MTTR** — fault injection to the first committed
   post-recovery step — on the flight ring, the metrics registry, and
   the ``kind: recovery`` JSONL record
   (``observability.exporters.validate_recovery_record``).

While a recovery is in flight the supervisor reports the distinct
degraded-but-live ``recovering`` state
(:meth:`~apex_tpu.observability.supervisor.RunSupervisor.begin_recovery`),
so ``/healthz`` says "being handled" instead of flapping an
orchestrator into a restart loop mid-shrink.

:class:`RecoveryLog` is the shared episode/action/MTTR bookkeeping —
the serving-side controller (:mod:`apex_tpu.fleet.autoscale`) uses the
same log, so both directions of the loop emit one record shape.

Preemption (PR 12).  The most common failure on real TPU fleets is not
a crash but a PLANNED maintenance/preemption event: SIGTERM with a
grace window.  :class:`PreemptionGuard` turns that signal (or a
programmatic :meth:`~PreemptionGuard.preempt` — what the
``TrainingFaults.preemption`` window calls) into a request the trainer
honors at its next STEP BOUNDARY: a coordinated emergency snapshot —
model/optimizer tree plus the data pipeline's exported cursor
(``data_state``) under one content checksum — then a clean exit with
``verdict == "preempted"`` instead of dying mid-write.  A new trainer
built with ``resume=True`` restores the latest durable snapshot AND
the data cursor, so the resumed run's loss trajectory and consumed
sample-index sequence are bitwise-identical to an undisturbed run
(the acceptance pin in tests/test_recovery.py).
"""

from __future__ import annotations

import time
from collections import deque
from typing import Any, Callable, Dict, List, Optional

import numpy as np

from .faults import ReplicaFault

__all__ = ["RECOVERY_ROLES", "RECOVERY_ACTION_KINDS", "RECOVERY_CAUSES",
           "RecoveryError", "RecoveryLog", "PreemptionGuard",
           "ElasticConfig", "ElasticTrainer", "reshard_flat_state"]

# both directions of the telemetry→action loop emit the same
# ``kind: recovery`` record; ``role`` says which controller wrote it
RECOVERY_ROLES = ("training", "serving")

# every action a controller may take (exporters.validate_recovery_record
# rejects records naming anything else; a test pins the two tuples
# equal, the RUN_ANOMALY_KINDS discipline):
# training — world_shrink (drop dead replicas from the data axis),
#   resume (restore the last durable snapshot + re-jit), rollback
#   (verdict-triggered restore at the SAME world), preempt_snapshot
#   (the coordinated emergency snapshot a preemption notice triggers
#   at the next step boundary, within the grace budget);
# serving — admission_tighten/relax (the fleet's bounded-queue knob),
#   class_admission_tighten/relax (PR 19: the same knob scoped to ONE
#   QoS class's queue quota — tighten the lowest-priority class first,
#   never rank 0, so interactive admission survives a batch flood),
#   window_shrink/grow (decode window on replicas that support it),
#   drain/undrain (capacity out/in), cooldown_shorten/extend (the
#   breaker's step-counted cooldowns).
RECOVERY_ACTION_KINDS = (
    "world_shrink", "resume", "rollback", "preempt_snapshot",
    "admission_tighten", "admission_relax",
    "class_admission_tighten", "class_admission_relax",
    "window_shrink", "window_grow",
    "drain", "undrain",
    "cooldown_shorten", "cooldown_extend")

# why a recovery/exit happened, when a record says:
# fault = an injected/real replica death, verdict = a supervisor
# anomaly triggered the rollback, preemption = a planned SIGTERM /
# maintenance notice honored at a step boundary.  Duplicated
# stdlib-side in observability.exporters (tuple-pinned by a test).
RECOVERY_CAUSES = ("fault", "verdict", "preemption")


class RecoveryError(RuntimeError):
    """Recovery itself failed (no survivors to shrink onto, no durable
    snapshot, recovery budget exhausted) — the point where a human IS
    needed and a loud failure beats a silent loop."""


class RecoveryLog:
    """Episode / action / MTTR bookkeeping shared by both controllers.

    An EPISODE opens on the transition into a sick state (fault caught,
    SLO breached) and closes when the controller declares the system
    recovered; every actuation lands as an ACTION inside the current
    episode.  Actions are bounded per episode by the caller's config —
    the anti-oscillation contract ``tests/ci/chaos_smoke.py`` gates —
    and the retained detail list is bounded like the supervisor's
    anomaly list (counts exact forever, details flight-ring
    discipline).  MTTR is fault-to-first-good-step, fed by the caller
    at the instants it owns."""

    def __init__(self, role: str, subject: str,
                 clock: Callable[[], float] = time.perf_counter,
                 max_actions: int = 256, ring=None, registry=None):
        if role not in RECOVERY_ROLES:
            raise ValueError(f"role must be one of {RECOVERY_ROLES}, "
                             f"got {role!r}")
        if not subject:
            raise ValueError("subject must be non-empty")
        self.role = role
        self.subject = str(subject)
        self._clock = clock
        self._t0 = clock()
        self._ring = ring
        self.registry = registry
        self.episodes = 0
        self.actions_total = 0
        self.max_actions_in_episode = 0
        self._actions_this_episode = 0
        self._episode_open = False
        self._episode_t0: Optional[float] = None
        self._actions: deque = deque(maxlen=max_actions)
        self._mttr_count = 0
        self._mttr_sum = 0.0
        self._mttr_last: Optional[float] = None

    @property
    def ring(self):
        from ..observability import flightrec
        return flightrec.resolve(self._ring)

    def _reg(self):
        from ..observability.metrics import get_registry
        return self.registry if self.registry is not None \
            else get_registry()

    @property
    def in_flight(self) -> bool:
        return self._episode_open

    @property
    def actions_this_episode(self) -> int:
        return self._actions_this_episode

    def open_episode(self, reason: str, **attrs):
        """Transition into a sick state (idempotent while open)."""
        if self._episode_open:
            return
        self._episode_open = True
        self.episodes += 1
        self._actions_this_episode = 0
        self._episode_t0 = self._clock()
        self.ring.append("recovery_started", role=self.role,
                         subject=self.subject, reason=reason,
                         episode=self.episodes, **attrs)
        self._reg().counter(
            "recovery_episodes_total",
            help="telemetry→action recovery episodes opened"
        ).labels(role=self.role).inc()

    def action(self, kind: str, **detail) -> Dict[str, Any]:
        """One actuation inside the current episode."""
        if kind not in RECOVERY_ACTION_KINDS:
            raise ValueError(f"unknown recovery action {kind!r} "
                             f"(known: {RECOVERY_ACTION_KINDS})")
        t = self._clock() - self._t0
        if t < 0:
            # catch the PR 11 gotcha AT THE SOURCE: a negative offset
            # means this log's t0 predates the current clock reading —
            # the fleet/controller/trainer was constructed BEFORE an
            # injected tick clock was reset.  Failing here, with the
            # remedy, beats the validator rejecting the finished
            # record later in validate_recovery_record.
            raise ValueError(
                f"RecoveryLog t_s went negative ({t:.6f}s): the log "
                f"was constructed before its clock was reset (an "
                f"injected tick clock rewound past the log's t0). "
                f"Reset the clock FIRST, then build the fleet and "
                f"controller/trainer.")
        # an action before ANY episode (e.g. a relax correcting a
        # mis-tuned construction) carries episode=None — stamping a
        # phantom episode 1 into a record declaring zero episodes
        # would fail its own validator
        ev = {"kind": kind,
              "episode": self.episodes if self.episodes else None,
              "t_s": round(t, 6)}
        ev.update({k: v for k, v in detail.items() if v is not None})
        self.actions_total += 1
        if self._episode_open:
            # only in-episode actuation counts toward the per-episode
            # oscillation bound — the relax actions a controller takes
            # AFTER declaring recovery are the unwinding, not the
            # thrashing the bound exists to catch
            self._actions_this_episode += 1
            self.max_actions_in_episode = max(
                self.max_actions_in_episode,
                self._actions_this_episode)
        self._actions.append(ev)
        self.ring.append("recovery_action", role=self.role,
                         subject=self.subject,
                         **{("action" if k == "kind" else k): v
                            for k, v in ev.items()})
        self._reg().counter(
            "recovery_actions_total",
            help="recovery-controller actuations by kind"
        ).labels(role=self.role, kind=kind).inc()
        return ev

    def close_episode(self, mttr_s: Optional[float] = None, **attrs):
        """The system recovered; ``mttr_s`` is fault-to-first-good-step
        when the caller measured one."""
        if not self._episode_open:
            return
        self._episode_open = False
        if mttr_s is not None:
            mttr_s = float(mttr_s)
            self._mttr_count += 1
            self._mttr_sum += mttr_s
            self._mttr_last = mttr_s
            self._reg().histogram(
                "recovery_mttr_seconds",
                help="fault injection to first post-recovery step"
            ).observe(mttr_s)
        self.ring.append("recovery_done", role=self.role,
                         subject=self.subject, episode=self.episodes,
                         actions=self._actions_this_episode,
                         mttr_s=(round(mttr_s, 6)
                                 if mttr_s is not None else None),
                         **attrs)

    def mttr(self) -> Dict[str, Any]:
        return {"last": self._mttr_last,
                "mean": (self._mttr_sum / self._mttr_count
                         if self._mttr_count else None),
                "count": self._mttr_count}

    def record(self, **extra) -> Dict[str, Any]:
        """One ``kind: recovery`` JSONL payload (enrich through
        ``JsonlExporter``; ``exporters.validate_recovery_record`` pins
        the shape)."""
        rec: Dict[str, Any] = {
            "kind": "recovery", "role": self.role,
            "subject": self.subject,
            "episodes": self.episodes,
            "actions_total": self.actions_total,
            "max_actions_in_episode": self.max_actions_in_episode,
            "actions": [dict(a) for a in self._actions],
            "mttr_s": self.mttr(),
            "in_flight": self._episode_open,
            "duration_s": round(self._clock() - self._t0, 6),
        }
        rec.update(extra)
        return rec


class PreemptionGuard:
    """Turn a preemption notice into a step-boundary snapshot request.

    Real TPU fleets preempt with SIGTERM plus a grace window;
    :meth:`install` registers a handler for it (restoring the previous
    handler on :meth:`uninstall` / context exit), and
    :meth:`preempt` is the programmatic entry point — what the handler
    calls, and what ``TrainingFaults(preemption=...)`` calls in tests.
    The guard never acts on its own: it records the request (first one
    wins, later ones are no-ops), stamps the grace clock, appends a
    ``preemption_requested`` flight-ring event and bumps
    ``preemptions_total``; the :class:`ElasticTrainer` polls
    :attr:`requested` at every step boundary and, with grace left,
    writes the coordinated emergency snapshot (tree + ``data_state``)
    before exiting with a ``preempted`` verdict — with the grace
    budget already exhausted it exits WITHOUT starting a write a
    torn-snapshot cleanup would have to mop up."""

    def __init__(self, grace_s: float = 30.0,
                 clock: Callable[[], float] = time.perf_counter,
                 ring=None, registry=None):
        if grace_s < 0:
            raise ValueError(f"grace_s must be >= 0, got {grace_s}")
        self.grace_s = float(grace_s)
        self._clock = clock
        self._ring = ring
        self.registry = registry
        self._reason: Optional[str] = None
        self._t0: Optional[float] = None
        self._installed: Dict[int, Any] = {}

    @property
    def ring(self):
        from ..observability import flightrec
        return flightrec.resolve(self._ring)

    def _reg(self):
        from ..observability.metrics import get_registry
        return self.registry if self.registry is not None \
            else get_registry()

    # -- the request --------------------------------------------------------
    def preempt(self, reason: str = "programmatic") -> None:
        """Request a coordinated shutdown (idempotent: the FIRST
        request starts the grace clock; repeats are no-ops)."""
        if self._reason is not None:
            return
        self._reason = str(reason) or "programmatic"
        self._t0 = self._clock()
        self.ring.append("preemption_requested", reason=self._reason,
                         grace_s=self.grace_s)
        self._reg().counter(
            "preemptions_total",
            help="preemption notices received (signal or programmatic)"
        ).inc()

    @property
    def requested(self) -> bool:
        return self._reason is not None

    @property
    def reason(self) -> Optional[str]:
        return self._reason

    @property
    def requested_at(self) -> Optional[float]:
        """Clock reading of the first :meth:`preempt` call (the MTTR
        window's left edge), ``None`` before any request."""
        return self._t0

    def grace_remaining(self) -> float:
        """Seconds of grace budget left (the full budget before any
        request; clamped at 0)."""
        if self._t0 is None:
            return self.grace_s
        return max(0.0, self.grace_s - (self._clock() - self._t0))

    def reset(self) -> None:
        """Clear the request (a resumed test harness reusing one
        guard; production resumes build a fresh process anyway)."""
        self._reason = None
        self._t0 = None

    # -- the signal surface -------------------------------------------------
    def _handle(self, signum, frame):
        self.preempt(f"signal {signum}")

    def install(self, signals=None) -> "PreemptionGuard":
        """Register the handler (default: SIGTERM — what TPU
        maintenance/preemption sends); previous handlers are kept and
        restored by :meth:`uninstall`.  Main-thread only, per the
        stdlib signal contract."""
        import signal as _signal
        if signals is None:
            signals = (_signal.SIGTERM,)
        for s in signals:
            if s in self._installed:
                # already ours: re-installing would record OUR handler
                # as "previous" and uninstall could never restore the
                # original one
                continue
            self._installed[s] = _signal.signal(s, self._handle)
        return self

    def uninstall(self) -> None:
        import signal as _signal
        for s, prev in self._installed.items():
            _signal.signal(s, prev)
        self._installed = {}

    def __enter__(self) -> "PreemptionGuard":
        return self.install()

    def __exit__(self, *exc) -> None:
        self.uninstall()


def reshard_flat_state(tree: Any, total: int, old_world: int,
                       new_world: int) -> Any:
    """Redistribute ZeRO flat optimizer shards onto a resized world.

    The flat-buffer ZeRO state (``amp.zero_optimizer_specs``) pads
    every 1-D shard buffer — fp32 masters and the elementwise inner
    optimizer's moment buffers — to a multiple of the shard population
    so the device-concat global splits evenly.  ``total`` is the
    logical (unpadded) element count
    (``opt_state.masters.layout.total``); every 1-D leaf of exactly
    the old padded length is sliced back to ``total`` and
    zero-re-padded for the new population.  Scalars and non-flat
    leaves pass through unchanged.  Host-side numpy math — the
    resharded tree is handed to the re-jitted step, whose shard_map
    in_specs place the new shards on the survivors.

    ``old_world`` / ``new_world`` are the shard POPULATIONS, which is
    what the buffers were padded for: the full axis size for ZeRO-1,
    the ICI slice size (``layout.zero_ici``) for ZeRO-2/3 — an 8->4
    world shrink at ici 4->2 resharding stage-2/3 state passes (4, 2)
    here while the ZeRO-1 leg of the same shrink passes (8, 4).  The
    math is identical: stage 2/3 state is replicated across slices, so
    redistributing one slice's padding redistributes them all."""
    if old_world < 1 or new_world < 1:
        raise ValueError(f"world sizes must be >= 1, got {old_world} "
                         f"and {new_world}")
    import jax
    old_pad = total + (-total) % old_world
    new_pad = total + (-total) % new_world

    def fix(leaf):
        arr = np.asarray(leaf)
        if arr.ndim == 1 and arr.shape[0] == old_pad:
            return np.pad(arr[:total], (0, new_pad - total))
        return arr

    return jax.tree_util.tree_map(fix, tree)


class ElasticConfig:
    """Recovery policy knobs.

    - ``checkpoint_every``: snapshot cadence in committed steps (the
      recovery controller can only resume from what was saved);
    - ``shrink_factor`` / ``min_world``: a replica death divides the
      world by ``shrink_factor`` (data-parallel replicas die in
      slices), never below ``min_world`` — shrinking past it raises
      :class:`RecoveryError` instead of limping on;
    - ``max_recoveries``: total recovery budget for the run (a run
      that keeps dying needs a human, not an infinite loop);
    - ``recover_on_verdicts``: supervisor anomaly kinds that trigger a
      rollback-restore (NaN'd loss, stall, replica divergence);
      ``shrink_on_verdict`` additionally shrinks the world on those —
      off by default, since a NaN is usually numerics, not hardware.
    """

    def __init__(self, checkpoint_every: int = 1,
                 shrink_factor: int = 2,
                 min_world: int = 1,
                 max_recoveries: int = 8,
                 recover_on_verdicts=("nan", "stall",
                                      "replica_divergence"),
                 shrink_on_verdict: bool = False):
        if checkpoint_every < 1:
            raise ValueError(f"checkpoint_every must be >= 1, got "
                             f"{checkpoint_every}")
        if shrink_factor < 2:
            raise ValueError(f"shrink_factor must be >= 2, got "
                             f"{shrink_factor}")
        if min_world < 1:
            raise ValueError(f"min_world must be >= 1, got {min_world}")
        if max_recoveries < 1:
            raise ValueError(f"max_recoveries must be >= 1, got "
                             f"{max_recoveries}")
        self.checkpoint_every = checkpoint_every
        self.shrink_factor = shrink_factor
        self.min_world = min_world
        self.max_recoveries = max_recoveries
        self.recover_on_verdicts = tuple(recover_on_verdicts)
        self.shrink_on_verdict = shrink_on_verdict


class ElasticTrainer:
    """Elastic data-parallel run harness: the job survives the fleet.

    The caller supplies the world-parameterized pieces; the harness
    owns the loop, the snapshots, and the recovery policy::

        trainer = ElasticTrainer(
            build_step=build,          # build(world) -> jitted step
            state=state0,              # live state for `world`
            world=8, ckpt_dir=d,
            to_host=to_host,           # state -> canonical host tree
            from_host=from_host,       # (tree, world) -> live state
            supervisor=sup, faults=faults)
        history = trainer.run(steps, data_fn)   # data_fn(i) -> batch

    Contracts:

    - ``build_step(world)`` returns ``step(state, batch) ->
      (new_state, loss)`` jitted over a mesh of the first ``world``
      devices; the harness re-invokes it after every shrink (the
      predivide factors and the comm plan rescale at trace time);
    - ``to_host(state)`` produces a WORLD-INDEPENDENT canonical host
      tree (for ZeRO-1, slice the padded flat shards back to their
      logical length — :func:`reshard_flat_state` composed with the
      identity is the common shape); ``from_host(tree, world)``
      re-shards it for ``world``.  Defaults are plain ``np.asarray``
      round-trips, correct for fully replicated DDP state;
    - the harness calls ``faults.check_step`` AFTER the device math
      but BEFORE committing the result — an injected
      :class:`ReplicaFault` therefore models a mid-step death whose
      partial results are abandoned, exactly what resuming from the
      last durable snapshot assumes;
    - a committed step closes any open MTTR window (fault-to-first-
      good-step), feeds the supervisor (whose configured verdicts
      trigger rollback), and snapshots on the ``checkpoint_every``
      cadence.

    ``history`` rows are ``(step, loss, world)``; ``record()`` emits
    the ``kind: recovery`` JSONL payload with the training extras
    (current world, resumed step, recovery count)."""

    def __init__(self, build_step: Callable[[int], Callable],
                 state: Any, *, world: int, ckpt_dir: str,
                 to_host: Optional[Callable[[Any], Any]] = None,
                 from_host: Optional[Callable[[Any, int], Any]] = None,
                 supervisor=None, faults=None,
                 config: Optional[ElasticConfig] = None,
                 checkpointer=None, run: str = "elastic",
                 clock: Callable[[], float] = time.perf_counter,
                 ring=None, registry=None,
                 data=None, guard: Optional[PreemptionGuard] = None,
                 resume: bool = False):
        if world < 1:
            raise ValueError(f"world must be >= 1, got {world}")
        self.build_step = build_step
        self.world = int(world)
        self.ckpt_dir = ckpt_dir
        self.config = config or ElasticConfig()
        self.supervisor = supervisor
        self.faults = faults
        self._clock = clock
        if checkpointer is None:
            from ..utils import checkpoint as checkpointer
        self._ckpt = checkpointer
        self._to_host = to_host if to_host is not None else (
            lambda st: _np_tree(st))
        self._from_host = from_host if from_host is not None else (
            lambda tree, w: tree)
        self._state = state
        self._step = 0
        self._step_fn = build_step(self.world)
        self.recoveries = 0
        self.resumed_step: Optional[int] = None
        self.history: List[tuple] = []
        self.log = RecoveryLog("training", run, clock=clock,
                               ring=ring, registry=registry)
        self._registry = registry
        self._mttr_t0: Optional[float] = None
        # data pipeline with the state protocol (state_dict /
        # load_state_dict, e.g. apex_tpu.data.DataLoader): its cursor
        # is folded into every snapshot/restore so the sample stream
        # resumes bitwise-identically
        self.data = data
        self.guard = guard
        # the trainer's exit verdict: None while running, "completed"
        # after a full run() call, "preempted" after a guard-honoring
        # exit; cause names why the LAST recovery/exit happened
        self.verdict: Optional[str] = None
        self.cause: Optional[str] = None
        # resume accounting:
        # wall cost of the resume=True restore, and the clock reading
        # of the first COMMITTED step of this trainer — with the
        # guard's requested_at, the preempt→first-good-step MTTR
        self.resume_overhead_s: Optional[float] = None
        self.first_commit_at: Optional[float] = None
        self._last_saved_step: Optional[int] = None
        if (guard is not None and faults is not None
                and getattr(faults, "guard", None) is None):
            # auto-wire: a TrainingFaults preemption window fires into
            # THIS run's guard unless the harness bound its own
            faults.guard = guard
        if resume:
            self._resume_from_disk()

    # -- snapshots ----------------------------------------------------------
    def _save(self):
        self._last_saved_step = self._step
        tree = self._to_host(self._state)
        if self.data is not None:
            # the snapshot names its exact data cursor, under the same
            # content checksum as the tree — tree and stream can never
            # restore out of step with each other
            path = self._ckpt.save_checkpoint(
                self.ckpt_dir, self._step, tree,
                data_state=self.data.state_dict())
        else:
            path = self._ckpt.save_checkpoint(self.ckpt_dir,
                                              self._step, tree)
        if self.faults is not None:
            # torn-write injection happens AFTER the atomic rename —
            # the save-time checkpoint_saved event truthfully named a
            # snapshot that verified; the tear is what restore-time
            # verification exists to catch
            self.faults.after_checkpoint(path)
        return path

    def _restore_latest_durable(self):
        """Newest snapshot that verifies, restored into the canonical
        host template (plus its data_state when a pipeline is
        attached); torn snapshots are skipped with a ring note."""
        template = self._to_host(self._state)
        from ..utils.checkpoint import CheckpointCorrupt
        for step in reversed(self._ckpt.available_steps(self.ckpt_dir)):
            try:
                tree = self._ckpt.restore_checkpoint(
                    self.ckpt_dir, template, step=step)
            except CheckpointCorrupt as e:
                self.log.ring.append("snapshot_skipped", step=step,
                                     reason=str(e))
                continue
            ds = None
            if self.data is not None:
                loader = getattr(self._ckpt, "load_data_state", None)
                ds = loader(self.ckpt_dir, step=step) \
                    if loader is not None else None
                if ds is None:
                    # LOUD, not a silent divergence: a pipeline is
                    # attached but this snapshot cannot say where its
                    # sample stream stood
                    raise RecoveryError(
                        f"snapshot step {step} in {self.ckpt_dir!r} "
                        f"carries no data_state but a data pipeline "
                        f"is attached — the sample stream cannot "
                        f"resume deterministically (save through this "
                        f"trainer, or detach the pipeline)")
            return step, tree, ds
        raise RecoveryError(
            f"no durable snapshot in {self.ckpt_dir!r} — every "
            f"candidate failed content verification")

    def _apply_restore(self, step: int, tree: Any, ds) -> None:
        self._state = self._from_host(tree, self.world)
        self._step = step
        self.resumed_step = step
        if ds is not None:
            self.data.load_state_dict(ds)

    def _resume_from_disk(self) -> bool:
        """``resume=True`` construction: continue from the newest
        durable snapshot (tree + data cursor) when one exists; a fresh
        directory is just a fresh run."""
        if not self._ckpt.available_steps(self.ckpt_dir):
            return False
        t0 = self._clock()
        step, tree, ds = self._restore_latest_durable()
        self._apply_restore(step, tree, ds)
        self.resume_overhead_s = self._clock() - t0
        self.log.action("resume", step=step, world=self.world,
                        resumed_from="disk")
        self._reg_world()
        return True

    # -- recovery -----------------------------------------------------------
    def _recover(self, reason: str, shrink: bool,
                 cause: str = "fault"):
        cfg = self.config
        self.cause = cause
        if self.recoveries >= cfg.max_recoveries:
            raise RecoveryError(
                f"recovery budget exhausted ({cfg.max_recoveries}); "
                f"last failure: {reason}")
        self.recoveries += 1
        self.log.open_episode(reason, world=self.world,
                              step=self._step)
        if self.supervisor is not None:
            self.supervisor.begin_recovery(reason)
        try:
            old_world = self.world
            if shrink:
                new_world = max(cfg.min_world,
                                self.world // cfg.shrink_factor)
                if new_world == self.world:
                    raise RecoveryError(
                        f"no survivors to shrink onto (world "
                        f"{self.world} is already min_world "
                        f"{cfg.min_world}); last failure: {reason}")
                self.world = new_world
                self.log.action("world_shrink", world_from=old_world,
                                world_to=new_world)
            step, tree, ds = self._restore_latest_durable()
            if shrink:
                # the mesh changed: re-jit the step on the survivors
                # (predivide factors + comm plan rescale at trace time)
                self._step_fn = self.build_step(self.world)
            self._apply_restore(step, tree, ds)
            self.log.action("resume" if shrink else "rollback",
                            step=step, world=self.world)
            if self.supervisor is not None:
                # the run rewound: reset the progress watermark so a
                # long replay below the old high-water mark cannot
                # fire a spurious stall verdict (and a second,
                # pointless rollback)
                self.supervisor.rewind(step)
            self._reg_world()
        finally:
            if self.supervisor is not None:
                self.supervisor.end_recovery()

    def _reg_world(self):
        from ..observability.metrics import get_registry
        reg = (self._registry if self._registry is not None
               else get_registry())
        reg.gauge("elastic_world_size",
                  help="current data-parallel world of the elastic run"
                  ).labels(run=self.log.subject).set(float(self.world))

    # -- preemption ---------------------------------------------------------
    def _preempt_exit(self):
        """Honor a preemption request at the step boundary: with grace
        budget left, write the coordinated emergency snapshot (tree +
        data cursor, one checksum) and exit ``preempted``; with the
        budget already gone, exit WITHOUT starting a write — the last
        durable snapshot stays the resume point, and nobody has to
        mop up a torn one."""
        g = self.guard
        left = g.grace_remaining()
        snapshotted = False
        if left > 0:
            # the cadence save at the end of the last iteration may
            # already cover this exact step — don't burn grace-window
            # time re-serializing identical content
            reused = self._last_saved_step == self._step
            if not reused:
                self._save()
            snapshotted = True
            self.log.action("preempt_snapshot", step=self._step,
                            world=self.world,
                            grace_left_s=round(left, 6),
                            reused_cadence_save=reused)
        else:
            self.log.ring.append("preemption_grace_exhausted",
                                 step=self._step, reason=g.reason)
        self.cause = "preemption"
        self.verdict = "preempted"
        if self.supervisor is not None:
            self.supervisor.mark_preempted(step=self._step,
                                           reason=g.reason)
        self.log.ring.append("preempted", step=self._step,
                             world=self.world, reason=g.reason,
                             snapshot=snapshotted)

    # -- the loop -----------------------------------------------------------
    def run(self, num_steps: int,
            data_fn: Optional[Callable[[int], Any]] = None
            ) -> List[tuple]:
        """Drive the run to ``num_steps`` committed steps, recovering
        through any scheduled faults; returns the history rows
        ``(step, loss, world)`` committed by THIS call.

        ``data_fn(i) -> batch`` produces the batch for run-step ``i``;
        when omitted, the attached ``data=`` pipeline feeds the run
        (``next_batch()``; its checkpointed cursor — not the step
        index — is then what makes the stream deterministic across
        preemption, rollback, and elastic world changes).  A
        ``PreemptionGuard`` request is honored at the next step
        boundary: emergency snapshot within the grace budget, then a
        clean exit with ``verdict == "preempted"``."""
        cfg = self.config
        if data_fn is None:
            if self.data is None:
                raise ValueError(
                    "run() needs data_fn or a data= pipeline")
            data_fn = lambda i: self.data.next_batch()[:2]  # noqa: E731
        self.verdict = None
        out: List[tuple] = []
        if not self._ckpt.available_steps(self.ckpt_dir):
            self._save()                  # step-0 fallback snapshot
        while self._step < num_steps:
            if self.guard is not None and self.guard.requested:
                self._preempt_exit()
                return out
            batch = data_fn(self._step)
            t0 = self._clock()
            try:
                new_state, loss = self._step_fn(self._state, batch)
                loss = float(loss)        # host fetch = commit point
                if self.faults is not None:
                    self.faults.check_step(self._step)
            except ReplicaFault as e:
                if self._mttr_t0 is None:
                    # a second death before the first committed
                    # post-recovery step EXTENDS the same MTTR window
                    # (the fleet-side contract) — never restart it
                    self._mttr_t0 = self._clock()
                self._recover(f"replica death: {e}", shrink=True,
                              cause="fault")
                continue
            dt = self._clock() - t0
            self._state = new_state
            if self.first_commit_at is None:
                self.first_commit_at = self._clock()
            row = (self._step, loss, self.world)
            self.history.append(row)
            out.append(row)
            self._step += 1
            if self._mttr_t0 is not None:
                # first committed step after a recovery closes MTTR
                self.log.close_episode(
                    mttr_s=self._clock() - self._mttr_t0,
                    step=self._step, world=self.world)
                self._mttr_t0 = None
            elif self.log.in_flight:
                self.log.close_episode(step=self._step,
                                       world=self.world)
            anomalies = []
            if self.supervisor is not None:
                anomalies = self.supervisor.observe_step(
                    step=self._step, loss=loss, step_time_s=dt)
            trigger = [a for a in anomalies
                       if a.get("kind") in cfg.recover_on_verdicts]
            if trigger:
                # verdict-triggered rollback: do NOT snapshot the sick
                # state — restore the last durable one instead
                if self._mttr_t0 is None:
                    self._mttr_t0 = self._clock()
                self._recover(
                    f"supervisor verdict: "
                    f"{trigger[0].get('kind')}",
                    shrink=cfg.shrink_on_verdict, cause="verdict")
                continue
            if self._step % cfg.checkpoint_every == 0:
                self._save()
        self.verdict = "completed"
        return out

    def record(self, **extra) -> Dict[str, Any]:
        """The training-side ``kind: recovery`` record (the envelope
        plus ``cause``/``preempted`` and — when a pipeline is
        attached — its ``data_state`` census, so the record names the
        exact sample-stream position the run stood at)."""
        fields: Dict[str, Any] = dict(
            world=self.world, recoveries=self.recoveries,
            resumed_step=self.resumed_step,
            preempted=(self.verdict == "preempted"))
        if self.cause is not None:
            fields["cause"] = self.cause
        if self.data is not None:
            fields["data_state"] = self.data.state_dict()
        fields.update(extra)
        return self.log.record(**fields)


def _np_tree(tree: Any) -> Any:
    import jax
    return jax.tree_util.tree_map(np.asarray, tree)
