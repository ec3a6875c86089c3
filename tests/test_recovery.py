"""Elastic fault-tolerant training (fleet/recovery.py): a replica can
die mid-step and the job continues — shrink the data axis, re-jit on
the survivors, redistribute ZeRO-1 shards, resume from the last
checksum-durable snapshot.

The acceptance pin: the post-recovery loss trajectory must match an
undisturbed run at the shrunk world size, both resumed from the same
snapshot — same restored state, same batches, same re-jitted step, so
the documented tolerance is float round-off (rtol 1e-6; empirically
bitwise on the CPU mesh).  Fault timelines use the seeded
half-open-window harness (fleet/faults.py TrainingFaults), so every
death/tear lands at an exact observed step."""

import os
import signal
import time

import numpy as np
import pytest
import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from apex_tpu import amp, nn, optimizers, parallel
from apex_tpu import observability as obs
from apex_tpu.data import DataLoader
from apex_tpu.fleet import (ElasticConfig, ElasticTrainer,
                            PreemptionGuard, RecoveryError,
                            TrainingFaults, reshard_flat_state)
from apex_tpu.nn import functional as F
from apex_tpu.observability.exporters import (JsonlExporter,
                                              validate_recovery_record)
from apex_tpu.utils import checkpoint as ckpt


def _np_tree(tree):
    return jax.tree_util.tree_map(np.asarray, tree)


# -- plain-DDP elastic run (replicated state, SGD) -----------------------

def _ddp_build_step(model, ddp, lr=0.05):
    def build_step(world):
        mesh = Mesh(np.array(jax.devices()[:world]), ("data",))

        def step(state, batch):
            params, nan_steps = state
            xb, yb = batch

            def loss_fn(p):
                out, _ = model.apply(p, xb, train=True)
                return F.cross_entropy(out, yb)

            loss, g = jax.value_and_grad(loss_fn)(params)
            g = ddp.allreduce_grads(g)
            params = jax.tree_util.tree_map(
                lambda p, gg: p - lr * gg, params, g)
            loss = lax.pmean(loss, "data")
            # in-graph numerics residue: counts the nonfinite losses
            # this state has EVER trained through — the "no stale
            # pre-fault numerics state" probe (a rolled-back state
            # must not remember the poisoned step)
            nan_steps = nan_steps + (
                ~jnp.isfinite(loss)).astype(jnp.int32)
            return (params, nan_steps), loss

        return jax.jit(jax.shard_map(
            step, mesh=mesh,
            in_specs=(P(), (P("data"), P("data"))),
            out_specs=(P(), P()), check_vma=False))
    return build_step


def _mlp():
    net = nn.Sequential([nn.Flatten(), nn.Linear(24, 16), nn.ReLU(),
                         nn.Linear(16, 10)])
    params, _ = net.init(jax.random.PRNGKey(0))
    return net, params


def _batches(n, b=16, d=24, seed=3):
    rng = np.random.RandomState(seed)
    return [(jnp.asarray(rng.randn(b, d), jnp.float32),
             jnp.asarray(rng.randint(0, 10, b), jnp.int32))
            for _ in range(n)]


def test_replica_death_shrinks_world_and_matches_undisturbed(tmp_path):
    model, params = _mlp()
    ddp = parallel.DistributedDataParallel(model)
    build = _ddp_build_step(model, ddp)
    state0 = (params, jnp.zeros((), jnp.int32))
    batches = _batches(12)
    ring = obs.EventRing(256)
    sup = obs.RunSupervisor("elastic_test", ring=ring,
                            registry=obs.MetricsRegistry())

    faults = TrainingFaults(replica_death=(5, 6), seed=0, ring=ring)
    trainer = ElasticTrainer(
        build, state0, world=8, ckpt_dir=str(tmp_path),
        to_host=_np_tree, supervisor=sup, faults=faults,
        config=ElasticConfig(checkpoint_every=2, min_world=1),
        ring=ring, registry=obs.MetricsRegistry(), run="elastic_test")
    trainer.run(10, lambda i: batches[i])

    assert trainer.world == 4
    assert trainer.recoveries == 1
    assert trainer.resumed_step == 4          # last durable snapshot
    # run completed: committed steps 0..9, with 4..9 replayed/continued
    # on the shrunk world
    assert trainer.history[-1][0] == 9
    post = [(s, loss) for s, loss, w in trainer.history if w == 4]
    assert [s for s, _ in post] == list(range(4, 10))

    # undisturbed shrunk-world run from the SAME snapshot: restore the
    # step-4 snapshot, re-jit at world 4, run the same batches —
    # trajectories must match within the documented tolerance
    template = _np_tree(state0)
    restored = ckpt.restore_checkpoint(str(tmp_path), template, step=4)
    step4 = build(4)
    st = restored
    undisturbed = []
    for i in range(4, 10):
        st, loss = step4(st, batches[i])
        undisturbed.append(float(loss))
    np.testing.assert_allclose([loss for _, loss in post],
                               undisturbed, rtol=1e-6)

    # MTTR + record + ring story
    rec = JsonlExporter.enrich(trainer.record())
    assert validate_recovery_record(rec) == []
    assert rec["world"] == 4 and rec["recoveries"] == 1
    assert rec["mttr_s"]["count"] == 1 and rec["mttr_s"]["last"] >= 0
    kinds = [ev["kind"] for ev in ring.snapshot()]
    for k in ("fault_injected", "recovery_started", "recovery_action",
              "recovery_done", "run_recovery_begin",
              "run_recovery_end"):
        assert k in kinds, k
    acts = [a["kind"] for a in rec["actions"]]
    assert acts == ["world_shrink", "resume"]
    # the supervisor exits recovery LIVE (no 503 flap mid-shrink)
    ok, detail = sup.health_check()
    assert ok
    assert sup.status()["recoveries"] == 1


def test_torn_snapshot_skipped_falls_back_to_durable(tmp_path):
    model, params = _mlp()
    ddp = parallel.DistributedDataParallel(model)
    build = _ddp_build_step(model, ddp)
    state0 = (params, jnp.zeros((), jnp.int32))
    batches = _batches(12)
    ring = obs.EventRing(256)
    # checkpoint_saved telemetry goes to the PROCESS ring (the
    # supervisor watermark contract) — point it at this test's ring
    # so the whole story lands in one place
    prev_ring = obs.get_ring()
    obs.set_ring(ring)

    # snapshot cadence 2 -> snapshots at observed steps 2 and 4; the
    # torn window [4, 5) corrupts the step-4 write AFTER its atomic
    # rename (out-of-band tear), the death at 5 forces a resume: the
    # controller must skip the torn snapshot and fall back to step 2
    faults = TrainingFaults(replica_death=(5, 6),
                            torn_checkpoint=(4, 5), seed=0, ring=ring)
    trainer = ElasticTrainer(
        build, state0, world=8, ckpt_dir=str(tmp_path),
        to_host=_np_tree, faults=faults,
        config=ElasticConfig(checkpoint_every=2, min_world=1),
        ring=ring, registry=obs.MetricsRegistry(), run="torn")
    try:
        trainer.run(8, lambda i: batches[i])
    finally:
        obs.set_ring(prev_ring)
    assert trainer.resumed_step == 2
    assert trainer.world == 4
    assert trainer.history[-1][0] == 7

    events = ring.snapshot()
    skipped = [ev for ev in events if ev["kind"] == "snapshot_skipped"]
    assert [ev["step"] for ev in skipped] == [4]
    # every checkpoint_saved event named a snapshot that verified at
    # durability time (the tear happened out-of-band AFTER the atomic
    # rename); the replay past step 4 re-saved it, healing the file —
    # so by end of run every on-disk snapshot verifies again and the
    # step-4 path carries TWO save events (the torn original + the
    # healing re-save after the fallback resume)
    saved = [ev["path"] for ev in events
             if ev["kind"] == "checkpoint_saved"]
    assert faults.torn_paths and set(faults.torn_paths) <= set(saved)
    assert saved.count(faults.torn_paths[0]) == 2
    for step in ckpt.available_steps(str(tmp_path)):
        ckpt.verify_checkpoint(str(tmp_path), step)
    assert ckpt.latest_durable_step(str(tmp_path)) \
        == max(ckpt.available_steps(str(tmp_path)))


def test_nan_verdict_rolls_back_with_no_stale_numerics(tmp_path):
    model, params = _mlp()
    ddp = parallel.DistributedDataParallel(model)
    build = _ddp_build_step(model, ddp)
    state0 = (params, jnp.zeros((), jnp.int32))
    batches = _batches(12)
    poisoned = {"done": False}

    def data_fn(i):
        x, y = batches[i]
        if i == 6 and not poisoned["done"]:
            # one-shot poison: the first visit to step 6 trains
            # through a NaN batch; the post-rollback replay is clean
            poisoned["done"] = True
            return x.at[0, 0].set(jnp.nan), y
        return x, y

    sup = obs.RunSupervisor("nan_rollback", ring=obs.EventRing(128),
                            registry=obs.MetricsRegistry())
    trainer = ElasticTrainer(
        build, state0, world=8, ckpt_dir=str(tmp_path),
        to_host=_np_tree, supervisor=sup,
        config=ElasticConfig(checkpoint_every=2, min_world=1),
        registry=obs.MetricsRegistry(), run="nan_rollback")
    trainer.run(10, data_fn)

    # the verdict triggered a rollback at the SAME world (a NaN is
    # numerics, not hardware)
    assert trainer.world == 8
    assert trainer.recoveries == 1
    assert trainer.resumed_step == 6
    rec = trainer.record()
    assert [a["kind"] for a in rec["actions"]] == ["rollback"]
    # the NaN was observed once (history keeps the honest record) ...
    nan_rows = [row for row in trainer.history
                if not np.isfinite(row[1])]
    assert len(nan_rows) == 1 and nan_rows[0][0] == 6
    # ... but the final state carries NO stale pre-fault numerics:
    # the in-graph nonfinite counter of the committed state is 0 —
    # the rolled-back state never trained through the poison
    _, nan_steps = trainer._state
    assert int(nan_steps) == 0
    assert float(trainer.history[-1][1]) == pytest.approx(
        float(trainer.history[-1][1]))  # finite (not NaN)
    assert np.isfinite(trainer.history[-1][1])
    assert sup.status()["anomaly_counts"]["nan"] == 1
    ok, _ = sup.health_check()
    assert ok


# -- ZeRO-1 shard redistribution -----------------------------------------

def test_zero1_shards_redistribute_onto_survivors(tmp_path):
    net = nn.Sequential([nn.Flatten(), nn.Linear(24, 10)])
    model, optimizer = amp.initialize(
        net, optimizers.FusedAdam(lr=1e-2), opt_level="O2",
        verbosity=0, hard_override=True)
    params, _ = model.init(jax.random.PRNGKey(0))
    ospecs = amp.zero_optimizer_specs(optimizer, params, "data")
    total = optimizer.init(params).masters.layout.total
    batches = _batches(10)

    def build_step(world):
        mesh = Mesh(np.array(jax.devices()[:world]), ("data",))

        def step(state, batch):
            p, ost = state
            xb, yb = batch

            def loss_fn(pp):
                out, _ = model.apply(pp, xb, train=True)
                return F.cross_entropy(out, yb)

            loss, g = amp.scaled_grad(loss_fn, p, ost)
            # no pre-allreduce: ZeRO-1 reduce-scatters inside step()
            p, ost, _ = optimizer.step(p, ost, g)
            return (p, ost), lax.pmean(loss, "data")

        return jax.jit(jax.shard_map(
            step, mesh=mesh,
            in_specs=((P(), ospecs), (P("data"), P("data"))),
            out_specs=((P(), ospecs), P()), check_vma=False))

    def init_state(world):
        mesh = Mesh(np.array(jax.devices()[:world]), ("data",))
        opt0 = jax.jit(jax.shard_map(
            lambda pp: optimizer.init(pp, zero_axis="data"),
            mesh=mesh, in_specs=(P(),), out_specs=ospecs,
            check_vma=False))(params)
        return (params, opt0)

    def to_host(state):
        # canonical = world-independent: slice the flat shard buffers
        # back to their logical length (pad-for-world-1 == unpadded);
        # the padding world is inferred from the buffer length
        p, ost = _np_tree(state)
        buf_len = ost.masters.buf.shape[0]
        old_world = next(w for w in (8, 4, 2, 1)
                         if buf_len == total + (-total) % w)
        return (p, reshard_flat_state(ost, total, old_world, 1))

    def from_host(tree, world):
        p, ost = tree
        return (p, reshard_flat_state(ost, total, 1, world))

    faults = TrainingFaults(replica_death=(3, 4), seed=0)
    trainer = ElasticTrainer(
        build_step, init_state(8), world=8, ckpt_dir=str(tmp_path),
        to_host=to_host, from_host=from_host, faults=faults,
        config=ElasticConfig(checkpoint_every=1, min_world=1),
        registry=obs.MetricsRegistry(), run="zero_elastic")
    trainer.run(7, lambda i: batches[i])

    assert trainer.world == 4
    assert trainer.resumed_step == 3
    # the flat optimizer shards were REDISTRIBUTED: the live state's
    # master buffer is padded for the 4-survivor world, not the
    # original 8
    _, ost = trainer._state
    assert ost.masters.buf.shape[0] == total + (-total) % 4
    assert trainer.history[-1][0] == 6

    # undisturbed shrunk-world run from the same snapshot
    template = to_host(init_state(8))
    restored = ckpt.restore_checkpoint(str(tmp_path), template, step=3)
    st = from_host(restored, 4)
    step4 = build_step(4)
    undisturbed = []
    for i in range(3, 7):
        st, loss = step4(st, batches[i])
        undisturbed.append(float(loss))
    post = [loss for s, loss, w in trainer.history if w == 4]
    np.testing.assert_allclose(post, undisturbed, rtol=1e-6)


def _retree(ost, specs):
    # transplant the state's leaves into the spec tree's treedef: the
    # ZeRO-2/3 layout (zero_ici) is FlatMasters aux data, so a state
    # resharded for a different world must also carry the new world's
    # layout before shard_map will accept it against the new specs
    return jax.tree_util.tree_unflatten(
        jax.tree_util.tree_structure(specs),
        jax.tree_util.tree_leaves(ost))


def _dedup_slices(ost, padded, dcn):
    # stage-2/3 host view is the device-concat over the FULL axis: the
    # padded in-slice concat repeated dcn times (slices hold bitwise
    # identical shards after the DCN reduce) — keep one copy
    def fix(a):
        if getattr(a, "ndim", 0) == 1 and a.shape[0] == dcn * padded:
            return a[:padded]
        return a
    return jax.tree_util.tree_map(fix, ost)


def _tile_slices(ost, padded, dcn):
    # inverse of _dedup_slices: rebuild the device-concat global by
    # repeating the slice concat across the DCN dimension
    def fix(a):
        if getattr(a, "ndim", 0) == 1 and a.shape[0] == padded:
            return np.concatenate([np.asarray(a)] * dcn)
        return a
    return jax.tree_util.tree_map(fix, ost)


def test_zero2_shards_redistribute_onto_survivors_hierarchical(tmp_path):
    # 8 -> 4 world shrink where the ICI slice shrinks with it (4 -> 2):
    # stage-2 shards live on the slice, so the redistribution population
    # is layout.zero_ici, not the world — reshard_flat_state gets
    # (old_ici, new_ici) and the state is re-treed onto the new layout
    net = nn.Sequential([nn.Flatten(), nn.Linear(24, 10)])
    model, optimizer = amp.initialize(
        net, optimizers.FusedAdam(lr=1e-2), opt_level="O2",
        verbosity=0, hard_override=True)
    params, _ = model.init(jax.random.PRNGKey(0))
    total = optimizer.init(params).masters.layout.total
    batches = _batches(10)

    def ici_of(world):
        return max(world // 2, 1)

    def ospecs_for(world):
        return amp.zero_optimizer_specs(
            optimizer, params, "data", zero_stage=2,
            zero_ici_size=ici_of(world))

    def build_step(world):
        mesh = Mesh(np.array(jax.devices()[:world]), ("data",))
        ospecs = ospecs_for(world)

        def step(state, batch):
            p, ost = state
            xb, yb = batch

            def loss_fn(pp):
                out, _ = model.apply(pp, xb, train=True)
                return F.cross_entropy(out, yb)

            loss, g = amp.scaled_grad(loss_fn, p, ost)
            # stage 2 reduce-scatters in-slice + DCN-reduces inside
            p, ost, _ = optimizer.step(p, ost, g)
            return (p, ost), lax.pmean(loss, "data")

        return jax.jit(jax.shard_map(
            step, mesh=mesh,
            in_specs=((P(), ospecs), (P("data"), P("data"))),
            out_specs=((P(), ospecs), P()), check_vma=False))

    def init_state(world):
        mesh = Mesh(np.array(jax.devices()[:world]), ("data",))
        opt0 = jax.jit(jax.shard_map(
            lambda pp: optimizer.init(
                pp, zero_axis="data", zero_stage=2,
                zero_ici_size=ici_of(world)),
            mesh=mesh, in_specs=(P(),), out_specs=ospecs_for(world),
            check_vma=False))(params)
        return (params, opt0)

    def to_host(state):
        # canonical form: population-1 buffers + the ici=1 layout, so
        # snapshots taken at any world share one treedef
        p, ost = _np_tree(state)
        buf_len = ost.masters.buf.shape[0]
        old_ici = next(i for i in (4, 2, 1)
                       if buf_len == 2 * (total + (-total) % i))
        padded = buf_len // 2
        ost = _dedup_slices(ost, padded, 2)
        ost = reshard_flat_state(ost, total, old_ici, 1)
        return (p, _retree(ost, ospecs_for(2)))

    def from_host(tree, world):
        p, ost = tree
        ici = ici_of(world)
        ost = reshard_flat_state(ost, total, 1, ici)
        ost = _tile_slices(ost, total + (-total) % ici, world // ici)
        return (p, _retree(ost, ospecs_for(world)))

    faults = TrainingFaults(replica_death=(3, 4), seed=0)
    trainer = ElasticTrainer(
        build_step, init_state(8), world=8, ckpt_dir=str(tmp_path),
        to_host=to_host, from_host=from_host, faults=faults,
        config=ElasticConfig(checkpoint_every=1, min_world=2),
        registry=obs.MetricsRegistry(), run="zero2_elastic")
    trainer.run(7, lambda i: batches[i])

    assert trainer.world == 4
    assert trainer.resumed_step == 3
    # shards were redistributed for the SHRUNK slice: the global view
    # is dcn(2) copies of the concat padded for ici 2, not ici 4
    _, ost = trainer._state
    assert ost.masters.buf.shape[0] == 2 * (total + (-total) % ici_of(4))
    assert ost.masters.layout.zero_ici == ici_of(4)
    assert trainer.history[-1][0] == 6

    # undisturbed shrunk-world run from the same snapshot
    template = to_host(init_state(8))
    restored = ckpt.restore_checkpoint(str(tmp_path), template, step=3)
    st = from_host(restored, 4)
    step4 = build_step(4)
    undisturbed = []
    for i in range(3, 7):
        st, loss = step4(st, batches[i])
        undisturbed.append(float(loss))
    post = [loss for s, loss, w in trainer.history if w == 4]
    np.testing.assert_allclose(post, undisturbed, rtol=1e-6)


def test_zero3_torn_snapshot_falls_back_and_reshards(tmp_path):
    # ZeRO-3: the master shard IS the parameter store, so the elastic
    # snapshot carries the whole model inside the flat shard buffers —
    # a torn snapshot must fall back to the previous durable one and
    # the fallback state must reshard 8 -> 4 (ici 4 -> 2) cleanly
    net = nn.Sequential([nn.Flatten(), nn.Linear(24, 10)])
    model, optimizer = amp.initialize(
        net, optimizers.FusedAdam(lr=1e-2), opt_level="O2",
        verbosity=0, hard_override=True)
    params, _ = model.init(jax.random.PRNGKey(0))
    total = optimizer.init(params).masters.layout.total
    batches = _batches(10)

    def ici_of(world):
        return max(world // 2, 1)

    def ospecs_for(world):
        return amp.zero_optimizer_specs(
            optimizer, params, "data", zero_stage=3,
            zero_ici_size=ici_of(world))

    def build_step(world):
        mesh = Mesh(np.array(jax.devices()[:world]), ("data",))
        ospecs = ospecs_for(world)

        def step(ost, batch):
            xb, yb = batch

            def loss_fn(m):
                # just-in-time gather: no replicated params in the state
                pp = amp.zero_gather_params(m)
                out, _ = model.apply(pp, xb, train=True)
                return F.cross_entropy(out, yb)

            loss, g = amp.scaled_grad(loss_fn, ost.masters, ost)
            _, ost, _ = optimizer.step((), ost, g)
            return ost, lax.pmean(loss, "data")

        return jax.jit(jax.shard_map(
            step, mesh=mesh,
            in_specs=(ospecs, (P("data"), P("data"))),
            out_specs=(ospecs, P()), check_vma=False))

    def init_state(world):
        mesh = Mesh(np.array(jax.devices()[:world]), ("data",))
        return jax.jit(jax.shard_map(
            lambda pp: optimizer.init(
                pp, zero_axis="data", zero_stage=3,
                zero_ici_size=ici_of(world)),
            mesh=mesh, in_specs=(P(),), out_specs=ospecs_for(world),
            check_vma=False))(params)

    def to_host(ost):
        ost = _np_tree(ost)
        buf_len = ost.masters.buf.shape[0]
        old_ici = next(i for i in (4, 2, 1)
                       if buf_len == 2 * (total + (-total) % i))
        padded = buf_len // 2
        ost = _dedup_slices(ost, padded, 2)
        ost = reshard_flat_state(ost, total, old_ici, 1)
        return _retree(ost, ospecs_for(2))

    def from_host(ost, world):
        ici = ici_of(world)
        ost = reshard_flat_state(ost, total, 1, ici)
        ost = _tile_slices(ost, total + (-total) % ici, world // ici)
        return _retree(ost, ospecs_for(world))

    ring = obs.EventRing(256)
    prev_ring = obs.get_ring()
    obs.set_ring(ring)
    faults = TrainingFaults(replica_death=(5, 6),
                            torn_checkpoint=(4, 5), seed=0, ring=ring)
    trainer = ElasticTrainer(
        build_step, init_state(8), world=8, ckpt_dir=str(tmp_path),
        to_host=to_host, from_host=from_host, faults=faults,
        config=ElasticConfig(checkpoint_every=2, min_world=2),
        ring=ring, registry=obs.MetricsRegistry(), run="zero3_torn")
    try:
        trainer.run(8, lambda i: batches[i])
    finally:
        obs.set_ring(prev_ring)

    # torn step-4 snapshot skipped -> durable step-2 fallback, and the
    # restored stage-3 state landed resharded on the survivor slice
    assert trainer.resumed_step == 2
    assert trainer.world == 4
    assert trainer.history[-1][0] == 7
    skipped = [ev for ev in ring.snapshot()
               if ev["kind"] == "snapshot_skipped"]
    assert [ev["step"] for ev in skipped] == [4]
    ost = trainer._state
    assert ost.masters.buf.shape[0] == 2 * (total + (-total) % ici_of(4))
    assert ost.masters.layout.zero_ici == ici_of(4)

    # trajectory parity vs an undisturbed world-4 replay from step 2
    template = to_host(init_state(8))
    restored = ckpt.restore_checkpoint(str(tmp_path), template, step=2)
    st = from_host(restored, 4)
    step4 = build_step(4)
    undisturbed = []
    for i in range(2, 8):
        st, loss = step4(st, batches[i])
        undisturbed.append(float(loss))
    post = [loss for s, loss, w in trainer.history if w == 4]
    np.testing.assert_allclose(post, undisturbed, rtol=1e-6)


def test_reshard_flat_state_pads_and_slices_exactly():
    total = 10
    base = np.arange(total, dtype=np.float32)
    padded8 = np.pad(base, (0, 6))            # 16 = pad to 8
    tree = {"buf": padded8, "scalar": np.float32(3.0),
            "other": np.ones((3, 3), np.float32)}
    out = reshard_flat_state(tree, total, 8, 4)
    assert out["buf"].shape == (12,)          # pad to 4
    np.testing.assert_array_equal(out["buf"][:total], base)
    assert not out["buf"][total:].any()
    assert out["scalar"] == 3.0               # scalars untouched
    assert out["other"].shape == (3, 3)       # non-flat untouched
    with pytest.raises(ValueError):
        reshard_flat_state(tree, total, 0, 4)


# -- preemption-safe deterministic resume (PR 12) ------------------------

def _uint8_dataset(n=64):
    rng = np.random.RandomState(5)
    images = rng.randint(0, 256, (n, 4, 4, 3), np.uint8)
    labels = np.arange(n, dtype=np.int32)   # label == sample index
    return images, labels


def test_preempt_resume_matches_undisturbed(tmp_path):
    """THE acceptance pin: preempt a run mid-training (coordinated
    emergency snapshot at the step boundary, clean ``preempted``
    exit), resume in a fresh trainer with a fresh loader — the loss
    trajectory AND the consumed-sample-index sequence are identical
    to an undisturbed run."""
    images, labels = _uint8_dataset()
    net = nn.Sequential([nn.Flatten(), nn.Linear(48, 32), nn.ReLU(),
                         nn.Linear(32, 64)])
    params, _ = net.init(jax.random.PRNGKey(0))
    ddp = parallel.DistributedDataParallel(net)
    build = _ddp_build_step(net, ddp)
    state0 = (params, jnp.zeros((), jnp.int32))

    def make_loader():
        # the portable (checkpointable) stream; batch 16 splits over
        # the world-8 data axis
        return DataLoader(images, labels, batch_size=16, shuffle=True,
                          seed=7, native=False)

    def run_trainer(d, loader, log, **kw):
        def data_fn(i):
            imgs, lbls, _ = loader.next_batch()
            log.append(tuple(int(v) for v in lbls))
            return jnp.asarray(imgs), jnp.asarray(lbls)
        tr = ElasticTrainer(
            build, state0, world=8, ckpt_dir=str(d),
            to_host=_np_tree, data=loader,
            config=ElasticConfig(checkpoint_every=3, min_world=1),
            registry=obs.MetricsRegistry(), **kw)
        tr.run(10, data_fn)
        return tr

    und_log = []
    und = run_trainer(tmp_path / "und", make_loader(), und_log,
                      run="preempt_und")
    assert und.verdict == "completed"
    und_losses = [loss for _, loss, _ in und.history]

    ring = obs.EventRing(256)
    sup = obs.RunSupervisor("preempt_test", ring=ring,
                            registry=obs.MetricsRegistry())
    guard = PreemptionGuard(grace_s=60.0, ring=ring,
                            registry=obs.MetricsRegistry())
    faults = TrainingFaults(preemption=(4, 5), seed=0, ring=ring)
    pre_log = []
    pre = run_trainer(tmp_path / "pre", make_loader(), pre_log,
                      guard=guard, faults=faults, supervisor=sup,
                      ring=ring, run="preempt_run")
    # the notice was honored at the NEXT step boundary: step 4 (where
    # the fault fired) still committed, then snapshot + clean exit
    assert pre.verdict == "preempted" and pre.cause == "preemption"
    assert [s for s, _, _ in pre.history] == list(range(5))
    assert faults.guard is guard          # auto-wired by the trainer
    kinds = [ev["kind"] for ev in ring.snapshot()]
    for k in ("preemption_requested", "preempted", "run_preempted"):
        assert k in kinds, k
    acts = [a["kind"] for a in pre.record()["actions"]]
    assert acts == ["preempt_snapshot"]
    # the supervisor reports the clean, LIVE preempted state
    assert sup.preempted
    ok, detail = sup.health_check()
    assert ok and "preempted" in detail
    assert sup.status()["preempted_step"] == 5

    rec = JsonlExporter.enrich(pre.record())
    assert validate_recovery_record(rec) == []
    assert rec["cause"] == "preemption" and rec["preempted"] is True
    assert rec["data_state"]["samples_consumed"] == 5 * 16

    # resume: fresh trainer, fresh loader — the snapshot's data_state
    # positions the stream, resume_overhead is accounted
    res = run_trainer(tmp_path / "pre", make_loader(), pre_log,
                      resume=True, run="preempt_resumed")
    assert res.resumed_step == 5 and res.verdict == "completed"
    assert res.resume_overhead_s is not None \
        and res.resume_overhead_s >= 0
    assert [s for s, _, _ in res.history] == list(range(5, 10))

    res_losses = [loss for _, loss, _ in pre.history + res.history]
    np.testing.assert_allclose(res_losses, und_losses, rtol=1e-6)
    assert pre_log == und_log             # exact index sequence


def test_replica_death_with_loader_rewinds_data_exactly_once(
        tmp_path):
    """The kill half of the pin, with a real data pipeline: a replica
    death mid-step abandons a drawn batch; recovery restores the
    snapshot's data_state alongside the tree, so the loader rewinds
    WITH the model and every committed step consumes its sample slice
    exactly once — no drift from the abandoned draw, across the 8→4
    shrink."""
    images, labels = _uint8_dataset()
    net = nn.Sequential([nn.Flatten(), nn.Linear(48, 32), nn.ReLU(),
                         nn.Linear(32, 64)])
    params, _ = net.init(jax.random.PRNGKey(0))
    ddp = parallel.DistributedDataParallel(net)
    build = _ddp_build_step(net, ddp)
    state0 = (params, jnp.zeros((), jnp.int32))

    loader = DataLoader(images, labels, batch_size=16, shuffle=True,
                        seed=7, native=False)
    faults = TrainingFaults(replica_death=(5, 6), seed=0)
    trainer = ElasticTrainer(
        build, state0, world=8, ckpt_dir=str(tmp_path),
        to_host=_np_tree, data=loader, faults=faults,
        config=ElasticConfig(checkpoint_every=2, min_world=1),
        registry=obs.MetricsRegistry(), run="death_loader")
    trainer.run(10)                      # data= feeds the run
    assert trainer.world == 4 and trainer.resumed_step == 4

    # exactly-once: 10 committed steps = 10 global batches, despite
    # the abandoned draw at the death (its consumption was rewound
    # with the snapshot's data_state)
    assert loader.stats()["samples_consumed"] == 10 * 16

    # the post-shrink trajectory matches an undisturbed world-4 run
    # resumed from the SAME snapshot with a FRESH loader positioned
    # by the snapshot's data_state
    template = _np_tree(state0)
    restored = ckpt.restore_checkpoint(str(tmp_path), template, step=4)
    ds = ckpt.load_data_state(str(tmp_path), step=4)
    assert ds["samples_consumed"] == 4 * 16
    loader2 = DataLoader(images, labels, batch_size=16, shuffle=True,
                         seed=7, native=False)
    loader2.load_state_dict(ds)
    step4 = build(4)
    st, undisturbed = restored, []
    for i in range(4, 10):
        imgs, lbls, _ = loader2.next_batch()
        st, loss = step4(st, (jnp.asarray(imgs), jnp.asarray(lbls)))
        undisturbed.append(float(loss))
    post = [loss for s, loss, w in trainer.history if w == 4]
    np.testing.assert_allclose(post, undisturbed, rtol=1e-6)


def test_preemption_guard_sigterm_handler():
    """The real entry point: SIGTERM lands in the installed guard's
    handler; uninstall restores the previous handler."""
    ring = obs.EventRing(16)
    guard = PreemptionGuard(grace_s=5.0, ring=ring,
                            registry=obs.MetricsRegistry())
    prev = signal.getsignal(signal.SIGTERM)
    with guard:
        os.kill(os.getpid(), signal.SIGTERM)
        for _ in range(400):              # delivery is async-ish
            if guard.requested:
                break
            time.sleep(0.005)
        assert guard.requested
        assert "signal" in guard.reason
    assert signal.getsignal(signal.SIGTERM) is prev
    # double install is idempotent: uninstall still restores the
    # ORIGINAL handler, not the guard's own
    guard2 = PreemptionGuard(registry=obs.MetricsRegistry())
    guard2.install()
    guard2.install()
    guard2.uninstall()
    assert signal.getsignal(signal.SIGTERM) is prev
    (ev,) = ring.snapshot("preemption_requested")
    assert ev["grace_s"] == 5.0
    # idempotent: a second notice does not restart the grace clock
    t0 = guard.requested_at
    guard.preempt("again")
    assert guard.requested_at == t0 and "signal" in guard.reason


def test_preemption_with_exhausted_grace_skips_snapshot(tmp_path):
    """Grace already gone when the boundary arrives: exit WITHOUT
    starting a write — the last durable snapshot stays the resume
    point, and nothing is torn."""
    def build(world):
        return lambda st, b: ({"w": st["w"] + 1}, 1.0)

    ring = obs.EventRing(64)
    guard = PreemptionGuard(grace_s=0.0, ring=ring,
                            registry=obs.MetricsRegistry())
    faults = TrainingFaults(preemption=(2, 3), seed=0, ring=ring)
    trainer = ElasticTrainer(
        build, {"w": np.zeros(2, np.float32)}, world=4,
        ckpt_dir=str(tmp_path), guard=guard, faults=faults,
        config=ElasticConfig(checkpoint_every=5, min_world=1),
        ring=ring, registry=obs.MetricsRegistry(), run="nograce")
    trainer.run(6, lambda i: None)
    assert trainer.verdict == "preempted"
    # only the step-0 fallback snapshot exists — no emergency write
    assert ckpt.available_steps(str(tmp_path)) == [0]
    kinds = [ev["kind"] for ev in ring.snapshot()]
    assert "preemption_grace_exhausted" in kinds
    assert "preempt_snapshot" not in [
        a["kind"] for a in trainer.record()["actions"]]
    # a resumed trainer falls back to the durable step-0 snapshot
    res = ElasticTrainer(
        build, {"w": np.zeros(2, np.float32)}, world=4,
        ckpt_dir=str(tmp_path), resume=True,
        registry=obs.MetricsRegistry(), run="nograce_res")
    assert res.resumed_step == 0


def test_legacy_snapshot_without_data_state_is_loud(tmp_path):
    """A pipeline is attached but the snapshot cannot say where the
    stream stood: RecoveryError, not a silent divergence."""
    images, labels = _uint8_dataset()
    ckpt.save_checkpoint(str(tmp_path), 0,
                         {"w": np.zeros(2, np.float32)})

    def build(world):
        return lambda st, b: ({"w": st["w"] + 1}, 1.0)

    loader = DataLoader(images, labels, batch_size=16, shuffle=True,
                        native=False)
    with pytest.raises(RecoveryError, match="data_state"):
        ElasticTrainer(
            build, {"w": np.zeros(2, np.float32)}, world=4,
            ckpt_dir=str(tmp_path), data=loader, resume=True,
            registry=obs.MetricsRegistry(), run="legacy")


# -- recovery failure paths (loud, not loops) ----------------------------

def test_recovery_error_when_no_survivors(tmp_path):
    def build(world):
        return lambda st, b: ({"w": st["w"] + 1}, 1.0)

    faults = TrainingFaults(replica_death=(2, 3), seed=0)
    trainer = ElasticTrainer(
        build, {"w": np.zeros(2, np.float32)}, world=1,
        ckpt_dir=str(tmp_path), faults=faults,
        config=ElasticConfig(min_world=1),
        registry=obs.MetricsRegistry(), run="floor")
    with pytest.raises(RecoveryError, match="no survivors"):
        trainer.run(6, lambda i: None)


def test_recovery_error_when_budget_exhausted(tmp_path):
    def build(world):
        return lambda st, b: ({"w": st["w"] + 1}, 1.0)

    faults = TrainingFaults(replica_death=(2, None), seed=0)
    trainer = ElasticTrainer(
        build, {"w": np.zeros(2, np.float32)}, world=64,
        ckpt_dir=str(tmp_path), faults=faults,
        config=ElasticConfig(min_world=1, max_recoveries=2),
        registry=obs.MetricsRegistry(), run="budget")
    with pytest.raises(RecoveryError, match="budget"):
        trainer.run(20, lambda i: None)
    assert trainer.recoveries == 2


def test_recovery_error_when_no_durable_snapshot(tmp_path):
    def build(world):
        return lambda st, b: ({"w": st["w"] + 1}, 1.0)

    # tear EVERY snapshot (window [0, None)); the death then finds no
    # durable resume point
    faults = TrainingFaults(replica_death=(3, 4),
                            torn_checkpoint=(0, None),
                            seed=0)
    trainer = ElasticTrainer(
        build, {"w": np.zeros(2, np.float32)}, world=4,
        ckpt_dir=str(tmp_path), faults=faults,
        config=ElasticConfig(checkpoint_every=1, min_world=1),
        registry=obs.MetricsRegistry(), run="nodurable")
    with pytest.raises(RecoveryError, match="durable"):
        trainer.run(6, lambda i: None)
