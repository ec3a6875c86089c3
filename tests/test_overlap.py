"""Overlapped gradient communication (PR 14, ROADMAP item 2).

The staged DDP backward issues bucket *i*'s reduction while bucket
*i-1*'s gradients are still being computed.  Pinned here:

- **numerics**: the overlapped schedule computes the SAME gradients as
  the reduce-after-backward schedule (rtol 1e-6) and as the classic
  monolithic ``allreduce_grads_tree`` step — the schedule moves issue
  positions, never math; the bf16-compressed variant matches its own
  baseline at 1e-6 and the uncompressed one at bf16 tolerance;
- **static interleaving**: in the traced jaxpr the first bucket's
  reduction eqns precede the last stage's grad eqns under
  ``overlap=True`` and trail the whole backward under ``False`` (the
  property the collective lint rule's ``interleaving`` check pins);
- **plan/runtime consistency**: ``overlap_comm_schedule`` buckets and
  the traced ``comm_stats`` agree bucket-for-bucket (stage,
  issue_order, wire bytes) — the shared-helper contract that keeps a
  schedule change from desyncing plan from graph;
- **observability contracts** survive the new schedule:
  ``numerics_out=`` per-bucket scalars arrive in schedule order.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from apex_tpu import parallel
from apex_tpu.analysis import graphs as G

S, H, B = 4, 32, 8
_rng = np.random.RandomState(14)
STAGE_PARAMS = [
    {"w": jnp.asarray(_rng.randn(H, H) * 0.1, jnp.float32),
     "b": jnp.asarray(_rng.randn(H) * 0.01, jnp.float32)}
    for _ in range(S)]
X = jnp.asarray(_rng.randn(B, H), jnp.float32)
Y = jnp.asarray(_rng.randn(B, H), jnp.float32)
STAGE_FNS = [lambda p, a: jnp.tanh(a @ p["w"] + p["b"])] * S


def _mesh():
    return Mesh(np.array(jax.devices()[:8]), ("data",))


def make_staged_step(overlap, compress=False,
                     numerics=False, topo="hierarchical", ici=4):
    """(ddp, mapped_fn) for the staged train step; the mapped fn
    returns (per-stage grads, loss)."""
    ddp = parallel.DistributedDataParallel(
        comm_topology=topo, allreduce_compress_bf16=compress,
        ici_size=ici, overlap=overlap)

    def step(params_list, batch):
        xb, yb = batch
        nout = [] if numerics else None
        loss, grads = ddp.staged_allreduce_grads(
            STAGE_FNS, lambda a: jnp.mean((a - yb) ** 2), params_list,
            xb, numerics_out=nout)
        return list(grads), loss

    mapped = jax.shard_map(step, mesh=_mesh(),
                           in_specs=(P(), (P("data"), P("data"))),
                           out_specs=(P(), P()), check_vma=False)
    return ddp, mapped


def _grads(fn):
    g, _ = jax.jit(fn)(STAGE_PARAMS, (X, Y))
    return jax.tree_util.tree_leaves(g)


def test_overlap_matches_reduce_after_backward_and_monolithic():
    """The acceptance pin: overlapped grads == reduce-after-backward
    grads at 1e-6 rtol, and both == the monolithic hierarchical step
    (one allreduce_grads_tree over the whole tree after jax.grad)."""
    _, f_ov = make_staged_step(True)
    _, f_ba = make_staged_step(False)
    g_ov, g_ba = _grads(f_ov), _grads(f_ba)
    for a, b in zip(g_ov, g_ba):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6)

    def mono_step(params_list, batch):
        xb, yb = batch

        def loss_fn(ps):
            a = xb
            for fn, p in zip(STAGE_FNS, ps):
                a = fn(p, a)
            return jnp.mean((a - yb) ** 2)

        loss, grads = jax.value_and_grad(loss_fn)(list(params_list))
        grads = parallel.allreduce_grads_tree(
            grads, "data", comm_topology="hierarchical", ici_size=4)
        return grads, loss

    mono = jax.shard_map(mono_step, mesh=_mesh(),
                         in_specs=(P(), (P("data"), P("data"))),
                         out_specs=(P(), P()), check_vma=False)
    for a, b in zip(g_ov, _grads(mono)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6)


def test_overlap_bf16_compressed_tolerances():
    """The compressed overlapped schedule matches its own
    reduce-after-backward baseline at 1e-6 (identical per-bucket ops,
    only issue positions differ) and the uncompressed schedule at bf16
    tolerance (the DCN hop quantizes either way)."""
    _, f_cov = make_staged_step(True, compress=True)
    _, f_cba = make_staged_step(False, compress=True)
    _, f_ov = make_staged_step(True)
    g_cov, g_cba, g_ov = _grads(f_cov), _grads(f_cba), _grads(f_ov)
    for a, b in zip(g_cov, g_cba):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6)
    for a, b in zip(g_cov, g_ov):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-2, atol=2e-2)


def _positions(jaxpr, min_payload=64):
    """(first big-collective index, last matmul index) in program
    order — the property the lint rule's interleaving check reads."""
    first_coll = last_mm = None
    for i, e in enumerate(G.walk_jaxpr(jaxpr)):
        if (first_coll is None
                and e.primitive.name in G.COLLECTIVE_PRIMS
                and G.eqn_payload_bytes(e) >= min_payload):
            first_coll = i
        if e.primitive.name in ("dot_general", "conv_general_dilated"):
            last_mm = i
    return first_coll, last_mm


def test_overlap_static_interleaving_both_ways():
    """overlap=True: the first bucket's reduction sits AHEAD of the
    last stage's grad matmuls in the jaxpr; overlap=False: every
    bucket reduction trails the whole backward.  Same census, same
    payloads — position is the only difference, which is exactly what
    the collective rule's interleaving expectation pins."""
    _, f_ov = make_staged_step(True)
    _, f_ba = make_staged_step(False)
    jx_ov = jax.make_jaxpr(f_ov)(STAGE_PARAMS, (X, Y))
    jx_ba = jax.make_jaxpr(f_ba)(STAGE_PARAMS, (X, Y))
    fc, lm = _positions(jx_ov)
    assert fc is not None and lm is not None and fc < lm, (fc, lm)
    fc_b, lm_b = _positions(jx_ba)
    assert fc_b is not None and fc_b > lm_b, (fc_b, lm_b)
    # identical collective census either way (the interleaving is not
    # bought with extra collectives)
    from collections import Counter
    census = lambda jx: Counter(  # noqa: E731
        e.primitive.name for e in G.collective_eqns(jx))
    assert census(jx_ov) == census(jx_ba)


def test_overlap_shares_one_axis_size_scalar():
    """staged_allreduce_grads psums the axis-size scalar ONCE
    (world_scalar=) — the census carries exactly one 4-byte scalar
    psum for the average no matter how many stages reduce."""
    _, f_ov = make_staged_step(True)
    jx = jax.make_jaxpr(f_ov)(STAGE_PARAMS, (X, Y))
    scalars = [e for e in G.collective_eqns(jx)
               if G.eqn_payload_bytes(e) <= 8]
    # the shared axis-size psum only — the step above returns grads,
    # no loss pmean inside the mapped fn
    assert len(scalars) == 1, [
        (e.primitive.name, G.eqn_payload_bytes(e)) for e in scalars]


def test_overlap_schedule_matches_runtime_comm_stats():
    """The shared-helper contract: overlap_comm_schedule (static, from
    shapes) and the traced comm_stats agree bucket-for-bucket on
    stage, issue order, cause, topology and wire bytes — a schedule
    change cannot silently desync plan from graph."""
    ddp, f_ov = make_staged_step(True)
    jax.make_jaxpr(f_ov)(STAGE_PARAMS, (X, Y))
    sched = parallel.overlap_comm_schedule(
        STAGE_PARAMS, comm_topology="hierarchical", ici_size=4,
        world=8, nproc=1)
    assert sched["overlap_mode"] == "overlapped"
    assert sched["issue_order"] == \
        parallel.overlap_issue_order(S) == [3, 2, 1, 0]
    assert len(sched["buckets"]) == len(ddp.last_comm_stats) == S
    for pb, rb in zip(sched["buckets"], ddp.last_comm_stats):
        assert pb["stage"] == rb["stage"]
        assert pb["issue_order"] == rb["issue_order"]
        assert pb["cause"] == rb["cause"]
        assert pb["topology"] == rb["topology"]
        assert pb["wire_bytes"] == rb["bytes"]
        assert pb["ici_wire_bytes"] == rb["ici_wire_bytes"]
        assert pb["dcn_wire_bytes"] == rb["dcn_wire_bytes"]
    ls = ddp.last_overlap_schedule
    assert ls["overlap_mode"] == "overlapped" and ls["n_stages"] == S
    assert ls["issue_order"] == sched["issue_order"]
    assert ls["issue_order"] == [3, 2, 1, 0]
    # the plain schedule carries NO zero_stage key at all — absent,
    # not None, so a reader can gate on presence
    assert "zero_stage" not in ls


def test_overlap_numerics_out_arrives_in_schedule_order():
    """numerics_out per-bucket scalars under the overlapped schedule:
    one record per bucket, stamped with the SAME stage/issue_order the
    schedule stamps, traced scalars present — the PR 9 plan-order
    contract holds when the buckets are issued inside the backward."""
    for compress in (False, True):
        ddp, f_n = make_staged_step(True, compress=compress,
                                    numerics=True)
        nout_probe = []

        def step(params_list, batch):
            xb, yb = batch
            loss, grads = ddp.staged_allreduce_grads(
                STAGE_FNS, lambda a: jnp.mean((a - yb) ** 2),
                params_list, xb, numerics_out=nout_probe)
            return list(grads), loss

        mapped = jax.shard_map(step, mesh=_mesh(),
                               in_specs=(P(), (P("data"), P("data"))),
                               out_specs=(P(), P()), check_vma=False)
        jax.make_jaxpr(mapped)(STAGE_PARAMS, (X, Y))
        sched = parallel.overlap_comm_schedule(
            STAGE_PARAMS, comm_topology="hierarchical", ici_size=4,
            allreduce_compress_bf16=compress, world=8, nproc=1)
        assert len(nout_probe) == len(sched["buckets"]) == S
        for ns, pb in zip(nout_probe, sched["buckets"]):
            assert ns["stage"] == pb["stage"]
            assert ns["issue_order"] == pb["issue_order"]
            assert ns["elements"] == pb["elements"]
            for key in ("nonfinite", "abs_max", "sq_sum"):
                assert key in ns
            assert ("compression_sq_error" in ns) == compress


def test_overlap_knob_clashes():
    for kw in ({"delay_allreduce": True},
               {"allreduce_trigger_params": ["w"]}):
        with pytest.raises(ValueError, match="overlap"):
            parallel.DistributedDataParallel(overlap=True, **kw)
    # the staged method itself refuses the clashing knobs even when
    # overlap=False (the baseline schedule still stages the buckets)
    ddp = parallel.DistributedDataParallel(delay_allreduce=True)
    with pytest.raises(ValueError, match="staged"):
        ddp.staged_allreduce_grads(STAGE_FNS, lambda a: jnp.sum(a),
                                   STAGE_PARAMS, X)


def test_overlap_issue_order_helper():
    assert parallel.overlap_issue_order(1) == [0]
    assert parallel.overlap_issue_order(3) == [2, 1, 0]
    with pytest.raises(ValueError):
        parallel.overlap_issue_order(0)


def test_overlap_collective_expectations_derivation():
    """The lint expectations derive from the schedule: census +
    payloads via plan_collective_expectations, and the interleaving
    pin ONLY for the overlapped mode, with a threshold that clears
    every scalar psum but no gradient bucket hop."""
    for overlap in (True, False):
        sched = parallel.overlap_comm_schedule(
            STAGE_PARAMS, comm_topology="hierarchical", ici_size=4,
            world=8, nproc=1, overlap=overlap)
        exp = parallel.overlap_collective_expectations(
            sched, extra_psums=2, extra_psum_bytes=8)
        assert exp["counts"]["reduce_scatter"] == S
        assert exp["counts"]["psum"] == S + 2
        if overlap:
            inter = exp["interleaving"]
            assert inter["min_payload_bytes"] > 8
            assert inter["min_payload_bytes"] <= min(
                b["dcn_wire_bytes"] for b in sched["buckets"])
            assert inter["min_matmuls_after"] >= 1
        else:
            assert "interleaving" not in exp


# -- the fused ZeRO-2 staged step ------------------------------------------

def make_zero2_step(overlap, compress=False):
    """(ddp, mapped_fn) for the fused ZeRO-2 staged step (SGD shard
    update); the mapped fn returns (new per-stage params, loss)."""
    ddp = parallel.DistributedDataParallel(
        comm_topology="hierarchical", allreduce_compress_bf16=compress,
        ici_size=4, overlap=overlap, zero_stage=2)

    def step(params_list, batch):
        xb, yb = batch
        loss, new = ddp.staged_zero2_allreduce_grads(
            STAGE_FNS, lambda a: jnp.mean((a - yb) ** 2), params_list,
            xb, lambda stage, p_sh, g_sh: p_sh - 0.1 * g_sh)
        return list(new), loss

    mapped = jax.shard_map(step, mesh=_mesh(),
                           in_specs=(P(), (P("data"), P("data"))),
                           out_specs=(P(), P()), check_vma=False)
    return ddp, mapped


def test_staged_zero2_matches_unfused_update_and_baseline():
    """Numerics pin for the fused chain: scatter-reduce -> shard
    update -> in-slice gather lands on the SAME new params as the
    plain staged reduction followed by the identical SGD update on the
    full tree (rtol 1e-6) — fusing moves WHERE the update runs (on the
    1/ici shard, inside the backward), never its math.  Overlap on/off
    agree the same way (issue positions only)."""
    _, fz_ov = make_zero2_step(True)
    _, fz_ba = make_zero2_step(False)
    nz_ov, _ = jax.jit(fz_ov)(STAGE_PARAMS, (X, Y))
    nz_ba, _ = jax.jit(fz_ba)(STAGE_PARAMS, (X, Y))

    _, f_g = make_staged_step(True)
    g, _ = jax.jit(f_g)(STAGE_PARAMS, (X, Y))
    ref = jax.tree_util.tree_map(lambda p, gg: p - 0.1 * gg,
                                 list(STAGE_PARAMS), list(g))
    for a, b in zip(jax.tree_util.tree_leaves(nz_ov),
                    jax.tree_util.tree_leaves(ref)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6, atol=1e-7)
    for a, b in zip(jax.tree_util.tree_leaves(nz_ov),
                    jax.tree_util.tree_leaves(nz_ba)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6, atol=1e-7)


def test_staged_zero2_schedule_tag_and_runtime_stats():
    """Plan/runtime consistency for the fused path: the static
    ``overlap_comm_schedule(zero_stage=2)`` and the traced
    ``comm_stats`` agree bucket-for-bucket (stage, issue order, cause,
    topology, wire bytes, both fabric levels), and the traced schedule
    is tagged ``zero_stage=2``."""
    ddp, fz = make_zero2_step(True)
    jax.make_jaxpr(fz)(STAGE_PARAMS, (X, Y))
    sched = parallel.overlap_comm_schedule(
        STAGE_PARAMS, comm_topology="hierarchical", ici_size=4,
        world=8, nproc=1, zero_stage=2)
    assert sched["zero_stage"] == 2
    assert len(sched["buckets"]) == len(ddp.last_comm_stats) == S
    for pb, rb in zip(sched["buckets"], ddp.last_comm_stats):
        assert pb["stage"] == rb["stage"]
        assert pb["issue_order"] == rb["issue_order"]
        assert pb["cause"] == rb["cause"]
        assert pb["topology"] == rb["topology"] == "hierarchical"
        assert pb["wire_bytes"] == rb["bytes"]
        assert pb["ici_wire_bytes"] == rb["ici_wire_bytes"]
        assert pb["dcn_wire_bytes"] == rb["dcn_wire_bytes"]
    ls = ddp.last_overlap_schedule
    assert ls["zero_stage"] == 2
    assert ls["overlap_mode"] == "overlapped"
    assert ls["issue_order"] == sched["issue_order"]


def test_staged_zero2_knob_clashes():
    """The fused path's guard rails: stage 2 only, hierarchical only;
    the method refuses a DDP without zero_stage=2 armed."""
    with pytest.raises(ValueError, match="stage 2 only"):
        parallel.DistributedDataParallel(
            comm_topology="hierarchical", ici_size=4, zero_stage=3)
    with pytest.raises(ValueError, match="hierarchical"):
        parallel.DistributedDataParallel(zero_stage=2)
    with pytest.raises(ValueError, match="zero_stage"):
        parallel.overlap_comm_schedule(
            STAGE_PARAMS, comm_topology="hierarchical", ici_size=4,
            world=8, nproc=1, zero_stage=1)

    plain = parallel.DistributedDataParallel(
        comm_topology="hierarchical", ici_size=4)
    with pytest.raises(ValueError, match="zero_stage=2"):
        plain.staged_zero2_allreduce_grads(
            STAGE_FNS, lambda a: jnp.sum(a), STAGE_PARAMS, X,
            lambda stage, p, g: p)

    armed = parallel.DistributedDataParallel(
        comm_topology="hierarchical", ici_size=4, zero_stage=2)
    # a full-gradient allreduce on a zero_stage=2 DDP is refused too
    with pytest.raises(ValueError, match="shards the update"):
        armed.allreduce_grads({"w": X})
