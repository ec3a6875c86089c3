"""ZeRO stage-1 (optimizer-state sharding over the data axis): the
reduce-scatter/update-shard/all-gather step must track the
DDP-allreduce + full-replicated-state trajectory (identical math;
psum vs psum_scatter reduction order separates them at float
round-off), with the masters/moments 1/dp the size per device and
overflow skips staying global."""

import numpy as np
import pytest
import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from apex_tpu import amp, nn, optimizers, parallel
from apex_tpu.nn import functional as F


def _setup(opt_level="O2"):
    net = nn.Sequential([nn.Conv2d(3, 4, 3, padding=1),
                         nn.BatchNorm2d(4), nn.ReLU(), nn.Flatten(),
                         nn.Linear(4 * 8 * 8, 10)])
    model, optimizer = amp.initialize(
        net, optimizers.FusedAdam(lr=1e-2), opt_level=opt_level,
        verbosity=0, hard_override=True)
    params, bn_state = model.init(jax.random.PRNGKey(0))
    return model, optimizer, params, bn_state


def _data(n=16):
    rng = np.random.RandomState(0)
    return (jnp.asarray(rng.randn(n, 3, 8, 8), jnp.float32),
            jnp.asarray(rng.randint(0, 10, n), jnp.int32))


def test_zero1_matches_ddp_trajectory():
    model, optimizer, params, bn_state = _setup()
    mesh = Mesh(np.array(jax.devices()[:8]), ("data",))
    x, y = _data()
    ddp = parallel.DistributedDataParallel(model)

    def loss_fn_of(xb, yb, bn):
        def loss_fn(p):
            out, new_bn = model.apply(p, xb, state=bn, train=True)
            return F.cross_entropy(out, yb), new_bn
        return loss_fn

    # -- reference: DDP allreduce + replicated optimizer state ----------
    opt_ref = optimizer.init(params)

    def ddp_step(p, os, bn, xb, yb):
        loss, new_bn, g = amp.scaled_grad(loss_fn_of(xb, yb, bn), p, os,
                                          has_aux=True)
        g = ddp.allreduce_grads(g)
        p, os, _ = optimizer.step(p, os, g)
        return p, os, new_bn, lax.pmean(loss, "data")

    run_ref = jax.jit(jax.shard_map(
        ddp_step, mesh=mesh,
        in_specs=(P(), P(), P(), P("data"), P("data")),
        out_specs=(P(), P(), P(), P()), check_vma=False))

    # -- ZeRO-1: sharded state, NO pre-allreduce ------------------------
    ospecs = amp.zero_optimizer_specs(optimizer, params, "data")
    opt_z = jax.jit(jax.shard_map(
        lambda p: optimizer.init(p, zero_axis="data"), mesh=mesh,
        in_specs=(P(),), out_specs=ospecs, check_vma=False))(params)
    # the flat state really is sharded: the global array is the
    # device-concat (= padded full buffer), but each DEVICE holds only
    # a 1/dp slice of it
    full_elems = optimizer.init(params).masters.layout.total
    gshape = opt_z.masters.buf.shape[0]
    dp = mesh.devices.size
    assert full_elems <= gshape < full_elems + dp     # padded concat
    shard_sizes = {np.asarray(s.data).size
                   for s in opt_z.masters.buf.addressable_shards}
    assert shard_sizes == {gshape // dp}

    def zero_step(p, os, bn, xb, yb):
        loss, new_bn, g = amp.scaled_grad(loss_fn_of(xb, yb, bn), p, os,
                                          has_aux=True)
        # no ddp.allreduce_grads: step() reduce-scatters internally
        p, os, _ = optimizer.step(p, os, g)
        return p, os, new_bn, lax.pmean(loss, "data")

    run_z = jax.jit(jax.shard_map(
        zero_step, mesh=mesh,
        in_specs=(P(), ospecs, P(), P("data"), P("data")),
        out_specs=(P(), ospecs, P(), P()), check_vma=False))

    # single-step exactness: after ONE step from identical state the
    # gathered ZeRO master shards equal the replicated masters to float
    # round-off (the windowing/scatter math is exact; measured 3e-8)
    def ref_masters(p, os, bn, xb, yb):
        _, _, g = amp.scaled_grad(loss_fn_of(xb, yb, bn), p, os,
                                  has_aux=True)
        g = ddp.allreduce_grads(g)
        _, os, _ = optimizer.step(p, os, g)
        return os.masters.buf

    def zero_masters(p, os, bn, xb, yb):
        _, _, g = amp.scaled_grad(loss_fn_of(xb, yb, bn), p, os,
                                  has_aux=True)
        _, os, _ = optimizer.step(p, os, g)
        return lax.all_gather(os.masters.buf, "data", axis=0,
                              tiled=True)

    mref = jax.jit(jax.shard_map(
        ref_masters, mesh=mesh,
        in_specs=(P(), P(), P(), P("data"), P("data")),
        out_specs=P(), check_vma=False))(params, optimizer.init(params),
                                         bn_state, x, y)
    mz = jax.jit(jax.shard_map(
        zero_masters, mesh=mesh,
        in_specs=(P(), ospecs, P(), P("data"), P("data")),
        out_specs=P(), check_vma=False))(params, opt_z, bn_state, x, y)
    # the shards count in tree order; where the replicated layout keeps a
    # leaf is its own matter (offsets)
    lay = optimizer.init(params).masters.layout
    mref = np.concatenate([np.asarray(mref)[o:o + n]
                           for o, n in zip(lay.offsets, lay.sizes)])
    np.testing.assert_allclose(np.asarray(mz)[:full_elems], mref, atol=1e-6)

    # multi-step: the trajectories track (Adam amplifies the psum-vs-
    # psum_scatter reduction-order round-off, so bitwise equality is
    # not expected — closeness of the LOSS curve is)
    pa, osa, bna = params, optimizer.init(params), bn_state
    pb, osb, bnb = params, opt_z, bn_state
    for i in range(4):
        pa, osa, bna, la = run_ref(pa, osa, bna, x, y)
        pb, osb, bnb, lb = run_z(pb, osb, bnb, x, y)
        np.testing.assert_allclose(float(la), float(lb), rtol=1e-2,
                                   err_msg=f"step {i}")


def test_zero1_tracks_hierarchical_ddp_trajectory():
    """Composition pin for the hierarchical comm topology: a DDP step
    whose grads ride the two-level ICI/DCN reduction must (a) produce
    the SAME grads as the flat psum to round-off inside one traced
    step — i.e. the hierarchy divides by world exactly once, never per
    level — and (b) its trajectory must track the ZeRO-1 sharded-state
    run exactly like the flat DDP reference does (the two differ only
    by reduction order, Adam-amplified)."""
    model, optimizer, params, bn_state = _setup()
    mesh = Mesh(np.array(jax.devices()[:8]), ("data",))
    x, y = _data()
    ddp_h = parallel.DistributedDataParallel(
        model, comm_topology="hierarchical", ici_size=4)
    ddp_f = parallel.DistributedDataParallel(model)

    def loss_fn_of(xb, yb, bn):
        def loss_fn(p):
            out, new_bn = model.apply(p, xb, state=bn, train=True)
            return F.cross_entropy(out, yb), new_bn
        return loss_fn

    # (a) grad-level: hierarchical == flat to round-off, one average
    def grads_both(p, os, bn, xb, yb):
        _, _, g = amp.scaled_grad(loss_fn_of(xb, yb, bn), p, os,
                                  has_aux=True)
        return ddp_f.allreduce_grads(g), ddp_h.allreduce_grads(g)

    gf, gh = jax.jit(jax.shard_map(
        grads_both, mesh=mesh,
        in_specs=(P(), P(), P(), P("data"), P("data")),
        out_specs=(P(), P()), check_vma=False))(
        params, optimizer.init(params), bn_state, x, y)
    # O2 grads are bf16: reduction-order differences on
    # near-cancelling 8-term sums reach a few bf16 ulps in absolute
    # terms, so the absolute floor is bf16-scaled
    for a, b in zip(jax.tree_util.tree_leaves(gf),
                    jax.tree_util.tree_leaves(gh)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=2e-2, atol=1e-3)

    # (b) trajectory-level vs ZeRO-1 (which reduce-scatters inside
    # optimizer.step and averages once itself)
    def hier_step(p, os, bn, xb, yb):
        loss, new_bn, g = amp.scaled_grad(loss_fn_of(xb, yb, bn), p,
                                          os, has_aux=True)
        g = ddp_h.allreduce_grads(g)
        p, os, _ = optimizer.step(p, os, g)
        return p, os, new_bn, lax.pmean(loss, "data")

    run_h = jax.jit(jax.shard_map(
        hier_step, mesh=mesh,
        in_specs=(P(), P(), P(), P("data"), P("data")),
        out_specs=(P(), P(), P(), P()), check_vma=False))

    ospecs = amp.zero_optimizer_specs(optimizer, params, "data")
    opt_z = jax.jit(jax.shard_map(
        lambda p: optimizer.init(p, zero_axis="data"), mesh=mesh,
        in_specs=(P(),), out_specs=ospecs, check_vma=False))(params)

    def zero_step(p, os, bn, xb, yb):
        loss, new_bn, g = amp.scaled_grad(loss_fn_of(xb, yb, bn), p,
                                          os, has_aux=True)
        p, os, _ = optimizer.step(p, os, g)
        return p, os, new_bn, lax.pmean(loss, "data")

    run_z = jax.jit(jax.shard_map(
        zero_step, mesh=mesh,
        in_specs=(P(), ospecs, P(), P("data"), P("data")),
        out_specs=(P(), ospecs, P(), P()), check_vma=False))

    pa, osa, bna = params, optimizer.init(params), bn_state
    pb, osb, bnb = params, opt_z, bn_state
    for i in range(4):
        pa, osa, bna, la = run_h(pa, osa, bna, x, y)
        pb, osb, bnb, lb = run_z(pb, osb, bnb, x, y)
        np.testing.assert_allclose(float(la), float(lb), rtol=1e-2,
                                   err_msg=f"step {i}")


def test_zero1_overflow_skip_is_global():
    """An inf that reduce-scatters into ONE device's grad window must
    skip the update and halve the scale on EVERY device."""
    model, optimizer, params, bn_state = _setup()
    mesh = Mesh(np.array(jax.devices()[:8]), ("data",))
    ospecs = amp.zero_optimizer_specs(optimizer, params, "data")
    opt_z = jax.jit(jax.shard_map(
        lambda p: optimizer.init(p, zero_axis="data"), mesh=mesh,
        in_specs=(P(),), out_specs=ospecs, check_vma=False))(params)

    # grads: inf in ONE leaf (first conv weight) only
    grads = jax.tree_util.tree_map(jnp.ones_like, params)
    k0 = list(grads)[0]
    leaf0 = list(grads[k0])[0]
    g0 = jax.tree_util.tree_map(
        lambda a: jnp.full_like(a, jnp.inf), grads[k0][leaf0])
    grads = {**grads, k0: {**grads[k0], leaf0: g0}}

    def step(p, os, g):
        p, os, info = optimizer.step(p, os, g)
        return p, os, info["loss_scale"], info["found_inf"]

    new_p, new_os, scale, found = jax.jit(jax.shard_map(
        step, mesh=mesh, in_specs=(P(), ospecs, P()),
        out_specs=(P(), ospecs, P(), P()), check_vma=False))(
        params, opt_z, grads)
    assert float(found) > 0
    # every param identical to before (skip applied everywhere)
    for a, b in zip(jax.tree_util.tree_leaves(params),
                    jax.tree_util.tree_leaves(new_p)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # every master shard untouched too
    np.testing.assert_array_equal(np.asarray(new_os.masters.buf),
                                  np.asarray(opt_z.masters.buf))


def test_zero_requires_flat_path():
    net = nn.Sequential([nn.Linear(4, 4)])
    model, optimizer = amp.initialize(
        net, optimizers.FusedLAMB(lr=1e-3), opt_level="O2",
        verbosity=0, hard_override=True)
    params, _ = model.init(jax.random.PRNGKey(0))
    mesh = Mesh(np.array(jax.devices()[:4]), ("data",))
    with pytest.raises(ValueError, match="elementwise"):
        jax.jit(jax.shard_map(
            lambda p: optimizer.init(p, zero_axis="data"), mesh=mesh,
            in_specs=(P(),),
            out_specs=jax.tree_util.tree_map(lambda _: P(), params),
            check_vma=False))(params)


def test_zero_step_outside_mesh_raises():
    """A ZeRO-sharded state stepped without the axis mapped must fail
    loudly — the flat fallback would corrupt params silently."""
    model, optimizer, params, _ = _setup()
    mesh = Mesh(np.array(jax.devices()[:8]), ("data",))
    ospecs = amp.zero_optimizer_specs(optimizer, params, "data")
    opt_z = jax.jit(jax.shard_map(
        lambda p: optimizer.init(p, zero_axis="data"), mesh=mesh,
        in_specs=(P(),), out_specs=ospecs, check_vma=False))(params)
    grads = jax.tree_util.tree_map(jnp.ones_like, params)
    with pytest.raises(RuntimeError, match="ZeRO-sharded"):
        optimizer.step(params, opt_z, grads)


def test_zero_masters_unpack_raises():
    model, optimizer, params, _ = _setup()
    mesh = Mesh(np.array(jax.devices()[:8]), ("data",))
    ospecs = amp.zero_optimizer_specs(optimizer, params, "data")
    opt_z = jax.jit(jax.shard_map(
        lambda p: optimizer.init(p, zero_axis="data"), mesh=mesh,
        in_specs=(P(),), out_specs=ospecs, check_vma=False))(params)
    with pytest.raises(RuntimeError, match="all_gather"):
        opt_z.masters.as_tree()


def test_zero1_rides_make_step():
    """The standard make_step builder accepts the ZeRO state specs
    (state_specs param), including the steps_per_call scan."""
    model, optimizer, params, bn_state = _setup()
    mesh = Mesh(np.array(jax.devices()[:8]), ("data",))
    ospecs = amp.zero_optimizer_specs(optimizer, params, "data")
    opt_z = jax.jit(jax.shard_map(
        lambda p: optimizer.init(p, zero_axis="data"), mesh=mesh,
        in_specs=(P(),), out_specs=ospecs, check_vma=False))(params)
    ddp = parallel.DistributedDataParallel(model)
    x, y = _data()

    def step(state, batch):
        p, bn, os = state
        xb, yb = batch

        def loss_fn(pp):
            out, nb = model.apply(pp, xb, state=bn, train=True)
            return F.cross_entropy(out, yb), nb
        loss, nb, g = amp.scaled_grad(loss_fn, p, os, has_aux=True)
        p, os, _ = optimizer.step(p, os, g)   # reduce-scatter inside
        return (p, nb, os), lax.pmean(loss, "data")

    train = ddp.make_step(step, mesh=mesh, donate_state=False,
                          steps_per_call=2,
                          state_specs=(P(), P(), ospecs))
    kx = jnp.stack([x, x])
    ky = jnp.stack([y, y])
    state = (params, bn_state, opt_z)
    state, losses = train(state, (kx, ky))
    assert losses.shape == (2,)
    assert np.isfinite(np.asarray(losses)).all()
    # second call continues from the updated sharded state
    state, losses2 = train(state, (kx, ky))
    assert float(losses2[-1]) < float(losses[0])


# ---------------------------------------------------------------------------
# ZeRO-2/3: in-slice sharding on the hierarchical fabric
# ---------------------------------------------------------------------------

def _zero1_reference_masters(model, optimizer, params, bn_state, mesh,
                             x, y):
    """Gathered ZeRO-1 masters after one step — the parity baseline for
    the stage-2/3 variants (stage 1 is itself pinned to flat DDP
    above)."""

    def loss_fn_of(xb, yb, bn):
        def loss_fn(p):
            out, new_bn = model.apply(p, xb, state=bn, train=True)
            return F.cross_entropy(out, yb), new_bn
        return loss_fn

    ospecs = amp.zero_optimizer_specs(optimizer, params, "data")
    opt_z = jax.jit(jax.shard_map(
        lambda p: optimizer.init(p, zero_axis="data"), mesh=mesh,
        in_specs=(P(),), out_specs=ospecs, check_vma=False))(params)

    def masters(p, os, bn, xb, yb):
        _, _, g = amp.scaled_grad(loss_fn_of(xb, yb, bn), p, os,
                                  has_aux=True)
        _, os, _ = optimizer.step(p, os, g)
        return lax.all_gather(os.masters.buf, "data", axis=0, tiled=True)

    m1 = jax.jit(jax.shard_map(
        masters, mesh=mesh,
        in_specs=(P(), ospecs, P(), P("data"), P("data")),
        out_specs=P(), check_vma=False))(params, opt_z, bn_state, x, y)
    total = optimizer.init(params).masters.layout.total
    return np.asarray(m1)[:total], total


@pytest.mark.parametrize("compress", [False, True],
                         ids=["fp32-dcn", "bf16-dcn"])
def test_zero2_masters_match_zero1(compress):
    """ZeRO-2 (state sharded over the ICI slice, grads reduce-scattered
    in-slice then psum'd over DCN) must land on the same masters as
    ZeRO-1 after one step from identical state: the reduction totals
    are identical, only the scatter geometry differs.  With
    allreduce-style bf16 compression on the DCN hop the parity loosens
    to the bf16 rounding of the cross-slice partial sums."""
    model, optimizer, params, bn_state = _setup()
    mesh = Mesh(np.array(jax.devices()[:8]), ("data",))
    x, y = _data()
    m1, total = _zero1_reference_masters(model, optimizer, params,
                                         bn_state, mesh, x, y)

    def loss_fn_of(xb, yb, bn):
        def loss_fn(p):
            out, new_bn = model.apply(p, xb, state=bn, train=True)
            return F.cross_entropy(out, yb), new_bn
        return loss_fn

    ospecs = amp.zero_optimizer_specs(optimizer, params, "data",
                                      zero_stage=2, zero_ici_size=4,
                                      zero_compress_bf16=compress)
    opt_z = jax.jit(jax.shard_map(
        lambda p: optimizer.init(p, zero_axis="data", zero_stage=2,
                                 zero_ici_size=4,
                                 zero_compress_bf16=compress),
        mesh=mesh, in_specs=(P(),), out_specs=ospecs,
        check_vma=False))(params)

    # each device holds a 1/ici shard (NOT 1/world): the state is
    # replicated across the two DCN slices
    shard_sizes = {np.asarray(s.data).size
                   for s in opt_z.masters.buf.addressable_shards}
    padded = total + (-total) % 4
    assert shard_sizes == {padded // 4}
    assert opt_z.masters.layout.zero_ici == 4

    def z2_masters(p, os, bn, xb, yb):
        _, _, g = amp.scaled_grad(loss_fn_of(xb, yb, bn), p, os,
                                  has_aux=True)
        _, os, _ = optimizer.step(p, os, g)
        # full-axis gather: the device concat is [slice0's padded
        # buffer, slice1's padded buffer] back to back
        return lax.all_gather(os.masters.buf, "data", axis=0,
                              tiled=True)

    m2 = jax.jit(jax.shard_map(
        z2_masters, mesh=mesh,
        in_specs=(P(), ospecs, P(), P("data"), P("data")),
        out_specs=P(), check_vma=False))(params, opt_z, bn_state, x, y)
    m2 = np.asarray(m2)
    # the two DCN slices must hold bitwise-equal state (the DCN reduce
    # is deterministic and every slice applies the same update)
    assert m2.shape[0] == 2 * padded
    np.testing.assert_array_equal(m2[:padded], m2[padded:])
    tol = 2e-2 if compress else 1e-6
    np.testing.assert_allclose(m2[:total], m1, atol=tol)


def test_zero3_masters_match_zero1():
    """ZeRO-3: the masters ARE the param store — the forward regathers
    working-precision params just in time via zero_gather_params and
    step((), ...) consumes the already-scattered flat grad the gather
    transpose produces.  One step from identical state must agree with
    ZeRO-1 to float round-off."""
    model, optimizer, params, bn_state = _setup()
    mesh = Mesh(np.array(jax.devices()[:8]), ("data",))
    x, y = _data()
    m1, total = _zero1_reference_masters(model, optimizer, params,
                                         bn_state, mesh, x, y)

    ospecs = amp.zero_optimizer_specs(optimizer, params, "data",
                                      zero_stage=3, zero_ici_size=4)
    opt_z = jax.jit(jax.shard_map(
        lambda p: optimizer.init(p, zero_axis="data", zero_stage=3,
                                 zero_ici_size=4),
        mesh=mesh, in_specs=(P(),), out_specs=ospecs,
        check_vma=False))(params)

    def z3_masters(os, bn, xb, yb):
        def loss_fn(masters):
            p = amp.zero_gather_params(masters, "data")
            out, new_bn = model.apply(p, xb, state=bn, train=True)
            return F.cross_entropy(out, yb), new_bn
        loss, new_bn, g = amp.scaled_grad(loss_fn, os.masters, os,
                                          has_aux=True)
        _, os, _ = optimizer.step((), os, g)
        ici_groups = [[0, 1, 2, 3], [4, 5, 6, 7]]
        full = lax.all_gather(os.masters.buf, "data", axis=0,
                              tiled=True, axis_index_groups=ici_groups)
        return full, lax.pmean(loss, "data")

    m3, loss = jax.jit(jax.shard_map(
        z3_masters, mesh=mesh,
        in_specs=(ospecs, P(), P("data"), P("data")),
        out_specs=(P(), P()), check_vma=False))(opt_z, bn_state, x, y)
    m3 = np.asarray(m3)
    np.testing.assert_allclose(m3[:total], m1, atol=1e-6)
    assert np.isfinite(float(loss))


def test_zero_knob_validation():
    """The stage/ici/compress knob triple is validated identically at
    spec-building time and (inside the mapped trace) at init time —
    outside shard_map init deliberately degrades to replicated state,
    so the mapped path is the one that must reject."""
    model, optimizer, params, _ = _setup()
    mesh = Mesh(np.array(jax.devices()[:8]), ("data",))
    bad_knobs = (dict(zero_stage=4, zero_ici_size=2),
                 dict(zero_stage=0),
                 dict(zero_stage=2),                    # no ici size
                 dict(zero_stage=3),
                 dict(zero_stage=1, zero_compress_bf16=True))
    for bad in bad_knobs:
        with pytest.raises(ValueError):
            amp.zero_optimizer_specs(optimizer, params, "data", **bad)

    # one representative through the mapped init (trace-time raise)
    ospecs = amp.zero_optimizer_specs(optimizer, params, "data",
                                      zero_stage=2, zero_ici_size=4)
    with pytest.raises(ValueError, match="zero_ici_size"):
        jax.jit(jax.shard_map(
            lambda p: optimizer.init(p, zero_axis="data", zero_stage=2),
            mesh=mesh, in_specs=(P(),), out_specs=ospecs,
            check_vma=False))(params)


def test_zero3_rejects_nonfloat_leaves():
    """Stage 3 drops the working-precision params entirely, so every
    leaf must be rebuildable from the fp32 master buffer — an int leaf
    has no master storage and must be rejected at mapped init."""
    model, optimizer, params, _ = _setup()
    mesh = Mesh(np.array(jax.devices()[:8]), ("data",))
    tainted = dict(params)
    tainted["step_count"] = jnp.zeros((), jnp.int32)
    with pytest.raises(ValueError, match="non-float"):
        jax.jit(jax.shard_map(
            lambda p: optimizer.init(p, zero_axis="data", zero_stage=3,
                                     zero_ici_size=4),
            mesh=mesh, in_specs=(P(),),
            out_specs=jax.tree_util.tree_map(lambda _: P(), tainted),
            check_vma=False))(tainted)


def test_zero3_step_rejects_tree_grads():
    """Stage-3 step() consumes the flat grad shard produced by the
    zero_gather_params transpose; feeding it a per-param grad tree (the
    stage-1/2 shape) must fail loudly instead of silently mis-flattening."""
    model, optimizer, params, _ = _setup()
    mesh = Mesh(np.array(jax.devices()[:8]), ("data",))
    ospecs = amp.zero_optimizer_specs(optimizer, params, "data",
                                      zero_stage=3, zero_ici_size=4)
    opt_z = jax.jit(jax.shard_map(
        lambda p: optimizer.init(p, zero_axis="data", zero_stage=3,
                                 zero_ici_size=4),
        mesh=mesh, in_specs=(P(),), out_specs=ospecs,
        check_vma=False))(params)
    tree_grads = jax.tree_util.tree_map(jnp.ones_like, params)
    with pytest.raises(ValueError, match="flat grad shard"):
        jax.jit(jax.shard_map(
            lambda os, g: optimizer.step((), os, g)[1], mesh=mesh,
            in_specs=(ospecs, P()), out_specs=ospecs,
            check_vma=False))(opt_z, tree_grads)
