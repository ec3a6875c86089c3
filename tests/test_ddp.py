"""DDP multi-device tests — the analogue of the reference's
tests/distributed/DDP/ddp_race_condition_test.py (grads must equal the
analytic cross-rank sum) plus options parity, run on the virtual 8-device
CPU mesh."""

import numpy as np
import pytest
import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from apex_tpu.parallel import (DistributedDataParallel, Reducer,
                               allreduce_grads_tree, flat_dist_call,
                               predivide_factors)


@pytest.fixture
def mesh():
    return Mesh(np.array(jax.devices()[:8]), ("data",))


def _run(mesh, fn, *args, in_specs, out_specs):
    return jax.jit(jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                                 out_specs=out_specs, check_vma=False))(*args)


def test_allreduce_matches_analytic_sum(mesh):
    # each rank contributes rank-dependent grads; result must be the mean
    x = jnp.arange(8.0)

    def fn(xs):
        rank = lax.axis_index("data").astype(jnp.float32)
        grads = {"w": jnp.full((5,), rank + 1.0),
                 "b": jnp.full((3,), 2.0 * (rank + 1.0))}
        out = allreduce_grads_tree(grads, "data")
        return out

    out = _run(mesh, fn, x, in_specs=(P("data"),), out_specs=P())
    # mean over ranks of (rank+1) = 4.5
    np.testing.assert_allclose(np.asarray(out["w"]), 4.5)
    np.testing.assert_allclose(np.asarray(out["b"]), 9.0)


def test_allreduce_no_average(mesh):
    def fn(xs):
        grads = {"w": jnp.ones((4,))}
        return allreduce_grads_tree(grads, "data", gradient_average=False)

    out = _run(mesh, fn, jnp.arange(8.0), in_specs=(P("data"),),
               out_specs=P())
    np.testing.assert_allclose(np.asarray(out["w"]), 8.0)


def test_allreduce_predivide_factor(mesh):
    # predivide by k, postdivide by world/k: same mean, different range
    def fn(xs):
        grads = {"w": jnp.full((4,), 8.0)}
        return allreduce_grads_tree(grads, "data",
                                    gradient_predivide_factor=4.0)

    out = _run(mesh, fn, jnp.arange(8.0), in_specs=(P("data"),),
               out_specs=P())
    np.testing.assert_allclose(np.asarray(out["w"]), 8.0)


def test_allreduce_fp32_upcast_of_half_grads(mesh):
    def fn(xs):
        grads = {"w": jnp.full((4,), 3.0, jnp.bfloat16)}
        out = allreduce_grads_tree(grads, "data",
                                   allreduce_always_fp32=True)
        return out

    out = _run(mesh, fn, jnp.arange(8.0), in_specs=(P("data"),),
               out_specs=P())
    assert out["w"].dtype == jnp.bfloat16  # cast back after the collective
    np.testing.assert_allclose(np.asarray(out["w"], np.float32), 3.0)


def test_allreduce_message_size_chunking_matches_unchunked(mesh):
    rng = np.random.RandomState(0)
    g_np = rng.randn(1000).astype(np.float32)

    def fn_chunked(xs):
        rank = lax.axis_index("data").astype(jnp.float32)
        grads = {"w": jnp.asarray(g_np) * (rank + 1)}
        return allreduce_grads_tree(grads, "data", message_size=128)

    def fn_whole(xs):
        rank = lax.axis_index("data").astype(jnp.float32)
        grads = {"w": jnp.asarray(g_np) * (rank + 1)}
        return allreduce_grads_tree(grads, "data", delay_allreduce=True)

    a = _run(mesh, fn_chunked, jnp.arange(8.0), in_specs=(P("data"),),
             out_specs=P())
    b = _run(mesh, fn_whole, jnp.arange(8.0), in_specs=(P("data"),),
             out_specs=P())
    np.testing.assert_allclose(np.asarray(a["w"]), np.asarray(b["w"]),
                               rtol=1e-6)


def test_mixed_dtype_grads_split_buckets(mesh):
    def fn(xs):
        grads = {"a": jnp.ones((4,), jnp.float32),
                 "b": jnp.ones((4,), jnp.bfloat16),
                 "c": jnp.ones((2, 2), jnp.float32)}
        return allreduce_grads_tree(grads, "data")

    out = _run(mesh, fn, jnp.arange(8.0), in_specs=(P("data"),),
               out_specs=P())
    assert out["a"].dtype == jnp.float32
    assert out["b"].dtype == jnp.bfloat16
    assert out["c"].shape == (2, 2)


def test_reducer(mesh):
    def fn(xs):
        rank = lax.axis_index("data").astype(jnp.float32)
        red = Reducer(axis_name="data")
        return red.reduce({"t": jnp.full((3,), rank)})

    out = _run(mesh, fn, jnp.arange(8.0), in_specs=(P("data"),),
               out_specs=P())
    np.testing.assert_allclose(np.asarray(out["t"]), 3.5)  # mean of 0..7


def test_flat_dist_call_ops(mesh):
    def fn(xs):
        rank = lax.axis_index("data").astype(jnp.float32)
        t = {"v": jnp.full((2,), rank)}
        return (flat_dist_call(t, "data", "psum")["v"],
                flat_dist_call(t, "data", "pmax")["v"])

    s, mx = _run(mesh, fn, jnp.arange(8.0), in_specs=(P("data"),),
                 out_specs=(P(), P()))
    np.testing.assert_allclose(np.asarray(s), 28.0)
    np.testing.assert_allclose(np.asarray(mx), 7.0)


def test_ddp_wrapper_make_step_end_to_end(mesh):
    """Full DDP train step: sharded batch, replicated params, loss down."""
    import apex_tpu
    from apex_tpu import amp, nn, optimizers
    from apex_tpu.nn import functional as F

    class Tiny(nn.Module):
        def __init__(self):
            super().__init__()
            self.fc1 = nn.Linear(8, 32)
            self.fc2 = nn.Linear(32, 4)

        def forward(self, p, x):
            return self.fc2(p["fc2"], F.relu(self.fc1(p["fc1"], x)))

    model, optimizer = amp.initialize(Tiny(), optimizers.FusedAdam(1e-2),
                                      opt_level="O2", verbosity=0)
    ddp = DistributedDataParallel(model, message_size=64)
    params, _ = model.init(jax.random.PRNGKey(0))
    opt_state = optimizer.init(params)
    rng = np.random.RandomState(0)
    X = jnp.asarray(rng.randn(64, 8), jnp.float32)
    Y = jnp.asarray(rng.randint(0, 4, 64))

    def step(state, batch):
        params, opt_state = state
        x, y = batch

        def loss_fn(p):
            out, _ = model.apply(p, x)
            return F.cross_entropy(out, y)

        loss, grads = amp.scaled_grad(loss_fn, params, opt_state)
        grads = ddp.allreduce_grads(grads)
        params, opt_state, _ = optimizer.step(params, opt_state, grads)
        return (params, opt_state), lax.pmean(loss, "data")

    train = ddp.make_step(step, mesh=mesh, donate_state=False)
    state = (params, opt_state)
    losses = []
    for _ in range(10):
        state, loss = train(state, (X, Y))
        losses.append(float(loss))
    assert losses[-1] < losses[0]


def test_allreduce_trigger_params_bucket_boundaries(mesh):
    """allreduce_trigger_params (reference distributed.py:162-171): the
    listed leaves mark bucket flush points; values must equal the
    untriggered allreduce, and unknown paths must raise."""
    def fn(xs):
        rank = lax.axis_index("data").astype(jnp.float32)
        grads = {"a": jnp.full((5,), rank + 1.0),
                 "b": jnp.full((3,), 2.0 * (rank + 1.0)),
                 "c": jnp.full((2,), 3.0 * (rank + 1.0))}
        ref = allreduce_grads_tree(grads, "data")
        out = allreduce_grads_tree(grads, "data", trigger_paths={"b"})
        return ref, out

    ref, out = _run(mesh, fn, jnp.arange(8.0), in_specs=(P("data"),),
                    out_specs=P())
    for k in ref:
        np.testing.assert_allclose(np.asarray(out[k]), np.asarray(ref[k]))

    ddp = DistributedDataParallel(allreduce_trigger_params=["nope"])
    with pytest.raises(ValueError, match="nope"):
        _run(mesh, lambda xs: ddp.allreduce_grads(
            {"a": jnp.ones((4,))}), jnp.arange(8.0),
            in_specs=(P("data"),), out_specs=P())


def test_broadcast_params_from_rank0(mesh):
    """Reducer/DDP init-broadcast parity (reference distributed.py:100-104,
    :234): after broadcast every rank holds rank 0's values."""
    def fn(xs):
        rank = lax.axis_index("data").astype(jnp.float32)
        params = {"w": jnp.full((4,), rank + 7.0),
                  "b": jnp.full((2,), rank).astype(jnp.bfloat16)}
        red = Reducer(axis_name="data")
        out = red.broadcast_params(params)
        ddp = DistributedDataParallel()
        out2 = ddp.broadcast_params(params)
        return out, out2

    out, out2 = _run(mesh, fn, jnp.arange(8.0), in_specs=(P("data"),),
                     out_specs=P())  # replicated out => identical everywhere
    np.testing.assert_allclose(np.asarray(out["w"]), 7.0)
    np.testing.assert_allclose(np.asarray(out["b"], np.float32), 0.0)
    assert out["b"].dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(out2["w"]), 7.0)


def test_syncbn_unmapped_axis_check_does_not_swallow_errors():
    """The mapped-axis check replaces the NameError catch: outside any
    mesh the module degrades to local BN (world_size==1 parity), but a
    genuine error inside stat sync propagates."""
    from apex_tpu.parallel import SyncBatchNorm
    bn = SyncBatchNorm(3)
    params, state = bn.init(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 3, 4, 4))
    out, _ = bn.apply(params, x, state=state, train=True)   # no mesh: local
    assert out.shape == x.shape


def test_make_step_steps_per_call_matches_sequential(mesh):
    """K steps in one dispatch (lax.scan) must equal K sequential
    dispatches bitwise."""
    from apex_tpu import nn, optimizers
    from apex_tpu.nn import functional as F
    model = nn.Sequential([nn.Linear(4, 8), nn.ReLU(), nn.Linear(8, 2)])
    params, _ = model.init(jax.random.PRNGKey(0))
    opt = optimizers.SGD(lr=0.1)
    opt_state = opt.init(params)
    ddp = DistributedDataParallel(model)

    def step(state, batch):
        p, s = state
        x, y = batch

        def loss_fn(p):
            return jnp.mean((model(p, x) - y) ** 2)

        loss, grads = jax.value_and_grad(loss_fn)(p)
        grads = ddp.allreduce_grads(grads)
        p, s = opt.update(grads, s, p)
        return (p, s), lax.pmean(loss, "data")

    rng = np.random.RandomState(0)
    K = 3
    xs = jnp.asarray(rng.randn(K, 16, 4), jnp.float32)
    ys = jnp.asarray(rng.randn(K, 16, 2), jnp.float32)

    one = ddp.make_step(step, mesh=mesh, donate_state=False)
    st = (params, opt_state)
    for i in range(K):
        st, loss = one(st, (xs[i], ys[i]))

    multi = ddp.make_step(step, mesh=mesh, donate_state=False,
                          steps_per_call=K)
    st2, losses = multi((params, opt_state), (xs, ys))
    assert losses.shape == (K,)
    for a, b in zip(jax.tree_util.tree_leaves(st),
                    jax.tree_util.tree_leaves(st2)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def _rank_grads(g_np):
    rank = lax.axis_index("data").astype(jnp.float32)
    return {"w": jnp.asarray(g_np) * (rank + 1)}


def test_hierarchical_allreduce_matches_flat(mesh):
    """The tentpole numerics pin: the two-level ICI/DCN reduction
    (psum_scatter in-slice -> DCN reduce on the 1/ici shard ->
    all_gather back) must track the flat psum to float round-off —
    the same reduction-order caveat test_zero.py pins for ZeRO-1's
    psum_scatter-vs-psum split.  Both ici splits of the 8-device mesh,
    and a size that forces shard padding."""
    rng = np.random.RandomState(0)
    g_np = rng.randn(1001).astype(np.float32)   # 1001 % 4 != 0: pads

    def fn(xs):
        flat = allreduce_grads_tree(_rank_grads(g_np), "data")
        h4 = allreduce_grads_tree(_rank_grads(g_np), "data",
                                  comm_topology="hierarchical",
                                  ici_size=4)
        h2 = allreduce_grads_tree(_rank_grads(g_np), "data",
                                  comm_topology="hierarchical",
                                  ici_size=2)
        return flat, h4, h2

    flat, h4, h2 = _run(mesh, fn, jnp.arange(8.0),
                        in_specs=(P("data"),), out_specs=P())
    np.testing.assert_allclose(np.asarray(h4["w"]), np.asarray(flat["w"]),
                               rtol=1e-6)
    np.testing.assert_allclose(np.asarray(h2["w"]), np.asarray(flat["w"]),
                               rtol=1e-6)


def test_hierarchical_compressed_matches_flat_at_bf16_tolerance(mesh):
    """allreduce_compress_bf16 quantizes ONLY the DCN hop: the result
    tracks the flat mean at bf16 resolution (one quantization of the
    per-slice partial sums), not at fp32 round-off."""
    rng = np.random.RandomState(1)
    g_np = rng.randn(512).astype(np.float32)

    def fn(xs):
        flat = allreduce_grads_tree(_rank_grads(g_np), "data")
        comp = allreduce_grads_tree(_rank_grads(g_np), "data",
                                    comm_topology="hierarchical",
                                    ici_size=4,
                                    allreduce_compress_bf16=True)
        return flat, comp

    flat, comp = _run(mesh, fn, jnp.arange(8.0),
                      in_specs=(P("data"),), out_specs=P())
    f, c = np.asarray(flat["w"]), np.asarray(comp["w"])
    assert np.max(np.abs(c - f) / np.maximum(np.abs(f), 1e-3)) < 2e-2
    # and it is NOT bitwise flat (the wire really was quantized)
    assert np.any(c != f)


def test_hierarchical_composes_with_fp32_comm_and_dtypes(mesh):
    """allreduce_always_fp32 + hierarchical: bf16 grads upcast once,
    the whole two-level reduction runs fp32 (compression would halve
    only the DCN hop), and the result casts back to bf16."""
    def fn(xs):
        g = {"w": jnp.full((6,), 3.0, jnp.bfloat16)}
        out = allreduce_grads_tree(g, "data",
                                   comm_topology="hierarchical",
                                   ici_size=4,
                                   allreduce_always_fp32=True)
        outc = allreduce_grads_tree(g, "data",
                                    comm_topology="hierarchical",
                                    ici_size=4,
                                    allreduce_always_fp32=True,
                                    allreduce_compress_bf16=True)
        return out, outc

    out, outc = _run(mesh, fn, jnp.arange(8.0), in_specs=(P("data"),),
                     out_specs=P())
    assert out["w"].dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(out["w"], np.float32), 3.0)
    np.testing.assert_allclose(np.asarray(outc["w"], np.float32), 3.0)


def test_hierarchical_predivide_no_double_average(mesh):
    """gradient_predivide_factor under the hierarchical topology: the
    pre/post split still divides by world exactly ONCE across both
    fabric levels (no per-level re-averaging)."""
    def fn(xs):
        g = {"w": jnp.full((4,), 8.0)}
        return allreduce_grads_tree(g, "data",
                                    comm_topology="hierarchical",
                                    ici_size=2,
                                    gradient_predivide_factor=4.0)

    out = _run(mesh, fn, jnp.arange(8.0), in_specs=(P("data"),),
               out_specs=P())
    np.testing.assert_allclose(np.asarray(out["w"]), 8.0)


def test_predivide_factors_helper_and_groups(mesh):
    """The audited pre/post division split (satellite): pre * post ==
    world for any factor, and the grouped + predivide + fp32-comm
    combination — where ``world`` is the GROUP size — still yields the
    group mean in the right dtype."""
    pre, post = predivide_factors(8.0, 4.0)
    assert pre * post == 8.0
    pre1, post1 = predivide_factors(8.0)
    assert (pre1, post1) == (1.0, 8.0)

    groups = [[0, 1, 2, 3], [4, 5, 6, 7]]

    def fn(xs):
        rank = lax.axis_index("data").astype(jnp.float32)
        # group 0 holds 4.0s, group 1 holds 8.0s (bf16 exact values)
        g = {"w": jnp.full((4,), jnp.where(rank < 4, 4.0, 8.0)
                           ).astype(jnp.bfloat16)}
        return allreduce_grads_tree(g, "data", axis_index_groups=groups,
                                    gradient_predivide_factor=2.0,
                                    allreduce_always_fp32=True)

    out = _run(mesh, fn, jnp.arange(8.0), in_specs=(P("data"),),
               out_specs=P("data"))
    assert out["w"].dtype == jnp.bfloat16
    # out_specs=P("data"): rank r owns out[4r:4r+4] — ranks 0-3 are
    # group 0, ranks 4-7 group 1
    vals = np.asarray(out["w"], np.float32)
    np.testing.assert_allclose(vals[:16], 4.0)  # group means, not /8
    np.testing.assert_allclose(vals[16:], 8.0)


def test_hierarchical_composes_with_larc(mesh):
    """LARC composition: the trust-ratio rescale consumes hierarchical
    grads exactly like flat ones — loss trajectories must agree to
    round-off step for step."""
    from apex_tpu import nn, optimizers, parallel
    model = nn.Sequential([nn.Linear(4, 8), nn.ReLU(), nn.Linear(8, 2)])
    params, _ = model.init(jax.random.PRNGKey(0))
    opt = parallel.LARC(optimizers.SGD(lr=0.05), trust_coefficient=0.02)
    opt_state = opt.init(params)
    rng = np.random.RandomState(3)
    X = jnp.asarray(rng.randn(16, 4), jnp.float32)
    Y = jnp.asarray(rng.randn(16, 2), jnp.float32)

    def make(topology):
        ddp = DistributedDataParallel(
            model, comm_topology=topology,
            ici_size=4 if topology == "hierarchical" else None)

        def step(state, batch):
            p, s = state
            x, y = batch

            def loss_fn(p):
                return jnp.mean((model(p, x) - y) ** 2)

            loss, grads = jax.value_and_grad(loss_fn)(p)
            grads = ddp.allreduce_grads(grads)
            p, s = opt.update(grads, s, p)
            return (p, s), lax.pmean(loss, "data")
        return ddp.make_step(step, mesh=mesh, donate_state=False)

    state_f = state_h = (params, opt_state)
    train_f, train_h = make("flat"), make("hierarchical")
    for _ in range(3):
        state_f, lf = train_f(state_f, (X, Y))
        state_h, lh = train_h(state_h, (X, Y))
        np.testing.assert_allclose(float(lf), float(lh), rtol=1e-5)
    for a, b in zip(jax.tree_util.tree_leaves(state_f[0]),
                    jax.tree_util.tree_leaves(state_h[0])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-6)


def test_comm_topology_auto_resolves_flat_single_process(mesh):
    """The auto heuristic: one process => no DCN => flat (recorded in
    the trace-time comm stats), and compression silently stays off."""
    ddp = DistributedDataParallel(comm_topology="auto",
                                  allreduce_compress_bf16=True)

    def fn(xs):
        return ddp.allreduce_grads({"w": jnp.ones((4,))})

    out = _run(mesh, fn, jnp.arange(8.0), in_specs=(P("data"),),
               out_specs=P())
    np.testing.assert_allclose(np.asarray(out["w"]), 1.0)
    assert [b["topology"] for b in ddp.last_comm_stats] == ["flat"]


def test_comm_topology_validation_errors(mesh):
    with pytest.raises(ValueError, match="comm_topology"):
        DistributedDataParallel(comm_topology="diagonal")
    with pytest.raises(ValueError, match="no inner level"):
        DistributedDataParallel(comm_topology="flat",
                                allreduce_compress_bf16=True)
    from apex_tpu.parallel import hierarchical_axis_groups
    with pytest.raises(ValueError, match="divide"):
        hierarchical_axis_groups(8, 3)

    def bad_ici(xs):
        return allreduce_grads_tree({"w": jnp.ones((4,))}, "data",
                                    comm_topology="hierarchical",
                                    ici_size=3)
    with pytest.raises(ValueError, match="divide"):
        _run(mesh, bad_ici, jnp.arange(8.0), in_specs=(P("data"),),
             out_specs=P())

    def hier_groups(xs):
        return allreduce_grads_tree(
            {"w": jnp.ones((4,))}, "data",
            comm_topology="hierarchical", ici_size=4,
            axis_index_groups=[[0, 1, 2, 3], [4, 5, 6, 7]])
    with pytest.raises(NotImplementedError, match="axis_index_groups"):
        _run(mesh, hier_groups, jnp.arange(8.0), in_specs=(P("data"),),
             out_specs=P())


def test_hierarchical_comm_stats_per_level_bytes(mesh):
    """comm_stats / ddp.last_comm_stats carry the per-level split: DCN
    bytes are exactly 1/ici of the (padded) bucket, and the chunked
    flat path now reports TRUE on-wire bytes (padding included) plus
    the padded_elements field — the byte-accounting satellite."""
    ddp_h = DistributedDataParallel(comm_topology="hierarchical",
                                    ici_size=4)
    ddp_c = DistributedDataParallel(message_size=100)

    def fn(xs):
        g = {"w": jnp.ones((310,), jnp.float32)}
        return ddp_h.allreduce_grads(g), ddp_c.allreduce_grads(g)

    _run(mesh, fn, jnp.arange(8.0), in_specs=(P("data"),),
         out_specs=P())
    (h,) = ddp_h.last_comm_stats
    assert h["topology"] == "hierarchical"
    assert h["wire_elements"] == 312 and h["padded_elements"] == 2
    assert h["dcn_wire_bytes"] == (312 // 4) * 4
    assert h["ici_wire_bytes"] == 312 * 4 + (312 // 4) * 4
    assert h["bytes"] == h["ici_wire_bytes"] + h["dcn_wire_bytes"]
    (c,) = ddp_c.last_comm_stats
    assert c["cause"] == "chunked" and c["chunks"] == 4
    assert c["wire_elements"] == 400 and c["padded_elements"] == 90
    assert c["bytes"] == 400 * 4            # true on-wire, not 310*4
    assert c["ici_wire_bytes"] == c["dcn_wire_bytes"] == 400 * 4


def test_make_mesh_axis_inference_and_errors():
    from apex_tpu.parallel.topology import make_mesh, mesh_info

    m = make_mesh(data=-1)
    assert m.axis_names == ("data",)
    assert m.devices.size == len(jax.devices())

    m2 = make_mesh(data=-1, sp=2)
    assert m2.axis_names == ("data", "sp")
    assert m2.devices.shape == (len(jax.devices()) // 2, 2)

    with pytest.raises(ValueError, match="at most one axis"):
        make_mesh(a=-1, b=-1)
    with pytest.raises(ValueError, match="do not divide"):
        make_mesh(data=3)   # 8 CPU devices % 3 != 0

    info = mesh_info(m2)
    assert "sp" in info and "device(s)" in info


def test_constructor_is_the_references_plus_five():
    """The constructor takes the reference's nine (``SURVEY.md``,
    distributed.py:129-171: the module and eight options), the mesh axis
    that stands where the reference has its process group, and this
    repo's five that a cell across chips could judge.  A further one
    cannot arrive unnoticed; nor can a switch set on the object after it
    is built (the compute twin's ``comm_enabled`` was one)."""
    import inspect
    reference = ["module", "message_size", "delay_allreduce",
                 "shared_param", "allreduce_trigger_params",
                 "retain_allreduce_buffers", "allreduce_always_fp32",
                 "gradient_average", "gradient_predivide_factor"]
    ours = ["comm_topology", "allreduce_compress_bf16", "ici_size",
            "overlap", "zero_stage"]
    sig = inspect.signature(DistributedDataParallel.__init__)
    assert list(sig.parameters)[1:] == reference + ["axis_name"] + ours
    # what the object holds beyond its arguments is what it recorded
    # while tracing, never a switch
    recorded = {"allreduce_buffers", "last_comm_stats",
                "last_overlap_schedule"}
    held = set(vars(DistributedDataParallel())) - recorded
    assert held == set(reference + ["axis_name"] + ours) - {"shared_param"}
