"""Expert-parallel MoE parity: the all_to_all dispatch over an 'expert'
mesh axis must match running the same per-shard routing math locally —
outputs and gradients — and the aux loss must be finite and O(1)."""

import numpy as np
import pytest
import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from apex_tpu.ops import row_moves
from apex_tpu.parallel import expert_parallel as ep
from conftest import assert_trees_close


def moe_and_params(E=8, d=8, h=16, seed=12, cap=2.0):
    moe = ep.ExpertParallelMLP(d, h, E, capacity_factor=cap)
    params, _ = moe.init(jax.random.PRNGKey(seed))
    return moe, params


def _ref_sharded(moe, params, x, n_shards):
    """Reference: each token shard routed independently (ep=1 path,
    outside any mesh), concatenated — the exact per-shard capacity
    semantics of the sharded run."""
    outs = [moe(params, xs) for xs in np.split(np.asarray(x), n_shards)]
    return jnp.concatenate([jnp.asarray(o) for o in outs])


def specs_of(moe, params):
    from apex_tpu.parallel import tensor_parallel as tp
    s = tp.partition_specs(moe, params)
    assert s["w_in"] == P("expert", None, None)
    assert s["router"] == P()
    return s


def test_moe_forward_matches_per_shard_reference():
    mesh = Mesh(np.array(jax.devices()[:4]), ("expert",))
    moe, params = moe_and_params()
    specs = specs_of(moe, params)
    x = jnp.asarray(np.random.RandomState(0).randn(16, 8), jnp.float32)

    y = jax.jit(jax.shard_map(
        lambda p, xb: moe(p, xb), mesh=mesh,
        in_specs=(specs, P("expert")), out_specs=P("expert"),
        check_vma=False))(params, x)
    y_ref = _ref_sharded(moe, params, x, 4)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               atol=2e-5)


def test_moe_capacity_drops_tokens():
    """capacity_factor small enough forces drops: output rows for
    dropped tokens are zero, and nothing NaNs."""
    moe, params = moe_and_params(cap=0.25)
    x = jnp.asarray(np.random.RandomState(1).randn(16, 8), jnp.float32)
    y = moe(params, x)
    assert np.isfinite(np.asarray(y)).all()
    zero_rows = np.sum(np.all(np.asarray(y) == 0.0, axis=-1))
    assert zero_rows > 0          # with C=ceil(0.25*16/8)=1 some drop


def test_moe_gradients_match_per_shard_reference():
    mesh = Mesh(np.array(jax.devices()[:4]), ("expert",))
    moe, params = moe_and_params()
    specs = specs_of(moe, params)
    x = jnp.asarray(np.random.RandomState(2).randn(16, 8), jnp.float32)

    def sharded_grad(p, xb):
        g = jax.grad(lambda pp: jnp.sum(jnp.square(moe(pp, xb))))(p)
        # the router is data-parallel over the expert axis (each device
        # routed only its token shard): sum its grad like DDP would
        g["router"] = lax.psum(g["router"], "expert")
        return g

    g_tp = jax.jit(jax.shard_map(
        sharded_grad, mesh=mesh, in_specs=(specs, P("expert")),
        out_specs=specs, check_vma=False))(params, x)

    def ref_loss(p):
        return jnp.sum(jnp.square(_ref_sharded(moe, p, x, 4)))

    assert_trees_close(g_tp, jax.grad(ref_loss)(params), atol=3e-5)


def test_moe_aux_loss():
    moe, params = moe_and_params()
    x = jnp.asarray(np.random.RandomState(3).randn(16, 8), jnp.float32)
    y, aux = moe(params, x, return_aux_loss=True)
    # Switch aux: >= 1 (perfect balance) and modest for random routing
    assert 0.9 < float(aux) < 8.0
    assert y.shape == x.shape


def test_moe_expert_divisibility_check():
    mesh = Mesh(np.array(jax.devices()[:4]), ("expert",))
    moe, params = moe_and_params(E=6)     # 6 experts, ep=4
    x = jnp.zeros((8, 8))
    # replicated params so shard_map's own shape check doesn't fire
    # first — the module's divisibility error is the one users see
    with pytest.raises(ValueError, match="not divisible"):
        jax.jit(jax.shard_map(
            lambda p, xb: moe(p, xb), mesh=mesh,
            in_specs=(jax.tree_util.tree_map(lambda _: P(), params),
                      P("expert")),
            out_specs=P("expert"), check_vma=False))(params, x)


# -- top-k (Mixtral-shape) routing ---------------------------------------

def _loop_moe(moe, params, x):
    """Per-token loop oracle: choice-major capacity queueing (all first
    choices enqueue before any second choice), renormalized gates,
    SwiGLU or plain experts."""
    import math
    x2 = np.asarray(x)
    T, d = x2.shape
    E, k = moe.n_experts, moe.top_k
    # an expert's capacity counts all k assignments of a token (the
    # capacity repair of the sorted dispatch: cf * T * k / E rows)
    C = moe.capacity(T)
    assert C == max(1, math.ceil(moe.capacity_factor * T * k / E))
    logits = x2 @ np.asarray(params["router"])
    z = np.exp(logits - logits.max(1, keepdims=True))
    probs = z / z.sum(1, keepdims=True)
    top = np.argsort(-probs, axis=1, kind="stable")[:, :k]
    gates = np.take_along_axis(probs, top, 1)
    if k > 1:
        gates = gates / gates.sum(1, keepdims=True)
    counts = np.zeros(E, np.int64)
    y = np.zeros_like(x2)
    wi = np.asarray(params["w_in"])
    wo = np.asarray(params["w_out"])
    wg = np.asarray(params.get("w_gate")) if "w_gate" in params else None
    for c in range(k):
        for t in range(T):
            e = top[t, c]
            if counts[e] >= C:
                continue
            counts[e] += 1
            if wg is not None:
                h = x2[t] @ wg[e]
                h = h / (1.0 + np.exp(-h)) * (x2[t] @ wi[e])
            else:
                h = x2[t] @ wi[e]
                h = 0.5 * h * (1.0 + np.tanh(
                    np.sqrt(2.0 / np.pi) * (h + 0.044715 * h ** 3)))
            y[t] += gates[t, c] * (h @ wo[e])
    return y


def _dense_grad_f64(moe, params, x, n_shards):
    """Gradient of sum(y**2) in float64 with no dispatch at all: each
    token shard routed on its own (softmax, top-k renormalized,
    choice-major queue under the capacity), every SwiGLU expert computed
    for every token and weighted by the gate it kept."""
    E, k = moe.n_experts, moe.top_k
    with jax.enable_x64(True):
        def loss(p):
            total = 0.0
            for xs in np.split(np.asarray(x, np.float64), n_shards):
                xs = jnp.asarray(xs)
                T = xs.shape[0]
                gates, experts = lax.top_k(
                    jax.nn.softmax(xs @ p["router"], axis=-1), k)
                gates = gates / jnp.sum(gates, axis=-1, keepdims=True)
                oh = jax.nn.one_hot(experts.T.reshape(-1), E)  # (kT, E)
                pos = jnp.sum(jnp.cumsum(oh, axis=0) * oh, axis=-1) - 1
                kept = (pos < moe.capacity(T)).reshape(k, T).T
                w = jnp.einsum("tk,tke->te", gates * kept,
                               jax.nn.one_hot(experts, E))
                h = (jax.nn.silu(jnp.einsum("td,edh->teh", xs, p["w_gate"]))
                     * jnp.einsum("td,edh->teh", xs, p["w_in"]))
                y = jnp.einsum("te,teh,ehd->td", w, h, p["w_out"])
                total = total + jnp.sum(jnp.square(y))
            return total

        return jax.grad(loss)(jax.tree_util.tree_map(
            lambda a: jnp.asarray(np.asarray(a), jnp.float64), params))


@pytest.mark.parametrize("cap", [2.0, 0.5])
def test_moe_top2_swiglu_matches_loop_oracle(cap):
    """top_k=2 + SwiGLU experts vs the per-token loop — including
    tight capacity (cap=0.5 forces drops, and the oracle's choice-major
    queue checks that second choices drop first)."""
    moe = ep.ExpertParallelMLP(8, 16, 8, capacity_factor=cap,
                               top_k=2, expert_type="swiglu")
    params, _ = moe.init(jax.random.PRNGKey(5))
    x = jnp.asarray(np.random.RandomState(5).randn(24, 8), jnp.float32)
    y = moe(params, x)
    np.testing.assert_allclose(np.asarray(y), _loop_moe(moe, params, x),
                               rtol=2e-4, atol=2e-5)


def test_moe_top2_sharded_matches_per_shard_reference():
    mesh = Mesh(np.array(jax.devices()[:4]), ("expert",))
    moe = ep.ExpertParallelMLP(8, 16, 8, capacity_factor=2.0,
                               top_k=2, expert_type="swiglu")
    params, _ = moe.init(jax.random.PRNGKey(6))
    specs = specs_of(moe, params)
    assert specs["w_gate"] == P("expert", None, None)
    x = jnp.asarray(np.random.RandomState(6).randn(16, 8), jnp.float32)

    y = jax.jit(jax.shard_map(
        lambda p, xb: moe(p, xb), mesh=mesh,
        in_specs=(specs, P("expert")), out_specs=P("expert"),
        check_vma=False))(params, x)
    y_ref = _ref_sharded(moe, params, x, 4)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               atol=2e-5)


def test_moe_top2_gradients_match_per_shard_reference():
    mesh = Mesh(np.array(jax.devices()[:4]), ("expert",))
    moe = ep.ExpertParallelMLP(8, 16, 8, capacity_factor=2.0,
                               top_k=2, expert_type="swiglu")
    params, _ = moe.init(jax.random.PRNGKey(7))
    specs = specs_of(moe, params)
    x = jnp.asarray(np.random.RandomState(7).randn(16, 8), jnp.float32)

    def sharded_grad(p, xb):
        g = jax.grad(lambda pp: jnp.sum(jnp.square(moe(pp, xb))))(p)
        g["router"] = lax.psum(g["router"], "expert")
        return g

    g_tp = jax.jit(jax.shard_map(
        sharded_grad, mesh=mesh, in_specs=(specs, P("expert")),
        out_specs=specs, check_vma=False))(params, x)

    def ref_loss(p):
        return jnp.sum(jnp.square(_ref_sharded(moe, p, x, 4)))

    # the exchanged masks and the local sorted dispatch sum in different
    # orders (router gradients reach 60 here), so each is held to the
    # float64 oracle and not to the other
    g64 = _dense_grad_f64(moe, params, x, 4)
    assert_trees_close(g_tp, g64, atol=3e-5)
    assert_trees_close(jax.grad(ref_loss)(params), g64, atol=3e-5)


def test_moe_top2_gates_renormalized():
    """Combine weights for an un-dropped token sum to 1 (Mixtral
    renormalization), not to the raw top-2 softmax mass."""
    moe = ep.ExpertParallelMLP(8, 16, 4, capacity_factor=8.0, top_k=2)
    params, _ = moe.init(jax.random.PRNGKey(8))
    x = jnp.asarray(np.random.RandomState(8).randn(8, 8), jnp.float32)
    _, combine, _ = moe._dispatch(
        x, params["router"], capacity=16)
    np.testing.assert_allclose(np.asarray(combine).sum((1, 2)),
                               np.ones(8), rtol=1e-5)


def test_moe_top_k_validation():
    with pytest.raises(ValueError, match="top_k"):
        ep.ExpertParallelMLP(8, 16, 4, top_k=5)
    with pytest.raises(ValueError, match="expert_type"):
        ep.ExpertParallelMLP(8, 16, 4, expert_type="dense")


# -- the sorted dispatch's row moves (ops/row_moves.py) ----------------------

def _row_move_calls():
    from apex_tpu.observability.metrics import get_registry
    c = get_registry().get("moe_row_move_calls_total")
    return ({tuple(v for _, v in sorted(k)): child.value
             for k, child in c.children().items()} if c else {})


def _sorted_layer(expert_type, shared, cap=None, held=4):
    layer = ep.ExpertParallelMLP(
        128, 128, 16, capacity_factor=cap, top_k=4, expert_type=expert_type,
        activation="relu", experts_held=(4, held), shared_hidden=shared,
        row_buffer_factor=2.0)
    params = layer.init(jax.random.PRNGKey(3))[0]
    x = jax.random.normal(jax.random.PRNGKey(4), (128, 128), jnp.float32)
    return layer, params, x


@pytest.mark.parametrize("expert_type,shared,cap,held", [
    ("swiglu", None, None, 4), ("swiglu", 128, None, 4), ("mlp", None, None, 4),
    ("mlp", 256, None, 4), ("swiglu", 128, 1.0, 4), ("swiglu", 128, None, 1)],
    ids=["gated", "gated_shared", "ungated", "ungated_shared",
         "gated_shared_capacity", "a_sixteenth_held"])
def test_sorted_forward_by_gathers_equals_sorted_forward_by_scatter_add(
        monkeypatch, expert_type, shared, cap, held):
    """``_sorted_forward`` forward and backward, the grouped kernels forced
    (the production gating, interpreted off the chip), its way home by
    gathers against its way home by ``.at[token].add``, whichever the shapes
    would choose: values, what was dropped and every gradient, the router's
    through the gate weights among them; no scatter is traced for the rows in
    either direction of the first."""
    import re
    monkeypatch.setenv("APEX_TPU_FORCE_PALLAS", "1")
    monkeypatch.delenv("APEX_TPU_DISABLE_PALLAS", raising=False)
    layer, params, x = _sorted_layer(expert_type, shared, cap, held)
    rows = 2 * 128 * 4 * held // 16
    assert row_moves.home_by_gathers(128 * 4, rows) == (held == 4)

    def loss(by_gathers):
        def of(p, x):
            # (a fresh function a form: the layer's trace is cached)
            monkeypatch.setattr(row_moves, "home_by_gathers", lambda *_: by_gathers)
            y, aux, stats = layer._sorted_forward(p, x, True)
            return jnp.sum(y ** 2) + aux, stats["moe_dropped_assignments"]
        return jax.value_and_grad(of, (0, 1), has_aux=True)

    (want, want_dropped), want_g = loss(False)(params, x)
    (got, got_dropped), got_g = loss(True)(params, x)
    np.testing.assert_allclose(got, want, rtol=1e-5)
    assert int(got_dropped) == int(want_dropped) and (int(got_dropped) > 0) == (cap is not None)
    for a, b in zip(jax.tree_util.tree_leaves(got_g), jax.tree_util.tree_leaves(want_g)):
        assert a.dtype == b.dtype and a.shape == b.shape
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-5 * float(jnp.abs(b).max()))
    # the scatter-add left is the router's own (top-k's gradient), (T, E);
    # the other form keeps the rows' two and the gates' one
    for by_gathers, more in ((True, set()), (False, {"f32[128,128]", "f32[512]"})):
        text = str(jax.make_jaxpr(lambda p, x: loss(by_gathers)(p, x)[1])(params, x))
        assert f"f32[{rows},128]" in text
        assert set(re.findall(r"(\w+\[[\d,]*\]) = scatter", text)) == {"f32[128,16]"} | more


def test_row_moves_are_counted_where_they_are_traced():
    """4 a traced layer and gradient, each move and its transpose; 2 a traced
    forward; and the layer whose slots are mostly empty says that its way
    home is the scatter-add."""
    layer, params, x = _sorted_layer("swiglu", None)
    loss = lambda p, x: jnp.sum(layer(p, x) ** 2)
    sparse, sparse_params, _ = _sorted_layer("swiglu", None, held=1)

    def grown(trace):
        before = _row_move_calls()
        trace()
        return {k: v - before.get(k, 0) for k, v in _row_move_calls().items()
                if v != before.get(k, 0)}

    assert grown(lambda: jax.make_jaxpr(lambda p, x: loss(p, x))(params, x)) == {
        ("gather", "rows_from_tokens"): 1, ("gather", "tokens_from_rows"): 1}
    assert grown(lambda: jax.make_jaxpr(jax.grad(lambda p, x: loss(p, x), (0, 1)))(
        params, x)) == {("gather", "rows_from_tokens"): 2, ("gather", "tokens_from_rows"): 2}
    assert grown(lambda: jax.make_jaxpr(lambda p, x: jnp.sum(sparse(p, x)))(
        sparse_params, x)) == {("gather", "rows_from_tokens"): 2,
                               ("scatter_add", "tokens_from_rows"): 2}
