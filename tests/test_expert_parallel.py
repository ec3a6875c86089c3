"""Expert-parallel MoE parity: the all_to_all dispatch over an 'expert'
mesh axis must match running the same per-shard routing math locally —
outputs and gradients — and the aux loss must be finite and O(1)."""

import numpy as np
import pytest
import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

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
