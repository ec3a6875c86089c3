"""The per-layer decoder (models/laguna.py) against the benchmark's plain
reference (benchmark/references/laguna.py) at a tiny config that keeps every
kind of layer: a dense layer and one whole period (three sliding, one full),
two head counts, both RoPE kinds, routed experts with a shared one.  And the
expert layer's contract: the shares of an expert-parallel group add up to the
uncut layer, nothing is dropped under skew, and what a too small row buffer
loses is counted."""

import os
import sys

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from apex_tpu import models
from apex_tpu.parallel import expert_parallel as ep

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if os.path.join(ROOT, "benchmark") not in sys.path:
    sys.path.insert(0, os.path.join(ROOT, "benchmark"))
from references import laguna as ref  # noqa: E402

TINY = dict(
    vocab_size=64, hidden_size=32, intermediate_size=64, num_hidden_layers=5,
    layer_types=["full_attention", "sliding_attention", "sliding_attention",
                 "sliding_attention", "full_attention"],
    num_attention_heads_per_layer=[4, 8, 8, 8, 4],
    mlp_layer_types=["dense", "sparse", "sparse", "sparse", "sparse"],
    num_key_value_heads=2, head_dim=16, gating=True, rms_norm_eps=1e-6,
    rope_parameters={
        "full_attention": {"rope_theta": 500000, "rope_type": "yarn", "factor": 64,
                           "original_max_position_embeddings": 16, "beta_slow": 1,
                           "beta_fast": 64, "attention_factor": 1.4158883083359672,
                           "partial_rotary_factor": 0.5},
        "sliding_attention": {"rope_type": "default", "rope_theta": 10000,
                              "partial_rotary_factor": 1}},
    sliding_window=8, num_experts=4, num_experts_published=16, experts_held_start=8,
    num_experts_per_tok=4, moe_intermediate_size=16, shared_expert_intermediate_size=16,
    moe_routed_scaling_factor=2.5, max_position_embeddings=64, head_chunk=24)
T = 32


@pytest.fixture(scope="module")
def tiny():
    model = models.Laguna(models.LagunaConfig.from_dict(TINY))
    params, _ = model.init(jax.random.PRNGKey(0))
    # norm gains away from 1 and a router that spreads its scores, so that no
    # term of the model is silent in the comparison
    leaves, tree = jax.tree_util.tree_flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(1), len(leaves))
    leaves = [x + 0.05 * jax.random.normal(k, x.shape) for x, k in zip(leaves, keys)]
    params = jax.tree_util.tree_unflatten(tree, leaves)
    ids = jnp.asarray(np.random.RandomState(0).randint(0, 64, (2, T)), jnp.int32)
    return model, params, ids


def _ref_loss(params, ids):
    return ref.summed_nll(params, ids, TINY) / (ids.shape[0] * (T - 1))


def test_rope_frequencies_match_the_reference_tables():
    for kind, rope in TINY["rope_parameters"].items():
        inv, scale = models.laguna.rope_inv_freq(rope, 16)
        ang = np.arange(T)[:, None] * inv[None, :]
        cos, _ = ref.rope_tables(rope, 16, T)
        np.testing.assert_allclose(np.cos(np.concatenate([ang, ang], -1)) * scale,
                                   np.asarray(cos), atol=2e-6, err_msg=kind)
    assert models.laguna.rope_inv_freq(TINY["rope_parameters"]["full_attention"], 16)[0].shape == (4,)


def test_logits_match_the_reference(tiny):
    model, params, ids = tiny
    np.testing.assert_allclose(np.asarray(model(params, ids)),
                               np.asarray(ref.logits(params, ids, TINY)), atol=2e-5)


def test_loss_matches_the_reference_and_counts_its_assignments(tiny):
    model, params, ids = tiny
    loss, stats = model.loss(params, ids, return_stats=True)
    np.testing.assert_allclose(float(loss), float(_ref_loss(params, ids)), rtol=2e-6)
    assert int(stats["moe_dropped_assignments"]) == 0
    # 4 expert layers x 64 tokens x 4 choices, a quarter of the experts held
    assert 0 < int(stats["moe_assignments_held"]) < 4 * 64 * 4
    assert int(stats["moe_expert_load_max"]) <= 64


@pytest.mark.parametrize("remat", [None, "dots", "nothing"])
def test_gradients_match_the_reference(tiny, remat):
    _, params, ids = tiny
    model = models.Laguna(models.LagunaConfig.from_dict(TINY, remat=remat))
    got = jax.grad(lambda p: model.loss(p, ids))(params)
    want = jax.grad(_ref_loss)(params, ids)
    flat_g, flat_w = (jax.tree_util.tree_leaves_with_path(t) for t in (got, want))
    assert len(flat_g) == len(flat_w)
    for (path, g), (_, w) in zip(flat_g, flat_w):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=3e-6, rtol=2e-4,
                                   err_msg=jax.tree_util.keystr(path))
        assert float(jnp.abs(w).max()) > 0, jax.tree_util.keystr(path)


def test_o2_keeps_the_router_in_float32_and_trains():
    from apex_tpu import amp, optimizers
    model, opt = amp.initialize(models.Laguna(models.LagunaConfig.from_dict(TINY)),
                                optimizers.FusedAdam(lr=1e-3), opt_level="O2", verbosity=0)
    params, _ = model.init(jax.random.PRNGKey(0))
    mlp = params["layers"]["1"]["mlp"]
    assert mlp["router"].dtype == jnp.float32
    assert mlp["w_in"].dtype == mlp["shared"]["w_in"].dtype == jnp.bfloat16
    opt_state = opt.init(params)
    ids = jnp.asarray(np.random.RandomState(1).randint(0, 64, (2, T)), jnp.int32)

    @jax.jit
    def step(params, opt_state):
        loss, grads = amp.scaled_grad(lambda p: model.loss(p, ids), params, opt_state)
        params, opt_state, _ = opt.step(params, opt_state, grads)
        return params, opt_state, loss

    losses = []
    for _ in range(8):
        params, opt_state, loss = step(params, opt_state)
        losses.append(float(loss))
    assert np.isfinite(losses).all() and losses[-1] < losses[0]


# -- the expert layer ---------------------------------------------------------

def _layer(held=None, **kw):
    return ep.ExpertParallelMLP(8, 16, 16, capacity_factor=None, top_k=4, expert_type="swiglu",
                                router_type="sigmoid", routed_scaling=2.5, experts_held=held,
                                shared_hidden=16, **kw)


def _ref_cfg(start=0):
    return {"num_experts_per_tok": 4, "moe_routed_scaling_factor": 2.5,
            "experts_held_start": start}


def test_the_shares_add_up_to_the_uncut_layer():
    """The outputs of all 16 one-expert shares, the shared expert counted
    once, sum to what the reference gives for the whole layer."""
    whole = _layer()
    params, _ = whole.init(jax.random.PRNGKey(3))
    x = jnp.asarray(np.random.RandomState(3).randn(24, 8), jnp.float32)
    want = ref.sparse_mlp(params, x, _ref_cfg(), "float32")
    shared = ref.swiglu(x, *(params["shared"][k].T for k in ("w_gate", "w_in", "w_out")), "float32")
    np.testing.assert_allclose(np.asarray(whole(params, x)), np.asarray(want), atol=2e-5)
    total, held = jnp.zeros_like(x), 0
    for e in range(16):
        share = {**params, **{k: params[k][e:e + 1] for k in ("w_gate", "w_in", "w_out")}}
        y, stats = _layer(held=(e, 1))(share, x, return_stats=True)
        np.testing.assert_allclose(np.asarray(y), np.asarray(
            ref.sparse_mlp(share, x, _ref_cfg(e), "float32")), atol=2e-5)
        total, held = total + (y - shared), held + int(stats["moe_assignments_held"])
    np.testing.assert_allclose(np.asarray(total + shared), np.asarray(want), atol=5e-5)
    assert held == 24 * 4           # every assignment lands on exactly one share


def _skewed(layer, seed=4):
    """Params whose router sends every token's first choice to expert 5."""
    params, _ = layer.init(jax.random.PRNGKey(seed))
    x = jnp.asarray(np.abs(np.random.RandomState(seed).randn(40, 8)) + 0.5, jnp.float32)
    return {**params, "router": params["router"].at[:, 5].set(4.0)}, x


def test_dispatch_drops_nothing_under_a_routing_skewed_onto_one_expert():
    layer = _layer()
    params, x = _skewed(layer)
    y, stats = layer(params, x, return_stats=True)
    assert int(stats["moe_expert_load_max"]) == 40      # every token chose expert 5
    assert int(stats["moe_dropped_assignments"]) == 0
    assert int(stats["moe_assignments_held"]) == 40 * 4
    np.testing.assert_allclose(np.asarray(y), np.asarray(
        ref.sparse_mlp(params, x, _ref_cfg(), "float32")), atol=3e-5)


def test_a_row_buffer_made_too_small_counts_what_it_lost():
    # half the expected 40 rows, rounded up to whole sublanes: 24
    layer = _layer(held=(4, 4), row_buffer_factor=0.5)
    params, x = _skewed(_layer(held=(4, 4)))
    y, stats = layer(params, x, return_stats=True)
    held, dropped = int(stats["moe_assignments_held"]), int(stats["moe_dropped_assignments"])
    assert held >= 40 and dropped == held - 24
    assert np.isfinite(np.asarray(y)).all()
    g = jax.grad(lambda p: jnp.sum(layer(p, x) ** 2))(params)
    assert all(np.isfinite(np.asarray(l)).all() for l in jax.tree_util.tree_leaves(g))


def test_the_sorted_dispatch_builds_no_token_by_expert_by_slot_operand():
    layer = _layer()
    params, x = _skewed(layer)
    jaxpr = jax.make_jaxpr(jax.grad(lambda p: jnp.sum(layer(p, x) ** 2)))(params)
    tokens, experts, k, d, h = 40, 16, 4, 8, 16
    largest = max(tokens * k * experts, tokens * k * max(d, h))   # sizes compare, row buffer

    def sizes(jp):
        for eqn in jp.eqns:
            yield from (int(np.prod(v.aval.shape)) for v in eqn.outvars)
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from sizes(sub)

    assert max(sizes(jaxpr.jaxpr)) <= largest
    assert "ragged_dot" in str(jaxpr)


def test_record_moe_counters_sets_the_registry_gauges():
    from apex_tpu.observability.metrics import MetricsRegistry
    reg = MetricsRegistry()
    ep.record_moe_counters({"moe_assignments_held": 7, "moe_dropped_assignments": 0, "loss": 1.0},
                           registry=reg)
    assert reg.gauge("moe_assignments_held").value == 7.0
    assert reg.gauge("moe_dropped_assignments").value == 0.0
