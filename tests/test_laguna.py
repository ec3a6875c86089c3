"""The per-layer decoder (models/laguna.py) against the benchmark's plain
reference (benchmark/references/laguna.py) at a tiny config that keeps every
kind of layer: a dense layer and one whole period (three sliding, one full),
two head counts, both RoPE kinds, routed experts with a shared one; its
attention layer at a head of 128 through the kernels (the rotary pass and the
token-major flash kernels, interpreted).  And the
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


@pytest.mark.parametrize("kind,rotated", [("full_attention", 64), ("sliding_attention", 128)])
def test_attention_layer_through_the_kernels_matches_the_reference(monkeypatch, kind, rotated):
    """Heads of a whole lane tile with Pallas on (interpreted here): q, k, v go
    from the projections through the rotary pass to the flash kernels
    token-major, K/V at their own head count; half-rotary and whole, full and
    banded, forward and gradients against the reference's attention."""
    from apex_tpu.models.laguna import LagunaAttention
    from apex_tpu.ops import pallas_rope
    from apex_tpu.transformer import attention
    cfg = dict(TINY, hidden_size=64, head_dim=128, num_hidden_layers=1, layer_types=[kind],
               num_attention_heads_per_layer=[4], mlp_layer_types=["dense"], sliding_window=24)
    layer = LagunaAttention(models.LagunaConfig.from_dict(cfg), 0)
    assert 2 * layer.inv_freq.shape[0] == rotated
    params, _ = layer.init(jax.random.PRNGKey(2))
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 64, 64), jnp.float32)
    want_fn = lambda p, x: jnp.stack([ref.attention(p, row, 4, cfg, kind, "float32") for row in x])
    weigh = jax.random.normal(jax.random.PRNGKey(4), x.shape)
    loss = lambda fn: (lambda p, x: jnp.sum(fn(p, x) * weigh))
    want, want_grads = want_fn(params, x), jax.grad(loss(want_fn), (0, 1))(params, x)
    plain = layer(params, x)                     # the CPU's forms: dense, jnp rotation
    monkeypatch.setenv("APEX_TPU_FORCE_PALLAS", "1")
    monkeypatch.delenv("APEX_TPU_DISABLE_PALLAS", raising=False)
    paths, passes = [], []
    real = pallas_rope.rope_token_major
    monkeypatch.setattr(pallas_rope, "rope_token_major",
                        lambda x, *a: passes.append(x.shape) or real(x, *a))
    attention.set_path_hook(paths.append)
    try:
        got, got_grads = layer(params, x), jax.grad(loss(layer), (0, 1))(params, x)
    finally:
        attention.set_path_hook(None)
    assert set(paths) == {"flash"} and passes[:2] == [(2, 64, 4 * 128), (2, 64, 2 * 128)]
    for a in (got, plain):
        np.testing.assert_allclose(np.asarray(a), np.asarray(want), atol=2e-5, rtol=2e-4)
    for (path, g), (_, w) in zip(*(jax.tree_util.tree_leaves_with_path(t)
                                   for t in (got_grads, want_grads))):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=2e-4, rtol=2e-4,
                                   err_msg=jax.tree_util.keystr(path))


@pytest.mark.parametrize("rd,dtype", [(128, jnp.float32), (64, jnp.float32), (64, jnp.bfloat16)])
def test_rotary_pass_matches_the_reference_rotation_and_its_gradient(rd, dtype):
    from apex_tpu.ops import pallas_rope
    rope = {"rope_theta": 10000, "partial_rotary_factor": rd / 128}
    cos, sin = ref.rope_tables(rope, 128, 72)
    x, g = (jax.random.normal(jax.random.PRNGKey(i), (2, 72, 3 * 128), dtype) for i in (5, 6))
    want_fn = lambda x: jax.vmap(lambda row: ref.apply_rope(
        row.reshape(72, 3, 128).astype(jnp.float32), cos, sin))(x).reshape(x.shape)
    f32 = lambda a: np.asarray(a, np.float32)
    got = pallas_rope.rope_token_major(x, cos, sin, 128)
    assert got.dtype == dtype
    tol = dict(atol=1e-5) if dtype == jnp.float32 else dict(atol=0.04, rtol=0.01)
    np.testing.assert_allclose(f32(got), f32(want_fn(x)), **tol)
    back = lambda fn: jax.grad(lambda x: jnp.sum(f32_j(fn(x)) * f32_j(g)))(x)
    f32_j = lambda a: a.astype(jnp.float32)
    np.testing.assert_allclose(f32(back(lambda x: pallas_rope.rope_token_major(x, cos, sin, 128))),
                               f32(back(want_fn)), **tol)
    assert pallas_rope.rows_per_block(8192) == 512 and pallas_rope.rows_per_block(300) == 0
    with pytest.raises(ValueError, match="lane tiles"):
        pallas_rope.rope_token_major(x[..., :192], cos, sin, 64)


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
    # sizes compare (the queue positions count it in blocks of 128, against a
    # 128 x 128 triangle of ones), row buffer
    largest = max(-(-tokens * k // 128) * 128 * experts, 128 * 128, tokens * k * max(d, h))

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
