"""The per-layer decoder (models/laguna.py) configured as ``mellum`` against
the benchmark's plain reference (benchmark/references/mellum2.py) at a tiny
config that keeps a whole period (three sliding layers, one full), 8 query
heads to a K/V group, YaRN over the whole head, a softmax router whose top-k
is renormalized, no shared expert and no dense layer; the shares of its 4-way
expert-parallel group adding up; the sorted dispatch at 8 experts a token; a
sliding layer through the interpreted kernels; the flash launches of the five
shapes the benchmark's cells run, pinned side by side; the expert layer's
trace-time counters; and the ``laguna`` step unchanged by the switches this
configuration needed."""

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
from references import laguna as laguna_ref, mellum2 as ref  # noqa: E402
from test_laguna import TINY as LAGUNA_TINY  # noqa: E402

TINY = dict(
    model_type="mellum", vocab_size=64, hidden_size=32, intermediate_size=64,
    num_hidden_layers=4, layer_types=["sliding_attention"] * 3 + ["full_attention"],
    mlp_layer_types=["sparse"] * 4, num_attention_heads=8, num_key_value_heads=1, head_dim=16,
    rms_norm_eps=1e-6, norm_topk_prob=True, router_type="softmax", gating=False,
    rope_parameters={
        "full_attention": {"rope_type": "yarn", "rope_theta": 500000, "factor": 16,
                           "original_max_position_embeddings": 16, "beta_fast": 32,
                           "beta_slow": 1, "attention_factor": 1.2772588722239782},
        "sliding_attention": {"rope_type": "default", "rope_theta": 500000}},
    sliding_window=8, num_experts=4, num_experts_published=16, experts_held_start=8,
    num_experts_per_tok=4, moe_intermediate_size=16, max_position_embeddings=64, head_chunk=24)
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


def test_the_configuration_builds_what_the_published_file_says(tiny):
    model, params, _ = tiny
    cfg = model.cfg
    assert cfg.num_attention_heads_per_layer == (8, 8, 8, 8)      # from one num_attention_heads
    assert cfg.router_type == "softmax" and not cfg.gating
    layer = params["layers"]["0"]
    assert set(layer["self_attn"]) == {"q_proj", "k_proj", "v_proj", "o_proj"}       # no gate
    assert set(layer["mlp"]) == {"router", "w_gate", "w_in", "w_out"}                # no shared expert
    assert layer["mlp"]["router"].shape == (32, 16) and layer["mlp"]["w_in"].shape == (4, 32, 16)
    assert all(block.sparse for block in model.layers)                              # no dense layer
    # YaRN over all 8 pairs of the head, scaled by the file's attention_factor
    inv, scale = models.laguna.rope_inv_freq(TINY["rope_parameters"]["full_attention"], 16)
    assert inv.shape == (8,) and scale == pytest.approx(1.2772588722239782)
    with pytest.raises(ValueError, match="num_attention_heads"):
        models.LagunaConfig.from_dict({k: v for k, v in TINY.items() if k != "num_attention_heads"})


def test_rope_frequencies_match_the_reference_tables():
    for kind, rope in TINY["rope_parameters"].items():
        inv, scale = models.laguna.rope_inv_freq(rope, 16)
        ang = np.arange(T)[:, None] * inv[None, :]
        cos, _ = ref.rope_tables(rope, 16, T)
        np.testing.assert_allclose(np.cos(np.concatenate([ang, ang], -1)) * scale,
                                   np.asarray(cos), atol=2e-6, err_msg=kind)


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


def test_one_fused_adam_step_matches_the_references_adam(tiny):
    """The model's gradient through FusedAdam's flat buffers (float32, no amp)
    against the reference's gradient through its own Adam, leaf by leaf."""
    from apex_tpu import optimizers
    model, params, ids = tiny
    hp = ref.ADAM
    opt = optimizers.FusedAdam(lr=hp["lr"], weight_decay=hp["weight_decay"])
    state = opt.init(params)
    new, _ = opt.step(params, state, jax.grad(lambda p: model.loss(p, ids))(params))[:2]
    zeros = jax.tree_util.tree_map(jnp.zeros_like, params)
    want, _, _ = ref.adam_update(params, zeros, zeros, jax.grad(_ref_loss)(params, ids),
                                 jnp.float32(1), hp, "float32")
    for (path, a), (_, b), (_, p0) in zip(*(jax.tree_util.tree_leaves_with_path(t)
                                            for t in (new, want, params))):
        moved = np.asarray(b) - np.asarray(p0)
        assert np.abs(moved).max() > 0, jax.tree_util.keystr(path)
        # a first Adam step is lr * g / (|g| + eps): where |g| is near eps, rounding shows
        np.testing.assert_allclose(np.asarray(a) - np.asarray(p0), moved, atol=1e-5, rtol=2e-3,
                                   err_msg=jax.tree_util.keystr(path))


def test_o2_keeps_the_router_in_float32_and_trains():
    from apex_tpu import amp, optimizers
    model, opt = amp.initialize(models.Laguna(models.LagunaConfig.from_dict(TINY)),
                                optimizers.FusedAdam(lr=1e-3), opt_level="O2", verbosity=0)
    params, _ = model.init(jax.random.PRNGKey(0))
    mlp = params["layers"]["1"]["mlp"]
    assert mlp["router"].dtype == jnp.float32 and mlp["w_in"].dtype == jnp.bfloat16
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


def test_the_references_projections_measure_what_two_gradients_differ_by():
    """``leaf_norms`` keeps a norm and 64 fixed random projections a leaf; the
    projections of two trees differ by what the trees differ by, so ``compare``
    reads the size of a gradient's error where norms alone read how long each
    gradient is: a rotation that keeps every norm is seen, and its size is
    estimated to within the projections' own scatter."""
    ks = jax.random.split(jax.random.PRNGKey(11), 5)
    tree = {"stack": jax.random.normal(ks[0], (4, 64, 32)), "gain": jax.random.normal(ks[1], (2304,)),
            "proj": jax.random.normal(ks[2], (300, 200)), "odd": jax.random.normal(ks[3], (32,))}
    want = np.asarray(ref.leaf_norms(tree))
    assert want.shape == (4, 1 + ref.SKETCH ** 2)
    np.testing.assert_allclose(want[:, 0], [float(jnp.linalg.norm(tree[k])) for k in sorted(tree)],
                               rtol=1e-5)
    np.testing.assert_array_equal(want, np.asarray(ref.leaf_norms(tree)))        # fixed signs
    for eps in (0.01, 0.1):
        noise = {k: jax.random.normal(ks[4], x.shape) for k, x in tree.items()}
        # keep each leaf's norm: only the direction moves
        moved = {k: (x + eps * noise[k]) * jnp.linalg.norm(x) / jnp.linalg.norm(x + eps * noise[k])
                 for k, x in tree.items()}
        got = np.asarray(ref.leaf_norms(moved))
        np.testing.assert_allclose(got[:, 0], want[:, 0], rtol=1e-5)
        worst, _, mean = ref.difference_norms(got, want)
        assert 0.6 * eps < mean <= worst < 1.5 * eps
        run = lambda norms: {"losses": [1.0], "first_grad_norms": norms, "update_norms": want}
        numbers = ref.compare(run(got), run(want))
        assert numbers["grad_norm_gap_mean"] < 1e-5 and numbers["grad_diff_mean"] == mean
    assert set(ref.LIMITS) <= set(numbers) and ref.difference_norms(want, want)[0] == 0.0


def test_one_leaf_with_the_wrong_sign_is_read_at_the_worst_leaf_and_hardly_by_the_mean():
    """What ``grad_diff_mean`` is blunt to (PERF.md section 2): one leaf of forty
    with the wrong sign reads 2 at that leaf and adds a fortieth to the mean, a
    leaf counting for at most 1; the norms see nothing of it."""
    tree = {f"{i:02d}": jax.random.normal(jax.random.PRNGKey(i), (64, 48)) for i in range(40)}
    want = np.asarray(ref.leaf_norms(tree))
    got = np.asarray(ref.leaf_norms(dict(tree, **{"07": -tree["07"]})))
    run = lambda norms: {"losses": [1.0], "first_grad_norms": norms, "update_norms": want}
    numbers = ref.compare(run(got), run(want))
    assert numbers["grad_diff_leaf"] == 7 and 1.5 < numbers["grad_diff_at_worst_leaf"] < 2.5
    assert abs(numbers["grad_diff_mean"] - 1 / 40) < 1e-6 and numbers["grad_diff_at_median_leaf"] == 0.0
    assert numbers["grad_norm_gap_mean"] < 1e-6


# -- the expert layer: a softmax router over 16, 8 a token, no shared expert -----

def _layer(held=None, **kw):
    return ep.ExpertParallelMLP(8, 16, 16, capacity_factor=None, top_k=8, expert_type="swiglu",
                                router_type="softmax", experts_held=held, **kw)


def _ref_cfg(start=0):
    return {"num_experts_per_tok": 8, "norm_topk_prob": True, "experts_held_start": start}


def test_the_four_shares_add_up_to_the_uncut_layer():
    """The outputs of the 4 shares of a 4-way expert-parallel group (4 of 16
    experts each; nothing is computed by every chip alike, there is no shared
    expert) sum to what the reference gives for the whole layer."""
    whole = _layer()
    params, _ = whole.init(jax.random.PRNGKey(3))
    assert "shared" not in params
    x = jnp.asarray(np.random.RandomState(3).randn(24, 8), jnp.float32)
    want = ref.sparse_mlp(params, x, _ref_cfg(), "float32")
    np.testing.assert_allclose(np.asarray(whole(params, x)), np.asarray(want), atol=2e-5)
    total, held = jnp.zeros_like(x), 0
    for start in range(0, 16, 4):
        share = {**params, **{k: params[k][start:start + 4] for k in ("w_gate", "w_in", "w_out")}}
        y, stats = _layer(held=(start, 4))(share, x, return_stats=True)
        np.testing.assert_allclose(np.asarray(y), np.asarray(
            ref.sparse_mlp(share, x, _ref_cfg(start), "float32")), atol=2e-5)
        total, held = total + y, held + int(stats["moe_assignments_held"])
    np.testing.assert_allclose(np.asarray(total), np.asarray(want), atol=5e-5)
    assert held == 24 * 8           # every assignment lands on exactly one share


def test_gate_weights_follow_norm_topk_prob():
    """softmax then top-k: the chosen weights are renormalized to sum 1, which
    is what a config's ``norm_topk_prob: true`` states; a config that states
    false is refused, not built with the weights renormalized all the same."""
    x = jnp.asarray(np.random.RandomState(5).randn(12, 8), jnp.float32)
    router = jnp.asarray(np.random.RandomState(6).randn(8, 16), jnp.float32)
    gates, experts, _ = _layer()._route(x, router, False)
    probs = jax.nn.softmax(x @ router, -1)
    top = jnp.take_along_axis(probs, experts, -1)
    np.testing.assert_allclose(np.asarray(gates.sum(-1)), 1.0, atol=1e-6)
    assert float(top.sum(-1).max()) < 1.0
    np.testing.assert_allclose(np.asarray(top / top.sum(-1, keepdims=True)), np.asarray(gates), atol=1e-6)
    with pytest.raises(ValueError, match="norm_topk_prob"):
        models.LagunaConfig.from_dict(dict(TINY, norm_topk_prob=False))


def _skewed(layer, seed=4):
    """Params whose router sends every token's first choice to expert 5."""
    params, _ = layer.init(jax.random.PRNGKey(seed))
    x = jnp.asarray(np.abs(np.random.RandomState(seed).randn(40, 8)) + 0.5, jnp.float32)
    return {**params, "router": params["router"].at[:, 5].set(4.0)}, x


def test_dispatch_at_eight_a_token_drops_nothing_under_a_skewed_router():
    layer = _layer()
    params, x = _skewed(layer)
    y, stats = layer(params, x, return_stats=True)
    assert int(stats["moe_expert_load_max"]) == 40      # every token chose expert 5
    assert int(stats["moe_dropped_assignments"]) == 0
    assert int(stats["moe_assignments_held"]) == 40 * 8
    np.testing.assert_allclose(np.asarray(y), np.asarray(
        ref.sparse_mlp(params, x, _ref_cfg(), "float32")), atol=3e-5)


def test_a_row_buffer_made_too_small_at_eight_a_token_counts_what_it_lost():
    # half the expected 40 * 8 * 4 / 16 = 80 rows: 40
    layer = _layer(held=(4, 4), row_buffer_factor=0.5)
    params, x = _skewed(_layer(held=(4, 4)))
    y, stats = layer(params, x, return_stats=True)
    held, dropped = int(stats["moe_assignments_held"]), int(stats["moe_dropped_assignments"])
    assert held >= 40 and dropped == held - 40
    assert np.isfinite(np.asarray(y)).all()
    g = jax.grad(lambda p: jnp.sum(layer(p, x) ** 2))(params)
    assert all(np.isfinite(np.asarray(l)).all() for l in jax.tree_util.tree_leaves(g))


def test_a_traced_expert_layer_says_which_router_buffer_and_share_it_is():
    from apex_tpu.observability.metrics import get_registry
    reg = get_registry()

    def read():
        calls = reg.get("moe_router_calls_total")
        by = ({tuple(v for _, v in sorted(k)): c.value for k, c in calls.children().items()}
              if calls else {})
        totals = {n: (reg.get(n).value if reg.get(n) else 0.0) for n in (
            "moe_row_buffer_rows_total", "moe_experts_held_total", "moe_router_experts_total")}
        return by, totals

    x = jax.ShapeDtypeStruct((40, 8), jnp.float32)
    soft = _layer(held=(4, 4), row_buffer_factor=2.0)
    sig = ep.ExpertParallelMLP(8, 16, 16, capacity_factor=None, top_k=4, expert_type="swiglu",
                               router_type="sigmoid")
    p_soft, p_sig = (jax.eval_shape(lambda k, l=l: l.init(k)[0], jax.random.PRNGKey(0))
                     for l in (soft, sig))
    by0, tot0 = read()
    jax.eval_shape(soft, p_soft, x)
    jax.eval_shape(sig, p_sig, x)
    by1, tot1 = read()
    grew = {k: v - by0.get(k, 0) for k, v in by1.items() if v != by0.get(k, 0)}
    assert grew == {("softmax", "8"): 1, ("sigmoid", "4"): 1}
    # softmax share: 2 x 40 * 8 * 4 / 16 = 160 rows, 4 of 16; the whole sigmoid layer: 40 * 4 rows
    assert tot1["moe_row_buffer_rows_total"] - tot0["moe_row_buffer_rows_total"] == 160 + 160
    assert tot1["moe_experts_held_total"] - tot0["moe_experts_held_total"] == 4 + 16
    assert tot1["moe_router_experts_total"] - tot0["moe_router_experts_total"] == 16 + 16
    # recorded on the host while tracing: the program has no output for them
    out = jax.eval_shape(lambda p, x: soft(p, x, return_stats=True), p_soft, x)
    assert set(out[1]) == set(ep.MOE_COUNTERS)


# -- attention: 8 query heads to a K/V head, a band of two blocks ---------------

def test_sliding_layer_through_the_kernels_matches_the_reference(monkeypatch):
    """Heads of a whole lane tile with Pallas on (interpreted here): q, k, v go
    from the projections through the rotary pass (the whole head rotates) to the
    flash kernels token-major, 8 query heads of one group a grid step reading
    the one K/V head, a window of one block, so that a row of blocks sees two:
    forward and gradients against the reference's attention."""
    from apex_tpu.models.laguna import LagunaAttention
    from apex_tpu.ops import pallas_flash_attention as pfa, pallas_rope
    from apex_tpu.transformer import attention
    cfg = dict(TINY, hidden_size=64, head_dim=128, num_hidden_layers=1,
               layer_types=["sliding_attention"], mlp_layer_types=["sparse"], sliding_window=256)
    seq = 512
    blk = pfa._block_for(seq, 256)
    assert blk == 256 and pfa._band_blocks(256, blk, seq // blk) == 2
    assert pfa._heads_per_step(8, 128, 4, False, blk, 8) == 1      # float32 here: one head a step
    assert pfa._heads_per_step(8, 128, 2, False, blk, 8) == 8      # bf16 on the chip: the group
    layer = LagunaAttention(models.LagunaConfig.from_dict(cfg), 0)
    assert 2 * layer.inv_freq.shape[0] == 128 and not layer.gating
    params, _ = layer.init(jax.random.PRNGKey(2))
    x = jax.random.normal(jax.random.PRNGKey(3), (1, seq, 64), jnp.float32)
    want_fn = lambda p, x: jnp.stack([ref.attention(p, row, cfg, "sliding_attention", "float32")
                                      for row in x])
    weigh = jax.random.normal(jax.random.PRNGKey(4), x.shape)
    loss = lambda fn: (lambda p, x: jnp.sum(fn(p, x) * weigh))
    want, want_grads = want_fn(params, x), jax.grad(loss(want_fn), (0, 1))(params, x)
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
    assert set(paths) == {"flash"} and passes[:2] == [(1, seq, 8 * 128), (1, seq, 128)]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5, rtol=2e-4)
    for (path, g), (_, w) in zip(*(jax.tree_util.tree_leaves_with_path(t)
                                   for t in (got_grads, want_grads))):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=2e-4, rtol=2e-4,
                                   err_msg=jax.tree_util.keystr(path))


def test_a_group_of_eight_bf16_heads_a_step_equals_the_reference_band():
    """The launch the chip makes for a sliding layer: bf16, the 8 heads of the
    group in one grid step, a band of two blocks (interpreted)."""
    from apex_tpu.ops import pallas_flash_attention as pfa
    seq, window, D = 512, 256, 128
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    q = jax.random.normal(ks[0], (1, seq, 8, D), jnp.bfloat16)
    k, v = (jax.random.normal(kk, (1, seq, 1, D), jnp.bfloat16) for kk in ks[1:])
    got = pfa.flash_attention_token_major(q, k, v, causal=True, window=window)
    f32 = lambda a: a.astype(jnp.float32)
    s = jnp.einsum("bqhd,bkd->bhqk", f32(q), f32(k[:, :, 0])) / np.sqrt(D)
    i, j = np.arange(seq)[:, None], np.arange(seq)[None, :]
    see = (j <= i) & (j > i - window)
    want = jnp.einsum("bhqk,bkd->bqhd", jax.nn.softmax(jnp.where(see, s, -jnp.inf), -1),
                      f32(v[:, :, 0]))
    np.testing.assert_allclose(np.asarray(f32(got)), np.asarray(want), atol=0.03, rtol=0.02)


# (what the cell's layer is, query heads, K/V heads, head size, T, window, masked) -> (block, heads a step)
CELL_LAUNCHES = [
    ("bert-large", 16, 16, 64, 512, None, (512, 4)),
    ("laguna-xs2 full", 48, 8, 128, 8192, None, (512, 6)),
    ("laguna-xs2 sliding", 64, 8, 128, 8192, 512, (256, 8)),
    ("mellum2-12b full", 32, 4, 128, 8192, None, (512, 4)),
    ("mellum2-12b sliding", 32, 4, 128, 8192, 1024, (256, 8)),
]


@pytest.mark.parametrize("what,H,Hkv,D,seq,window,want", CELL_LAUNCHES,
                         ids=[c[0].replace(" ", "-") for c in CELL_LAUNCHES])
def test_the_flash_launch_of_each_shape_the_cells_run_is_pinned(what, H, Hkv, D, seq, window, want):
    """One chooser serves every cell: a change meant for one shows here in the
    others (bf16 operands, no mask operand, as the cells call it)."""
    from apex_tpu.ops import pallas_flash_attention as pfa
    blk = pfa._block_for(seq, window)
    assert (blk, pfa._heads_per_step(H, pfa._head_width(D), 2, False, blk, H // Hkv)) == want


# -- the laguna step is the one it was ------------------------------------------

def test_the_new_switches_at_their_defaults_leave_the_laguna_step_as_it_was():
    """The tiny ``laguna`` training step traced twice, once from a config with
    every key this configuration added absent and once with each stated at its
    default: one jaxpr, letter for letter."""
    from apex_tpu import amp, optimizers
    stated = dict(LAGUNA_TINY, router_type="sigmoid", norm_topk_prob=True, num_attention_heads=4)
    ids = jnp.asarray(np.random.RandomState(1).randint(0, 64, (2, T)), jnp.int32)

    def step_jaxpr(cfg):
        amp.policy.set_policy(amp.policy.NoPolicy())
        model, opt = amp.initialize(models.Laguna(models.LagunaConfig.from_dict(cfg)),
                                    optimizers.FusedAdam(lr=1e-3), opt_level="O2", verbosity=0)
        params = jax.eval_shape(lambda k: model.init(k)[0], jax.random.PRNGKey(0))
        opt_state = jax.eval_shape(opt.init, params)

        def step(params, opt_state):
            loss, stats, grads = amp.scaled_grad(
                lambda p: model.loss(p, ids, return_stats=True), params, opt_state, has_aux=True)
            params, opt_state, _ = opt.step(params, opt_state, grads)
            return params, opt_state, loss, stats

        try:
            return str(jax.make_jaxpr(step)(params, opt_state))
        finally:
            amp.policy.set_policy(amp.policy.NoPolicy())

    absent, present = step_jaxpr(LAGUNA_TINY), step_jaxpr(stated)
    assert absent == present and "ragged_dot" in absent and "logistic" in absent
