"""The per-layer decoder (models/laguna.py) configured as ``lfm2_moe`` against
the benchmark's plain reference (benchmark/references/lfm2.py) at a tiny config
with every mechanism present: a leading dense ``conv`` layer, a sparse attention
layer (grouped heads, QK-norm), sparse ``conv`` layers, 8 experts held of a
16-wide sigmoid router at 4 a token with a nonzero selection bias, a tied head.
Beside it: the bias enters the choice and not the weights and gets no gradient;
the shares of a 4-way expert-parallel group add up; the convolution starts every
row from zeros; a grouped head of 64 reaches the flash kernels (interpreted);
the grouped products' tile at this configuration's block, with the accepted
cells' tiles where they were; the trace-time counters; and the ``laguna`` and
``mellum`` steps unchanged by the switches this configuration needed."""

import os
import sys

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from apex_tpu import models
from apex_tpu.parallel import expert_parallel as ep
from apex_tpu.transformer import GatedShortConv, gated_short_conv

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if os.path.join(ROOT, "benchmark") not in sys.path:
    sys.path.insert(0, os.path.join(ROOT, "benchmark"))
from references import lfm2 as ref  # noqa: E402
from test_laguna import TINY as LAGUNA_TINY  # noqa: E402
from test_mellum2 import TINY as MELLUM_TINY  # noqa: E402

TINY = dict(
    model_type="lfm2_moe", vocab_size=64, hidden_size=32, intermediate_size=64,
    num_hidden_layers=5, layer_types=["conv", "full_attention", "conv", "conv", "conv"],
    mlp_layer_types=["dense"] + ["sparse"] * 4, num_dense_layers=1,
    num_attention_heads=4, num_key_value_heads=2, head_dim=8, norm_eps=1e-5, rms_norm_eps=1e-5,
    norm_topk_prob=True, router_type="sigmoid", gating=False, qk_norm=True, conv_L_cache=3,
    use_expert_bias=True, tie_word_embeddings=True, router_out_in=True, sliding_window=None,
    rope_parameters={"full_attention": {"rope_type": "default", "rope_theta": 1000000}},
    num_experts=8, num_experts_published=16, experts_held_start=4, num_experts_per_tok=4,
    moe_intermediate_size=16, moe_routed_scaling_factor=1.0, shared_expert_intermediate_size=0,
    max_position_embeddings=64, head_chunk=24)
T = 32


def _perturbed(params, seed=1, scale=0.05):
    """Norm gains away from 1, a bias away from 0 and a router that spreads its
    scores, so that no term of the model is silent in a comparison."""
    leaves, tree = jax.tree_util.tree_flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(leaves))
    return jax.tree_util.tree_unflatten(
        tree, [x + scale * jax.random.normal(k, x.shape) for x, k in zip(leaves, keys)])


@pytest.fixture(scope="module")
def tiny():
    model = models.Laguna(models.LagunaConfig.from_dict(TINY))
    params = _perturbed(model.init(jax.random.PRNGKey(0))[0])
    ids = jnp.asarray(np.random.RandomState(0).randint(0, 64, (2, T)), jnp.int32)
    return model, params, ids


def _ref_loss(params, ids):
    return ref.summed_nll(params, ids, TINY) / (ids.shape[0] * (T - 1))


def test_the_configuration_builds_what_the_published_file_says(tiny):
    model, params, _ = tiny
    cfg = model.cfg
    assert cfg.rms_norm_eps == 1e-5 and cfg.qk_norm and cfg.use_expert_bias
    assert set(params) == {"embed_tokens", "layers", "norm"}                  # a tied head: one leaf
    kinds = [(b.mixer, b.sparse) for b in model.layers]
    assert kinds == [("conv", False), ("self_attn", True)] + [("conv", True)] * 3
    conv = params["layers"]["0"]["conv"]
    assert set(conv) == {"in_proj", "conv", "out_proj"} and "self_attn" not in params["layers"]["0"]
    assert conv["in_proj"]["weight"].shape == (96, 32) and conv["conv"]["weight"].shape == (3, 32)
    attn = params["layers"]["1"]["self_attn"]
    assert set(attn) == {"q_proj", "k_proj", "v_proj", "o_proj", "q_layernorm", "k_layernorm"}
    assert attn["q_layernorm"]["weight"].shape == (8,) and attn["k_proj"]["weight"].shape == (16, 32)
    assert set(params["layers"]["0"]["mlp"]) == {"gate_proj", "up_proj", "down_proj"}
    mlp = params["layers"]["2"]["mlp"]
    assert set(mlp) == {"router", "expert_bias", "w_gate", "w_in", "w_out"}
    assert mlp["router"].shape == (16, 32) and mlp["expert_bias"].shape == (16,)   # (out, in)
    assert mlp["w_in"].shape == (8, 32, 16)
    # a conv layer needs no rope group; an attention layer still does
    with pytest.raises(ValueError, match="rope_parameters"):
        models.LagunaConfig.from_dict(dict(TINY, rope_parameters={}))
    with pytest.raises(ValueError, match="unknown layer type"):
        models.LagunaConfig.from_dict(dict(TINY, layer_types=["conv1d"] * 5))


def test_logits_match_the_reference(tiny):
    model, params, ids = tiny
    np.testing.assert_allclose(np.asarray(model(params, ids)),
                               np.asarray(ref.logits(params, ids, TINY)), atol=2e-5)


def test_loss_matches_the_reference_and_counts_its_assignments(tiny):
    model, params, ids = tiny
    loss, stats = model.loss(params, ids, return_stats=True)
    np.testing.assert_allclose(float(loss), float(_ref_loss(params, ids)), rtol=2e-6)
    assert int(stats["moe_dropped_assignments"]) == 0
    # 4 expert layers x 64 tokens x 4 choices, half of the experts held
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
        name = jax.tree_util.keystr(path)
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=3e-6, rtol=2e-4, err_msg=name)
        # the selection bias has no gradient path; every other leaf has one
        assert (float(jnp.abs(w).max()) > 0) == ("expert_bias" not in name), name
        if "expert_bias" in name:
            assert float(jnp.abs(g).max()) == 0.0


def test_one_fused_adam_step_matches_the_references_adam_and_only_decays_the_bias(tiny):
    """The model's gradient through FusedAdam's flat buffers (float32, no amp)
    against the reference's gradient through its own Adam, leaf by leaf.  The
    bias is a leaf of the flat buffers: its gradient is zero, so Adam's moments
    stay zero and the step is the decoupled decay alone, here and there."""
    from apex_tpu import optimizers
    model, params, ids = tiny
    hp = ref.ADAM
    opt = optimizers.FusedAdam(lr=hp["lr"], weight_decay=hp["weight_decay"])
    state = opt.init(params)
    new, _ = opt.step(params, state, jax.grad(lambda p: model.loss(p, ids))(params))[:2]
    zeros = jax.tree_util.tree_map(jnp.zeros_like, params)
    want, m, _ = ref.adam_update(params, zeros, zeros, jax.grad(_ref_loss)(params, ids),
                                 jnp.float32(1), hp, "float32")
    for (path, a), (_, b), (_, p0) in zip(*(jax.tree_util.tree_leaves_with_path(t)
                                            for t in (new, want, params))):
        name = jax.tree_util.keystr(path)
        moved = np.asarray(b) - np.asarray(p0)
        assert np.abs(moved).max() > 0, name
        np.testing.assert_allclose(np.asarray(a) - np.asarray(p0), moved, atol=1e-5, rtol=2e-3,
                                   err_msg=name)
        if "expert_bias" in name:
            step = hp["lr"] * np.sqrt(1 - hp["beta2"]) / (1 - hp["beta1"])
            # a float32 ulp of a bias near 0.1 is 7e-9: the decay shows to within one
            np.testing.assert_allclose(moved, -step * hp["weight_decay"] * np.asarray(p0),
                                       atol=8e-9)
            assert np.abs(moved).max() < 1e-7
    assert float(jnp.abs(m["layers"]["2"]["mlp"]["expert_bias"]).max()) == 0.0


def test_o2_keeps_router_bias_and_taps_in_float32_and_trains():
    from apex_tpu import amp, optimizers
    model, opt = amp.initialize(models.Laguna(models.LagunaConfig.from_dict(TINY)),
                                optimizers.FusedAdam(lr=1e-3), opt_level="O2", verbosity=0)
    params, _ = model.init(jax.random.PRNGKey(0))
    mlp, conv = params["layers"]["2"]["mlp"], params["layers"]["2"]["conv"]
    assert mlp["router"].dtype == mlp["expert_bias"].dtype == jnp.float32
    assert mlp["w_in"].dtype == jnp.bfloat16
    assert conv["conv"]["weight"].dtype == jnp.float32 and conv["in_proj"]["weight"].dtype == jnp.bfloat16
    assert params["embed_tokens"]["weight"].dtype == jnp.bfloat16
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


# -- the expert layer: a sigmoid router over 16, 4 a token, a selection bias -----

def _layer(held=None, **kw):
    return ep.ExpertParallelMLP(8, 16, 16, capacity_factor=None, top_k=4, expert_type="swiglu",
                                router_type="sigmoid", experts_held=held, router_bias=True, **kw)


def _ref_cfg(start=0):
    return {"num_experts_per_tok": 4, "norm_topk_prob": True, "experts_held_start": start,
            "moe_routed_scaling_factor": 1.0}


def _biased(layer, seed=3, scale=0.3):
    params, _ = layer.init(jax.random.PRNGKey(seed))
    assert float(jnp.abs(params["expert_bias"]).max()) == 0.0       # built at zero, seeded by the caller
    bias = scale * jax.random.normal(jax.random.PRNGKey(seed + 1), (16,))
    return {**params, "expert_bias": bias}


def test_the_bias_changes_the_choice_and_not_the_weights():
    layer = _layer()
    params = _biased(layer)
    x = jnp.asarray(np.random.RandomState(5).randn(48, 8), jnp.float32)
    scores = jax.nn.sigmoid(x @ params["router"])
    gates, experts, _ = layer._route(x, params["router"], False, bias=params["expert_bias"])
    plain_gates, plain_experts, _ = layer._route(x, params["router"], False)
    # the choice is the 4 largest of score + bias, and differs from the unbiased choice
    want = jax.lax.top_k(scores + params["expert_bias"], 4)[1]
    np.testing.assert_array_equal(np.asarray(experts), np.asarray(want))
    assert (np.sort(np.asarray(experts), -1) != np.sort(np.asarray(plain_experts), -1)).any()
    # the weights are the chosen scores alone over their sum (+ 1e-6): no bias in them
    top = jnp.take_along_axis(scores, experts, -1)
    np.testing.assert_allclose(np.asarray(gates), np.asarray(top / (top.sum(-1, keepdims=True) + 1e-6)),
                               rtol=1e-6)
    # a bias of zero picks what no bias picks, and weighs it by the same rule but for the 1e-6
    zero_gates, zero_experts, _ = layer._route(x, params["router"], False, bias=jnp.zeros((16,)))
    np.testing.assert_array_equal(np.asarray(zero_experts), np.asarray(plain_experts))
    np.testing.assert_allclose(np.asarray(zero_gates), np.asarray(plain_gates), rtol=1e-5)
    # no gradient reaches the bias; the router's comes through the weights
    g = jax.grad(lambda p: jnp.sum(layer(p, x) ** 2))(params)
    assert float(jnp.abs(g["expert_bias"]).max()) == 0.0 and float(jnp.abs(g["router"]).max()) > 0
    np.testing.assert_allclose(np.asarray(layer(params, x)), np.asarray(
        ref.sparse_mlp(params, x, _ref_cfg(), "float32")), atol=2e-5)
    with pytest.raises(NotImplementedError, match="selection bias"):
        layer._all_to_all_forward(params, x, 2)


def test_the_four_shares_add_up_to_the_uncut_layer():
    """The outputs of the 4 shares of a 4-way expert-parallel group (4 of 16
    experts each; router and bias replicated; nothing is computed by every chip
    alike) sum to what the reference gives for the whole layer."""
    whole = _layer()
    params = _biased(whole)
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
    assert held == 24 * 4           # every assignment lands on exactly one share


# -- the convolution operator ---------------------------------------------------

def test_a_rows_first_tokens_see_zeros_and_not_the_previous_rows_last():
    op = GatedShortConv(32, 3)
    params = _perturbed(op.init(jax.random.PRNGKey(2))[0], seed=3, scale=0.2)
    x = jax.random.normal(jax.random.PRNGKey(4), (3, 16, 32), jnp.float32)
    both = op(params, x)
    for row in range(3):
        alone = op(params, x[row:row + 1])
        np.testing.assert_allclose(np.asarray(both[row]), np.asarray(alone[0]), atol=1e-6)
        np.testing.assert_allclose(np.asarray(both[row]), np.asarray(
            ref.short_conv(params, x[row], {}, "float32")), atol=1e-5)
    # causal: a later token moves nothing before it
    later = op(params, x.at[:, 9].add(1.0))
    np.testing.assert_array_equal(np.asarray(later[:, :9]), np.asarray(both[:, :9]))
    assert float(jnp.abs(later[:, 9:12] - both[:, 9:12]).max()) > 0
    np.testing.assert_array_equal(np.asarray(later[:, 12:]), np.asarray(both[:, 12:]))
    # by hand at the first three positions: tap L-1 meets the token itself
    bcz = x @ params["in_proj"]["weight"].T
    g = bcz[..., :32] * bcz[..., 64:]
    w = params["conv"]["weight"]
    mixed1 = w[2] * g[:, 1] + w[1] * g[:, 0]
    np.testing.assert_allclose(
        np.asarray(gated_short_conv(bcz, w)[:, 1]), np.asarray(bcz[:, 1, 32:64] * mixed1), atol=1e-5)
    np.testing.assert_allclose(np.asarray(gated_short_conv(bcz, w)[:, 0]),
                               np.asarray(bcz[:, 0, 32:64] * w[2] * g[:, 0]), atol=1e-5)


def test_the_convolution_pass_keeps_its_input_and_writes_its_dtype():
    """bf16 in and out, float32 between; the backward recomputes the pass from
    the projection's output (one residual of its size, in its dtype)."""
    bcz = jax.random.normal(jax.random.PRNGKey(0), (2, 16, 3 * 128), jnp.bfloat16)
    w = jax.random.normal(jax.random.PRNGKey(1), (3, 128), jnp.float32)
    out = gated_short_conv(bcz, w)
    assert out.dtype == jnp.bfloat16 and out.shape == (2, 16, 128)
    f32 = gated_short_conv(bcz.astype(jnp.float32), w)
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(f32), rtol=1e-2, atol=1e-2)
    op = GatedShortConv(128, 3)
    params, _ = op.init(jax.random.PRNGKey(2))
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 16, 128), jnp.float32)
    _, kept = jax.vjp(lambda x: op(params, x), x)
    sizes = sorted(l.size for l in jax.tree_util.tree_leaves(kept) if hasattr(l, "size"))
    # the taps, the projection's output and the two weights: nothing of g's size
    assert sizes == [3 * 128, 2 * 16 * 384, 128 * 128, 384 * 128]


# -- attention: a grouped head of 64 with QK-norm through the kernels ------------

def test_a_grouped_head_of_64_reaches_the_flash_kernels_head_major(monkeypatch):
    """Heads of half a lane tile with Pallas on (interpreted here): q and k go
    through the per-head norm and the rotation, then q, k, v are moved
    head-major once and handed to the flash kernels with K/V at their 2 heads:
    forward and gradients against the reference's attention, and never the
    dense path."""
    from apex_tpu.models.laguna import LagunaAttention
    from apex_tpu.observability.metrics import get_registry
    from apex_tpu.ops import pallas_flash_attention as pfa
    from apex_tpu.transformer import attention
    cfg = dict(TINY, hidden_size=64, head_dim=64, num_attention_heads=4, num_key_value_heads=2,
               num_hidden_layers=1, layer_types=["full_attention"], mlp_layer_types=["sparse"])
    seq = 256
    assert pfa._head_width(64) == 64
    # the launch the chip makes at the cell's shape: bf16, the 4 heads of a group a step
    assert pfa._heads_per_step(32, 64, 2, False, pfa._block_for(8192), 4) == 4
    layer = LagunaAttention(models.LagunaConfig.from_dict(cfg), 0)
    params = _perturbed(layer.init(jax.random.PRNGKey(2))[0], seed=5, scale=0.1)
    x = jax.random.normal(jax.random.PRNGKey(3), (2, seq, 64), jnp.float32)
    want_fn = lambda p, x: jnp.stack([ref.attention(p, row, cfg, "full_attention", "float32")
                                      for row in x])
    weigh = jax.random.normal(jax.random.PRNGKey(4), x.shape)
    loss = lambda fn: (lambda p, x: jnp.sum(fn(p, x) * weigh))
    want, want_grads = want_fn(params, x), jax.grad(loss(want_fn), (0, 1))(params, x)
    # without the kernels the same layer takes the dense path and agrees
    np.testing.assert_allclose(np.asarray(layer(params, x)), np.asarray(want), atol=2e-5, rtol=2e-4)
    monkeypatch.setenv("APEX_TPU_FORCE_PALLAS", "1")
    monkeypatch.delenv("APEX_TPU_DISABLE_PALLAS", raising=False)

    def calls():
        c = get_registry().get("flash_calls_total")
        return ({tuple(v for _, v in sorted(k)): m.value for k, m in c.children().items()}
                if c else {})

    paths, before = [], calls()
    attention.set_path_hook(paths.append)
    try:
        got, got_grads = layer(params, x), jax.grad(loss(layer), (0, 1))(params, x)
    finally:
        attention.set_path_hook(None)
    assert set(paths) == {"flash"}
    grew = {k: v - before.get(k, 0) for k, v in calls().items() if v != before.get(k, 0)}
    assert set(grew) == {("grouped", "head_major")}
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5, rtol=2e-4)
    for (path, g), (_, w) in zip(*(jax.tree_util.tree_leaves_with_path(t)
                                   for t in (got_grads, want_grads))):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=2e-4, rtol=2e-4,
                                   err_msg=jax.tree_util.keystr(path))


# -- the grouped products' tile --------------------------------------------------

# (rows, K, N, groups) -> tile: the accepted cells' shapes, both directions, as the parent chose
ACCEPTED_TILES = [((32768, 2304, 896, 16), 256), ((32768, 896, 2304, 16), 256),
                  ((16384, 2048, 512, 16), 256), ((16384, 512, 2048, 16), 256)]


@pytest.mark.parametrize("shape,tile", ACCEPTED_TILES, ids=lambda v: "x".join(map(str, v))
                         if isinstance(v, tuple) else str(v))
def test_the_accepted_cells_tiles_are_where_they_were(shape, tile):
    from apex_tpu.ops import pallas_grouped_matmul as pgm
    assert pgm.row_tile(*shape, jnp.bfloat16) == tile
    # inside the kernel's default VMEM: the launch asks for nothing
    assert pgm._limit(pgm._rows_vmem(tile, *shape[1:3], 2)) is None


@pytest.mark.parametrize("rows", [32768, 16384])
def test_the_tile_at_a_block_that_does_not_fit_vmem_twice_is_not_zero(rows):
    """(2048, 1792) in bf16 is 7.3 MB a group: resident in both pipeline buffers
    it passes the 14 MiB a kernel has by default at every tile, so the chooser
    takes a tile under a stated larger ask and the launch names it."""
    from apex_tpu.ops import pallas_grouped_matmul as pgm
    for K, N in ((2048, 1792), (1792, 2048)):
        assert all(pgm._rows_vmem(tm, K, N, 2) > pgm._VMEM_BUDGET for tm in (128, 256, 512))
        tile = pgm.row_tile(rows, K, N, 8, jnp.bfloat16)
        assert tile == 256 and 4 * tile <= rows // 8
        need = pgm._rows_vmem(tile, K, N, 2)
        assert pgm._VMEM_BUDGET < need <= pgm._VMEM_ASK and pgm._limit(need) == need + 4 * 2 ** 20
    # a block no ask holds is still refused
    assert pgm.row_tile(rows, 8192, 4096, 8, jnp.bfloat16) == 0


# -- what a traced layer says of itself ------------------------------------------

def test_traced_layers_count_their_convolutions_and_their_biased_routers(tiny):
    from apex_tpu.observability.metrics import get_registry
    reg = get_registry()

    def read():
        conv = reg.get("short_conv_calls_total")
        taps = ({tuple(v for _, v in sorted(k)): c.value for k, c in conv.children().items()}
                if conv else {})
        bias = reg.get("moe_router_bias_calls_total")
        routers = reg.get("moe_router_calls_total")
        by = ({tuple(v for _, v in sorted(k)): c.value for k, c in routers.children().items()}
              if routers else {})
        # labels sorted by name: (impl, taps); off the chip the XLA form
        return taps.get(("xla", "3"), 0), bias.value if bias else 0, by.get(("sigmoid", "4"), 0)

    model, params, ids = tiny
    before = read()
    jax.eval_shape(lambda p: model.loss(p, ids), params)
    after = read()
    # 4 conv layers, 4 expert layers, each of them with a selection bias
    assert tuple(a - b for a, b in zip(after, before)) == (4, 4, 4)
    plain = models.Laguna(models.LagunaConfig.from_dict(MELLUM_TINY))
    shapes = jax.eval_shape(lambda k: plain.init(k)[0], jax.random.PRNGKey(0))
    jax.eval_shape(lambda p: plain.loss(p, ids), shapes)
    assert read()[:2] == after[:2]          # no convolution, no bias in the other decoders


def test_the_new_scopes_are_of_the_phase_vocabulary_and_sit_under_the_operator(tiny):
    from apex_tpu.observability import phases
    assert {"conv.in_proj", "conv.mix", "conv.out_proj", "attn.qk_norm"} <= set(phases.PHASES)
    model, params, ids = tiny
    text = jax.jit(lambda p: model.loss(p, ids)).lower(params).as_text(debug_info=True)
    for scope in ("layers/0/conv/conv.mix", "layers/2/conv/conv.in_proj",
                  "layers/4/conv/conv.out_proj", "layers/1/self_attn/attn.qk_norm"):
        assert scope in text, scope
    path, backward = phases.phase_of_op_name(
        "jit(step)/jvp(model)/layers/3/conv/conv.mix/checkpoint/mul")
    assert path == ("model", "layers/3/conv", "conv.mix") and not backward


# -- the other decoders' steps are the ones they were ------------------------------

@pytest.mark.parametrize("name,base", [("laguna", LAGUNA_TINY), ("mellum", MELLUM_TINY)])
def test_the_new_switches_at_their_defaults_leave_the_other_steps_as_they_were(name, base):
    """The tiny ``laguna`` and ``mellum`` training steps traced twice, once from
    a config with every key this configuration added absent and once with each
    stated at its default: one jaxpr, letter for letter, and nothing of the new
    mechanisms in it."""
    from apex_tpu import amp, optimizers
    stated = dict(base, qk_norm=False, conv_L_cache=3, use_expert_bias=False,
                  tie_word_embeddings=False)
    ids = jnp.asarray(np.random.RandomState(1).randint(0, 64, (2, T)), jnp.int32)

    def step_jaxpr(cfg):
        amp.policy.set_policy(amp.policy.NoPolicy())
        model, opt = amp.initialize(models.Laguna(models.LagunaConfig.from_dict(cfg)),
                                    optimizers.FusedAdam(lr=1e-3), opt_level="O2", verbosity=0)
        params = jax.eval_shape(lambda k: model.init(k)[0], jax.random.PRNGKey(0))
        assert "lm_head" in params and "conv" not in params["layers"]["0"]
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

    absent, present = step_jaxpr(base), step_jaxpr(stated)
    assert absent == present and "ragged_dot" in absent
