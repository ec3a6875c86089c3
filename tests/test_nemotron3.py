"""The one-branch decoder (models/nemotron_h.py: Mamba-2 mixers, routed
squared-ReLU experts with a shared one, position-free attention) against the
benchmark's plain reference (benchmark/references/nemotron3.py) at a tiny
config with every mechanism present.  Beside it: the chunked scan against the
step-by-step recurrence, outputs and every gradient, at decays under which the
state carried between chunks matters; the shares of a 4-way expert-parallel
group adding up with the shared expert counted once; non-gated experts at a
width that is not whole lane tiles through the grouped path; position-free
attention at 16 query heads to a K/V head; no loop over positions and no
(T, T) array in a traced step; the trace-time counters and scopes; and the
four accepted decoders' steps unchanged."""

import os
import sys

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from apex_tpu import models
from apex_tpu.parallel import expert_parallel as ep
from apex_tpu.transformer import mamba2

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if os.path.join(ROOT, "benchmark") not in sys.path:
    sys.path.insert(0, os.path.join(ROOT, "benchmark"))
from references import nemotron3 as ref  # noqa: E402
from test_laguna import TINY as LAGUNA_TINY  # noqa: E402
from test_lfm2 import TINY as LFM2_TINY  # noqa: E402
from test_mellum2 import TINY as MELLUM_TINY  # noqa: E402
from test_ouro import TINY as OURO_TINY  # noqa: E402

TINY = dict(
    model_type="nemotron_h", vocab_size=64, hidden_size=32, num_hidden_layers=9,
    hybrid_override_pattern="MEMEM*EME", mamba_num_heads=4, mamba_head_dim=8, ssm_state_size=16,
    n_groups=2, conv_kernel=4, chunk_size=8, use_conv_bias=True, mlp_hidden_act="relu2",
    num_attention_heads=4, num_key_value_heads=2, head_dim=8, norm_eps=1e-5,
    n_routed_experts=8, num_experts_published=16, experts_held_start=4, num_experts_per_tok=6,
    norm_topk_prob=True, moe_intermediate_size=24, moe_shared_expert_intermediate_size=48,
    routed_scaling_factor=2.5, max_position_embeddings=64, head_chunk=24)
T = 32


def _perturbed(params, seed=1, scale=0.05):
    """Norm gains away from 1, biases away from 0 and a router that spreads its
    scores, so that no term of the model is silent in a comparison."""
    leaves, tree = jax.tree_util.tree_flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(leaves))
    return jax.tree_util.tree_unflatten(
        tree, [x + scale * jax.random.normal(k, x.shape) for x, k in zip(leaves, keys)])


@pytest.fixture(scope="module")
def tiny():
    model = models.NemotronH(models.NemotronHConfig.from_dict(TINY))
    params = _perturbed(model.init(jax.random.PRNGKey(0))[0])
    ids = jnp.asarray(np.random.RandomState(0).randint(0, 64, (2, T)), jnp.int32)
    return model, params, ids


def _ref_loss(params, ids):
    return ref.summed_nll(params, ids, TINY) / (ids.shape[0] * (T - 1))


# -- the scan ---------------------------------------------------------------------

def _scan_inputs(dtype=jnp.float32, b=2, seq=64, H=4, P=8, G=2, N=16):
    """Decays as the family draws them (delta in [0.001, 0.1], A in [1, 16]): a
    chunk of 16 forgets little, so what the chunks before it left matters."""
    k = jax.random.split(jax.random.PRNGKey(7), 6)
    x = jax.random.normal(k[0], (b, seq, H, P)).astype(dtype)
    B = jax.random.normal(k[1], (b, seq, G, N)).astype(dtype)
    C = jax.random.normal(k[2], (b, seq, G, N)).astype(dtype)
    dt = jnp.exp(jax.random.uniform(k[3], (b, seq, H), minval=np.log(1e-3), maxval=np.log(1e-1)))
    A = -jax.random.uniform(k[4], (H,), minval=1.0, maxval=16.0)
    D = jax.random.normal(k[5], (H,))
    return x, dt, A, B, C, D


def _recurrence(x, dt, A, B, C, D):
    """The reference's step-by-step scan, a row at a time, in float32."""
    f32 = lambda a: a.astype(jnp.float32)
    return jnp.stack([ref.ssm_scan(f32(x[i]), dt[i], A, f32(B[i]), f32(C[i]), D)
                      for i in range(x.shape[0])])


def test_the_chunked_scan_is_the_recurrence_in_float32_outputs_and_every_gradient():
    args = _scan_inputs()
    weigh = jax.random.normal(jax.random.PRNGKey(8), args[0].shape)
    with jax.default_matmul_precision("highest"):
        got = mamba2.ssd_chunked(*args, 16)                      # 4 chunks
        want = _recurrence(*args)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5, rtol=1e-5)
        every = tuple(range(6))
        g = jax.grad(lambda *a: jnp.sum(mamba2.ssd_chunked(*a, 16) * weigh), every)(*args)
        w = jax.grad(lambda *a: jnp.sum(_recurrence(*a) * weigh), every)(*args)
    for name, a, b in zip(("x", "dt", "A", "B", "C", "D"), g, w):
        scale = float(jnp.abs(b).max())
        assert scale > 0, name
        np.testing.assert_allclose(np.asarray(a) / scale, np.asarray(b) / scale, atol=1e-5,
                                   err_msg=name)
    # the carried state matters at these decays: without it the result is another
    alone = mamba2.ssd_chunked(*(a[:, 48:] if a.ndim > 1 else a for a in args), 16)
    assert float(jnp.abs(alone - want[:, 48:]).max()) > 0.1
    with pytest.raises(ValueError, match="whole chunks"):
        mamba2.ssd_chunked(*args, 24)


def test_the_chunked_scan_in_bfloat16_operands_stays_within_their_rounding():
    """bf16 operands, float32 decays and accumulation: each product rounds its
    operands to 8 bits, so the result is within 2 % of the largest output and
    the gradients within 3 % of their largest (read: 0.5 % and 1 %)."""
    args32, args16 = _scan_inputs(), _scan_inputs(jnp.bfloat16)
    weigh = jax.random.normal(jax.random.PRNGKey(8), args32[0].shape)
    want = _recurrence(*args32)
    got = mamba2.ssd_chunked(*args16, 16)
    assert got.dtype == jnp.float32
    assert float(jnp.abs(got - want).max()) < 0.02 * float(jnp.abs(want).max())
    g = jax.grad(lambda *a: jnp.sum(mamba2.ssd_chunked(*a, 16) * weigh), (0, 1, 3, 4))(*args16)
    w = jax.grad(lambda *a: jnp.sum(_recurrence(*a) * weigh), (0, 1, 3, 4))(*args32)
    for name, a, b in zip(("x", "dt", "B", "C"), g, w):
        assert a.dtype == (jnp.float32 if name == "dt" else jnp.bfloat16)
        assert float(jnp.abs(a.astype(jnp.float32) - b).max()) < 0.03 * float(jnp.abs(b).max()), name


def test_a_rows_first_tokens_see_zeros_and_not_the_previous_rows_last():
    mixer = mamba2.Mamba2Mixer(32, 4, 8, 16, 2, taps=4, chunk=8)
    params = _perturbed(mixer.init(jax.random.PRNGKey(2))[0], seed=3, scale=0.2)
    u = jax.random.normal(jax.random.PRNGKey(4), (2, T, 32))
    both = mixer(params, u)
    np.testing.assert_allclose(np.asarray(both[1]), np.asarray(mixer(params, u[1:])[0]), atol=1e-5)
    want = jnp.stack([ref.mamba(params, row, TINY, "float32") for row in u])
    np.testing.assert_allclose(np.asarray(both), np.asarray(want), atol=2e-5, rtol=2e-4)


# -- the model --------------------------------------------------------------------

def test_the_configuration_builds_what_the_published_file_says(tiny):
    model, params, _ = tiny
    assert [b.mixer for b in model.layers] == ["mamba", "mlp", "mamba", "mlp", "mamba",
                                               "self_attn", "mlp", "mamba", "mlp"]
    assert set(params) == {"embed_tokens", "layers", "norm", "lm_head"}           # untied
    assert all(set(params["layers"][str(i)]) == {"input_layernorm", b.mixer}
               for i, b in enumerate(model.layers))                               # one branch
    m = params["layers"]["0"]["mamba"]
    assert set(m) == {"in_proj", "conv1d", "A_log", "dt_bias", "D", "norm", "out_proj"}
    # d_in + (d_in + 2 G N) + H columns; the taps a row each, with a bias
    assert m["in_proj"]["weight"].shape == (32 + 96 + 4, 32)
    assert m["conv1d"]["weight"].shape == (4, 96) and m["conv1d"]["bias"].shape == (96,)
    assert m["A_log"].shape == m["dt_bias"].shape == m["D"].shape == (4,)
    assert m["norm"]["weight"].shape == (32,) and m["out_proj"]["weight"].shape == (32, 32)
    attn = params["layers"]["5"]["self_attn"]
    assert set(attn) == {"q_proj", "k_proj", "v_proj", "o_proj"}      # no gate, no QK-norm
    assert model.layers[5].self_attn.inv_freq is None                 # and nothing rotates
    e = params["layers"]["1"]["mlp"]
    assert set(e) == {"router", "expert_bias", "w_in", "w_out", "shared"}         # no w_gate
    assert set(e["shared"]) == {"w_in", "w_out"} and e["shared"]["w_in"].shape == (32, 48)
    assert e["router"].shape == (32, 16) and e["w_in"].shape == (8, 32, 24)
    # the family's initialization: A in [1, 16], delta in [0.001, 0.1], D ones
    fresh = model.init(jax.random.PRNGKey(0))[0]["layers"]["0"]["mamba"]
    A, dt = np.exp(fresh["A_log"]), np.log1p(np.exp(fresh["dt_bias"]))
    assert (A >= 1).all() and (A <= 16).all() and (dt >= 1e-3 * 0.99).all() and (dt <= 0.1001).all()
    with pytest.raises(ValueError, match="unknown block"):
        models.NemotronHConfig.from_dict(dict(TINY, hybrid_override_pattern="M-M"))
    # the per-layer decoder's own configuration still refuses attention without a rope group
    with pytest.raises(ValueError, match="rope_parameters"):
        models.LagunaConfig.from_dict(dict(LFM2_TINY, rope_parameters={}))


def test_logits_and_loss_match_the_reference(tiny):
    model, params, ids = tiny
    np.testing.assert_allclose(np.asarray(model(params, ids)),
                               np.asarray(ref.logits(params, ids, TINY)), atol=3e-5)
    loss, stats = model.loss(params, ids, return_stats=True)
    np.testing.assert_allclose(float(loss), float(_ref_loss(params, ids)), rtol=3e-6)
    assert int(stats["moe_dropped_assignments"]) == 0
    # 4 expert layers x 64 tokens x 6 choices, half of the experts held
    assert 0 < int(stats["moe_assignments_held"]) < 4 * 64 * 6


@pytest.mark.parametrize("remat", [None, "dots", "nothing"])
def test_gradients_match_the_reference(tiny, remat):
    _, params, ids = tiny
    model = models.NemotronH(models.NemotronHConfig.from_dict(TINY, remat=remat))
    got = jax.grad(lambda p: model.loss(p, ids))(params)
    want = jax.grad(_ref_loss)(params, ids)
    flat_g, flat_w = (jax.tree_util.tree_leaves_with_path(t) for t in (got, want))
    assert len(flat_g) == len(flat_w) == 72
    for (path, g), (_, w) in zip(flat_g, flat_w):
        name = jax.tree_util.keystr(path)
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=3e-6, rtol=3e-4, err_msg=name)
        assert (float(jnp.abs(w).max()) > 0) == ("expert_bias" not in name), name


def test_the_whole_step_through_build_at_o2_matches_the_reference_leaf_by_leaf(tmp_path):
    """``examples/gpt/main_amp.py``'s ``build()`` (amp O2, FusedAdam, the same
    step as the other decoders) on the benchmark's weights: the loss and every
    leaf's gradient as Adam got it against the float32 reference, within what
    bf16 operands round (a leaf's difference over its norm or the median
    leaf's: under 0.15 on every leaf and 0.04 in the mean; read 0.11 and 0.02)."""
    import importlib.util
    import json
    from lib import weights
    spec = importlib.util.spec_from_file_location(
        "nemotron3_example", os.path.join(ROOT, "examples", "gpt", "main_amp.py"))
    example = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(example)
    file = tmp_path / "tiny.json"
    file.write_text(json.dumps(TINY))
    run = example.build(example.parse_args(
        ["--arch", "nemotron_h", "--model-config", str(file), "-b", "1", "--seq-len", str(T),
         "--lr", "1e-4", "--weight-decay", "0.01"]))
    shapes = jax.tree_util.tree_map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                                    run.state[0])
    mixer = shapes["layers"]["0"]["mamba"]
    assert mixer["in_proj"]["weight"].dtype == jnp.bfloat16
    assert {mixer[k].dtype for k in ("A_log", "dt_bias", "D")} == {jnp.dtype(jnp.float32)}
    assert mixer["conv1d"]["bias"].dtype == mixer["norm"]["weight"].dtype == jnp.float32
    params = weights.make_weights(shapes, seed=2 ** 31 + 9, std=0.05)
    opt_state = run.optimizer.init(params)
    ids = np.random.RandomState(3).randint(0, 64, (run.global_batch, T)).astype(np.int32)
    (_, opt_state), metrics = run.train_step((params, opt_state), run.put_batch((ids,)))
    got = ref.leaf_norms(opt_state.masters.layout.unpack_masters(opt_state.inner.m)) / 0.1
    batches = [(ids,)]
    want = ref.train(weights.make_weights(shapes, seed=2 ** 31 + 9, std=0.05), batches, TINY)
    assert abs(float(metrics["loss"]) - want["losses"][0]) < 1e-3 * want["losses"][0]
    rel = ref.leaf_differences(np.asarray(got), want["first_grad_norms"])
    assert rel.shape == (72,) and rel.max() < 0.15 and rel.mean() < 0.04, rel
    assert int(metrics["moe_dropped_assignments"]) == 0


# -- the expert layer: non-gated relu2 experts and a shared one of their kind -------

def _layer(held=None, hidden=24, shared=40, **kw):
    return ep.ExpertParallelMLP(8, hidden, 16, capacity_factor=None, top_k=6, expert_type="mlp",
                                activation="relu2", router_type="sigmoid", routed_scaling=2.5,
                                experts_held=held, shared_hidden=shared, router_bias=True, **kw)


def _ref_cfg(start=0):
    return {"num_experts_per_tok": 6, "experts_held_start": start, "routed_scaling_factor": 2.5}


def _biased(layer, seed=3, scale=0.3):
    params, _ = layer.init(jax.random.PRNGKey(seed))
    bias = scale * jax.random.normal(jax.random.PRNGKey(seed + 1), (16,))
    return {**params, "expert_bias": bias}


def test_the_four_shares_add_up_to_the_uncut_layer_with_the_shared_expert_once():
    """16 routed experts in 4 shares of 4 (router, bias and shared expert on
    every chip alike): the four shares' routed parts plus the shared expert
    once are what the reference gives for the whole layer."""
    whole = _layer()
    params = _biased(whole)
    x = jnp.asarray(np.random.RandomState(3).randn(24, 8), jnp.float32)
    want = ref.experts(params, x, _ref_cfg(), "float32")
    np.testing.assert_allclose(np.asarray(whole(params, x)), np.asarray(want), atol=3e-5)
    shared = ref.relu2_mlp(x, params["shared"]["w_in"].T, params["shared"]["w_out"].T, "float32")
    assert float(jnp.abs(shared).max()) > 0.01
    total, held = jnp.zeros_like(x), 0
    for start in range(0, 16, 4):
        share = {**params, **{k: params[k][start:start + 4] for k in ("w_in", "w_out")}}
        y, stats = _layer(held=(start, 4))(share, x, return_stats=True)
        np.testing.assert_allclose(np.asarray(y), np.asarray(
            ref.experts(share, x, _ref_cfg(start), "float32")), atol=3e-5)
        total, held = total + (y - shared), held + int(stats["moe_assignments_held"])
    np.testing.assert_allclose(np.asarray(total + shared), np.asarray(want), atol=8e-5)
    assert held == 24 * 6           # every assignment lands on exactly one share
    # the shared expert is the experts' kind: no gate matrix anywhere
    assert set(params["shared"]) == {"w_in", "w_out"} and "w_gate" not in params
    assert set(whole.param_specs()["shared"]) == {"w_in", "w_out"}
    # a gated layer keeps its gated shared expert
    gated, _ = ep.ExpertParallelMLP(8, 16, 4, capacity_factor=None, top_k=2, expert_type="swiglu",
                                    shared_hidden=16).init(jax.random.PRNGKey(0))
    assert set(gated["shared"]) == {"w_gate", "w_in", "w_out"}


def test_non_gated_experts_at_a_width_of_a_tile_and_a_half_go_through_the_grouped_kernels(
        monkeypatch):
    """relu2 experts of 256 x 192 (1.5 lane tiles, as 1856 is 14.5) where Pallas
    runs (interpreted here): both products of a layer and their four gradients
    are the Mosaic kernels', equal to the layer through ``lax.ragged_dot``, values
    and every gradient, and to the reference; the leaves keep their shapes."""
    from apex_tpu.observability.metrics import get_registry
    from apex_tpu.ops import dispatch, pallas_grouped_matmul as pgm
    assert pgm._chunks(192) == ((0, 128), (128, 64)) and pgm._chunks(256) == ((0, 256),)
    assert pgm.row_tile(256, 256, 192, 4, jnp.float32) == pgm.row_tile(256, 192, 256, 4,
                                                                       jnp.float32) == 128
    # half a tile alone, an odd width, and two half-tile sides are still refused
    for K, N in ((256, 64), (256, 100), (128, 72), (192, 192), (100, 128)):
        assert pgm.row_tile(256, K, N, 4, jnp.float32) == 0, (K, N)
    layer = ep.ExpertParallelMLP(256, 192, 16, capacity_factor=None, top_k=6, expert_type="mlp",
                                 activation="relu2", router_type="sigmoid", routed_scaling=2.5,
                                 experts_held=(4, 4), shared_hidden=384, row_buffer_factor=2.0,
                                 router_bias=True)
    params = _biased(layer)
    assert params["w_in"].shape == (4, 256, 192) and params["w_out"].shape == (4, 192, 256)
    x = jax.random.normal(jax.random.PRNGKey(1), (128, 256), jnp.float32)
    loss = lambda p, x: jnp.sum(layer(p, x) ** 2)

    def calls():
        c = get_registry().get("moe_grouped_dot_calls_total")
        return ({tuple(v for _, v in sorted(k)): m.value for k, m in c.children().items()}
                if c else {})

    want, want_g = jax.value_and_grad(loss, (0, 1))(params, x)
    np.testing.assert_allclose(np.asarray(layer(params, x)), np.asarray(
        ref.experts(params, x, _ref_cfg(4), "float32")), atol=2e-4, rtol=2e-4)
    monkeypatch.setattr(dispatch, "pallas_enabled", lambda: True)
    before = calls()
    got, got_g = jax.value_and_grad(lambda p, x: loss(p, x), (0, 1))(params, x)
    grew = {k: v - before.get(k, 0) for k, v in calls().items() if v != before.get(k, 0)}
    assert grew == {("mosaic", "128"): 6}               # 2 forward, 4 backward; no ragged_dot
    np.testing.assert_allclose(got, want, rtol=1e-5)
    for a, b in zip(jax.tree_util.tree_leaves(got_g), jax.tree_util.tree_leaves(want_g)):
        assert a.dtype == b.dtype and a.shape == b.shape
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-5 * float(jnp.abs(b).max()) + 1e-12)


def test_relu2_is_the_squared_relu():
    from apex_tpu.nn import functional as F
    x = jnp.asarray([-2.0, -0.0, 0.5, 3.0])
    np.testing.assert_array_equal(np.asarray(F.relu2(x)), [0.0, 0.0, 0.25, 9.0])
    assert float(jax.grad(lambda v: F.relu2(v))(3.0)) == 6.0 and "relu2" in F.__all__


# -- position-free attention --------------------------------------------------------

def test_attention_without_positions_at_16_query_heads_to_a_kv_head():
    """32 query heads over 2 K/V heads, nothing rotated: the layer against dense
    masked attention, and a layer that is told to rotate is another layer."""
    from apex_tpu.models.laguna import LagunaAttention
    cfg = dict(TINY, hidden_size=64, num_attention_heads=32, num_key_value_heads=2, head_dim=8)
    layer = LagunaAttention(models.NemotronHConfig.from_dict(cfg), 5)
    assert layer.inv_freq is None and layer.H // layer.Hkv == 16
    params = _perturbed(layer.init(jax.random.PRNGKey(2))[0], seed=5, scale=0.1)
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 48, 64), jnp.float32)
    q, k, v = (jnp.einsum("btd,od->bto", x, params[n]["weight"]) for n in ("q_proj", "k_proj",
                                                                            "v_proj"))
    q = q.reshape(2, 48, 2, 16, 8)
    k, v = k.reshape(2, 48, 2, 8), v.reshape(2, 48, 2, 8)
    s = jnp.einsum("bqkgd,bskd->bkgqs", q, k) / np.sqrt(8)
    s = jnp.where(jnp.tril(jnp.ones((48, 48), bool)), s, -jnp.inf)
    ctx = jnp.einsum("bkgqs,bskd->bqkgd", jax.nn.softmax(s, -1), v).reshape(2, 48, 256)
    want = jnp.einsum("bto,do->btd", ctx, params["o_proj"]["weight"])
    np.testing.assert_allclose(np.asarray(layer(params, x)), np.asarray(want), atol=2e-5, rtol=2e-4)
    np.testing.assert_allclose(np.asarray(layer(params, x)), np.asarray(jnp.stack(
        [ref.attention(params, row, cfg, "float32") for row in x])), atol=2e-5, rtol=2e-4)
    # positions are nowhere: the last token's output does not change when the tokens before
    # it change places
    swapped = x.at[:, jnp.array([3, 11])].set(x[:, jnp.array([11, 3])])
    np.testing.assert_allclose(np.asarray(layer(params, swapped))[:, -1],
                               np.asarray(layer(params, x))[:, -1], atol=2e-5)
    rotating = models.LagunaConfig.from_dict(dict(LFM2_TINY, qk_norm=False))
    assert LagunaAttention(rotating, 1).inv_freq is not None


# -- what a traced step holds ----------------------------------------------------------

def _eqns(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _eqns(sub)


def test_no_step_loops_over_positions_or_builds_a_T_by_T_array_in_a_mamba_block(tiny):
    """A model of Mamba-2 blocks alone at T = 256 in chunks of 16, forward and
    backward from the embedding to the final norm: no loop of any kind (the
    attention and expert layers and the chunked head are left out so that what
    is found is the mixer's), and no array with two axes of T."""
    cfg = dict(TINY, hybrid_override_pattern="MM", chunk_size=16, max_position_embeddings=256)
    model = models.NemotronH(models.NemotronHConfig.from_dict(cfg, remat="nothing"))
    shapes = jax.eval_shape(lambda k: model.init(k)[0], jax.random.PRNGKey(0))
    ids = jax.ShapeDtypeStruct((1, 256), jnp.int32)
    closed = jax.make_jaxpr(jax.value_and_grad(lambda p, i: jnp.sum(
        model._backbone(p, i)[0].astype(jnp.float32))))(shapes, ids)
    names = set()
    for eqn in _eqns(closed.jaxpr):
        names.add(eqn.primitive.name)
        for var in eqn.outvars:
            shape = getattr(var.aval, "shape", ())
            assert sum(int(d) == 256 for d in shape) < 2, (eqn.primitive.name, shape)
    assert not names & {"scan", "while", "fori_loop"}, names
    assert "cumsum" in names and "dot_general" in names


def test_traced_layers_count_their_mixers_scans_and_routers(tiny):
    from apex_tpu.observability.metrics import get_registry
    reg = get_registry()

    def read():
        def children(name):
            c = reg.get(name)
            return ({tuple(v for _, v in sorted(k)): m.value for k, m in c.children().items()}
                    if c else {})
        dots = children("moe_grouped_dot_calls_total")
        return (children("mamba_mixers_total").get(("2", "4", "16"), 0),      # groups, heads, state
                children("ssd_scan_calls_total").get(("8", "chunked_xla"), 0),  # chunk, impl
                children("moe_router_calls_total").get(("sigmoid", "6"), 0),
                dots.get(("ragged_dot", "0"), 0))

    model, params, ids = tiny
    before = read()
    jax.eval_shape(lambda p: model.loss(p, ids), params)
    # 4 mixers with a scan each, 4 expert layers of 2 forward products each (off the chip
    # through lax.ragged_dot, whose gradients autodiff writes uncounted)
    assert tuple(a - b for a, b in zip(read(), before)) == (4, 4, 4, 8)


def test_the_new_scopes_are_of_the_phase_vocabulary_and_sit_under_the_mixer(tiny):
    from apex_tpu.observability import phases
    scopes = {"mamba.in_proj", "mamba.conv", "mamba.scan", "mamba.gate_norm", "mamba.out_proj"}
    assert scopes <= set(phases.PHASES)
    model, params, ids = tiny
    text = jax.jit(lambda p: model.loss(p, ids)).lower(params).as_text(debug_info=True)
    for scope in ("layers/0/mamba/mamba.scan", "layers/2/mamba/mamba.in_proj",
                  "layers/4/mamba/mamba.conv", "layers/7/mamba/mamba.gate_norm",
                  "layers/7/mamba/mamba.out_proj", "layers/1/mlp/moe.experts",
                  "layers/5/self_attn"):
        assert scope in text, scope
    of = phases.phase_of_op_name
    assert of("jit(step)/jvp(model)/layers/2/mamba/mamba.scan/dot_general") == (
        ("model", "layers/2/mamba", "mamba.scan"), False)
    assert of("jit(step)/transpose(jvp(model))/jvp(model)/checkpoint/layers/0/mamba/mamba.scan/"
              "cumsum") == (("model", "layers/0/mamba", "mamba.scan"), True)


# -- the other decoders' steps are the ones they were ------------------------------

@pytest.mark.parametrize("name,base", [("laguna", LAGUNA_TINY), ("mellum", MELLUM_TINY),
                                       ("lfm2_moe", LFM2_TINY), ("ouro", OURO_TINY)])
def test_what_this_decoder_needed_leaves_the_other_steps_as_they_were(name, base):
    """The tiny ``laguna``, ``mellum``, ``lfm2_moe`` and ``ouro`` training steps
    traced twice, once with the block of a layer left to the class's default and
    once with it stated: one jaxpr, letter for letter, their attention still
    rotating, a gated shared expert where there is one, and nothing of the new
    mechanisms in it."""
    from apex_tpu import amp, optimizers
    from apex_tpu.models.laguna import Laguna, LagunaBlock

    class Stated(Laguna):
        block = LagunaBlock

    ids = jnp.asarray(np.random.RandomState(1).randint(0, 64, (2, T)), jnp.int32)

    def step_jaxpr(cls):
        amp.policy.set_policy(amp.policy.NoPolicy())
        net = cls(models.LagunaConfig.from_dict(base))
        assert all(b.self_attn.inv_freq is not None for b in net.layers if b.mixer == "self_attn")
        model, opt = amp.initialize(net, optimizers.FusedAdam(lr=1e-3), opt_level="O2",
                                    verbosity=0)
        params = jax.eval_shape(lambda k: model.init(k)[0], jax.random.PRNGKey(0))
        assert not any("mamba" in layer for layer in params["layers"].values())
        for layer in params["layers"].values():
            if "shared" in layer["mlp"]:
                assert set(layer["mlp"]["shared"]) == {"w_gate", "w_in", "w_out"}
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

    default, stated = step_jaxpr(Laguna), step_jaxpr(Stated)
    assert default == stated and "mamba" not in default and "square" in default
