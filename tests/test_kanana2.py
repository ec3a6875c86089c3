"""The latent-attention decoder (models/deepseek_v3.py over models/laguna.py;
transformer/mla.py) against the benchmark's plain reference
(benchmark/references/kanana2.py) at a tiny config with every mechanism present:
a leading dense layer, latent attention in every layer with one rotated key head
for all query heads, 4 experts held of a 16-wide sigmoid router at 4 a token with
a nonzero selection bias and two shared experts, an untied head.  Beside it: the
catalog row's keys parse and what is not built is refused by name; the layer
alone; the whole step through ``build()`` at O2 inside a band that fp8-rounded
operands leave; the shares of a 4-way expert-parallel group add up with the
shared experts counted once; the layer at heads of 128 + 64 / 128 through the
interpreted flash kernels; the two planted faults of this mechanism (the key
head not rotated, its gradient from one query head and not the sum) fail the
comparison; counters and scopes; and the five accepted decoders' steps as they
were."""

import json
import os
import sys

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from apex_tpu import models
from apex_tpu.parallel import expert_parallel as ep
from apex_tpu.transformer import mla

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if os.path.join(ROOT, "benchmark") not in sys.path:
    sys.path.insert(0, os.path.join(ROOT, "benchmark"))
from references import kanana2 as ref  # noqa: E402
from test_laguna import TINY as LAGUNA_TINY  # noqa: E402
from test_lfm2 import TINY as LFM2_TINY  # noqa: E402
from test_mellum2 import TINY as MELLUM_TINY  # noqa: E402
from test_nemotron3 import TINY as NEMOTRON_TINY  # noqa: E402
from test_ouro import TINY as OURO_TINY  # noqa: E402

CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
# the catalog row's ``config`` as published
PUBLISHED = {
    "attention_bias": False, "first_k_dense_replace": 1, "head_dim": 64, "hidden_act": "silu",
    "hidden_size": 2048, "intermediate_size": 6144, "kv_lora_rank": 512,
    "max_position_embeddings": 32768, "model_type": "deepseek_v3", "moe_intermediate_size": 768,
    "moe_layer_freq": 1, "n_group": 1, "n_routed_experts": 128, "n_shared_experts": 2,
    "norm_topk_prob": True, "num_attention_heads": 32, "num_experts_per_tok": 6,
    "num_hidden_layers": 48, "num_key_value_heads": 32, "q_lora_rank": None, "qk_head_dim": 192,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
    "rope_interleave": True, "rope_scaling": None, "rope_theta": 1000000,
    "routed_scaling_factor": 2.448, "scoring_func": "sigmoid", "tie_word_embeddings": False,
    "topk_group": 1, "topk_method": "noaux_tc", "v_head_dim": 128, "vocab_size": 128256}
TINY = dict(
    PUBLISHED, hidden_size=32, intermediate_size=64, kv_lora_rank=16, moe_intermediate_size=16,
    num_attention_heads=4, num_key_value_heads=4, num_experts_per_tok=4, num_hidden_layers=5,
    qk_head_dim=24, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16, vocab_size=64,
    n_routed_experts=4, num_experts_published=16, experts_held_start=4,
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
    model = models.DeepseekV3(models.DeepseekV3Config.from_dict(TINY))
    params = _perturbed(model.init(jax.random.PRNGKey(0))[0])
    ids = jnp.asarray(np.random.RandomState(0).randint(0, 64, (2, T)), jnp.int32)
    return model, params, ids


def _ref_loss(params, ids, precision="float32"):
    return ref.summed_nll(params, ids, TINY, precision) / (ids.shape[0] * (T - 1))


# one compile each for every test that reads them
_ref_value_and_grad = jax.jit(jax.value_and_grad(_ref_loss), static_argnums=2)
_rows = jax.jit(ref.leaf_norms)


def _model_value_and_grad(model, params, ids):
    return jax.jit(jax.value_and_grad(lambda p: model.loss(p, ids)))(params)


# -- the configuration ------------------------------------------------------------

def test_the_catalog_rows_keys_build_the_published_model():
    if os.path.exists(CATALOG):
        row = next(json.loads(l) for l in open(CATALOG)
                   if '"name": "kanana-2-30b-a3b-instruct-2601"' in l)
        assert row["config"] == PUBLISHED
    cfg = models.DeepseekV3Config.from_dict(PUBLISHED)
    assert cfg.num_hidden_layers == 48 and cfg.mlp_layer_types == ("dense",) + ("sparse",) * 47
    assert (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim, cfg.kv_lora_rank) == (
        128, 64, 128, 512)
    assert cfg.shared_expert_intermediate_size == 2 * 768 and cfg.use_expert_bias
    assert cfg.router_type == "sigmoid" and cfg.moe_routed_scaling_factor == 2.448
    assert cfg.num_experts == cfg.router_experts == 128 and not cfg.tie_word_embeddings
    # the cut the benchmark runs, by shapes alone: 575.96 M parameters
    cut = dict(PUBLISHED, num_hidden_layers=5, n_routed_experts=16, num_experts_published=128,
               vocab_size=16032)
    model = models.DeepseekV3(models.DeepseekV3Config.from_dict(cut))
    shapes = jax.eval_shape(lambda k: model.init(k)[0], jax.random.PRNGKey(0))
    attn = shapes["layers"]["3"]["self_attn"]
    assert {k: v["weight"].shape for k, v in attn.items()} == {
        "q_nope_proj": (4096, 2048), "q_rope_proj": (2048, 2048), "kv_down_proj": (512, 2048),
        "k_rope_proj": (64, 2048), "kv_norm": (512,), "k_up_proj": (4096, 512),
        "v_up_proj": (4096, 512),
        "o_proj": (2048, 4096)}
    mlp = shapes["layers"]["3"]["mlp"]
    assert mlp["router"].shape == (2048, 128) and mlp["expert_bias"].shape == (128,)
    assert mlp["w_in"].shape == (16, 2048, 768) and mlp["shared"]["w_in"].shape == (2048, 1536)
    assert set(shapes["layers"]["0"]["mlp"]) == {"gate_proj", "up_proj", "down_proj"}
    assert sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(shapes)) == 575_955_968


@pytest.mark.parametrize("key,value", [
    ("q_lora_rank", 1536), ("n_group", 8), ("topk_group", 4), ("norm_topk_prob", False),
    ("rope_scaling", {"type": "yarn", "factor": 40}), ("rope_interleave", False),
    ("num_key_value_heads", 8), ("attention_bias", True), ("topk_method", "greedy"),
    ("v_head_dim", 32)])
def test_what_is_not_built_is_refused_by_name(key, value):
    with pytest.raises(ValueError, match=key.replace("v_head_dim", "v_head_dim|value head")):
        models.DeepseekV3(models.DeepseekV3Config.from_dict(dict(TINY, **{key: value})))


# -- the model against the reference -----------------------------------------------

def test_logits_and_loss_match_the_reference(tiny):
    model, params, ids = tiny
    np.testing.assert_allclose(
        np.asarray(jax.jit(model.__call__)(params, ids)),
        np.asarray(jax.jit(lambda p, i: ref.logits(p, i, TINY))(params, ids)), atol=1e-5)
    loss, stats = jax.jit(lambda p: model.loss(p, ids, return_stats=True))(params)
    np.testing.assert_allclose(float(loss), float(_ref_value_and_grad(params, ids)[0]), rtol=2e-6)
    assert int(stats["moe_dropped_assignments"]) == 0
    # 4 expert layers x 64 tokens x 4 choices, a quarter of the experts held
    assert 0 < int(stats["moe_assignments_held"]) < 4 * 64 * 4


@pytest.mark.parametrize("remat", [None, "dots", "nothing"])
def test_gradients_match_the_reference(tiny, remat):
    _, params, ids = tiny
    model = models.DeepseekV3(models.DeepseekV3Config.from_dict(TINY, remat=remat))
    _, got = _model_value_and_grad(model, params, ids)
    _, want = _ref_value_and_grad(params, ids)
    flat_g, flat_w = (jax.tree_util.tree_leaves_with_path(t) for t in (got, want))
    assert len(flat_g) == len(flat_w) == 5 * 10 + 3 + 4 * 8 + 3
    for (path, g), (_, w) in zip(flat_g, flat_w):
        name = jax.tree_util.keystr(path)
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=1e-5, rtol=2e-4, err_msg=name)
        # the selection bias has no gradient path; every other leaf has one
        assert (float(jnp.abs(w).max()) > 0) == ("expert_bias" not in name), name


def _layer(dim=32, heads=4, nope=16, rope=8, latent=16):
    layer = mla.LatentAttention(dim, heads, nope, rope, nope, latent, 1e6)
    cfg = dict(num_attention_heads=heads, qk_nope_head_dim=nope, qk_rope_head_dim=rope,
               v_head_dim=nope, kv_lora_rank=latent, rms_norm_eps=1e-6, rope_theta=1e6)
    params = _perturbed(layer.init(jax.random.PRNGKey(2))[0], seed=5, scale=0.1)
    return layer, params, cfg


def _against_the_reference(layer, params, cfg, x, atol):
    want_fn = lambda p, x: jnp.stack([ref.attention(p, row, cfg, "float32") for row in x])
    weigh = jax.random.normal(jax.random.PRNGKey(4), x.shape)
    both = lambda fn: jax.jit(lambda p, x: (fn(p, x), jax.grad(
        lambda p, x: jnp.sum(fn(p, x) * weigh), (0, 1))(p, x)))(params, x)
    (out, got), (want_out, want) = both(layer.__call__), both(want_fn)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want_out), atol=atol, rtol=2e-4)
    for (path, g), (_, w) in zip(*(jax.tree_util.tree_leaves_with_path(t) for t in (got, want))):
        scale = float(jnp.abs(w).max())
        assert scale > 0, path
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=atol * max(scale, 1.0),
                                   rtol=2e-4, err_msg=jax.tree_util.keystr(path))


def test_the_layer_alone_matches_the_references_attention_outputs_and_every_gradient():
    layer, params, cfg = _layer()
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 48, 32), jnp.float32)
    _against_the_reference(layer, params, cfg, x, 1e-5)
    # the rotation is on interleaved pairs: the pair (2j, 2j + 1) of token t turned by
    # t * theta^(-2j / d_r), every head of the array alike
    r = jax.random.normal(jax.random.PRNGKey(6), (1, 5, 3 * 8), jnp.float32)
    got = np.asarray(mla.rope_interleaved(r, layer.inv_freq)).reshape(5, 3, 4, 2)
    ang = np.arange(5)[:, None] * (1e6 ** (-np.arange(0, 8, 2) / 8.0))[None]
    a, b = np.asarray(r).reshape(5, 3, 4, 2)[..., 0], np.asarray(r).reshape(5, 3, 4, 2)[..., 1]
    c, s = np.cos(ang)[:, None], np.sin(ang)[:, None]
    np.testing.assert_allclose(got[..., 0], a * c - b * s, atol=1e-5)
    np.testing.assert_allclose(got[..., 1], b * c + a * s, atol=1e-5)


def test_the_layer_at_192_and_128_goes_through_the_flash_kernels(monkeypatch):
    """Heads of 128 + 64 against values of 128 with Pallas on (interpreted
    here): the parts reach the kernels where the projections wrote them, the
    key head once for both query heads; forward and every gradient against the
    reference's attention, never the dense path, nothing padded."""
    from apex_tpu.observability.metrics import get_registry
    from apex_tpu.transformer import attention
    layer, params, cfg = _layer(dim=64, heads=2, nope=128, rope=64, latent=32)
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 128, 64), jnp.float32)
    monkeypatch.setenv("APEX_TPU_FORCE_PALLAS", "1")
    monkeypatch.delenv("APEX_TPU_DISABLE_PALLAS", raising=False)

    def read():
        calls, pads = (get_registry().get(n) for n in ("flash_calls_total",
                                                        "flash_pad_copies_total"))
        by = ({tuple(v for _, v in sorted(k)): m.value for k, m in calls.children().items()}
              if calls else {})
        return by.get(("per_query_head", "token_major", "shared"), 0), pads.value if pads else 0

    paths, before = [], read()
    attention.set_path_hook(paths.append)
    try:
        _against_the_reference(layer, params, cfg, x, 3e-5)
    finally:
        attention.set_path_hook(None)
    assert set(paths) == {"flash"}
    calls, pads = (a - b for a, b in zip(read(), before))
    assert calls == 3 and pads == 0         # a forward, and a forward and a backward


def test_bf16_compute_stays_in_a_band_that_fp8_rounded_operands_leave(tiny):
    """The model on a bf16 copy of the weights (what amp O2 hands it) against
    the float32 reference, the first gradient leaf by leaf as the runner reads
    it: a leaf's difference over its norm or the median leaf's stays under 0.2
    on every leaf and 0.03 in the mean (read 0.10 and 0.013); the reference
    itself with fp8-rounded operands leaves that band (read 0.09 in the mean).
    The whole step through ``build()`` at O2 is benchmark/tests/test_bm_kanana2.py's."""
    model, params, ids = tiny
    half = jax.tree_util.tree_map_with_path(      # the router and its bias stay float32
        lambda path, x: x if {"router", "expert_bias"} & {getattr(k, "key", None) for k in path}
        else x.astype(jnp.bfloat16), params)
    loss, grads = _model_value_and_grad(model, half, ids)
    params = jax.tree_util.tree_map(lambda x: x.astype(jnp.float32), half)     # the same numbers
    want_loss, want = _ref_value_and_grad(params, ids)
    assert abs(float(loss) - float(want_loss)) < 2e-3 * float(want_loss)
    want = np.asarray(_rows(want))
    rel = ref.leaf_differences(np.asarray(_rows(jax.tree_util.tree_map(
        lambda g: g.astype(jnp.float32), grads))), want)
    assert rel.shape == (88,) and rel.max() < 0.2 and rel.mean() < 0.03, (rel.max(), rel.mean())
    low = np.asarray(_rows(_ref_value_and_grad(params, ids, "fp8")[1]))
    rel_low = ref.leaf_differences(low, want)
    assert rel_low.mean() > 0.03 and rel_low.mean() > 3 * rel.mean(), rel_low.mean()


# -- the expert layer: a chip's share ----------------------------------------------

def test_the_four_shares_add_up_to_the_uncut_layer_with_the_shared_experts_once():
    """16 routed experts in 4 shares of 4 (router, bias and the two shared
    experts on every chip alike): the four shares' routed parts plus the shared
    experts once are what the reference gives for the whole layer."""
    def layer(held=None):
        return ep.ExpertParallelMLP(8, 6, 16, capacity_factor=None, top_k=4, expert_type="swiglu",
                                    router_type="sigmoid", routed_scaling=2.448,
                                    experts_held=held, shared_hidden=2 * 6, router_bias=True)

    cfg = lambda start=0: {"num_experts_per_tok": 4, "experts_held_start": start,
                           "routed_scaling_factor": 2.448}
    whole = layer()
    params, _ = whole.init(jax.random.PRNGKey(3))
    params = {**params, "expert_bias": 0.3 * jax.random.normal(jax.random.PRNGKey(4), (16,))}
    x = jnp.asarray(np.random.RandomState(3).randn(24, 8), jnp.float32)
    reference = jax.jit(lambda p, x, start, shared=True: ref.sparse_mlp(
        p, x, cfg(start), "float32", shared), static_argnums=(2, 3))
    want = reference(params, x, 0)
    np.testing.assert_allclose(np.asarray(jax.jit(whole.__call__)(params, x)), np.asarray(want),
                               atol=3e-5)
    shared = want - reference(params, x, 0, False)
    assert float(jnp.abs(shared).max()) > 0.01 and params["shared"]["w_in"].shape == (8, 12)
    total, held = jnp.zeros_like(x), 0
    for start in range(0, 16, 4):
        share = {**params, **{k: params[k][start:start + 4] for k in ("w_gate", "w_in", "w_out")}}
        y, stats = jax.jit(lambda p, x, start=start: layer(held=(start, 4))(
            p, x, return_stats=True))(share, x)
        np.testing.assert_allclose(np.asarray(y), np.asarray(reference(share, x, start)),
                                   atol=3e-5)
        total, held = total + (y - shared), held + int(stats["moe_assignments_held"])
    np.testing.assert_allclose(np.asarray(total + shared), np.asarray(want), atol=8e-5)
    assert held == 24 * 4           # every assignment lands on exactly one share


# -- the planted faults of this mechanism --------------------------------------------

def _first_gradient_rows(model, params, ids):
    loss, grads = _model_value_and_grad(model, params, ids)
    return float(loss), np.asarray(_rows(grads))


def _plant(fault):
    """``benchmark/tools/kanana2_faults.py``'s ``planted``: the one home of the two faults."""
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "kanana2_faults", os.path.join(ROOT, "benchmark", "tools", "kanana2_faults.py"))
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    return tool.planted(fault)


@pytest.mark.parametrize("fault,forward_moves", [("unrotated", True), ("one_head", False)])
def test_a_planted_fault_of_the_mechanism_fails_the_comparison(tiny, fault, forward_moves):
    """The first gradient, leaf by leaf, as the runner reads it and the
    reference compares it (``references/kanana2.compare``): sound, the program's
    differs from the reference's by a rounding; with the one key head left
    unrotated, or its gradient taken from query head 0 and not summed over the
    four, the fifth-worst leaf (one ``k_rope_proj`` a layer) differs by most of
    its own norm, past the tiny configuration's limit
    (benchmark/tests/tiny/configs/kanana2-tiny.json); a gradient from one head
    is also short by half its length."""
    model, params, ids = tiny
    want_loss, want = _ref_value_and_grad(params, ids)
    reading = lambda loss, rows: {"losses": [loss], "first_grad_norms": rows, "update_norms": rows}
    want = reading(float(want_loss), np.asarray(_rows(want)))
    sound = ref.compare(reading(*_first_gradient_rows(model, params, ids)), want)
    assert sound["grad_diff_own_5th"] < 1e-4 and sound["grad_norm_own_worst"] < 1e-4
    limits = json.load(open(os.path.join(ROOT, "benchmark", "tests", "tiny", "configs",
                                         "kanana2-tiny.json")))["limits"]
    with _plant(fault):
        got = ref.compare(reading(*_first_gradient_rows(model, params, ids)), want)
    names = [jax.tree_util.keystr(p) for p, _ in jax.tree_util.tree_leaves_with_path(params)]
    assert got["grad_diff_own_5th"] > limits["grad_diff_own_5th"], got
    assert "_rope_proj" in names[got["grad_diff_own_worst_leaf"]]
    assert (got["loss_gap"] > 1e-6) == forward_moves
    if not forward_moves:       # the forward is the sound one, and the key head's gradient short
        assert got["grad_norm_own_worst"] > limits["grad_norm_own_worst"]
        assert "k_rope_proj" in names[got["grad_norm_own_leaf"]]


# -- what a traced step holds ----------------------------------------------------------

def test_traced_layers_count_themselves_and_sit_under_scopes_of_the_vocabulary(tiny):
    from apex_tpu.observability import phases
    from apex_tpu.observability.metrics import get_registry
    scopes = {"mla.q_proj", "mla.kv_down", "mla.kv_norm", "mla.kv_up", "mla.rope", "mla.o_proj"}
    assert scopes <= set(phases.PHASES)
    model, params, ids = tiny

    def layers():
        c = get_registry().get("mla_layers_total")
        by = ({tuple(v for _, v in sorted(k)): m.value for k, m in c.children().items()}
              if c else {})
        return by.get(("4", "16", "24", "16"), 0)          # heads, latent, qk, v

    before = layers()
    text = jax.jit(lambda p: model.loss(p, ids)).lower(params).as_text(debug_info=True)
    assert layers() - before == 5
    for scope in ("layers/0/self_attn/mla.q_proj", "layers/1/self_attn/mla.kv_down",
                  "layers/2/self_attn/mla.kv_norm", "layers/3/self_attn/mla.kv_up",
                  "layers/4/self_attn/mla.rope", "layers/4/self_attn/mla.o_proj",
                  "layers/1/mlp/moe.experts"):
        assert scope in text, scope
    of = phases.phase_of_op_name
    assert of("jit(step)/jvp(model)/layers/2/self_attn/mla.rope/mul") == (
        ("model", "layers/2/self_attn", "mla.rope"), False)
    assert of("jit(step)/transpose(jvp(model))/jvp(model)/checkpoint/layers/0/self_attn/"
              "mla.kv_up/dot_general") == (("model", "layers/0/self_attn", "mla.kv_up"), True)


# -- the other decoders' steps are the ones they were ------------------------------

@pytest.mark.parametrize("name,base", [("laguna", LAGUNA_TINY), ("mellum", MELLUM_TINY),
                                       ("lfm2_moe", LFM2_TINY), ("ouro", OURO_TINY),
                                       ("nemotron_h", NEMOTRON_TINY)])
def test_what_this_decoder_needed_leaves_the_other_steps_as_they_were(name, base):
    """The tiny ``laguna``, ``mellum``, ``lfm2_moe``, ``ouro`` and ``nemotron_h``
    training steps traced twice, once with what attends in a block left to the
    class's default and once with it stated: one jaxpr, letter for letter, and
    nothing of the latent layer in it."""
    from apex_tpu import amp, optimizers
    from apex_tpu.models import nemotron_h
    from apex_tpu.models.laguna import Laguna, LagunaAttention, LagunaBlock

    class StatedBlock(LagunaBlock):
        attention = LagunaAttention

    class Stated(Laguna):
        block = StatedBlock

    class StatedOneBranch(models.NemotronH):
        block = nemotron_h.NemotronHBlock

    default, stated, config = ((models.NemotronH, StatedOneBranch, models.NemotronHConfig)
                               if name == "nemotron_h" else (Laguna, Stated, models.LagunaConfig))
    ids = jnp.asarray(np.random.RandomState(1).randint(0, 64, (2, T)), jnp.int32)

    def step_jaxpr(cls):
        amp.policy.set_policy(amp.policy.NoPolicy())
        model, opt = amp.initialize(cls(config.from_dict(base)), optimizers.FusedAdam(lr=1e-3),
                                    opt_level="O2", verbosity=0)
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

    one, other = step_jaxpr(default), step_jaxpr(stated)
    assert one == other and "mla" not in one
