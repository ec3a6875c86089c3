"""The per-layer decoder (models/laguna.py) configured as ``ouro`` against the
benchmark's plain reference (benchmark/references/ouro.py) at a tiny config
with every mechanism present: 3 dense full-attention layers applied 4 times
over the same weights, sandwich norms, an untied head read at every pass and a
seeded exit gate with a nonzero bias.  Beside it: the scan is four written-out
passes and a stack weight's gradient the sum of four copies'; the exit
distribution; a gate shut at every pass but the last; the trace-time counters
and scopes; a dense-only file needs no expert keys; and the ``laguna``,
``mellum`` and ``lfm2_moe`` steps unchanged by the switches this configuration
needed."""

import os
import sys

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from apex_tpu import models
from apex_tpu.models.laguna import exit_log_probs

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if os.path.join(ROOT, "benchmark") not in sys.path:
    sys.path.insert(0, os.path.join(ROOT, "benchmark"))
from references import ouro as ref  # noqa: E402
from test_laguna import TINY as LAGUNA_TINY  # noqa: E402
from test_lfm2 import TINY as LFM2_TINY  # noqa: E402
from test_mellum2 import TINY as MELLUM_TINY  # noqa: E402

R, T = 4, 32
TINY = dict(
    model_type="ouro", vocab_size=64, hidden_size=32, intermediate_size=48,
    num_hidden_layers=3, layer_types=["full_attention"] * 3, mlp_layer_types=["dense"] * 3,
    num_attention_heads=4, num_key_value_heads=4, head_dim=8, rms_norm_eps=1e-6,
    gating=False, qk_norm=False, sliding_window=None, tie_word_embeddings=False,
    shared_expert_intermediate_size=0,
    rope_parameters={"full_attention": {"rope_type": "default", "rope_theta": 1000000}},
    total_ut_steps=R, sandwich_norm=True, exit_beta=0.05, max_position_embeddings=64,
    head_chunk=24)


def _perturbed(params, seed=1, scale=0.05):
    """Norm gains away from 1 and a gate bias away from its draw, so that no
    term of the model is silent in a comparison."""
    leaves, tree = jax.tree_util.tree_flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(leaves))
    return jax.tree_util.tree_unflatten(
        tree, [x + scale * jax.random.normal(k, x.shape) for x, k in zip(leaves, keys)])


@pytest.fixture(scope="module")
def tiny():
    model = models.Laguna(models.LagunaConfig.from_dict(TINY))
    params = _perturbed(model.init(jax.random.PRNGKey(0))[0])
    params["exit_gate"]["bias"] = params["exit_gate"]["bias"] + 0.3
    ids = jnp.asarray(np.random.RandomState(0).randint(0, 64, (2, T)), jnp.int32)
    return model, params, ids


def _ref_loss(params, ids):
    return ref.summed_loss(params, ids, TINY) / (ids.shape[0] * (T - 1))


def _close(got, want, **tol):
    flat_g, flat_w = (jax.tree_util.tree_leaves_with_path(t) for t in (got, want))
    assert len(flat_g) == len(flat_w)
    for (path, g), (_, w) in zip(flat_g, flat_w):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), err_msg=jax.tree_util.keystr(path),
                                   **tol)


def test_the_configuration_builds_what_the_published_file_says(tiny):
    model, params, _ = tiny
    assert set(params) == {"embed_tokens", "exit_gate", "layers", "lm_head", "norm"}
    assert params["exit_gate"]["weight"].shape == (1, 32) and params["exit_gate"]["bias"].shape == (1,)
    block = params["layers"]["0"]
    assert set(block) == {"input_layernorm", "input_layernorm_2", "post_attention_layernorm",
                          "post_attention_layernorm_2", "self_attn", "mlp"}
    assert set(block["self_attn"]) == {"q_proj", "k_proj", "v_proj", "o_proj"}
    assert not any(b.sparse for b in model.layers) and all(b.sandwich for b in model.layers)
    # a dense-only file names no expert size; a sparse layer without them is refused, and so
    # is a looped stack with one
    assert model.cfg.num_experts is None and model.cfg.moe_intermediate_size is None
    with pytest.raises(ValueError, match="a sparse layer needs"):
        models.LagunaConfig.from_dict(dict(TINY, total_ut_steps=1,
                                           mlp_layer_types=["dense", "sparse", "dense"]))
    with pytest.raises(ValueError, match="loops dense layers only"):
        models.LagunaConfig.from_dict(dict(
            TINY, mlp_layer_types=["dense", "sparse", "dense"], num_experts=4,
            num_experts_per_tok=2, moe_intermediate_size=16))
    with pytest.raises(ValueError, match="total_ut_steps"):
        models.LagunaConfig.from_dict(dict(TINY, total_ut_steps=0))


def test_logits_of_every_pass_match_the_reference(tiny):
    model, params, ids = tiny
    got = model(params, ids)
    assert got.shape == (R, 2, T, 64)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref.logits(params, ids, TINY)),
                               atol=2e-5)
    # the passes differ: the loop is not one pass read four times
    assert float(jnp.abs(got[0] - got[R - 1]).max()) > 1e-2


def test_loss_and_the_step_sums_match_the_reference(tiny):
    model, params, ids = tiny
    loss, stats = model.loss(params, ids, return_stats=True)
    np.testing.assert_allclose(float(loss), float(_ref_loss(params, ids)), rtol=2e-6)
    want = np.sum([[float(v) for v in ref.row_sums(params, row, TINY)] for row in ids], 0)
    assert int(stats["exit_positions"]) == 2 * (T - 1)
    np.testing.assert_allclose(float(stats["exit_step_sum"]), want[1], rtol=1e-5)
    np.testing.assert_allclose(float(stats["nll_last_sum"]), want[2], rtol=1e-5)
    mean_step = float(stats["exit_step_sum"]) / int(stats["exit_positions"])
    assert 1.0 < mean_step < R


@pytest.mark.parametrize("remat", [None, "dots", "nothing"])
def test_gradients_match_the_reference(tiny, remat):
    _, params, ids = tiny
    model = models.Laguna(models.LagunaConfig.from_dict(TINY, remat=remat))
    got = jax.grad(lambda p: model.loss(p, ids))(params)
    want = jax.grad(_ref_loss)(params, ids)
    _close(got, want, atol=3e-6, rtol=2e-4)
    # every leaf has a gradient path, the gate's two among them
    assert all(float(jnp.abs(g).max()) > 0 for g in jax.tree_util.tree_leaves(got))
    assert float(jnp.abs(got["exit_gate"]["bias"]).max()) > 1e-4
    assert float(jnp.abs(got["exit_gate"]["weight"]).max()) > 1e-4


def test_one_fused_adam_step_matches_the_references_adam(tiny):
    from apex_tpu import optimizers
    model, params, ids = tiny
    hp = ref.ADAM
    opt = optimizers.FusedAdam(lr=hp["lr"], weight_decay=hp["weight_decay"])
    new, _ = opt.step(params, opt.init(params), jax.grad(lambda p: model.loss(p, ids))(params))[:2]
    zeros = jax.tree_util.tree_map(jnp.zeros_like, params)
    want, _, _ = ref.adam_update(params, zeros, zeros, jax.grad(_ref_loss)(params, ids),
                                 jnp.float32(1), hp, "float32")
    moved = lambda tree: jax.tree_util.tree_map(lambda a, b: a - b, tree, params)
    assert all(float(jnp.abs(m).max()) > 0 for m in jax.tree_util.tree_leaves(moved(want)))
    _close(moved(new), moved(want), atol=1e-5, rtol=2e-3)


def test_o2_keeps_the_gate_in_float32_and_trains():
    from apex_tpu import amp, optimizers
    model, opt = amp.initialize(models.Laguna(models.LagunaConfig.from_dict(TINY)),
                                optimizers.FusedAdam(lr=1e-3), opt_level="O2", verbosity=0)
    try:
        params, _ = model.init(jax.random.PRNGKey(0))
        gate = params["exit_gate"]
        assert gate["weight"].dtype == gate["bias"].dtype == jnp.float32
        assert params["lm_head"]["weight"].dtype == jnp.bfloat16
        assert params["layers"]["0"]["mlp"]["up_proj"]["weight"].dtype == jnp.bfloat16
        opt_state = opt.init(params)
        ids = jnp.asarray(np.random.RandomState(1).randint(0, 64, (2, T)), jnp.int32)

        @jax.jit
        def step(params, opt_state):
            loss, stats, grads = amp.scaled_grad(
                lambda p: model.loss(p, ids, return_stats=True), params, opt_state, has_aux=True)
            params, opt_state, _ = opt.step(params, opt_state, grads)
            return params, opt_state, loss, stats

        losses = []
        for _ in range(8):
            params, opt_state, loss, stats = step(params, opt_state)
            losses.append(float(loss))
        assert np.isfinite(losses).all() and losses[-1] < losses[0]
        assert set(stats) == {"exit_step_sum", "nll_last_sum", "exit_positions"}
    finally:
        amp.policy.set_policy(amp.policy.NoPolicy())


# -- the loop: one scan over the passes ---------------------------------------------

def _written_out_loss(model, copies, params, ids):
    """The same loss with the passes written out, pass ``t`` through
    ``copies[t]``, a tree of the layers of its own."""
    x = model.embed_tokens(params["embed_tokens"], ids)
    states = []
    for layers in copies:
        x, _ = model._stack({"layers": layers}, x)
        x = model.norm(params["norm"], x)
        states.append(x)
    labels = jnp.concatenate([ids[:, 1:], jnp.zeros((ids.shape[0], 1), ids.dtype)], 1)
    return model._exit_loss(params, jnp.stack(states), labels)[0]


def test_the_scan_is_four_written_out_passes_and_a_weights_gradient_their_sum(tiny):
    model, params, ids = tiny
    copies = [params["layers"]] * R
    loss, grads = jax.value_and_grad(lambda p: model.loss(p, ids))(params)
    out, by_pass = jax.value_and_grad(
        lambda c: _written_out_loss(model, c, params, ids))(copies)
    np.testing.assert_allclose(float(loss), float(out), rtol=1e-6)
    summed = jax.tree_util.tree_map(lambda *g: sum(g), *by_pass)
    _close(grads["layers"], summed, atol=1e-6, rtol=1e-4)
    # no pass's share is the whole: each of the four contributes
    w = lambda tree: tree["1"]["mlp"]["down_proj"]["weight"]
    assert all(float(jnp.abs(w(g)).max()) > 1e-2 * float(jnp.abs(w(summed)).max()) for g in by_pass)


def test_the_traced_loss_holds_each_block_once_whatever_the_passes(tiny):
    """A scan's body holds the stack once: as many matrix products and as
    many scans (the passes', the head's chunks) at three passes as at four."""
    _, params, ids = tiny

    def dots(passes):
        model = models.Laguna(models.LagunaConfig.from_dict(TINY, total_ut_steps=passes))
        text = str(jax.make_jaxpr(lambda p: model.loss(p, ids))(params))
        return text.count("dot_general"), text.count("scan["), text.count("length=%d" % passes)

    assert dots(3) == dots(R) and dots(R)[2] == 1


def test_the_exit_distribution_sums_to_one_and_reads_no_gate_of_the_last_pass():
    z = jnp.asarray(np.random.RandomState(3).normal(0, 2.0, (R - 1, 5, 7)), jnp.float32)
    logp = exit_log_probs(z)                    # R - 1 gates in, R passes out
    assert logp.shape == (R, 5, 7)
    prob = np.exp(np.asarray(logp, np.float64))
    np.testing.assert_allclose(prob.sum(0), 1.0, atol=1e-6)
    lam = 1.0 / (1.0 + np.exp(-np.asarray(z, np.float64)))
    want = [lam[0], lam[1] * (1 - lam[0]), lam[2] * (1 - lam[0]) * (1 - lam[1]),
            (1 - lam[0]) * (1 - lam[1]) * (1 - lam[2])]
    np.testing.assert_allclose(prob, np.stack(want), rtol=1e-5)
    # gates shut or open far past float32's sigmoid: still a distribution, no nan
    hard = exit_log_probs(jnp.asarray([[-200.0, 200.0], [200.0, -200.0], [0.0, 0.0]]))
    assert np.isfinite(np.exp(np.asarray(hard))).all()
    np.testing.assert_allclose(np.exp(np.asarray(hard)).sum(0), 1.0, atol=1e-6)


def test_the_loss_does_not_read_the_last_passes_gate(tiny):
    """The last state enters through its head alone: the loss is linear in the
    last pass's per-position loss with the weight ``p_R``, which the earlier
    passes' gates decide, so the last state's cotangent is the head's under
    that weight and nothing else."""
    from apex_tpu.nn.fused_xent import linear_cross_entropy
    model, params, ids = tiny
    states = model._backbone(params, ids)[0]
    labels = jnp.concatenate([ids[:, 1:], jnp.zeros((2, 1), ids.dtype)], 1)
    gate = params["exit_gate"]

    def from_last(last):
        return model._exit_loss(params, jnp.concatenate([states[:-1], last[None]]), labels)[0]

    def nll(last):
        return linear_cross_entropy(last.reshape(2 * T, -1), params["lm_head"]["weight"],
                                    labels.reshape(-1), 24).reshape(2, T)

    z = jnp.sum(states[:-1] * gate["weight"][0], -1) + gate["bias"]
    weight = jnp.exp(exit_log_probs(z)[-1]) * (jnp.arange(T) < T - 1) / (2 * (T - 1))
    want = jax.vjp(nll, states[-1])[1](weight)[0]
    got = jax.grad(from_last)(states[-1])
    assert float(jnp.abs(got).max()) > 0
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-8)


def test_a_gate_shut_until_the_last_pass_leaves_the_last_passes_cross_entropy(tiny):
    model, params, ids = tiny
    shut = {**params, "exit_gate": {"weight": params["exit_gate"]["weight"] * 0.0,
                                    "bias": jnp.full((1,), -30.0)}}
    loss, stats = model.loss(shut, ids, return_stats=True)
    last = float(stats["nll_last_sum"]) / int(stats["exit_positions"])
    np.testing.assert_allclose(float(loss), last, rtol=1e-6)
    np.testing.assert_allclose(float(stats["exit_step_sum"]) / int(stats["exit_positions"]), R,
                               rtol=1e-6)
    # and it is the plain model's loss on the last pass's logits
    logp = jax.nn.log_softmax(model(shut, ids)[R - 1][:, :-1], -1)
    plain = -jnp.mean(jnp.take_along_axis(logp, ids[:, 1:, None], -1))
    np.testing.assert_allclose(last, float(plain), rtol=1e-5)
    # an open gate at the first pass leaves the first pass's
    first = {**params, "exit_gate": {"weight": params["exit_gate"]["weight"] * 0.0,
                                     "bias": jnp.full((1,), 30.0)}}
    loss, stats = model.loss(first, ids, return_stats=True)
    np.testing.assert_allclose(float(stats["exit_step_sum"]) / int(stats["exit_positions"]), 1.0,
                               rtol=1e-6)
    logp = jax.nn.log_softmax(model(first, ids)[0][:, :-1], -1)
    np.testing.assert_allclose(
        float(loss), float(-jnp.mean(jnp.take_along_axis(logp, ids[:, 1:, None], -1))), rtol=1e-5)


# -- what a traced program says of itself ---------------------------------------------

def test_a_traced_looped_stack_counts_itself_and_its_gate(tiny):
    from apex_tpu.observability.metrics import get_registry
    reg = get_registry()

    def read():
        loops = reg.get("looped_stack_total")
        by = ({tuple(v for _, v in sorted(k)): c.value for k, c in loops.children().items()}
              if loops else {})
        gates = reg.get("exit_gate_calls_total")
        return by.get(("3", "4"), 0), gates.value if gates else 0      # labels sorted: layers, passes

    model, params, ids = tiny
    before = read()
    jax.eval_shape(lambda p: model.loss(p, ids), params)
    after = read()
    assert tuple(a - b for a, b in zip(after, before)) == (1, 1)
    plain = models.Laguna(models.LagunaConfig.from_dict(MELLUM_TINY))
    shapes = jax.eval_shape(lambda k: plain.init(k)[0], jax.random.PRNGKey(0))
    jax.eval_shape(lambda p: plain.loss(p, ids), shapes)
    assert read() == after                      # no loop, no gate in the other decoders


def test_the_new_scopes_are_of_the_phase_vocabulary_and_keep_the_module_paths(tiny):
    from apex_tpu.observability import phases
    assert {"loop", "loop.norm", "loss.head", "loss.exit"} <= set(phases.PHASES)
    model, params, ids = tiny
    text = jax.jit(jax.grad(lambda p: model.loss(p, ids))).lower(params).as_text(debug_info=True)
    for scope in ("loop/", "loop.norm/norm", "layers/2/input_layernorm_2",
                  "layers/0/post_attention_layernorm_2", "loss.head/", "loss.exit/"):
        assert scope in text, scope
    of = phases.phase_of_op_name
    # the scope around the scan follows the module path it encloses, forward and in a
    # rematerialized block's backward
    assert of("jit(step)/jvp(model)/loop/while/body/closed_call/layers/3/mlp/gate_proj/dot_general"
              ) == (("model", "layers/3/mlp/gate_proj", "loop"), False)
    assert of("jit(step)/transpose(jvp(model))/loop/while/body/closed_call/checkpoint/"
              "rematted_computation/layers/0/self_attn/q_proj/dot_general"
              ) == (("model", "layers/0/self_attn/q_proj", "loop"), True)
    assert of("jit(step)/jvp(model)/loop/while/body/closed_call/loop.norm/norm/mul"
              ) == (("model", "norm", "loop", "loop.norm"), False)
    # the scan's own stacking of what it saves is the loop's
    assert of("jit(step)/jvp(model)/loop/while/body/dynamic_update_slice") == (("model", "loop"),
                                                                               False)
    assert of("jit(step)/jvp(loss)/loss.head/while/body/dot_general") == (("loss", "loss.head"),
                                                                          False)
    # names the accepted cells' steps hold read as they did
    assert of("jit(step)/jvp(model)/layers/3/conv/conv.mix/checkpoint/mul") == (
        ("model", "layers/3/conv", "conv.mix"), False)
    assert of("jit(step)/transpose(jvp(model))/jvp(model)/checkpoint/layers/1/mlp/moe.route/dot"
              ) == (("model", "layers/1/mlp", "moe.route"), True)
    assert of("jit(step)/shard_map/amp.update/cond/branch_0_fun/optim.adam/mul") == (
        ("amp.update", "optim.adam"), False)


# -- the other decoders' steps are the ones they were ------------------------------

@pytest.mark.parametrize("name,base", [("laguna", LAGUNA_TINY), ("mellum", MELLUM_TINY),
                                       ("lfm2_moe", LFM2_TINY)])
def test_the_new_switches_at_their_defaults_leave_the_other_steps_as_they_were(name, base):
    """The tiny ``laguna``, ``mellum`` and ``lfm2_moe`` training steps traced
    twice, once from a config with every key this configuration added absent
    and once with each stated at its default: one jaxpr, letter for letter, no
    gate among the parameters and nothing of the loop in it."""
    from apex_tpu import amp, optimizers
    stated = dict(base, total_ut_steps=1, sandwich_norm=False, exit_beta=0.0)
    ids = jnp.asarray(np.random.RandomState(1).randint(0, 64, (2, T)), jnp.int32)

    def step_jaxpr(cfg):
        amp.policy.set_policy(amp.policy.NoPolicy())
        model, opt = amp.initialize(models.Laguna(models.LagunaConfig.from_dict(cfg)),
                                    optimizers.FusedAdam(lr=1e-3), opt_level="O2", verbosity=0)
        params = jax.eval_shape(lambda k: model.init(k)[0], jax.random.PRNGKey(0))
        assert "exit_gate" not in params
        assert "input_layernorm_2" not in params["layers"]["0"]
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
    assert absent == present and "ragged_dot" in absent and "exit" not in absent


# -- compiled for a described (not attached) v5e (``one_chip``: conftest.py) ---------

def test_v5e_compiles_the_looped_block_with_no_forward_kernel_in_its_replay(one_chip,
                                                                            for_the_chip):
    """One sandwich block of ``ouro-2.6b`` under the cell's ``remat: nothing`` at
    the cell's widths (1 x 8192 tokens, bf16), forward and gradient: the
    backward's replay of the block recomputes its projections and holds no
    ``flash_fwd``, whose ``o`` and ``lse`` the mode keeps by name; the step
    launches each flash kernel once."""
    import json
    import re
    from apex_tpu.models import laguna
    from apex_tpu.models._remat import wrap_block
    with open(os.path.join(ROOT, "benchmark", "configs", "ouro-2.6b.json")) as f:
        cfg = laguna.LagunaConfig.from_dict(json.load(f))
    block = laguna.LagunaBlock(cfg, 0)
    shapes = jax.eval_shape(lambda k: block.init(k)[0], jax.random.PRNGKey(0))
    params = jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, jnp.bfloat16, sharding=one_chip), shapes)
    x = jax.ShapeDtypeStruct((1, 8192, cfg.hidden_size), jnp.bfloat16, sharding=one_chip)

    def loss(p, x):
        return jnp.sum(wrap_block(lambda pp, xx: block(pp, xx)[0], cfg.remat)(p, x)
                       .astype(jnp.float32))

    text = jax.jit(jax.grad(loss, (0, 1))).lower(params, x).compile().as_text()
    for kernel in ("flash_fwd", "flash_dq", "flash_dkv"):
        assert len(re.findall(rf"%{kernel}[.\d]* = ", text)) == 1, kernel
    replay = [line for line in text.splitlines() if "rematted_computation" in line]
    assert any("self_attn/q_proj/dot_general" in line for line in replay)
    assert any("mlp/gate_proj/dot_general" in line for line in replay)
    assert not any("flash_fwd" in line for line in replay)
