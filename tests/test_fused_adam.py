"""FusedAdam parity vs torch.optim.Adam — mirrors the reference's
tests/L0/run_mixed_adam/test_mixed_adam.py:18-69 (ref/tst pairs stepped on
identical grads, max diff <= 1e-3; synthetic scaled half grads)."""

import numpy as np
import pytest
import jax
import jax.numpy as jnp

torch = pytest.importorskip("torch")

from apex_tpu.optimizers import FusedAdam, FusedLAMB, FP16_Optimizer


def _trees(seed, shapes):
    rng = np.random.RandomState(seed)
    params = {f"p{i}": rng.randn(*s).astype(np.float32)
              for i, s in enumerate(shapes)}
    grads = {f"p{i}": rng.randn(*s).astype(np.float32)
             for i, s in enumerate(shapes)}
    return params, grads


SHAPES = [(13,), (4, 7), (2, 3, 5)]


@pytest.mark.parametrize("wd", [0.0])
@pytest.mark.parametrize("eps_inside", [False])
def test_adam_parity_vs_torch(wd, eps_inside):
    params_np, _ = _trees(0, SHAPES)
    t_params = [torch.nn.Parameter(torch.tensor(v)) for v in
                params_np.values()]
    t_opt = torch.optim.Adam(t_params, lr=1e-3, betas=(0.9, 0.999),
                             eps=1e-8, weight_decay=wd)
    j_params = {k: jnp.asarray(v) for k, v in params_np.items()}
    j_opt = FusedAdam(lr=1e-3, betas=(0.9, 0.999), eps=1e-8,
                      weight_decay=wd, eps_inside_sqrt=eps_inside)
    st = j_opt.init(j_params)
    for it in range(5):
        _, grads_np = _trees(100 + it, SHAPES)
        for p, g in zip(t_params, grads_np.values()):
            p.grad = torch.tensor(g)
        t_opt.step()
        j_grads = {k: jnp.asarray(v) for k, v in grads_np.items()}
        j_params, st = j_opt.update(j_grads, st, j_params)
    for p_t, (k, p_j) in zip(t_params, j_params.items()):
        np.testing.assert_allclose(np.asarray(p_j),
                                   p_t.detach().numpy(), atol=1e-3)


def test_adam_scale_divides_grads():
    params = {"w": jnp.asarray([1.0, 2.0, 3.0])}
    opt = FusedAdam(lr=1e-2)
    st = opt.init(params)
    g = {"w": jnp.asarray([128.0, 256.0, -128.0])}
    p1, _ = opt.step(params, st, g, scale=128.0)
    p2, _ = opt.step(params, st, {"w": g["w"] / 128.0}, scale=1.0)
    np.testing.assert_allclose(np.asarray(p1["w"]), np.asarray(p2["w"]),
                               rtol=1e-6)


def test_adam_max_grad_norm_clips():
    # clipping folds into combined_scale (reference fused_adam.py:98-104):
    # stepping with max_grad_norm must equal stepping on grads pre-divided
    # by the clip factor ((norm/scale)+1e-6)/max_norm
    params = {"w": jnp.zeros((4,))}
    opt = FusedAdam(lr=1.0, max_grad_norm=1.0, bias_correction=False)
    st = opt.init(params)
    g = {"w": jnp.full((4,), 100.0)}  # norm 200 >> max_norm 1
    p1, _ = opt.step(params, st, g)
    clip = (200.0 + 1e-6) / 1.0
    opt2 = FusedAdam(lr=1.0, bias_correction=False)
    p2, _ = opt2.step(params, opt2.init(params), {"w": g["w"] / clip})
    np.testing.assert_allclose(np.asarray(p1["w"]), np.asarray(p2["w"]),
                               rtol=1e-6)


def test_adam_half_output_params():
    params = {"w": jnp.asarray([1.0, 2.0])}
    opt = FusedAdam(lr=0.1)
    st = opt.init(params)
    g = {"w": jnp.asarray([0.5, -0.5])}
    new_p, _, half = opt.step(params, st, g,
                              output_params_dtype=jnp.bfloat16)
    assert half.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        np.asarray(half, np.float32),
        np.asarray(jnp.concatenate([new_p["w"]])).astype(np.float32),
        rtol=1e-2)


def test_lamb_trust_ratio_step():
    params = {"a": jnp.ones((8,)), "b": jnp.full((4,), 2.0)}
    opt = FusedLAMB(lr=0.1, weight_decay=0.0, max_grad_norm=0.0)
    st = opt.init(params)
    grads = {"a": jnp.full((8,), 0.5), "b": jnp.full((4,), -0.25)}
    new_p, st2 = opt.update(grads, st, params)
    assert int(st2.step) == 1
    # after one step update direction == sign(grad): p decreases for a
    assert np.all(np.asarray(new_p["a"]) < 1.0)
    assert np.all(np.asarray(new_p["b"]) > 2.0)
    # trust ratio: ||p||/||update|| scales the step
    for k in ("a", "b"):
        assert np.all(np.isfinite(np.asarray(new_p[k])))


def test_lamb_zero_param_norm_uses_unit_ratio():
    params = {"a": jnp.zeros((4,))}
    opt = FusedLAMB(lr=0.1, weight_decay=0.0)
    st = opt.init(params)
    grads = {"a": jnp.ones((4,))}
    new_p, _ = opt.update(grads, st, params)
    assert np.all(np.isfinite(np.asarray(new_p["a"])))
    assert np.all(np.asarray(new_p["a"]) != 0.0)


def test_fp16_optimizer_skips_on_overflow():
    params = {"w": jnp.asarray([1.0, 2.0], jnp.float16)}
    fo = FP16_Optimizer(FusedAdam(lr=0.1), dynamic_loss_scale=True)
    st = fo.init(params)
    scale0 = float(st.scaler.loss_scale)
    bad = {"w": jnp.asarray([jnp.inf, 1.0], jnp.float16)}
    new_p, st2, info = fo.step(params, st, bad)
    assert float(info["found_inf"]) == 1.0
    np.testing.assert_array_equal(np.asarray(new_p["w"], np.float32),
                                  np.asarray(params["w"], np.float32))
    assert float(st2.scaler.loss_scale) == scale0 / 2
    good = {"w": jnp.asarray([0.5, -0.5], jnp.float16)}
    new_p, st3, info = fo.step(params, st2, good)
    assert float(info["found_inf"]) == 0.0
    assert not np.allclose(np.asarray(new_p["w"], np.float32),
                           np.asarray(params["w"], np.float32))


def test_fp16_optimizer_masters_stay_fp32():
    params = {"w": jnp.asarray([1.0, 2.0], jnp.float16)}
    fo = FP16_Optimizer(FusedAdam(lr=0.01), static_loss_scale=128.0)
    st = fo.init(params)
    assert st.masters["w"].dtype == jnp.float32

    def loss_fn(p):
        return jnp.sum(p["w"].astype(jnp.float32) ** 2)

    loss, grads = fo.backward(loss_fn, params, st)
    # grads are scaled by 128
    np.testing.assert_allclose(np.asarray(grads["w"], np.float32),
                               128.0 * 2 * np.asarray([1.0, 2.0]), rtol=1e-2)
    new_p, st2, info = fo.step(params, st, grads)
    assert new_p["w"].dtype == jnp.float16
    assert float(info["found_inf"]) == 0.0


def test_flat_masters_nonfloat_leaf_roundtrip():
    """Flat-master fast path with a non-float leaf in the params tree:
    the int leaf passes through updates untouched and masters_tree /
    master_params yield None for it instead of crashing."""
    from apex_tpu import amp
    from apex_tpu.amp._process_optimizer import FlatMasters
    import apex_tpu.nn as nn

    class M(nn.Module):
        def forward(self, params, x):
            return x * params["w"].sum()

    model, opt = amp.initialize(M(), FusedAdam(lr=0.1), opt_level="O2",
                                verbosity=0)
    params = {"w": jnp.ones((4,), jnp.bfloat16),
              "idx": jnp.arange(3, dtype=jnp.int32)}
    st = opt.init(params)
    assert isinstance(st.masters, FlatMasters)
    grads = {"w": jnp.ones((4,), jnp.bfloat16),
             "idx": jnp.zeros((3,), jnp.int32)}
    new_p, new_st, info = opt.step(params, st, grads)
    assert new_p["idx"].dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(new_p["idx"]), [0, 1, 2])
    assert new_p["w"].dtype == jnp.bfloat16
    assert not np.allclose(np.asarray(new_p["w"], np.float32),
                           np.asarray(params["w"], np.float32))
    mt = opt.masters_tree(new_st)
    assert mt["idx"] is None and mt["w"].dtype == jnp.float32


# -- the gradient as segments, unscaled and widened in the kernel -------------
def _segmented(seed, n_half, n_f32, scale):
    from apex_tpu.optimizers import GradSegments
    rng = np.random.RandomState(seed)
    n = n_half + n_f32
    p, m = (jnp.asarray(rng.randn(n), jnp.float32) for _ in range(2))
    v = jnp.asarray(rng.rand(n), jnp.float32)
    g = jnp.asarray(rng.randn(n) * scale, jnp.float32)
    half = g[:n_half].astype(jnp.bfloat16)
    whole = jnp.concatenate([half.astype(jnp.float32), g[n_half:]])
    parts = tuple(x for x in (half, g[n_half:]) if x.shape[0])
    return p, m, v, GradSegments(parts), whole


@pytest.mark.parametrize("mode", ["jnp", "pallas"])
@pytest.mark.parametrize("n_half,n_f32", [(2048, 1024), (2048, 0), (0, 1024)])
def test_adam_on_gradient_segments_equals_the_whole_float32_buffer(
        n_half, n_f32, mode, monkeypatch):
    from apex_tpu.optimizers.fused_adam import AdamState
    monkeypatch.setenv("APEX_TPU_DISABLE_PALLAS", "1" if mode == "jnp" else "0")
    monkeypatch.setenv("APEX_TPU_FORCE_PALLAS", "0" if mode == "jnp" else "1")
    scale = 128.0
    p, m, v, segments, whole = _segmented(7, n_half, n_f32, scale)
    opt = FusedAdam(lr=1e-2, weight_decay=0.01)
    state = AdamState(step=jnp.asarray(4, jnp.int32), m=m, v=v)
    with jax.disable_jit():
        got = opt.step(p, state, segments, scale=scale,
                       output_params_dtype=jnp.bfloat16)
        want = opt.step(p, state, whole, scale=scale,
                        output_params_dtype=jnp.bfloat16)
    for a, b in zip(jax.tree_util.tree_leaves(got[:2]),
                    jax.tree_util.tree_leaves(want[:2])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # the half copy covers the first piece, the elements the whole one starts with
    first = segments.parts[0].shape[0]
    assert got[2].shape == (first,) and got[2].dtype == jnp.bfloat16
    np.testing.assert_array_equal(np.asarray(got[2], np.float32),
                                  np.asarray(want[2][:first], np.float32))


def test_adam_clips_by_the_norm_over_all_segments():
    from apex_tpu.optimizers.fused_adam import AdamState
    p, m, v, segments, whole = _segmented(8, 2048, 1024, 64.0)
    opt = FusedAdam(lr=1e-2, max_grad_norm=0.25)
    state = AdamState(step=jnp.zeros((), jnp.int32), m=m, v=v)
    got = opt.step(p, state, segments, scale=64.0)
    want = opt.step(p, state, whole, scale=64.0)
    unclipped = FusedAdam(lr=1e-2).step(p, state, segments, scale=64.0)
    assert not np.allclose(np.asarray(got[0]), np.asarray(unclipped[0]))
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6)


def test_adam_refuses_a_segment_off_the_block_boundaries(monkeypatch):
    from apex_tpu.ops.pallas_adam import fused_adam
    monkeypatch.setenv("APEX_TPU_DISABLE_PALLAS", "0")
    monkeypatch.setenv("APEX_TPU_FORCE_PALLAS", "1")
    p = jnp.zeros((4096,), jnp.float32)
    g = jnp.zeros((1024,), jnp.bfloat16)
    fused_adam(p, p, p, g, 1e-2, 1.0, 0.9, 0.999, 1e-8, False, 0.0, None, 1024)
    with pytest.raises(ValueError, match="must lie on blocks"):
        fused_adam(p, p, p, g, 1e-2, 1.0, 0.9, 0.999, 1e-8, False, 0.0, None, 1000)
