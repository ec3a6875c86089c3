"""Pallas-kernel vs jnp-path parity — the L1 philosophy of the reference
(tests/L1/common/compare.py: extension path and Python path must agree)
applied at the kernel level, via interpret mode on CPU.

Marked slow: interpret mode executes the kernels element-by-element.
"""

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from apex_tpu.ops import dispatch
from apex_tpu.ops import pallas_multi_tensor as pk
from apex_tpu.ops import pallas_adam as pa
from apex_tpu.ops import pallas_layer_norm as pln
from apex_tpu.multi_tensor_apply import multi_tensor


@pytest.fixture(autouse=True)
def force_jnp_reference(monkeypatch):
    # the reference path must not dispatch to pallas while we compare
    monkeypatch.setenv("APEX_TPU_DISABLE_PALLAS", "1")
    yield


def test_kernels_available():
    assert dispatch.kernels_available()


def test_pallas_scale_matches_jnp():
    tree = {"a": jnp.asarray(np.random.RandomState(0).randn(777), jnp.float32),
            "b": jnp.asarray(np.random.RandomState(1).randn(33, 5),
                             jnp.float32)}
    ref, ref_flag = multi_tensor.multi_tensor_scale(tree, 0.25)
    out, flag = pk.multi_tensor_scale(tree, 0.25)
    for k in tree:
        np.testing.assert_allclose(np.asarray(out[k]), np.asarray(ref[k]),
                                   rtol=1e-6)
    assert float(flag) == float(ref_flag) == 0.0


def test_pallas_scale_overflow_flag():
    x = np.ones(300, np.float32)
    x[123] = np.inf
    _, flag = pk.multi_tensor_scale([jnp.asarray(x)], 1.0)
    assert float(flag) == 1.0
    x[123] = np.nan
    _, flag = pk.multi_tensor_scale([jnp.asarray(x)], 1.0)
    assert float(flag) == 1.0


def test_pallas_axpby_matches_jnp():
    rng = np.random.RandomState(2)
    xt = [jnp.asarray(rng.randn(100), jnp.float32)]
    yt = [jnp.asarray(rng.randn(100), jnp.float32)]
    ref, _ = multi_tensor.multi_tensor_axpby(2.0, -0.5, xt, yt)
    out, flag = pk.multi_tensor_axpby(2.0, -0.5, xt, yt)
    np.testing.assert_allclose(np.asarray(out[0]), np.asarray(ref[0]),
                               rtol=1e-6)
    assert float(flag) == 0.0
    ybad = [jnp.asarray(np.array([np.nan] + [0.0] * 99, np.float32))]
    _, flag = pk.multi_tensor_axpby(1.0, 1.0, xt, ybad, arg_to_check=0)
    assert float(flag) == 0.0
    _, flag = pk.multi_tensor_axpby(1.0, 1.0, xt, ybad, arg_to_check=1)
    assert float(flag) == 1.0


def test_pallas_l2norm_matches_jnp():
    rng = np.random.RandomState(3)
    tree = [jnp.asarray(rng.randn(1000), jnp.float32),
            jnp.asarray(rng.randn(77), jnp.float32)]
    ref, _ = multi_tensor.multi_tensor_l2norm(tree)
    out, _ = pk.multi_tensor_l2norm(tree)
    np.testing.assert_allclose(float(out), float(ref), rtol=1e-6)


@pytest.mark.parametrize("n,grad_dtype", [
    (700, jnp.float32),       # 6 rows -> one 8-row block
    (700, jnp.bfloat16),      # bf16 grads in AND bf16 copy out through an
    (3000, jnp.bfloat16),     # 8- / 24-row block: under the (16, 128)
                              # bf16 tile — every ZeRO shard of a small
                              # model has this shape
    (70000, jnp.bfloat16),    # 547 rows -> two full 512-row blocks
])
def test_pallas_adam_matches_jnp(n, grad_dtype):
    rng = np.random.RandomState(4)
    p = jnp.asarray(rng.randn(n), jnp.float32)
    m = jnp.asarray(np.abs(rng.randn(n)) * 0.1, jnp.float32)
    v = jnp.asarray(np.abs(rng.randn(n)) * 0.01, jnp.float32)
    g = jnp.asarray(rng.randn(n), grad_dtype)
    args = dict(step_size=0.01, combined_scale=2.0, beta1=0.9, beta2=0.999,
                eps=1e-8, eps_inside_sqrt=False, weight_decay=0.01)
    # jnp reference (fused_adam._adam_kernel math)
    gs = g.astype(jnp.float32) / args["combined_scale"]
    rm = args["beta1"] * m + 0.1 * gs
    rv = args["beta2"] * v + 0.001 * gs * gs
    denom = jnp.sqrt(rv) + args["eps"]
    rp = p - args["step_size"] * (rm / denom + args["weight_decay"] * p)

    np_, nm, nv, half = pa.fused_adam(p, m, v, g, **args,
                                      half_dtype=jnp.bfloat16)
    # atol: a few of thousands of elements land near zero, where 1e-5
    # relative is below fp32 rounding of the three-term update
    for got, want in ((np_, rp), (nm, rm), (nv, rv)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-7)
    assert half.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(half, np.float32),
                               np.asarray(rp), rtol=1e-2, atol=1e-4)


@pytest.mark.parametrize("shape,n2", [((10, 96), 96), ((9, 99), 99),
                                      ((33, 256), 256)])
def test_pallas_layer_norm_fwd_bwd_matches_jnp(shape, n2):
    rng = np.random.RandomState(5)
    x = jnp.asarray(rng.randn(*shape), jnp.float32)
    w = jnp.asarray(rng.randn(n2), jnp.float32)
    b = jnp.asarray(rng.randn(n2), jnp.float32)
    eps = 1e-5

    # jnp reference (fused_layer_norm jnp path)
    x32 = x.astype(jnp.float32)
    mean_ref = jnp.mean(x32, axis=1)
    var = jnp.mean(jnp.square(x32), axis=1) - mean_ref ** 2
    inv_ref = 1.0 / jnp.sqrt(var + eps)
    y_ref = (x32 - mean_ref[:, None]) * inv_ref[:, None] * w[None] + b[None]

    y, mean, inv = pln.forward(x, w, b, eps)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref), atol=1e-5)
    np.testing.assert_allclose(np.asarray(mean), np.asarray(mean_ref),
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(inv), np.asarray(inv_ref),
                               atol=1e-4)

    dy = jnp.asarray(rng.randn(*shape), jnp.float32)
    xhat = (x32 - mean_ref[:, None]) * inv_ref[:, None]
    dy_g = dy * w[None]
    c1 = jnp.mean(dy_g, axis=1, keepdims=True)
    c2 = jnp.mean(dy_g * xhat, axis=1, keepdims=True)
    dx_ref = inv_ref[:, None] * (dy_g - c1 - xhat * c2)
    dw_ref = jnp.sum(dy * xhat, axis=0)
    db_ref = jnp.sum(dy, axis=0)

    dx, dw, db = pln.backward(dy, x, w, b, mean, inv)
    np.testing.assert_allclose(np.asarray(dx), np.asarray(dx_ref), atol=1e-5)
    np.testing.assert_allclose(np.asarray(dw), np.asarray(dw_ref), atol=1e-4)
    np.testing.assert_allclose(np.asarray(db), np.asarray(db_ref), atol=1e-4)


def test_layer_norm_large_mean_no_cancellation():
    # rows with mean >> std: E[x^2]-mean^2 would be catastrophically wrong
    rng = np.random.RandomState(7)
    x_np = (5000.0 + 0.01 * rng.randn(8, 256)).astype(np.float32)
    x = jnp.asarray(x_np)
    y, mean, inv = pln.forward(x, None, None, 1e-5)
    true_inv = 1.0 / np.sqrt(x_np.var(axis=1) + 1e-5)
    np.testing.assert_allclose(np.asarray(inv), true_inv, rtol=0.05)
    y_np = np.asarray(y)
    np.testing.assert_allclose(y_np.std(axis=1), 1.0, rtol=0.1)


def test_layer_norm_no_affine():
    x = jnp.asarray(np.random.RandomState(6).randn(4, 64), jnp.float32)
    y, mean, inv = pln.forward(x, None, None, 1e-5)
    dy = jnp.ones_like(x)
    dx, dw, db = pln.backward(dy, x, None, None, mean, inv)
    assert dw is None and db is None
    assert dx.shape == x.shape


@pytest.mark.parametrize("adam_w_mode", [True, False])
def test_pallas_lamb_matches_jnp(monkeypatch, adam_w_mode):
    from apex_tpu.optimizers import FusedLAMB
    rng = np.random.RandomState(3)
    params = {"w": jnp.asarray(rng.randn(37, 5), jnp.float32),
              "b": jnp.asarray(rng.randn(129), jnp.float32)}
    grads = {"w": jnp.asarray(rng.randn(37, 5), jnp.float32),
             "b": jnp.asarray(rng.randn(129), jnp.float32)}
    opt = FusedLAMB(lr=0.01, weight_decay=0.01, adam_w_mode=adam_w_mode)
    state = opt.init(params)

    ref_p, ref_s = opt.step(params, state, grads)          # jnp path
    ref_p2, _ = opt.step(ref_p, ref_s, grads)

    monkeypatch.setenv("APEX_TPU_DISABLE_PALLAS", "0")
    monkeypatch.setenv("APEX_TPU_FORCE_PALLAS", "1")
    out_p, out_s = opt.step(params, state, grads)          # pallas path
    out_p2, _ = opt.step(out_p, out_s, grads)

    for k in params:
        np.testing.assert_allclose(np.asarray(out_p[k]),
                                   np.asarray(ref_p[k]), rtol=1e-5,
                                   atol=1e-6)
        np.testing.assert_allclose(np.asarray(out_p2[k]),
                                   np.asarray(ref_p2[k]), rtol=1e-5,
                                   atol=1e-6)
    np.testing.assert_allclose(np.asarray(out_s.m.buf),
                               np.asarray(ref_s.m.buf), rtol=1e-5,
                               atol=1e-6)


def test_pallas_lamb_grad_clipping(monkeypatch):
    # grads above max_grad_norm are pre-scaled by norm/max_norm
    # (multi_tensor_lamb_stage_1.cu: clipped global-norm prescale)
    from apex_tpu.optimizers import FusedLAMB
    big = {"w": jnp.full((64,), 100.0, jnp.float32)}
    params = {"w": jnp.ones((64,), jnp.float32)}
    opt = FusedLAMB(lr=0.01, weight_decay=0.0, max_grad_norm=1.0)
    state = opt.init(params)
    ref_p, _ = opt.step(params, state, big)
    monkeypatch.setenv("APEX_TPU_DISABLE_PALLAS", "0")
    monkeypatch.setenv("APEX_TPU_FORCE_PALLAS", "1")
    out_p, _ = opt.step(params, state, big)
    np.testing.assert_allclose(np.asarray(out_p["w"]),
                               np.asarray(ref_p["w"]), rtol=1e-5)


# ---------------------------------------------------------------------------
# BatchNorm apply: jnp on every backend
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("train", [True, False], ids=["train", "eval"])
def test_bn_apply_lowers_with_no_kernel_under_force_pallas(monkeypatch, train):
    """The chip's gating, which APEX_TPU_FORCE_PALLAS=1 reproduces here:
    a BatchNorm2d forward and backward dispatch no Pallas kernel (XLA
    fuses the scale+shift; the standalone kernel lost on the chip), while
    the same switch does send LayerNorm through its kernel."""
    from apex_tpu import nn
    from apex_tpu.normalization import FusedLayerNorm
    monkeypatch.setenv("APEX_TPU_FORCE_PALLAS", "1")
    monkeypatch.delenv("APEX_TPU_DISABLE_PALLAS", raising=False)
    bn = nn.BatchNorm2d(6)
    params, state = bn.init(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 6, 8, 8))

    def loss(p, x):
        out, _ = bn.apply(p, x, state=state, train=train)
        return jnp.sum(out ** 2)

    jaxpr = str(jax.make_jaxpr(jax.grad(loss, argnums=(0, 1)))(params, x))
    assert "pallas_call" not in jaxpr
    # the switch is live: a kernel the chip does dispatch is there
    ln = FusedLayerNorm(32)
    lp, _ = ln.init(jax.random.PRNGKey(2))
    ln_jaxpr = str(jax.make_jaxpr(
        lambda v: ln.apply(lp, v))(jnp.ones((8, 32))))
    assert "pallas_call" in ln_jaxpr



# ---------------------------------------------------------------------------
# fused flash attention (pallas_flash_attention)
# ---------------------------------------------------------------------------

def _dense_attn(q, k, v, causal):
    import math
    D = q.shape[-1]
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) / math.sqrt(D)
    if causal:
        T = q.shape[2]
        m = jnp.arange(T)[:, None] >= jnp.arange(T)[None, :]
        s = jnp.where(m[None, None], s, -1e30)
    p = jax.nn.softmax(s, -1)
    return jnp.einsum("bhqk,bhkd->bhqd", p,
                      v.astype(jnp.float32)).astype(q.dtype)


@pytest.mark.slow
@pytest.mark.parametrize("shape", [(2, 2, 64, 16), (1, 3, 130, 24)])
@pytest.mark.parametrize("causal", [False, True])
def test_flash_attention_fwd_bwd_matches_dense(shape, causal):
    from apex_tpu.ops.pallas_flash_attention import flash_attention
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q, k, v = (jax.random.normal(kk, shape, jnp.float32) for kk in ks)
    ref = _dense_attn(q, k, v, causal)
    out = flash_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)

    g_ref = jax.grad(lambda t: jnp.sum(_dense_attn(*t, causal) ** 2)
                     )((q, k, v))
    g_out = jax.grad(lambda t: jnp.sum(
        flash_attention(*t, causal=causal) ** 2))((q, k, v))
    for a, b, name in zip(g_ref, g_out, "qkv"):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   rtol=5e-4, atol=5e-4, err_msg=name)


def test_flash_attention_bf16():
    from apex_tpu.ops.pallas_flash_attention import flash_attention
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q, k, v = (jax.random.normal(kk, (2, 2, 64, 32), jnp.bfloat16)
               for kk in ks)
    ref = _dense_attn(q, k, v, True).astype(jnp.float32)
    raw = flash_attention(q, k, v, causal=True)
    assert raw.dtype == jnp.bfloat16  # kernel preserves the input dtype
    np.testing.assert_allclose(np.asarray(raw, np.float32),
                               np.asarray(ref), rtol=3e-2, atol=3e-2)


def test_dot_product_attention_dispatches_to_flash(monkeypatch):
    """With pallas forced, the mask-free 4-D path must route through the
    flash kernel and agree with the dense jnp path."""
    from apex_tpu.transformer import dot_product_attention
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    q, k, v = (jax.random.normal(kk, (2, 2, 64, 16)) for kk in ks)

    ref = dot_product_attention(q, k, v, causal=True)  # jnp (fixture)
    monkeypatch.setenv("APEX_TPU_FORCE_PALLAS", "1")
    monkeypatch.delenv("APEX_TPU_DISABLE_PALLAS", raising=False)
    called = {}
    from apex_tpu.ops import pallas_flash_attention as pfa
    orig = pfa.flash_attention

    def spy(*a, **kw):
        called["yes"] = True
        return orig(*a, **kw)

    monkeypatch.setattr(pfa, "flash_attention", spy)
    out = dot_product_attention(q, k, v, causal=True)
    assert called.get("yes"), "flash path not taken"
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_flash_path_respects_amp_policy(monkeypatch):
    """Under an O1 cast policy the flash branch must return the same half
    dtype the dense whitelisted-matmul path does."""
    from apex_tpu.amp import policy as pol
    from apex_tpu.transformer import dot_product_attention
    ks = jax.random.split(jax.random.PRNGKey(4), 3)
    q, k, v = (jax.random.normal(kk, (1, 2, 64, 16)) for kk in ks)

    with pol.use_policy(pol.CastPolicy(jnp.bfloat16)):
        dense = dot_product_attention(q, k, v, causal=True)
        monkeypatch.setenv("APEX_TPU_FORCE_PALLAS", "1")
        monkeypatch.delenv("APEX_TPU_DISABLE_PALLAS", raising=False)
        flash = dot_product_attention(q, k, v, causal=True)
    assert dense.dtype == flash.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(flash, np.float32),
                               np.asarray(dense, np.float32),
                               rtol=3e-2, atol=3e-2)


def _dense_attn_kvmask(q, k, v, causal, kv_mask):
    import math
    D = q.shape[-1]
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) / math.sqrt(D)
    s = jnp.where(kv_mask[:, None, None, :], s, -1e30)
    if causal:
        T = q.shape[2]
        m = jnp.arange(T)[:, None] >= jnp.arange(T)[None, :]
        s = jnp.where(m[None, None], s, -1e30)
    p = jax.nn.softmax(s, -1)
    return jnp.einsum("bhqk,bhkd->bhqd", p,
                      v.astype(jnp.float32)).astype(q.dtype)


# tier-1 budget (PR 2): slowest tests by --durations carry the slow
# marker so a cold `-m 'not slow'` run fits the 870 s timeout
@pytest.mark.slow
@pytest.mark.parametrize("causal", [False, True])
def test_flash_attention_kv_mask_matches_dense(causal):
    """Key-padding mask streamed through the kernel == dense masked
    attention, forward and backward (BERT-style variable-length batch)."""
    from apex_tpu.ops.pallas_flash_attention import flash_attention
    B, H, T, D = 2, 2, 160, 16
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q, k, v = (jax.random.normal(kk, (B, H, T, D), jnp.float32)
               for kk in ks)
    lengths = jnp.array([T, T - 37])
    kv_mask = jnp.arange(T)[None, :] < lengths[:, None]

    ref = _dense_attn_kvmask(q, k, v, causal, kv_mask)
    out = flash_attention(q, k, v, causal=causal, kv_mask=kv_mask)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)

    g_ref = jax.grad(lambda t: jnp.sum(
        _dense_attn_kvmask(*t, causal, kv_mask) ** 2))((q, k, v))
    g_out = jax.grad(lambda t: jnp.sum(
        flash_attention(*t, causal=causal, kv_mask=kv_mask) ** 2))((q, k, v))
    for a, b, name in zip(g_ref, g_out, "qkv"):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   rtol=5e-4, atol=5e-4, err_msg=name)
    # masked keys must receive zero dk/dv
    for g, name in ((g_out[1], "dk"), (g_out[2], "dv")):
        tail = np.asarray(g)[1, :, T - 37:, :]
        np.testing.assert_array_equal(tail, np.zeros_like(tail),
                                      err_msg=name)


@pytest.mark.slow
def test_flash_attention_kv_mask_fully_masked_row():
    """A batch entry whose keys are ALL masked yields zero output and
    zero/finite grads (dense softmax would emit a uniform average)."""
    from apex_tpu.ops.pallas_flash_attention import flash_attention
    B, H, T, D = 2, 1, 128, 16
    ks = jax.random.split(jax.random.PRNGKey(4), 3)
    q, k, v = (jax.random.normal(kk, (B, H, T, D), jnp.float32)
               for kk in ks)
    kv_mask = jnp.stack([jnp.ones((T,), bool), jnp.zeros((T,), bool)])
    out = flash_attention(q, k, v, kv_mask=kv_mask)
    np.testing.assert_array_equal(np.asarray(out[1]),
                                  np.zeros_like(np.asarray(out[1])))
    g = jax.grad(lambda t: jnp.sum(
        flash_attention(*t, kv_mask=kv_mask) ** 2))((q, k, v))
    for arr in g:
        assert np.all(np.isfinite(np.asarray(arr)))
        np.testing.assert_array_equal(np.asarray(arr[1]),
                                      np.zeros_like(np.asarray(arr[1])))


def test_dot_product_attention_kv_mask_dispatches_to_flash(monkeypatch):
    """A (B, 1, 1, Tk) padding mask must stay on the flash path and agree
    with the dense path."""
    from apex_tpu.transformer import dot_product_attention
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    B, H, T, D = 2, 2, 64, 16
    q, k, v = (jax.random.normal(kk, (B, H, T, D)) for kk in ks)
    kv_mask = (jnp.arange(T)[None, :] < jnp.array([T, T - 11])[:, None])
    mask4 = kv_mask[:, None, None, :]

    ref = dot_product_attention(q, k, v, mask4, causal=True)  # jnp path
    monkeypatch.setenv("APEX_TPU_FORCE_PALLAS", "1")
    monkeypatch.delenv("APEX_TPU_DISABLE_PALLAS", raising=False)
    called = {}
    import apex_tpu.ops.pallas_flash_attention as pfa
    orig = pfa.flash_attention

    def spy(*a, **kw):
        called["kv_mask"] = kw.get("kv_mask")
        return orig(*a, **kw)

    monkeypatch.setattr(pfa, "flash_attention", spy)
    out = dot_product_attention(q, k, v, mask4, causal=True)
    assert called.get("kv_mask") is not None, "flash path not taken"
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


def _dense_attn_dropout(q, k, v, causal, seed, rate):
    """Dense reference applying the EXACT mask the kernel generates: the
    same _keep_unit counter hash over absolute (batch*head, qpos, kpos),
    undropped softmax normalizer, dropped+rescaled value accumulation."""
    import math
    from apex_tpu.ops.pallas_flash_attention import _keep_unit
    B, H, T, D = q.shape
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) / math.sqrt(D)
    if causal:
        m = jnp.arange(T)[:, None] >= jnp.arange(T)[None, :]
        s = jnp.where(m[None, None], s, -1e30)
    p = jax.nn.softmax(s, -1)
    bh = jnp.arange(B * H, dtype=jnp.int32).reshape(B, H, 1, 1)
    qpos = jnp.arange(T, dtype=jnp.int32).reshape(1, 1, T, 1)
    kpos = jnp.arange(T, dtype=jnp.int32).reshape(1, 1, 1, T)
    u = _keep_unit(jnp.int32(seed),
                   jnp.int32(seed) ^ jnp.int32(0x5555AAAA), bh, qpos, kpos)
    p = jnp.where(u >= rate, p, 0.0) / (1.0 - rate)
    return jnp.einsum("bhqk,bhkd->bhqd", p,
                      v.astype(jnp.float32)).astype(q.dtype)


@pytest.mark.slow
@pytest.mark.parametrize("causal", [False, True])
def test_flash_attention_dropout_matches_dense(causal):
    """In-kernel dropout == dense attention with the identical
    counter-hash mask, forward and backward (deterministic: same seed,
    same mask, everywhere)."""
    from apex_tpu.ops.pallas_flash_attention import flash_attention
    B, H, T, D = 2, 2, 160, 16
    rate, seed = 0.25, 1234
    ks = jax.random.split(jax.random.PRNGKey(6), 3)
    q, k, v = (jax.random.normal(kk, (B, H, T, D), jnp.float32)
               for kk in ks)

    ref = _dense_attn_dropout(q, k, v, causal, seed, rate)
    out = flash_attention(q, k, v, causal=causal, dropout_rate=rate,
                          dropout_seed=jnp.int32(seed))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)

    g_ref = jax.grad(lambda t: jnp.sum(
        _dense_attn_dropout(*t, causal, seed, rate) ** 2))((q, k, v))
    g_out = jax.grad(lambda t: jnp.sum(
        flash_attention(*t, causal=causal, dropout_rate=rate,
                        dropout_seed=jnp.int32(seed)) ** 2))((q, k, v))
    for a, b, name in zip(g_ref, g_out, "qkv"):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   rtol=1e-3, atol=1e-3, err_msg=name)


@pytest.mark.slow
def test_flash_attention_dropout_statistics():
    """Mask statistics: drop fraction ~= rate, different seeds give
    different masks, same seed is bitwise deterministic, and
    dropout_rate=0 is exactly the old path."""
    from apex_tpu.ops.pallas_flash_attention import (_keep_unit,
                                                     flash_attention)
    u = _keep_unit(jnp.int32(7), jnp.int32(11), jnp.int32(3),
                   jnp.arange(512, dtype=jnp.int32)[:, None],
                   jnp.arange(512, dtype=jnp.int32)[None, :])
    frac = float(jnp.mean((u < 0.25).astype(jnp.float32)))
    assert abs(frac - 0.25) < 0.01, frac          # 512^2 samples
    # uniformity beyond the threshold: mean ~ 0.5
    assert abs(float(jnp.mean(u)) - 0.5) < 0.01

    ks = jax.random.split(jax.random.PRNGKey(8), 3)
    q, k, v = (jax.random.normal(kk, (1, 2, 128, 16)) for kk in ks)
    o1 = flash_attention(q, k, v, dropout_rate=0.5,
                         dropout_seed=jnp.int32(1))
    o1b = flash_attention(q, k, v, dropout_rate=0.5,
                          dropout_seed=jnp.int32(1))
    o2 = flash_attention(q, k, v, dropout_rate=0.5,
                         dropout_seed=jnp.int32(2))
    np.testing.assert_array_equal(np.asarray(o1), np.asarray(o1b))
    assert float(jnp.max(jnp.abs(o1 - o2))) > 1e-3
    o0 = flash_attention(q, k, v, dropout_rate=0.0)
    o_plain = flash_attention(q, k, v)
    np.testing.assert_array_equal(np.asarray(o0), np.asarray(o_plain))


@pytest.mark.slow
def test_dot_product_attention_dropout_stays_on_flash(monkeypatch):
    """Train-mode attention dropout must ride the flash kernel (not fall
    to dense), drop roughly the configured fraction, and keep the
    no-dropout eval path unchanged."""
    import apex_tpu.ops.pallas_flash_attention as pfa
    from apex_tpu import nn
    from apex_tpu.transformer import MultiheadAttention

    monkeypatch.setenv("APEX_TPU_FORCE_PALLAS", "1")
    monkeypatch.delenv("APEX_TPU_DISABLE_PALLAS", raising=False)
    called = {}
    orig = pfa.flash_attention

    def spy(*a, **kw):
        called["dropout_rate"] = kw.get("dropout_rate")
        called["seed"] = kw.get("dropout_seed")
        return orig(*a, **kw)

    monkeypatch.setattr(pfa, "flash_attention", spy)

    mha = MultiheadAttention(16, 2, dropout=0.0)
    mha.drop.rate = 0.0
    # attention-probability dropout lives in dot_product_attention
    from apex_tpu.transformer import attention as attn_mod
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 64, 16))
    params, _ = mha.init(jax.random.PRNGKey(1))

    def fwd_train(p, x):
        q = jnp.moveaxis(
            mha.qkv(p["qkv"], x).reshape(2, 64, 3, 2, 8)[:, :, 0], 2, 1)
        return attn_mod.dot_product_attention(q, q, q, dropout_rate=0.5)

    # eval (no ctx): no dropout, flash taken
    out_eval = fwd_train(params, x)
    assert called.get("dropout_rate") == 0.0

    # train ctx (module apply context provides ctx.train + rng):
    class Wrap(nn.Module):
        def __init__(self):
            super().__init__()
            self.inner = mha
        def forward(self, p, x):
            q = jnp.moveaxis(self.inner.qkv(
                p["inner"]["qkv"], x).reshape(2, 64, 3, 2, 8)[:, :, 0], 2, 1)
            return attn_mod.dot_product_attention(q, q, q,
                                                  dropout_rate=0.5)

    w = Wrap()
    wp, _ = w.init(jax.random.PRNGKey(3))
    out_train, _ = nn.apply(w, wp, x, train=True,
                            rng=jax.random.PRNGKey(4))
    assert called.get("dropout_rate") == 0.5
    assert called.get("seed") is not None


@pytest.mark.slow
def test_flash_attention_dropout_bf16():
    from apex_tpu.ops.pallas_flash_attention import flash_attention
    ks = jax.random.split(jax.random.PRNGKey(12), 3)
    q, k, v = (jax.random.normal(kk, (1, 2, 128, 16), jnp.bfloat16)
               for kk in ks)
    out = flash_attention(q, k, v, causal=True, dropout_rate=0.3,
                          dropout_seed=jnp.int32(5))
    assert out.dtype == jnp.bfloat16
    arr = np.asarray(out, np.float32)
    assert np.all(np.isfinite(arr))
    # parity with the dense reference sharing the same hash (bf16 tol)
    ref = np.asarray(_dense_attn_dropout(q, k, v, True, 5, 0.3),
                     np.float32)
    np.testing.assert_allclose(arr, ref, rtol=3e-2, atol=3e-2)
    # dropout actually perturbs relative to the clean output
    clean = np.asarray(flash_attention(q, k, v, causal=True), np.float32)
    assert np.max(np.abs(arr - clean)) > 1e-3


def test_fits_vmem_dropout_flag():
    """The dropout working set costs two extra score-shaped tiles; the
    gate must be at least as strict with dropout as without."""
    from apex_tpu.ops.pallas_flash_attention import fits_vmem
    for T in (128, 512, 4096):
        for D in (64, 128, 256):
            assert (not fits_vmem(T, D, dropout=True)
                    or fits_vmem(T, D))
    # a discriminating point: base fits exactly at the budget, dropout
    # exceeds it — catches the accounting regressing to flag-blind
    assert fits_vmem(4096, 256) and not fits_vmem(4096, 256, dropout=True)


def _dense_attn_segments(q, k, v, causal, segment_ids):
    import math
    D = q.shape[-1]
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) / math.sqrt(D)
    seg = segment_ids[:, None, :, None] == segment_ids[:, None, None, :]
    s = jnp.where(seg, s, -1e30)
    if causal:
        T = q.shape[2]
        m = jnp.arange(T)[:, None] >= jnp.arange(T)[None, :]
        s = jnp.where(m[None, None], s, -1e30)
    p = jax.nn.softmax(s, -1)
    return jnp.einsum("bhqk,bhkd->bhqd", p,
                      v.astype(jnp.float32)).astype(q.dtype)


@pytest.mark.slow
@pytest.mark.parametrize("causal", [False, True])
def test_flash_attention_segment_ids_matches_dense(causal):
    """Packed-sequence masking: pairs attend only within equal segment
    ids, forward and backward — and cross-segment grads are exactly
    zero (information isolation between packed examples)."""
    from apex_tpu.ops.pallas_flash_attention import flash_attention
    B, H, T, D = 2, 2, 160, 16
    ks = jax.random.split(jax.random.PRNGKey(13), 3)
    q, k, v = (jax.random.normal(kk, (B, H, T, D), jnp.float32)
               for kk in ks)
    # three segments of uneven length per batch row
    bounds = np.array([[0, 50, 120, T], [0, 80, 100, T]])
    seg = np.zeros((B, T), np.int32)
    for b in range(B):
        for s_i in range(3):
            seg[b, bounds[b, s_i]:bounds[b, s_i + 1]] = s_i
    seg = jnp.asarray(seg)

    ref = _dense_attn_segments(q, k, v, causal, seg)
    out = flash_attention(q, k, v, causal=causal, segment_ids=seg)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)

    g_ref = jax.grad(lambda t: jnp.sum(
        _dense_attn_segments(*t, causal, seg) ** 2))((q, k, v))
    g_out = jax.grad(lambda t: jnp.sum(
        flash_attention(*t, causal=causal, segment_ids=seg) ** 2))((q, k, v))
    for a, b_, name in zip(g_ref, g_out, "qkv"):
        np.testing.assert_allclose(np.asarray(b_), np.asarray(a),
                                   rtol=5e-4, atol=5e-4, err_msg=name)

    # isolation: perturbing segment 0's v must not change segment 1's out
    v2 = v.at[:, :, :50, :].add(100.0)
    out2 = flash_attention(q, k, v2, causal=causal, segment_ids=seg)
    np.testing.assert_allclose(np.asarray(out2[0, :, 50:120]),
                               np.asarray(out[0, :, 50:120]),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.slow
def test_flash_attention_segment_ids_compose_kv_mask_dropout():
    """All three masking mechanisms compose in one call."""
    from apex_tpu.ops.pallas_flash_attention import flash_attention
    B, H, T, D = 1, 2, 128, 16
    ks = jax.random.split(jax.random.PRNGKey(14), 3)
    q, k, v = (jax.random.normal(kk, (B, H, T, D), jnp.float32)
               for kk in ks)
    seg = jnp.asarray(np.repeat([0, 1], T // 2)[None, :], jnp.int32)
    kvm = jnp.arange(T)[None, :] < (T - 17)
    out = flash_attention(q, k, v, causal=True, segment_ids=seg,
                          kv_mask=kvm, dropout_rate=0.2,
                          dropout_seed=jnp.int32(9))
    assert np.all(np.isfinite(np.asarray(out)))
    g = jax.grad(lambda t: jnp.sum(flash_attention(
        *t, causal=True, segment_ids=seg, kv_mask=kvm,
        dropout_rate=0.2, dropout_seed=jnp.int32(9)) ** 2))((q, k, v))
    for arr in g:
        assert np.all(np.isfinite(np.asarray(arr)))
