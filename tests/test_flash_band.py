"""The causal band of the flash kernels (``flash_attention(window=)``),
interpreted on the CPU: forward and both gradients against the dense band for
lengths and windows that are no multiples of the block, the streamed axis cut
to the band's blocks, ``window=None`` the program it was, the dispatch in
``dot_product_attention`` and in the Llama attention; and, compiled for a
described v5e, the kernels at the widths of the benchmark's decoder cell."""

import math

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from apex_tpu.ops import pallas_flash_attention as pfa
from apex_tpu.transformer import attention


def _dense(q, k, v, window):
    T = q.shape[2]
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / math.sqrt(q.shape[-1])
    i, j = jnp.arange(T)[:, None], jnp.arange(T)[None]
    see = (j <= i) if window is None else (j <= i) & (j > i - window)
    return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(jnp.where(see, s, -1e30), -1), v)


def _operands(T, seed=0, D=32):
    ks = jax.random.split(jax.random.PRNGKey(seed + T), 4)
    return [jax.random.normal(k, (1, 2, T, D), jnp.float32) for k in ks]


# (T, W): T and W off the block (128 here), a window inside one block, one
# wider than the sequence, one of a single key
SHAPES = [(300, 100), (700, 200), (384, 128), (640, 1000), (256, 1), (1300, 515)]


@pytest.mark.parametrize("T,W", SHAPES)
def test_banded_forward_matches_the_dense_band(T, W):
    q, k, v, _ = _operands(T)
    got = pfa.flash_attention(q, k, v, causal=True, window=W)
    np.testing.assert_allclose(np.asarray(got), np.asarray(_dense(q, k, v, W)), atol=3e-6)


@pytest.mark.parametrize("T,W", SHAPES[:4])
def test_banded_gradients_match_the_dense_band(T, W):
    q, k, v, do = _operands(T)
    got = jax.grad(lambda *a: jnp.sum(pfa.flash_attention(*a, causal=True, window=W) * do),
                   (0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: jnp.sum(_dense(*a, W) * do), (0, 1, 2))(q, k, v)
    for name, g, w in zip("qkv", got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=1e-5, err_msg=f"d{name}")


def test_band_composes_with_a_key_padding_mask_and_segments():
    q, k, v, _ = _operands(300, seed=3)
    kv_mask = jnp.arange(300)[None] < 270
    seg = (jnp.arange(300)[None] >= 120).astype(jnp.int32)
    got = pfa.flash_attention(q, k, v, causal=True, window=64, kv_mask=kv_mask, segment_ids=seg)
    i, j = jnp.arange(300)[:, None], jnp.arange(300)[None]
    see = (j <= i) & (j > i - 64) & kv_mask[0][None] & (seg[0][:, None] == seg[0][None])
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / math.sqrt(32)
    want = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(jnp.where(see, s, -1e30), -1), v)
    rows = np.asarray(see.any(-1))              # a row with no key left is zero in flash
    np.testing.assert_allclose(np.asarray(got)[:, :, rows], np.asarray(want)[:, :, rows],
                               atol=3e-6)


def test_window_none_is_the_program_it_was_and_a_whole_band_equals_it_bitwise():
    q, k, v, do = _operands(384, seed=5)
    plain = jax.make_jaxpr(lambda *a: pfa.flash_attention(*a, causal=True))(q, k, v)
    none = jax.make_jaxpr(lambda *a: pfa.flash_attention(*a, causal=True, window=None))(q, k, v)
    assert str(plain) == str(none)
    f = lambda w: jax.value_and_grad(
        lambda *a: jnp.sum(pfa.flash_attention(*a, causal=True, window=w) * do), (0, 1, 2))(q, k, v)
    (o0, g0), (o1, g1) = f(None), f(384)        # the same blocks in the same order
    assert float(o0) == float(o1)
    for a, b in zip(g0, g1):
        assert (np.asarray(a) == np.asarray(b)).all()


@pytest.mark.parametrize("T,W,blocks", [(8192, 512, 2), (8192, 513, 2), (8192, 514, 3), (1024, 1, 1),
                                        (1024, 4096, 2)])
def test_streamed_axis_covers_the_band_only(T, W, blocks):
    """The grid's last axis: 2 of 16 blocks for the decoder cell's layers."""
    blk = pfa._block_for(T)
    assert pfa._band_blocks(W, blk, T // blk) == blocks
    q = jax.ShapeDtypeStruct((1, T, 128), jnp.bfloat16)
    text = str(jax.make_jaxpr(lambda q, k, v: pfa._fwd(
        q, k, v, None, None, None, None, 0.1, True, 1, 0.0, W))(q, q, q))
    assert f"grid=(1, {T // blk}, {blocks})" in text.replace("\n", " ")


def test_window_needs_causal():
    q, k, v, _ = _operands(256)
    with pytest.raises(ValueError, match="causal"):
        pfa.flash_attention(q, k, v, window=8)
    with pytest.raises(ValueError, match="causal"):
        attention.dot_product_attention(q, k, v, window=8)


def test_dot_product_attention_keeps_a_window_on_the_flash_path(monkeypatch):
    q, k, v, _ = _operands(256, seed=7)
    dense = attention.dot_product_attention(q, k, v, causal=True, window=40)
    np.testing.assert_allclose(np.asarray(dense), np.asarray(_dense(q, k, v, 40)), atol=3e-6)
    monkeypatch.setenv("APEX_TPU_FORCE_PALLAS", "1")
    monkeypatch.delenv("APEX_TPU_DISABLE_PALLAS", raising=False)
    paths = []
    attention.set_path_hook(paths.append)
    try:
        flash = attention.dot_product_attention(q, k, v, causal=True, window=40)
    finally:
        attention.set_path_hook(None)
    assert paths == ["flash"]
    np.testing.assert_allclose(np.asarray(flash), np.asarray(dense), atol=3e-6)


def test_llama_sliding_window_trains_on_the_flash_path(monkeypatch):
    from apex_tpu import models
    cfg = dict(vocab_size=64, hidden_size=32, intermediate_size=64, num_hidden_layers=1,
               num_attention_heads=4, num_key_value_heads=2, max_position_embeddings=160)
    ids = jnp.asarray(np.random.RandomState(0).randint(0, 64, (1, 160)))
    windowed = models.Llama(models.LlamaConfig(sliding_window=24, **cfg))
    params, _ = windowed.init(jax.random.PRNGKey(0))
    want = jax.grad(lambda p: windowed.loss(p, ids))(params)
    monkeypatch.setenv("APEX_TPU_FORCE_PALLAS", "1")
    monkeypatch.delenv("APEX_TPU_DISABLE_PALLAS", raising=False)
    paths = []
    attention.set_path_hook(paths.append)
    try:
        got = jax.grad(lambda p: windowed.loss(p, ids))(params)
    finally:
        attention.set_path_hook(None)
    assert set(paths) == {"flash"}
    for g, w in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=2e-5)


# -- compiled for a described (not attached) v5e -------------------------------

@pytest.fixture(scope="module")
def one_chip():
    import os
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:                      # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def for_the_chip(monkeypatch):
    """Kernels as the chip runs them (Mosaic, not the interpreter), compiled
    past the persistent cache, which cannot read such an entry back."""
    from apex_tpu.ops import dispatch
    monkeypatch.setattr(dispatch, "backend", lambda: "tpu")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.mark.parametrize("window,fetched", [(512, 2), (None, 16)])
def test_v5e_compiles_the_flash_kernels_at_the_decoder_cells_widths(one_chip, for_the_chip,
                                                                    window, fetched):
    x = jax.ShapeDtypeStruct((1, 8, 8192, 128), jnp.bfloat16, sharding=one_chip)

    def loss(q, k, v):
        return jnp.sum(pfa.flash_attention(q, k, v, causal=True, window=window)
                       .astype(jnp.float32))

    text = jax.jit(jax.grad(loss, (0, 1, 2))).lower(x, x, x).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 3
    for kernel in ("flash_fwd", "flash_dq", "flash_dkv"):
        assert kernel in text
    assert "f32[8,8192,8192]" not in text and "bf16[8,8192,8192]" not in text
    del fetched


def test_v5e_compiles_the_grouped_expert_products(one_chip, for_the_chip):
    from apex_tpu.parallel.expert_parallel import ExpertParallelMLP
    layer = ExpertParallelMLP(2048, 512, 256, capacity_factor=None, top_k=8,
                              expert_type="swiglu", router_type="sigmoid", routed_scaling=2.5,
                              experts_held=(0, 16), shared_hidden=512, row_buffer_factor=2.0)
    shapes = jax.eval_shape(lambda k: layer.init(k)[0], jax.random.PRNGKey(0))
    params = jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, jnp.float32 if s.ndim == 2 and s.shape[1] == 256
                                       else jnp.bfloat16, sharding=one_chip), shapes)
    x = jax.ShapeDtypeStruct((16384, 2048), jnp.bfloat16, sharding=one_chip)
    text = jax.jit(jax.grad(lambda p, x: jnp.sum(layer(p, x).astype(jnp.float32)))).lower(
        params, x).compile().as_text()
    assert "ragged-dot" in text
    # 16 384 tokens x 256 experts x any slots would be >= 4 M x slots elements
    assert "16384,256,16" not in text and "16384,16,16384" not in text
