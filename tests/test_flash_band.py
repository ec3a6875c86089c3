"""The causal band of the flash kernels (``flash_attention(window=)``),
interpreted on the CPU: forward and both gradients against the dense band for
lengths and windows that are no multiples of the block, the streamed axis cut
to the band's blocks, ``window=None`` the program it was, the dispatch in
``dot_product_attention`` and in the Llama attention; every path the kernels
separate (interior and edge pairs, the folded causal triangle, heads of 64 and
128 unpadded, ragged lengths, masks, segments and dropout) against one dense
reference, and the block-pair counters; the token-major operand form with
K/V once per K/V head against the same reference and against the head-major
call on the same data moved and repeated by hand; and, compiled for a
described v5e, the kernels at the widths of the benchmark's cells and the
decoder's attention layer around them; and a score head of a lane tile and a
half against a value head of one (192 / 128, latent attention), its trailing 64
as ``q_rope`` / ``k_rope``, one key head for all query heads or one a head."""

import math
import re

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from apex_tpu.ops import pallas_flash_attention as pfa
from apex_tpu.transformer import attention


def _dense(q, k, v, window):
    return _dense_all(q, k, v, causal=True, window=window)


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


@pytest.mark.parametrize("T,W,blk,blocks", [(8192, 512, 256, 3), (8192, 513, 256, 3), (8192, 514, 256, 4),
                                            (1024, 1, 256, 1), (8192, 1024, 256, 5), (8192, 1025, 512, 3),
                                            (1024, 4096, 512, 2)])
def test_streamed_axis_covers_the_band_only(T, W, blk, blocks):
    """The grid's last axis: 3 of 32 blocks for the decoder cell's layers, whose
    window of 512 selects blocks of 256, as a window of 1024 does (5 of 32: 1280
    keys a row, not 1536); past two 512-blocks, blocks of 512."""
    assert pfa._block_for(T, W) == blk and pfa._block_for(T) == 512
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


# -- every path the kernels separate, against one dense reference -------------

def _dense_all(q, k, v, causal=False, window=None, kv_mask=None, segment_ids=None,
               dropout_rate=0.0, dropout_seed=None):
    """Dense attention with every feature of ``flash_attention``: a row with no
    visible key is zero, and dropout applies the kernel's own counter hash."""
    B, H, T, D = q.shape
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / math.sqrt(D)
    i, j = jnp.arange(T)[:, None], jnp.arange(T)[None]
    see = jnp.ones((B, 1, T, T), bool)
    if causal:
        see = see & (j <= i)
    if window is not None:
        see = see & (j > i - window)
    if kv_mask is not None:
        see = see & kv_mask[:, None, None, :]
    if segment_ids is not None:
        see = see & (segment_ids[:, None, :, None] == segment_ids[:, None, None, :])
    p = jax.nn.softmax(jnp.where(see, s, -1e30), -1) * see.any(-1, keepdims=True)
    if dropout_rate:
        bh = jnp.arange(B * H, dtype=jnp.int32).reshape(B, H, 1, 1)
        seed = jnp.int32(dropout_seed)
        u = pfa._keep_unit(seed, seed ^ jnp.int32(0x5555AAAA), bh,
                           i.astype(jnp.int32)[None, None], j.astype(jnp.int32)[None, None])
        p = jnp.where(u >= dropout_rate, p, 0.0) / (1.0 - dropout_rate)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v)


def _half_masked(B, T):
    """Keys past 3/4 masked in entry 0; every key masked in the last entry."""
    m = np.ones((B, T), bool)
    m[0, 3 * T // 4:] = False
    m[-1] = B == 1 or False
    return jnp.asarray(m)


# name: (B, H, T, D, features).  Blocks are 512 rows but for T = 600 and 384
# (128) and under a window below 1024 (256): so 1536 folds an odd triangle of
# 3, 2048 an even one of 4, and the window of 512 has whole pairs inside it.
PATHS = {
    "causal-1536-interior-and-edge-odd-fold": (1, 2, 1536, 128, dict(causal=True)),
    "causal-2048-even-fold-d64": (1, 1, 2048, 64, dict(causal=True)),
    "window-512-at-2048-in-blocks-of-256": (1, 1, 2048, 128, dict(causal=True, window=512)),
    "window-1100-at-2048-off-the-block": (1, 1, 2048, 64, dict(causal=True, window=1100)),
    "window-300-at-600-ragged": (1, 2, 600, 32, dict(causal=True, window=300)),
    "whole-1024-all-interior-d128": (1, 2, 1024, 128, dict()),
    "whole-512-one-pair-d64": (2, 2, 512, 64, dict()),
    "ragged-600-padding-takes-the-edge": (1, 2, 600, 64, dict()),
    "ragged-600-causal": (1, 2, 600, 64, dict(causal=True)),
    "kv-mask-with-a-fully-masked-row": (2, 1, 256, 64, dict(kv_mask=_half_masked)),
    "dropout-segments-causal": (1, 2, 384, 32, dict(
        causal=True, dropout_rate=0.2, dropout_seed=77,
        segment_ids=lambda B, T: _thirds(B, T))),
}


def _path(name):
    B, H, T, D, features = PATHS[name]
    ks = jax.random.split(jax.random.PRNGKey(len(name) + T), 4)
    q, k, v, do = (jax.random.normal(kk, (B, H, T, D), jnp.float32) for kk in ks)
    features = {f: (x(B, T) if callable(x) else x) for f, x in features.items()}
    return (q, k, v), do, features


@pytest.mark.parametrize("name", PATHS)
def test_forward_matches_dense_on_every_path(name):
    (q, k, v), _, features = _path(name)
    np.testing.assert_allclose(np.asarray(pfa.flash_attention(q, k, v, **features)),
                               np.asarray(_dense_all(q, k, v, **features)), atol=5e-6)


@pytest.mark.parametrize("name", PATHS)
def test_gradients_match_dense_on_every_path(name):
    qkv, do, features = _path(name)
    got = jax.grad(lambda *a: jnp.sum(pfa.flash_attention(*a, **features) * do), (0, 1, 2))(*qkv)
    want = jax.grad(lambda *a: jnp.sum(_dense_all(*a, **features) * do), (0, 1, 2))(*qkv)
    for x, g, w in zip("qkv", got, want):
        assert np.isfinite(np.asarray(g)).all()
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=2e-5, err_msg=f"d{x}")


@pytest.mark.parametrize("features,T,heads", [
    (dict(causal=True), 512, 4),
    (dict(causal=True, window=200), 384, 4),
    (dict(), 384, 4),
    (dict(causal=True, kv_mask=True, segment_ids=True, dropout_rate=0.2, dropout_seed=5), 512, 2),
])
def test_heads_sharing_a_grid_step_equal_heads_taken_one_a_step(features, T, heads):
    """bf16 heads of one lane tile go 4 a step (2 beside mask operands, twice as
    many at small blocks); one head a batch entry goes alone through the same
    arithmetic: bitwise equal, forward and gradients, dropout's hash keyed on
    the same head."""
    B, H, D = 2, 4, 64
    blk = pfa._block_for(T, features.get("window"))
    assert pfa._heads_per_step(H, D, 2, "kv_mask" in features, blk) == heads
    assert pfa._heads_per_step(1, D, 2, False, 512) == pfa._heads_per_step(H, D, 4, False, 512) == 1
    assert pfa._heads_per_step(8, D, 2, False, 512) == 4 and pfa._heads_per_step(8, D, 2, False, 256) == 8
    ks = jax.random.split(jax.random.PRNGKey(9), 4)
    q, k, v, do = (jax.random.normal(kk, (B, H, T, D), jnp.bfloat16) for kk in ks)
    features = dict(features)
    per_entry, per_head = {}, {}
    if features.pop("kv_mask", False):
        per_entry["kv_mask"] = jnp.arange(T)[None] < jnp.asarray([[T - 40], [T]])
    if features.pop("segment_ids", False):
        per_entry["segment_ids"] = (jnp.arange(T)[None] >= jnp.asarray([[100], [250]])).astype(jnp.int32)
    per_head = {name: jnp.repeat(x, H, axis=0) for name, x in per_entry.items()}
    alone = lambda x: x.reshape(B * H, 1, T, D)

    def run(fold, extra):
        f = lambda *a: jnp.sum(pfa.flash_attention(*a, **features, **extra).astype(jnp.float32)
                               * fold(do).astype(jnp.float32))
        return jax.value_and_grad(f, (0, 1, 2))(fold(q), fold(k), fold(v))

    (o4, g4), (o1, g1) = run(lambda x: x, per_entry), run(alone, per_head)
    assert float(o4) == float(o1)
    for a, b in zip(g4, g1):
        assert (np.asarray(a, np.float32) == np.asarray(alone(b), np.float32).reshape(a.shape)).all()
    want = _dense_all(*(x.astype(jnp.float32) for x in (q, k, v)), **features, **per_entry)
    got = pfa.flash_attention(q, k, v, **features, **per_entry)
    np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(want), atol=4e-2)


# -- token-major operands, K/V once per K/V head --------------------------------

def _thirds(B, T):
    return jnp.asarray(np.repeat([0, 1, 2], -(-T // 3))[:T][None].repeat(B, 0))


# name: (B, H, Hkv, T, features); D = 128, a head being whole lane tiles
TOKEN_MAJOR = {
    "causal-rep1": (1, 2, 2, 1024, dict(causal=True)),
    "causal-rep6": (1, 6, 1, 1024, dict(causal=True)),
    "causal-rep8": (1, 8, 1, 1024, dict(causal=True)),
    "window-512-rep1": (1, 2, 2, 1024, dict(causal=True, window=512)),
    "window-512-rep6": (1, 6, 1, 1024, dict(causal=True, window=512)),
    "window-512-rep8-two-groups": (1, 16, 2, 1024, dict(causal=True, window=512)),
    "kv-mask-segments-rep6-ragged": (2, 12, 2, 600, dict(causal=True, kv_mask=_half_masked,
                                                         segment_ids=_thirds)),
    "whole-rep2-dropout": (1, 4, 2, 512, dict(dropout_rate=0.2, dropout_seed=11)),
}


def _token_major(name, dtype):
    B, H, Hkv, T, features = TOKEN_MAJOR[name]
    ks = jax.random.split(jax.random.PRNGKey(len(name) + T), 4)
    q, do = (jax.random.normal(kk, (B, T, H, 128), dtype) for kk in ks[::3])
    k, v = (jax.random.normal(kk, (B, T, Hkv, 128), dtype) for kk in ks[1:3])
    features = {f: (x(B, T) if callable(x) else x) for f, x in features.items()}
    return (q, k, v), do, features


def _by_hand(fn, features):
    """``fn`` (head-major, K/V at the query head count) on token-major, grouped
    operands: axes moved and K/V heads repeated around it."""
    def run(q, k, v):
        heads = lambda x: jnp.repeat(jnp.moveaxis(x, 2, 1), q.shape[2] // x.shape[2], axis=1)
        return jnp.moveaxis(fn(heads(q), heads(k), heads(v), **features), 1, 2)
    return run


def _value_and_grads(fn, qkv, do):
    f32 = lambda x: x.astype(jnp.float32)
    return jax.value_and_grad(lambda *a: jnp.sum(f32(fn(*a)) * f32(do)), (0, 1, 2))(*qkv)


@pytest.mark.parametrize("name", TOKEN_MAJOR)
def test_token_major_matches_dense_forward_and_gradients(name):
    """fp32 heads go one a step, so a K/V group's query heads are ``rep`` steps
    of the dk/dv grid's sequential axis adding into one accumulator."""
    qkv, do, features = _token_major(name, jnp.float32)
    got = pfa.flash_attention_token_major(*qkv, **features)
    want = _by_hand(_dense_all, features)(*qkv)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=5e-6)
    (_, gg), (_, gw) = (_value_and_grads(fn, qkv, do) for fn in (
        lambda *a: pfa.flash_attention_token_major(*a, **features), _by_hand(_dense_all, features)))
    for x, g, w in zip("qkv", gg, gw):
        assert g.shape == w.shape and np.isfinite(np.asarray(g)).all()
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=4e-5, err_msg=f"d{x}")


@pytest.mark.parametrize("name", TOKEN_MAJOR)
def test_token_major_grouped_equals_head_major_moved_and_repeated_by_hand(name):
    """bf16, so heads share steps (6 of a group of 6, 8 of 8 at blocks of 256):
    o and dq bitwise; dk and dv to bf16 rounding, because the kernel sums a
    group's heads in fp32 before its one cast where the repeat's transpose
    sums the casts."""
    qkv, do, features = _token_major(name, jnp.bfloat16)
    (o, (dq, dk, dv)), (o_, (dq_, dk_, dv_)) = (_value_and_grads(fn, qkv, do) for fn in (
        lambda *a: pfa.flash_attention_token_major(*a, **features),
        _by_hand(pfa.flash_attention, features)))
    f32 = lambda x: np.asarray(x, np.float32)
    assert float(o) == float(o_) and (f32(dq) == f32(dq_)).all()
    for x, g, w in (("k", dk, dk_), ("v", dv, dv_)):
        rep = qkv[0].shape[2] // g.shape[2]
        if rep == 1:
            assert (f32(g) == f32(w)).all(), f"d{x}"
        assert np.abs(f32(g) - f32(w)).max() <= 2.0 ** -6 * np.abs(f32(w)).max(), f"d{x}"
    # the same operands head-major, K/V still once per K/V head
    mv = lambda x: jnp.moveaxis(x, 2, 1)
    o_hm, g_hm = _value_and_grads(lambda q, k, v: mv(pfa.flash_attention(mv(q), mv(k), mv(v), **features)),
                                  qkv, do)
    assert float(o_hm) == float(o)
    for a, b in zip(g_hm, (dq, dk, dv)):
        assert (f32(a) == f32(b)).all()


@pytest.mark.parametrize("window", [None, 512])
def test_heads_of_one_group_sharing_a_step_equal_one_head_a_step(monkeypatch, window):
    """Any divisor of ``rep`` heads a step: the fp32 additions into dk/dv's
    accumulator come in the same order (streamed block, then head), bitwise."""
    B, H, Hkv, T = 1, 12, 2, 1024
    blk = pfa._block_for(T, window)
    assert pfa._heads_per_step(H, 128, 2, False, blk, H // Hkv) == 6
    assert pfa._heads_per_step(48, 128, 2, False, 512, 6) == 6       # the decoder's full layers
    assert pfa._heads_per_step(64, 128, 2, False, 256, 8) == 8       # and its sliding ones
    assert pfa._heads_per_step(64, 128, 2, False, 512, 8) == 4
    assert pfa._heads_per_step(48, 128, 2, True, 512, 6) == 3
    assert pfa._heads_per_step(48, 128, 4, False, 512, 6) == 1
    ks = jax.random.split(jax.random.PRNGKey(3), 4)
    q, do = (jax.random.normal(kk, (B, T, H, 128), jnp.bfloat16) for kk in ks[::3])
    k, v = (jax.random.normal(kk, (B, T, Hkv, 128), jnp.bfloat16) for kk in ks[1:3])
    f32 = lambda x: np.asarray(x, np.float32)
    results = []
    for hb in (6, 3, 2, 1):
        monkeypatch.setattr(pfa, "_heads_per_step", lambda *a, **kw: hb)
        pfa._fwd.clear_cache(), pfa._bwd.clear_cache()
        results.append(_value_and_grads(lambda *a: pfa.flash_attention_token_major(
            *a, causal=True, window=window), (q, k, v), do))
    pfa._fwd.clear_cache(), pfa._bwd.clear_cache()
    for o, grads in results[1:]:
        assert float(o) == float(results[0][0])
        for a, b in zip(grads, results[0][1]):
            assert (f32(a) == f32(b)).all()


def test_token_major_needs_whole_lane_tiles_and_heads_that_divide():
    x = jnp.zeros((1, 256, 4, 64), jnp.bfloat16)
    with pytest.raises(ValueError, match="lane tiles"):
        pfa.flash_attention_token_major(x, x, x)
    q, k = jnp.zeros((1, 256, 6, 128)), jnp.zeros((1, 256, 4, 128))
    with pytest.raises(ValueError, match="divides"):
        pfa.flash_attention_token_major(q, k, k)
    with pytest.raises(ValueError, match="divides"):
        pfa.flash_attention(q[:, :, :3], k, k)


def test_dot_product_attention_token_major_dispatches_like_the_head_major_entry(monkeypatch):
    """Dense on the CPU (query heads grouped over K/V heads in one einsum), the
    flash kernels where Pallas is on: token-major where a head is whole lane
    tiles, head-major behind one transpose each way where it is under one."""
    qkv, _, _ = _token_major("window-512-rep6", jnp.float32)
    seg = _thirds(1, 1024)
    want = _by_hand(_dense_all, dict(causal=True, window=512, segment_ids=seg))(*qkv)
    paths = []
    attention.set_path_hook(paths.append)
    try:
        dense = attention.dot_product_attention_token_major(*qkv, causal=True, window=512,
                                                            segment_ids=seg)
        narrow_dense = attention.dot_product_attention_token_major(
            *(x[..., :64] for x in qkv), causal=True)
        monkeypatch.setenv("APEX_TPU_FORCE_PALLAS", "1")
        monkeypatch.delenv("APEX_TPU_DISABLE_PALLAS", raising=False)
        flash = attention.dot_product_attention_token_major(*qkv, causal=True, window=512,
                                                            segment_ids=seg)
        narrow = attention.dot_product_attention_token_major(
            *(x[..., :64] for x in qkv), causal=True)          # D = 64: half a lane tile
    finally:
        attention.set_path_hook(None)
    assert paths == ["dense", "dense", "flash", "flash"] and narrow.shape == (1, 1024, 6, 64)
    np.testing.assert_allclose(np.asarray(narrow), np.asarray(narrow_dense), atol=5e-6)
    for got in (dense, flash):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=5e-6)
    with pytest.raises(ValueError, match="causal"):
        attention.dot_product_attention_token_major(*qkv, window=8)


# -- a score head wider than the value head: 192 = 128 + 64 against 128 ---------

def _two_widths(dtype, rope_heads, H, T, B=2):
    ks = jax.random.split(jax.random.PRNGKey(T + rope_heads), 6)
    q, k, v, do = (jax.random.normal(kk, (B, T, H, 128), dtype) for kk in ks[:4])
    return (q, k, v, jax.random.normal(ks[4], (B, T, H, 64), dtype),
            jax.random.normal(ks[5], (B, T, rope_heads, 64), dtype)), do


def _dense_two_widths(q, k, v, q_rope, k_rope, **features):
    """``_dense_all`` on the score head whole: (B, H, T, 192) against values of
    128 (it scales by the width of q, and v's may be another)."""
    heads = lambda x: jnp.moveaxis(x.astype(jnp.float32), 2, 1)
    k_rope = jnp.broadcast_to(k_rope, q_rope.shape)
    qq, kk = (heads(jnp.concatenate(parts, -1)) for parts in ((q, q_rope), (k, k_rope)))
    return jnp.moveaxis(_dense_all(qq, kk, heads(v), **features), 1, 2)


TWO_WIDTHS = {      # name: (dtype, rope key heads, H, T, features, tolerance)
    "shared-bf16-4-heads-a-step-folded-triangle": (jnp.bfloat16, 1, 4, 1024, dict(causal=True), 2e-2),
    "shared-fp32-2-heads-a-step": (jnp.float32, 1, 2, 512, dict(causal=True), 2e-5),
    "shared-fp32-ragged": (jnp.float32, 1, 4, 200, dict(causal=True), 2e-5),
    "shared-fp32-masked-and-packed": (jnp.float32, 1, 2, 256, dict(
        causal=True, kv_mask=lambda B, T: _half_masked(B, T).at[-1].set(True),
        segment_ids=lambda B, T: _thirds(B, T)), 2e-5),
    # a key head a query head: the entry's dense path alone takes it
    "per-head-fp32-ragged-dense": (jnp.float32, 4, 4, 200, dict(causal=True), 2e-5),
    "per-head-bf16-whole-dense": (jnp.bfloat16, 2, 2, 256, dict(), 2e-2),
}


@pytest.mark.parametrize("name", TWO_WIDTHS)
def test_a_score_head_of_192_against_values_of_128_matches_dense_outputs_and_every_gradient(name):
    """o, dq, dk, dv and both rope parts' gradients against the dense path on the
    joined heads; the one shared key head's gradient is the sum over the query
    heads (the kernel's over a step's heads, ``_bwd``'s over the steps).  Without
    the shared head (a key head a query head) the kernels refuse and the entry's
    dense path joins the parts."""
    dtype, rope_heads, H, T, features, tol = TWO_WIDTHS[name]
    operands, do = _two_widths(dtype, rope_heads, H, T)
    features = {f: (x(2, T) if callable(x) else x) for f, x in features.items()}
    entry = (pfa.flash_attention_token_major if rope_heads == 1
             else attention.dot_product_attention_token_major)
    flash = lambda q, k, v, qr, kr: entry(q, k, v, q_rope=qr, k_rope=kr, **features)
    f32 = lambda x: x.astype(jnp.float32)
    both = lambda fn: jax.jit(jax.value_and_grad(
        lambda *a: jnp.sum(f32(fn(*a)) * f32(do)), (0, 1, 2, 3, 4)))(*operands)
    got = jax.jit(flash)(*operands)
    want = _dense_two_widths(*operands, **features)
    assert got.shape == (2, T, H, 128) and got.dtype == dtype
    np.testing.assert_allclose(np.asarray(f32(got)), np.asarray(want), atol=tol)
    (_, gg), (_, gw) = both(flash), both(lambda *a: _dense_two_widths(*a, **features))
    for x, g, w in zip(("q", "k", "v", "q_rope", "k_rope"), gg, gw):
        assert g.shape == w.shape and g.dtype == dtype and np.isfinite(np.asarray(f32(g))).all()
        scale = max(1.0, float(jnp.abs(f32(w)).max()))
        np.testing.assert_allclose(np.asarray(f32(g)), np.asarray(f32(w)), atol=5 * tol * scale,
                                   err_msg=f"d{x}")


def test_the_dispatch_takes_the_two_parts_to_the_kernels_and_joins_them_on_the_dense_path(
        monkeypatch):
    operands, _ = _two_widths(jnp.float32, 1, 2, 256)
    want = _dense_two_widths(*operands, causal=True)
    call = lambda: attention.dot_product_attention_token_major(
        *operands[:3], causal=True, q_rope=operands[3], k_rope=operands[4])
    paths = []
    attention.set_path_hook(paths.append)
    try:
        dense = call()
        monkeypatch.setenv("APEX_TPU_FORCE_PALLAS", "1")
        monkeypatch.delenv("APEX_TPU_DISABLE_PALLAS", raising=False)
        flash = call()
        # a rope part that is not half a lane tile stays off the kernels
        narrow = attention.dot_product_attention_token_major(
            *operands[:3], causal=True, q_rope=operands[3][..., :32], k_rope=operands[4][..., :32])
    finally:
        attention.set_path_hook(None)
    assert paths == ["dense", "flash", "dense"] and narrow.shape == dense.shape
    for got in (dense, flash):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=5e-6)
    q, k, v, qr, kr = operands
    with pytest.raises(ValueError, match="come together"):
        attention.dot_product_attention_token_major(q, k, v, q_rope=qr)
    with pytest.raises(ValueError, match="k_rope"):
        attention.dot_product_attention_token_major(q, k, v, q_rope=qr, k_rope=kr[:, :, :, :32])
    with pytest.raises(ValueError, match="rope part"):       # K/V at q's head count
        pfa.flash_attention_token_major(q, k[:, :, :1], v[:, :, :1], q_rope=qr, k_rope=kr)
    with pytest.raises(ValueError, match="q_rope and k_rope"):
        pfa.flash_attention_token_major(q, k, v, k_rope=kr)
    # the value head's width is q's and k's: a wider score head comes in two parts
    with pytest.raises(ValueError, match="q_rope and k_rope"):
        attention.dot_product_attention_token_major(
            jnp.concatenate([q, qr], -1), jnp.concatenate([k, jnp.broadcast_to(kr, qr.shape)], -1),
            v)


# -- the counters: what a call's grids visit, and what it copies ---------------

def _counted(fn, *shapes):
    """The registry's flash counters over one trace of ``fn``."""
    from apex_tpu.observability.metrics import get_registry

    def read():
        pairs = get_registry().get("flash_block_pairs_total")
        pads = get_registry().get("flash_pad_copies_total")
        got = {k[0][1]: c.value for k, c in pairs.children().items()} if pairs else {}
        return dict({"interior": 0, "edge": 0, "dead": 0, "pads": pads.value if pads else 0}, **got)
    before = read()
    jax.eval_shape(fn, *shapes)
    return {k: int(v - before[k]) for k, v in read().items()}


def _calls_counted(fn, *shapes):
    """``flash_calls_total`` by (layout, kv) over one trace of ``fn``."""
    from apex_tpu.observability.metrics import get_registry

    def read():
        calls = get_registry().get("flash_calls_total")
        return {tuple(v for _, v in sorted(k)): c.value
                for k, c in calls.children().items()} if calls else {}
    before = read()
    jax.eval_shape(fn, *shapes)
    return {k: int(v - before.get(k, 0)) for k, v in read().items() if v != before.get(k, 0)}


def _qkv(BH, T, D):
    return [jax.ShapeDtypeStruct((1, BH, T, D), jnp.bfloat16)] * 3


def test_counter_causal_8k_visits_the_triangle_and_fetches_no_dead_block():
    got = _counted(lambda q, k, v: pfa.flash_attention(q, k, v, causal=True), *_qkv(1, 8192, 128))
    assert got == {"interior": 120, "edge": 16, "dead": 0, "pads": 0}
    # the backward's two launches visit the same pairs, three heads thrice
    both = _counted(jax.grad(lambda q, k, v: jnp.sum(pfa.flash_attention(q, k, v, causal=True)
                                                     .astype(jnp.float32))), *_qkv(3, 8192, 128))
    assert both == {"interior": 3 * 3 * 120, "edge": 3 * 3 * 16, "dead": 0, "pads": 0}
    # and the grid has no step but these: 8 folded rows of 17
    sweep = pfa._Sweep(16, 512, True, None, "k", False, False)
    assert (sweep.rows, sweep.steps) == (8, 17)


def test_counter_window_512_at_8k_visits_only_pairs_the_band_touches():
    got = _counted(lambda q, k, v: pfa.flash_attention(q, k, v, causal=True, window=512),
                   *_qkv(1, 8192, 128))
    # blocks of 256: 32 diagonal pairs, the 31 before them whole inside the
    # band, the 30 before those cut by its far end; the three dead steps are
    # the first two rows' look back before block 0, clamped onto it
    assert got == {"interior": 31, "edge": 62, "dead": 3, "pads": 0}
    wide = _counted(lambda q, k, v: pfa.flash_attention(q, k, v, causal=True, window=1100),
                    *_qkv(1, 8192, 128))
    touched = sum(1 for i in range(16) for j in range(i + 1) if (i - j) * 512 - 511 < 1100)
    whole = sum(1 for i in range(16) for j in range(i) if (i - j + 1) * 512 <= 1100)
    assert (wide["interior"], wide["edge"]) == (whole, touched - whole) and whole == 15


@pytest.mark.parametrize("T,D,pads", [(512, 64, 0), (8192, 128, 0), (600, 64, 11), (512, 160, 11)])
def test_counter_pad_copies_are_zero_when_length_and_head_fit(T, D, pads):
    got = _counted(jax.grad(lambda q, k, v: jnp.sum(pfa.flash_attention(q, k, v)
                                                    .astype(jnp.float32)), (0, 1, 2)),
                   *_qkv(2, T, D))
    assert got["pads"] == pads
    text = str(jax.make_jaxpr(lambda q, k, v: pfa.flash_attention(q, k, v))(
        *[jnp.zeros(s.shape, s.dtype) for s in _qkv(2, T, D)]))
    assert ("pad[" in text) == bool(pads)


def test_counter_says_which_form_a_call_took():
    """(kv, layout): a forward counts one call, a gradient its forward and its
    backward."""
    x = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.bfloat16)
    loss = lambda f: (lambda q, k, v: jnp.sum(f(q, k, v, causal=True).astype(jnp.float32)))
    tm, hm = pfa.flash_attention_token_major, pfa.flash_attention
    assert _calls_counted(jax.grad(loss(tm)), x(2, 512, 6, 128), x(2, 512, 1, 128), x(2, 512, 1, 128)) \
        == {("grouped", "token_major"): 2}
    assert _calls_counted(loss(tm), x(2, 512, 4, 128), x(2, 512, 4, 128), x(2, 512, 4, 128)) \
        == {("per_query_head", "token_major"): 1}
    assert _calls_counted(jax.grad(loss(hm)), *_qkv(16, 512, 64)) == {("per_query_head", "head_major"): 2}
    assert _calls_counted(loss(hm), x(1, 8, 256, 64), x(1, 2, 256, 64), x(1, 2, 256, 64)) \
        == {("grouped", "head_major"): 1}
    # a grouped call visits the pairs of its query heads, and pads nothing
    got = _counted(jax.grad(loss(tm)), x(1, 8192, 6, 128), x(1, 8192, 1, 128), x(1, 8192, 1, 128))
    assert got == {"interior": 6 * 3 * 120, "edge": 6 * 3 * 16, "dead": 0, "pads": 0}


def test_a_call_with_a_rope_part_is_told_apart_and_launches_the_cells_grids():
    """The latent-attention cell's call (2, 8192, 32, 128 + 64 / 128, one key
    head): counted with ``rope="shared"``, nothing padded, the pairs of the
    folded triangle, 4 heads a step as the accepted token-major calls at 128,
    the rope parts two heads a lane tile, dk's rope part one float32 tile a
    step; the accepted calls' counts carry no third label."""
    from apex_tpu.analysis import pallas_lint
    x = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.bfloat16)
    shapes = (x(2, 8192, 32, 128),) * 3 + (x(2, 8192, 32, 64), x(2, 8192, 1, 64))
    loss = lambda q, k, v, qr, kr: jnp.sum(pfa.flash_attention_token_major(
        q, k, v, causal=True, q_rope=qr, k_rope=kr).astype(jnp.float32))
    pfa._fwd.clear_cache(), pfa._bwd.clear_cache()
    sites = []
    with pallas_lint.capture_kernel_sites(sites):
        assert _calls_counted(jax.grad(loss, (0, 1, 2, 3, 4)), *shapes) == {
            ("per_query_head", "token_major", "shared"): 2}
    pfa._fwd.clear_cache(), pfa._bwd.clear_cache()
    assert _counted(jax.grad(loss), *shapes) == {
        "interior": 64 * 3 * 120, "edge": 64 * 3 * 16, "dead": 0, "pads": 0}
    with pytest.raises(ValueError, match="rope part"):       # one key head, not one a head
        jax.eval_shape(loss, *shapes[:4], x(2, 8192, 32, 64))
    assert [s.name for s in sites] == ["_fwd_kernel", "_dq_kernel", "_dkv_kernel"]
    fwd, dq, dkv = sites
    for site in sites:
        assert site.grid == (16, 8, 17) and pallas_lint.check_site(site) == []
        blocks = [spec.block_shape for spec in site.in_specs]
        assert blocks[:3] == [(1, 512, 512)] * 3            # 4 heads of 128, q, k and v alike
    rope = lambda site: [spec.block_shape for spec, (shape, _) in zip(site.in_specs, site.in_shapes)
                         if shape[-1] in (2048, 128) and len(shape) == 3 and shape[1] == 8192]
    assert rope(fwd) == rope(dq) == rope(dkv) == [(1, 512, 256), (1, 512, 128)]
    assert [tuple(s) for s, _ in dkv.out_shapes] == [(2, 8192, 4096), (2, 8192, 4096),
                                                     (16, 8192, 128)]
    assert [tuple(s) for s, _ in dq.out_shapes] == [(2, 8192, 4096), (2, 8192, 2048)]
    assert pfa.fits_vmem(8192, 128, rope=64) and pfa.fits_vmem(8192, 128)
    # what the accepted token-major cells launch is what they launched: counted as before
    plain = _calls_counted(lambda q, k, v: pfa.flash_attention_token_major(q, k, v, causal=True),
                           *shapes[:3])
    assert plain == {("per_query_head", "token_major"): 1}


# what the parent of PR 29 launched for these head-major calls: grid, q block, k block
PARENTS_LAUNCHES = [
    ("bert", (8, 16, 512, 64), dict(), (32, 1, 1), (4, 512, 64)),
    ("bert-masked", (8, 16, 512, 64), dict(kv_mask=True), (64, 1, 1), (2, 512, 64)),
    ("decoder-full-repeated", (2, 48, 8192, 128), dict(causal=True), (24, 8, 17), (4, 512, 128)),
    ("decoder-sliding-repeated", (2, 64, 8192, 128), dict(causal=True, window=512), (16, 32, 3),
     (8, 256, 128)),
]


@pytest.mark.parametrize("name,shape,features,grid,block", PARENTS_LAUNCHES,
                         ids=[c[0] for c in PARENTS_LAUNCHES])
def test_head_major_calls_launch_what_they_launched(name, shape, features, grid, block):
    """Same grids, same blocks, same ``hb``, same kernel names, K/V blocks like
    q's, the counters of a call as they were (and its form named)."""
    from apex_tpu.analysis import pallas_lint
    features = dict(features)
    if features.pop("kv_mask", False):
        features["kv_mask"] = jnp.ones(shape[::2], bool)
    pfa._fwd.clear_cache(), pfa._bwd.clear_cache()
    sites = []
    x = jax.ShapeDtypeStruct(shape, jnp.bfloat16)
    with pallas_lint.capture_kernel_sites(sites):
        calls = _calls_counted(jax.grad(lambda q, k, v: jnp.sum(
            pfa.flash_attention(q, k, v, **features).astype(jnp.float32)), (0, 1, 2)), x, x, x)
    pfa._fwd.clear_cache(), pfa._bwd.clear_cache()
    assert calls == {("per_query_head", "head_major"): 2}
    assert [s.name for s in sites] == ["_fwd_kernel", "_dq_kernel", "_dkv_kernel"]
    for site in sites:
        assert site.grid == grid, site.describe()
        operands = [spec.block_shape for spec, (sh, _) in zip(site.in_specs, site.in_shapes)
                    if len(sh) == 3 and sh[1:] == shape[2:]]
        assert operands and set(operands) == {block}, site.describe()
        assert pallas_lint.check_site(site) == []


def test_index_maps_of_the_folded_triangle_cover_every_pair_once():
    for n in (1, 2, 3, 4, 7, 16):
        for streams in "kq":
            sweep = pfa._Sweep(n, 512, True, None, streams, False, False)
            g, t = np.meshgrid(np.arange(sweep.rows), np.arange(sweep.steps), indexing="ij")
            qi, kj, live, first, last = sweep.qk(g, t, np)
            pairs = sorted(zip(qi.ravel().tolist(), kj.ravel().tolist()))
            assert pairs == [(i, j) for i in range(n) for j in range(i + 1)]
            row = sweep.at(g, t, np)[0]
            assert first.sum() == last.sum() == n          # once a row of the triangle
            # a row's steps are consecutive: its output block is visited in one run
            for r in range(sweep.rows):
                runs = [x for x, y in zip(row[r], np.r_[-1, row[r][:-1]]) if x != y]
                assert len(runs) == len(set(runs))


# -- compiled for a described (not attached) v5e (``one_chip``: conftest.py) ---

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


_LAYER_STEPS = {}


def _attention_layer_step(one_chip, kind, heads):
    """The text of ``LagunaAttention`` forward + gradient under the cell's remat
    mode at the cell's widths (B 2, T 8192, bf16), compiled once a layer kind."""
    if kind in _LAYER_STEPS:
        return _LAYER_STEPS[kind]
    import json
    import os
    from apex_tpu.models import laguna
    from apex_tpu.models._remat import wrap_block
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs", "laguna-xs2.json")) as f:
        cfg = laguna.LagunaConfig.from_dict(json.load(f))
    layer = cfg.layer_types.index(kind)
    assert cfg.num_attention_heads_per_layer[layer] == heads
    attn = laguna.LagunaAttention(cfg, layer)
    shapes = jax.eval_shape(lambda k: attn.init(k)[0], jax.random.PRNGKey(0))
    params = jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, jnp.bfloat16, sharding=one_chip), shapes)
    x = jax.ShapeDtypeStruct((2, 8192, cfg.hidden_size), jnp.bfloat16, sharding=one_chip)

    def loss(p, x):
        return jnp.sum(wrap_block(lambda pp, xx: attn(pp, xx), cfg.remat)(p, x).astype(jnp.float32))

    text = jax.jit(jax.grad(loss, (0, 1))).lower(params, x).compile().as_text()
    return _LAYER_STEPS.setdefault(kind, text)


LAYER_KINDS = [("sliding_attention", 64), ("full_attention", 48)]


@pytest.mark.parametrize("kind,heads", LAYER_KINDS)
def test_v5e_compiles_the_decoders_attention_layer_without_copies_around_the_kernels(
        one_chip, for_the_chip, kind, heads):
    """``LagunaAttention`` forward + gradient under the cell's remat mode at the
    cell's widths (B 2, T 8192, bf16): between the projections and the kernels
    the entry computation moves no axis, repeats no K/V head and widens
    nothing of q's size.  What keeps the copies from coming back with a later
    edit of the model: a (B, T, H, D) view of a projection's output is a
    relayout on a TPU, whose tiles are 8 tokens x 128 lanes."""
    text = _attention_layer_step(one_chip, kind, heads)
    for kernel in ("flash_fwd", "flash_dq", "flash_dkv", "rope"):
        assert re.search(rf"%{kernel}[.\d]* = ", text), kernel
    q_elements = 2 * 8192 * heads * 128
    entry = text[text.index("ENTRY"):]
    for line in entry.splitlines():
        m = re.match(r"\s*(?:ROOT )?%?([\w.\-]+) = (\(?.*?\)?) ([\w\-]+)\(", line)
        if not m:
            continue
        name, shape, op = m.groups()
        for dtype, dims in re.findall(r"(\w+)\[([\d,]+)\]", shape):
            dims = [int(d) for d in dims.split(",")]
            if int(np.prod(dims)) < q_elements:
                continue
            # K or V at the query head count, in any arrangement of its axes
            assert not (op == "broadcast" and heads // 8 in dims and 8 in dims), line[:200]
            assert dtype != "f32", f"an fp32 array of q's size outside a fusion: {line[:200]}"
            assert op not in ("copy", "transpose", "reshape", "convert", "broadcast"), \
                f"{op} of q's size: {line[:200]}"
    # K and V reach the kernels at their own 8 heads: dk, dv come back so
    assert re.search(r"%flash_dkv[.\d]* = \(bf16\[2,8192,1024\]", text)


@pytest.mark.parametrize("kind,heads", LAYER_KINDS)
def test_v5e_compiles_the_decoders_rematerialized_attention_layer_with_one_forward_kernel(
        one_chip, for_the_chip, kind, heads):
    """The same step launches each flash kernel once: the cell's ``dots`` keeps the
    forward kernel's ``o`` and ``lse`` by name, so the backward's replay of the
    layer holds no second ``flash_fwd`` (it held one, the longest of the three
    kernels in ``laguna-xs2.pretrain-8k``)."""
    text = _attention_layer_step(one_chip, kind, heads)
    for kernel in ("flash_fwd", "flash_dq", "flash_dkv"):
        assert len(re.findall(rf"%{kernel}[.\d]* = ", text)) == 1, kernel


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_v5e_compiles_the_flash_kernels_at_the_latent_attention_cells_widths(one_chip, for_the_chip,
                                                                             dtype):
    """(2, 8192, 32, 128 + 64 / 128), one key head for all query heads, in bf16
    (the cell's call, 4 heads a step) and in float32 (2 a step: a rope tile
    needs two): three Mosaic kernels inside the 16 MiB a kernel may use, no
    operand padded, no (T, T) array."""
    x = lambda *shape: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def loss(q, k, v, qr, kr):
        return jnp.sum(pfa.flash_attention_token_major(q, k, v, causal=True, q_rope=qr, k_rope=kr)
                       .astype(jnp.float32))

    text = jax.jit(jax.grad(loss, (0, 1, 2, 3, 4))).lower(
        x(2, 8192, 32, 128), x(2, 8192, 32, 128), x(2, 8192, 32, 128), x(2, 8192, 32, 64),
        x(2, 8192, 1, 64)).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 3
    for kernel in ("flash_fwd", "flash_dq", "flash_dkv"):
        assert kernel in text
    assert _largest_pad(text) <= 2 * 8192 * 128 and "8192,8192]" not in text


def _largest_pad(text):
    """Elements of the largest array any ``pad`` of a compiled program writes (the
    one key head written twice into a lane tile is a pad and a maximum, 2 x 8192
    x 128; an operand padded to the next lane tile would be q's size and more)."""
    sizes = [int(np.prod([int(d) for d in dims.split(",")]))
             for dims in re.findall(r"= \w+\[([\d,]+)\][^=]*? pad\(", text)]
    return max(sizes, default=0)


def test_v5e_compiles_the_latent_attention_layer_without_pads_or_copies_around_the_kernels(
        one_chip, for_the_chip):
    """``transformer.LatentAttention`` forward + gradient under the cell's remat
    mode at the cell's widths (B 2, T 8192, bf16): each flash kernel once, q's
    two parts, k, v and the one key head reach them where the projections (and
    the rotation) wrote them: the entry computation pads nothing, and copies,
    moves, widens or repeats nothing of q's size (2 x 8192 x 32 x 128)."""
    import json
    import os
    from apex_tpu import models
    from apex_tpu.models._remat import wrap_block
    from apex_tpu.models.deepseek_v3 import DeepseekV3Block
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs", "kanana-2-30b-a3b.json")) as f:
        cfg = models.DeepseekV3Config.from_dict(json.load(f))
    attn = DeepseekV3Block.attention(cfg, 1)
    shapes = jax.eval_shape(lambda k: attn.init(k)[0], jax.random.PRNGKey(0))
    params = jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, jnp.bfloat16, sharding=one_chip), shapes)
    x = jax.ShapeDtypeStruct((2, 8192, cfg.hidden_size), jnp.bfloat16, sharding=one_chip)

    def loss(p, x):
        return jnp.sum(wrap_block(lambda pp, xx: attn(pp, xx), cfg.remat)(p, x).astype(jnp.float32))

    text = jax.jit(jax.grad(loss, (0, 1))).lower(params, x).compile().as_text()
    for kernel in ("flash_fwd", "flash_dq", "flash_dkv"):
        assert len(re.findall(rf"%{kernel}[.\d]* = ", text)) == 1, kernel
    assert _largest_pad(text) <= 2 * 8192 * 128 and "8192,8192]" not in text
    q_elements = 2 * 8192 * 32 * 128
    entry = text[text.index("ENTRY"):]
    for line in entry.splitlines():
        m = re.match(r"\s*(?:ROOT )?%?([\w.\-]+) = (\(?.*?\)?) ([\w\-]+)\(", line)
        if not m:
            continue
        name, shape, op = m.groups()
        for dtype, dims in re.findall(r"(\w+)\[([\d,]+)\]", shape):
            if int(np.prod([int(d) for d in dims.split(",")])) < q_elements:
                continue
            assert dtype != "f32", f"an fp32 array of q's size outside a fusion: {line[:200]}"
            assert op not in ("copy", "transpose", "reshape", "convert", "broadcast", "pad",
                              "concatenate"), f"{op} of q's size: {line[:200]}"
    # dq's rope part beside dq, dk's as one float32 tile a grid step (8 of them a batch entry)
    assert re.search(r"%flash_dq[.\d]* = \(bf16\[2,8192,4096\]\S*, bf16\[2,8192,2048\]", text)
    assert re.search(r"%flash_dkv[.\d]* = \(bf16\[2,8192,4096\]\S*, bf16\[2,8192,4096\]\S*, "
                     r"f32\[16,8192,128\]", text)


def test_v5e_compiles_the_flash_kernels_at_the_encoder_cells_widths(one_chip, for_the_chip):
    """D = 64 goes to the kernels as it is: no pad before, no slice after."""
    x = jax.ShapeDtypeStruct((8, 16, 512, 64), jnp.bfloat16, sharding=one_chip)

    def loss(q, k, v):
        return jnp.sum(pfa.flash_attention(q, k, v).astype(jnp.float32))

    text = jax.jit(jax.grad(loss, (0, 1, 2))).lower(x, x, x).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 3
    assert "bf16[128,512,128]" not in text and "f32[128,512,128]" not in text


def _the_rows_way_home(text, tokens, k, width, rows):
    """How the compiled expert layer brings its rows home (PR 45).  Where the
    ``k * tokens`` slots are few beside the buffer's rows: by the compiler's
    own gather fusion of the slots (the gather's transpose here; the combine's
    too where the loss reads its value), each read by ONE fused pass (no fp32
    array of the slots' size: the convert rides in the pass that sums them),
    the buffer cut by columns into sources of at most 48 MiB, which the
    compiler keeps in VMEM, and no scatter at a row's width in either
    direction.  Where most slots
    would be empty: by the scatter-add, and no array of the slots' size."""
    import re
    from apex_tpu.ops import row_moves
    at_width = [line for line in text.splitlines() if re.search(r" scatter\(", line)
                and f",{width}]" in line.split(" scatter(")[0]]
    entry = text[text.index("ENTRY"):]
    # the buffer is the source of one gather a chunk of columns that fits VMEM
    chunks = row_moves._column_chunks(rows, width, 2)
    wide = width // chunks
    slots = re.findall(rf"= bf16\[{k * tokens},{wide}\]\S* fusion\(.*kind=kCustom", entry)
    if row_moves.home_by_gathers(k * tokens, rows):
        assert not at_width, at_width[0][:200]
        for shape in (f"[{k * tokens},{wide}]", f"[{k},{tokens},{wide}]", f"[{tokens},{k},{wide}]"):
            assert "f32" + shape not in text, shape
        assert chunks <= len(slots) <= 2 * chunks, (chunks, len(slots))
        assert rows * wide * 2 <= row_moves._SOURCE_BYTES
    else:
        assert at_width and not slots and f"[{k * tokens},{wide}]" not in text


@pytest.mark.parametrize("cell,width,hidden,scored,router,shared,tokens", [
    ("laguna-xs2", 2048, 512, 256, "sigmoid", 512, 16384),
    ("mellum2-12b", 2304, 896, 64, "softmax", None, 8192)])
def test_v5e_compiles_the_grouped_expert_products(one_chip, for_the_chip, cell, width, hidden,
                                                  scored, router, shared, tokens):
    """One expert layer at each decoder cell's widths, forward and backward:
    under ``moe.experts`` nine Mosaic kernels, a product each (the six of the
    ``custom_vjp``'s backward keep the scope), nothing of the compiler's own
    grouped product, no fp32 array of the row buffer's size and no transposed
    copy of a weight stack.  ``benchmark/readers/grouped_dot_roofline.py``
    reads nothing where the kernels a layer are not the products."""
    import re
    from apex_tpu.observability.phases import instruction_phases
    from apex_tpu.parallel.expert_parallel import ExpertParallelMLP
    layer = ExpertParallelMLP(width, hidden, scored, capacity_factor=None, top_k=8,
                              expert_type="swiglu", router_type=router,
                              routed_scaling=2.5 if router == "sigmoid" else 1.0,
                              experts_held=(0, 16), shared_hidden=shared, row_buffer_factor=2.0)
    rows = 2 * tokens * 8 * 16 // scored
    shapes = jax.eval_shape(lambda k: layer.init(k)[0], jax.random.PRNGKey(0))
    params = jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, jnp.float32 if s.shape == (width, scored)
                                       else jnp.bfloat16, sharding=one_chip), shapes)
    x = jax.ShapeDtypeStruct((tokens, width), jnp.bfloat16, sharding=one_chip)
    text = jax.jit(jax.grad(lambda p, x: jnp.sum(layer(p, x).astype(jnp.float32)), (0, 1))).lower(
        params, x).compile().as_text()
    assert "ragged-dot" not in text
    # tokens x experts x any slots would be >= 4 M x slots elements
    assert f"{tokens},{scored},16" not in text and f"{tokens},16,{tokens}" not in text
    phases = instruction_phases(text)
    kernels, entry = [], text[text.index("ENTRY"):]
    for line in entry.splitlines():
        m = re.match(r"\s*(?:ROOT )?%?([\w.\-]+) = (\(?.*?\)?) ([\w\-]+)\(", line)
        if not m or "moe.experts" not in phases.get(m.group(1), ((), False))[0]:
            continue
        name, shape, op = m.groups()
        if op == "custom-call":
            assert "tpu_custom_call" in line, line[:200]
            kernels.append((re.sub(r"[.\d]+$", "", name), phases[name][1]))
        for dtype, dims in re.findall(r"(\w+)\[([\d,]+)\]", shape):
            dims = [int(d) for d in dims.split(",")]
            assert not (dtype == "f32" and dims[0] == rows and len(dims) == 2), \
                f"an fp32 array of the row buffer's size: {line[:200]}"
            assert not (op in ("copy", "transpose") and sorted(dims) == sorted([16, width, hidden])), \
                f"a copy of a weight stack: {line[:200]}"
    forward = [k for k, backward in kernels if not backward]
    backward = [k for k, backward in kernels if backward]
    assert len(forward) == 3 and len(backward) == 6, kernels
    assert sum("grouped_stack" in k for k in backward) == 3, kernels
    assert sum("grouped_rows_t" in k for k in backward) == 3, kernels
    _the_rows_way_home(text, tokens, 8, width, rows)


def test_v5e_compiles_the_grouped_products_at_a_block_that_asks_for_more_vmem(one_chip,
                                                                              for_the_chip):
    """``lfm2-8b-a1b``'s expert layer, forward and backward: a group's
    2048 x 1792 block does not fit the kernels' default VMEM twice, so the
    chooser names a tile under a stated larger ask; the chip's compiler takes
    the nine kernels, and nothing of the compiler's own grouped product is
    left."""
    from apex_tpu.ops import pallas_grouped_matmul as pgm
    from apex_tpu.parallel.expert_parallel import ExpertParallelMLP
    layer = ExpertParallelMLP(2048, 1792, 32, capacity_factor=None, top_k=4,
                              expert_type="swiglu", router_type="sigmoid", experts_held=(0, 8),
                              row_buffer_factor=2.0, router_bias=True, router_out_in=True)
    tokens = 16384
    assert pgm.row_tile(2 * tokens * 4 * 8 // 32, 2048, 1792, 8, jnp.bfloat16) == 256
    shapes = jax.eval_shape(lambda k: layer.init(k)[0], jax.random.PRNGKey(0))
    assert shapes["router"].shape == (32, 2048) and shapes["expert_bias"].shape == (32,)
    params = jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, jnp.float32 if s.shape[0] == 32 else jnp.bfloat16,
                                       sharding=one_chip), shapes)
    x = jax.ShapeDtypeStruct((tokens, 2048), jnp.bfloat16, sharding=one_chip)
    text = jax.jit(jax.grad(lambda p, x: jnp.sum(layer(p, x).astype(jnp.float32)), (0, 1))).lower(
        params, x).compile().as_text()
    assert "ragged-dot" not in text
    names = re.findall(r"%(grouped_rows_t|grouped_rows|grouped_stack)[.\d]* = ", text)
    assert sorted(names) == ["grouped_rows"] * 3 + ["grouped_rows_t"] * 3 + ["grouped_stack"] * 3
    _the_rows_way_home(text, tokens, 4, 2048, 2 * tokens * 4 * 8 // 32)


def test_v5e_compiles_the_grouped_products_at_an_expert_width_of_14_and_a_half_lane_tiles(
        one_chip, for_the_chip):
    """``nemotron3-nano-30b-a3b``'s expert layer, forward and backward: non-gated
    relu2 experts of 2688 x 1856 (14.5 lane tiles), 8 held of 128 at 6 a token,
    the leaves at their published shapes.  The chip's compiler takes the six
    kernels (2 forward, 4 backward), the half tile a narrower matrix step inside
    them; nothing of the compiler's own grouped product is left, and no padded
    copy of a weight stack (1920 wide) exists."""
    from apex_tpu.ops import pallas_grouped_matmul as pgm
    from apex_tpu.parallel.expert_parallel import ExpertParallelMLP
    layer = ExpertParallelMLP(2688, 1856, 128, capacity_factor=None, top_k=6, expert_type="mlp",
                              activation="relu2", router_type="sigmoid", routed_scaling=2.5,
                              experts_held=(0, 8), shared_hidden=3712, row_buffer_factor=2.0,
                              router_bias=True)
    tokens = 8192
    rows = 2 * tokens * 6 * 8 // 128
    assert rows == 6144 and pgm._chunks(1856) == ((0, 896), (896, 896), (1792, 64))
    assert pgm.row_tile(rows, 2688, 1856, 8, jnp.bfloat16) == 128
    assert pgm.row_tile(rows, 1856, 2688, 8, jnp.bfloat16) == 128
    shapes = jax.eval_shape(lambda k: layer.init(k)[0], jax.random.PRNGKey(0))
    assert shapes["w_in"].shape == (8, 2688, 1856) and shapes["w_out"].shape == (8, 1856, 2688)
    params = jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, jnp.float32 if s.shape[-1] == 128 else jnp.bfloat16,
                                       sharding=one_chip), shapes)
    x = jax.ShapeDtypeStruct((tokens, 2688), jnp.bfloat16, sharding=one_chip)
    text = jax.jit(jax.grad(lambda p, x: jnp.sum(layer(p, x).astype(jnp.float32)), (0, 1))).lower(
        params, x).compile().as_text()
    assert "ragged-dot" not in text and ",1920]" not in text
    names = re.findall(r"%(grouped_rows_t|grouped_rows|grouped_stack)[.\d]* = ", text)
    assert sorted(names) == ["grouped_rows"] * 2 + ["grouped_rows_t"] * 2 + ["grouped_stack"] * 2
    _the_rows_way_home(text, tokens, 6, 2688, rows)


def test_v5e_compiles_a_grouped_head_of_64_through_the_head_major_kernels(one_chip, for_the_chip):
    """``lfm2-8b-a1b``'s attention call: 32 query heads over 8 K/V heads of 64
    from ``dot_product_attention_token_major``: the three flash kernels with
    K/V at their 8 heads (the dkv kernel writes 16 = 2 x 8 folds), and no
    array of scores."""
    from apex_tpu.transformer import attention
    q = jax.ShapeDtypeStruct((2, 8192, 32, 64), jnp.bfloat16, sharding=one_chip)
    kv = jax.ShapeDtypeStruct((2, 8192, 8, 64), jnp.bfloat16, sharding=one_chip)
    paths = []
    attention.set_path_hook(paths.append)
    try:
        text = jax.jit(jax.grad(
            lambda q, k, v: jnp.sum(attention.dot_product_attention_token_major(
                q, k, v, causal=True).astype(jnp.float32)), (0, 1, 2))).lower(
                    q, kv, kv).compile().as_text()
    finally:
        attention.set_path_hook(None)
    assert set(paths) == {"flash"}
    assert text.count('custom_call_target="tpu_custom_call"') == 3
    assert "bf16[16,8192,64]" in text and "8192,8192]" not in text


@pytest.mark.parametrize("mode,passes", [
    ("dots", ["fwd", "fwd", "bwd"]), (None, ["fwd", "bwd"])])
def test_v5e_compiles_a_mamba_mixers_scan_as_the_kernel_pair(one_chip, for_the_chip, mode, passes):
    """One Mamba-2 block at ``nemotron3-nano-30b-a3b``'s widths (64 heads of
    64, 8 groups, state 128, chunks of 128, 1 x 8192 tokens), forward and
    backward: every Mosaic kernel of the step stands under ``mamba.scan`` or
    under ``mamba.conv`` (the convolution's pair reads its 6144 columns from
    column 4096 of the projection's 10304), the forward, under a ``remat``
    its replay (the scan's also writes the chunks' entering states), and the
    backward under a transposed scope; no array of ``(.., 128, 128)`` decays
    or scores of any type, which is what XLA's form of the scan writes, and no
    float32 array of the convolved columns' size, which is what its form of
    the shift writes; the counters name the kernels."""
    import re
    from apex_tpu.models import _remat
    from apex_tpu.observability.metrics import get_registry
    from apex_tpu.observability.phases import instruction_phases
    from apex_tpu.transformer import mamba2
    mixer = mamba2.Mamba2Mixer(2688, 64, 64, 128, 8, taps=4, chunk=128)
    shapes = jax.eval_shape(lambda: mixer.init(jax.random.PRNGKey(0))[0])
    params = jax.tree_util.tree_map_with_path(
        lambda path, s: jax.ShapeDtypeStruct(
            s.shape, jnp.float32 if path[0].key in mixer.fp32_param_names else jnp.bfloat16,
            sharding=one_chip), shapes)
    u = jax.ShapeDtypeStruct((1, 8192, 2688), jnp.bfloat16, sharding=one_chip)
    block = _remat.wrap_block(lambda p, x: x + mixer(p, x), mode)

    def loss(p, x):
        with jax.named_scope("model"):
            return jnp.sum(block(p, x).astype(jnp.float32) ** 2)

    reg = get_registry()
    counted = lambda: (
        reg.counter("ssd_scan_calls_total").labels(impl="pallas", chunk="128").value,
        reg.counter("short_conv_calls_total").labels(taps="4", impl="pallas").value)
    before = counted()
    text = jax.jit(jax.grad(loss, (0, 1))).lower(params, u).compile().as_text()
    assert all(a > b for a, b in zip(counted(), before))
    assert not re.search(r"\[[\d,]*128,128\]", text)
    assert "= f32[1,8192,6144]" not in text[text.index("ENTRY"):]
    phases = instruction_phases(text)
    found = {"mamba.scan": [], "mamba.conv": []}
    for name in re.findall(r"%([\w.\-]+) = [^\n]*custom_call_target=\"tpu_custom_call\"",
                           text[text.index("ENTRY"):]):
        path, backward = phases[name]
        scope = next((s for s in found if s in path), None)
        assert scope, (name, path)
        found[scope].append((re.sub(r"[.\d]+$", "", name), backward))
    for scope, stem in (("mamba.scan", "ssd_"), ("mamba.conv", "short_conv_")):
        assert [k for k, _ in found[scope]] == [stem + p for p in passes]
        assert [b for _, b in found[scope]] == [False] + [True] * (len(passes) - 1)


def test_v5e_compiles_a_gated_short_convolution_as_one_pass_a_direction(one_chip, for_the_chip):
    """One operator at ``lfm2-8b-a1b``'s width (2048 channels, 3 taps, 2 x 8192
    tokens), forward and backward: ``conv.mix`` is the kernel pair, the
    backward's cotangent the projection's whole ``(2, 8192, 6144)`` written by
    the kernel, and no float32 array of ``g``'s size exists in the step (XLA's
    form of the shift writes one forward and three backward)."""
    import re
    from apex_tpu.observability.metrics import get_registry
    from apex_tpu.observability.phases import instruction_phases
    from apex_tpu.transformer import short_conv
    op = short_conv.GatedShortConv(2048, 3)
    shapes = jax.eval_shape(lambda: op.init(jax.random.PRNGKey(0))[0])
    params = jax.tree_util.tree_map_with_path(
        lambda path, s: jax.ShapeDtypeStruct(
            s.shape, jnp.float32 if path[0].key in op.fp32_param_names else jnp.bfloat16,
            sharding=one_chip), shapes)
    u = jax.ShapeDtypeStruct((2, 8192, 2048), jnp.bfloat16, sharding=one_chip)

    def loss(p, x):
        with jax.named_scope("model"):
            return jnp.sum((x + op(p, x)).astype(jnp.float32) ** 2)

    counted = lambda: get_registry().counter("short_conv_calls_total").labels(
        taps="3", impl="pallas").value
    before = counted()
    text = jax.jit(jax.grad(loss, (0, 1))).lower(params, u).compile().as_text()
    assert counted() == before + 1
    entry = text[text.index("ENTRY"):]      # what the step writes to HBM
    assert "= f32[2,8192,2048]" not in entry and "= f32[2,8192,6144]" not in entry
    phases = instruction_phases(text)
    found = []
    for line in re.findall(r"%[\w.\-]+ = [^\n]*custom_call_target=\"tpu_custom_call\"",
                           text[text.index("ENTRY"):]):
        name = re.match(r"%([\w.\-]+) = ", line).group(1)
        path, backward = phases[name]
        assert "conv.mix" in path, (name, path)
        found.append((re.sub(r"[.\d]+$", "", name), backward))
        if backward:
            assert "bf16[2,8192,6144]" in line.split("custom-call(")[0]
    assert found == [("short_conv_fwd", False), ("short_conv_bwd", True)]
