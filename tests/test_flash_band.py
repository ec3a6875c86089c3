"""The causal band of the flash kernels (``flash_attention(window=)``),
interpreted on the CPU: forward and both gradients against the dense band for
lengths and windows that are no multiples of the block, the streamed axis cut
to the band's blocks, ``window=None`` the program it was, the dispatch in
``dot_product_attention`` and in the Llama attention; every path the kernels
separate (interior and edge pairs, the folded causal triangle, heads of 64 and
128 unpadded, ragged lengths, masks, segments and dropout) against one dense
reference, and the block-pair counters; and, compiled for a described v5e,
the kernels at the widths of the benchmark's cells."""

import math

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
                                            (1024, 1, 256, 1), (8192, 1024, 512, 3), (1024, 4096, 512, 2)])
def test_streamed_axis_covers_the_band_only(T, W, blk, blocks):
    """The grid's last axis: 3 of 32 blocks for the decoder cell's layers, whose
    window of 512 selects blocks of 256; from two 512-blocks up, blocks of 512."""
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
        segment_ids=lambda B, T: jnp.asarray(np.repeat([0, 1, 2], T // 3)[None].repeat(B, 0)))),
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


def test_v5e_compiles_the_flash_kernels_at_the_encoder_cells_widths(one_chip, for_the_chip):
    """D = 64 goes to the kernels as it is: no pad before, no slice after."""
    x = jax.ShapeDtypeStruct((8, 16, 512, 64), jnp.bfloat16, sharding=one_chip)

    def loss(q, k, v):
        return jnp.sum(pfa.flash_attention(q, k, v).astype(jnp.float32))

    text = jax.jit(jax.grad(loss, (0, 1, 2))).lower(x, x, x).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 3
    assert "bf16[128,512,128]" not in text and "f32[128,512,128]" not in text


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
