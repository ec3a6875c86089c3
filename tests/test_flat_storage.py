"""amp's flat fp32 buffers are STORED at a block-aligned length
(``_FlatLayout.storage``) while every reader keeps counting in the
logical one (``_FlatLayout.total``): the Adam and unscale kernels then
take them through ``to_2d`` / ``from_2d`` without a pad or a slice.

(a) lengths and logical views, (b) the tail is inert: three steps equal
the same updates computed leaf by leaf, bit for bit, and the tail stays
zero, also over a skipped step, (c) under Pallas dispatch the flat step
holds no pad and no slice of the flat length and the registry's counter
reads 0, while an unaligned direct call still pads, matches and is
counted, (d) a snapshot saved at the old length restores and steps
identically, (e) a leaf leaves a flat buffer as a piece of its own size:
``rebuild`` and ``unpack_masters`` equal numpy's slices bit for bit, and
compiled for a described v5e they hold no result of the buffer's size."""

import re

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from apex_tpu.amp._process_optimizer import (AmpOptimizer, FlatMasters,
                                             _FlatLayout)
from apex_tpu.amp.scaler import LossScaler
from apex_tpu.observability.metrics import get_registry
from apex_tpu.ops.pallas_common import (BLOCK_ELEMS, LANES, aligned_len,
                                        from_2d, pick_block_rows, to_2d)
from apex_tpu.optimizers import FusedAdam, FusedLion
from apex_tpu.optimizers.base import SGD
from apex_tpu.utils import checkpoint as ckpt

INNERS = {
    "adam": lambda: FusedAdam(lr=1e-2, weight_decay=0.01),
    "sgd": lambda: SGD(lr=1e-2, momentum=0.9, weight_decay=0.01),
    "lion": lambda: FusedLion(lr=1e-3, weight_decay=0.01),
}


def _params(seed=0, big=False):
    """Mixed bf16 / fp32 float leaves and an int leaf; the float count
    (1117, or 75 092 with ``big``) is no multiple of any block."""
    rng = np.random.RandomState(seed)
    w, b = ((300, 250), 77) if big else ((37, 29), 29)
    return {"w": jnp.asarray(rng.randn(*w), jnp.bfloat16),
            "b": jnp.asarray(rng.randn(b), jnp.float32),
            "count": jnp.asarray(7, jnp.int32),
            "ln": jnp.asarray(rng.randn(5, 3), jnp.float32)}


def _grads(params, seed, scale):
    rng = np.random.RandomState(seed)
    return jax.tree_util.tree_map(
        lambda p: (jnp.asarray(rng.randn(*p.shape), jnp.float32) * scale
                   ).astype(p.dtype)
        if jnp.issubdtype(p.dtype, jnp.floating) else jnp.zeros_like(p),
        params)


def _amp(inner, loss_scale=16.0):
    return AmpOptimizer(inner, LossScaler(loss_scale), master_weights=True)


def _flat_leaves(opt_state):
    """The persistent 1-D buffers of a flat state: masters + moments."""
    return [opt_state.masters.buf] + [
        l for l in jax.tree_util.tree_leaves(opt_state.inner)
        if getattr(l, "ndim", 0) == 1]


def _counter(name="flat_pad_copies_total"):
    m = get_registry().get(name)
    return m.value if m is not None else 0.0


def _labelled(name):
    """``{(label values, sorted by label name): count}`` of a counter."""
    m = get_registry().get(name)
    if m is None:
        return {}
    return {tuple(v for _, v in key): child.value
            for key, child in m.children().items()}


def _since(name, before):
    return {k: v - before.get(k, 0.0) for k, v in _labelled(name).items()
            if v != before.get(k, 0.0)}


def _unowned(lay, buf):
    """The elements of a flat buffer that no leaf owns: the zeros after
    each segment's last leaf."""
    mask = np.ones(buf.shape[0], bool)
    for off, n in zip(lay.offsets, lay.sizes):
        mask[off:off + n] = False
    return np.asarray(buf)[mask]


def _tree_order(lay, buf, length):
    """``buf`` as a snapshot from before the layout kept its leaves by
    dtype held it: the float leaves in tree order, zeros up to
    ``length``."""
    flat = np.asarray(buf)
    out = np.zeros((length,), flat.dtype)
    at = 0
    for off, n in zip(lay.offsets, lay.sizes):
        out[at:at + n] = flat[off:off + n]
        at += n
    return jnp.asarray(out)


@pytest.mark.parametrize("n", [1, 1000, 1024, 1025, 64000, BLOCK_ELEMS,
                               BLOCK_ELEMS + 1, 336226108])
def test_aligned_len_is_what_to_2d_views_for_free(n):
    a = aligned_len(n)
    rows = pick_block_rows(a)
    assert pick_block_rows(n) == rows
    assert a % (rows * LANES) == 0 and n <= a < n + rows * LANES

    def view(x):                      # shapes only: nothing is allocated
        return from_2d(*to_2d(x, rows))
    before = _counter()
    out = jax.eval_shape(view, jax.ShapeDtypeStruct((a,), jnp.float32))
    assert out.shape == (a,) and _counter() == before
    if a != n:
        out = jax.eval_shape(view, jax.ShapeDtypeStruct((n,), jnp.float32))
        assert out.shape == (n,) and _counter() == before + 2


def test_aligned_len_of_nothing_is_nothing():
    assert aligned_len(0) == 0 and _FlatLayout({"i": jnp.asarray(3)}).storage == 0


# -- (a) lengths and logical views --------------------------------------------
@pytest.mark.parametrize("name", sorted(INNERS))
def test_buffers_have_storage_length_views_stay_logical(name):
    params = _params()
    opt = _amp(INNERS[name]())
    state = opt.init(params)
    lay = state.masters.layout
    assert lay.total == 37 * 29 + 29 + 15 == 1117
    # the bf16 leaf, then from the next block the float32 leaves
    assert lay.order == "dtype" and aligned_len(1117) == 2048
    assert lay.segments == ((0, 2048), (2048, 2048)) and lay.storage == 4096
    assert (lay.offsets[3], lay.offsets[0], lay.offsets[2]) == (0, 2048, 2077)
    bufs = _flat_leaves(state)
    assert len(bufs) == {"adam": 3, "sgd": 2, "lion": 2}[name]
    assert {b.shape for b in bufs} == {(lay.storage,)}
    assert _unowned(lay, state.masters.buf).size == 4096 - 1117
    assert not _unowned(lay, state.masters.buf).any()

    # a gradient packs to the same length; the zeros are part of the concat
    g = lay.pack(_grads(params, 1, 1.0))
    assert g.shape == (lay.storage,) and g.dtype == jnp.float32
    assert not _unowned(lay, g).any()

    for tree in (opt.masters_tree(state),
                 lay.unpack_masters(state.masters.buf)):
        assert tree["count"] is None
        for k in ("w", "b", "ln"):
            assert tree[k].shape == params[k].shape
            assert tree[k].dtype == jnp.float32
            np.testing.assert_array_equal(
                np.asarray(tree[k]),
                np.asarray(params[k].astype(jnp.float32)))
    half = state.masters.buf.astype(jnp.bfloat16)
    back = lay.rebuild(state.masters.buf, half,
                       jax.tree_util.tree_leaves(params))
    for k in params:
        assert back[k].dtype == params[k].dtype
        np.testing.assert_array_equal(np.asarray(back[k]),
                                      np.asarray(params[k]))


def test_zero_layout_keeps_its_lengths():
    """Under ZeRO the layout packs the logical length (its callers pad
    to the shard population) — no tail."""
    params = _params()
    lay = _FlatLayout(params, zero_axis="data")
    assert lay.order == "tree" and lay.segments == ((0, lay.total),)
    assert [lay.offsets[i] for i in (0, 2, 3)] == [0, 29, 44]     # b, ln, w
    assert lay.pack(params).shape == (lay.total,)


# -- (b) the tail is inert -----------------------------------------------------
@pytest.mark.parametrize("name", sorted(INNERS))
def test_three_steps_equal_leafwise_updates_and_tail_stays_zero(name):
    scale = 16.0
    params = _params()
    opt = _amp(INNERS[name](), scale)
    state = opt.init(params)
    lay = state.masters.layout

    # op by op, on both sides: inside one compiled program XLA:CPU
    # contracts a*b+c differently in a loop's vector body and in its
    # remainder, and which elements fall into the remainder depends on
    # the buffer's length — an ulp that says nothing about the tail
    def step(*a):
        with jax.disable_jit():
            return opt.step(*a)

    # the same optimizer, one float leaf at a time, on fp32 masters
    inner = INNERS[name]()
    keys = ("w", "b", "ln")
    ref_p = {k: params[k].astype(jnp.float32) for k in keys}
    ref_s = {k: inner.init(ref_p[k]) for k in keys}

    def ref_update(g, s, p):
        with jax.disable_jit():
            return inner.update(
                g.astype(jnp.float32) * (1.0 / jnp.float32(scale)), s, p)

    def check(p, st):
        masters = opt.masters_tree(st)
        for k in keys:
            np.testing.assert_array_equal(np.asarray(masters[k]),
                                          np.asarray(ref_p[k]), err_msg=k)
            np.testing.assert_array_equal(
                np.asarray(p[k]),
                np.asarray(ref_p[k].astype(params[k].dtype)), err_msg=k)
        # moments: every 1-D state buffer against the leafwise state's
        flat_moments = _flat_leaves(st)[1:]
        ref_moments = [
            [l for l in jax.tree_util.tree_leaves(ref_s[k])
             if getattr(l, "ndim", 0) >= 1] for k in keys]
        for i, buf in enumerate(flat_moments):
            tree = lay.unpack_masters(buf)
            for k, rm in zip(keys, ref_moments):
                np.testing.assert_array_equal(
                    np.asarray(tree[k]), np.asarray(rm[i]).reshape(
                        params[k].shape), err_msg=f"{k} moment {i}")
        for buf in _flat_leaves(st):
            assert buf.shape == (lay.storage,)
            assert not _unowned(lay, buf).any()
        assert int(p["count"]) == 7

    p = params
    for it in range(3):
        g = _grads(params, 10 + it, scale)
        p, state, info = step(p, state, g)
        assert float(info["found_inf"]) == 0.0
        for k in keys:
            ref_p[k], ref_s[k] = ref_update(g[k], ref_s[k], ref_p[k])
        check(p, state)

    # an overflowed step is skipped: nothing moves, the tail included
    g = _grads(params, 99, scale)
    g["b"] = g["b"].at[3].set(jnp.inf)
    p2, state2, info = step(p, state, g)
    assert float(info["found_inf"]) == 1.0
    assert int(info["steps_skipped"]) == 1
    check(p2, state2)


# -- (c) no pad, no slice under Pallas dispatch -------------------------------
_COPY_PRIMS = ("pad", "slice", "dynamic_slice", "gather")


def _copies_under(jaxpr, scopes, min_len, prefix=""):
    """(scope path, primitive, operand shape) of every pad / slice whose
    1-D operand has at least ``min_len`` elements, under one of
    ``scopes`` — through cond branches and nested jits."""
    found = []
    for eqn in jaxpr.eqns:
        path = f"{prefix}/{eqn.source_info.name_stack}"
        if (eqn.primitive.name in _COPY_PRIMS
                and any(s in path for s in scopes)):
            shape = eqn.invars[0].aval.shape
            if len(shape) == 1 and shape[0] >= min_len:
                found.append((path, eqn.primitive.name, shape))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += _copies_under(sub, scopes, min_len, path)
    return found


@pytest.fixture
def pallas_dispatch(monkeypatch):
    monkeypatch.setenv("APEX_TPU_DISABLE_PALLAS", "0")
    monkeypatch.setenv("APEX_TPU_FORCE_PALLAS", "1")


def test_flat_step_holds_no_pad_and_no_slice(pallas_dispatch):
    params = _params(big=True)
    opt = _amp(INNERS["adam"]())
    state = opt.init(params)
    lay = state.masters.layout
    assert lay.total == 75_092
    assert lay.segments == ((0, 2 * BLOCK_ELEMS), (2 * BLOCK_ELEMS, BLOCK_ELEMS))
    grads = _grads(params, 3, 16.0)

    before = _counter(), _counter("flat_pad_copy_elements_total")
    engaged = _labelled("amp_unscale_total"), _labelled("amp_grad_pack_total")
    jaxpr = jax.make_jaxpr(opt.step)(params, state, grads)
    assert (_counter(), _counter("flat_pad_copy_elements_total")) == before
    # the counters that say the gradient went to the kernel as it came
    assert _since("amp_unscale_total", engaged[0]) == {("kernel",): 1.0}
    assert _since("amp_grad_pack_total", engaged[1]) == {
        ("bfloat16", "native"): 1.0, ("float32", "native"): 1.0}
    text = str(jaxpr)
    assert text.count("_adam_flat") == 2 and "_scale_flat" not in text
    assert _copies_under(jaxpr.jaxpr, ("optim.adam", "amp.unscale"),
                         lay.total) == []
    # the walker does see such copies where they are: amp.rebuild takes
    # each leaf out of the flat buffer
    assert _copies_under(jaxpr.jaxpr, ("amp.rebuild",), lay.total)

    # run through the kernels (interpret mode), the tail stays zero
    _, new_s, _ = jax.jit(opt.step)(params, state, grads)
    for buf in _flat_leaves(new_s):
        assert not _unowned(lay, buf).any()


def test_flat_step_under_pallas_matches_jnp_path(monkeypatch):
    params = _params(big=True)
    grads = _grads(params, 3, 16.0)
    out = {}
    for mode in ("jnp", "pallas"):
        monkeypatch.setenv("APEX_TPU_DISABLE_PALLAS",
                           "1" if mode == "jnp" else "0")
        monkeypatch.setenv("APEX_TPU_FORCE_PALLAS",
                           "0" if mode == "jnp" else "1")
        opt = _amp(INNERS["adam"]())
        out[mode] = opt.step(params, opt.init(params), grads)
    (pj, sj, _), (pp, sp, _) = out["jnp"], out["pallas"]
    for a, b in zip(_flat_leaves(sj), _flat_leaves(sp)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6, atol=1e-7)
    for k in ("w", "b", "ln"):
        np.testing.assert_allclose(np.asarray(pj[k], np.float32),
                                   np.asarray(pp[k], np.float32),
                                   rtol=1e-2, atol=1e-6)


def test_unaligned_fused_adam_still_pads_matches_and_is_counted(
        pallas_dispatch):
    from apex_tpu.ops.pallas_adam import fused_adam
    n = 70_001                      # a length no other test traces
    rng = np.random.RandomState(4)
    p, m, g = (jnp.asarray(rng.randn(n), jnp.float32) for _ in range(3))
    v = jnp.asarray(rng.rand(n), jnp.float32)
    before = _counter(), _counter("flat_pad_copy_elements_total")
    new_p, new_m, new_v, half = fused_adam(
        p, m, v, g, 1e-2, 4.0, 0.9, 0.999, 1e-8, False, 0.01, jnp.bfloat16)
    # four operands padded, four results sliced
    assert _counter() - before[0] == 8
    assert (_counter("flat_pad_copy_elements_total") - before[1]
            == 4 * 2 * BLOCK_ELEMS + 4 * n)
    gs = g / 4.0
    rm = 0.9 * m + (1.0 - 0.9) * gs
    rv = 0.999 * v + (1.0 - 0.999) * gs * gs
    rp = p - 1e-2 * (rm / (jnp.sqrt(rv) + 1e-8) + 0.01 * p)
    for got, want in ((new_p, rp), (new_m, rm), (new_v, rv)):
        assert got.shape == (n,)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-6)
    assert half.shape == (n,) and half.dtype == jnp.bfloat16


def test_unaligned_multi_tensor_scale_still_pads_matches_and_is_counted(
        pallas_dispatch):
    from apex_tpu.ops import pallas_multi_tensor as pk
    n = 70_003
    x = jnp.asarray(np.random.RandomState(5).randn(n), jnp.float32)
    before = _counter()
    out, flag = pk.multi_tensor_scale([x], 0.25)
    assert _counter() - before == 2      # one pad in, one slice out
    np.testing.assert_array_equal(np.asarray(out[0]), np.asarray(x) * 0.25)
    assert float(flag) == 0.0


# -- (d) a state saved at the old length --------------------------------------
@pytest.mark.parametrize("name", sorted(INNERS))
def test_old_length_snapshot_restores_and_steps_identically(name, tmp_path,
                                                            monkeypatch):
    params = _params()
    opt = _amp(INNERS[name]())
    state = opt.init(params)
    step = jax.jit(opt.step)
    p, state, _ = step(params, state, _grads(params, 20, 16.0))
    lay = state.masters.layout

    # what a state from before PR 25 saved: every flat buffer in tree
    # order at the logical length
    def cut(l):
        return (_tree_order(lay, l, lay.total)
                if getattr(l, "ndim", 0) == 1 else l)
    old = state._replace(
        inner=jax.tree_util.tree_map(cut, state.inner),
        masters=FlatMasters(cut(state.masters.buf), lay))
    assert {b.shape for b in _flat_leaves(old)} == {(lay.total,)}
    with monkeypatch.context() as m:    # and no word on the order
        m.setattr(ckpt, "flat_orders", lambda tree: {})
        ckpt.save_checkpoint(str(tmp_path), 1, {"p": p, "opt": old})

    template = {"p": params, "opt": opt.init(params)}
    got = ckpt.restore_checkpoint(str(tmp_path), template)
    for a, b in zip(jax.tree_util.tree_leaves(got["opt"]),
                    jax.tree_util.tree_leaves(state)):
        assert a.shape == b.shape and a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    g = _grads(params, 21, 16.0)
    want = step(p, state, g)
    have = step(got["p"], got["opt"], g)
    for a, b in zip(jax.tree_util.tree_leaves(have[:2]),
                    jax.tree_util.tree_leaves(want[:2])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_restore_still_refuses_a_wrong_length(tmp_path):
    params = _params()
    opt = _amp(INNERS["adam"]())
    state = opt.init(params)
    lay = state.masters.layout
    bad = state._replace(masters=FlatMasters(
        state.masters.buf[:lay.total - 1], lay))
    ckpt.save_checkpoint(str(tmp_path), 1, bad)
    with pytest.raises(ValueError, match="shape mismatch"):
        ckpt.restore_checkpoint(str(tmp_path), state)


# -- (e) how a leaf leaves a flat buffer --------------------------------------
def _leaves_of_widths(aligned: bool, rows: int = 8):
    """bf16 leaves of five last dimensions (64, 256, 512, 1024 and a 1-D
    bias), a float32 (rows, 64) leaf as a router keeps, a float32 norm and
    an int leaf; ``aligned`` puts every offset on a multiple of 128."""
    bias, taps = (256, 128) if aligned else (77, 15)
    z = jnp.zeros
    return {"a_qkv": z((rows, 1024), jnp.bfloat16),
            "b_bias": z((bias,), jnp.bfloat16),
            "c_head": z((rows * 2, 64), jnp.bfloat16),
            "d_router": z((rows * 3, 64), jnp.float32),
            "e_experts": z((2, rows, 512), jnp.bfloat16),
            "f_norm": z((taps,), jnp.float32),
            "g_down": z((rows * 4, 256), jnp.bfloat16),
            "step": jnp.asarray(3, jnp.int32)}


@pytest.mark.parametrize("aligned", [True, False], ids=["aligned", "unaligned"])
def test_leaves_come_out_of_the_buffers_as_numpy_slices_them(aligned):
    params = _leaves_of_widths(aligned)
    lay = _FlatLayout(params)
    on_tiles = all(o % LANES == 0 for o in lay.offsets)
    assert on_tiles == aligned
    rng = np.random.RandomState(5)
    flat32 = jnp.asarray(rng.randn(lay.storage), jnp.float32)
    half = flat32.astype(jnp.bfloat16)
    like = jax.tree_util.tree_leaves(params)

    def bits(x):
        x = np.asarray(x)
        return x.view(np.uint16) if x.dtype == jnp.bfloat16 else x

    def want(src, i, dtype):
        cut = np.asarray(src)[lay.offsets[i]:lay.offsets[i] + lay.sizes[i]]
        return bits(cut.reshape(lay.shapes[i]).astype(dtype))

    for rebuild in (lay.rebuild, jax.jit(lay.rebuild)):
        with_half = jax.tree_util.tree_leaves(rebuild(flat32, half, like))
        cast = jax.tree_util.tree_leaves(rebuild(flat32, None, like))
        for i, (l, f) in enumerate(zip(like, lay.is_float)):
            if not f:
                assert int(with_half[i]) == int(cast[i]) == 3
                continue
            src = half if l.dtype == jnp.bfloat16 else flat32
            for got in (with_half[i], cast[i]):
                assert got.dtype == l.dtype and got.shape == l.shape
                np.testing.assert_array_equal(bits(got), want(src, i, l.dtype))
    masters = lay.unpack_masters(flat32)
    assert masters["step"] is None
    got = jax.tree_util.tree_leaves(masters)          # the None leaf drops out
    floats = [i for i, f in enumerate(lay.is_float) if f]
    assert len(got) == len(floats)
    for g, i in zip(got, floats):
        assert g.dtype == jnp.float32
        np.testing.assert_array_equal(np.asarray(g), want(flat32, i, np.float32))


@pytest.mark.parametrize("source", ["bfloat16", "float32"])
def test_v5e_cuts_each_leaf_out_of_the_buffers_before_it_is_reshaped(one_chip, past_the_cache,
                                                                     source):
    """The TPU's compiler turns a slice that is reshaped round into a
    reshape of the WHOLE buffer, once for every distinct last dimension
    among the leaves, and cuts rows out of that.  With the piece held
    whole (``_cut``) no result of the compiled program has the buffer's
    size and no temporary of that size is planned: ``bfloat16`` reads
    ``rebuild`` (the kernel's half copy, and the float32 buffer for the
    router and the norm), ``float32`` reads ``unpack_masters``."""
    # larger than the chip's fast memory, as a model's buffers are: a
    # buffer that fits there the compiler may stage whole, in slices
    lay = _FlatLayout(_leaves_of_widths(True, rows=12288))
    assert lay.total * 2 > 64 * 2 ** 20
    flat32 = jax.ShapeDtypeStruct((lay.storage,), jnp.float32, sharding=one_chip)
    half = jax.ShapeDtypeStruct((lay.storage,), jnp.bfloat16, sharding=one_chip)
    step = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    if source == "bfloat16":
        def leaves(flat32, half, step):
            like = [step if not f else None for f in lay.is_float]
            return lay.rebuild(flat32, half, like)
        compiled = jax.jit(leaves).lower(flat32, half, step).compile()
    else:
        compiled = jax.jit(lay.unpack_masters).lower(flat32).compile()
    results = re.findall(r"^\s*(?:ROOT )?%?[\w.\-]+ = \w+\[([\d,]*)\]\S* ([\w\-]+)\(",
                         compiled.as_text(), re.M)
    assert len(results) > len(lay.shapes)
    whole = [(op, dims) for dims, op in results if op != "parameter"
             and np.prod([int(d) for d in dims.split(",") if d]) >= lay.total]
    assert whole == []
    assert compiled.memory_analysis().temp_size_in_bytes < 2 * lay.total


# -- (f) the gradient reaches the Adam kernel as the backward wrote it --------
class _PassAdam(FusedAdam):
    """FusedAdam as an inner optimizer that takes no scale: amp packs the
    gradient to float32, unscales it by a pass of its own (``_scale_flat``)
    and runs the kernel once over the whole buffer, the path every step
    took before the kernel unscaled."""
    unscales_grads = False


def _mixed(seed=0):
    """What an O2 tree holds: bf16 matrices and an unaligned bf16 bias,
    float32 norm, router and selection-bias leaves, an int leaf."""
    rng = np.random.RandomState(seed)

    def leaf(dtype, *shape):
        return jnp.asarray(rng.randn(*shape), dtype)
    return {"attn": leaf(jnp.bfloat16, 64, 48), "bias": leaf(jnp.bfloat16, 77),
            "expert_bias": leaf(jnp.float32, 8), "mlp": leaf(jnp.bfloat16, 48, 200),
            "norm": leaf(jnp.float32, 48), "router": leaf(jnp.float32, 48, 8),
            "step": jnp.asarray(3, jnp.int32)}


def _dispatch(monkeypatch, mode):
    monkeypatch.setenv("APEX_TPU_DISABLE_PALLAS", "1" if mode == "jnp" else "0")
    monkeypatch.setenv("APEX_TPU_FORCE_PALLAS", "0" if mode == "jnp" else "1")


def _bits(tree):
    return [np.asarray(l).view(np.uint16) if l.dtype == jnp.bfloat16
            else np.asarray(l) for l in jax.tree_util.tree_leaves(tree)]


def _assert_same_bits(a, b):
    for x, y in zip(_bits(a), _bits(b), strict=True):
        assert x.dtype == y.dtype and x.shape == y.shape
        np.testing.assert_array_equal(x, y)


@pytest.mark.parametrize("mode", ["jnp", "pallas"])
@pytest.mark.parametrize("scale", [1.0, 2.0 ** 16])
def test_three_steps_with_the_kernel_unscaling_equal_the_pass_bit_for_bit(
        scale, mode, monkeypatch):
    _dispatch(monkeypatch, mode)
    params = _mixed()
    new = _amp(FusedAdam(lr=1e-2, weight_decay=0.01), scale)
    old = _amp(_PassAdam(lr=1e-2, weight_decay=0.01), scale)
    (pn, sn), (po, so) = (params, new.init(params)), (params, old.init(params))
    lay = sn.masters.layout
    assert lay == so.masters.layout and len(lay.segments) == 2
    for it in range(3):
        g = _grads(params, 30 + it, scale)
        # float32 gradients that no bf16 number holds
        g["norm"] = g["norm"] * jnp.float32(1.0 + 2.0 ** -12)
        assert not np.array_equal(np.asarray(g["norm"]), np.asarray(
            g["norm"].astype(jnp.bfloat16).astype(jnp.float32)))
        packed = lay.pack_grads(g).parts
        assert [p.dtype for p in packed] == [jnp.bfloat16, jnp.float32]
        at = lay.offsets[4] - lay.segments[1][0]
        np.testing.assert_array_equal(np.asarray(packed[1][at:at + 48]),
                                      np.asarray(g["norm"]))
        with jax.disable_jit():      # op by op: see (b) above
            pn, sn, info_n = new.step(pn, sn, g)
            po, so, info_o = old.step(po, so, g)
        _assert_same_bits((pn, sn), (po, so))
        assert float(info_n["found_inf"]) == 0.0
        np.testing.assert_allclose(float(info_n["grad_norm"]),
                                   float(info_o["grad_norm"]), rtol=1e-6)
    # and the float32 leaf's gradient went into the moments unrounded
    m = lay.unpack_masters(sn.inner.m)["norm"]
    assert np.asarray(m).any()


@pytest.mark.parametrize("scale", [1.0, 2.0 ** 16])
def test_clipping_on_the_scaled_segments_matches_clipping_the_unscaled_buffer(
        scale):
    params = _mixed()
    adam = dict(lr=1e-2, weight_decay=0.01, max_grad_norm=0.5)
    new, old = _amp(FusedAdam(**adam), scale), _amp(_PassAdam(**adam), scale)
    g = _grads(params, 40, scale)
    pn, sn, _ = new.step(params, new.init(params), g)
    po, so, _ = old.step(params, old.init(params), g)
    # it clipped: the unclipped update differs
    pu, _, _ = _amp(FusedAdam(lr=1e-2, weight_decay=0.01), scale).step(
        params, new.init(params), g)
    assert not np.array_equal(_bits(pn)[0], _bits(pu)[0])
    for a, b in zip(_flat_leaves(sn), _flat_leaves(so)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6, atol=1e-9)


@pytest.mark.parametrize("bad", [np.inf, np.nan], ids=["inf", "nan"])
@pytest.mark.parametrize("leaf", ["attn", "router"], ids=["half", "float32"])
def test_a_nonfinite_gradient_skips_the_step_and_halves_a_dynamic_scale(
        leaf, bad):
    params = _mixed()
    opt = AmpOptimizer(FusedAdam(lr=1e-2), LossScaler("dynamic"),
                       master_weights=True)
    state = opt.init(params)
    step = jax.jit(opt.step)
    p, state, info = step(params, state, _grads(params, 50, 2.0 ** 16))
    assert float(info["found_inf"]) == 0.0 and int(info["steps_skipped"]) == 0
    g = _grads(params, 51, 2.0 ** 16)
    g[leaf] = g[leaf].at[(1,) * g[leaf].ndim].set(bad)
    p2, state2, info = step(p, state, g)
    assert float(info["found_inf"]) == 1.0
    assert int(info["steps_skipped"]) == 1
    assert float(info["loss_scale"]) == 2.0 ** 15
    _assert_same_bits((p2, state2.masters, state2.inner),
                      (p, state.masters, state.inner))
    assert int(state2.inner.step) == int(state.inner.step) == 1
    # and the next finite step applies, at the halved scale
    _, state3, info = step(p2, state2, _grads(params, 52, 2.0 ** 15))
    assert float(info["found_inf"]) == 0.0 and int(state3.inner.step) == 2


def _only(tree, keep):
    return {k: v for k, v in tree.items() if keep(v)}


@pytest.mark.parametrize("leaves,launches", [("mixed", 2), ("bfloat16", 1),
                                             ("float32", 1)])
def test_v5e_step_unscales_in_one_adam_launch_a_segment(one_chip, for_the_chip,
                                                        leaves, launches):
    """The step compiled for a described v5e: no pass of its own to unscale,
    the kernel launched once for every segment that holds a leaf, and no
    float32 array of the buffers' length beyond masters, moments and what
    the launches make of them (the packed gradient is bf16 where the
    leaves are)."""
    params = _leaves_of_widths(True, rows=12288)      # past the fast memory
    if leaves != "mixed":
        params = _only(params, lambda v: v.dtype == jnp.dtype(leaves))
    opt = _amp(FusedAdam(lr=1e-2, weight_decay=0.01), 1.0)
    state = jax.eval_shape(opt.init, params)
    lay = state.masters.layout
    assert len(lay.segments) == launches and lay.storage > 16 * BLOCK_ELEMS

    def on_chip(tree):
        return jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
            tree)
    # parameters and state out, as the cells' steps: the gauge is dropped
    text = jax.jit(lambda p, s, g: opt.step(p, s, g)[:2], donate_argnums=(0, 1)
                   ).lower(on_chip(params), on_chip(state), on_chip(params)
                           ).compile().as_text()
    assert "_scale_flat" not in text
    assert len(re.findall(r"= \(.*\) custom-call\(.*_adam_flat", text)) == launches
    whole = "(?:%d|%d,128)" % (lay.storage, lay.storage // LANES)
    made = re.findall(r"^\s*(?:ROOT )?%?[\w.\-]+ = f32\[" + whole
                      + r"\]\S* ([\w\-]+)\(", text, re.M)
    if leaves == "float32":
        return              # there the packed gradient is such an array
    # (the three copies are the skip branch's, of the state it hands back)
    assert made.count("copy") <= 3 and set(made) <= {
        "parameter", "bitcast", "get-tuple-element", "copy"}


# -- (g) what keeps the float32 pack and the pass ------------------------------
def _zero1_step(opt, params, grads):
    from jax.sharding import Mesh, PartitionSpec as P
    from apex_tpu import amp
    mesh = Mesh(np.asarray(jax.devices()[:2]), ("data",))
    specs = amp.zero_optimizer_specs(opt, params, "data")
    state = jax.jit(jax.shard_map(
        lambda p: opt.init(p, zero_axis="data"), mesh=mesh, in_specs=(P(),),
        out_specs=specs, check_vma=False))(params)
    assert state.masters.layout.order == "tree"
    return jax.make_jaxpr(jax.shard_map(
        lambda p, s, g: opt.step(p, s, g)[:2], mesh=mesh,
        in_specs=(P(), specs, P()), out_specs=(P(), specs),
        check_vma=False))(params, state, grads)


@pytest.mark.parametrize("case", ["lamb", "sgd", "zero1", "two_half_dtypes"])
def test_the_float32_pack_and_the_pass_stay_where_no_kernel_unscales(
        case, pallas_dispatch):
    from apex_tpu.optimizers import FusedLAMB
    params = _mixed()
    if case == "two_half_dtypes":
        params["bias"] = params["bias"].astype(jnp.float16)
    inner = {"lamb": lambda: FusedLAMB(lr=1e-2),
             "sgd": INNERS["sgd"]}.get(case, INNERS["adam"])()
    opt = _amp(inner)
    grads = _grads(params, 60, 16.0)
    before = _labelled("amp_unscale_total"), _labelled("amp_grad_pack_total")
    if case == "zero1":
        jaxpr = _zero1_step(opt, params, grads)
    else:
        state = opt.init(params)
        if case == "two_half_dtypes":
            lay = state.masters.layout      # today's order: as the tree goes
            assert lay.order == "tree" and lay.half_dtype is None
            assert lay.segments == ((0, aligned_len(lay.total)),)
            floats = [i for i, f in enumerate(lay.is_float) if f]
            assert [lay.offsets[i] for i in floats] == list(
                np.cumsum([0] + [lay.sizes[i] for i in floats])[:-1])
        jaxpr = jax.make_jaxpr(opt.step)(params, state, grads)
    assert _since("amp_unscale_total", before[0]) == {("pass",): 1.0}
    assert _since("amp_grad_pack_total", before[1]) == {}
    text = str(jaxpr)
    assert "_scale_flat" in text
    if case != "lamb":             # LAMB keeps a master tree: nothing packed
        packs = [e for e in _eqns(jaxpr.jaxpr) if e.primitive.name == "concatenate"
                 and "amp.pack" in str(e.source_info.name_stack)]
        assert packs and all(e.outvars[0].aval.dtype == jnp.float32 for e in packs)


def _eqns(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _eqns(sub)


# -- (h) a snapshot says which order it holds ---------------------------------
@pytest.mark.parametrize("held", ["logical", "aligned"])
def test_tree_order_snapshot_restores_into_the_layout_by_dtype(held, tmp_path,
                                                               monkeypatch):
    """What PR 25 to PR 38 saved (tree order, ``aligned_len(total)``) and
    what came before (tree order, ``total``): no word on the order, moved
    leaf by leaf.  On a tree where the two orders differ and have ONE
    length at the aligned size, so that length alone cannot tell."""
    params = {"a_norm": jnp.ones((40,), jnp.float32),
              "b_w": jnp.full((BLOCK_ELEMS - 8,), 0.5, jnp.bfloat16)}
    opt = _amp(INNERS["adam"]())
    step = jax.jit(opt.step)
    p, state, _ = step(params, opt.init(params), _grads(params, 70, 16.0))
    lay = state.masters.layout
    assert lay.offsets == (BLOCK_ELEMS, 0) and lay.storage == 2 * BLOCK_ELEMS
    n = lay.total if held == "logical" else aligned_len(lay.total)
    assert (n == lay.storage) == (held == "aligned")

    def old(l):
        return _tree_order(lay, l, n) if getattr(l, "ndim", 0) == 1 else l
    was = state._replace(inner=jax.tree_util.tree_map(old, state.inner),
                         masters=FlatMasters(old(state.masters.buf), lay))
    with monkeypatch.context() as m:
        m.setattr(ckpt, "flat_orders", lambda tree: {})
        ckpt.save_checkpoint(str(tmp_path), 1, {"p": p, "opt": was})
    got = ckpt.restore_checkpoint(str(tmp_path),
                                  {"p": params, "opt": opt.init(params)})
    _assert_same_bits(got["opt"], state)
    g = _grads(params, 71, 16.0)
    _assert_same_bits(step(got["p"], got["opt"], g)[:2], step(p, state, g)[:2])


def test_a_snapshot_says_its_order_and_round_trips(tmp_path):
    params = _mixed()
    opt = _amp(INNERS["adam"]())
    p, state, _ = jax.jit(opt.step)(params, opt.init(params),
                                    _grads(params, 72, 16.0))
    path = ckpt.save_checkpoint(str(tmp_path), 3, {"p": p, "opt": state})
    with np.load(path) as stored:
        said = bytes(stored["__flat_order__"]).decode()
    assert said == '{"%d": "dtype"}' % state.masters.layout.storage
    got = ckpt.restore_checkpoint(str(tmp_path),
                                  {"p": params, "opt": opt.init(params)})
    _assert_same_bits((got["p"], got["opt"]), (p, state))


@pytest.mark.parametrize("backend", ["npz", "orbax"])
def test_a_snapshot_in_an_order_the_template_cannot_take_is_refused(
        backend, tmp_path, monkeypatch):
    if backend == "npz":
        # a layout in tree order (two half dtypes) offered a snapshot that
        # says its buffers of that length are by dtype
        params = _mixed()
        params["bias"] = params["bias"].astype(jnp.float16)
        state = _amp(INNERS["adam"]()).init(params)
        assert state.masters.layout.order == "tree"
        with monkeypatch.context() as m:
            m.setattr(ckpt, "flat_orders", lambda tree: {
                str(state.masters.layout.storage): "dtype"})
            ckpt.save_checkpoint(str(tmp_path), 1, state)
        with pytest.raises(ValueError, match="cannot be placed"):
            ckpt.restore_checkpoint(str(tmp_path), state)
        return
    pytest.importorskip("orbax.checkpoint")
    from apex_tpu.utils import checkpoint_orbax as co
    # Orbax restores by shape: a snapshot that does not say "dtype" is not
    # taken into a layout whose two segments both hold leaves
    state = _amp(INNERS["adam"]()).init(_mixed())
    with monkeypatch.context() as m:
        m.setattr(co, "flat_orders", lambda tree: {})
        co.save_checkpoint(str(tmp_path), 1, state)
    with pytest.raises(ValueError, match="'tree' order"):
        co.restore_checkpoint(str(tmp_path), state)
    co.save_checkpoint(str(tmp_path), 2, state)
    _assert_same_bits(co.restore_checkpoint(str(tmp_path), state), state)
