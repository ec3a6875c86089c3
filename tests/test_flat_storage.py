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
    assert lay.storage == aligned_len(1117) == 2048
    bufs = _flat_leaves(state)
    assert len(bufs) == {"adam": 3, "sgd": 2, "lion": 2}[name]
    assert {b.shape for b in bufs} == {(lay.storage,)}
    assert not np.asarray(state.masters.buf[lay.total:]).any()

    # a gradient packs to the same length; the tail is part of the concat
    g = lay.pack(_grads(params, 1, 1.0))
    assert g.shape == (lay.storage,)
    assert not np.asarray(g[lay.total:]).any()

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
    lay = _FlatLayout(params)
    lay.zero_axis = "data"
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
            assert not np.asarray(buf[lay.total:]).any()
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
    assert lay.total == 75_092 and lay.storage == 2 * BLOCK_ELEMS
    grads = _grads(params, 3, 16.0)

    before = _counter(), _counter("flat_pad_copy_elements_total")
    jaxpr = jax.make_jaxpr(opt.step)(params, state, grads)
    assert (_counter(), _counter("flat_pad_copy_elements_total")) == before
    text = str(jaxpr)
    assert "_adam_flat" in text and "_scale_flat" in text
    assert _copies_under(jaxpr.jaxpr, ("optim.adam", "amp.unscale"),
                         lay.total) == []
    # the walker does see such copies where they are: amp.rebuild takes
    # each leaf out of the flat buffer
    assert _copies_under(jaxpr.jaxpr, ("amp.rebuild",), lay.total)

    # run through the kernels (interpret mode), the tail stays zero
    _, new_s, _ = jax.jit(opt.step)(params, state, grads)
    for buf in _flat_leaves(new_s):
        assert not np.asarray(buf[lay.total:]).any()


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
def test_old_length_snapshot_restores_and_steps_identically(name, tmp_path):
    params = _params()
    opt = _amp(INNERS[name]())
    state = opt.init(params)
    step = jax.jit(opt.step)
    p, state, _ = step(params, state, _grads(params, 20, 16.0))
    lay = state.masters.layout

    # what the parent of this change saved: every flat buffer at the
    # logical length
    def cut(l):
        return l[:lay.total] if getattr(l, "ndim", 0) == 1 else l
    old = state._replace(
        inner=jax.tree_util.tree_map(cut, state.inner),
        masters=FlatMasters(cut(state.masters.buf), lay))
    assert {b.shape for b in _flat_leaves(old)} == {(lay.total,)}
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
    lay = _FlatLayout(_leaves_of_widths(True, rows=2048))
    assert lay.total > 16 * BLOCK_ELEMS
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
