"""The grouped matrix products of ``ops/pallas_grouped_matmul.py`` in the
interpreter against ``lax.ragged_dot`` and its ``jax.grad``: the forward,
the rows' gradient and the stack's gradient, over group sizes that leave
groups empty, smaller than a tile, off every tile edge, filling the buffer
and leaving half of it dead; what lies in the operands outside every group
(NaN here) reaches nothing, and the results read exactly zero there."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from apex_tpu.ops import pallas_grouped_matmul as pgm
from apex_tpu.parallel import expert_parallel as ep

R, K, N, TILE = 1024, 256, 128, 128

SIZES = {
    "an_empty_group_a_small_one_and_a_dead_tail": [100, 0, 300, 57, 128, 200],
    "boundaries_off_every_tile_edge_filling_the_buffer": [171, 171, 170, 171, 170, 171],
    "boundaries_on_tile_edges_filling_the_buffer": [256, 128, 128, 256, 128, 128],
    "a_dead_tail_of_half_the_buffer": [128, 64, 64, 128, 64, 64],
    "every_group_empty": [0, 0, 0, 0, 0, 0],
    "one_group_holds_every_row": [0, 0, 1024, 0, 0, 0],
    "groups_cut_where_the_buffer_ends": [600, 600, 5, 0, 0, 0],
    "groups_smaller_than_a_tile_inside_one_tile": [5, 9, 0, 30, 17, 3],
}


def _reference(x, w, sizes):
    """The parent's ``gdot``: ragged_dot between its masks, fp32 results."""
    live = (jnp.arange(x.shape[0]) < jnp.sum(sizes))[:, None]
    y = lax.ragged_dot(jnp.where(live, x, 0), w, sizes, preferred_element_type=jnp.float32)
    return jnp.where(live, y, 0).astype(x.dtype)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["fp32", "bf16"])
@pytest.mark.parametrize("case", sorted(SIZES))
def test_the_three_products_agree_with_ragged_dot_and_own_the_dead_rows(case, dtype):
    rng = np.random.RandomState(len(case))
    asked = np.asarray(SIZES[case])
    # what the buffer has room for, group after group
    ends = np.minimum(np.cumsum(asked), R)
    cut = jnp.asarray(np.diff(np.r_[0, ends]), jnp.int32)
    held = int(ends[-1])
    live = (np.arange(R) < held)[:, None]
    x = jnp.asarray(rng.randn(R, K), dtype)
    w = jnp.asarray(rng.randn(len(asked), K, N) / 16, dtype)
    dy = jnp.asarray(rng.randn(R, N), dtype)

    y0, vjp0 = jax.vjp(lambda x, w: _reference(x, w, cut), x, w)
    dx0, dw0 = vjp0(jnp.where(live, dy, 0))
    # NaN wherever no group is, in the rows and in the cotangent
    items = pgm.work_items(jnp.asarray(asked, jnp.int32), R, TILE)
    y1, vjp1 = jax.vjp(lambda x, w: pgm.grouped_matmul(x, w, items, TILE),
                       jnp.where(live, x, jnp.nan), w)
    dx1, dw1 = vjp1(jnp.where(live, dy, jnp.nan))

    assert y1.dtype == dx1.dtype == dw1.dtype == dtype and dw1.shape == w.shape
    tol = dict(rtol=2e-5, atol=2e-5) if dtype == jnp.float32 else dict(rtol=2e-2, atol=2e-2)
    for name, want, got in (("y", y0, y1), ("dx", dx0, dx1), ("dw", dw0, dw1)):
        want, got = np.asarray(want, np.float32), np.asarray(got, np.float32)
        assert np.isfinite(got).all(), name
        scale = max(1.0, float(np.abs(want).max()))
        np.testing.assert_allclose(got / scale, want / scale, err_msg=name, **tol)
    for name, got in (("y", y1), ("dx", dx1)):
        assert not np.asarray(got, np.float32)[held:].any(), f"{name} outside every group"
    empty = np.asarray(cut) == 0
    assert not np.asarray(dw1, np.float32)[empty].any()


@pytest.mark.parametrize("tile", [8, 128, 512])
def test_work_items_visit_every_live_row_once_and_every_tile_at_least_once(tile):
    rng = np.random.RandomState(tile)
    rows, groups = 4096, 7
    for trial in range(8):
        held = [0, rows, rows // 2, 777, 3000, rows, 4000, 1][trial]
        sizes = rng.multinomial(held, rng.dirichlet(np.ones(groups) * (0.3 if trial % 2 else 5)))
        if trial == 5:
            sizes[:] = 0
            sizes[3] = rows
        both = pgm.work_items(jnp.asarray(sizes, jnp.int32), rows, tile)
        for for_stack in (False, True):
            scalars = [np.asarray(a) for a in both[for_stack]]
            offsets, group, read, at, kind = scalars if len(scalars) == 5 else (
                *scalars[:3], scalars[2], scalars[3])
            assert len(group) == rows // tile + groups - 1
            assert offsets.tolist() == np.r_[0, np.cumsum(sizes)].tolist()
            covered = np.zeros(rows, int)
            work = kind != pgm._SKIP if for_stack else np.isin(kind, (pgm._FIRST, pgm._AGAIN))
            for g, t in zip(group[work], at[work]):
                lo, hi = max(offsets[g], t * tile), min(offsets[g + 1], (t + 1) * tile)
                covered[lo:hi] += 1
            assert (covered[:held] == 1).all() and not covered[held:].any()
            # a block that stays where it was is not fetched again: a group's items lie together
            runs = [g for g, before in zip(group, np.r_[-1, group[:-1]]) if g != before]
            assert len(runs) == len(set(runs))
            if for_stack:
                # every group is opened and closed once, the empty ones too, and adds only its rows
                assert sorted(group[kind & pgm._OPENS != 0]) == list(range(groups))
                assert sorted(group[kind & pgm._CLOSES != 0]) == list(range(groups))
                assert ((kind & pgm._ADDS != 0) == (work & (sizes[group] > 0))).all()
            else:
                # every tile of the result is written: first by a product or by zeros, once
                fresh = np.isin(kind, (pgm._FIRST, pgm._ZERO))
                assert sorted(at[fresh]) == list(range(rows // tile))
                # a tile outside every group fetches nothing: its operand stays the last item's
                dead = kind == pgm._ZERO
                assert (at[dead] * tile >= held).all()
                last = np.flatnonzero(work)[-1] if work.any() else 0
                assert (read[~work] == read[last]).all() and (group[~work] == group[last]).all()


def test_the_tile_follows_the_shapes_and_refuses_what_the_kernel_cannot_take():
    bf16 = jnp.bfloat16
    # the two cells, both directions of their three stacks
    assert pgm.row_tile(32768, 2304, 896, 16, bf16) == pgm.row_tile(32768, 896, 2304, 16, bf16) != 0
    assert pgm.row_tile(16384, 2048, 512, 16, bf16) == pgm.row_tile(16384, 512, 2048, 16, bf16) != 0
    for R_, K_, N_, dtype in ((1024, 100, 128, bf16), (1024, 128, 72, bf16), (1000, 128, 128, bf16),
                              (1024, 128, 128, jnp.float16), (1024, 128, 128, jnp.int8)):
        assert pgm.row_tile(R_, K_, N_, 4, dtype) == 0, (R_, K_, N_, dtype)
    # a tile is never more than the rows, and fp32 operands fit too
    assert pgm.row_tile(128, 128, 128, 4, jnp.float32) == 128
    with pytest.raises(ValueError, match="grouped_matmul needs"):
        pgm.grouped_matmul(jnp.zeros((64, 100), bf16), jnp.zeros((4, 100, 128), bf16),
                           pgm.work_items(jnp.zeros((4,), jnp.int32), 64, 8), 8)
    for K_, N_ in ((2304, 896), (896, 2304), (2048, 512), (512, 2048), (128, 128)):
        bk, bn = pgm._stack_blocks(K_, N_)
        assert K_ % bk == 0 and N_ % bn == 0 and bk % 128 == 0 and bn % 128 == 0
        assert (bk == K_ or bn == N_) and bk * bn * 4 <= max(pgm._ACCUMULATOR, 128 * max(K_, N_) * 4)


def _grown(before):
    calls = _calls()
    return {k: v - before.get(k, 0) for k, v in calls.items() if v != before.get(k, 0)}


def _calls():
    from apex_tpu.observability.metrics import get_registry
    c = get_registry().get("moe_grouped_dot_calls_total")
    return ({tuple(v for _, v in sorted(k)): child.value for k, child in c.children().items()}
            if c else {})


def _layer_and_operands(dtype=jnp.float32):
    layer = ep.ExpertParallelMLP(128, 128, 16, capacity_factor=None, top_k=4, expert_type="swiglu",
                                 experts_held=(4, 4), row_buffer_factor=2.0)
    params = layer.init(jax.random.PRNGKey(0))[0]
    params = {k: v if k == "router" else v.astype(dtype) for k, v in params.items()}
    x = jax.random.normal(jax.random.PRNGKey(1), (128, 128), dtype)
    return layer, params, x


def test_the_expert_layer_takes_the_kernel_where_pallas_runs_and_ragged_dot_elsewhere(monkeypatch):
    """128 tokens x 4 of 16 experts, 4 held, a row buffer of 256 rows: the
    layer through the interpreted kernels equals the layer through
    ``lax.ragged_dot``, values and every gradient, and the counter says
    which it was: 9 products a traced gradient on the chip's path, the 3
    forward ones the layer itself writes off it."""
    from apex_tpu.ops import dispatch
    layer, params, x = _layer_and_operands()

    def loss(p, x):
        return jnp.sum(layer(p, x) ** 2)

    before = _calls()
    want, want_g = jax.value_and_grad(loss, (0, 1))(params, x)
    assert _grown(before) == {("ragged_dot", "0"): 3}
    assert "ragged_dot" in str(jax.make_jaxpr(loss)(params, x))

    monkeypatch.setattr(dispatch, "pallas_enabled", lambda: True)
    before = _calls()
    got, got_g = jax.value_and_grad(loss, (0, 1))(params, x)
    assert _grown(before) == {("mosaic", "128"): 9}
    # (a fresh function: the trace of ``loss`` above is cached)
    text = str(jax.make_jaxpr(lambda p, x: loss(p, x))(params, x))
    # launches of one shape share one traced body, which the jaxpr prints once
    assert "ragged_dot" not in text and "pallas_call" in text
    assert text.count("name=_rows_product") == 3
    np.testing.assert_allclose(got, want, rtol=1e-5)
    for a, b in zip(jax.tree_util.tree_leaves(got_g), jax.tree_util.tree_leaves(want_g)):
        assert a.dtype == b.dtype and a.shape == b.shape
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-5 * float(jnp.abs(b).max()))


def test_the_kernel_keeps_the_operands_type_and_no_fp32_array_of_the_buffers_size(monkeypatch):
    """bf16 in, bf16 out, bf16 residuals: nothing of the experts' three
    products and their gradients is fp32 at the row buffer's size (the
    parent's ragged_dot wrote fp32 and cast) and no ``where(live)`` is left
    around them; the stack's gradient leaves in the stack's type, as the
    parent's ``jax.grad`` gave it."""
    import re
    from apex_tpu.ops import dispatch
    monkeypatch.setattr(dispatch, "pallas_enabled", lambda: True)
    layer, params, _ = _layer_and_operands(jnp.bfloat16)
    rows = jax.random.normal(jax.random.PRNGKey(2), (256, 128), jnp.bfloat16)
    sizes = jnp.asarray([40, 0, 90, 33], jnp.int32)
    live = (jnp.arange(256) < 163)[:, None]

    def grad(p, rows, dy):
        return jax.vjp(lambda p, r: layer._grouped_mlp(p, r, sizes, live), p, rows)[1](dy)

    text = str(jax.make_jaxpr(grad)(params, rows, rows))
    assert "pallas_call" in text and "ragged_dot" not in text
    assert text.count("name=_rows_product") == 6 and text.count("name=_stack_product") == 3
    # no fp32 array and no select at the buffer's size (the work items' are int32 vectors)
    assert not re.search(r"f32\[256,\d+\]", text)
    assert not re.search(r":\w+\[256,\d+\] = select_n", text)
    g, d_rows = grad(params, rows, rows)
    assert d_rows.dtype == jnp.bfloat16 and not np.asarray(d_rows, np.float32)[163:].any()
    assert {k: v.dtype for k, v in g.items() if k != "router"} == {
        k: jnp.dtype(jnp.bfloat16) for k in ("w_in", "w_out", "w_gate")}
