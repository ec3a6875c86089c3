"""The row moves of ``ops/row_moves.py`` against the lines they replace,
``x[token]`` and ``zeros.at[token].add(ys * weight)``, and against
``jax.grad`` of those lines: rows from tokens, tokens from rows, and each as
the other's transpose, over routings that leave half the buffer dead, a held
expert without a row, groups off every tile edge, rows over a capacity and
assignments past the buffer's end.  What lies in a row buffer past its live
rows (NaN here) reaches no token and no gradient, and no scatter is traced."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.ops import row_moves as rm
from apex_tpu.parallel import expert_parallel as ep

T, D = 64, 128

# experts scored, held, rows of the buffer, capacity (rows an expert), and
# which experts the tokens may choose (None: any)
CASES = {
    "a_dead_half": dict(scored=8, held=4, rows=256, cap=None, among=None),
    "an_empty_group": dict(scored=8, held=4, rows=128, cap=None, among=[0, 1, 3, 4, 5, 6, 7]),
    "tiles_that_straddle_groups": dict(scored=6, held=5, rows=240, cap=None, among=None),
    "a_capacity_that_drops_rows": dict(scored=4, held=3, rows=192, cap=9, among=None),
    "an_overflowing_buffer": dict(scored=4, held=4, rows=48, cap=None, among=None),
}


def _routing(k, scored, held, rows, cap, among, seed):
    """What ``ExpertParallelMLP._sorted_forward`` hands the moves, in numpy:
    token and weight by row, position and kept gate by assignment."""
    rng = np.random.RandomState(seed)
    rows = min(rows, T * k)             # as the layer sizes it
    among = np.arange(scored) if among is None else np.asarray(among)
    experts = np.stack([rng.permutation(among)[:k] for _ in range(T)])      # (T, k)
    gates = rng.rand(T, k).astype(np.float32) + 0.1
    key = experts.T.reshape(-1)
    key = np.where(key < held, key, held)
    order = np.argsort(key, kind="stable")
    live = min(int((key < held).sum()), rows)
    sizes = np.bincount(key, minlength=held + 1)[:held]
    starts = np.cumsum(sizes) - sizes
    at = np.full(T * k, -1, np.int32)
    at[order[:live]] = np.arange(live)
    place = np.arange(T * k) - starts[np.minimum(key[order], held - 1)]
    kept = np.arange(T * k) < live
    if cap is not None:
        kept &= place < cap
    order, kept = order[:rows], kept[:rows]
    weight = np.where(kept, gates.T.reshape(-1)[order], 0.0).astype(np.float32)
    fits = np.zeros(T * k, bool)
    fits[order[kept]] = True
    return dict(token=jnp.asarray(order % T, jnp.int32), weight=jnp.asarray(weight),
                at=jnp.asarray(at), n_live=live,
                rows=rows, gates=jnp.asarray(gates), fits=jnp.asarray(fits.reshape(k, T).T),
                key=jnp.asarray(key, jnp.int32), starts=jnp.asarray(starts, jnp.int32),
                dropped=int((key < held).sum() - kept.sum()))


def _close(got, want, dtype, what):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    assert np.isfinite(got).all(), what
    tol = 2e-6 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(got, want, rtol=tol, atol=tol * max(1.0, float(np.abs(want).max())),
                               err_msg=what)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["fp32", "bf16"])
@pytest.mark.parametrize("k", [1, 4], ids=["top1", "top4"])
@pytest.mark.parametrize("case", sorted(CASES))
def test_both_moves_and_their_transposes_agree_with_the_xla_lines(case, k, dtype):
    spec = CASES[case]
    r = _routing(k, seed=len(case) + k, **spec)
    rows, live = r["rows"], r["n_live"]
    assert 0 < live <= rows
    assert r["dropped"] > 0 or case not in ("an_overflowing_buffer", "a_capacity_that_drops_rows")
    rng = np.random.RandomState(k)
    x = jnp.asarray(rng.randn(T, D), dtype)
    add = jnp.asarray(rng.randn(T, D), dtype)
    seen = (jnp.arange(rows) < live)[:, None]
    # what the experts would do to the rows: something row by row, not linear
    experts = lambda xs: jnp.tanh(xs.astype(jnp.float32) * 0.7).astype(dtype)
    cot = jnp.asarray(rng.randn(T, D), dtype)

    def xla(x, gates, add):
        xs = jnp.where(seen, x[r["token"]], 0)
        weight = jnp.where(r["weight"] != 0, gates.T.reshape(-1)[jnp.argsort(r["key"], stable=True)[:rows]], 0.0)
        y = jnp.zeros((T, D), jnp.float32).at[r["token"]].add(
            experts(xs).astype(jnp.float32) * weight[:, None])
        return (y + add.astype(jnp.float32)).astype(dtype)

    def gathers(x, gates, add):
        xs = rm.gather(x, r["token"], r["at"])
        # the rows past the live ones are nobody's: NaN there must stay there
        ys = jnp.where(seen, experts(xs), jnp.nan)
        return rm.combine(ys, jnp.where(r["fits"], gates, 0.0), add, r["token"],
                          r["weight"], r["at"])

    xs = rm.gather(x, r["token"], r["at"])
    assert xs.dtype == dtype and xs.shape == (rows, D)
    np.testing.assert_array_equal(np.asarray(xs, np.float32),
                                  np.asarray(x[r["token"]], np.float32))
    want, pull_want = jax.vjp(xla, x, r["gates"], add)
    got, pull_got = jax.vjp(gathers, x, r["gates"], add)
    assert got.dtype == dtype
    _close(got, want, dtype, "tokens from rows")
    for name, g, w in zip(("rows' cotangent to the tokens", "gate weights' gradient", "add's"),
                          pull_got(cot), pull_want(cot)):
        assert g.dtype == w.dtype and g.shape == w.shape, name
        _close(g, w, dtype, name)
    # the gather's cotangent alone, NaN in the rows no token has
    dxs = jnp.where(seen, jnp.asarray(rng.randn(rows, D), dtype), jnp.nan)
    dx = jax.vjp(lambda x: rm.gather(x, r["token"], r["at"]), x)[1](dxs)[0]
    dx_want = jax.vjp(lambda x: x[r["token"]], x)[1](jnp.where(seen, dxs, 0))[0]
    _close(dx, dx_want, dtype, "the gather's cotangent")


def test_combine_without_an_addend_and_a_token_nobody_holds_reads_zero():
    r = _routing(4, scored=8, held=2, rows=128, cap=None, among=None, seed=5)
    nobody = np.flatnonzero((np.asarray(r["at"]).reshape(4, T) < 0).all(axis=0))
    assert len(nobody) > 0
    ys = jnp.where((jnp.arange(128) < r["n_live"])[:, None], 1.0, jnp.nan).astype(jnp.bfloat16)
    y = rm.combine(ys, jnp.where(r["fits"], r["gates"], 0.0), None, r["token"], r["weight"],
                   r["at"])
    assert np.isfinite(np.asarray(y, np.float32)).all()
    assert not np.asarray(y, np.float32)[nobody].any()
    want = np.asarray(jnp.sum(jnp.where(r["fits"], r["gates"], 0.0), axis=1))
    np.testing.assert_allclose(np.asarray(y, np.float32)[:, 0], want, rtol=1e-2)


@pytest.mark.parametrize("case", sorted(CASES))
def test_queue_positions_invert_the_stable_sort_without_sorting(case):
    spec = CASES[case]
    for k in (1, 4):
        r = _routing(k, seed=3, **spec)
        at, place = ep._queue_positions(r["key"], r["starts"], spec["held"], r["rows"])
        np.testing.assert_array_equal(np.asarray(at), np.asarray(r["at"]))
        order = np.argsort(np.asarray(r["key"]), kind="stable")
        held = np.asarray(r["key"]) < spec["held"]
        want = np.arange(T * k) - np.asarray(r["starts"])[np.minimum(np.asarray(r["key"])[order],
                                                                     spec["held"] - 1)]
        np.testing.assert_array_equal(np.asarray(place)[order][held[order]], want[held[order]])
    assert "scatter" not in str(jax.make_jaxpr(
        lambda key, starts: ep._queue_positions(key, starts, spec["held"], spec["rows"]))(
            r["key"], r["starts"]))


def test_no_scatter_is_traced_in_either_direction():
    r = _routing(4, seed=1, **CASES["a_dead_half"])
    x = jnp.zeros((T, D), jnp.bfloat16)

    def loss(x, gates):
        xs = rm.gather(x, r["token"], r["at"])
        y = rm.combine(xs, jnp.where(r["fits"], gates, 0.0), x, r["token"], r["weight"], r["at"])
        return jnp.sum(y.astype(jnp.float32))

    text = str(jax.make_jaxpr(jax.grad(loss, (0, 1)))(x, r["gates"]))
    assert "scatter" not in text and text.count("gather") >= 5


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["fp32", "bf16"])
def test_a_buffer_gathered_from_in_chunks_of_columns_comes_home_the_same(monkeypatch, dtype):
    """A buffer over ``_SOURCE_BYTES`` is the source of one gather a chunk of
    whole lane tiles of columns (what the TPU's compiler can hold in VMEM):
    the same numbers, chunk beside chunk."""
    r = _routing(4, seed=2, **CASES["a_dead_half"])
    rng = np.random.RandomState(3)
    wide = 3 * 128
    ys = jnp.asarray(rng.randn(r["rows"], wide), dtype)
    add = jnp.asarray(rng.randn(T, wide), dtype)
    scale = jnp.where(r["fits"], r["gates"], 0.0)
    # the cells' buffers: three chunks of 768, four of 512, and whole where 48 MiB hold them
    assert [rm._column_chunks(*shape, 2) for shape in (
        (32768, 2304), (32768, 2048), (8192, 2048), (6144, 2688))] == [3, 4, 1, 1]
    assert rm._column_chunks(r["rows"], wide, ys.dtype.itemsize) == 1
    whole = rm.tokens_from_rows(ys, r["at"], scale, add)
    monkeypatch.setattr(rm, "_SOURCE_BYTES", r["rows"] * 128 * ys.dtype.itemsize)
    assert rm._column_chunks(r["rows"], wide, ys.dtype.itemsize) == 3
    text = str(jax.make_jaxpr(lambda ys: rm.tokens_from_rows(ys, r["at"], scale, add))(ys))
    assert text.count(" gather[") == 3 and "concatenate" in text
    np.testing.assert_array_equal(np.asarray(rm.tokens_from_rows(ys, r["at"], scale, add), np.float32),
                                  np.asarray(whole, np.float32))
