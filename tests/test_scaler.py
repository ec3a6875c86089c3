"""LossScaler state-machine tests — semantics of apex/amp/scaler.py:190-210
(init 2^16, halve+skip on overflow, double every scale_window clean steps,
min/max caps)."""

import jax
import jax.numpy as jnp
import pytest

from apex_tpu.amp.scaler import LossScaler, ScalerState


def test_dynamic_defaults():
    s = LossScaler("dynamic")
    st = s.init_state()
    assert float(st.loss_scale) == 2.0 ** 16
    assert int(st.unskipped) == 0


def test_overflow_halves_scale():
    s = LossScaler("dynamic")
    st = s.init_state()
    st = s.update(st, jnp.ones(()))
    assert float(st.loss_scale) == 2.0 ** 15
    assert int(st.steps_skipped) == 1
    assert int(st.unskipped) == 0


def test_growth_after_window():
    s = LossScaler("dynamic", scale_window=3)
    st = s.init_state()
    for _ in range(2):
        st = s.update(st, jnp.zeros(()))
        assert float(st.loss_scale) == 2.0 ** 16
    st = s.update(st, jnp.zeros(()))
    assert float(st.loss_scale) == 2.0 ** 17
    assert int(st.unskipped) == 0


def test_overflow_resets_window():
    s = LossScaler("dynamic", scale_window=3)
    st = s.init_state()
    st = s.update(st, jnp.zeros(()))
    st = s.update(st, jnp.ones(()))   # overflow
    st = s.update(st, jnp.zeros(()))
    st = s.update(st, jnp.zeros(()))
    # only 2 clean since overflow: not yet grown
    assert float(st.loss_scale) == 2.0 ** 15


def test_max_loss_scale_cap():
    s = LossScaler("dynamic", scale_window=1, max_loss_scale=2.0 ** 17)
    st = s.init_state()
    for _ in range(5):
        st = s.update(st, jnp.zeros(()))
    assert float(st.loss_scale) == 2.0 ** 17


def test_min_loss_scale_floor():
    s = LossScaler("dynamic", min_loss_scale=1024.0)
    st = s.init_state()
    for _ in range(20):
        st = s.update(st, jnp.ones(()))
    assert float(st.loss_scale) == 1024.0


def test_static_scaler_never_changes():
    s = LossScaler(128.0)
    st = s.init_state()
    assert float(st.loss_scale) == 128.0
    st = s.update(st, jnp.ones(()))
    assert float(st.loss_scale) == 128.0
    assert int(st.steps_skipped) == 1  # still counts skips


def test_unscale_produces_masters_and_flag():
    s = LossScaler(8.0)
    st = s.init_state()
    grads = {"w": jnp.asarray([8.0, 16.0], jnp.float16)}
    out, flag = s.unscale(grads, st)
    assert out["w"].dtype == jnp.float32
    assert jnp.allclose(out["w"], jnp.asarray([1.0, 2.0]))
    assert float(flag) == 0.0
    bad = {"w": jnp.asarray([8.0, jnp.inf], jnp.float16)}
    _, flag = s.unscale(bad, st)
    assert float(flag) == 1.0


def test_unscale_with_stashed_accumulates():
    s = LossScaler(4.0)
    st = s.init_state()
    new = {"w": jnp.asarray([4.0, 8.0], jnp.float32)}
    stash = {"w": jnp.asarray([1.0, 1.0], jnp.float32)}
    out, flag = s.unscale_with_stashed(new, stash, st)
    assert jnp.allclose(out["w"], jnp.asarray([2.0, 3.0]))
    assert float(flag) == 0.0


def test_growth_at_exactly_scale_window():
    """The boundary semantics (ADVICE r5 coverage ask): scale_window-1
    consecutive clean steps leave the scale untouched; the
    scale_window-th clean step doubles it AND resets the streak, so
    growth recurs every exactly-scale_window clean steps."""
    W = 5
    s = LossScaler("dynamic", scale_window=W)
    st = s.init_state()
    for i in range(W - 1):
        st = s.update(st, jnp.zeros(()))
        assert float(st.loss_scale) == 2.0 ** 16, f"grew early at {i}"
        assert int(st.unskipped) == i + 1
    st = s.update(st, jnp.zeros(()))          # the W-th clean step
    assert float(st.loss_scale) == 2.0 ** 17
    assert int(st.unskipped) == 0             # streak reset on growth
    for _ in range(W - 1):
        st = s.update(st, jnp.zeros(()))
        assert float(st.loss_scale) == 2.0 ** 17
    st = s.update(st, jnp.zeros(()))
    assert float(st.loss_scale) == 2.0 ** 18


def test_cap_behavior_at_max_loss_scale():
    """At the cap the grow branch still fires (streak keeps
    resetting), the scale stays clamped, and an overflow halves FROM
    the cap — it never sticks."""
    W = 2
    cap = 2.0 ** 17
    s = LossScaler("dynamic", scale_window=W, max_loss_scale=cap)
    st = s.init_state()
    for _ in range(W):
        st = s.update(st, jnp.zeros(()))
    assert float(st.loss_scale) == cap
    for cycle in range(3):
        for _ in range(W):
            st = s.update(st, jnp.zeros(()))
        assert float(st.loss_scale) == cap
        assert int(st.unskipped) == 0         # grow branch keeps firing
    st = s.update(st, jnp.ones(()))           # overflow at the cap
    assert float(st.loss_scale) == cap / 2
    assert int(st.steps_skipped) == 1
    for _ in range(W):
        st = s.update(st, jnp.zeros(()))
    assert float(st.loss_scale) == cap        # recovers, re-clamps


def test_update_inside_jit():
    s = LossScaler("dynamic", scale_window=2)

    @jax.jit
    def step(st, f):
        return s.update(st, f)

    st = s.init_state()
    st = step(st, jnp.zeros(()))
    st = step(st, jnp.zeros(()))
    assert float(st.loss_scale) == 2.0 ** 17
    st = step(st, jnp.ones(()))
    assert float(st.loss_scale) == 2.0 ** 16
