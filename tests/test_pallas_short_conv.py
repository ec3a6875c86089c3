"""The short causal convolution's kernel pair (``ops/pallas_short_conv.py``) in
the interpreter on the CPU, under ``APEX_TPU_FORCE_PALLAS=1``: values and every
gradient (operand, taps, bias) against the ``jax.numpy`` lines the other
backends run, over both forms, 2 to 4 taps, both float types, one and two
sequences, one block of tokens and three (the halo rows cross two block edges,
and a sequence's first rows see zeros, not the rows before them in memory); a
planted fault (every block a sequence of its own) that the same comparison
refuses; the two call sites' choice from the dispatch and the shapes, with the
counter's labels; and the two modules' gradients with the kernels against
without."""

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from apex_tpu.observability.metrics import get_registry
from apex_tpu.ops import pallas_short_conv as psc
from apex_tpu.transformer import mamba2, short_conv

ROWS = psc._ROWS
D = 128                 # channels of a part: one lane tile
LEFT, RIGHT = 128, 64   # the projection's columns before and after the silu form's


@pytest.fixture(autouse=True)
def forced(monkeypatch):
    monkeypatch.setenv("APEX_TPU_FORCE_PALLAS", "1")
    monkeypatch.delenv("APEX_TPU_DISABLE_PALLAS", raising=False)


def _case(form, L, dtype, B, T, seed=0):
    """``(kernel, xla, arguments, a cotangent)`` of one form: both take the
    projection's whole output; the silu form's columns start at ``LEFT``."""
    k = jax.random.split(jax.random.PRNGKey(seed + 7 * L), 4)
    taps = jax.random.uniform(k[1], (L, D), jnp.float32, -0.6, 0.6)
    weigh = jax.random.normal(k[3], (B, T, D))
    if form == "gated":
        x = jax.random.normal(k[0], (B, T, 3 * D)).astype(dtype)
        return (lambda x, w: psc.short_conv(x, w, form="gated"),
                short_conv.gated_short_conv_xla, (x, taps), weigh)
    x = jax.random.normal(k[0], (B, T, LEFT + D + RIGHT)).astype(dtype)
    bias = jax.random.uniform(k[2], (D,), jnp.float32, -0.5, 0.5)
    return (lambda x, w, b: psc.short_conv(x, w, b, form="silu", offset=LEFT),
            lambda x, w, b: mamba2.causal_conv_silu_xla(x[..., LEFT:LEFT + D], w, b),
            (x, taps, bias), weigh)


def _grads(fn, args, weigh):
    every = tuple(range(len(args)))
    return jax.grad(lambda *a: jnp.sum(fn(*a).astype(jnp.float32) * weigh), every)(*args)


def _worst(got, want):
    """Largest difference over the largest element of ``want``."""
    got, want = got.astype(jnp.float32), want.astype(jnp.float32)
    return float(jnp.abs(got - want).max() / jnp.abs(want).max())


@pytest.mark.parametrize("T", [ROWS, 3 * ROWS], ids=["one_block", "three_blocks"])
@pytest.mark.parametrize("B", [1, 2])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bfloat16"])
@pytest.mark.parametrize("L", [2, 3, 4])
@pytest.mark.parametrize("form", psc.FORMS)
def test_the_kernels_are_the_jax_numpy_lines_values_and_every_gradient(form, L, dtype, B, T):
    """Float32 between in both, so float32 operands agree to rounding's last
    bits (the taps' and the bias's gradients are summed in another order) and
    bf16 results to a unit in their last place."""
    kernel, xla, args, weigh = _case(form, L, dtype, B, T)
    assert psc.takes(*args, form=form, offset=0 if form == "gated" else LEFT)
    got, want = kernel(*args), jax.jit(xla)(*args)
    assert got.dtype == want.dtype == dtype and got.shape == want.shape == (B, T, D)
    half = dtype == jnp.bfloat16
    assert _worst(got, want) < (4e-3 if half else 1e-6)
    g, w = _grads(kernel, args, weigh), jax.jit(lambda *a: _grads(xla, a, weigh))(*args)
    for name, a, b in zip(("operand", "taps", "bias"), g, w):
        assert a.dtype == b.dtype and a.shape == b.shape, name
        assert float(jnp.abs(b).max()) > 0, name
        assert _worst(a, b) < (4e-3 if half and name == "operand" else 2e-6), name
    if form == "silu":      # the projection's other columns get no gradient
        assert float(jnp.abs(g[0][..., :LEFT]).max()) == 0.0
        assert float(jnp.abs(g[0][..., LEFT + D:]).max()) == 0.0


@pytest.mark.parametrize("form", psc.FORMS)
def test_blocks_that_see_nothing_of_their_neighbours_are_refused_by_the_same_comparison(form):
    """THE PLANTED FAULT: every block of tokens a sequence of its own through
    the same kernels, so the rows before a block read as zeros and no cotangent
    comes back from the rows after it."""
    kernel, xla, args, weigh = _case(form, 4, jnp.float32, 1, 3 * ROWS)
    cut = lambda a: a.reshape(3, ROWS, a.shape[-1])
    alone = lambda x, *rest: kernel(cut(x), *rest).reshape(1, 3 * ROWS, D)
    want = xla(*args)
    assert _worst(kernel(*args), want) < 1e-6
    assert _worst(alone(*args), want) > 0.05
    # the first block needs nothing in front of it; every other block's first rows do
    np.testing.assert_allclose(np.asarray(alone(*args)[:, :ROWS]), np.asarray(want[:, :ROWS]),
                               atol=1e-5)
    assert _worst(alone(*args)[:, ROWS:ROWS + 3], want[:, ROWS:ROWS + 3]) > 0.05
    np.testing.assert_allclose(np.asarray(alone(*args)[:, ROWS + 3:2 * ROWS]),
                               np.asarray(want[:, ROWS + 3:2 * ROWS]), atol=1e-5)
    # and the operand's gradient in a block's last rows lacks the rows after it
    g, w = _grads(alone, args, weigh)[0], _grads(xla, args, weigh)[0]
    assert _worst(g[:, ROWS - 3:ROWS], w[:, ROWS - 3:ROWS]) > 0.05
    np.testing.assert_allclose(np.asarray(g[:, :ROWS - 3]), np.asarray(w[:, :ROWS - 3]), atol=1e-5)


def test_sequences_of_a_batch_stay_apart():
    for form in psc.FORMS:
        kernel, _, args, _ = _case(form, 3, jnp.float32, 2, ROWS)
        both = kernel(*args)
        for i in range(2):
            alone = kernel(args[0][i:i + 1], *args[1:])
            np.testing.assert_array_equal(np.asarray(alone[0]), np.asarray(both[i]))


def _counted(taps, impl):
    return get_registry().counter("short_conv_calls_total").labels(
        taps=str(taps), impl=impl).value


@pytest.mark.parametrize("form", psc.FORMS)
@pytest.mark.parametrize("case,impl", [
    ("whole_tiles_forced", "pallas"), ("whole_tiles_off_the_chip", "xla"),
    ("whole_tiles_disabled", "xla"), ("channels_not_whole_lane_tiles_forced", "xla"),
    ("tokens_not_whole_blocks_forced", "xla"), ("tokens_not_whole_tiles_forced", "xla"),
    ("nine_taps_forced", "xla"), ("half_precision_of_another_kind_forced", "xla")])
def test_each_call_site_chooses_from_the_dispatch_and_the_shapes(monkeypatch, form, case, impl):
    if case == "whole_tiles_off_the_chip":
        monkeypatch.delenv("APEX_TPU_FORCE_PALLAS")
    if case == "whole_tiles_disabled":
        monkeypatch.setenv("APEX_TPU_DISABLE_PALLAS", "1")
    L, T, d, dtype = 4, ROWS, D, jnp.bfloat16
    if case.startswith("channels"):
        d = 96
    elif case.startswith("tokens_not_whole_blocks"):
        T = ROWS + 16
    elif case.startswith("tokens_not_whole_tiles"):
        T = 24
    elif case.startswith("nine"):
        L = 9
    elif case.startswith("half"):
        dtype = jnp.float16
    taps = jnp.ones((L, d), jnp.float32)
    if form == "gated":
        args, offset = (jnp.ones((2, T, 3 * d), dtype), taps), 0
        call = short_conv.gated_short_conv
    else:
        args, offset = (jnp.ones((2, T, 2 * d + 64), dtype), taps, jnp.ones((d,))), d
        call = lambda *a: mamba2.causal_conv_silu(*a, offset=offset)
    assert psc.takes(*args, form=form, offset=offset) == case.startswith("whole_tiles")
    before = _counted(L, impl)
    got = call(*args)       # eagerly: a cached trace would count nothing
    assert got.shape == (2, T, d) and got.dtype == dtype
    assert _counted(L, impl) == before + 1
    if not case.startswith("whole_tiles"):
        with pytest.raises(ValueError, match="does not take"):
            psc.short_conv(*args, form=form, offset=offset)


def test_a_form_takes_its_own_operands_only():
    x, taps, bias = jnp.ones((1, ROWS, 3 * D)), jnp.ones((3, D)), jnp.ones((D,))
    assert psc.takes(x, taps, form="gated") and psc.takes(x, taps, bias, form="silu")
    assert not psc.takes(x, taps, bias, form="gated")      # no bias there
    assert not psc.takes(x, taps, form="silu")             # and one here
    assert not psc.takes(x, taps, form="plain")
    assert not psc.takes(x, taps, form="gated", offset=D)  # the parts would end past x
    assert not psc.takes(x, taps, bias, form="silu", offset=64)


def _module_grads(module, x, forced_on, monkeypatch):
    if not forced_on:
        monkeypatch.delenv("APEX_TPU_FORCE_PALLAS")
    params = module.init(jax.random.PRNGKey(2))[0]
    return jax.jit(jax.value_and_grad(
        lambda p, x: jnp.sum(module(p, x) ** 2), (0, 1)))(params, x)


@pytest.mark.parametrize("name", ["GatedShortConv", "Mamba2Mixer"])
def test_a_module_with_the_kernels_is_the_module_without(monkeypatch, name):
    """The operator and the mixer at widths the kernels take, loss and every
    gradient: the convolution by the kernel pair (the mixer's scan too) against
    XLA's forms."""
    if name == "GatedShortConv":
        module, taps = short_conv.GatedShortConv(D, 3), 3
    else:       # conv_dim 384 from column 128 of a projection 576 wide
        module, taps = mamba2.Mamba2Mixer(64, 2, 64, 128, 1, taps=4, chunk=128), 4
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 256, module.dim))
    before = _counted(taps, "pallas"), _counted(taps, "xla")
    with jax.default_matmul_precision("highest"):
        on = _module_grads(module, x, True, monkeypatch)
        assert (_counted(taps, "pallas"), _counted(taps, "xla")) == (before[0] + 1, before[1])
        off = _module_grads(module, x, False, monkeypatch)
    assert (_counted(taps, "pallas"), _counted(taps, "xla")) == (before[0] + 1, before[1] + 1)
    for a, b in zip(jax.tree_util.tree_leaves(on), jax.tree_util.tree_leaves(off)):
        assert float(jnp.abs(b).max()) > 0
        assert _worst(a, b) < 1e-4
