"""The selective scan's kernel pair (``ops/pallas_ssd.py``) in the interpreter
on the CPU, under ``APEX_TPU_FORCE_PALLAS=1``: outputs and all six gradients
against the reference's position-by-position recurrence at the family's decays
(a chunk forgets little, so the carried state matters) in float32, and within
their rounding in bfloat16 operands, beside ``ssd_chunked``; a planted fault
(no state and no ``dS`` from chunk to chunk) that the same comparison refuses;
rows of a batch and groups of heads apart; the chooser in ``Mamba2Mixer``'s
one call and the counter's two labels; and a rematerialized mixer's gradients
bit for bit those of the plain one."""

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from apex_tpu.models import _remat
from apex_tpu.observability.metrics import get_registry
from apex_tpu.ops import pallas_ssd
from apex_tpu.transformer import mamba2

from test_nemotron3 import _recurrence, _scan_inputs

Q = 128
NAMES = ("x", "dt", "A", "B", "C", "D")
EVERY = tuple(range(6))
# (heads, channels a head, groups): two heads sharing a lane tile in one group,
# two groups of such a pair, and heads of a whole tile, a group each
SHAPES = [(2, 64, 1), (4, 64, 2), (2, 128, 2)]


@pytest.fixture(autouse=True)
def forced(monkeypatch):
    monkeypatch.setenv("APEX_TPU_FORCE_PALLAS", "1")
    monkeypatch.delenv("APEX_TPU_DISABLE_PALLAS", raising=False)


def _inputs(dtype=jnp.float32, H=2, P=64, G=1, b=2, seq=4 * Q, N=128):
    """``test_nemotron3``'s scan inputs (decays as the family draws them: delta
    in [0.001, 0.1], A in [1, 16]) at four chunks of 128, and a cotangent."""
    args = _scan_inputs(dtype, b=b, seq=seq, H=H, P=P, G=G, N=N)
    return args, jax.random.normal(jax.random.PRNGKey(8), args[0].shape)


def _kernel(*args):
    return pallas_ssd.ssd_scan(*args, Q)


def _chunks_alone(x, dt, A, B, C, D):
    """THE PLANTED FAULT: every chunk a row of its own through the same
    kernels, so no state enters a chunk and no ``dS`` leaves one."""
    b, T = x.shape[:2]
    cut = lambda a: a.reshape(b * T // Q, Q, *a.shape[2:])
    return pallas_ssd.ssd_scan(cut(x), cut(dt), A, cut(B), cut(C), D, Q).reshape(x.shape)


def _grads(fn, args, weigh, wrt=EVERY):
    return jax.grad(lambda *a: jnp.sum(fn(*a) * weigh), wrt)(*args)


def _worst(got, want):
    """Largest difference over the largest element of ``want``."""
    got, want = got.astype(jnp.float32), want.astype(jnp.float32)
    return float(jnp.abs(got - want).max() / jnp.abs(want).max())


@pytest.mark.parametrize("H,P,G", SHAPES)
def test_the_kernels_are_the_recurrence_in_float32_outputs_and_every_gradient(H, P, G):
    args, weigh = _inputs(H=H, P=P, G=G)
    with jax.default_matmul_precision("highest"):
        got, want = _kernel(*args), _recurrence(*args)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5, rtol=1e-5)
        g, w = _grads(_kernel, args, weigh), _grads(_recurrence, args, weigh)
    for name, a, b in zip(NAMES, g, w):
        scale = float(jnp.abs(b).max())
        assert scale > 0 and a.dtype == b.dtype and a.shape == b.shape, name
        np.testing.assert_allclose(np.asarray(a) / scale, np.asarray(b) / scale, atol=1e-5,
                                   err_msg=name)


@pytest.mark.parametrize("H,P,G", SHAPES[:2])
def test_the_kernels_in_bfloat16_operands_stay_within_their_rounding(H, P, G):
    """The limits ``tests/test_nemotron3.py`` holds ``ssd_chunked`` to against
    the recurrence, 2 % of the largest output and 3 % of each gradient's
    largest, here between the kernels and ``ssd_chunked`` on the same bf16
    operands (the float32 recurrence: the planted fault's test below)."""
    args, weigh = _inputs(jnp.bfloat16, H=H, P=P, G=G)
    chunked = jax.jit(lambda *a: mamba2.ssd_chunked(*a, Q))
    got = _kernel(*args)
    assert got.dtype == jnp.float32
    assert _worst(got, chunked(*args)) < 0.02
    g, want = _grads(_kernel, args, weigh), jax.jit(lambda *a: _grads(chunked, a, weigh))(*args)
    for name, a, b in zip(NAMES, g, want):
        assert a.dtype == b.dtype == (jnp.bfloat16 if name in "xBC" else jnp.float32), name
        assert _worst(a, b) < 0.03, name


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_a_scan_that_carries_nothing_between_chunks_is_refused_by_the_same_comparison(dtype):
    args, weigh = _inputs(dtype, H=2, P=64, G=1)
    args32 = tuple(a.astype(jnp.float32) for a in args)
    want = _recurrence(*args32)
    assert _worst(_kernel(*args), want) < 0.02
    assert _worst(_chunks_alone(*args), want) > 0.1
    # the first chunk needs nothing carried, the others do
    np.testing.assert_allclose(np.asarray(_chunks_alone(*args)[:, :Q]),
                               np.asarray(_kernel(*args)[:, :Q]), atol=1e-6)
    # and a backward that hands no dS to the chunk before is another gradient
    w = _grads(_recurrence, args32, weigh, (0, 1, 3, 4))
    g = _grads(_chunks_alone, args, weigh, (0, 1, 3, 4))
    for name, a, b in zip(("x", "dt", "B", "C"), g, w):
        assert _worst(a, b) > 0.1, name


def test_rows_of_a_batch_and_groups_of_heads_stay_apart():
    args, weigh = _inputs(H=4, P=64, G=2)
    y = _kernel(*args)
    row = lambda a, i: a[i:i + 1] if a.ndim > 1 else a
    for i in range(2):                          # a row alone is the row in the batch
        np.testing.assert_array_equal(np.asarray(_kernel(*(row(a, i) for a in args))[0]),
                                      np.asarray(y[i]))
    # a state left in the first row would reach the second: the second alone
    # after another first row is the same
    x, dt, A, B, C, D = args
    other = _kernel(x.at[0].mul(3.0), dt, A, B.at[0].mul(-2.0), C, D)
    np.testing.assert_array_equal(np.asarray(other[1]), np.asarray(y[1]))
    # heads 0-1 read group 0's B and C, heads 2-3 group 1's
    for moved in (_kernel(x, dt, A, B.at[:, :, 1].mul(2.0), C, D),
                  _kernel(x, dt, A, B, C.at[:, :, 1].add(1.0), D)):
        np.testing.assert_array_equal(np.asarray(moved[:, :, :2]), np.asarray(y[:, :, :2]))
        assert float(jnp.abs(moved[:, :, 2:] - y[:, :, 2:]).max()) > 0.1
    dB = _grads(_kernel, args, weigh.at[:, :, 2:].set(0.0), (3,))[0]
    assert float(jnp.abs(dB[:, :, 1]).max()) == 0.0 < float(jnp.abs(dB[:, :, 0]).max())


def _scans_counted(impl, chunk=Q):
    return get_registry().counter("ssd_scan_calls_total").labels(
        impl=impl, chunk=str(chunk)).value


@pytest.mark.parametrize("case,impl", [
    ("tile_filling_forced", "pallas"), ("tile_filling_off_the_chip", "chunked_xla"),
    ("tile_filling_disabled", "chunked_xla"), ("tiny_forced", "chunked_xla"),
    ("half_a_chunk_forced", "chunked_xla"), ("mixed_types_forced", "chunked_xla"),
    ("a_group_past_vmem_forced", "chunked_xla")])
def test_the_mixers_one_call_chooses_from_the_dispatch_and_the_shapes(monkeypatch, case, impl):
    if case == "tile_filling_off_the_chip":
        monkeypatch.delenv("APEX_TPU_FORCE_PALLAS")
    if case == "tile_filling_disabled":
        monkeypatch.setenv("APEX_TPU_DISABLE_PALLAS", "1")
    chunk = Q
    if case == "tiny_forced":                   # the tiny test configuration's shapes
        (args, _), chunk = _inputs(H=4, P=8, G=2, N=16, seq=32), 8
    elif case == "half_a_chunk_forced":
        (args, _), chunk = _inputs(seq=4 * Q), 64
    elif case == "a_group_past_vmem_forced":    # 80 heads of 64 in one group: 5120 lanes
        args, _ = _inputs(H=80, b=1, seq=Q)
    else:
        args, _ = _inputs()
    if case == "mixed_types_forced":
        args = (args[0].astype(jnp.bfloat16),) + args[1:]
    assert pallas_ssd.takes(args[0], args[3], args[4], chunk) == (
        case.startswith("tile_filling"))
    before = _scans_counted(impl, chunk)
    got = jax.eval_shape(lambda *a: mamba2.selective_scan(*a, chunk), *args)
    assert got.shape == args[0].shape and got.dtype == jnp.float32
    assert _scans_counted(impl, chunk) == before + 1
    if not case.startswith("tile_filling"):
        with pytest.raises(ValueError, match="does not take"):
            pallas_ssd.ssd_scan(*args, chunk)


@pytest.mark.parametrize("mode", ["dots", "nothing"])
def test_a_rematerialized_mixer_has_the_plain_mixers_gradients_bit_for_bit(mode):
    mixer = mamba2.Mamba2Mixer(64, 2, 64, 128, 1, taps=4, chunk=Q)
    params = mixer.init(jax.random.PRNGKey(2))[0]
    u = jax.random.normal(jax.random.PRNGKey(4), (2, 2 * Q, 64))
    before = _scans_counted("pallas")

    def grads(block):
        return jax.jit(jax.grad(lambda p, x: jnp.sum(block(p, x) ** 2), (0, 1)))(params, u)

    plain = grads(lambda p, x: x + mixer(p, x))
    again = grads(_remat.wrap_block(lambda p, x: x + mixer(p, x), mode))
    assert _scans_counted("pallas") > before
    for a, b in zip(jax.tree_util.tree_leaves(plain), jax.tree_util.tree_leaves(again)):
        assert float(jnp.abs(a).max()) > 0
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
