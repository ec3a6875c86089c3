"""scale_loss and gradient helpers.

The reference's ``with amp.scale_loss(loss, optimizer)`` (apex/amp/
handle.py:15-157) scales the loss on entry, and on exit unscales grads,
checks overflow, and patches ``optimizer.step`` into a one-shot skip.
JAX has no autograd tape, so apex_tpu offers the same protocol in two
forms:

1. **Functional (the jit/performance path)** — :func:`scaled_grad` computes
   grads of ``loss * loss_scale``; ``AmpOptimizer.step`` unscales, updates
   the scale, and `lax.cond`-skips — all device-resident.

2. **Eager (API-parity path)** — ``with amp.scale_loss(loss_fn, optimizer)
   as scaled_loss: scaled_loss.backward()`` against a *bound* stateful
   optimizer (see amp.stateful.bind), matching the reference's call shape
   for scripts and tests.
"""

from __future__ import annotations

import contextlib
from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp

from . import policy as _policy
from ._amp_state import _amp_state, maybe_print
from ._process_optimizer import AmpOptimizer, AmpOptState

__all__ = ["scale_loss", "scaled_grad", "scaled_grad_accum",
           "disable_casts"]

disable_casts = _policy.disable_casts


def scaled_grad(loss_fn: Callable, params: Any, opt_state: AmpOptState,
                *args, loss_id: int = 0, has_aux: bool = False, **kwargs):
    """value_and_grad of ``loss * loss_scale``.

    Returns ``(loss, scaled_grads)`` or ``(loss, aux, scaled_grads)``; pass
    ``scaled_grads`` straight to ``AmpOptimizer.step`` which unscales them.
    The *unscaled* loss is returned for logging, like the reference yields
    the scaled loss only for backward (handle.py:117).
    """
    scale = opt_state.scalers[loss_id].loss_scale

    def scaled_fn(p):
        res = loss_fn(p, *args, **kwargs)
        loss, aux = res if has_aux else (res, None)
        with jax.named_scope("amp.scale_loss"):
            scaled = loss.astype(jnp.float32) * scale
        return (scaled, aux) if has_aux else scaled

    if has_aux:
        (scaled_loss, aux), grads = jax.value_and_grad(
            scaled_fn, has_aux=True)(params)
    else:
        scaled_loss, grads = jax.value_and_grad(scaled_fn)(params)
    with jax.named_scope("amp.scale_loss"):
        loss = scaled_loss / scale
    return (loss, aux, grads) if has_aux else (loss, grads)


def scaled_grad_accum(loss_fn: Callable, params: Any,
                      opt_state: AmpOptState, batches: Any,
                      loss_id: int = 0, average: bool = True):
    """Gradient accumulation inside jit: K micro-batch backward passes,
    ONE optimizer step.

    ``loss_fn(params, microbatch) -> loss``; ``batches`` is a pytree
    whose leaves carry a leading K axis.  Runs a ``lax.scan`` over the
    micro-batches summing the SCALED gradients (peak memory = one
    micro-batch's activations + one grad tree), and returns
    ``(mean_loss, scaled_grads)`` to pass straight to
    ``AmpOptimizer.step`` — the single unscale there preserves the
    reference's accumulation semantics (``delay_unscale=True`` across
    backwards, ``unscale_with_stashed`` once at step time,
    handle.py:117-137).  ``average=True`` divides by K so the update
    matches one big batch of the concatenated micro-batches (mean-loss
    convention); ``False`` leaves the raw sum.
    """
    scale = opt_state.scalers[loss_id].loss_scale
    K = jax.tree_util.tree_leaves(batches)[0].shape[0]

    def scaled_fn(pp, mb):
        loss = loss_fn(pp, mb)
        with jax.named_scope("amp.scale_loss"):
            return loss.astype(jnp.float32) * scale

    def one(p, mb):
        return jax.value_and_grad(scaled_fn)(p, mb)

    def body(carry, mb):
        loss_sum, acc = carry
        scaled_loss, g = one(params, mb)
        # fp32 accumulator: summing K half-precision grad trees would
        # lose a few ulps per add (the reference stashes fp32 too)
        acc = jax.tree_util.tree_map(
            lambda a, gg: a + gg.astype(a.dtype), acc, g)
        return (loss_sum + scaled_loss, acc), None

    # value_and_grad rejects non-float params, so every leaf gets a
    # grad and the fp32 accumulator is always the right dtype
    zeros = jax.tree_util.tree_map(
        lambda l: jnp.zeros(l.shape, jnp.float32), params)
    (loss_sum, grads), _ = jax.lax.scan(
        body, (jnp.zeros((), jnp.float32), zeros), batches)
    with jax.named_scope("amp.scale_loss"):
        loss = loss_sum / scale
        # sum convention (average=False): loss and grads agree, the
        # caller's objective is the SUM of micro-batch losses
        if average:
            loss = loss / K
            grads = jax.tree_util.tree_map(lambda g: g / K, grads)
    return loss, grads


class _ScaledLoss:
    """What the eager ``scale_loss`` yields: float()-able, backward()-able."""

    def __init__(self, bound, loss_fn: Callable, loss_id: int):
        self._bound = bound
        self._loss_fn = loss_fn
        self._loss_id = loss_id
        self.value: Optional[jax.Array] = None

    def backward(self) -> None:
        self._bound._backward(self._loss_fn, self._loss_id)

    def __float__(self) -> float:
        if self.value is None:
            self.value = self._bound._eval_scaled_loss(
                self._loss_fn, self._loss_id)
        return float(self.value)

    def item(self) -> float:
        return float(self)


@contextlib.contextmanager
def scale_loss(loss: Any, optimizer: AmpOptimizer, loss_id: int = 0,
               model=None, delay_unscale: bool = False,
               delay_overflow_check: bool = False):
    """Eager-mode context manager with the reference's shape
    (handle.py:15-157).

    ``loss`` is a callable ``loss_fn(params) -> scalar`` (JAX is tape-free,
    so the loss must be re-expressible as a function of params); the
    optimizer must have been bound to params via
    ``amp.stateful.bind(optimizer, params)`` or be the optimizer half of a
    bound pair.  On exit, gradients stashed by ``scaled_loss.backward()``
    are unscaled, the scale is updated, and an overflowed step will be
    skipped by the next ``optimizer.step()`` — announcing the scale change
    like the reference (handle.py:142-144).
    """
    if isinstance(optimizer, (list, tuple)):
        raise NotImplementedError(
            "pass a single optimizer per scale_loss context")
    bound = optimizer._bound
    if bound is None:
        raise RuntimeError(
            "Eager scale_loss needs a bound optimizer: call "
            "apex_tpu.amp.stateful.bind(optimizer, params) first, or use "
            "the functional path (amp.scaled_grad + optimizer.step).")
    if not callable(loss):
        raise TypeError(
            "In apex_tpu, amp.scale_loss takes a callable loss_fn(params) "
            "(JAX has no autograd tape to replay a computed loss).")
    sl = _ScaledLoss(bound, loss, loss_id)
    yield sl
    bound._post_backward(loss_id,
                         delay_unscale=delay_unscale,
                         delay_overflow_check=delay_overflow_check)
