"""Optimizer protocol for apex_tpu.

A functional analogue of torch.optim: an Optimizer object holds
hyperparameters and exposes pure ``init(params) -> state`` and
``update(grads, state, params) -> (new_params, new_state)``.  The amp
machinery wraps these the way the reference performs surgery on torch
optimizers (apex/amp/_process_optimizer.py) — but as composition, not
monkey-patching.

``lr`` may be a float or a schedule ``f(step) -> float``; ``state.step``
counts applied (non-skipped) updates so LR schedules and Adam bias
correction see the same step numbering as the reference's skip semantics.
"""

from __future__ import annotations

from typing import Any, Callable, NamedTuple, Optional, Tuple, Union

import jax
import jax.numpy as jnp

__all__ = ["Optimizer", "SGD", "SGDState", "GradSegments", "resolve_lr",
           "global_grad_norm"]

Schedule = Union[float, Callable[[jax.Array], jax.Array]]


def resolve_lr(lr: Schedule, step: jax.Array) -> jax.Array:
    if callable(lr):
        return jnp.asarray(lr(step), jnp.float32)
    return jnp.asarray(lr, jnp.float32)


def global_grad_norm(grads: Any) -> jax.Array:
    """Global L2 norm over a gradient pytree (or flat buffer) as an fp32
    device scalar — the observability gauge the amp step reports in its
    info dict.  Pure jnp, so it composes with jit/shard_map; under
    data-parallel the grads are already allreduced, so every replica
    computes the same value with no extra collective."""
    leaves = jax.tree_util.tree_leaves(grads)
    if not leaves:
        return jnp.zeros((), jnp.float32)
    sq = sum(jnp.sum(jnp.square(l.astype(jnp.float32))) for l in leaves)
    return jnp.sqrt(sq)


class GradSegments(NamedTuple):
    """The gradient of a flat parameter buffer as consecutive pieces, each
    in the dtype the backward wrote it: piece ``k`` covers the elements
    after pieces ``0..k-1`` and the lengths add up to the buffer's
    (``amp._FlatLayout.pack_grads``: the half leaves' segment, then the
    float32 leaves').  What an inner optimizer with ``unscales_grads``
    is handed in place of an unscaled float32 buffer, together with
    ``scale=``."""
    parts: Tuple[jax.Array, ...]


class Optimizer:
    def init(self, params: Any) -> Any:
        raise NotImplementedError

    def update(self, grads: Any, state: Any, params: Any) -> Tuple[Any, Any]:
        raise NotImplementedError


class SGDState(NamedTuple):
    step: jax.Array
    momentum: Any  # pytree like params, or None


class SGD(Optimizer):
    # purely elementwise given scalar hyperparams: safe to run on a fused
    # flat buffer (amp._process_optimizer.FlatMasters fast path)
    elementwise = True

    def __init__(self, lr: Schedule = 0.01, momentum: float = 0.0,
                 weight_decay: float = 0.0, nesterov: bool = False,
                 dampening: float = 0.0):
        self.lr = lr
        self.momentum = momentum
        self.weight_decay = weight_decay
        self.nesterov = nesterov
        self.dampening = dampening

    def init(self, params: Any) -> SGDState:
        mom = None
        if self.momentum:
            mom = jax.tree_util.tree_map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params)
        return SGDState(step=jnp.zeros((), jnp.int32), momentum=mom)

    def update(self, grads: Any, state: SGDState, params: Any):
        lr = resolve_lr(self.lr, state.step)
        wd = self.weight_decay

        def upd(p, g, m):
            g = g.astype(jnp.float32)
            p32 = p.astype(jnp.float32)
            if wd:
                g = g + wd * p32
            if m is not None:
                m_new = self.momentum * m + (1.0 - self.dampening) * g
                if self.nesterov:
                    g = g + self.momentum * m_new
                else:
                    g = m_new
            else:
                m_new = None
            return (p32 - lr * g).astype(p.dtype), m_new

        if state.momentum is None:
            new_params = jax.tree_util.tree_map(
                lambda p, g: upd(p, g, None)[0], params, grads)
            new_mom = None
        else:
            pairs = jax.tree_util.tree_map(upd, params, grads, state.momentum)
            new_params = jax.tree_util.tree_map(
                lambda pr: pr[0], pairs, is_leaf=lambda x: isinstance(x, tuple))
            new_mom = jax.tree_util.tree_map(
                lambda pr: pr[1], pairs, is_leaf=lambda x: isinstance(x, tuple))
        return new_params, SGDState(step=state.step + 1, momentum=new_mom)
