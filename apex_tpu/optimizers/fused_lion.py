"""FusedLion: Lion (Chen et al. 2023, "Symbolic Discovery of
Optimization Algorithms") over one fused flat parameter buffer.

Beyond the reference's optimizer set (it ships Adam-era optimizers
only), but built with exactly its fused-buffer discipline
(apex/optimizers/fused_adam.py:50-147): one elementwise pass over the
flat fp32 buffer, grad unscale folded in, optional half-precision
parameter write-out in the same pass.  Lion is pure elementwise, so
the jnp expression IS the fused kernel after XLA fusion — a dedicated
Pallas kernel would add nothing (the op is bandwidth-bound with one
read/write per buffer).

    g~ = g / combined_scale
    u  = sign(b1*m + (1-b1)*g~)
    p -= lr * (u + weight_decay*p)          (decoupled decay)
    m  = b2*m + (1-b2)*g~

Memory: ONE moment buffer (half of Adam's optimizer state) — the
reason Lion matters at scale.
"""

from __future__ import annotations

from typing import Any, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from .base import Optimizer, resolve_lr
from ..multi_tensor_apply import multi_tensor_l2norm
from ..multi_tensor_apply.flatten import pack_flat, unpack_flat

__all__ = ["FusedLion", "LionState"]


class LionState(NamedTuple):
    step: jax.Array   # int32; number of applied updates
    m: jax.Array      # fp32 flat momentum


class FusedLion(Optimizer):
    elementwise = True
    supports_output_params_dtype = True

    def __init__(self, lr: float = 1e-4,
                 betas: Tuple[float, float] = (0.9, 0.99),
                 weight_decay: float = 0.0,
                 max_grad_norm: float = 0.0):
        self.lr = lr
        self.betas = betas
        self.weight_decay = weight_decay
        self.max_grad_norm = max_grad_norm

    def init(self, params: Any) -> LionState:
        n = sum(int(l.size) for l in jax.tree_util.tree_leaves(params))
        return LionState(step=jnp.zeros((), jnp.int32),
                         m=jnp.zeros((n,), jnp.float32))

    def update(self, grads: Any, state: LionState, params: Any):
        return self.step(params, state, grads)[:2]

    @jax.named_scope("optim.lion")
    def step(self, params: Any, state: LionState, grads: Any,
             scale: float = 1.0, grad_norm: Optional[jax.Array] = None,
             output_params_dtype=None):
        """One fused Lion step; signature matches FusedAdam.step
        (scale/grad_norm/output_params_dtype contract)."""
        flat_g, _, _ = pack_flat(grads, jnp.float32)
        flat_p, p_leaves, p_treedef = pack_flat(params, jnp.float32)

        combined_scale = jnp.asarray(scale, jnp.float32)
        if self.max_grad_norm > 0:
            if grad_norm is None:
                grad_norm, _ = multi_tensor_l2norm(flat_g)
            clip = ((grad_norm / combined_scale) + 1e-6) \
                / self.max_grad_norm
            combined_scale = jnp.where(clip > 1.0,
                                       clip * combined_scale,
                                       combined_scale)

        beta1, beta2 = self.betas
        lr = resolve_lr(self.lr, state.step)
        gs = flat_g / combined_scale
        update = jnp.sign(beta1 * state.m + (1.0 - beta1) * gs)
        new_p = flat_p - lr * (update + self.weight_decay * flat_p)
        new_m = beta2 * state.m + (1.0 - beta2) * gs
        half = (new_p.astype(output_params_dtype)
                if output_params_dtype is not None else None)

        new_params = unpack_flat(new_p, p_leaves, p_treedef)
        new_state = LionState(step=state.step + 1, m=new_m)
        if output_params_dtype is not None:
            return new_params, new_state, half
        return new_params, new_state
