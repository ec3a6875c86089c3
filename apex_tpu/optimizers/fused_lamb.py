"""FusedLAMB: layer-wise adaptive large-batch optimizer.

The reference ships the LAMB CUDA kernels (csrc/multi_tensor_lamb_stage_1.cu,
multi_tensor_lamb_stage_2.cu, exposed at csrc/amp_C_frontend.cpp:50-53) but
no Python optimizer class (apex/optimizers/__init__.py:1-2 exports only
FusedAdam) — SURVEY.md §2.2 flags this gap and BASELINE config #5 requires
the optimizer.  This class implements the two-stage algorithm the kernels
encode:

stage 1 (multi_tensor_lamb_stage_1.cu:86-108): grads pre-scaled by the
clipped global norm, Adam-style m/v update with bias correction, producing
a per-parameter ``update = m^/(sqrt(v^)+eps) + weight_decay*p``.

stage 2 (multi_tensor_lamb_stage_2.cu:38-48,66-70): per-tensor trust ratio
``r = ||p|| / ||update||`` (1.0 when either norm is zero), then
``p -= lr * r * update``.

Per-tensor norms come from multi_tensor_l2norm(per_tensor=True)
(csrc/multi_tensor_l2norm_kernel.cu:117-180).
"""

from __future__ import annotations

from typing import Any, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from .base import Optimizer, resolve_lr
from ..multi_tensor_apply.flatten import ChunkedFlat, ChunkedFlatLayout

__all__ = ["FusedLAMB", "LambState"]


class LambState(NamedTuple):
    step: jax.Array
    m: Any   # ChunkedFlat fp32 moments over the padded fused buffer
    v: Any


class FusedLAMB(Optimizer):
    def __init__(self, lr=1e-3, bias_correction: bool = True,
                 betas: Tuple[float, float] = (0.9, 0.999), eps: float = 1e-6,
                 weight_decay: float = 0.01, amsgrad: bool = False,
                 adam_w_mode: bool = True, grad_averaging: bool = True,
                 max_grad_norm: float = 1.0, use_nvlamb: bool = False):
        if amsgrad:
            raise RuntimeError("FusedLAMB does not support the AMSGrad "
                               "variant.")
        self.lr = lr
        self.bias_correction = bias_correction
        self.betas = betas
        self.eps = eps
        self.weight_decay = weight_decay
        self.adam_w_mode = adam_w_mode
        self.grad_averaging = grad_averaging
        self.max_grad_norm = max_grad_norm
        self.use_nvlamb = use_nvlamb

    def init(self, params: Any) -> LambState:
        layout = ChunkedFlatLayout(params)
        zeros = jnp.zeros((layout.total,), jnp.float32)
        return LambState(step=jnp.zeros((), jnp.int32),
                         m=ChunkedFlat(zeros, layout),
                         v=ChunkedFlat(zeros, layout))

    def update(self, grads: Any, state: LambState, params: Any):
        return self.step(params, state, grads)

    @jax.named_scope("optim.lamb")
    def step(self, params: Any, state: LambState, grads: Any,
             grad_norm: Optional[jax.Array] = None):
        """One LAMB step over the chunk-padded fused buffer.

        m/v live flat across steps (round-2 VERDICT item 7: no per-step
        tree re-pack of state), and the per-tensor ||p||/||update|| norms
        come from the layout's segment map — one dense pass + a tiny
        segment-sum, not a Python loop over leaves.  Padded slots carry
        zero grads, so m/v/update stay zero there and stage 2 leaves the
        (nonexistent) padded params untouched."""
        beta1, beta2 = self.betas
        t = state.step + 1
        tf = t.astype(jnp.float32)
        lr = resolve_lr(self.lr, state.step)
        beta3 = 1.0 - beta1 if self.grad_averaging else 1.0

        lay = state.m.layout
        g_flat = lay.pack(grads)
        p_flat = lay.pack(params)

        # global grad-norm clipping (stage_1.cu: grads scaled by
        # global_norm/max_norm when above threshold)
        if grad_norm is None:
            grad_norm = jnp.sqrt(jnp.sum(lay.per_tensor_sqsum(g_flat)))
        if self.max_grad_norm and self.max_grad_norm > 0:
            clip_factor = jnp.where(grad_norm > self.max_grad_norm,
                                    grad_norm / self.max_grad_norm, 1.0)
        else:
            clip_factor = jnp.ones((), jnp.float32)

        if self.bias_correction:
            bc1 = 1.0 - jnp.power(beta1, tf)
            bc2 = 1.0 - jnp.power(beta2, tf)
        else:
            bc1 = bc2 = jnp.ones((), jnp.float32)

        wd = self.weight_decay

        from ..ops import dispatch
        use_pallas = dispatch.use_pallas_for(params)
        if use_pallas:
            from ..ops import pallas_lamb
            upd, new_m, new_v = pallas_lamb.lamb_stage1(
                g_flat, p_flat, state.m.buf, state.v.buf, 1.0 / clip_factor,
                1.0 / bc1, 1.0 / bc2, beta1, beta2, beta3, self.eps, wd,
                self.adam_w_mode)
        else:
            g32 = g_flat / clip_factor
            if not self.adam_w_mode and wd:
                g32 = g32 + wd * p_flat  # classic L2 ("adam mode")
            new_m = beta1 * state.m.buf + beta3 * g32
            new_v = beta2 * state.v.buf + (1.0 - beta2) * g32 * g32
            upd = (new_m / bc1) / (jnp.sqrt(new_v / bc2) + self.eps)
            if self.adam_w_mode and wd:
                upd = upd + wd * p_flat  # decoupled decay enters the update

        # stage 2: per-tensor trust ratio (stage_2.cu:38-48)
        p_sq = lay.per_tensor_sqsum(p_flat)
        u_sq = lay.per_tensor_sqsum(upd)
        ratios = jnp.where((p_sq > 0) & (u_sq > 0),
                           jnp.sqrt(p_sq) / jnp.sqrt(u_sq),
                           jnp.ones_like(p_sq))
        ratio_flat = lay.expand_per_tensor(ratios)

        if use_pallas:
            new_p = pallas_lamb.lamb_stage2(p_flat, upd, ratio_flat, lr)
        else:
            new_p = p_flat - lr * ratio_flat * upd

        new_params = lay.unpack(
            new_p, like_leaves=jax.tree_util.tree_leaves(params))
        return new_params, LambState(step=t, m=ChunkedFlat(new_m, lay),
                                     v=ChunkedFlat(new_v, lay))
