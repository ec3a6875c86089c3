"""FusedAdam: Adam over one fused flat parameter buffer.

TPU-native equivalent of apex.optimizers.FusedAdam (fused_adam.py:50-147)
backed by csrc/fused_adam_cuda_kernel.cu.  The CUDA kernel's fusion — one
grid-stride pass updating p/m/v with the grad unscale folded in, plus an
optional fp16 parameter write-out in the same kernel (:94-115) — maps here
to a single Pallas elementwise kernel over a flat fp32 buffer (or a jnp
expression XLA fuses identically off-TPU).

Math matches the reference exactly (fused_adam_cuda_kernel.cu:15-18,43-55,
83-91):

    g~ = g / combined_scale
    m  = b1*m + (1-b1)*g~
    v  = b2*v + (1-b2)*g~^2
    denom = sqrt(v + eps)        (eps_inside_sqrt / ADAM_MODE_0)
          | sqrt(v) + eps        (default / ADAM_MODE_1)
    step_size = lr * sqrt(1-b2^t) / (1-b1^t)   (bias correction, host-side)
    p -= step_size * (m/denom + weight_decay*p)

``combined_scale`` folds grad clipping via a precomputed global grad norm
(reference fused_adam.py:98-104).
"""

from __future__ import annotations

from typing import Any, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from .base import GradSegments, Optimizer, resolve_lr
from ..multi_tensor_apply import multi_tensor_l2norm
from ..multi_tensor_apply.flatten import pack_flat, unpack_flat

__all__ = ["FusedAdam", "AdamState"]


class AdamState(NamedTuple):
    step: jax.Array   # int32; number of applied updates
    m: jax.Array      # fp32 flat first moment
    v: jax.Array      # fp32 flat second moment


def _adam_kernel(p, m, v, g, step_size, combined_scale, beta1, beta2, eps,
                 eps_inside_sqrt, weight_decay, half_dtype=None, start=0):
    """The fused elementwise update on flat fp32 buffers, of the
    ``g.shape[0]`` elements from ``start`` (``g`` in any float dtype,
    widened here); returns (new_p, new_m, new_v, optional half copy of
    those elements of new_p)."""
    from ..ops import dispatch
    if dispatch.use_pallas_for(p):
        from ..ops import pallas_adam
        return pallas_adam.fused_adam(
            p, m, v, g, step_size, combined_scale, beta1, beta2, eps,
            eps_inside_sqrt, weight_decay, half_dtype, start)
    if g.shape[0] != p.shape[0]:
        stop = start + g.shape[0]
        new = _adam_kernel(
            *(jax.lax.slice_in_dim(x, start, stop) for x in (p, m, v)), g,
            step_size, combined_scale, beta1, beta2, eps, eps_inside_sqrt,
            weight_decay, half_dtype)
        return tuple(
            jax.lax.dynamic_update_slice_in_dim(x, y, start, 0)
            for x, y in zip((p, m, v), new)) + new[3:]
    gs = g.astype(jnp.float32) / combined_scale
    new_m = beta1 * m + (1.0 - beta1) * gs
    new_v = beta2 * v + (1.0 - beta2) * gs * gs
    if eps_inside_sqrt:
        denom = jnp.sqrt(new_v + eps)
    else:
        denom = jnp.sqrt(new_v) + eps
    update = new_m / denom + weight_decay * p
    new_p = p - step_size * update
    half = new_p.astype(half_dtype) if half_dtype is not None else None
    return new_p, new_m, new_v, half


class FusedAdam(Optimizer):
    """Signature parity with the reference (fused_adam.py:17-49)."""

    # purely elementwise given scalars: safe on a fused flat buffer, and
    # the kernel can emit the half model copy in the same pass
    elementwise = True
    supports_output_params_dtype = True
    # ``step`` takes the scaled gradient as ``GradSegments`` with
    # ``scale=`` and unscales it in the kernel's registers: amp then makes
    # no pass of its own to unscale and packs each segment in its own dtype
    unscales_grads = True

    def __init__(self, lr=1e-3, bias_correction: bool = True,
                 betas: Tuple[float, float] = (0.9, 0.999), eps: float = 1e-8,
                 eps_inside_sqrt: bool = False, weight_decay: float = 0.0,
                 max_grad_norm: float = 0.0, amsgrad: bool = False):
        if amsgrad:
            raise RuntimeError("FusedAdam does not support the AMSGrad "
                               "variant.")  # fused_adam.py:38
        self.lr = lr
        self.bias_correction = bias_correction
        self.betas = betas
        self.eps = eps
        self.eps_inside_sqrt = eps_inside_sqrt
        self.weight_decay = weight_decay
        self.max_grad_norm = max_grad_norm

    # -- Optimizer protocol ------------------------------------------------
    def init(self, params: Any) -> AdamState:
        n = sum(int(l.size) for l in jax.tree_util.tree_leaves(params))
        return AdamState(step=jnp.zeros((), jnp.int32),
                         m=jnp.zeros((n,), jnp.float32),
                         v=jnp.zeros((n,), jnp.float32))

    def update(self, grads: Any, state: AdamState, params: Any):
        return self.step(params, state, grads)[:2]

    # -- reference-shaped step --------------------------------------------
    @jax.named_scope("optim.adam")
    def step(self, params: Any, state: AdamState, grads: Any,
             scale: float = 1.0, grad_norm: Optional[jax.Array] = None,
             output_params_dtype=None):
        """One fused Adam step.

        ``scale``: grads are divided by this (loss scale; fused_adam.py:86).
        ``grad_norm``: precomputed global norm of the *scaled* grads for
        clipping (fused_adam.py:98-104); computed on the fly if
        ``max_grad_norm`` is set and none is given.
        ``output_params_dtype``: emit a half-precision copy of the updated
        params in the same pass (the kernel's p_copy, :94-115).
        ``grads`` as ``GradSegments`` (``params`` is then the flat
        buffer): one kernel launch a piece over that piece's elements,
        the piece widened in the kernel; the half copy is written for the
        FIRST piece only, where amp's layout keeps the half leaves.
        Returns (new_params, new_state[, half_params]).
        """
        if isinstance(grads, GradSegments):
            pieces = grads.parts
        else:
            pieces = (pack_flat(grads, jnp.float32)[0],)
        flat_p, p_leaves, p_treedef = pack_flat(params, jnp.float32)

        combined_scale = jnp.asarray(scale, jnp.float32)
        if self.max_grad_norm > 0:
            if grad_norm is None:
                norms = [multi_tensor_l2norm(g)[0] for g in pieces]
                grad_norm = (norms[0] if len(norms) == 1 else
                             jnp.sqrt(sum(n * n for n in norms)))
            clip = ((grad_norm / combined_scale) + 1e-6) / self.max_grad_norm
            combined_scale = jnp.where(clip > 1.0, clip * combined_scale,
                                       combined_scale)

        t = state.step + 1
        beta1, beta2 = self.betas
        lr = resolve_lr(self.lr, state.step)
        if self.bias_correction:
            tf = t.astype(jnp.float32)
            bc1 = 1.0 - jnp.power(beta1, tf)
            bc2 = 1.0 - jnp.power(beta2, tf)
            step_size = lr * jnp.sqrt(bc2) / bc1
        else:
            step_size = lr

        new_p, new_m, new_v, half, start = flat_p, state.m, state.v, None, 0
        for g in pieces:
            new_p, new_m, new_v, h = _adam_kernel(
                new_p, new_m, new_v, g, step_size, combined_scale, beta1,
                beta2, self.eps, self.eps_inside_sqrt, self.weight_decay,
                output_params_dtype if start == 0 else None, start)
            half = h if start == 0 else half
            start += g.shape[0]

        new_params = unpack_flat(new_p, p_leaves, p_treedef)
        new_state = AdamState(step=t, m=new_m, v=new_v)
        if output_params_dtype is not None:
            return new_params, new_state, half
        return new_params, new_state
