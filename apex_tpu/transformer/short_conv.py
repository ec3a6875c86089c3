"""A gated short convolution along the sequence: the token mixer of the
layers that are not attention in the decoders built on it (LFM2's ``conv``
layers).  With ``u`` the normed input of the sub-block, ``(B, T, d)``:

    [B, C, z] = split_3(u W_in)          W_in: d -> 3d
    g = B * z
    c_t = sum_k w[k] * g_{t - (L - 1) + k}      k = 0 .. L - 1, per channel
    out = (C * c) W_out                   W_out: d -> d

depthwise and causal: ``L`` taps (``conv_L_cache``), ``g`` zero before a
row's first token, no bias anywhere.  ``w`` is kept ``(L, d)``, a tap a row,
so that a tap is whole lane tiles beside ``g``'s.

The three parts of ``u W_in`` are whole lane tiles of the array the
projection wrote (``d % 128 == 0`` at any published width), so the gates, the
taps and the output gate are one elementwise pass over it in float32 between
a read and a write in its dtype.  The shift along the sequence is a ``pad`` in
front and ``L`` static slices, which the compiler fuses into that pass with
the gates (PERF.md section 6, PR 33, has what a v5e's compiled step holds);
the pass is rematerialized in the backward pass, so that what it keeps is the
projection's output and not float32 arrays of ``g``'s size.

Scopes ``conv.in_proj`` / ``conv.mix`` / ``conv.out_proj``
(observability/phases.py); ``short_conv_calls_total{taps}`` counts the
operators traced (docs/observability.md).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..nn.layers import Linear
from ..nn.module import Module

__all__ = ["GatedShortConv", "gated_short_conv"]


def gated_short_conv(bcz: jax.Array, taps: jax.Array) -> jax.Array:
    """``bcz``: (B, T, 3d), the input projection's output; ``taps``: (L, d)
    -> ``C * conv(B * z)``, (B, T, d) in ``bcz``'s dtype, float32 between."""
    d = bcz.shape[-1] // 3
    L = taps.shape[0]
    f32 = lambda a: a.astype(jnp.float32)
    b, c, z = (f32(bcz[..., i * d:(i + 1) * d]) for i in range(3))
    g = jnp.pad(b * z, ((0, 0), (L - 1, 0), (0, 0)))
    T = bcz.shape[1]
    w = f32(taps)
    mixed = sum(w[k] * g[:, k:k + T] for k in range(L))
    return (c * mixed).astype(bcz.dtype)


class GatedShortConv(Module):
    """``in_proj`` (d -> 3d), ``conv.weight`` (L, d), ``out_proj`` (d -> d);
    module docstring.  The taps stay float32 under amp, as a norm's gains
    do: they meet float32 values in the elementwise pass."""

    fp32_param_names = ("conv",)

    def __init__(self, dim: int, taps: int = 3):
        super().__init__()
        if taps < 1:
            raise ValueError(f"taps={taps}: a convolution has at least one")
        self.dim, self.taps = dim, taps
        self.in_proj = Linear(dim, 3 * dim, bias=False)
        self.out_proj = Linear(dim, dim, bias=False)

    def create_params(self, key):
        # torch's Conv1d default for a depthwise kernel of L taps
        bound = (1.0 / self.taps) ** 0.5
        return {"conv": {"weight": jax.random.uniform(
            key, (self.taps, self.dim), jnp.float32, -bound, bound)}}

    def forward(self, p, x):
        from ..observability.metrics import get_registry
        get_registry().counter(
            "short_conv_calls_total",
            help="gated short-convolution operators traced, by their taps"
        ).labels(taps=str(self.taps)).inc()
        with jax.named_scope("conv.in_proj"):
            bcz = self.in_proj(p["in_proj"], x)
        with jax.named_scope("conv.mix"):
            y = jax.checkpoint(gated_short_conv)(bcz, p["conv"]["weight"])
        with jax.named_scope("conv.out_proj"):
            return self.out_proj(p["out_proj"], y)
