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
a read and a write in its dtype.  **Who runs it** (:func:`gated_short_conv`,
the operator's one call): where ``ops.dispatch.pallas_enabled()`` and
``ops.pallas_short_conv.takes`` the shapes (the channels whole lane tiles,
the tokens whole blocks, at most 8 taps) the pass is that module's kernel
pair, the ``gated`` form: one read of the projection's output and one write
forward, and backward one pass that recomputes ``g``, writes the projection's
cotangent once and keeps nothing but its operand and the taps.  Everything
else (every backend but the TPU, the tiny test shapes) runs
:func:`gated_short_conv_xla`: the shift along the sequence as a ``pad`` in
front and ``L`` static slices, rematerialized in the backward pass so that
what it keeps is the projection's output.  On the chip the compiler does NOT
fuse that form into one pass (a slice that starts one or two rows into an
(8, 128) tile): it writes ``g`` as a float32 array forward and three float32
arrays of that size backward, 3 x and 6 x the bytes the pass needs (PERF.md
section 6, PRs 33 and 49), which is why the kernel exists.

Scopes ``conv.in_proj`` / ``conv.mix`` / ``conv.out_proj``
(observability/phases.py); ``short_conv_calls_total{taps, impl}`` counts the
operators traced by what implements them, ``pallas`` or ``xla``
(docs/observability.md).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..nn.layers import Linear
from ..nn.module import Module

__all__ = ["GatedShortConv", "gated_short_conv", "gated_short_conv_xla",
           "count_short_conv"]


def count_short_conv(taps: int, kernel: bool) -> None:
    """One short convolution traced, by its taps and what implements it."""
    from ..observability.metrics import get_registry
    get_registry().counter(
        "short_conv_calls_total",
        help="short causal convolutions along the sequence traced (the gated "
        "operators and the Mamba-2 mixers'), by their taps and what implements "
        "them").labels(taps=str(taps), impl="pallas" if kernel else "xla").inc()


def gated_short_conv_xla(bcz: jax.Array, taps: jax.Array) -> jax.Array:
    """:func:`gated_short_conv` as XLA compiles it: a ``pad`` and ``L``
    static slices."""
    d = bcz.shape[-1] // 3
    L = taps.shape[0]
    f32 = lambda a: a.astype(jnp.float32)
    b, c, z = (f32(bcz[..., i * d:(i + 1) * d]) for i in range(3))
    g = jnp.pad(b * z, ((0, 0), (L - 1, 0), (0, 0)))
    T = bcz.shape[1]
    w = f32(taps)
    mixed = sum(w[k] * g[:, k:k + T] for k in range(L))
    return (c * mixed).astype(bcz.dtype)


def gated_short_conv(bcz: jax.Array, taps: jax.Array) -> jax.Array:
    """``bcz``: (B, T, 3d), the input projection's output; ``taps``: (L, d)
    -> ``C * conv(B * z)``, (B, T, d) in ``bcz``'s dtype, float32 between: by
    the kernel pair where the dispatch and the shapes allow it (module
    docstring), else the XLA form rematerialized; counts the call under what
    implements it."""
    from ..ops import dispatch, pallas_short_conv
    kernel = dispatch.pallas_enabled() and pallas_short_conv.takes(
        bcz, taps, form="gated")
    count_short_conv(taps.shape[0], kernel)
    if kernel:
        return pallas_short_conv.short_conv(bcz, taps, form="gated")
    return jax.checkpoint(gated_short_conv_xla)(bcz, taps)


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
        with jax.named_scope("conv.in_proj"):
            bcz = self.in_proj(p["in_proj"], x)
        with jax.named_scope("conv.mix"):
            y = gated_short_conv(bcz, p["conv"]["weight"])
        with jax.named_scope("conv.out_proj"):
            return self.out_proj(p["out_proj"], y)
