"""A Mamba-2 mixer: a selective state-space layer whose scan runs in the
chunked (state-space duality) form.  With ``u`` the normed input of the block,
``(B, T, d)``, ``H`` heads of ``P`` channels (``d_in = H P``), ``G`` groups of
``B``/``C`` rows of ``N`` state numbers (head ``h`` reads group
``h // (H / G)``):

    [z | xBC | dt] = u W_in              W_in: d -> d_in + (d_in + 2 G N) + H
    xBC = silu(conv(xBC) + b_c)          depthwise, causal, L taps, zero
                                         before a row's first token
    [x | B | C] = xBC                    d_in | G N | G N
    delta_t = softplus(dt_t + dt_bias)   (H,), float32
    S_t = exp(delta_t A) S_{t-1} + delta_t B_t (x) x_t     A = -exp(A_log),
    y_t = C_t^T S_t + D x_t              one number a head; S: (N, P) a head,
                                         zero before the row
    y = RMSNorm_g(y * silu(z))           over each of the G groups of
                                         d_in / G channels apart, one gain
    out = y W_out                        d_in -> d

**The scan** (:func:`ssd_chunked`) is never a loop over positions and never a
``(T, T)`` array: the row is cut into chunks of ``chunk`` positions
(``T % chunk != 0`` is refused), and with ``c_i = sum_{k <= i} delta_k A`` the
running sum inside a chunk (every exponent below is <= 0),

- inside a chunk ``Y = (L o C B^T)(delta x)``, ``L_ij = exp(c_i - c_j)`` for
  ``j <= i``: products of ``chunk x chunk`` a head;
- each chunk's end state from its own inputs,
  ``sum_j exp(c_last - c_j) delta_j B_j (x) x_j``;
- the states carried over the chunks by their decays: one small product a
  head over the ``T / chunk`` chunk states (``W_zc = exp(sum of the chunks'
  totals between c and z)``), float32 at the highest precision;
- ``C`` times the carried state, decayed to the position: ``exp(c_i)
  C_i^T S_prev``.

Decays are float32 from a cumulative sum of ``delta A``; the products take
their operands in the compute type (what ``x`` came in) and accumulate in
float32.

**Who runs it** (:func:`selective_scan`, the one call of the mixer).  Where
``ops.dispatch.pallas_enabled()`` and the shapes fill tiles (``chunk``, ``N``
and a group's ``R P`` channels multiples of 128, ``P`` 64 or a multiple of
128, ``T % chunk == 0``: ``ops.pallas_ssd.takes``) the same form runs as a
Pallas kernel pair, ``ops/pallas_ssd.py``: a chunk's decays and scores live in
VMEM only, the state goes from chunk to chunk in a VMEM scratch, and the
backward is a kernel that walks the chunks in reverse from each chunk's saved
entering state.  Everything else (every backend but the TPU, the tiny test
shapes) runs :func:`ssd_chunked` as XLA compiles it, and its backward is
autodiff's of this form.  Either way the block's rematerialization
(``models/_remat.py``) decides what is kept: nothing of the scan, so a
rematerialized block runs its forward twice.

**The convolution** (:func:`causal_conv_silu`) reads ``xBC`` where the
projection wrote it, columns ``d_in .. d_in + d_in + 2 G N`` of ``u W_in``:
where the dispatch and ``ops.pallas_short_conv.takes`` allow it (the offset
and the channels whole lane tiles, the tokens whole blocks) it is that
module's kernel pair in its ``silu`` form, one pass a direction with the
shift done in VMEM; elsewhere a ``pad`` and ``L`` static slices, which the
TPU's compiler does not fuse into one pass (it writes float32 arrays of
``xBC``'s size; PERF.md section 6, PR 49).

``A_log``, ``dt_bias``, ``D``, the taps, their bias and the gain meet float32
values and stay float32 under amp (``fp32_param_names``).  Scopes
``mamba.in_proj`` / ``mamba.conv`` / ``mamba.scan`` / ``mamba.gate_norm`` /
``mamba.out_proj`` (observability/phases.py); ``mamba_mixers_total{heads,
state, groups}``, ``ssd_scan_calls_total{impl, chunk}`` (``impl`` is
``pallas`` or ``chunked_xla``) and ``short_conv_calls_total{taps, impl}``
(``pallas`` or ``xla``) count what a traced program holds
(docs/observability.md).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

from ..nn.layers import Linear
from ..nn.module import Module
from ..ops.pallas_common import token_tile_axes

__all__ = ["Mamba2Mixer", "selective_scan", "ssd_chunked", "causal_conv_silu",
           "causal_conv_silu_xla", "gated_group_norm"]


def ssd_chunked(x, dt, A, B, C, D, chunk: int):
    """The selective scan in the chunked form (module docstring).

    ``x`` (b, T, H, P) in the compute type; ``dt`` (b, T, H) float32, after
    its softplus; ``A`` (H,) float32, negative; ``B``, ``C`` (b, T, G, N);
    ``D`` (H,) -> ``y`` (b, T, H, P) float32."""
    b, T, H, P = x.shape
    G, N = B.shape[2:]
    if T % chunk:
        raise ValueError(f"sequence length {T} is not whole chunks of {chunk}")
    if H % G:
        raise ValueError(f"{H} heads do not divide over {G} groups")
    nc, R, Q = T // chunk, H // G, chunk
    cdt = x.dtype
    f32 = jnp.float32
    # heads as (group, head of the group): B and C are never repeated
    xg = x.reshape(b, nc, Q, G, R, P)
    Bc, Cc = B.reshape(b, nc, Q, G, N), C.reshape(b, nc, Q, G, N)
    dtc = dt.astype(f32).reshape(b, nc, Q, G, R)
    cum = jnp.cumsum(dtc * A.astype(f32).reshape(G, R), axis=2)
    dtx32 = xg.astype(f32) * dtc[..., None]                 # delta x
    dtx = dtx32.astype(cdt)
    # inside a chunk: (L o C B^T)(delta x), L masked before the exp
    cb = jnp.einsum("bclgn,bcsgn->bcgls", Cc, Bc, preferred_element_type=f32)
    at = lambda a: jnp.moveaxis(a, 2, -1)                   # (b, nc, G, R, Q)
    gap = at(cum)[..., :, None] - at(cum)[..., None, :]     # c_l - c_s
    later = jnp.arange(Q)[:, None] >= jnp.arange(Q)[None, :]
    decay = jnp.exp(jnp.where(later, gap, -jnp.inf))
    m = (cb[:, :, :, None] * decay).astype(cdt)             # (b, nc, G, R, l, s)
    y = jnp.einsum("bcgrls,bcsgrp->bclgrp", m, dtx, preferred_element_type=f32)
    # each chunk's end state from its own inputs
    to_end = jnp.exp(cum[:, :, -1:] - cum)                  # (b, nc, Q, G, R)
    states = jnp.einsum("bcsgn,bcsgrp->bcgrnp", Bc,
                        (dtx32 * to_end[..., None]).astype(cdt),
                        preferred_element_type=f32)
    # carried over the chunks: the state before chunk z is the sum over the
    # chunks c < z of their end states, decayed by the chunks between
    total = jnp.cumsum(cum[:, :, -1], axis=1)               # (b, nc, G, R)
    before = total - cum[:, :, -1]                          # through chunk z - 1
    tz = jnp.moveaxis(before, 1, -1)[..., :, None]          # (b, G, R, z, 1)
    tc = jnp.moveaxis(total, 1, -1)[..., None, :]           # (b, G, R, 1, c)
    earlier = jnp.arange(nc)[:, None] > jnp.arange(nc)[None, :]
    carry = jnp.exp(jnp.where(earlier, tz - tc, -jnp.inf))  # (b, G, R, z, c)
    prev = jnp.einsum("bgrzc,bcgrnp->bzgrnp", carry, states,
                      precision=lax.Precision.HIGHEST)
    y = y + jnp.einsum("bclgn,bcgrnp->bclgrp", Cc, prev.astype(cdt),
                       preferred_element_type=f32) * jnp.exp(cum)[..., None]
    y = y + xg.astype(f32) * D.astype(f32).reshape(G, R)[:, :, None]
    return y.reshape(b, T, H, P)


def selective_scan(x, dt, A, B, C, D, chunk: int):
    """:func:`ssd_chunked`'s arguments and result, by the kernel pair where
    the dispatch and the shapes allow it (module docstring); counts the call
    under what implements it."""
    from ..observability.metrics import get_registry
    from ..ops import dispatch, pallas_ssd
    kernel = dispatch.pallas_enabled() and pallas_ssd.takes(x, B, C, chunk)
    get_registry().counter(
        "ssd_scan_calls_total",
        help="selective state-space scans traced, by what implements them "
        "and the chunk").labels(
            impl="pallas" if kernel else "chunked_xla", chunk=str(chunk)).inc()
    return (pallas_ssd.ssd_scan if kernel else ssd_chunked)(
        x, dt, A, B, C, D, chunk)


def causal_conv_silu_xla(xbc, taps, bias):
    """:func:`causal_conv_silu` as XLA compiles it: a ``pad`` in front and
    ``L`` static slices, as ``short_conv.gated_short_conv_xla`` shifts."""
    L, T = taps.shape[0], xbc.shape[1]
    g = jnp.pad(xbc.astype(jnp.float32), ((0, 0), (L - 1, 0), (0, 0)))
    w = taps.astype(jnp.float32)
    y = sum(w[k] * g[:, k:k + T] for k in range(L)) + bias.astype(jnp.float32)
    return (y * jax.nn.sigmoid(y)).astype(xbc.dtype)


def causal_conv_silu(x, taps, bias, offset: int = 0):
    """``x`` (b, T, W), of which the convolution reads the ``c`` columns from
    ``offset`` (the mixer hands over its projection's whole output);
    ``taps`` (L, c), a tap a row; ``bias`` (c,) -> ``silu(conv(x) + bias)``,
    (b, T, c), depthwise and causal (zero before a row's first token), in
    ``x``'s dtype with float32 between: by ``ops/pallas_short_conv.py``'s
    kernel pair (the ``silu`` form, the columns picked by its index maps)
    where the dispatch and the shapes allow it, else
    :func:`causal_conv_silu_xla` over the slice; counts the call under what
    implements it."""
    from ..ops import dispatch, pallas_short_conv
    from .short_conv import count_short_conv
    kernel = dispatch.pallas_enabled() and pallas_short_conv.takes(
        x, taps, bias, form="silu", offset=offset)
    count_short_conv(taps.shape[0], kernel)
    if kernel:
        return pallas_short_conv.short_conv(x, taps, bias, form="silu",
                                            offset=offset)
    return causal_conv_silu_xla(x[..., offset:offset + taps.shape[1]], taps,
                                bias)


def gated_group_norm(y, z, gain, groups: int, eps: float):
    """``RMSNorm(y * silu(z))`` over each of ``groups`` runs of the last axis
    apart, one ``gain`` over the whole axis; float32 inside, -> ``z``'s
    dtype.  The groups are cut in the view ``(b, T / 8, 8, groups, d)``
    (``token_tile_axes``): a reshape of ``(b, T, groups * d)`` to
    ``(b, T, groups, d)`` is a relayout on the TPU where ``y`` comes from a
    kernel, which fixes its layout."""
    zf = z.astype(jnp.float32)
    g = y.astype(jnp.float32) * (zf * jax.nn.sigmoid(zf))
    parts = g.reshape(*token_tile_axes(*g.shape[:2]), groups, -1)
    parts = parts * lax.rsqrt(jnp.mean(parts * parts, -1, keepdims=True) + eps)
    return (parts.reshape(g.shape) * gain.astype(jnp.float32)).astype(z.dtype)


class Mamba2Mixer(Module):
    """``in_proj`` (d -> 2 d_in + 2 G N + H), ``conv1d`` = {``weight`` (L,
    d_in + 2 G N), ``bias``}, ``A_log``, ``dt_bias``, ``D`` (H,), ``norm`` =
    {``weight`` (d_in,)}, ``out_proj`` (d_in -> d); module docstring."""

    fp32_param_names = ("conv1d", "A_log", "dt_bias", "D", "norm")

    def __init__(self, dim: int, heads: int, head_dim: int, state: int,
                 groups: int, taps: int = 4, chunk: int = 128,
                 eps: float = 1e-5):
        super().__init__()
        if heads % groups:
            raise ValueError(f"{heads} heads do not divide over {groups} "
                             f"groups")
        self.dim, self.H, self.P, self.N, self.G = (dim, heads, head_dim,
                                                    state, groups)
        self.taps, self.chunk, self.eps = taps, chunk, eps
        self.d_in = heads * head_dim
        self.conv_dim = self.d_in + 2 * groups * state
        self.in_proj = Linear(dim, self.d_in + self.conv_dim + heads,
                              bias=False)
        self.out_proj = Linear(self.d_in, dim, bias=False)

    def create_params(self, key):
        """As the family initializes them: ``A`` uniform in [1, 16],
        ``delta`` log-uniform in [0.001, 0.1] at a zero projection (the
        inverse softplus in ``dt_bias``), ``D`` and the gain ones, the taps
        torch's Conv1d default."""
        ka, kd, kw, kb = jax.random.split(key, 4)
        A = jax.random.uniform(ka, (self.H,), jnp.float32, 1.0, 16.0)
        dt = jnp.exp(jax.random.uniform(kd, (self.H,), jnp.float32,
                                        math.log(1e-3), math.log(1e-1)))
        bound = (1.0 / self.taps) ** 0.5
        conv = {"weight": jax.random.uniform(
                    kw, (self.taps, self.conv_dim), jnp.float32, -bound, bound),
                "bias": jax.random.uniform(
                    kb, (self.conv_dim,), jnp.float32, -bound, bound)}
        return {"conv1d": conv, "A_log": jnp.log(A),
                "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
                "D": jnp.ones((self.H,), jnp.float32),
                "norm": {"weight": jnp.ones((self.d_in,), jnp.float32)}}

    def forward(self, p, u):
        from ..observability.metrics import get_registry
        reg = get_registry()
        reg.counter("mamba_mixers_total",
                    help="Mamba-2 mixers traced, by heads, state size and "
                    "groups").labels(heads=str(self.H), state=str(self.N),
                                     groups=str(self.G)).inc()
        b, T, _ = u.shape
        d_in, gn = self.d_in, self.G * self.N
        with jax.named_scope("mamba.in_proj"):
            zxbcdt = self.in_proj(p["in_proj"], u)
        z = zxbcdt[..., :d_in]
        with jax.named_scope("mamba.conv"):
            xbc = causal_conv_silu(zxbcdt, p["conv1d"]["weight"],
                                   p["conv1d"]["bias"], offset=d_in)
        with jax.named_scope("mamba.scan"):
            dt = jax.nn.softplus(
                zxbcdt[..., d_in + self.conv_dim:].astype(jnp.float32)
                + p["dt_bias"].astype(jnp.float32))
            y = selective_scan(
                xbc[..., :d_in].reshape(b, T, self.H, self.P), dt,
                -jnp.exp(p["A_log"].astype(jnp.float32)),
                xbc[..., d_in:d_in + gn].reshape(b, T, self.G, self.N),
                xbc[..., d_in + gn:].reshape(b, T, self.G, self.N),
                p["D"], self.chunk)
        with jax.named_scope("mamba.gate_norm"):
            y = gated_group_norm(y.reshape(b, T, d_in), z,
                                 p["norm"]["weight"], self.G, self.eps)
        with jax.named_scope("mamba.out_proj"):
            return self.out_proj(p["out_proj"], y)
