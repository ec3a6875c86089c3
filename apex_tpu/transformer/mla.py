"""Multi-head latent attention, the training form: keys and values come out of
one narrow latent a token, and the rotated part of a key is ONE head that
every query head reads.  With ``u`` the normed input of the block,
``(B, T, d)``, ``H`` heads, a score head of ``d_n + d_r`` numbers (``d_n``
without position, ``d_r`` rotated), a value head of ``d_v`` and a latent of
``r``:

    [q_n,i | q_r,i] = u W_q                  d -> H (d_n + d_r), head i
    [c | k_r]       = u W_kva                d -> r + d_r; k_r: one head
    [k_n,i | v_i]   = RMSNorm_c(c) W_kvb     r -> H (d_n + d_v)
    q_r,i <- R_t q_r,i;  k_r <- R_t k_r      R_t turns the pair (2j, 2j + 1)
                                             by t theta^(-2j / d_r)
    s_i = (q_n,i . k_n,i + q_r,i . k_r) / sqrt(d_n + d_r), causal, softmax
    out = [P_1 v_1 .. P_H v_H] W_o           H d_v -> d

**Where the parts live.**  A projection's output goes to the flash kernels
as it is written, so the two parts of a score head are two projections'
outputs and not slices of one: ``q_nope_proj`` (d -> H d_n) and
``q_rope_proj`` (d -> H d_r) are the columns of the published ``W_q`` sorted
by part (head ``i`` of each is head ``i``'s part: a loader of published
weights cuts ``W_q``'s head-major columns that way), and ``k_up_proj`` /
``v_up_proj`` (r -> H d_n, r -> H d_v) the columns of ``W_kvb`` likewise, and
``kv_down_proj`` (d -> r) / ``k_rope_proj`` (d -> d_r) those of ``W_kva``: the
one shared key head is a leaf of its own, whose whole gradient is the sum over
the query heads.  ``transformer.dot_product_attention_token_major(q_rope=,
k_rope=)`` takes the parts: nothing is joined, padded or repeated over heads
on the way to the kernels (``d_n == d_v`` of whole lane tiles, ``d_r`` 64),
and the gradient of the one rotated key head is the kernels' sum over the
query heads.

**The rotation** is on interleaved pairs (the family's ``rope_interleave``),
float32 between a read and a write in the compute type, written on the
``(B, T, heads * d_r)`` arrays in the view of ``token_tile_axes``: a pair's
partner is the neighbouring lane, so there is no (B, T, heads, d_r) view, which
would be a relayout on the TPU.

Scopes ``mla.q_proj`` / ``mla.kv_down`` / ``mla.kv_norm`` / ``mla.kv_up`` /
``mla.rope`` / ``mla.o_proj`` (observability/phases.py; the kernels are their
own names in a trace); ``mla_layers_total{heads, qk, v, latent}`` counts what
a traced program holds (docs/observability.md).  Training and full-sequence
forward only: no cache of latents, no absorbed decode form (ROADMAP, Reach).
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

from ..nn.layers import Linear
from ..nn.module import Module
from ..ops.pallas_common import LANES, token_tile_axes
from .attention import dot_product_attention_token_major

__all__ = ["LatentAttention", "rope_interleaved"]


def rope_interleaved(x, inv_freq):
    """x: (B, T, n * R) with ``R = 2 * len(inv_freq)``, ``n`` heads side by
    side; in every run of ``R`` numbers the pair ``(2j, 2j + 1)`` of token
    ``t`` is turned by ``t * inv_freq[j]``.  float32 inside, -> x's dtype.
    Written in the view whose tiles are the array's own on a TPU
    (``token_tile_axes``), a lane tile (the heads that share one) a row: cos
    and sin are a token's (T, 128) and never the array's width."""
    B, T, W = x.shape
    R = 2 * len(inv_freq)
    G = LANES if W % LANES == 0 and LANES % R == 0 else R
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv_freq
    # a pair's cos twice; its sin with the sign of the partner's term
    cos = jnp.repeat(jnp.cos(ang), 2, -1)
    sin = jnp.repeat(jnp.sin(ang), 2, -1) * jnp.tile(
        jnp.asarray([-1.0, 1.0], jnp.float32), R // 2)
    tiles = token_tile_axes(B, T)
    cos, sin = (jnp.tile(t, (1, G // R)).reshape(*tiles[1:], 1, G)
                for t in (cos, sin))
    rows = x.reshape(*tiles, W // G, G)
    # x[2j + 1] at 2j and x[2j] at 2j + 1 as a product with the pairs'
    # permutation of a row, exact in any dtype (one 1 a column): the matrix
    # unit moves the lanes, where a roll of the lane axis is a pass of slices
    # and concatenations over the array in float32, forward and backward
    swap = np.zeros((G, G), np.float32)
    swap[np.arange(G) ^ 1, np.arange(G)] = 1.0
    partner = jnp.einsum(
        "...g,gh->...h", rows, jnp.asarray(swap, x.dtype),
        preferred_element_type=jnp.float32,
        precision=(jax.lax.Precision.HIGHEST if x.dtype == jnp.float32
                   else None))
    out = rows.astype(jnp.float32) * cos + partner * sin
    return out.astype(x.dtype).reshape(x.shape)


class LatentAttention(Module):
    """``q_nope_proj``, ``q_rope_proj``, ``kv_down_proj``, ``k_rope_proj``,
    ``kv_norm`` = {``weight`` (r,)}, ``k_up_proj``, ``v_up_proj``, ``o_proj``;
    module docstring."""

    def __init__(self, dim: int, heads: int, qk_nope: int, qk_rope: int,
                 v_dim: int, latent: int, rope_theta: float,
                 eps: float = 1e-6):
        super().__init__()
        if qk_nope != v_dim:
            raise ValueError(
                "the score head comes in two parts, the first at the value "
                f"head's width: qk_nope_head_dim {qk_nope} != v_head_dim "
                f"{v_dim}")
        if qk_rope % 2:
            raise ValueError(f"qk_rope_head_dim {qk_rope} is not pairs")
        from ..models.llama import RMSNorm
        self.H, self.dn, self.dr, self.r = heads, qk_nope, qk_rope, latent
        self.inv_freq = (1.0 / float(rope_theta) ** (
            np.arange(0, qk_rope, 2, dtype=np.float64) / qk_rope)).astype(
                np.float32)
        self.q_nope_proj = Linear(dim, heads * qk_nope, bias=False)
        self.q_rope_proj = Linear(dim, heads * qk_rope, bias=False)
        self.kv_down_proj = Linear(dim, latent, bias=False)
        self.k_rope_proj = Linear(dim, qk_rope, bias=False)
        self.kv_norm = RMSNorm(latent, eps)
        self.k_up_proj = Linear(latent, heads * qk_nope, bias=False)
        self.v_up_proj = Linear(latent, heads * v_dim, bias=False)
        self.o_proj = Linear(heads * v_dim, dim, bias=False)

    def forward(self, p, u):
        from ..observability.metrics import get_registry
        get_registry().counter(
            "mla_layers_total",
            help="latent-attention layers traced, by heads, score head, "
            "value head and latent width").labels(
                heads=str(self.H), qk=str(self.dn + self.dr),
                v=str(self.dn), latent=str(self.r)).inc()
        B, T, _ = u.shape
        with jax.named_scope("mla.q_proj"):
            q = self.q_nope_proj(p["q_nope_proj"], u)
            q_r = self.q_rope_proj(p["q_rope_proj"], u)
        with jax.named_scope("mla.kv_down"):
            c = self.kv_down_proj(p["kv_down_proj"], u)
            k_r = self.k_rope_proj(p["k_rope_proj"], u)
        with jax.named_scope("mla.kv_norm"):
            c = self.kv_norm(p["kv_norm"], c)
        with jax.named_scope("mla.kv_up"):
            k = self.k_up_proj(p["k_up_proj"], c)
            v = self.v_up_proj(p["v_up_proj"], c)
        with jax.named_scope("mla.rope"):
            q_r = rope_interleaved(q_r, self.inv_freq)
            k_r = rope_interleaved(k_r, self.inv_freq)
        heads = lambda y, n=self.H: y.reshape(B, T, n, -1)
        ctx = dot_product_attention_token_major(
            heads(q), heads(k), heads(v), causal=True,
            q_rope=heads(q_r), k_rope=heads(k_r, 1))
        with jax.named_scope("mla.o_proj"):
            return self.o_proj(p["o_proj"], ctx.reshape(B, T, -1))
