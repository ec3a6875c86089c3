"""Attention primitives (policy-aware, MXU-shaped).

The reference (2019 Apex) predates attention entirely (SURVEY.md §5:
long-context is absent there).  apex_tpu treats long-context as
first-class: this module provides the single-device attention core; the
sequence-parallel forms (ring attention over a mesh axis) live in
apex_tpu.transformer.ring_attention.

The inner matmuls route through the amp policy ("dot_product_attention" is
whitelisted → bf16 on the MXU) while the softmax runs in fp32 (blacklist),
matching the reference's cast philosophy applied to a new op.
"""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from ..nn import functional as F
from ..nn.module import Module, current_context
from ..nn.layers import Linear, Dropout

__all__ = ["dot_product_attention", "dot_product_attention_token_major",
           "MultiheadAttention", "set_path_hook"]

# Trace-time debug hook: parity harnesses comparing backends need to know
# which path a call compiled to, because flash vs dense differ
# statistically (dropout masks) and on fully-masked rows (see the
# dot_product_attention docstring).  The hook receives "flash" or
# "dense" each time dispatch resolves (at trace time, so once per
# compilation, not per step).
_path_hook = None


def set_path_hook(hook) -> None:
    """Install ``hook(path: str)`` (or None to clear).  A setter rather
    than a rebindable module global: ``from ... import path_hook`` would
    capture the value and assignments to it would silently install
    nothing."""
    global _path_hook
    _path_hook = hook


def _note_path(path: str) -> None:
    if _path_hook is not None:
        _path_hook(path)


def dot_product_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                          mask: Optional[jax.Array] = None,
                          scale: Optional[float] = None,
                          dropout_rate: float = 0.0,
                          causal: bool = False,
                          dropout_rng: Optional[jax.Array] = None,
                          segment_ids: Optional[jax.Array] = None,
                          window: Optional[int] = None
                          ) -> jax.Array:
    """q,k,v: (..., T, H) — softmax(qk^T/sqrt(H)) v with fp32 softmax.

    ``dropout_rate`` applies attention-probability dropout in train mode
    (rng drawn from the active apply-context, like nn.Dropout) — or
    unconditionally when an explicit ``dropout_rng`` is given (the
    functional path: the caller owns the train/eval decision, e.g. the
    sequence-parallel wrappers fold the device index into this key).
    ``segment_ids``: (B, T) int32 packed-sequence ids — attention is
    restricted to equal-id pairs (streamed through the flash kernel on
    TPU; applied as an equality mask on the dense path).
    ``causal=True`` applies the lower-triangular mask; on TPU this (and
    the mask-free case) dispatches to the fused Pallas flash kernel.
    Key-padding masks — a ``mask`` with no query-position dependence,
    shaped ``(B, 1, 1, Tk)`` (or with leading broadcast dims of 1) —
    ALSO stay on the flash path: the kernel streams the key-validity row
    alongside the K/V blocks.  Train-mode attention dropout stays on the
    flash path too (in-kernel counter-hash mask; the dense path and the
    kernel draw different masks from the rng, so expect statistical, not
    bitwise, agreement between backends).  Only arbitrary per-pair mask
    shapes take the dense path.
    ``window`` (static, with ``causal=True``): key j is visible to query i
    iff ``i - window < j <= i``.  The flash kernels apply the band and
    skip the block pairs outside it; the dense path builds the band mask.

    Caveat on fully-masked rows: flash emits zeros for a query whose
    keys are all masked, while the dense softmax degrades to a uniform
    average over all keys; real key-padding batches always keep at least
    one valid key per sequence."""
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    if not 0.0 <= dropout_rate < 1.0:
        raise ValueError(f"dropout_rate must be in [0, 1), got "
                         f"{dropout_rate}")
    if segment_ids is not None:
        if q.ndim != 4:
            raise ValueError("segment_ids requires (B, H, T, D) operands")
        expect = (q.shape[0], k.shape[-2])
        if segment_ids.shape != expect:
            raise ValueError(f"segment_ids must be (B, T) = {expect}, "
                             f"got {segment_ids.shape}")
    if window is not None and (not causal or window < 1):
        raise ValueError("window needs causal=True and window >= 1, got "
                         f"causal={causal}, window={window}")
    ctx = current_context()
    train_dropout = (dropout_rate > 0.0
                     and (dropout_rng is not None
                          or (ctx is not None and ctx.train)))
    B = q.shape[0] if q.ndim == 4 else None
    Tk = k.shape[-2]
    kv_mask = None
    if (mask is not None and q.ndim == 4 and mask.ndim == 4
            and mask.shape[-2] == 1 and mask.shape[1] == 1
            and mask.shape[0] in (1, B) and mask.shape[-1] == Tk):
        kv_mask = jnp.broadcast_to(mask[:, 0, 0, :] != 0, (B, Tk))
    if ((mask is None or kv_mask is not None)
            and q.ndim == 4 and q.shape == k.shape == v.shape):
        from ..ops import dispatch
        if dispatch.use_pallas_for(q):
            from ..ops import pallas_flash_attention as pfa
            if pfa.fits_vmem(q.shape[2], q.shape[3],
                             dropout=train_dropout,
                             segments=segment_ids is not None,
                             window=window):
                # same cast policy the dense path applies through its
                # whitelisted matmuls (op 'dot_product_attention' is in
                # amp.lists.FP16_FUNCS), so dtype is backend-independent
                from ..amp import policy as _pol
                (q, k, v), _ = _pol.cast_op_args("dot_product_attention",
                                                 (q, k, v), {})
                seed = None
                if train_dropout:
                    # both 32-bit key words feed the kernel's counter
                    # hash — a single word would collide by birthday
                    # bound over ~1e6 layer x step draws
                    key = (dropout_rng if dropout_rng is not None
                           else ctx.make_rng())
                    seed = jax.lax.bitcast_convert_type(
                        jax.random.key_data(key), jnp.int32)
                _note_path("flash")
                return pfa.flash_attention(
                    q, k, v, causal=causal, scale=scale, kv_mask=kv_mask,
                    dropout_rate=(dropout_rate if train_dropout else 0.0),
                    dropout_seed=seed, segment_ids=segment_ids,
                    window=window)
    _note_path("dense")
    if causal:
        Tq, Tk = q.shape[-2], k.shape[-2]
        # decode-style alignment: the last query attends to the full key
        # sequence (q_pos = Tk - Tq + i); reduces to lower-triangular
        # when Tq == Tk.  A user mask (e.g. padding) ANDs with the
        # causal constraint — it must never replace it.
        qpos = Tk - Tq + jnp.arange(Tq)
        cmask = qpos[:, None] >= jnp.arange(Tk)[None, :]
        if window is not None:
            cmask = cmask & (jnp.arange(Tk)[None, :]
                             > qpos[:, None] - window)
        mask = cmask if mask is None else jnp.logical_and(mask, cmask)
    scores = F.matmul(q, jnp.swapaxes(k, -1, -2)).astype(jnp.float32) * scale
    if mask is not None:
        scores = jnp.where(mask, scores, jnp.full_like(scores, -1e30))
    if segment_ids is not None:
        seg = (segment_ids[:, None, :, None]
               == segment_ids[:, None, None, :])
        scores = jnp.where(seg, scores, jnp.full_like(scores, -1e30))
    probs = jax.nn.softmax(scores, axis=-1)
    if train_dropout:
        key = dropout_rng if dropout_rng is not None else ctx.make_rng()
        probs = F.dropout(probs, dropout_rate, key)
    return F.matmul(probs.astype(v.dtype), v)


def dot_product_attention_token_major(q: jax.Array, k: jax.Array,
                                      v: jax.Array, causal: bool = False,
                                      scale: Optional[float] = None,
                                      window: Optional[int] = None,
                                      kv_mask: Optional[jax.Array] = None,
                                      segment_ids: Optional[jax.Array] = None,
                                      q_rope: Optional[jax.Array] = None,
                                      k_rope: Optional[jax.Array] = None
                                      ) -> jax.Array:
    """``dot_product_attention`` on operands where the projections wrote
    them: q (B, T, H, D); k, v (B, T, Hkv, D) with ``Hkv`` dividing ``H``
    (query head h reads K/V head ``h // (H // Hkv)``); -> (B, T, H, D).
    A caller that has ``x @ W`` as (B, T, H*D) reshapes it for free, moves
    no axis, repeats no K/V head, and reshapes the result for its output
    projection.

    On TPU, with a head of whole lane tiles (``D % 128 == 0``), the flash
    kernels read these arrays as they are (``ops.pallas_flash_attention``:
    token-major operands, K/V once per K/V head).  A head under one lane
    tile (64) is not a lane-aligned slice of a token's row, so it goes to the
    same kernels head-major, which take ``D < 128`` as it is and K/V at
    ``Hkv`` heads: one transpose each of q, k, v on the way in and of the
    result on the way out, never the dense path
    (``flash_calls_total{layout="head_major", kv="grouped"}`` says so).
    Elsewhere the dense path groups the query heads over the K/V heads in one
    einsum, softmax in fp32.  ``causal``, ``window``, ``segment_ids`` as in
    ``dot_product_attention``; ``kv_mask``: (B, T) bool key validity (True
    = attend), with its caveat on fully-masked rows.

    A value head narrower than the score head (latent attention: scores over
    192 = 128 + 64, values of 128): q, k and v at the value head's width
    ``D`` and the score head's trailing part beside them, ``q_rope``
    (B, T, H, R) and ``k_rope`` (B, T, 1, R), one head that every query head
    reads; a score is ``q . k + q_rope . k_rope`` over ``sqrt(D + R)`` and
    the result is (B, T, H, D).  Each part stays where its projection wrote
    it: on TPU at ``D % 128 == 0`` and ``R == 64`` the flash kernels take the
    parts as they are (two heads' rope parts a lane tile;
    ``flash_calls_total{rope="shared"}``), nothing is padded to 256 and the
    one shared head's gradient is the kernels' sum over the query heads.
    Elsewhere, and for a ``k_rope`` of a head a query head (B, T, H, R), the
    dense path joins the parts."""
    B, T, H, D = q.shape
    Hkv = k.shape[2]
    if k.shape != v.shape or k.shape != (B, T, Hkv, D) or H % Hkv:
        raise ValueError("expected q (B, T, H, D) and k, v (B, T, Hkv, D) "
                         "with Hkv dividing H (a wider score head's trailing "
                         "part comes as q_rope and k_rope), got "
                         f"{q.shape}, {k.shape}, {v.shape}")
    R = 0
    if q_rope is not None or k_rope is not None:
        if q_rope is None or k_rope is None:
            raise ValueError("q_rope and k_rope come together")
        R = q_rope.shape[-1]
        if (q_rope.shape != (B, T, H, R) or Hkv != H
                or k_rope.shape not in ((B, T, 1, R), (B, T, H, R))):
            raise ValueError(
                "expected q_rope (B, T, H, R) and k_rope (B, T, 1 or H, R) "
                "beside q, k, v at one head count, got "
                f"{q.shape}, {k.shape}, {q_rope.shape}, {k_rope.shape}")
    if scale is None:
        scale = 1.0 / math.sqrt(D + R)
    if window is not None and (not causal or window < 1):
        raise ValueError("window needs causal=True and window >= 1, got "
                         f"causal={causal}, window={window}")
    # the cast policy the dense matmuls of dot_product_attention apply (op
    # 'dot_product_attention' is in amp.lists.FP16_FUNCS), on either path
    from ..amp import policy as _pol
    (q, k, v), _ = _pol.cast_op_args("dot_product_attention", (q, k, v), {})
    if R:
        (q_rope, k_rope), _ = _pol.cast_op_args(
            "dot_product_attention", (q_rope, k_rope), {})
    from ..ops import dispatch
    # what the kernels take: a head under a lane tile or of whole ones; with a
    # rope part, whole ones, the part half a tile and two heads to share it
    takes = (D % 128 == 0 and R == 64 and H % 2 == 0
             and k_rope.shape[2] == 1) if R else (D % 128 == 0 or D < 128)
    if dispatch.use_pallas_for(q) and takes:
        from ..ops import pallas_flash_attention as pfa
        if pfa.fits_vmem(T, D, segments=segment_ids is not None,
                         window=window, rope=R):
            _note_path("flash")
            how = dict(causal=causal, scale=scale, kv_mask=kv_mask,
                       segment_ids=segment_ids, window=window)
            if R:
                return pfa.flash_attention_token_major(
                    q, k, v, q_rope=q_rope, k_rope=k_rope, **how)
            if D % 128 == 0:
                return pfa.flash_attention_token_major(q, k, v, **how)
            heads_first = lambda x: jnp.swapaxes(x, 1, 2)
            return heads_first(pfa.flash_attention(
                heads_first(q), heads_first(k), heads_first(v), **how))
    _note_path("dense")
    see = None if kv_mask is None else kv_mask[:, None, None, None, :]
    both = lambda a, b: b if a is None else jnp.logical_and(a, b)
    if causal:
        i, j = jnp.arange(T)[:, None], jnp.arange(T)[None, :]
        see = both(see, j <= i)
        if window is not None:
            see = both(see, j > i - window)
    if segment_ids is not None:
        see = both(see, (segment_ids[:, None, None, :, None]
                         == segment_ids[:, None, None, None, :]))
    if R:       # the score head whole: (B, T, H, D + R) on both sides
        q = jnp.concatenate([q, q_rope], -1)
        k = jnp.concatenate(
            [k, jnp.broadcast_to(k_rope, (B, T, H, R))], -1)
    grouped = q.reshape(B, T, Hkv, H // Hkv, D + R)
    scores = jnp.einsum("bqgrd,bkgd->bgrqk", grouped, k,
                        preferred_element_type=jnp.float32) * scale
    if see is not None:
        scores = jnp.where(see, scores, jnp.full_like(scores, -1e30))
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    return jnp.einsum("bgrqk,bkgd->bqgrd", probs, v).reshape(B, T, H, D)


class MultiheadAttention(Module):
    def __init__(self, embed_dim: int, num_heads: int, dropout: float = 0.0,
                 bias: bool = True):
        super().__init__()
        if embed_dim % num_heads:
            raise ValueError(f"num_heads ({num_heads}) must divide "
                             f"embed_dim ({embed_dim})")
        self.embed_dim = embed_dim
        self.num_heads = num_heads
        self.head_dim = embed_dim // num_heads
        self.qkv = Linear(embed_dim, 3 * embed_dim, bias=bias)
        self.out = Linear(embed_dim, embed_dim, bias=bias)
        self.drop = Dropout(dropout)

    def forward(self, params, x, mask: Optional[jax.Array] = None,
                key_padding_mask: Optional[jax.Array] = None):
        """``key_padding_mask``: (B, T) bool, True = IGNORE that key —
        torch.nn.MultiheadAttention's convention.  Internally inverted to
        key-validity and routed as a (B, 1, 1, T) mask, which the flash
        dispatch streams through the kernel."""
        B, T, E = x.shape
        qkv = self.qkv(params["qkv"], x)
        qkv = qkv.reshape(B, T, 3, self.num_heads, self.head_dim)
        q, k, v = (jnp.moveaxis(qkv[:, :, i], 2, 1) for i in range(3))
        if key_padding_mask is not None:
            kp = jnp.logical_not(key_padding_mask)[:, None, None, :]
            mask = kp if mask is None else jnp.logical_and(mask, kp)
        ctx = dot_product_attention(q, k, v, mask)
        ctx = jnp.moveaxis(ctx, 1, 2).reshape(B, T, E)
        ctx = self.drop(params.get("drop", {}), ctx)
        return self.out(params["out"], ctx)
