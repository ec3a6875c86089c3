"""Llama-family causal language model (RMSNorm + RoPE + SwiGLU + GQA).

The reference toolkit predates decoder-only LMs entirely; GPT-2
(models/gpt.py) covers the learned-position/LayerNorm generation, and
this module covers the modern generation every serving stack expects:
RMS pre-normalization, rotary position embeddings, SwiGLU MLPs,
grouped-query attention with the compact KV cache, and the fused
chunked LM-head loss (nn.fused_xent).  Output parity against the
HuggingFace torch implementation — including greedy generation token
for token — is pinned in tests/test_llama.py; ``utils.hf_interop
.llama_from_hf`` converts checkpoints.

TPU shape discipline matches GPT: fixed-buffer generation (one compiled
program for any prompt length), flash attention on the training path
via dot_product_attention's dispatch, int8 weight/KV-cache quantization
(apex_tpu.quantization) drops in unchanged.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from .. import nn
from ..nn import functional as F
from ..parallel.sync_batchnorm import _axis_in_scope as _sp_in_scope
from ..transformer.attention import dot_product_attention

__all__ = ["LlamaConfig", "Llama", "RMSNorm"]


class LlamaConfig:
    def __init__(self, vocab_size=32000, hidden_size=4096,
                 intermediate_size=11008, num_hidden_layers=32,
                 num_attention_heads=32, num_key_value_heads=None,
                 max_position_embeddings=2048, rms_norm_eps=1e-6,
                 rope_theta=10000.0, tie_word_embeddings=False,
                 head_chunk=8192, sp_axis=None, tp_axis=None,
                 remat=None, sliding_window=None, attention_bias=False,
                 head_dim=None, mlp_act="silu", rms_unit_offset=False,
                 embed_scale=False, norm_type="rmsnorm",
                 parallel_residual=False, rotary_pct=1.0,
                 mlp_type="swiglu", attention_out_bias=False):
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.intermediate_size = intermediate_size
        self.num_hidden_layers = num_hidden_layers
        self.num_attention_heads = num_attention_heads
        self.num_key_value_heads = (num_key_value_heads
                                    if num_key_value_heads is not None
                                    else num_attention_heads)
        if (self.num_key_value_heads < 1
                or num_attention_heads % self.num_key_value_heads):
            raise ValueError(
                f"num_key_value_heads={self.num_key_value_heads} must be "
                f"a positive divisor of num_attention_heads="
                f"{num_attention_heads}")
        if hidden_size % num_attention_heads:
            raise ValueError("hidden_size must divide into heads")
        self.max_position_embeddings = max_position_embeddings
        self.rms_norm_eps = rms_norm_eps
        self.rope_theta = rope_theta
        self.tie_word_embeddings = tie_word_embeddings
        self.head_chunk = head_chunk
        # sequence parallelism: tokens sharded over this mesh axis; the
        # causal attention runs as ring attention (K/V blocks rotate
        # over ICI) and RoPE uses GLOBAL positions, so
        # max_position_embeddings bounds the GLOBAL sequence (the GPT
        # sp contract, models/gpt.py)
        self.sp_axis = sp_axis
        # tensor parallelism: Megatron attention/MLP sharding over this
        # axis (parallel.ParallelSelfAttention with num_kv_heads +
        # rope_theta; SwiGLU as column/column/row).  Embeddings, norms,
        # and the LM head stay replicated — the row-parallel psum leaves
        # x replicated, so the fused head loss is unchanged.
        self.tp_axis = tp_axis
        if tp_axis is not None and sp_axis is not None:
            raise NotImplementedError(
                "combined tp+sp Llama is not wired; pick one")
        # per-block rematerialization: None | "nothing" | "dots"
        # (models/_remat.py) — the long-context HBM lever
        from ._remat import _MODES
        if remat not in _MODES:
            raise ValueError(f"remat={remat!r} not in {_MODES}")
        self.remat = remat
        # Mistral-style sliding-window attention: key j visible to
        # query i iff i - W < j <= i.  Training and prefill hand the
        # window to dot_product_attention (the flash kernels apply the
        # band and skip the blocks outside it); decode applies it in its
        # cache read.  The KV cache stays full-length (HF's rolling buffer
        # is a memory optimization, not a semantics change).
        if sliding_window is not None:
            if sliding_window < 1:
                raise ValueError(f"sliding_window={sliding_window} "
                                 f"must be >= 1")
            if sp_axis is not None or tp_axis is not None:
                raise NotImplementedError(
                    "sliding_window composes with dp only; the ring/"
                    "Megatron attention paths are full-window")
        self.sliding_window = sliding_window
        # Qwen2-style Q/K/V projection biases (o_proj stays bias-free)
        if attention_bias and tp_axis is not None:
            raise NotImplementedError(
                "attention_bias under tensor parallelism is not wired "
                "(ParallelSelfAttention biases all projections incl. "
                "out)")
        self.attention_bias = attention_bias
        # Gemma-family knobs: per-head dim decoupled from hidden_size
        # (gemma-7b: 16 heads x 256 > 3072), GeGLU MLP activation,
        # (1 + w) RMSNorm scaling, sqrt(hidden) embedding scale
        self.head_dim = (head_dim if head_dim is not None
                         else hidden_size // num_attention_heads)
        if head_dim is not None and tp_axis is not None:
            raise NotImplementedError(
                "custom head_dim under tensor parallelism is not wired")
        if mlp_act not in ("silu", "gelu_tanh"):
            raise ValueError(f"mlp_act={mlp_act!r} not in "
                             f"('silu', 'gelu_tanh')")
        self.mlp_act = mlp_act
        self.rms_unit_offset = rms_unit_offset
        self.embed_scale = embed_scale
        # GPT-NeoX/Pythia knobs: LayerNorm blocks, parallel residual
        # (x + attn(ln1 x) + mlp(ln2 x)), partial rotary (first
        # rotary_pct of each head's dims), biased 2-layer GeLU MLP
        if norm_type not in ("rmsnorm", "layernorm"):
            raise ValueError(f"norm_type={norm_type!r} not in "
                             f"('rmsnorm', 'layernorm')")
        self.norm_type = norm_type
        self.parallel_residual = parallel_residual
        if not 0.0 < rotary_pct <= 1.0:
            raise ValueError(f"rotary_pct={rotary_pct} not in (0, 1]")
        self.rotary_pct = rotary_pct
        if mlp_type not in ("swiglu", "gelu_mlp"):
            raise ValueError(f"mlp_type={mlp_type!r} not in "
                             f"('swiglu', 'gelu_mlp')")
        if mlp_type != "swiglu" and tp_axis is not None:
            raise NotImplementedError(
                "gelu_mlp under tensor parallelism is not wired")
        self.mlp_type = mlp_type
        self.attention_out_bias = attention_out_bias


class RMSNorm(nn.Module):
    """x * rsqrt(mean(x^2) + eps) * w — stats in fp32 (the norm is on
    amp's fp32 side, like LayerNorm), output in the input dtype."""

    def __init__(self, dim: int, eps: float = 1e-6,
                 unit_offset: bool = False):
        super().__init__()
        self.dim = dim
        self.eps = eps
        # Gemma convention: scale by (1 + w), checkpoint stores w
        self.unit_offset = unit_offset

    def create_params(self, key):
        return {"weight": jnp.ones((self.dim,), jnp.float32)}

    def forward(self, p, x):
        xf = x.astype(jnp.float32)
        var = jnp.mean(xf * xf, axis=-1, keepdims=True)
        y = xf * lax.rsqrt(var + self.eps)
        w = p["weight"].astype(jnp.float32)
        if self.unit_offset:
            w = 1.0 + w
        return (y * w).astype(x.dtype)


def _rope_cos_sin(pos, head_dim, theta, dtype):
    """HF-llama convention: inv_freq over the first D/2 dims, cos/sin
    tiled twice (rotate-half pairing, NOT interleaved)."""
    inv = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, jnp.float32)
                           / head_dim))
    ang = pos.astype(jnp.float32)[..., None] * inv      # (..., T, D/2)
    emb = jnp.concatenate([ang, ang], axis=-1)
    return jnp.cos(emb).astype(dtype), jnp.sin(emb).astype(dtype)


def _rotate_half(x):
    half = x.shape[-1] // 2
    return jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)


def apply_rope(q, k, pos, theta):
    """q: (B, H, T, D), k: (B, Hkv, T, D), pos: (B, T) or (T,)."""
    cos, sin = _rope_cos_sin(jnp.asarray(pos), q.shape[-1], theta,
                             jnp.float32)
    while cos.ndim < q.ndim:                  # -> broadcast over heads
        cos, sin = cos[..., None, :, :], sin[..., None, :, :]

    def rot(x):
        xf = x.astype(jnp.float32)
        return (xf * cos + _rotate_half(xf) * sin).astype(x.dtype)

    return rot(q), rot(k)


class LlamaAttention(nn.Module):
    def __init__(self, cfg: LlamaConfig):
        super().__init__()
        self.H = cfg.num_attention_heads
        self.Hkv = cfg.num_key_value_heads
        self.D = cfg.head_dim
        self.theta = cfg.rope_theta
        self.sp = cfg.sp_axis
        self.tp = cfg.tp_axis is not None
        self.window = getattr(cfg, "sliding_window", None)
        # partial rotary (GPT-NeoX): first rot_dim dims rotate, the
        # rest pass through
        self.rot_dim = int(getattr(cfg, "rotary_pct", 1.0) * self.D)
        E = cfg.hidden_size
        if self.tp:
            from ..parallel.tensor_parallel import ParallelSelfAttention
            self.core = ParallelSelfAttention(
                E, self.H, bias=False, causal=True,
                axis_name=cfg.tp_axis, num_kv_heads=self.Hkv,
                rope_theta=cfg.rope_theta)
        else:
            ab = getattr(cfg, "attention_bias", False)
            self.q_proj = nn.Linear(E, self.H * self.D, bias=ab)
            self.k_proj = nn.Linear(E, self.Hkv * self.D, bias=ab)
            self.v_proj = nn.Linear(E, self.Hkv * self.D, bias=ab)
            self.o_proj = nn.Linear(
                self.H * self.D, E,
                bias=getattr(cfg, "attention_out_bias", False))

    def _rope(self, q, k, pos):
        if self.rot_dim >= self.D:
            return apply_rope(q, k, pos, self.theta)
        rd = self.rot_dim
        q1, k1 = apply_rope(q[..., :rd], k[..., :rd], pos, self.theta)
        return (jnp.concatenate([q1, q[..., rd:]], axis=-1),
                jnp.concatenate([k1, k[..., rd:]], axis=-1))

    def _qkv(self, p, x, B, T):
        q = self.q_proj(p["q_proj"], x).reshape(B, T, self.H, self.D)
        k = self.k_proj(p["k_proj"], x).reshape(B, T, self.Hkv, self.D)
        v = self.v_proj(p["v_proj"], x).reshape(B, T, self.Hkv, self.D)
        return (jnp.moveaxis(q, 2, 1), jnp.moveaxis(k, 2, 1),
                jnp.moveaxis(v, 2, 1))

    def forward(self, p, x, mask=None):
        B, T, E = x.shape
        if self.tp:
            return self.core(p["core"], x, mask)
        q, k, v = self._qkv(p, x, B, T)
        in_sp = self.sp is not None and _sp_in_scope(self.sp)
        pos = jnp.arange(T)
        if in_sp:
            # GLOBAL positions for this device's token shard
            pos = lax.axis_index(self.sp) * T + pos
        q, k = self._rope(q, k, pos)
        if self.Hkv != self.H:
            rep = self.H // self.Hkv
            k = jnp.repeat(k, rep, axis=1)
            v = jnp.repeat(v, rep, axis=1)
        if in_sp:
            from ..transformer.ring_attention import ring_attention
            ctx = ring_attention(q, k, v, axis_name=self.sp, causal=True)
        else:
            ctx = dot_product_attention(q, k, v, mask, causal=True,
                                        dropout_rate=0.0,
                                        window=self.window)
        ctx = jnp.moveaxis(ctx, 1, 2).reshape(
            B, T, self.H * self.D)
        return self.o_proj(p["o_proj"], ctx)

    def prefill(self, p, x):
        """Full-sequence attention that also returns the COMPACT
        post-RoPE K/V for cache seeding: ``(out, k, v)`` with k/v
        (B, Hkv, T, D) — one MXU-friendly pass instead of T sequential
        ``decode`` steps (values identical to what decode would have
        written position by position)."""
        B, T, E = x.shape
        q, k, v = self._qkv(p, x, B, T)
        q, k = self._rope(q, k, jnp.arange(T))
        kc, vc = k, v
        if self.Hkv != self.H:
            rep = self.H // self.Hkv
            k = jnp.repeat(k, rep, axis=1)
            v = jnp.repeat(v, rep, axis=1)
        ctx = dot_product_attention(q, k, v, causal=True,
                                    dropout_rate=0.0, window=self.window)
        ctx = jnp.moveaxis(ctx, 1, 2).reshape(
            B, T, self.H * self.D)
        return self.o_proj(p["o_proj"], ctx), kc, vc

    def decode_chunk(self, p, x, pos, cache):
        """L-token cached step at PER-ROW positions: ``x`` (B, L, E)
        holds each row's tokens for positions ``[pos[b], pos[b]+L)``;
        writes the chunk's post-RoPE K/V there and attends each chunk
        query to cache keys <= its own position (within the sliding
        window if set).  This is the speculative-verify workhorse: one
        MXU pass scores gamma+1 proposals against the live cache.
        int8 caches quantize the chunk per position (the same
        amax/127 sidecar math as the single-token path)."""
        B, L, E = x.shape
        S = cache["k"].shape[2]
        rolling = self.window is not None and S == self.window
        if rolling and L > 1:
            # a chunk that wraps the ring overwrites slots still inside
            # EARLIER chunk queries' windows (slot (p' mod W) for a
            # later p' held p' - W, which is >= p - W + 1 for any
            # earlier in-chunk query p) — exactness would need per-query
            # cache snapshots.  L == 1 (the serving engine's tick) has
            # no such aliasing and is wired below.
            raise NotImplementedError(
                "decode_chunk over a rolling cache supports only "
                "L == 1 (engine ticks); use full-width caches for "
                "chunked verify/prefill")
        q, k, v = self._qkv(p, x, B, L)
        posL = pos[:, None] + jnp.arange(L)                 # (B, L)
        q, k = self._rope(q, k, posL)
        wpos = (pos % S) if rolling else pos                # write slot

        def put(buf, val):
            # per-row offsets: vmap a dynamic_update_slice over batch
            return jax.vmap(
                lambda b, vv, p0: lax.dynamic_update_slice(
                    b, vv.astype(b.dtype), (0, p0, 0)))(buf, val, wpos)

        cache = dict(cache)
        if cache["k"].dtype == jnp.int8:
            from ._cache import quantize_kv
            for name, val in (("k", k), ("v", v)):
                ints, scale = quantize_kv(val)
                cache[name] = put(cache[name], ints)
                cache[f"{name}_scale"] = put(cache[f"{name}_scale"],
                                             scale)
            kf = (cache["k"].astype(jnp.float32)
                  * cache["k_scale"].astype(jnp.float32))
            vf = (cache["v"].astype(jnp.float32)
                  * cache["v_scale"].astype(jnp.float32))
        else:
            cache["k"] = put(cache["k"], k)
            cache["v"] = put(cache["v"], v)
            kf = cache["k"].astype(jnp.float32)
            vf = cache["v"].astype(jnp.float32)
        G = self.H // self.Hkv
        qg = q.reshape(B, self.Hkv, G, L, self.D)
        scores = jnp.einsum("bkgld,bksd->bkgls",
                            qg.astype(jnp.float32), kf)
        scores = scores * (1.0 / (self.D ** 0.5))
        kpos = jnp.arange(S)[None, None, None, None, :]
        qpos = posL[:, None, None, :, None]
        if rolling:
            # slot s holds absolute position q - ((q - s) mod W) per
            # row (the step path's reconstruction, vectorized over B):
            # always <= q and > q - W, so only p_s >= 0 needs checking
            p_s = qpos - ((qpos - kpos) % S)
            valid = p_s >= 0
        else:
            valid = kpos <= qpos
            if self.window is not None:
                valid = valid & (kpos > qpos - self.window)
        scores = jnp.where(valid, scores, -1e30)
        probs = jax.nn.softmax(scores, axis=-1)
        ctx = jnp.einsum("bkgls,bksd->bkgld", probs, vf).astype(x.dtype)
        ctx = jnp.transpose(ctx, (0, 3, 1, 2, 4)).reshape(
            B, L, self.H * self.D)
        return self.o_proj(p["o_proj"], ctx), cache

    def decode(self, p, x, pos, cache):
        """One-token step; ``cache`` {"k","v"} (B, Hkv, S, D) (+int8
        scale sidecars) — RoPE applied at ``pos`` before the write, so
        cached keys are already rotated (the standard layout)."""
        if self.tp:
            raise NotImplementedError(
                "KV-cache decode is single-device; run the TP model "
                "through forward() or shard the batch instead")
        B, _, E = x.shape
        S = cache["k"].shape[2]
        q, k, v = self._qkv(p, x, B, 1)
        q, k = self._rope(q, k, jnp.full((1,), pos))
        q, k, v = q[:, :, 0], k[:, :, 0], v[:, :, 0]
        q8 = cache["k"].dtype == jnp.int8
        # rolling buffer: a cache exactly window-wide stores position p
        # in slot p % W (Mistral's layout) — W entries instead of the
        # full sequence; the slot's absolute position is reconstructed
        # below for the validity mask
        rolling = self.window is not None and S == self.window
        wpos = (pos % S) if rolling else pos

        def put(buf, val):
            return lax.dynamic_update_slice_in_dim(
                buf, val[:, :, None, :].astype(buf.dtype), wpos, axis=2)

        cache = dict(cache)
        if q8:
            from ._cache import quantize_kv
            for name, val in (("k", k), ("v", v)):
                ints, scale = quantize_kv(val)
                cache[name] = put(cache[name], ints)
                cache[f"{name}_scale"] = put(cache[f"{name}_scale"], scale)
            kf = (cache["k"].astype(jnp.float32)
                  * cache["k_scale"].astype(jnp.float32))
            vf = (cache["v"].astype(jnp.float32)
                  * cache["v_scale"].astype(jnp.float32))
        else:
            cache["k"] = put(cache["k"], k)
            cache["v"] = put(cache["v"], v)
            kf = cache["k"].astype(jnp.float32)
            vf = cache["v"].astype(jnp.float32)
        G = self.H // self.Hkv
        qg = q.reshape(B, self.Hkv, G, self.D)
        scores = jnp.einsum("bkgd,bksd->bkgs", qg.astype(jnp.float32), kf)
        scores = scores * (1.0 / (self.D ** 0.5))
        if rolling:
            # slot s holds absolute position pos - ((pos - s) mod W)
            s_idx = jnp.arange(S)
            p_s = pos - ((pos - s_idx) % S)
            valid = (p_s >= 0)[None, None, None, :]
        else:
            valid = jnp.arange(S)[None, None, None, :] <= pos
            if self.window is not None:
                valid = valid & (jnp.arange(S)[None, None, None, :]
                                 > pos - self.window)
        scores = jnp.where(valid, scores, -1e30)
        probs = jax.nn.softmax(scores, axis=-1)
        ctx = jnp.einsum("bkgs,bksd->bkgd", probs, vf).astype(x.dtype)
        return self.o_proj(
            p["o_proj"], ctx.reshape(B, 1, self.H * self.D)), cache


class LlamaMLP(nn.Module):
    def __init__(self, cfg: LlamaConfig):
        super().__init__()
        self.tp_axis = cfg.tp_axis
        self.act = getattr(cfg, "mlp_act", "silu")
        if cfg.tp_axis is not None:
            from ..parallel.tensor_parallel import (ColumnParallelLinear,
                                                    RowParallelLinear)
            # SwiGLU Megatron-style: gate/up column-parallel (one f at
            # entry, shared by both), down row-parallel (one psum)
            self.gate_proj = ColumnParallelLinear(
                cfg.hidden_size, cfg.intermediate_size, bias=False,
                input_grad_reduce=False, axis_name=cfg.tp_axis)
            self.up_proj = ColumnParallelLinear(
                cfg.hidden_size, cfg.intermediate_size, bias=False,
                input_grad_reduce=False, axis_name=cfg.tp_axis)
            self.down_proj = RowParallelLinear(
                cfg.intermediate_size, cfg.hidden_size, bias=False,
                axis_name=cfg.tp_axis)
        else:
            self.gate_proj = nn.Linear(cfg.hidden_size,
                                       cfg.intermediate_size, bias=False)
            self.up_proj = nn.Linear(cfg.hidden_size,
                                     cfg.intermediate_size, bias=False)
            self.down_proj = nn.Linear(cfg.intermediate_size,
                                       cfg.hidden_size, bias=False)

    def forward(self, p, x):
        if self.tp_axis is not None:
            from ..parallel.tensor_parallel import copy_to_model_parallel
            x = copy_to_model_parallel(x, self.tp_axis)
        act = F.silu if self.act == "silu" else F.gelu
        return self.down_proj(
            p["down_proj"],
            act(self.gate_proj(p["gate_proj"], x))
            * self.up_proj(p["up_proj"], x))


class GeluMLP(nn.Module):
    """GPT-NeoX 2-layer MLP: dense_h_to_4h -> exact gelu ->
    dense_4h_to_h, biases throughout (param names match the HF
    checkpoint keys for converter transparency)."""

    def __init__(self, cfg: LlamaConfig):
        super().__init__()
        self.dense_h_to_4h = nn.Linear(cfg.hidden_size,
                                       cfg.intermediate_size, bias=True)
        self.dense_4h_to_h = nn.Linear(cfg.intermediate_size,
                                       cfg.hidden_size, bias=True)

    def forward(self, p, x):
        return self.dense_4h_to_h(
            p["dense_4h_to_h"],
            F.gelu_exact(self.dense_h_to_4h(p["dense_h_to_4h"], x)))


def _make_norm(cfg):
    if getattr(cfg, "norm_type", "rmsnorm") == "layernorm":
        from ..normalization import FusedLayerNorm
        return FusedLayerNorm(cfg.hidden_size, eps=cfg.rms_norm_eps)
    return RMSNorm(cfg.hidden_size, cfg.rms_norm_eps,
                   getattr(cfg, "rms_unit_offset", False))


class LlamaBlock(nn.Module):
    def __init__(self, cfg: LlamaConfig):
        super().__init__()
        self.input_layernorm = _make_norm(cfg)
        self.self_attn = LlamaAttention(cfg)
        self.post_attention_layernorm = _make_norm(cfg)
        self.mlp = (GeluMLP(cfg)
                    if getattr(cfg, "mlp_type", "swiglu") == "gelu_mlp"
                    else LlamaMLP(cfg))
        self.parallel_residual = getattr(cfg, "parallel_residual",
                                         False)

    def forward(self, p, x, mask=None):
        a = self.self_attn(p["self_attn"],
                           self.input_layernorm(
                               p["input_layernorm"], x), mask)
        if self.parallel_residual:      # NeoX: both norms see x
            return x + a + self.mlp(
                p["mlp"], self.post_attention_layernorm(
                    p["post_attention_layernorm"], x))
        x = x + a
        return x + self.mlp(p["mlp"], self.post_attention_layernorm(
            p["post_attention_layernorm"], x))

    def decode(self, p, x, pos, cache):
        a, cache = self.self_attn.decode(
            p["self_attn"], self.input_layernorm(p["input_layernorm"], x),
            pos, cache)
        if self.parallel_residual:
            return x + a + self.mlp(
                p["mlp"], self.post_attention_layernorm(
                    p["post_attention_layernorm"], x)), cache
        x = x + a
        return x + self.mlp(p["mlp"], self.post_attention_layernorm(
            p["post_attention_layernorm"], x)), cache

    def prefill(self, p, x):
        a, k, v = self.self_attn.prefill(
            p["self_attn"], self.input_layernorm(p["input_layernorm"], x))
        if self.parallel_residual:
            return x + a + self.mlp(
                p["mlp"], self.post_attention_layernorm(
                    p["post_attention_layernorm"], x)), k, v
        x = x + a
        return x + self.mlp(p["mlp"], self.post_attention_layernorm(
            p["post_attention_layernorm"], x)), k, v

    def decode_chunk(self, p, x, pos, cache):
        a, cache = self.self_attn.decode_chunk(
            p["self_attn"], self.input_layernorm(p["input_layernorm"], x),
            pos, cache)
        if self.parallel_residual:
            return x + a + self.mlp(
                p["mlp"], self.post_attention_layernorm(
                    p["post_attention_layernorm"], x)), cache
        x = x + a
        return x + self.mlp(p["mlp"], self.post_attention_layernorm(
            p["post_attention_layernorm"], x)), cache


class Llama(nn.Module):
    block_cls = LlamaBlock      # hook for MoE (Mixtral) variants

    def __init__(self, cfg: LlamaConfig):
        super().__init__()
        self.cfg = cfg
        # Llama's initializer_range=0.02 (scratch-training sanity with
        # tied heads; HF-loaded checkpoints overwrite it anyway)
        self.embed_tokens = nn.Embedding(cfg.vocab_size,
                                         cfg.hidden_size,
                                         init_std=0.02)
        self.layers = nn.ModuleList(
            [self.block_cls(cfg) for _ in range(cfg.num_hidden_layers)])
        self.norm = _make_norm(cfg)
        if not cfg.tie_word_embeddings:
            self.lm_head = nn.Linear(cfg.hidden_size, cfg.vocab_size,
                                     bias=False)

    def _table(self, p):
        return (p["embed_tokens"]["weight"]
                if self.cfg.tie_word_embeddings
                else p["lm_head"]["weight"])

    def _backbone(self, p, input_ids, mask=None):
        B, T = input_ids.shape
        sp = self.cfg.sp_axis
        if sp is not None and _sp_in_scope(sp):
            if mask is not None:
                raise NotImplementedError(
                    "attention_mask under sequence parallelism is not "
                    "wired; pack/pad outside the sp axis instead")
            if T * lax.axis_size(sp) > self.cfg.max_position_embeddings:
                raise ValueError(
                    f"global sequence {T}x{lax.axis_size(sp)} exceeds "
                    f"max_position_embeddings "
                    f"{self.cfg.max_position_embeddings}")
        elif T > self.cfg.max_position_embeddings:
            raise ValueError(f"sequence length {T} exceeds "
                             f"max_position_embeddings "
                             f"{self.cfg.max_position_embeddings}")
        x = self.embed_tokens(p["embed_tokens"], input_ids)
        if self.cfg.embed_scale:
            x = x * jnp.asarray(self.cfg.hidden_size ** 0.5, x.dtype)
        m = None
        if mask is not None:
            m = mask[:, None, None, :].astype(bool)
        aux = 0.0
        from ._remat import wrap_block
        for i in range(self.cfg.num_hidden_layers):
            fn = wrap_block(
                lambda pp, xx, blk=self.layers[i]: blk(pp, xx, m),
                self.cfg.remat)
            out = fn(p["layers"][str(i)], x)
            if isinstance(out, tuple):      # MoE block: (x, aux loss)
                x, a = out
                aux = aux + a
            else:
                x = out
        return (self.norm(p["norm"], x),
                aux / self.cfg.num_hidden_layers)

    def forward(self, p, input_ids, attention_mask=None):
        x, _ = self._backbone(p, input_ids, attention_mask)
        table = self._table(p)
        return F.matmul(x, table.T.astype(x.dtype))

    def loss(self, p, input_ids, attention_mask=None, ignore_index=-100):
        """Next-token cross-entropy via the fused chunked head
        (nn.fused_xent) — same contract as GPT.loss, including the
        cross-shard label shift under ``sp_axis``."""
        sp = self.cfg.sp_axis
        if sp is not None and _sp_in_scope(sp):
            if attention_mask is not None:
                raise NotImplementedError(
                    "attention_mask under sequence parallelism is not "
                    "wired; pack/pad outside the sp axis instead")
            B, T = input_ids.shape
            spn = lax.axis_size(sp)
            idx = lax.axis_index(sp)
            x, aux = self._backbone(p, input_ids)
            nxt_first = lax.ppermute(
                input_ids[:, :1], sp,
                [(i, (i - 1) % spn) for i in range(spn)])
            labels = jnp.concatenate([input_ids[:, 1:], nxt_first], 1)
            is_last = (idx == spn - 1)
            labels = labels.at[:, -1].set(
                jnp.where(is_last, ignore_index, labels[:, -1]))
            valid = labels != ignore_index
            safe = jnp.where(valid, labels, 0)
            nll = self._nll(p, x, safe)
            num = lax.psum(jnp.sum(nll * valid), sp)
            den = lax.psum(jnp.sum(valid.astype(jnp.float32)), sp)
            return num / jnp.maximum(den, 1.0) + self._aux_term(aux, sp)
        labels = input_ids[:, 1:]
        if attention_mask is not None:
            labels = jnp.where(attention_mask[:, 1:] != 0, labels,
                               ignore_index)
        x, aux = self._backbone(p, input_ids, attention_mask)
        x = x[:, :-1]
        valid = labels != ignore_index
        safe = jnp.where(valid, labels, 0)
        nll = self._nll(p, x, safe)
        return (jnp.sum(nll * valid) / jnp.maximum(jnp.sum(valid), 1)
                + self._aux_term(aux, None))

    def _aux_term(self, aux, sp):
        """Router load-balance contribution; 0 for dense families."""
        coef = getattr(self.cfg, "router_aux_loss_coef", 0.0)
        if not coef:
            return 0.0
        if sp is not None:
            aux = lax.pmean(aux, sp)
        return coef * aux

    def _nll(self, p, x, safe_labels):
        """Per-position nll (B, T') through the head — fused chunked
        path by default (GPT._head_nll's contract)."""
        table = self._table(p)
        from ..quantization import QTensor
        if isinstance(table, QTensor):
            table = table.dequant(x.dtype)
        B, T, D = x.shape
        if self.cfg.head_chunk:
            from ..nn.fused_xent import linear_cross_entropy
            return linear_cross_entropy(
                x.reshape(B * T, D), table, safe_labels.reshape(-1),
                int(self.cfg.head_chunk)).reshape(B, T)
        logits = F.matmul(x, table.T.astype(x.dtype))
        logp = F.log_softmax(logits.astype(jnp.float32), axis=-1)
        return -jnp.take_along_axis(logp, safe_labels[..., None],
                                    axis=-1)[..., 0]

    # -- KV-cached decoding (mirrors GPT's fixed-buffer discipline) -----
    def init_cache(self, batch_size: int, dtype=jnp.float32,
                   rolling: bool = False):
        """``rolling=True`` (requires ``sliding_window``) allocates
        window-wide buffers — position p lives in slot p % W, so cache
        memory is O(window), not O(sequence); decode detects the layout
        from the buffer width."""
        cfg = self.cfg
        if rolling and cfg.sliding_window is None:
            raise ValueError("rolling cache requires sliding_window")
        width = (cfg.sliding_window if rolling
                 else cfg.max_position_embeddings)
        shape = (batch_size, cfg.num_key_value_heads,
                 width, cfg.head_dim)

        # one allocation PER LAYER — a zeros buffer shared across
        # layers would be donated num_hidden_layers times by the
        # serving engine's cache mutators (XLA rejects double donation)
        def layer():
            out = {"k": jnp.zeros(shape, dtype),
                   "v": jnp.zeros(shape, dtype)}
            if dtype == jnp.int8:
                sshape = shape[:3] + (1,)
                out["k_scale"] = jnp.zeros(sshape, jnp.float32)
                out["v_scale"] = jnp.zeros(sshape, jnp.float32)
            return out

        return {str(i): layer()
                for i in range(cfg.num_hidden_layers)}

    def _decode_hidden(self, p, token, pos, cache):
        """Blocks-only decode step — the LM head is separate so prefill
        steps can skip the full-vocab matmul (GPT's contract)."""
        new_cache = {}
        x = self.embed_tokens(p["embed_tokens"], token[:, None])
        if self.cfg.embed_scale:
            x = x * jnp.asarray(self.cfg.hidden_size ** 0.5, x.dtype)
        for i in range(self.cfg.num_hidden_layers):
            li = str(i)
            x, new_cache[li] = self.layers[i].decode(
                p["layers"][li], x, pos, cache[li])
        return self.norm(p["norm"], x), new_cache

    def decode_step(self, p, token, pos, cache):
        x, new_cache = self._decode_hidden(p, token, pos, cache)
        table = self._table(p)
        return F.matmul(x, table.T.astype(x.dtype))[:, 0], new_cache

    def prefill_cache(self, p, input_ids, cache=None, cache_dtype=None):
        """Seed every layer's KV cache with ONE full-buffer forward
        (models/_cache.py semantics; identical values to walking the
        positions with decode)."""
        from ._cache import seed_layer
        B, S = input_ids.shape
        if cache is None:
            if cache_dtype is None:
                cache_dtype = self._table(p).dtype
            cache = self.init_cache(B, dtype=cache_dtype)
        x = self.embed_tokens(p["embed_tokens"], input_ids)
        if self.cfg.embed_scale:
            x = x * jnp.asarray(self.cfg.hidden_size ** 0.5, x.dtype)
        for i in range(self.cfg.num_hidden_layers):
            li = str(i)
            x, k, v = self.layers[i].prefill(p["layers"][li], x)
            cache[li] = seed_layer(cache[li], k, v)
        return cache

    def decode_chunk(self, p, tokens, pos, cache):
        """Cached multi-token step at per-row positions: ``tokens``
        (B, L) for positions ``[pos[b], pos[b]+L)`` -> (final hidden
        (B, L, E), updated cache).  The head stays separate (same
        contract as _decode_hidden)."""
        x = self.embed_tokens(p["embed_tokens"], tokens)
        if self.cfg.embed_scale:
            x = x * jnp.asarray(self.cfg.hidden_size ** 0.5, x.dtype)
        new_cache = {}
        for i in range(self.cfg.num_hidden_layers):
            li = str(i)
            x, new_cache[li] = self.layers[i].decode_chunk(
                p["layers"][li], x, pos, cache[li])
        return self.norm(p["norm"], x), new_cache

    def generate_cached(self, p, input_ids, prompt_len,
                        max_new_tokens: int, temperature: float = 0.0,
                        rng: Optional[jax.Array] = None,
                        cache_dtype=None,
                        top_k: Optional[int] = None,
                        top_p: Optional[float] = None,
                        prefill_mode: str = "chunked",
                        rolling_cache: bool = False,
                        min_p: Optional[float] = None,
                        repetition_penalty: float = 1.0):
        """Fixed-buffer KV-cached greedy/sampled generation; one
        compiled program for any prompt length, prefill steps skipping
        the full-vocab head via ``lax.cond`` (GPT.generate_cached's
        contract; token-for-token vs HF greedy in tests).
        ``top_k``/``top_p`` filter sampled steps (models/sampling.py).

        ``prefill_mode="chunked"`` (default) seeds the KV cache with
        ONE full-buffer forward (models/_cache.py) and starts the
        sequential loop at the earliest prompt end — prefill rides the
        MXU instead of min(prompt_len) dependent steps.  ``"step"``
        restores the walk-every-position loop.

        ``rolling_cache=True`` (sliding-window models) allocates
        window-wide cache buffers (O(window) memory); the loop walks
        every position ("step" prefill — slots fill as it goes), and
        each step attends only the window's W entries."""
        from . import sampling
        if prefill_mode not in ("chunked", "step"):
            raise ValueError(f"prefill_mode {prefill_mode!r} not in "
                             f"('chunked', 'step')")
        if rolling_cache:
            prefill_mode = "step"     # slots fill as the loop walks
        B, S = input_ids.shape
        prompt_len = jnp.broadcast_to(jnp.asarray(prompt_len), (B,))
        if temperature > 0.0 and rng is None:
            raise ValueError("sampling (temperature > 0) needs rng=")
        final_len = jnp.minimum(prompt_len + max_new_tokens, S)
        first_gen = jnp.min(prompt_len)
        if cache_dtype is None:
            cache_dtype = self._table(p).dtype
        cache = self.init_cache(B, dtype=cache_dtype,
                                rolling=rolling_cache)
        key = rng if rng is not None else jax.random.PRNGKey(0)
        start = 0
        if prefill_mode == "chunked":
            cache = self.prefill_cache(p, input_ids, cache)
            # entries at positions >= first_gen - 1 are rewritten by
            # the loop before any later position reads them
            start = jnp.maximum(first_gen - 1, 0)

        def body(i, carry):
            ids, cache, key = carry
            x, cache = self._decode_hidden(p, ids[:, i], i, cache)

            def live(args):
                x, key = args
                table = self._table(p)
                logits = F.matmul(x, table.T.astype(x.dtype))[:, 0]
                if repetition_penalty != 1.0:
                    logits = sampling.apply_repetition_penalty(
                        logits, ids, jnp.maximum(prompt_len, i + 1),
                        repetition_penalty)
                if temperature > 0.0:
                    key, sub = jax.random.split(key)
                    nxt = sampling.sample_token(sub, logits, temperature,
                                                top_k=top_k, top_p=top_p,
                                                min_p=min_p)
                else:
                    nxt = jnp.argmax(logits, axis=-1)
                return nxt.astype(ids.dtype), key

            def prefill(args):
                _, key = args
                return jnp.zeros((B,), ids.dtype), key

            nxt, key = lax.cond(i + 1 >= first_gen, live, prefill,
                                (x, key))
            should = (i + 1 >= prompt_len) & (i + 1 < final_len)
            col = jnp.where(should, nxt, ids[:, i + 1])
            ids = lax.dynamic_update_slice_in_dim(
                ids, col[:, None], i + 1, axis=1)
            return ids, cache, key

        ids, _, _ = lax.fori_loop(start, jnp.max(final_len) - 1, body,
                                  (input_ids, cache, key))
        return ids, final_len


def llama_params_to_tp(params):
    """Rename a non-TP Llama param tree to the ``tp_axis`` structure.

    Under ``tp_axis`` attention is implemented by
    ``parallel.tensor_parallel.ParallelSelfAttention``, whose param tree
    is ``self_attn.core.{q,k,v,out}`` rather than the HF-style
    ``self_attn.{q_proj,k_proj,v_proj,o_proj}``; the MLP keeps its
    names (only the sharding layout changes).  Use this to feed
    ``utils.hf_interop.llama_from_hf`` output — or any checkpoint
    trained without tp_axis — into ``Llama(LlamaConfig(tp_axis=...))``.
    Weights stay full-size; sharding is applied by
    ``parallel.tensor_parallel.partition_specs`` + shard_map.
    """
    out = dict(params)
    out["layers"] = {}
    for i, blk in params["layers"].items():
        blk = dict(blk)
        at = blk.pop("self_attn")
        blk["self_attn"] = {"core": {
            "q": {"weight": at["q_proj"]["weight"]},
            "k": {"weight": at["k_proj"]["weight"]},
            "v": {"weight": at["v_proj"]["weight"]},
            "out": {"weight": at["o_proj"]["weight"]},
        }}
        out["layers"][i] = blk
    return out
