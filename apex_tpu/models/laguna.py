"""A decoder whose layers differ: per layer a token mixer (full or
sliding-window attention, or a gated short convolution), a query-head count,
a RoPE kind and an MLP kind (dense SwiGLU or routed experts, with or without
a shared expert).

The configuration says which decoder it is, in the keys of the published
config files of the families built on this shape (``layer_types``,
``mlp_layer_types``, one ``rope_parameters`` group per attention kind,
``sliding_window``; ``num_attention_heads_per_layer`` or one
``num_attention_heads`` for every layer; ``norm_topk_prob``;
``total_ut_steps``).  Four run in the benchmark as they are built here, and
two more over these parts (``models/nemotron_h.py``, its one-branch blocks;
``models/deepseek_v3.py``, which swaps the attention for a latent one):
``laguna`` (a leading dense layer, two head counts, a gate on the
attention output, a sigmoid router with a scaling factor and a shared
expert: the defaults below), ``mellum`` (every layer sparse, one head
count, no gate, a softmax router, no shared expert), ``lfm2_moe`` (``conv``
layers 3:1 with full attention at a head of 64 with QK-norm, a leading dense
layer, a sigmoid router with a selection bias, a tied head) and ``ouro``
(every layer dense with full attention, the stack applied ``total_ut_steps``
times over the same weights, sandwich-normed blocks, a learned exit gate and a
loss that weighs every pass's head by the exit distribution).  Pre-norm
residual blocks on the Llama parts (models/llama.py: RMSNorm, SwiGLU, the
fused chunked head), with

- attention: ``H_l`` query heads over ``num_key_value_heads`` K/V heads,
  causal, a band of ``sliding_window`` keys in a sliding layer (handed to
  ``dot_product_attention_token_major(window=)``, so the flash kernels
  apply it, on q, k, v where the projections wrote them), RoPE
  per kind (``default``: theta, the whole or a leading part of the head;
  ``yarn``: blended frequencies and a scale on cos/sin, as ``transformers``
  computes them), — ``gating`` — a sigmoid gate per head on the
  attention output, ``o_h <- sigmoid(x w_h) * o_h``, and — ``qk_norm`` — an
  RMSNorm over each head of q and of k before RoPE (one gain vector each,
  shared by the heads).  A head that is not whole lane tiles (64) reaches the
  flash kernels head-major behind one transpose each way
  (``dot_product_attention_token_major``), with the norm and the rotation
  written on the (B, T, heads, D) view that transpose reads;
- a ``conv`` layer: ``transformer.GatedShortConv`` in attention's place
  (module ``conv``; ``conv_L_cache`` taps), which needs no ``rope_parameters``;
- sparse MLP: ``parallel.ExpertParallelMLP`` with a ``router_type``
  (``sigmoid`` or ``softmax``) router over the published
  ``router_experts``, the ``num_experts_per_tok`` largest renormalized
  (a config's ``norm_topk_prob``: one that states false is refused) and
  scaled by ``moe_routed_scaling_factor``, — ``use_expert_bias`` — a
  selection bias that enters the choice and not the weights, the
  experts this chip holds (``experts_held``) and, where
  ``shared_expert_intermediate_size`` is not 0, a shared expert;
- ``tie_word_embeddings``: the head's matrix is the embedding's, one leaf;
- ``sandwich_norm``: a second RMSNorm on what the mixer and what the MLP
  return (``input_layernorm_2``, ``post_attention_layernorm_2``), each before
  its residual add;
- ``total_ut_steps`` R > 1, the looped stack: one ``lax.scan`` over the pass
  index (scope ``loop``) whose body is the layers and the final norm
  (``loop.norm``), the weights closed over, so that a compiled step holds each
  block once a direction; the normed state of a pass is the next pass's input
  and the R of them are what the head and the exit gate read.  The loss
  (``_exit_loss``): the fused head on every pass's state (``loss.head``) and,
  in float32 (``loss.exit``), ``exit_gate`` (``Linear(hidden, 1)`` with a
  bias, float32 leaves), ``lambda_t = sigmoid(h_t . w + b)``, the exit
  distribution ``p_t = lambda_t prod_{j<t} (1 - lambda_j)`` with the last pass
  taking what is left, and ``sum_t p_t nll_t - exit_beta H(p)`` a position.
  ``looped_stack_total{passes, layers}`` and ``exit_gate_calls_total`` count
  what a traced program holds (docs/observability.md).

Training and full-sequence forward only: a cache for decoding would have to
hold window and global layers side by side, and one slot a pass and layer
under a looped stack (ROADMAP, Reach).
"""

from __future__ import annotations

import inspect
import math
from typing import Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from .. import nn
from ..nn import functional as F
from ..ops.pallas_common import token_tile_axes
from ..parallel.expert_parallel import ExpertParallelMLP
from ..transformer.attention import dot_product_attention_token_major
from ._remat import _MODES, wrap_block
from ..transformer.short_conv import GatedShortConv
from .llama import LlamaMLP, RMSNorm, _rotate_half

__all__ = ["LagunaConfig", "Laguna", "rope_inv_freq", "exit_log_probs"]

FULL, SLIDING, CONV = "full_attention", "sliding_attention", "conv"


class LagunaConfig:
    """Sizes per layer.  ``num_experts`` experts are HELD here, from
    ``experts_held_start``, of the ``router_experts`` the router scores
    (default: all of them are held).  ``num_attention_heads_per_layer``
    may be left None where one ``num_attention_heads`` serves every
    layer; ``shared_expert_intermediate_size`` 0 is no shared expert.  A
    ``conv`` entry of ``layer_types`` is a gated short convolution of
    ``conv_L_cache`` taps (its head count is not read).  The three expert
    sizes are needed only where a layer is ``sparse``.  ``total_ut_steps``
    above 1 applies the stack that many times (dense layers only) and
    trains through the exit gate with the entropy weight ``exit_beta``."""

    def __init__(self, vocab_size, hidden_size, intermediate_size,
                 layer_types: Sequence[str],
                 num_attention_heads_per_layer: Optional[Sequence[int]],
                 mlp_layer_types: Sequence[str],
                 num_key_value_heads, head_dim, rope_parameters: dict,
                 sliding_window, num_experts=None, num_experts_per_tok=None,
                 moe_intermediate_size=None, shared_expert_intermediate_size=0,
                 moe_routed_scaling_factor=1.0, router_experts=None,
                 experts_held_start=0, moe_row_buffer_factor=None,
                 gating=True, rms_norm_eps=1e-6,
                 max_position_embeddings=8192, remat=None, head_chunk=8192,
                 num_attention_heads=None, router_type="sigmoid",
                 norm_topk_prob=True, qk_norm=False, conv_L_cache=3,
                 use_expert_bias=False, tie_word_embeddings=False,
                 router_out_in=False, total_ut_steps=1, sandwich_norm=False,
                 exit_beta=0.0):
        n = len(layer_types)
        if num_attention_heads_per_layer is None:
            if num_attention_heads is None:
                raise ValueError("neither num_attention_heads_per_layer "
                                 "nor num_attention_heads is given")
            num_attention_heads_per_layer = [num_attention_heads] * n
        if not (len(num_attention_heads_per_layer) == n
                and len(mlp_layer_types) == n):
            raise ValueError("layer_types, num_attention_heads_per_layer "
                             "and mlp_layer_types must have one entry a "
                             "layer")
        for kind in layer_types:
            if kind not in (FULL, SLIDING, CONV):
                raise ValueError(f"unknown layer type {kind!r}")
            if kind != CONV and kind not in rope_parameters:
                raise ValueError(f"no rope_parameters for {kind!r}")
        for kind in mlp_layer_types:
            if kind not in ("dense", "sparse"):
                raise ValueError(f"unknown mlp layer type {kind!r}")
        if "sparse" in mlp_layer_types:
            if None in (num_experts, num_experts_per_tok,
                        moe_intermediate_size):
                raise ValueError("a sparse layer needs num_experts, "
                                 "num_experts_per_tok and "
                                 "moe_intermediate_size")
            if total_ut_steps > 1:
                raise ValueError("total_ut_steps > 1 loops dense layers "
                                 "only: the expert layers' counters are "
                                 "not carried out of the loop")
        if total_ut_steps < 1:
            raise ValueError(f"total_ut_steps={total_ut_steps}")
        for h in num_attention_heads_per_layer:
            if h % num_key_value_heads:
                raise ValueError(f"{h} query heads do not divide over "
                                 f"{num_key_value_heads} K/V heads")
        if remat not in _MODES:
            raise ValueError(f"remat={remat!r} not in {_MODES}")
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.intermediate_size = intermediate_size
        self.num_hidden_layers = n
        self.layer_types = tuple(layer_types)
        self.num_attention_heads_per_layer = tuple(
            num_attention_heads_per_layer)
        self.mlp_layer_types = tuple(mlp_layer_types)
        self.num_key_value_heads = num_key_value_heads
        self.head_dim = head_dim
        self.rope_parameters = rope_parameters
        self.sliding_window = sliding_window
        self.num_experts = num_experts
        self.router_experts = router_experts or num_experts
        self.experts_held_start = experts_held_start
        self.num_experts_per_tok = num_experts_per_tok
        self.moe_intermediate_size = moe_intermediate_size
        self.shared_expert_intermediate_size = shared_expert_intermediate_size
        self.moe_routed_scaling_factor = moe_routed_scaling_factor
        self.moe_row_buffer_factor = moe_row_buffer_factor
        self.gating = gating
        if not norm_topk_prob:
            raise ValueError("norm_topk_prob false: the expert layer "
                             "renormalizes the chosen weights of every "
                             "router with more than one expert a token")
        self.router_type = router_type
        self.qk_norm = qk_norm
        self.conv_L_cache = conv_L_cache
        self.use_expert_bias = use_expert_bias
        self.tie_word_embeddings = tie_word_embeddings
        self.router_out_in = router_out_in
        self.total_ut_steps = total_ut_steps
        self.sandwich_norm = sandwich_norm
        self.exit_beta = exit_beta
        self.rms_norm_eps = rms_norm_eps
        self.max_position_embeddings = max_position_embeddings
        self.remat = remat
        self.head_chunk = head_chunk
        # what LlamaMLP reads of a config
        self.tp_axis = None
        self.mlp_act = "silu"

    @classmethod
    def from_dict(cls, d: dict, **over) -> "LagunaConfig":
        """From the keys of a published config file (others are ignored).
        Where the file is a chip's share, ``num_experts`` counts the experts
        held and ``num_experts_published`` the router's width."""
        names = inspect.signature(cls.__init__).parameters
        kw = {k: d[k] for k in names if k in d}
        kw.setdefault("num_attention_heads_per_layer", None)
        if "norm_eps" in d:             # the lfm2 files' name for it
            kw.setdefault("rms_norm_eps", d["norm_eps"])
        if "num_experts_published" in d:
            kw["router_experts"] = d["num_experts_published"]
        kw.update(over)
        return cls(**kw)


def rope_inv_freq(params: dict, head_dim: int) -> Tuple[np.ndarray, float]:
    """(inverse frequencies of the rotated pairs, scale on cos and sin) of
    one ``rope_parameters`` group, as ``transformers`` computes them
    (``ROPE_INIT_FUNCTIONS``: ``default`` and ``yarn``)."""
    dim = int(head_dim * params.get("partial_rotary_factor", 1.0))
    base = float(params["rope_theta"])
    pos_freqs = base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    kind = params.get("rope_type", "default")
    if kind == "default":
        return (1.0 / pos_freqs).astype(np.float32), 1.0
    if kind != "yarn":
        raise ValueError(f"unknown rope_type {kind!r}")
    factor = float(params["factor"])
    orig = params["original_max_position_embeddings"]
    scale = params.get("attention_factor")
    if scale is None:
        scale = 0.1 * math.log(factor) + 1.0

    def correction_dim(rotations):
        return (dim * math.log(orig / (rotations * 2 * math.pi))
                / (2 * math.log(base)))

    low = max(math.floor(correction_dim(params.get("beta_fast", 32))), 0)
    high = min(math.ceil(correction_dim(params.get("beta_slow", 1))),
               dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low)
                   / (high - low), 0.0, 1.0)
    extrapolation = 1.0 - ramp          # 1: keep the frequency as it is
    inv = ((1.0 / (factor * pos_freqs)) * (1.0 - extrapolation)
           + (1.0 / pos_freqs) * extrapolation)
    return inv.astype(np.float32), float(scale)


class LagunaAttention(nn.Module):
    def __init__(self, cfg: LagunaConfig, layer: int):
        super().__init__()
        self.H = cfg.num_attention_heads_per_layer[layer]
        self.Hkv = cfg.num_key_value_heads
        self.D = cfg.head_dim
        kind = cfg.layer_types[layer]
        self.window = cfg.sliding_window if kind == SLIDING else None
        # a kind with no ``rope_parameters`` group rotates nothing (a
        # position-free attention; LagunaConfig refuses it, the one-branch
        # decoder of models/nemotron_h.py states it)
        rope = cfg.rope_parameters.get(kind)
        self.inv_freq, self.rope_scale = (
            (None, 1.0) if rope is None else rope_inv_freq(rope, self.D))
        E = cfg.hidden_size
        self.q_proj = nn.Linear(E, self.H * self.D, bias=False)
        self.k_proj = nn.Linear(E, self.Hkv * self.D, bias=False)
        self.v_proj = nn.Linear(E, self.Hkv * self.D, bias=False)
        self.o_proj = nn.Linear(self.H * self.D, E, bias=False)
        if cfg.gating:
            self.g_proj = nn.Linear(E, self.H, bias=False)
        self.gating = cfg.gating
        self.qk_norm = cfg.qk_norm
        if cfg.qk_norm:
            self.q_layernorm = RMSNorm(self.D, cfg.rms_norm_eps)
            self.k_layernorm = RMSNorm(self.D, cfg.rms_norm_eps)

    def _rope(self, x):
        """x: (B, T, heads * D), as the projection wrote it; the first
        ``2 * len(inv_freq)`` dims of each head rotate (rotate-half
        pairing), the rest pass through.  fp32 arithmetic between a read
        and a write in x's dtype; cos and sin are a token's, shared by its
        heads.  On TPU one Pallas pass each way (``ops.pallas_rope``)."""
        B, T, _ = x.shape
        ang = jnp.arange(T, dtype=jnp.float32)[:, None] * self.inv_freq
        emb = jnp.concatenate([ang, ang], axis=-1)
        cos, sin = (jnp.cos(emb) * self.rope_scale,
                    jnp.sin(emb) * self.rope_scale)
        from ..ops import dispatch, pallas_rope
        if (dispatch.use_pallas_for(x) and self.D % 128 == 0
                and pallas_rope.rows_per_block(T)):
            return pallas_rope.rope_token_major(x, cos, sin, self.D)
        rd = emb.shape[-1]
        x = x.reshape(B, T, -1, self.D)
        xr = x[..., :rd].astype(jnp.float32)
        out = (xr * cos[:, None] + _rotate_half(xr) * sin[:, None]).astype(
            x.dtype)
        if rd < self.D:
            out = jnp.concatenate([out, x[..., rd:]], axis=-1)
        return out.reshape(B, T, -1)

    def _head_norm(self, norm, p, x):
        """RMSNorm over each head's ``D`` numbers of a projection's output
        (B, T, heads * D), one gain vector for all heads."""
        with jax.named_scope("attn.qk_norm"):
            return norm(p, x.reshape(*x.shape[:2], -1, self.D)).reshape(
                x.shape)

    def forward(self, p, x):
        B, T, _ = x.shape
        def rotated(proj, norm):
            y = getattr(self, proj)(p[proj], x)
            if self.qk_norm:
                y = self._head_norm(getattr(self, norm), p[norm], y)
            return y if self.inv_freq is None else self._rope(y)

        q = rotated("q_proj", "q_layernorm")
        k = rotated("k_proj", "k_layernorm")
        v = self.v_proj(p["v_proj"], x)
        # q, k, v stay where the projections wrote them: query head h reads
        # K/V head h // (H // Hkv), no axis is moved and no K/V head
        # repeated around the kernels
        heads = lambda y: y.reshape(B, T, -1, self.D)
        ctx = dot_product_attention_token_major(
            heads(q), heads(k), heads(v), causal=True, window=self.window)
        if self.gating:
            gate = jax.nn.sigmoid(
                self.g_proj(p["g_proj"], x).astype(jnp.float32))
            # in the view whose tiles are the array's own on a TPU, not
            # (B, T, H, D)'s
            tiles = (*token_tile_axes(B, T), self.H)
            ctx = ctx.reshape(*tiles, self.D) * gate.reshape(
                *tiles, 1).astype(ctx.dtype)
        return self.o_proj(p["o_proj"], ctx.reshape(B, T, self.H * self.D))


class LagunaBlock(nn.Module):
    attention = LagunaAttention     # what attends: (cfg, index) -> a module

    def __init__(self, cfg: LagunaConfig, layer: int):
        super().__init__()
        self.input_layernorm = RMSNorm(cfg.hidden_size, cfg.rms_norm_eps)
        self.mixer = "conv" if cfg.layer_types[layer] == CONV else "self_attn"
        if self.mixer == "conv":
            self.conv = GatedShortConv(cfg.hidden_size, cfg.conv_L_cache)
        else:
            self.self_attn = self.attention(cfg, layer)
        self.post_attention_layernorm = RMSNorm(cfg.hidden_size,
                                                cfg.rms_norm_eps)
        self.sandwich = cfg.sandwich_norm
        if self.sandwich:           # on what each branch returns
            self.input_layernorm_2 = RMSNorm(cfg.hidden_size,
                                             cfg.rms_norm_eps)
            self.post_attention_layernorm_2 = RMSNorm(cfg.hidden_size,
                                                      cfg.rms_norm_eps)
        self.sparse = cfg.mlp_layer_types[layer] == "sparse"
        if self.sparse:
            self.mlp = ExpertParallelMLP(
                cfg.hidden_size, cfg.moe_intermediate_size,
                cfg.router_experts, capacity_factor=None,
                top_k=cfg.num_experts_per_tok, expert_type="swiglu",
                router_type=cfg.router_type,
                routed_scaling=cfg.moe_routed_scaling_factor,
                experts_held=(cfg.experts_held_start, cfg.num_experts),
                shared_hidden=cfg.shared_expert_intermediate_size,
                row_buffer_factor=cfg.moe_row_buffer_factor,
                router_bias=cfg.use_expert_bias,
                router_out_in=cfg.router_out_in)
        else:
            self.mlp = LlamaMLP(cfg)

    def forward(self, p, x):
        """-> (x, the expert layer's counters or None)."""
        y = getattr(self, self.mixer)(p[self.mixer], self.input_layernorm(
            p["input_layernorm"], x))
        if self.sandwich:
            y = self.input_layernorm_2(p["input_layernorm_2"], y)
        x = x + y
        h = self.post_attention_layernorm(p["post_attention_layernorm"], x)
        if self.sparse:
            y, stats = self.mlp(p["mlp"], h, return_stats=True)
        else:
            y, stats = self.mlp(p["mlp"], h), None
        if self.sandwich:
            y = self.post_attention_layernorm_2(
                p["post_attention_layernorm_2"], y)
        return x + y, stats


def exit_log_probs(z):
    """Gate logits of the passes but the last, ``(R - 1, ...)``, -> the log
    of the exit distribution over all ``R`` passes, ``(R, ...)``:
    ``p_t = lambda_t prod_{j<t} (1 - lambda_j)`` with ``lambda = sigmoid(z)``,
    and the last pass takes what is left (it has no gate to read)."""
    stay = jax.nn.log_sigmoid(-z)               # log(1 - lambda_t)
    stayed = jnp.cumsum(stay, axis=0)           # through pass t
    return jnp.concatenate(
        [jax.nn.log_sigmoid(z) + stayed - stay, stayed[-1:]], axis=0)


class Laguna(nn.Module):
    fp32_param_names = ("exit_gate",)
    block = LagunaBlock         # what a layer is: (cfg, index) -> a module

    def __init__(self, cfg: LagunaConfig):
        super().__init__()
        self.cfg = cfg
        self.embed_tokens = nn.Embedding(cfg.vocab_size, cfg.hidden_size,
                                         init_std=0.02)
        self.layers = nn.ModuleList(
            [self.block(cfg, i) for i in range(cfg.num_hidden_layers)])
        self.norm = RMSNorm(cfg.hidden_size, cfg.rms_norm_eps)
        if not cfg.tie_word_embeddings:
            self.lm_head = nn.Linear(cfg.hidden_size, cfg.vocab_size,
                                     bias=False)

    def create_params(self, key):
        """The exit gate of a looped stack, torch's ``Linear(hidden, 1)``:
        ``weight`` (1, hidden) and ``bias`` (1,), float32 under O2."""
        if self.cfg.total_ut_steps == 1:
            return {}
        return {"exit_gate": nn.Linear(self.cfg.hidden_size,
                                       1).create_params(key)}

    def _head_weight(self, p):
        """The head's (vocabulary, hidden) matrix: the embedding's own leaf
        where the two are tied."""
        tied = self.cfg.tie_word_embeddings
        return p["embed_tokens" if tied else "lm_head"]["weight"]

    def _stack(self, p, x):
        """The layers once -> (x, the expert layers' counters, a list)."""
        stats = []
        for i, block in enumerate(self.layers):
            def layer(pp, xx, block=block):
                with jax.named_scope("layers"):
                    return block(pp, xx)
            x, s = wrap_block(layer, self.cfg.remat)(p["layers"][str(i)], x)
            if s is not None:
                stats.append(s)
        return x, stats

    def _backbone(self, p, input_ids):
        """-> (final hidden states, the step's MoE counters or {}); under a
        looped stack the normed state of every pass, (R, B, T, hidden)."""
        T = input_ids.shape[1]
        if T > self.cfg.max_position_embeddings:
            raise ValueError(f"sequence length {T} exceeds "
                             f"max_position_embeddings "
                             f"{self.cfg.max_position_embeddings}")
        x = self.embed_tokens(p["embed_tokens"], input_ids)
        R = self.cfg.total_ut_steps
        if R == 1:
            x, stats = self._stack(p, x)
            return (self.norm(p["norm"], x),
                    ExpertParallelMLP.reduce_stats(stats) if stats else {})
        from ..observability.metrics import get_registry
        get_registry().counter(
            "looped_stack_total",
            help="looped layer stacks traced, by passes and layers"
        ).labels(passes=str(R), layers=str(len(self.layers))).inc()

        def one_pass(x, _):
            x, _ = self._stack(p, x)
            with jax.named_scope("loop.norm"):
                x = self.norm(p["norm"], x)
            return x, x

        # one scan over the pass index, the weights closed over: the
        # compiled step holds each block once a direction
        with jax.named_scope("loop"):
            _, states = lax.scan(one_pass, x, None, length=R)
        return states, {}

    def forward(self, p, input_ids):
        """Logits; of every pass, (R, B, T, V), under a looped stack."""
        x, _ = self._backbone(p, input_ids)
        return F.matmul(x, self._head_weight(p).T.astype(x.dtype))

    def _exit_loss(self, p, states, labels):
        """The looped stack's training loss from the ``R`` normed states
        (R, B, T, hidden): every pass's next-token loss weighed by the
        learned exit distribution, less ``exit_beta`` times its entropy, the
        mean over every position but each row's last.  -> (loss, the sums
        over those positions of the expected exit pass, of the last pass's
        loss, and their count)."""
        from ..nn.fused_xent import linear_cross_entropy
        from ..observability.metrics import get_registry
        get_registry().counter(
            "exit_gate_calls_total",
            help="exit gates of a looped stack traced").inc()
        R, B, T, E = states.shape
        with jax.named_scope("loss.head"):
            nll = linear_cross_entropy(
                states.reshape(R * B * T, E), self._head_weight(p),
                jnp.tile(labels.reshape(-1), R),
                int(self.cfg.head_chunk)).reshape(R, B, T)
        with jax.named_scope("loss.exit"):
            gate = p["exit_gate"]
            # float32 on the vector unit, not a matmul in bf16 passes; the
            # last pass's gate is never read
            z = jnp.sum(states[:R - 1].astype(jnp.float32)
                        * gate["weight"][0].astype(jnp.float32), -1)
            logp = exit_log_probs(z + gate["bias"].astype(jnp.float32))
            prob = jnp.exp(logp)
            # sum_t p_t nll_t - beta H(p), H(p) = -sum_t p_t log p_t
            each = jnp.sum(prob * (nll + self.cfg.exit_beta * logp), 0)
            valid = jnp.arange(T) < T - 1
            count = B * (T - 1)
            loss = jnp.sum(each * valid) / count
            passes = jnp.arange(1, R + 1, dtype=jnp.float32)
            stats = {
                "exit_step_sum": jnp.sum(
                    jnp.tensordot(passes, prob, 1) * valid),
                "nll_last_sum": jnp.sum(nll[R - 1] * valid),
                "exit_positions": jnp.int32(count)}
        return loss, stats

    def loss(self, p, input_ids, return_stats: bool = False):
        """Mean next-token cross-entropy over every position but each row's
        last, through the fused chunked head (scope ``loss``); with
        ``return_stats`` also the step's MoE counters.  Under a looped stack
        the exit-weighted loss of ``_exit_loss`` and its three sums
        (``exit_step_sum``, ``nll_last_sum``, ``exit_positions``)."""
        B, T = input_ids.shape
        with jax.named_scope("model"):      # the root scope nn.apply opens
            x, stats = self._backbone(p, input_ids)
        with jax.named_scope("loss"):
            from ..nn.fused_xent import linear_cross_entropy
            labels = jnp.concatenate(
                [input_ids[:, 1:], jnp.zeros((B, 1), input_ids.dtype)], 1)
            if self.cfg.total_ut_steps > 1:
                loss, stats = self._exit_loss(p, x, labels)
            else:
                nll = linear_cross_entropy(
                    x.reshape(B * T, -1), self._head_weight(p),
                    labels.reshape(-1),
                    int(self.cfg.head_chunk)).reshape(B, T)
                valid = jnp.arange(T) < T - 1
                loss = jnp.sum(nll * valid) / (B * (T - 1))
        return (loss, stats) if return_stats else loss
