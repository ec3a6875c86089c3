"""The ``deepseek_v3`` family's decoder: every layer multi-head latent
attention (``transformer.LatentAttention``), the first
``first_k_dense_replace`` layers a dense SwiGLU and the rest routed experts
with shared ones.  The per-layer decoder of ``models/laguna.py`` with the
attention swapped: this module is the family's published keys read into a
``LagunaConfig`` and the block that builds the other attention; embedding,
stack, final norm, untied head, fused chunked loss and the expert layer
(``parallel.ExpertParallelMLP``) are ``Laguna``'s.

- attention: ``num_attention_heads`` heads, a score head of
  ``qk_nope_head_dim + qk_rope_head_dim``, a value head of ``v_head_dim``, K
  and V out of a latent of ``kv_lora_rank`` through an RMSNorm, the rotated
  part of a key one head for all query heads, RoPE at ``rope_theta`` on
  interleaved pairs (``rope_interleave``);
- layer ``l >= first_k_dense_replace`` (every ``moe_layer_freq``-th): a
  ``scoring_func`` router over the published experts with a selection bias
  (``topk_method: noaux_tc``), the ``num_experts_per_tok`` largest
  renormalized (``norm_topk_prob``) and scaled by ``routed_scaling_factor``,
  SwiGLU experts of ``moe_intermediate_size`` (the ``n_routed_experts`` held
  here, from ``experts_held_start``) and ``n_shared_experts`` shared ones,
  which are one SwiGLU of ``n_shared_experts * moe_intermediate_size``.

What the family's files can state and this module does not build is refused
by name, not guessed at: a low-rank query (``q_lora_rank``), group-limited
routing over more than one group (``n_group``, ``topk_group``), a
``rope_scaling`` (with the softmax scale that follows its factor),
``norm_topk_prob`` false, rotate-half pairing, biases.

Training and full-sequence forward only: a cache would hold latents, and
decoding would take the absorbed form (ROADMAP, Reach).
"""

from __future__ import annotations

from ..transformer.mla import LatentAttention
from .laguna import FULL, Laguna, LagunaBlock, LagunaConfig

__all__ = ["DeepseekV3Config", "DeepseekV3"]

# key -> the one value that is built
_ONLY = {"q_lora_rank": None, "rope_scaling": None, "n_group": 1,
         "topk_group": 1, "rope_interleave": True, "attention_bias": False,
         "hidden_act": "silu", "topk_method": "noaux_tc"}


class DeepseekV3Config(LagunaConfig):
    """``LagunaConfig`` from the family's keys, with the latent attention's
    sizes beside it.  ``n_routed_experts`` experts are HELD here, from
    ``experts_held_start``, of the ``num_experts_published`` the router
    scores (default: all are held)."""

    @classmethod
    def from_dict(cls, d: dict, **over) -> "DeepseekV3Config":
        for key, only in _ONLY.items():
            if d.get(key, only) != only:
                raise ValueError(
                    f"{key}={d[key]!r}: only {only!r} is built (models/"
                    f"deepseek_v3.py says what the family's files may state "
                    f"beyond what runs)")
        H = d["num_attention_heads"]
        if d.get("num_key_value_heads", H) != H:
            raise ValueError("latent attention has one K/V head a query "
                             f"head: num_key_value_heads="
                             f"{d['num_key_value_heads']} of {H}")
        n, dense = d["num_hidden_layers"], d.get("first_k_dense_replace", 0)
        every = d.get("moe_layer_freq", 1)
        names = ("vocab_size", "hidden_size", "intermediate_size",
                 "num_experts_per_tok", "moe_intermediate_size",
                 "experts_held_start", "moe_row_buffer_factor",
                 "rms_norm_eps", "max_position_embeddings", "remat",
                 "head_chunk", "norm_topk_prob", "tie_word_embeddings")
        kw = {k: d[k] for k in names if k in d}
        kw.update(
            layer_types=[FULL] * n, num_attention_heads_per_layer=[H] * n,
            mlp_layer_types=["sparse" if l >= dense and l % every == 0
                             else "dense" for l in range(n)],
            num_key_value_heads=H, head_dim=d["v_head_dim"],
            rope_parameters={FULL: {"rope_theta": d["rope_theta"]}},
            sliding_window=None, gating=False,
            num_experts=d.get("n_routed_experts"),
            router_experts=d.get("num_experts_published"),
            shared_expert_intermediate_size=(
                d.get("n_shared_experts", 0)
                * d.get("moe_intermediate_size", 0)),
            moe_routed_scaling_factor=d.get("routed_scaling_factor", 1.0),
            router_type=d.get("scoring_func", "sigmoid"),
            use_expert_bias=True)
        kw.update(over)
        cfg = cls(**kw)
        cfg.qk_nope_head_dim = d["qk_nope_head_dim"]
        cfg.qk_rope_head_dim = d["qk_rope_head_dim"]
        cfg.v_head_dim = d["v_head_dim"]
        cfg.kv_lora_rank = d["kv_lora_rank"]
        cfg.rope_theta = d["rope_theta"]
        return cfg


class DeepseekV3Block(LagunaBlock):
    @staticmethod
    def attention(cfg: DeepseekV3Config, layer: int):
        return LatentAttention(
            cfg.hidden_size, cfg.num_attention_heads_per_layer[layer],
            cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim,
            cfg.kv_lora_rank, cfg.rope_theta, cfg.rms_norm_eps)


class DeepseekV3(Laguna):
    block = DeepseekV3Block
