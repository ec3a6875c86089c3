"""Operations and bytes of the ``deepseek_v3`` decoder's training step, from
shapes alone (configs/kanana-2-30b-a3b.json's keys).  A multiply-add counts as
two; every layer is latent attention, whose scores contract over
``qk_nope_head_dim + qk_rope_head_dim`` and whose values are ``v_head_dim`` wide,
both as published and never a padded width; the causal half is counted once, as
the pairs of query and key that see each other; recomputed operations (a
rematerialized block's forward, flash attention's recomputed scores) count in a
kernel's own roofline and never in MFU."""

from lib.laguna_flops import visible_pairs


def attention_weights(cfg: dict) -> int:
    """Parameters of one layer's four projections: ``W_q`` (d -> H (d_n + d_r)),
    ``W_kva`` (d -> r + d_r), ``W_kvb`` (r -> H (d_n + d_v)), ``W_o`` (H d_v -> d)."""
    d, H, r = cfg["hidden_size"], cfg["num_attention_heads"], cfg["kv_lora_rank"]
    dn, dr, dv = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    return d * H * (dn + dr) + d * (r + dr) + r * H * (dn + dv) + H * dv * d


def sparse_layers(cfg: dict) -> int:
    every = cfg.get("moe_layer_freq", 1)
    return sum(1 for l in range(cfg["num_hidden_layers"])
               if l >= cfg["first_k_dense_replace"] and l % every == 0)


def forward_flops_per_seq(cfg: dict, seq: int, assignments_held_per_seq: float) -> dict:
    """Forward FLOPs of one sequence by part.  ``assignments_held_per_seq``:
    rows the experts held here computed, summed over the expert layers (the
    program's counter ``moe_assignments_held`` over the sequences of a step)."""
    d, H, layers = cfg["hidden_size"], cfg["num_attention_heads"], cfg["num_hidden_layers"]
    score, value = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    sparse = sparse_layers(cfg)
    width = cfg["moe_intermediate_size"]
    return {
        "attention_projections": layers * 2.0 * seq * attention_weights(cfg),
        "attention_scores": layers * 2.0 * (score + value) * H * visible_pairs(seq),
        "dense_mlp": (layers - sparse) * 6.0 * seq * d * cfg["intermediate_size"],
        "router": sparse * 2.0 * seq * d * cfg.get("num_experts_published",
                                                    cfg["n_routed_experts"]),
        "shared_experts": sparse * 6.0 * seq * d * cfg["n_shared_experts"] * width,
        "routed_experts": 6.0 * d * width * assignments_held_per_seq,
        "head": 2.0 * (seq - 1) * d * cfg["vocab_size"]}


def train_flops_per_seq(cfg: dict, seq: int, assignments_held_per_seq: float) -> float:
    """Forward + backward (twice the forward) of one sequence."""
    return 3.0 * sum(forward_flops_per_seq(cfg, seq, assignments_held_per_seq).values())


def flash_train_flops_bytes(cfg: dict, batch: int, seq: int, forward_calls: float = 1.0,
                            dtype_bytes: int = 2) -> tuple:
    """flash_fwd + flash_dq + flash_dkv of one step as executed, over every
    layer, at a score head of S = d_n + d_r and a value head of V, whichever way
    the kernels are handed the score head's two parts: per visible pair and head
    the forward 2 (S + V) (QK^T, PV), dq 2 (S + V + S) (recompute S, dP, dQ), dkv
    2 (S + V + V + S) (recompute S, dP, dV, dK); ``forward_calls`` forward kernels
    a layer (2 when the block is rematerialized).  Bytes from the unpadded shapes,
    K and V once a head: fwd reads q, k, v and writes o; dq reads q, k, v, o, do
    and writes dq; dkv reads q, k, v, o, do and writes dk, dv (row statistics are
    small and left out)."""
    H, layers = cfg["num_attention_heads"], cfg["num_hidden_layers"]
    S, V = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    per_pair = forward_calls * (S + V) + (2 * S + V) + (2 * S + 2 * V)
    flops = layers * 2.0 * per_pair * visible_pairs(seq) * batch * H
    per_token = (forward_calls * (2 * S + 2 * V) + (3 * S + 3 * V) + (3 * S + 4 * V))
    return flops, layers * per_token * H * batch * seq * dtype_bytes
