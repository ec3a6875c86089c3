"""Operations and bytes of the ``lfm2_moe`` decoder's training step, from
shapes alone (configs/lfm2-8b-a1b.json's keys).  A multiply-add counts as two;
attention is counted in the ``full_attention`` layers only (a ``conv`` layer has
no scores), its causal half once, as the pairs of query and key that see each
other; a ``conv`` layer's operator is its two projections and the elementwise
pass between them; recomputed operations (a rematerialized block's forward,
flash attention's recomputed scores, the convolution pass recomputed in the
backward) count in a kernel's own roofline and never in MFU."""

from lib.laguna_flops import visible_pairs

FULL, CONV = "full_attention", "conv"


def forward_flops_per_seq(cfg: dict, seq: int, assignments_held_per_seq: float) -> dict:
    """Forward FLOPs of one sequence by part.  ``assignments_held_per_seq``:
    rows the experts held here computed, summed over the expert layers (the
    program's counter ``moe_assignments_held`` over the sequences of a step)."""
    d, D = cfg["hidden_size"], cfg["head_dim"]
    heads, kv, taps = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["conv_L_cache"]
    parts = {"conv_projections": 0.0, "conv_taps": 0.0, "attention_projections": 0.0,
             "attention_scores": 0.0, "dense_mlp": 0.0, "router": 0.0, "routed_experts": 0.0,
             "head": 0.0}
    for i in range(cfg["num_hidden_layers"]):
        if cfg["layer_types"][i] == CONV:
            parts["conv_projections"] += 2.0 * seq * d * (3 * d + d)
            # B * z, the taps' multiply-adds, C * c
            parts["conv_taps"] += seq * d * (1 + 2 * taps + 1)
        else:
            parts["attention_projections"] += 2.0 * seq * d * (2 * heads * D + 2 * kv * D)
            parts["attention_scores"] += 4.0 * D * heads * visible_pairs(seq)
        if cfg["mlp_layer_types"][i] == "dense":
            parts["dense_mlp"] += 6.0 * seq * d * cfg["intermediate_size"]
        else:
            parts["router"] += 2.0 * seq * d * cfg.get("num_experts_published",
                                                       cfg["num_experts"])
    parts["routed_experts"] = 6.0 * d * cfg["moe_intermediate_size"] * assignments_held_per_seq
    parts["head"] = 2.0 * (seq - 1) * d * cfg["vocab_size"]
    return parts


def train_flops_per_seq(cfg: dict, seq: int, assignments_held_per_seq: float) -> float:
    """Forward + backward (twice the forward) of one sequence."""
    return 3.0 * sum(forward_flops_per_seq(cfg, seq, assignments_held_per_seq).values())


def flash_train_flops_bytes(cfg: dict, batch: int, seq: int, forward_calls: float = 1.0,
                            dtype_bytes: int = 2) -> tuple:
    """flash_fwd + flash_dq + flash_dkv of one step as executed, over the
    ``full_attention`` layers: forward 2 matmuls per visible pair (QK^T, PV), dq
    3 (recompute S, dP, dQ), dkv 4 (recompute S, dP, dV, dK), each 2 * head_dim
    operations; ``forward_calls`` forward kernels a layer (2 when the block is
    rematerialized).  Bytes: each operand and result once per kernel, K and V
    (and dK, dV) at the K/V heads the kernels are given, not repeated to the
    query heads: fwd reads q, k, v and writes o; dq reads q, k, v, o, do and
    writes dq; dkv reads q, k, v, o, do and writes dk, dv (row statistics are
    small and left out)."""
    D, heads, kv = cfg["head_dim"], cfg["num_attention_heads"], cfg["num_key_value_heads"]
    layers = cfg["layer_types"].count(FULL)
    flops = layers * (2 * forward_calls + 3 + 4) * 2.0 * D * visible_pairs(seq) * batch * heads
    per_token = (forward_calls * (2 * heads + 2 * kv) + (4 * heads + 2 * kv)
                 + (3 * heads + 4 * kv))
    return flops, layers * per_token * batch * seq * D * dtype_bytes
