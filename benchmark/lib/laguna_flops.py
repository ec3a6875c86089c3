"""Operations and bytes of the ``laguna`` decoder's training step, from shapes
alone (configs/laguna-xs2.json's keys).  A multiply-add counts as two; the
causal half and a sliding layer's band are counted once, as the pairs of
query and key that see each other; recomputed operations (a rematerialized
block's forward, flash attention's recomputed scores) count in a kernel's own
roofline and never in MFU."""

FULL, SLIDING = "full_attention", "sliding_attention"


def visible_pairs(seq: int, window=None) -> int:
    """Pairs (query i, key j) with j <= i, and i - window < j in a band."""
    if window is None or window >= seq:
        return seq * (seq + 1) // 2
    return window * (window + 1) // 2 + (seq - window) * window


def layer_window(cfg: dict, layer: int):
    return cfg["sliding_window"] if cfg["layer_types"][layer] == SLIDING else None


def forward_flops_per_seq(cfg: dict, seq: int, assignments_held_per_seq: float) -> dict:
    """Forward FLOPs of one sequence by part.  ``assignments_held_per_seq``:
    rows the experts held here computed, summed over the expert layers (the
    program's counter ``moe_assignments_held`` over the sequences of a step)."""
    d, D, kv = cfg["hidden_size"], cfg["head_dim"], cfg["num_key_value_heads"]
    parts = {"attention_projections": 0.0, "attention_scores": 0.0, "dense_mlp": 0.0,
             "router": 0.0, "shared_expert": 0.0, "routed_experts": 0.0, "head": 0.0}
    for i in range(cfg["num_hidden_layers"]):
        heads = cfg["num_attention_heads_per_layer"][i]
        gate = heads if cfg.get("gating") else 0
        parts["attention_projections"] += 2.0 * seq * d * (2 * heads * D + 2 * kv * D + gate)
        parts["attention_scores"] += 4.0 * D * heads * visible_pairs(seq, layer_window(cfg, i))
        if cfg["mlp_layer_types"][i] == "dense":
            parts["dense_mlp"] += 6.0 * seq * d * cfg["intermediate_size"]
        else:
            parts["router"] += 2.0 * seq * d * cfg.get("num_experts_published",
                                                       cfg["num_experts"])
            parts["shared_expert"] += 6.0 * seq * d * cfg["shared_expert_intermediate_size"]
    parts["routed_experts"] = 6.0 * d * cfg["moe_intermediate_size"] * assignments_held_per_seq
    parts["head"] = 2.0 * (seq - 1) * d * cfg["vocab_size"]
    return parts


def train_flops_per_seq(cfg: dict, seq: int, assignments_held_per_seq: float) -> float:
    """Forward + backward (twice the forward) of one sequence."""
    return 3.0 * sum(forward_flops_per_seq(cfg, seq, assignments_held_per_seq).values())


def flash_train_flops_bytes(cfg: dict, batch: int, seq: int, forward_calls: float = 1.0,
                            dtype_bytes: int = 2) -> tuple:
    """flash_fwd + flash_dq + flash_dkv of one step as executed, over the
    layers by type and head count: forward 2 matmuls per visible pair (QK^T,
    PV), dq 3 (recompute S, dP, dQ), dkv 4 (recompute S, dP, dV, dK), each
    2 * head_dim operations; ``forward_calls`` forward kernels a layer (2 when
    the block is rematerialized).  Bytes: each operand and result once per
    kernel, K and V as the kernel is given them (repeated to the query heads):
    fwd reads q,k,v writes o; dq reads q,k,v,o,do writes dq; dkv reads
    q,k,v,o,do writes dk,dv (row statistics are small and left out)."""
    D = cfg["head_dim"]
    flops = bytes_moved = 0.0
    for i in range(cfg["num_hidden_layers"]):
        heads = cfg["num_attention_heads_per_layer"][i]
        pairs = visible_pairs(seq, layer_window(cfg, i))
        flops += (2 * forward_calls + 3 + 4) * 2.0 * D * pairs * batch * heads
        bytes_moved += (4 * forward_calls + 6 + 7) * batch * heads * seq * D * dtype_bytes
    return flops, bytes_moved
