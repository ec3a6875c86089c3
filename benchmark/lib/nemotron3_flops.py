"""Operations and bytes of the ``nemotron_h`` decoder's training step, from
shapes alone (configs/nemotron3-nano-30b-a3b.json's keys).  A multiply-add
counts as two; a block is ONE mixer (``hybrid_override_pattern``: ``M`` a
Mamba-2 mixer, ``*`` attention, ``E`` routed experts with a shared one);
attention's causal half is counted once, as the pairs of query and key that see
each other; the selective scan is counted in its chunked form (the products
inside a chunk at the chunk's whole square, as every chunked implementation
computes them, and the carried state once a chunk); recomputed operations (a
rematerialized block's forward, flash attention's recomputed scores) count in a
kernel's own roofline and never in MFU."""

from lib.laguna_flops import visible_pairs


def scan_forward_flops_bytes(cfg: dict, batch: int, seq: int, dtype_bytes: int = 2) -> tuple:
    """One Mamba-2 mixer's chunked scan, forward, over ``batch`` rows of
    ``seq``: with H heads of P, G groups of N, chunks of Q,

    - ``C B^T`` inside a chunk (Q x Q a group), ``(L o C B^T)(delta x)``, each
      chunk's end state ``B^T (decay delta x)`` and ``C S_prev``: three products
      of ``2 Q H P`` or ``2 N H P`` a position and one of ``2 Q G N``;
    - the state carried from chunk to chunk: a multiply-add over ``H N P`` a chunk.

    Bytes, the least any implementation moves: x, B, C read and y written in the
    compute type, delta (float32, a number a head) read, every position once."""
    H, P, N, G, Q = (cfg["mamba_num_heads"], cfg["mamba_head_dim"], cfg["ssm_state_size"],
                     cfg["n_groups"], cfg["chunk_size"])
    per_position = 2.0 * Q * G * N + 2.0 * Q * H * P + 2 * (2.0 * N * H * P)
    flops = batch * (seq * per_position + (seq // Q) * 2.0 * H * N * P)
    bytes_moved = batch * seq * (dtype_bytes * (2 * H * P + 2 * G * N) + 4 * H)
    return flops, bytes_moved


def scan_train_flops_bytes(cfg: dict, batch: int, seq: int, forward_calls: float = 1.0,
                           dtype_bytes: int = 2) -> tuple:
    """The scans of one training step over the ``M`` blocks: the forward
    ``forward_calls`` times a block (2 where the block is rematerialized), and
    the backward, whose every product has two (twice the forward's operations),
    which reads what the forward read and the result's gradient and writes the
    gradients of x, B, C and delta."""
    layers = cfg["hybrid_override_pattern"].count("M")
    fl, by = scan_forward_flops_bytes(cfg, batch, seq, dtype_bytes)
    return layers * (forward_calls + 2.0) * fl, layers * (forward_calls + 2.0) * by


def forward_flops_per_seq(cfg: dict, seq: int, assignments_held_per_seq: float) -> dict:
    """Forward FLOPs of one sequence by part.  ``assignments_held_per_seq``:
    rows the experts held here computed, summed over the expert layers (the
    program's counter ``moe_assignments_held`` over the sequences of a step)."""
    d, D = cfg["hidden_size"], cfg["head_dim"]
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    H, P, N, G = (cfg["mamba_num_heads"], cfg["mamba_head_dim"], cfg["ssm_state_size"],
                  cfg["n_groups"])
    d_in = H * P
    pattern = cfg["hybrid_override_pattern"]
    mamba, attn, moe = pattern.count("M"), pattern.count("*"), pattern.count("E")
    scan, _ = scan_forward_flops_bytes(cfg, 1, seq)
    return {
        "mamba_projections": mamba * 2.0 * seq * d * ((2 * d_in + 2 * G * N + H) + d_in),
        "mamba_scan": mamba * scan,
        # the taps' multiply-adds and the bias over d_in + 2 G N channels; y * silu(z)
        "mamba_conv_gate": mamba * seq * ((2 * cfg["conv_kernel"] + 1) * (d_in + 2 * G * N)
                                          + d_in),
        "attention_projections": attn * 2.0 * seq * d * (2 * heads * D + 2 * kv * D),
        "attention_scores": attn * 4.0 * D * heads * visible_pairs(seq),
        "router": moe * 2.0 * seq * d * cfg.get("num_experts_published",
                                                 cfg["n_routed_experts"]),
        "shared_expert": moe * 4.0 * seq * d * cfg["moe_shared_expert_intermediate_size"],
        "routed_experts": 4.0 * d * cfg["moe_intermediate_size"] * assignments_held_per_seq,
        "head": 2.0 * (seq - 1) * d * cfg["vocab_size"]}


def train_flops_per_seq(cfg: dict, seq: int, assignments_held_per_seq: float) -> float:
    """Forward + backward (twice the forward) of one sequence."""
    return 3.0 * sum(forward_flops_per_seq(cfg, seq, assignments_held_per_seq).values())
