"""Operations and bytes of the ``ouro`` looped decoder's training step, from
shapes alone (configs/ouro-2.6b.json's keys).  A multiply-add counts as two;
every layer is counted ``total_ut_steps`` times (one application a pass), the
head ``total_ut_steps`` times (every pass's state is read), the gate once a
pass but the last; attention's causal half is counted once, as the pairs of
query and key that see each other; recomputed operations (a rematerialized
block's forward, flash attention's recomputed scores) count in a kernel's own
roofline and never in MFU."""

from lib import lfm2_flops
from lib.laguna_flops import visible_pairs


def forward_flops_per_seq(cfg: dict, seq: int) -> dict:
    """Forward FLOPs of one sequence by part, over all passes."""
    d, D = cfg["hidden_size"], cfg["head_dim"]
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    passes = cfg["total_ut_steps"]
    applications = passes * cfg["num_hidden_layers"]
    return {"attention_projections": applications * 2.0 * seq * d * (2 * heads * D + 2 * kv * D),
            "attention_scores": applications * 4.0 * D * heads * visible_pairs(seq),
            "dense_mlp": applications * 6.0 * seq * d * cfg["intermediate_size"],
            "head": passes * 2.0 * (seq - 1) * d * cfg["vocab_size"],
            "exit_gate": (passes - 1) * 2.0 * seq * d}


def train_flops_per_seq(cfg: dict, seq: int) -> float:
    """Forward + backward (twice the forward) of one sequence."""
    return 3.0 * sum(forward_flops_per_seq(cfg, seq).values())


def flash_train_flops_bytes(cfg: dict, batch: int, seq: int, forward_calls: float = 1.0,
                            dtype_bytes: int = 2) -> tuple:
    """flash_fwd + flash_dq + flash_dkv of one step as executed:
    ``lib/lfm2_flops.py``'s count (operations of the visible pairs, bytes with
    K/V at the heads the kernels are given) over the ``total_ut_steps x
    num_hidden_layers`` block applications, every one a full-attention layer;
    ``forward_calls`` forward kernels an application (2 when the block is
    rematerialized)."""
    applications = cfg["total_ut_steps"] * cfg["num_hidden_layers"]
    return lfm2_flops.flash_train_flops_bytes(
        {**cfg, "layer_types": [lfm2_flops.FULL] * applications}, batch, seq, forward_calls,
        dtype_bytes)
