"""Operations and bytes of the work, from shapes alone.  Matmul FLOPs count a
multiply-add as two; recomputed operations (flash attention's backward
recomputes the scores) count in a kernel's own roofline, never in MFU."""


def bert_matmul_params(cfg: dict) -> int:
    """Parameters that take part in a matmul per token: the encoder layers,
    the MLM transform and the tied decoder (vocab x hidden).  Embedding
    look-ups, the pooler and NSP (one row per sequence) are left out."""
    h, i, layers, v = (cfg["hidden_size"], cfg["intermediate_size"],
                       cfg["num_hidden_layers"], cfg["vocab_size"])
    per_layer = 4 * h * h + 2 * h * i
    return layers * per_layer + h * h + v * h


def bert_train_flops_per_seq(cfg: dict, seq_len: int) -> float:
    """Forward + backward FLOPs one sequence requires: 6 x matmul parameters
    per token, plus attention's QK^T and PV (forward 4*T*T*H per layer,
    backward twice that)."""
    dense = 6.0 * bert_matmul_params(cfg) * seq_len
    attn = 12.0 * cfg["num_hidden_layers"] * seq_len * seq_len * cfg["hidden_size"]
    return dense + attn


def flash_train_flops_bytes(batch: int, heads: int, seq: int, head_dim: int, layers: int,
                            causal: bool = False, dtype_bytes: int = 2) -> tuple:
    """flash_fwd + flash_dq + flash_dkv as executed per step: forward 2
    matmuls (QK^T, PV), dq kernel 3 (recompute S, dP, dQ), dkv kernel 4
    (recompute S, dP, dV, dK): 9 matmuls of 2*T*T*D each per head.  Bytes:
    the least traffic, each operand and result once per kernel: fwd reads
    q,k,v writes o; dq reads q,k,v,o,do writes dq; dkv reads q,k,v,o,do
    writes dk,dv (row statistics are small and left out)."""
    mm = 2.0 * seq * seq * head_dim * (0.5 if causal else 1.0)
    flops = 9.0 * mm * batch * heads * layers
    tensor = batch * heads * seq * head_dim * dtype_bytes
    bytes_moved = (4 + 6 + 7) * tensor * layers
    return flops, bytes_moved


def adam_bytes(n_params: int, grad_bytes: int = 2, half_copy_bytes: int = 2) -> float:
    """FusedAdam on the flat master: reads p, m, v (fp32) and the gradient,
    writes p, m, v and the half copy of p, each once."""
    return float(n_params) * (3 * 4 + grad_bytes + 3 * 4 + half_copy_bytes)
