"""Next-token pretraining of the ``lfm2_moe`` decoder in plain jax.numpy and
float32, from the published config's keys
(https://huggingface.co/LiquidAI/LFM2-8B-A1B/blob/main/config.json): the
forward pass, the loss over the held vocabulary slice, its gradient and Adam,
with no kernel, no policy and no code of ``apex_tpu``.  It reads a parameter
tree in the program's layout (torch-style (out, in) ``weight`` leaves; a
convolution's taps ``conv.weight`` (taps, d); an expert layer's ``router``
(E, d) with ``router_out_in`` and (d, E) without, ``expert_bias`` (E,), ``w_gate``/``w_in`` (n, d, h) and ``w_out``
(n, h, d)) that the benchmark made from the seed.  The pieces that are the same
mathematics in every such decoder (RMSNorm, SwiGLU, the RoPE tables and
rotation, Adam) are ``references/laguna.py``'s, the random projections kept
beside each leaf's norm and their comparison ``references/mellum2.py``'s; the
operators, the expert layer, the model and the limits are this file's own.

The layer equations (pre-norm, RMSNorm eps ``norm_eps``, no biases; ``u`` the
normed input of a sub-block):

    h = x + Op_l(RMSNorm(x));  y = h + FFN_l(RMSNorm(h));  final RMSNorm; head

``Op_l``, a ``conv`` layer: ``[B, C, z] = split_3(u W_in)``; ``g = B * z``;
``c_t = sum_k w[k] * g_{t - (L - 1) + k}`` over the ``conv_L_cache`` taps, per
channel, ``g`` zero before the row's first token; ``Op = (C * c) W_out``.
``Op_l``, a ``full_attention`` layer: ``num_attention_heads`` query heads over
``num_key_value_heads`` K/V heads of ``head_dim`` (query head h reads K/V head
h // group); q and k each through an RMSNorm over the head's numbers (one gain
vector for q, one for k) and then RoPE at ``rope_theta`` over the whole head
(rotate-half); scores q.k / sqrt(head_dim), causal.
``FFN_l``, ``mlp_layer_types[l] == "dense"``: SwiGLU of ``intermediate_size``.
``FFN_l``, the others: ``s = sigmoid(u W_r)`` in float32 over all published
experts; the choice is the ``num_experts_per_tok`` largest of ``s + b``
(``expert_bias``: it enters the choice only and takes no gradient);
``w = s[choice] / (sum s[choice] + 1e-6)`` times ``moe_routed_scaling_factor``;
``sum_k w_k SwiGLU_{e_k}(u)``; no shared expert.
The head's matrix is the embedding's (``tie_word_embeddings``).

A chip's share (configs/lfm2-8b-a1b.json: ``deployment``): the tree holds
``num_experts`` experts from ``experts_held_start``; the router and its bias
keep all ``num_experts_published``; an assignment to an expert held elsewhere
adds nothing.  The vocabulary is the slice the tree holds.

Assumed, each the configuration's own (configs/lfm2-8b-a1b.json: ``assumed``):
the head size, the tied head, the 1e-6 of the renormalization, a bias that the
seed draws and nothing updates but Adam's decoupled decay (its gradient is
zero, so Adam's moments stay zero and ``adam_update`` shrinks it by
``step * weight_decay`` of itself, in the program and here alike), no
auxiliary loss; Adam as apex's FusedAdam defines it.

To fit 8k sequences in float32 on one chip: each layer is recomputed in the
backward pass, attention runs in blocks of queries and the experts one at a
time over all tokens.

LIMITS: what the timed path may differ by, and why; set from chip readings at
the cell's own size (PERF.md, "Limits of correct"): above the largest a sound
bf16 run gave over its seeds, below the smallest the control one precision
down gave.
"""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from . import _precision as P
from .laguna import (ADAM, QUERY_BLOCK, adam_update, apply_rope, rms_norm,  # noqa: F401
                     rope_tables, swiglu)
from .mellum2 import (SKETCH, _SKETCH_KEY, _as_matrix, compare, difference_norms,  # noqa: F401
                      leaf_differences)

# number -> limit.  Readings they were set from, on the chip at the cell's own size (PR 33;
# PERF.md, "Limits of correct"; tools/control.py on seeds 3300000031-34, both controls on the
# first three, and the traced run on 3300000002): sound largest / fp8-compute control smallest
# / bf16-parameter control smallest.
LIMITS = {
    # |loss - ref| / ref, worst of the three steps: 4.4e-5 (5 sound readings, 1.3e-5 on) /
    # 1.16e-4 / 4.5e-5.  The lower precisions move it 3 x at most and the parameter control
    # not at all, so it is the accepted decoder cell's limit (references/laguna.py), 30 x the
    # largest sound reading.  Held against a part of the batch or of the model left out of
    # the loss, and against an update wrong in size or sign
    "loss_gap": 1.35e-3,
    # first gradient as Adam got it, mean over the 53 leaves of each leaf's norm gap: 2.9e-4
    # (1.9-2.9e-4) / 7.5e-4 (7.5e-4-1.08e-3) / 0.  COMPUTE precision, by how LONG the
    # gradient is: 2.6 x apart.  The second line behind ``grad_diff_mean``, so nearer the
    # control: 2.1 x over the largest sound reading, 1.25 x under the smallest control
    "grad_norm_gap_mean": 6e-4,
    # the same gradient, mean over the leaves of the estimated norm of (program - reference)
    # over the leaf's reference norm, a leaf counting for at most 1: 0.0533 (0.0506-0.0533) /
    # 0.2004 (0.2004-0.2100) / 0.  COMPUTE precision, by what the gradient DIFFERS by: 3.8 x
    # apart, midway by ratio (1.9 x over sound, 2.0 x under the control).  This network does
    # not amplify a rounding as ``mellum2-12b`` does (a sound run reads 0.05 there too on the
    # reference alone with bf16 operands, tools/lfm2_routing.py flips: 0.051 / 0.221)
    "grad_diff_mean": 0.10,
    # worst leaf, norm of the parameters' change after the steps: 5.4e-4 (2.0-5.4e-4) /
    # 6.8e-4 / 0.0699 (0.0699-0.0701; a stuck step reads 1.0).  PARAMETER precision: midway
    # by ratio, 11 x from either
    "update_norm_gap": 6e-3,
}
RENORM_EPS = 1e-6


def leaf_norms(tree):
    """``references/mellum2.py``'s rows (a leaf's norm, then SKETCH ** 2 fixed
    random projections of it), with a vector projected on SKETCH ** 2 sign
    vectors of its own length and never folded square.  The runner reads the
    program's leaves as slices of its flat float32 buffer, and a 64-long gain
    folded (8, 8) made the chip's compiler view the whole buffer (N / 8, 8)
    first: 16 x the buffer in 128-lane tiles, 32.5 GB that no chip has (my chip
    run, PR 33)."""
    rows, highest = [], jax.lax.Precision.HIGHEST
    for i, x in enumerate(jax.tree_util.tree_leaves(tree)):
        x = x.astype(jnp.float32)
        kl, kr = jax.random.split(jax.random.fold_in(jax.random.PRNGKey(_SKETCH_KEY), i))
        if x.ndim < 2:
            signs = jax.random.rademacher(kl, (SKETCH ** 2, x.size), jnp.float32)
            proj = jnp.matmul(signs, x, precision=highest)
        else:
            m = _as_matrix(x)
            left = jax.random.rademacher(kl, (SKETCH, m.shape[0]), jnp.float32)
            right = jax.random.rademacher(kr, (SKETCH, m.shape[1]), jnp.float32)
            proj = jnp.matmul(left, jnp.matmul(m, right.T, precision=highest),
                              precision=highest).reshape(-1)
        rows.append(jnp.concatenate([jnp.sqrt(jnp.sum(x * x))[None], proj]))
    return jnp.stack(rows)


def short_conv(p, x, cfg, precision):
    """x: (T, d) of one sequence -> (T, d): the gated short convolution."""
    T, d = x.shape
    taps = p["conv"]["weight"]                      # (L, d), a tap a row
    L = taps.shape[0]
    b, c, z = jnp.split(P.matmul(x, p["in_proj"]["weight"], precision), 3, axis=-1)
    g = jnp.concatenate([jnp.zeros((L - 1, d), x.dtype), b * z])
    mixed = sum(taps[k] * g[k:k + T] for k in range(L))
    return P.matmul(c * mixed, p["out_proj"]["weight"], precision)


def attention(p, x, cfg, kind, precision):
    """x: (T, d) of one sequence -> (T, d); ``kind`` names the layer's
    ``rope_parameters`` group."""
    T, D = x.shape[0], cfg["head_dim"]
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    group, eps = heads // kv, cfg["rms_norm_eps"]
    cos, sin = rope_tables(cfg["rope_parameters"][kind], D, T)
    q = P.matmul(x, p["q_proj"]["weight"], precision).reshape(T, heads, D)
    k = P.matmul(x, p["k_proj"]["weight"], precision).reshape(T, kv, D)
    if cfg.get("qk_norm"):
        q = rms_norm(q, p["q_layernorm"]["weight"], eps)
        k = rms_norm(k, p["k_layernorm"]["weight"], eps)
    q, k = apply_rope(q, cos, sin), apply_rope(k, cos, sin)
    v = P.matmul(x, p["v_proj"]["weight"], precision).reshape(T, kv, D)
    bq = min(QUERY_BLOCK, T)
    assert T % bq == 0, (T, bq)
    reach = T - bq                                  # keys before a block's first row
    pad = lambda a: jnp.pad(a, ((reach, 0), (0, 0), (0, 0)))
    kp, vp = pad(k), pad(v)

    @jax.checkpoint
    def block(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, bq).reshape(bq, kv, group, D)
        kb = jax.lax.dynamic_slice_in_dim(kp, start, bq + reach)
        vb = jax.lax.dynamic_slice_in_dim(vp, start, bq + reach)
        s = P.einsum("qkgd,skd->kgqs", qb, kb, precision) / math.sqrt(D)
        qpos = start + jnp.arange(bq)[:, None]
        kpos = start - reach + jnp.arange(bq + reach)[None, :]
        see = (kpos >= 0) & (kpos <= qpos)
        a = jax.nn.softmax(jnp.where(see, s, -jnp.inf), -1)
        return P.einsum("kgqs,skd->qkgd", a, vb, precision).reshape(bq, heads * D)

    ctx = jax.lax.map(block, jnp.arange(0, T, bq)).reshape(T, heads * D)
    return P.matmul(ctx, p["o_proj"]["weight"], precision)


def route(p, x, cfg, precision, chosen=None):
    """x: (T, d) -> each token's weights and experts, (T, k) both: sigmoid
    scores over all published experts in float32 (one precision down: bfloat16
    operands); the k largest of score + bias; the chosen scores over their sum.
    ``chosen``: the experts, given and not picked (tools/lfm2_routing.py)."""
    router_precision = "float32" if precision == "float32" else "bfloat16"
    router = p["router"] if cfg.get("router_out_in") else p["router"].T     # -> (E, d)
    s = jax.nn.sigmoid(P.matmul(x, router, router_precision))
    if chosen is None:
        biased = s + p["expert_bias"] if "expert_bias" in p else s
        _, chosen = jax.lax.top_k(jax.lax.stop_gradient(biased), cfg["num_experts_per_tok"])
    top = jnp.take_along_axis(s, chosen, -1)
    w = top / (jnp.sum(top, -1, keepdims=True) + RENORM_EPS)
    return cfg.get("moe_routed_scaling_factor", 1.0) * w, chosen


def sparse_mlp(p, x, cfg, precision):
    """x: (T, d).  The experts held run one at a time over all tokens, each
    weighted by what the tokens that chose it gave it."""
    held, start = p["w_in"].shape[0], cfg.get("experts_held_start", 0)
    w, idx = route(p, x, cfg, precision)
    t = lambda a: jnp.swapaxes(a, -1, -2)           # (in, out) -> (out, in)

    @jax.checkpoint
    def one(y, e):
        weight = jnp.sum(jnp.where(idx == start + e, w, 0.0), -1)
        out = swiglu(x, t(p["w_gate"][e]), t(p["w_in"][e]), t(p["w_out"][e]), precision)
        return y + weight[:, None] * out, None

    y, _ = jax.lax.scan(one, jnp.zeros_like(x), jnp.arange(held))
    return y


def operator(lp, u, cfg, kind, precision):
    if kind == "conv":
        return short_conv(lp["conv"], u, cfg, precision)
    return attention(lp["self_attn"], u, cfg, kind, precision)


def feed_forward(lp, u, cfg, sparse, precision):
    if sparse:
        return sparse_mlp(lp["mlp"], u, cfg, precision)
    m = lp["mlp"]
    return swiglu(u, m["gate_proj"]["weight"], m["up_proj"]["weight"],
                  m["down_proj"]["weight"], precision)


def hidden(p, ids, cfg, precision):
    """(T,) ids of one sequence -> (T, d) after the final norm."""
    eps = cfg["rms_norm_eps"]
    x = p["embed_tokens"]["weight"][ids]
    for i in range(cfg["num_hidden_layers"]):
        @jax.checkpoint
        def layer(lp, x, kind=cfg["layer_types"][i],
                  sparse=cfg["mlp_layer_types"][i] == "sparse"):
            x = x + operator(lp, rms_norm(x, lp["input_layernorm"]["weight"], eps), cfg, kind,
                             precision)
            h = rms_norm(x, lp["post_attention_layernorm"]["weight"], eps)
            return x + feed_forward(lp, h, cfg, sparse, precision)

        x = layer(p["layers"][str(i)], x)
    return rms_norm(x, p["norm"]["weight"], eps)


def head_weight(p):
    """The head's matrix: the embedding's, unless the tree holds one of its own."""
    return p["lm_head" if "lm_head" in p else "embed_tokens"]["weight"]


def logits(p, ids, cfg, precision="float32"):
    """(B, T) ids -> (B, T, V) over the vocabulary slice held."""
    return jnp.stack([P.matmul(hidden(p, row, cfg, precision), head_weight(p), precision)
                      for row in ids])


def summed_nll(p, ids, cfg, precision="float32"):
    """Sum over the rows of ``ids`` and every position but the last of the
    next token's negative log-likelihood."""
    logp = jax.nn.log_softmax(logits(p, ids, cfg, precision)[:, :-1], -1)
    return -jnp.sum(jnp.take_along_axis(logp, ids[:, 1:, None], -1))


def train(params, batches, cfg, groups=1, block_rows=1, precision="float32",
          param_dtype="float32", hp=ADAM):
    """Follow the first ``len(batches)`` steps from the seeded weights.
    Returns each step's loss (the mean over the rows' positions), the per-leaf
    norm and projections of the first gradient, and those of the parameters'
    change after the last step.  Gradients are accumulated over blocks of
    ``block_rows`` rows so that it fits."""
    del groups                      # every row is full: a mean over chips is the mean over all

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
    def step(p, m, v, t, ids):
        rows, T = ids.shape
        scale = 1.0 / (rows * (T - 1))
        loss, g = jnp.float32(0), None
        for block in ids.reshape(rows // block_rows, block_rows, T):
            l, gb = jax.value_and_grad(lambda q: scale * summed_nll(q, block, cfg, precision))(p)
            loss = loss + l
            g = gb if g is None else jax.tree_util.tree_map(jnp.add, g, gb)
        p, m, v = adam_update(p, m, v, g, t, hp, param_dtype)
        return p, m, v, loss, leaf_norms(g)

    # the seeded weights stay in the type they came in and are widened again where
    # they are compared
    p = jax.tree_util.tree_map(lambda x: jnp.array(x, jnp.float32), params)      # a copy: step donates
    m = jax.tree_util.tree_map(jnp.zeros_like, p)
    v = jax.tree_util.tree_map(jnp.zeros_like, p)
    losses, first_grad = [], None
    for t, (ids,) in enumerate(batches, start=1):
        p, m, v, loss, gn = step(p, m, v, jnp.float32(t), jnp.asarray(ids))
        losses.append(float(loss))
        if t == 1:
            first_grad = np.asarray(gn)
    change = np.asarray(jax.jit(lambda p, p0: leaf_norms(jax.tree_util.tree_map(
        jnp.subtract, p, P.to_f32(p0))))(p, params))
    return {"losses": losses, "first_grad_norms": first_grad, "update_norms": change}
