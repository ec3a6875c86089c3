"""Next-token pretraining of the ``mellum`` decoder in plain jax.numpy and
float32, from the published config's keys
(https://huggingface.co/JetBrains/Mellum2-12B-A2.5B-Instruct/blob/main/config.json):
the forward pass, the loss over the held vocabulary slice, its gradient and
Adam, with no kernel, no policy and no code of ``apex_tpu``.  It reads a
parameter tree in the program's layout (torch-style (out, in) ``weight``
leaves; an expert layer's ``router`` (d, E), ``w_gate``/``w_in`` (n, d, h) and
``w_out`` (n, h, d)) that the benchmark made from the seed.  The pieces that
are the same mathematics in every such decoder (RMSNorm, SwiGLU, the RoPE
tables and rotation, Adam, the norms and their comparison) are
``references/laguna.py``'s; attention, the expert layer, the model and the
limits are this file's own.

The layer equations (pre-norm, RMSNorm, no biases, untied embedding and head):

    h = x + Attn_l(RMSNorm(x));  y = h + MoE(RMSNorm(h));  final RMSNorm; head

``Attn_l``: ``num_attention_heads`` query heads over ``num_key_value_heads``
K/V heads in every layer (query head h reads K/V head h // group), scores
q.k / sqrt(head_dim), causal, and in a ``sliding_attention`` layer key j
visible to query i iff i - sliding_window < j <= i; no gate, no QK-norm.  RoPE
by layer type from ``rope_parameters`` over the whole head: ``default`` at
theta; ``yarn`` blends theta^(-2i/d) with that over ``factor`` by the linear
ramp between the correction dimensions of ``beta_fast`` and ``beta_slow`` at
``original_max_position_embeddings`` and scales cos and sin by
``attention_factor`` (as ``transformers`` computes them).
``MoE``, every layer (``mlp_layer_types`` is ``sparse`` throughout, and there
is no shared expert): ``p = softmax(x W_r)`` over all published experts, the
``num_experts_per_tok`` largest, ``w = p_top / sum(p_top)``
(``norm_topk_prob``), ``sum_k w_k SwiGLU_{e_k}(x)``.

A chip's share (configs/mellum2-12b.json: ``deployment``): the tree holds
``num_experts`` experts from ``experts_held_start``; the router scores all
``num_experts_published``; an assignment to an expert held elsewhere adds
nothing.  The vocabulary is the slice the tree holds.

Assumed, each the configuration's own (configs/mellum2-12b.json: ``assumed``):
softmax before top-k as the router's score, no router bias, no auxiliary loss;
Adam as apex's FusedAdam defines it.

To fit 8k sequences in float32 on one chip: each layer is recomputed in the
backward pass, attention runs in blocks of queries (a sliding layer reads only
the keys its band reaches) and the experts one at a time over all tokens.

LIMITS: what the timed path may differ by, and why; set from chip readings at
the cell's own size (PERF.md, "Limits of correct"): above the largest a sound
bf16 run gave over its seeds, below the smallest the control one precision
down gave.  The first gradient is compared by what it DIFFERS by, estimated
from random projections kept beside each leaf's norm (``leaf_norms``,
``leaf_differences``): in this model a lower compute precision moves a
gradient's direction long before it moves its length.
"""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from . import _precision as P
from .laguna import (ADAM, QUERY_BLOCK, adam_update, apply_rope, norm_gap,  # noqa: F401
                     rms_norm, rope_tables, swiglu)

# number -> limit.  Readings they were set from, on the chip (PR 31; PERF.md, "Limits of
# correct"): at an eighth of the vocabulary, the size the cell was first built at
# (tools/control.py on 6 seeds, a copy of it that keeps every per-leaf array on 8, the result
# lines of 12 runs), and again at the quarter it runs at (tools/control.py on 3 seeds, the
# result lines of its runs), where every number read inside the eighth's range:
# sound largest / fp8-compute control smallest / bf16-parameter control smallest.
LIMITS = {
    # |loss - ref| / ref, worst of the three steps: 2.05e-3 (32 sound readings; 1.65e-3 at
    # the quarter) / 1.0e-3 (3.9e-3) / 4.2e-4 (5.8e-5).  The lower precision hardly moves
    # it: 3 x sound.  Held against a part of the batch or of the model left out of the loss.
    # (The first step's alone reads to 1.22e-3; the accepted decoder cell's 9.5e-4 leaves it
    # no room, so it is not compared apart.)
    "loss_gap": 6e-3,
    # first gradient as Adam got it: mean over the 43 leaves of the estimated norm of
    # (program - reference) over the leaf's reference norm, a leaf counting for at most 1:
    # 0.507 (15 at an eighth: 0.465-0.504; 11 at the quarter: 0.466-0.507) / 0.713 (4 at an
    # eighth: 0.714-0.724; the quarter 0.713, 0.721) / 0.  COMPUTE precision: 1.16 x over the
    # largest sound reading, 1.21 x under the smallest control.  It is BLUNT, and
    # says so: a sound run reads near a half because this network at the assumed init_std
    # amplifies rounding a hundredfold on the way back (the gradient grows 30-fold from
    # layer 3 to layer 0; tools/routing_flips.py reads the same half from the reference
    # alone with bf16 operands, and 0.40 for 0.43 with the float32 run's choice of experts
    # given to it, so flipped assignments are a small part of it), and one layer's expert
    # gradients with the wrong sign add 0.03 and pass.  It holds against a lower compute
    # precision and against a gradient wrong in size or summed over chips (reads 1.0); the
    # per-leaf NORMS hold against neither here (their mean gap: 3.8e-3 sound, 3.2e-3
    # control; no leaf and no other statistic of them does better than 1.05 x)
    "grad_diff_mean": 0.59,
    # worst leaf, norm of the parameters' change after the steps: 1.21e-3 (32; 1.63e-3 at
    # the quarter) / 1.0e-3 / 0.209 (0.226; a stuck step reads 1.0).  PARAMETER precision:
    # midway by ratio
    "update_norm_gap": 0.015,
}

SKETCH = 8                  # random sign vectors a side: SKETCH ** 2 projections a leaf
_SKETCH_KEY = 31


def _as_matrix(x):
    """A leaf as (rows, columns): its last axis the columns; a vector folded
    square where its length allows."""
    if x.ndim >= 2:
        return x.reshape(-1, x.shape[-1])
    side = math.isqrt(x.size)
    return x.reshape(-1, side) if side * side == x.size else x.reshape(-1, 1)


def leaf_norms(tree):
    """Per leaf, in tree order, one row: its norm, then SKETCH ** 2 projections
    ``l^T G r`` of the leaf on fixed random sign vectors (the same for every
    tree of these shapes).  Projections are linear, so the mean square of the
    difference of two trees' projections estimates the squared norm of the
    difference of the leaves themselves (each has that expectation; 64 of them
    are within a fifth of it): what two gradients differ by, and not only how
    long each is."""
    rows, highest = [], jax.lax.Precision.HIGHEST
    for i, x in enumerate(jax.tree_util.tree_leaves(tree)):
        m = _as_matrix(x.astype(jnp.float32))
        kl, kr = jax.random.split(jax.random.fold_in(jax.random.PRNGKey(_SKETCH_KEY), i))
        left = jax.random.rademacher(kl, (SKETCH, m.shape[0]), jnp.float32)
        right = jax.random.rademacher(kr, (SKETCH, m.shape[1]), jnp.float32)
        proj = jnp.matmul(left, jnp.matmul(m, right.T, precision=highest), precision=highest)
        rows.append(jnp.concatenate([jnp.sqrt(jnp.sum(m * m))[None], proj.reshape(-1)]))
    return jnp.stack(rows)


def leaf_differences(program: np.ndarray, reference: np.ndarray) -> np.ndarray:
    """Per leaf, the norm of (program - reference) as the projections estimate
    it, over the reference's norm of that leaf or of the median leaf, whichever
    is larger (``norm_gap``'s scale)."""
    norms = reference[:, 0]
    diff = np.sqrt(np.mean(np.square(program[:, 1:] - reference[:, 1:]), axis=1))
    return diff / np.maximum(norms, np.median(norms))


def difference_norms(program: np.ndarray, reference: np.ndarray):
    """Of ``leaf_differences``: the worst leaf, which it is, and the mean over
    the leaves, a leaf counting for at most 1."""
    rel = leaf_differences(program, reference)
    return float(rel.max()), int(rel.argmax()), float(np.minimum(rel, 1.0).mean())


def compare(program: dict, reference: dict) -> dict:
    """The numbers ``correct`` rests on, each beside its limit."""
    gaps = [abs(a - b) / abs(b) for a, b in zip(program["losses"], reference["losses"])]
    got, want = np.asarray(program["first_grad_norms"]), np.asarray(reference["first_grad_norms"])
    g, gi, g_mean = norm_gap(got[:, 0], want[:, 0])
    d, di, d_mean = difference_norms(got, want)
    u, ui, _ = norm_gap(np.asarray(program["update_norms"])[:, 0],
                        np.asarray(reference["update_norms"])[:, 0])
    return {"first_loss_gap": gaps[0], "loss_gap": max(gaps), "grad_norm_gap": g,
            "grad_norm_gap_mean": g_mean, "grad_norm_gap_leaf": gi,
            "grad_diff_mean": d_mean, "grad_diff_leaf": di,
            # printed with the worst leaves and not compared (no control was read for them)
            "grad_diff_at_worst_leaf": d,
            "grad_diff_at_median_leaf": float(np.median(leaf_differences(got, want))),
            "update_norm_gap": u, "update_norm_gap_leaf": ui}


def attention(p, x, cfg, kind, precision):
    """x: (T, d) of one sequence -> (T, d)."""
    T, D = x.shape[0], cfg["head_dim"]
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    group = heads // kv
    cos, sin = rope_tables(cfg["rope_parameters"][kind], D, T)
    q = apply_rope(P.matmul(x, p["q_proj"]["weight"], precision).reshape(T, heads, D), cos, sin)
    k = apply_rope(P.matmul(x, p["k_proj"]["weight"], precision).reshape(T, kv, D), cos, sin)
    v = P.matmul(x, p["v_proj"]["weight"], precision).reshape(T, kv, D)
    window = cfg["sliding_window"] if kind == "sliding_attention" else None
    bq = min(QUERY_BLOCK, T)
    assert T % bq == 0, (T, bq)
    # a block of queries reads the keys from ``reach`` before its first row on
    reach = T - bq if window is None else min(window - 1, T - bq)
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
        if window is not None:
            see &= kpos > qpos - window
        a = jax.nn.softmax(jnp.where(see, s, -jnp.inf), -1)
        return P.einsum("kgqs,skd->qkgd", a, vb, precision).reshape(bq, heads * D)

    ctx = jax.lax.map(block, jnp.arange(0, T, bq)).reshape(T, heads * D)
    return P.matmul(ctx, p["o_proj"]["weight"], precision)


def route(p, x, cfg, precision, chosen=None):
    """x: (T, d) -> each token's weights and experts, (T, k) both: softmax over
    all published experts in float32 (one precision down: bfloat16 operands),
    the k largest, renormalized.  ``chosen``: the experts, given and not picked
    (tools/routing_flips.py reads what a run differs by beyond its choice)."""
    router_precision = "float32" if precision == "float32" else "bfloat16"
    probs = jax.nn.softmax(P.matmul(x, p["router"].T, router_precision), -1)
    if chosen is None:
        top, chosen = jax.lax.top_k(probs, cfg["num_experts_per_tok"])
    else:
        top = jnp.take_along_axis(probs, chosen, -1)
    w = top / jnp.sum(top, -1, keepdims=True) if cfg.get("norm_topk_prob", True) else top
    return w, chosen


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


def hidden(p, ids, cfg, precision):
    """(T,) ids of one sequence -> (T, d) after the final norm."""
    eps = cfg["rms_norm_eps"]
    x = p["embed_tokens"]["weight"][ids]
    for i in range(cfg["num_hidden_layers"]):
        @jax.checkpoint
        def layer(lp, x, kind=cfg["layer_types"][i]):
            x = x + attention(lp["self_attn"], rms_norm(x, lp["input_layernorm"]["weight"], eps),
                              cfg, kind, precision)
            h = rms_norm(x, lp["post_attention_layernorm"]["weight"], eps)
            return x + sparse_mlp(lp["mlp"], h, cfg, precision)

        x = layer(p["layers"][str(i)], x)
    return rms_norm(x, p["norm"]["weight"], eps)


def logits(p, ids, cfg, precision="float32"):
    """(B, T) ids -> (B, T, V) over the vocabulary slice held."""
    return jnp.stack([P.matmul(hidden(p, row, cfg, precision), p["lm_head"]["weight"], precision)
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
    norm of the first gradient, and the per-leaf norm of the parameters'
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

    # 595 M parameters in float32 are 2.4 GB a copy: the seeded weights stay in
    # the type they came in and are widened again where they are compared
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
