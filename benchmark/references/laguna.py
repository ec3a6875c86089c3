"""Next-token pretraining of the ``laguna`` decoder in plain jax.numpy and
float32, from the published config's keys
(https://huggingface.co/poolside/Laguna-XS.2/blob/main/config.json): the
forward pass, the loss over the held vocabulary slice, its gradient and Adam,
with no kernel, no policy and no code of ``apex_tpu``.  It reads a parameter
tree in the program's layout (torch-style (out, in) ``weight`` leaves; an expert
layer's ``router`` (d, E), ``w_gate``/``w_in`` (n, d, h), ``w_out`` (n, h, d)
and ``shared``) that the benchmark made from the seed.

The layer equations (pre-norm, RMSNorm, no biases):

    h = x + Attn_l(RMSNorm(x));  y = h + MLP_l(RMSNorm(h));  final RMSNorm; head

``Attn_l``: ``num_attention_heads_per_layer[l]`` query heads over
``num_key_value_heads`` K/V heads (query head h reads K/V head h // group),
scores q.k / sqrt(head_dim), causal, and in a ``sliding_attention`` layer key
j visible to query i iff i - sliding_window < j <= i.  RoPE by layer type from
``rope_parameters``: ``default`` rotates the leading ``partial_rotary_factor``
of the head at theta; ``yarn`` blends theta^(-2i/d) with that over ``factor``
by the linear ramp between the correction dimensions of ``beta_fast`` and
``beta_slow`` at ``original_max_position_embeddings`` and scales cos and sin
by ``attention_factor`` (as ``transformers`` computes them).
``MLP_l``: ``dense`` is a SwiGLU of ``intermediate_size``; ``sparse`` is
``s = sigmoid(x W_r)`` over all published experts, the ``num_experts_per_tok``
largest, ``w = moe_routed_scaling_factor * s_top / sum(s_top)``,
``sum_k w_k SwiGLU_{e_k}(x) + SwiGLU_shared(x)``.

A chip's share (configs/*.json: ``deployment``): the tree holds ``num_experts``
experts from ``experts_held_start``; the router scores all
``num_experts_published``; an assignment to an expert held elsewhere adds
nothing.  The vocabulary is the slice the tree holds.

Assumed, each the configuration's own (configs/*.json: ``assumed``): the
router's score function and normalization, ``gating`` as a sigmoid gate per
head on the attention output (``o_h <- sigmoid(x w_h) o_h``), no QK-norm, no
auxiliary loss; Adam as apex's FusedAdam defines it (decoupled weight decay
inside the bias-corrected step, epsilon outside the root).

To fit 8k sequences in float32 on one chip: gradients are accumulated over
blocks of rows, each layer is recomputed in the backward pass, attention runs
in blocks of queries (a sliding layer reads only the keys its band reaches)
and the experts one at a time over all tokens.

LIMITS: what the timed path may differ by, and why; set from chip readings
(PERF.md, "Limits of correct"): above the largest a sound bf16 run gave over
its seeds, below the smallest the control one precision down gave.
"""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from . import _precision as P

# number -> limit.  Readings they were set from (tools/control.py and
# tools/control_leaves.py on the chip at the cell's own size: 21 sound seeds, the
# first gradient of the fp8-compute control on 9, the other numbers of both
# controls on 3; PERF.md, "Limits of correct"): sound largest / fp8-compute
# control smallest / bf16-parameter control smallest.
LIMITS = {
    # |loss - ref| / ref at the seeded weights (step 1): 3.1e-4 / 3.1e-4 / 0.  The
    # lower precision hardly moves it: 3 x sound.  Held against a part of the
    # batch or of the model left out of the loss
    "first_loss_gap": 9.5e-4,
    # the same, worst of the three steps: 4.5e-4 / 6.9e-4 / 1.7e-4: 3 x sound.
    # Held against an update wrong in size or sign
    "loss_gap": 1.35e-3,
    # first gradient as Adam got it, mean over the 69 leaves of each leaf's norm
    # gap: 8.1e-4 (5.0-8.1e-4) / 1.51e-3 (1.5-2.6e-3) / 0.  COMPUTE precision:
    # midway by ratio.  Of the statistics over the leaves the mean parts the two
    # furthest (on six seeds read leaf by leaf: mean 2.7 x, median 1.5 x, worst
    # leaf 1.4 x, printed and not compared; no group of leaves does better)
    "grad_norm_gap_mean": 1.1e-3,
    # worst leaf, parameters' change after the steps: 1.28e-3 / 2.0e-3 / 0.21
    # (a stuck step reads 1.0).  PARAMETER precision: midway by ratio
    "update_norm_gap": 0.015,
}
ADAM = {"lr": 1e-4, "beta1": 0.9, "beta2": 0.999, "eps": 1e-8, "weight_decay": 0.01}
QUERY_BLOCK = 256


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def swiglu(x, gate, up, down, precision):
    """``gate``, ``up``: (hidden, d); ``down``: (d, hidden) — (out, in) weights."""
    return P.matmul(jax.nn.silu(P.matmul(x, gate, precision)) * P.matmul(x, up, precision),
                    down, precision)


def rope_tables(rope: dict, head_dim: int, T: int):
    """(cos, sin) of shape (T, rotated dims) for one ``rope_parameters`` group."""
    dim = int(head_dim * rope.get("partial_rotary_factor", 1.0))
    base = float(rope["rope_theta"])
    pos_freqs = base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    inv, scale = 1.0 / pos_freqs, 1.0
    if rope.get("rope_type", "default") == "yarn":
        orig, factor = rope["original_max_position_embeddings"], float(rope["factor"])
        scale = rope.get("attention_factor") or 0.1 * math.log(factor) + 1.0

        def correction_dim(rotations):
            return dim * math.log(orig / (rotations * 2 * math.pi)) / (2 * math.log(base))

        low = max(math.floor(correction_dim(rope["beta_fast"])), 0)
        high = min(math.ceil(correction_dim(rope["beta_slow"])), dim - 1)
        high = high + 0.001 if low == high else high
        keep = 1.0 - np.clip((np.arange(dim // 2) - low) / (high - low), 0.0, 1.0)
        inv = inv / factor * (1.0 - keep) + inv * keep
    ang = np.arange(T, dtype=np.float64)[:, None] * inv[None, :]
    ang = np.concatenate([ang, ang], -1)
    return (jnp.asarray(np.cos(ang) * scale, jnp.float32),
            jnp.asarray(np.sin(ang) * scale, jnp.float32))


def apply_rope(x, cos, sin):
    """x: (T, heads, D); the leading ``cos.shape[-1]`` dims rotate (rotate-half)."""
    rd = cos.shape[-1]
    xr, rest = x[..., :rd], x[..., rd:]
    half = jnp.concatenate([-xr[..., rd // 2:], xr[..., :rd // 2]], -1)
    return jnp.concatenate([xr * cos[:, None] + half * sin[:, None], rest], -1)


def attention(p, x, heads, cfg, kind, precision):
    """x: (T, d) of one sequence -> (T, d)."""
    T, D, kv = x.shape[0], cfg["head_dim"], cfg["num_key_value_heads"]
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
        return P.einsum("kgqs,skd->qkgd", a, vb, precision).reshape(bq, heads, D)

    ctx = jax.lax.map(block, jnp.arange(0, T, bq)).reshape(T, heads, D)
    if cfg.get("gating"):
        ctx = ctx * jax.nn.sigmoid(P.matmul(x, p["g_proj"]["weight"], precision))[..., None]
    return P.matmul(ctx.reshape(T, heads * D), p["o_proj"]["weight"], precision)


def sparse_mlp(p, x, cfg, precision):
    """x: (T, d).  Scores over all published experts in float32 (one precision
    down: bfloat16); the experts held run one at a time over all tokens, each
    weighted by what the tokens that chose it gave it."""
    held, start = p["w_in"].shape[0], cfg.get("experts_held_start", 0)
    router_precision = "float32" if precision == "float32" else "bfloat16"
    s = jax.nn.sigmoid(P.matmul(x, p["router"].T, router_precision))
    top, idx = jax.lax.top_k(s, cfg["num_experts_per_tok"])
    w = cfg["moe_routed_scaling_factor"] * top / jnp.sum(top, -1, keepdims=True)
    t = lambda a: jnp.swapaxes(a, -1, -2)           # (in, out) -> (out, in)

    @jax.checkpoint
    def one(y, e):
        weight = jnp.sum(jnp.where(idx == start + e, w, 0.0), -1)
        out = swiglu(x, t(p["w_gate"][e]), t(p["w_in"][e]), t(p["w_out"][e]), precision)
        return y + weight[:, None] * out, None

    y, _ = jax.lax.scan(one, jnp.zeros_like(x), jnp.arange(held))
    sh = p["shared"]
    return y + swiglu(x, t(sh["w_gate"]), t(sh["w_in"]), t(sh["w_out"]), precision)


def hidden(p, ids, cfg, precision):
    """(T,) ids of one sequence -> (T, d) after the final norm."""
    eps = cfg["rms_norm_eps"]
    x = p["embed_tokens"]["weight"][ids]
    for i in range(cfg["num_hidden_layers"]):
        kind, heads = cfg["layer_types"][i], cfg["num_attention_heads_per_layer"][i]
        sparse = cfg["mlp_layer_types"][i] == "sparse"

        @jax.checkpoint
        def layer(lp, x, kind=kind, heads=heads, sparse=sparse):
            x = x + attention(lp["self_attn"], rms_norm(x, lp["input_layernorm"]["weight"], eps),
                              heads, cfg, kind, precision)
            h = rms_norm(x, lp["post_attention_layernorm"]["weight"], eps)
            if sparse:
                return x + sparse_mlp(lp["mlp"], h, cfg, precision)
            m = lp["mlp"]
            return x + swiglu(h, m["gate_proj"]["weight"], m["up_proj"]["weight"],
                              m["down_proj"]["weight"], precision)

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


def adam_update(p, m, v, g, t, hp, param_dtype):
    bc1, bc2 = 1.0 - hp["beta1"] ** t, 1.0 - hp["beta2"] ** t
    step = hp["lr"] * jnp.sqrt(bc2) / bc1

    def one(p, m, v, g):
        m = hp["beta1"] * m + (1.0 - hp["beta1"]) * g
        v = hp["beta2"] * v + (1.0 - hp["beta2"]) * g * g
        new = p - step * (m / (jnp.sqrt(v) + hp["eps"]) + hp["weight_decay"] * p)
        return P.store(new, param_dtype), m, v

    out = jax.tree_util.tree_map(one, p, m, v, g)
    pick = lambda i: jax.tree_util.tree_map(lambda o: o[i], out,
                                            is_leaf=lambda o: isinstance(o, tuple))
    return pick(0), pick(1), pick(2)


def leaf_norms(tree):
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
                      for x in jax.tree_util.tree_leaves(tree)])


def train(params, batches, cfg, groups=1, block_rows=1, precision="float32",
          param_dtype="float32", hp=ADAM):
    """Follow the first ``len(batches)`` steps from the seeded weights.
    Returns each step's loss (the mean over chips of each chip's mean over
    its rows' positions, which with full rows is the mean over all), the
    per-leaf norm of the first gradient, and the per-leaf norm of the
    parameters' change after the last step.  Gradients are accumulated over
    blocks of ``block_rows`` rows so that it fits."""
    del groups                      # every row is full: the two means agree
    p0 = P.to_f32(params)

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
    def step(p, m, v, t, ids):
        rows, T = ids.shape
        scale = 1.0 / (rows * (T - 1))

        def body(acc, block):
            loss, g = jax.value_and_grad(
                lambda q: scale * summed_nll(q, block, cfg, precision))(p)
            return (acc[0] + loss, jax.tree_util.tree_map(jnp.add, acc[1], g)), None

        zero = jax.tree_util.tree_map(jnp.zeros_like, p)
        (loss, g), _ = jax.lax.scan(body, (jnp.float32(0), zero),
                                    ids.reshape(rows // block_rows, block_rows, T))
        p, m, v = adam_update(p, m, v, g, t, hp, param_dtype)
        return p, m, v, loss, leaf_norms(g)

    p = jax.tree_util.tree_map(jnp.copy, p0)
    m = jax.tree_util.tree_map(jnp.zeros_like, p0)
    v = jax.tree_util.tree_map(jnp.zeros_like, p0)
    losses, first_grad = [], None
    for t, (ids,) in enumerate(batches, start=1):
        p, m, v, loss, gn = step(p, m, v, jnp.float32(t), jnp.asarray(ids))
        losses.append(float(loss))
        if t == 1:
            first_grad = np.asarray(gn)
    change = np.asarray(leaf_norms(jax.tree_util.tree_map(jnp.subtract, p, p0)))
    return {"losses": losses, "first_grad_norms": first_grad, "update_norms": change}


def norm_gap(program: np.ndarray, reference: np.ndarray):
    """Per leaf, the gap between the two norms against the reference's norm of
    that leaf or of the median leaf, whichever is larger: the worst leaf, which
    leaf it is, and the mean over the leaves, a leaf counting for at most 1
    (references/bert.py has why the mean is what is compared)."""
    scale = np.maximum(reference, np.median(reference))
    gaps = np.abs(program - reference) / scale
    return float(gaps.max()), int(gaps.argmax()), float(np.minimum(gaps, 1.0).mean())


def compare(program: dict, reference: dict) -> dict:
    """The numbers ``correct`` rests on, each beside its limit."""
    gaps = [abs(a - b) / abs(b) for a, b in zip(program["losses"], reference["losses"])]
    g, gi, g_mean = norm_gap(np.asarray(program["first_grad_norms"]),
                             reference["first_grad_norms"])
    u, ui, _ = norm_gap(np.asarray(program["update_norms"]), reference["update_norms"])
    return {"first_loss_gap": gaps[0], "loss_gap": max(gaps), "grad_norm_gap": g,
            "grad_norm_gap_mean": g_mean, "grad_norm_gap_leaf": gi,
            "update_norm_gap": u, "update_norm_gap_leaf": ui}
