"""BERT pretraining in plain jax.numpy and float32, from Devlin et al. 2018
(arXiv:1810.04805) and the published ``bert-large-uncased`` config: the
forward pass, the MLM + NSP loss, its gradient and Adam, with no kernel, no
policy and no code of ``apex_tpu``.  It reads a parameter tree in the
program's layout (torch-style (out, in) weights, fused ``qkv`` rows ordered
[q; k; v]) that the benchmark made from the seed.

Departures from the paper, each the configuration's own (configs/*.json):
no dropout; no segment embedding added (the example passes no
``token_type_ids``); tanh-approximated GELU; no decoder bias on the tied MLM
head; Adam as apex's FusedAdam defines it (decoupled weight decay inside the
bias-corrected step, epsilon outside the root, no bias correction of the
denominator); under data parallelism the loss is the mean over chips of each
chip's own masked mean, which the caller expresses through ``token_weights``.

LIMITS: what the timed path may differ by, and why.  Set by steps 4-5 of the
contract from chip readings (PERF.md section 2 has them): above the largest a
sound bf16 run gave over a dozen seeds, below the smallest the control gave.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import _precision as P

# number -> limit.  Readings they were set from: PERF.md, "Limits of correct".
LIMITS = {
    "first_loss_gap": 7e-4,    # |loss - ref| / ref at the seeded weights (step 1)
    "loss_gap": 3.6e-2,         # the same, worst of three steps
    "grad_norm_gap_mean": 0.013,   # first gradient as Adam got it: mean over the leaves of each
                                   # leaf's norm gap (the worst leaf is printed, not compared: see norm_gap)
    "update_norm_gap": 0.4,   # worst leaf, parameters' change after three steps
}
ADAM = {"lr": 1e-4, "beta1": 0.9, "beta2": 0.999, "eps": 1e-8, "weight_decay": 0.01}


def layer_norm(x, p, eps):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * p["weight"] + p["bias"]


def linear(x, p, precision):
    return P.matmul(x, p["weight"], precision) + p["bias"]


def gelu(x):
    return 0.5 * x * (1.0 + jnp.tanh(np.sqrt(2.0 / np.pi) * (x + 0.044715 * x ** 3)))


def encoder(p, ids, cfg, precision):
    """(B, T) ids -> (B, T, H) hidden states and the (B, H) pooled [CLS]."""
    B, T = ids.shape
    heads = cfg["num_attention_heads"]
    eps = cfg["layer_norm_eps"]
    e = p["bert"]
    x = e["word_embeddings"]["weight"][ids] + e["position_embeddings"]["weight"][:T][None]
    x = layer_norm(x, e["embeddings_ln"], eps)
    d = x.shape[-1] // heads
    for i in range(cfg["num_hidden_layers"]):
        lp = e["layer"][str(i)]
        qkv = linear(x, lp["attention"]["qkv"], precision).reshape(B, T, 3, heads, d)
        q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
        s = P.einsum("bqhd,bkhd->bhqk", q, k, precision) / np.sqrt(d)
        a = P.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v, precision).reshape(B, T, -1)
        x = layer_norm(x + linear(a, lp["attention"]["out"], precision), lp["attention_ln"], eps)
        h = linear(gelu(linear(x, lp["intermediate"], precision)), lp["output"], precision)
        x = layer_norm(x + h, lp["output_ln"], eps)
    pooled = jnp.tanh(linear(x[:, 0], e["pooler"], precision))
    return x, pooled


def logits(p, ids, cfg, precision="float32"):
    x, pooled = encoder(p, ids, cfg, precision)
    h = layer_norm(gelu(linear(x, p["mlm_dense"], precision)), p["mlm_ln"], cfg["layer_norm_eps"])
    mlm = P.matmul(h, p["bert"]["word_embeddings"]["weight"], precision)
    return mlm, linear(pooled, p["nsp"], precision)


def weighted_loss(p, ids, labels, nsp, token_weights, nsp_weight, cfg, precision="float32"):
    """sum(token_weights * MLM nll) + nsp_weight * sum(NSP nll).  With weights
    1/(masked tokens) and 1/rows this is the paper's loss of one batch."""
    mlm, nsp_logits = logits(p, ids, cfg, precision)
    logp = jax.nn.log_softmax(mlm, -1)
    nll = -jnp.take_along_axis(logp, jnp.maximum(labels, 0)[..., None], -1)[..., 0]
    nsp_nll = -jnp.take_along_axis(jax.nn.log_softmax(nsp_logits, -1), nsp[:, None], -1)[:, 0]
    return jnp.sum(nll * token_weights) + nsp_weight * jnp.sum(nsp_nll)


def token_weights(labels: np.ndarray, groups: int) -> np.ndarray:
    """Each chip (a contiguous group of rows) takes the mean over its own
    masked tokens, and the chips' losses are averaged."""
    rows = labels.shape[0] // groups
    w = np.zeros(labels.shape, np.float32)
    for g in range(groups):
        sl = slice(g * rows, (g + 1) * rows)
        valid = labels[sl] != -100
        w[sl] = valid / (max(valid.sum(), 1) * groups)
    return w


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


def train(params, batches, cfg, groups=1, block_rows=2, precision="float32",
          param_dtype="float32", hp=ADAM):
    """Follow the first ``len(batches)`` steps from the seeded weights.
    Returns each step's loss, the per-leaf norm of the first gradient, and the
    per-leaf norm of the parameters' change after the last step.  Gradients
    are accumulated over blocks of ``block_rows`` rows so that it fits."""
    p0 = P.to_f32(params)

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
    def step(p, m, v, t, ids, labels, nsp, w):
        nb = ids.shape[0] // block_rows
        blk = lambda a: a.reshape((nb, block_rows) + a.shape[1:])

        def body(acc, xs):
            loss, g = jax.value_and_grad(weighted_loss)(
                p, xs[0], xs[1], xs[2], xs[3], 1.0 / ids.shape[0], cfg, precision)
            return (acc[0] + loss, jax.tree_util.tree_map(jnp.add, acc[1], g)), None

        zero = jax.tree_util.tree_map(jnp.zeros_like, p)
        (loss, g), _ = jax.lax.scan(body, (jnp.float32(0), zero),
                                    (blk(ids), blk(labels), blk(nsp), blk(w)))
        p, m, v = adam_update(p, m, v, g, t, hp, param_dtype)
        return p, m, v, loss, leaf_norms(g)

    p = jax.tree_util.tree_map(jnp.copy, p0)
    m = jax.tree_util.tree_map(jnp.zeros_like, p0)
    v = jax.tree_util.tree_map(jnp.zeros_like, p0)
    losses, first_grad = [], None
    for t, (ids, labels, nsp) in enumerate(batches, start=1):
        w = token_weights(labels, groups)
        p, m, v, loss, gn = step(p, m, v, jnp.float32(t), ids, labels, nsp, w)
        losses.append(float(loss))
        if t == 1:
            first_grad = np.asarray(gn)
    change = np.asarray(leaf_norms(jax.tree_util.tree_map(jnp.subtract, p, p0)))
    return {"losses": losses, "first_grad_norms": first_grad, "update_norms": change}


def norm_gap(program: np.ndarray, reference: np.ndarray):
    """Per leaf, the gap between the two norms against the reference's norm of
    that leaf or of the median leaf, whichever is larger: the worst leaf, which
    leaf it is, and the mean over the leaves, a leaf counting for at most 1.
    The mean is steady, and rounding noise, which inflates every norm a
    little, moves it most.  The worst leaf of the gradient is no number to hold
    a run to: the two-element ``nsp.bias`` gradient is a mean over the rows of
    signed numbers that can all but cancel, so its relative gap has no upper
    end in a sound run (0.003-0.019 on 29 chip runs, then 0.048 on one)."""
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
