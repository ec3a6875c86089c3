"""Next-token pretraining of the ``ouro`` looped decoder in plain jax.numpy and
float32, from the published config's keys
(https://huggingface.co/ByteDance/Ouro-2.6B/blob/main/config.json; the family's
paper is "Scaling Latent Reasoning via Looped Language Models",
arXiv:2510.25741): the forward pass of every pass, the exit-weighted loss, its
gradient and Adam, with no kernel, no policy and no code of ``apex_tpu``.  It
reads a parameter tree in the program's layout (torch-style (out, in)
``weight`` leaves; ``exit_gate`` a ``Linear(hidden, 1)``: ``weight`` (1, d) and
``bias`` (1,)) that the benchmark made from the seed.  The pieces that are the
same mathematics in every such decoder (RMSNorm, SwiGLU, the RoPE tables and
rotation, Adam) are ``references/laguna.py``'s, attention over equal query and
K/V heads ``references/mellum2.py``'s, the rows kept of every leaf (its norm
and 64 random projections) and their comparison ``references/lfm2.py``'s and
``references/mellum2.py``'s; the block, the loop, the loss and the limits are
this file's own.

The equations (``L`` layers, ``R = total_ut_steps`` passes over the same
weights, RMSNorm eps ``rms_norm_eps`` with a gain, no bias in any projection):

    h_0 = E[ids]
    pass t = 1..R:  x = h_{t-1};  x = Block_l(x) for l = 0..L-1;  h_t = RMSNorm_f(x)
    Block_l(x):     a = x + N2_l(Attn_l(N1_l(x)));  a + N4_l(MLP_l(N3_l(a)))

``N1..N4``: ``input_layernorm``, ``input_layernorm_2``,
``post_attention_layernorm``, ``post_attention_layernorm_2`` (a sandwich: a
norm before each branch and one on what it returns).  ``Attn_l``:
``num_attention_heads`` query heads over as many K/V heads of ``head_dim``,
RoPE at ``rope_theta`` over the whole head (rotate-half), positions 0..T-1 at
every pass, scores q.k / sqrt(head_dim), causal.  ``MLP_l``: SwiGLU of
``intermediate_size``.  ``h_t``, one final norm's output, is what the head and
the gate read and the next pass's input.

    z_t     = h_t W_head^T (one untied head for every pass);  nll_t,i its next-token loss
    lambda_t = sigmoid(h_t . w_g + b_g)                  (t < R; the last pass's is not read)
    p_t     = lambda_t prod_{j<t} (1 - lambda_j),  p_R = prod_{j<R} (1 - lambda_j)
    l_i     = sum_t p_t,i nll_t,i - exit_beta H(p_i),   H(p) = -sum_t p_t log p_t

and the loss is the mean of ``l_i`` over every position but each row's last.
Gradients flow through ``p`` into the gate and the backbone.  The passes are a
plain Python loop over one parameter tree, so a stack weight's gradient is the
float32 sum of its R contributions.

Assumed, each the configuration's own (configs/ouro-2.6b.json: ``assumed``):
the final norm inside the loop, the sandwich block, the gate as a linear map
with a bias, the first-stage objective with ``exit_beta``; Adam as apex's
FusedAdam defines it.

To fit one 8k sequence in float32 on one chip: every block application is
recomputed in the backward pass (``jax.checkpoint`` around a block), attention
runs in blocks of queries, and the head reads the passes' states in blocks of
``HEAD_ROWS`` positions, one after the other, each recomputed in the backward
pass.

LIMITS: what the timed path may differ by, and why; set from chip readings at
the cell's own size (PERF.md, "Limits of correct"): above the largest a sound
bf16 run gave over its seeds, below the smallest the control one precision
down gave.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import _precision as P
from .laguna import ADAM, adam_update, norm_gap, rms_norm, swiglu  # noqa: F401
from .lfm2 import leaf_norms
from .mellum2 import attention, difference_norms, leaf_differences  # noqa: F401

HEAD_ROWS = 1024            # positions whose logits over the vocabulary are live at once
GATE = "exit_gate"

# number -> limit.  Readings they were set from, on the chip at the cell's own size (PR 40;
# PERF.md, "Limits of correct"; tools/control.py on seeds 4000000011-13, both controls on all
# three, and the result lines of the runs on 4000000002-3 and, on the committed files,
# 4000000101-105, tools/control.py again on 4000000111-112 with both controls: 12 sound and
# 5 control readings): sound largest / fp8-compute control smallest /
# bf16-parameter control smallest.
LIMITS = {
    # |loss - ref| / ref, worst of the three steps: 7.7e-5 (12 sound readings, 5.6e-6 on) /
    # 1.8e-4 (1.8e-4-4.0e-4) / 1.0e-5.  The fp8 control moves it 3 x, the parameter control
    # not at all, so it is the accepted decoder cells' limit (references/laguna.py), 17 x
    # the largest sound reading.  Held against a part of the batch, of the model or of the
    # loss (a pass's head, the entropy term) left out, and against an update wrong in size
    "loss_gap": 1.35e-3,
    # first gradient as Adam got it, mean over the 43 leaves of the estimated norm of
    # (program - reference) over the leaf's reference norm, a leaf counting for at most 1:
    # 0.0111 (0.0075-0.0111 over 12) / 0.116 (0.116-0.202 over 5) / 0.  COMPUTE precision, by
    # what the gradient DIFFERS by: 10 x apart, midway by ratio (3.2 x over sound, 3.2 x under
    # the control).  This network does
    # not amplify a rounding: its sandwich norms hold every branch at unit scale (the
    # reference alone off the chip reads 0.011 / 0.16 at a reduced size)
    "grad_diff_mean": 0.036,
    # (the same gradient by how LONG each leaf is, ``grad_norm_gap_mean``, is NOT compared:
    # 1.33e-3 sound (12 readings: 2.2e-4-1.33e-3, the largest 3.2 x their median) / 3.96e-3
    # control (5: 3.96e-3-1.32e-2): 3 x apart with a sound spread that wide leaves no limit
    # room on both sides, and what it would hold (a gradient wrong in size reads 1.0)
    # ``grad_diff_mean`` holds)
    # the gate's two leaves by themselves (``gate_gap``): 0.0198 (12 sound readings,
    # 0.0030-0.0198, median 0.0056: a ratio over 2049 numbers spreads more than a mean over
    # 43 leaves) / 0.106 (5: 0.106-0.382) / 0, most of them read before a leaf was measured
    # against no less than GATE_FLOOR of the gate's whole gradient, which can only lower a
    # reading; with the floor, on the two seeds whose sound readings were largest: 0.0137
    # (0.0357 at a floor of a hundredth: the one-number bias where it came out small) with
    # its control at 0.184, and 0.0198 unmoved (the weight's own).  COMPUTE precision where
    # the exit distribution is made, and a gate leaf left out of the program (reads 1.0):
    # 3.0 x over the largest sound reading, 1.8 x under the smallest control
    "gate_grad_gap": 0.06,
    # worst leaf, norm of the parameters' change after the steps: 1.44e-3 (1.8e-4-1.44e-3) /
    # 2.2e-3 (2.2e-3-1.0e-2) / 0.0661 (0.0661-0.0773; a stuck step reads 1.0).  PARAMETER
    # precision: midway by ratio, 6.8 x from either
    "update_norm_gap": 9.8e-3,
}


def block(lp, x, cfg, precision):
    """One sandwich-normed block on (T, d) of one sequence."""
    norm = lambda name, y: rms_norm(y, lp[name]["weight"], cfg["rms_norm_eps"])
    a = x + norm("input_layernorm_2", attention(
        lp["self_attn"], norm("input_layernorm", x), cfg, "full_attention", precision))
    m = lp["mlp"]
    y = swiglu(norm("post_attention_layernorm", a), m["gate_proj"]["weight"],
               m["up_proj"]["weight"], m["down_proj"]["weight"], precision)
    return a + norm("post_attention_layernorm_2", y)


def states(p, ids, cfg, precision, by_pass=None):
    """(T,) ids of one sequence -> the normed state of every pass, R of (T, d).
    ``by_pass``: R trees of the layers, pass t reading its own
    (tools/control_loop_grad.py reads what each pass adds to a weight's gradient)."""
    x = p["embed_tokens"]["weight"][ids]
    once = jax.checkpoint(functools.partial(block, cfg=cfg, precision=precision))
    out = []
    for t in range(cfg["total_ut_steps"]):
        layers = p["layers"] if by_pass is None else by_pass[t]
        for i in range(cfg["num_hidden_layers"]):
            x = once(layers[str(i)], x)
        x = rms_norm(x, p["norm"]["weight"], cfg["rms_norm_eps"])
        out.append(x)
    return out


def logits(p, ids, cfg, precision="float32"):
    """(B, T) ids -> (R, B, T, V): every pass's logits."""
    rows = [states(p, row, cfg, precision) for row in ids]
    return jnp.stack([jnp.stack([P.matmul(h, p["lm_head"]["weight"], precision) for h in row])
                      for row in rows]).swapaxes(0, 1)


def head_nll(hs, head, labels, precision):
    """The R states of one sequence, (T, d) each, -> (R, T): every pass's
    next-token loss, ``HEAD_ROWS`` positions of one pass at a time."""
    R, (T, d) = len(hs), hs[0].shape
    rows = min(HEAD_ROWS, T)
    assert T % rows == 0, (T, rows)

    @jax.checkpoint
    def some(block):
        hb, lb = block
        logp = jax.nn.log_softmax(P.matmul(hb, head, precision), -1)
        return -jnp.take_along_axis(logp, lb[:, None], -1)[:, 0]

    # one block after the other, so that one block's logits are live at a time
    return jax.lax.map(some, (jnp.stack(hs).reshape(R * T // rows, rows, d),
                              jnp.tile(labels, R).reshape(R * T // rows, rows))).reshape(R, T)


def exit_distribution(p, hs):
    """The R states of one sequence -> (R, T): each position's exit distribution."""
    w, b = p[GATE]["weight"][0], p[GATE]["bias"][0]
    lam = [jax.nn.sigmoid(jnp.sum(h * w, -1) + b) for h in hs[:-1]]      # the last is not read
    stayed, out = jnp.ones_like(lam[0]), []
    for l in lam:
        out.append(l * stayed)
        stayed = stayed * (1.0 - l)
    return jnp.stack(out + [stayed])


def row_sums(p, ids, cfg, precision="float32", by_pass=None):
    """One sequence -> the sums over every position but the last of the loss
    ``l_i``, of the expected exit pass and of the last pass's loss."""
    hs = states(p, ids, cfg, precision, by_pass)
    labels = jnp.concatenate([ids[1:], ids[:1]])            # the last position's is not read
    nll = head_nll(hs, p["lm_head"]["weight"], labels, precision)
    prob = exit_distribution(p, hs)
    entropy = -jnp.sum(prob * jnp.log(prob), 0)
    each = jnp.sum(prob * nll, 0) - cfg["exit_beta"] * entropy
    passes = jnp.arange(1, len(hs) + 1, dtype=jnp.float32)
    return (jnp.sum(each[:-1]), jnp.sum((passes @ prob)[:-1]), jnp.sum(nll[-1][:-1]))


def summed_loss(p, ids, cfg, precision="float32", by_pass=None):
    """Sum over the rows of ``ids`` and every position but the last of ``l_i``."""
    return sum(row_sums(p, row, cfg, precision, by_pass)[0] for row in ids)


def gate_leaves(tree):
    """Where the gate's two leaves sit among the tree's leaves."""
    return [i for i, (path, _) in enumerate(jax.tree_util.tree_leaves_with_path(tree))
            if GATE in jax.tree_util.keystr(path)]


def train(params, batches, cfg, groups=1, block_rows=1, precision="float32",
          param_dtype="float32", hp=ADAM):
    """Follow the first ``len(batches)`` steps from the seeded weights.
    Returns each step's loss (the mean over the rows' positions), the rows of
    the first gradient's leaves (norm and projections), those of the
    parameters' change after the last step, and which leaves are the gate's.
    Gradients are accumulated over blocks of ``block_rows`` rows so that it
    fits, and Adam's two moments wait on the host while a gradient is taken:
    510 M float32 parameters, their gradient and the backward's temporaries
    (7.7 GB planned for a described v5e) leave no room for them beside."""
    del groups                      # every row is full: a mean over chips is the mean over all

    @jax.jit
    def gradient(p, ids):
        rows, T = ids.shape
        scale = 1.0 / (rows * (T - 1))
        loss, g = jnp.float32(0), None
        for some in ids.reshape(rows // block_rows, block_rows, T):
            l, gb = jax.value_and_grad(lambda q: scale * summed_loss(q, some, cfg, precision))(p)
            loss = loss + l
            g = gb if g is None else jax.tree_util.tree_map(jnp.add, g, gb)
        return loss, g, leaf_norms(g)

    update = jax.jit(lambda p, m, v, g, t: adam_update(p, m, v, g, t, hp, param_dtype),
                     donate_argnums=(0, 1, 2))
    to_host = lambda tree: jax.tree_util.tree_map(np.asarray, tree)
    # the seeded weights stay in the type they came in and are widened again where
    # they are compared
    p = jax.tree_util.tree_map(lambda x: jnp.array(x, jnp.float32), params)      # a copy: update donates
    m = v = jax.tree_util.tree_map(lambda x: np.zeros(x.shape, np.float32), params)
    losses, first_grad = [], None
    for t, (ids,) in enumerate(batches, start=1):
        loss, g, gn = gradient(p, jnp.asarray(ids))
        losses.append(float(loss))
        if t == 1:
            first_grad = np.asarray(gn)
        p, m, v = update(p, m, v, g, jnp.float32(t))
        del g
        if t < len(batches):
            m, v = to_host(m), to_host(v)
    change = np.asarray(jax.jit(lambda p, p0: leaf_norms(jax.tree_util.tree_map(
        jnp.subtract, p, P.to_f32(p0))))(p, params))
    return {"losses": losses, "first_grad_norms": first_grad, "update_norms": change,
            "gate_leaves": gate_leaves(params)}


GATE_FLOOR = 0.1            # of the gate's whole gradient: the least a leaf of it is measured against


def gate_gap(program: np.ndarray, reference: np.ndarray, leaves) -> float:
    """The gate's two leaves by themselves (2049 numbers among half a
    billion: no mean over the leaves sees them, and the update's worst leaf is
    read against the median leaf's norm, under which they disappear): the worse
    of the two by the estimated norm of (program - reference) over the leaf's
    own reference norm.  The bias is one number, a mean over the positions that
    can come out near zero on a seed, so a leaf is measured against no less
    than ``GATE_FLOOR`` of the norm of the gate's whole gradient: a leaf the
    program gives no gradient reads 1.0 unless the reference's is under that
    floor itself."""
    diff = np.sqrt(np.mean(np.square(program[leaves, 1:] - reference[leaves, 1:]), axis=1))
    norms = reference[leaves, 0]
    return float(np.max(diff / np.maximum(norms, GATE_FLOOR * np.sqrt(np.sum(norms ** 2)))))


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
            "gate_grad_gap": gate_gap(got, want, reference["gate_leaves"]),
            # printed with the worst leaves and not compared (no control was read for them)
            "grad_diff_at_worst_leaf": d,
            "grad_diff_at_median_leaf": float(np.median(leaf_differences(got, want))),
            "update_norm_gap": u, "update_norm_gap_leaf": ui}
