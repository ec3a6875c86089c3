"""Next-token pretraining of the ``nemotron_h`` decoder (Mamba-2 mixers, routed
squared-ReLU experts with a shared one, position-free attention, one branch a
block) in plain jax.numpy and float32, from the published config's keys
(https://huggingface.co/nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16/blob/main/config.json):
the forward pass, the loss over the held vocabulary slice, its gradient and
Adam, with no kernel, no policy and no code of ``apex_tpu``.  It reads a
parameter tree in the program's layout (torch-style (out, in) ``weight``
leaves; a mixer's ``conv1d.weight`` (taps, channels), ``conv1d.bias``,
``A_log``, ``dt_bias``, ``D`` (heads,), ``norm.weight``; an expert layer's
``router`` (d, E), ``expert_bias`` (E,), ``w_in`` (n, d, h), ``w_out`` (n, h, d)
and ``shared`` = {``w_in`` (d, hs), ``w_out`` (hs, d)}) that the benchmark made
from the seed.  RMSNorm and Adam are ``references/laguna.py``'s, the per-leaf
rows ``references/lfm2.py``'s and their comparison ``references/mellum2.py``'s;
the mixers, the model and the limits are this file's own.

The equations (RMSNorm eps ``norm_eps`` with a gain, no bias in any projection;
``u`` the normed input of a block; the kind of block ``l`` is the ``l``-th
letter of ``hybrid_override_pattern``):

    h = E[ids];  h = h + Mixer_l(RMSNorm_l(h));  logits RMSNorm_f(h) W_head^T

``M``, Mamba-2 (H = ``mamba_num_heads`` heads of P = ``mamba_head_dim``,
d_in = H P, G = ``n_groups`` groups of B and C, N = ``ssm_state_size``):
``[z | xBC | dt] = u W_in``; ``xBC <- silu(conv(xBC) + b_c)``, depthwise and
causal over the d_in + 2 G N channels, ``conv_kernel`` taps, zero before the
row's first token; ``[x | B | C] = xBC``, head h reads group h // (H / G);
``delta_t = softplus(dt_t + dt_bias)``; ``A = -exp(A_log)``;
``S_t = exp(delta_t A) S_{t-1} + delta_t B_t (x) x_t`` (N x P a head, zero
before the row), ``y_t = C_t^T S_t + D x_t``, **step by step over the
positions** (``ssm_scan``: a ``lax.scan`` of the recurrence, checkpointed every
``SCAN_BLOCK`` positions so that its gradient keeps one state a block);
``y <- RMSNorm_g(y * silu(z))`` over each of the G groups of d_in / G channels
apart, one gain of d_in; out ``y W_out``.
``*``, attention: ``num_attention_heads`` query heads over
``num_key_value_heads`` K/V heads of ``head_dim`` (query head h reads K/V head
h // group), scores q.k / sqrt(head_dim), causal, no rotation and no position
of any kind.
``E``, experts: ``s = sigmoid(u W_r)`` in float32 over all published experts;
the choice is the ``num_experts_per_tok`` largest of ``s + b``
(``expert_bias``: it enters the choice only and takes no gradient);
``w = s[choice] / (sum s[choice] + 1e-20)`` times ``routed_scaling_factor``;
expert e is ``W2_e relu(W1_e u)^2``; ``sum_k w_k expert_{e_k}(u) + Shared(u)``,
``Shared`` the same form at ``moe_shared_expert_intermediate_size``.

A chip's share (configs/nemotron3-nano-30b-a3b.json: ``deployment``): the tree
holds ``n_routed_experts`` experts from ``experts_held_start``; the router and
its bias keep all ``num_experts_published``; an assignment to an expert held
elsewhere adds nothing.  The vocabulary is the slice the tree holds.

Assumed, each the configuration's own (``assumed`` in that file): d_in from the
heads and not from ``expand``, no clamp on delta, no rotation in attention, no
auxiliary loss, a bias and float32 mixer leaves that Adam's decoupled decay
shrinks; Adam as apex's FusedAdam defines it.

To fit 8k sequences in float32 on one chip: each block is recomputed in the
backward pass, the scan keeps one state every ``SCAN_BLOCK`` positions,
attention runs in blocks of queries, the experts one at a time over all
tokens, and the head in blocks of rows.

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
from .laguna import ADAM as _ADAM, QUERY_BLOCK, adam_update, rms_norm  # noqa: F401
from .lfm2 import leaf_norms  # noqa: F401
from .mellum2 import compare, difference_norms, leaf_differences  # noqa: F401

# number -> limit.  Readings they were set from, on the chip at the cell's own size (PR 44;
# PERF.md, "Limits of correct"; a copy of tools/control.py that collects garbage before the
# reference, on seeds 4400000501-503 with both controls and on 4400000521-522 sound only; the
# traced run on 4400000511; the committed files' own runs on 4400000611-616 read inside the
# same ranges but for a loss_gap of 1.4e-4 and a grad_diff_mean of 0.0498): sound largest /
# fp8-compute control smallest / bf16-parameter control smallest.
LIMITS = {
    # |loss - ref| / ref, worst of the three steps: 9.7e-5 (6 sound readings, 3.8e-5 on) /
    # 1.4e-4 / 5.8e-5.  The lower precisions move it 4 x at most, so it is the accepted
    # decoder cells' limit (references/laguna.py), 14 x the largest sound reading.  Held
    # against a part of the batch or of the model left out of the loss (the mixer without its
    # skip D x reads 1.9e-3)
    "loss_gap": 1.35e-3,
    # first gradient as Adam got it, mean over the 72 leaves of the estimated norm of (program -
    # reference) over the leaf's reference norm, a leaf counting for at most 1: 0.0489
    # (0.0423-0.0489) / 0.1823 (0.1823, 0.1837, 0.1845) / 0.  COMPUTE precision, 3.7 x apart:
    # midway by ratio, 1.9 x over the largest sound reading and 1.9 x under the smallest
    # control.  With the selection bias balanced many experts lie a rounding away from the
    # choice, and the flipped assignments are part of what a sound run differs by.  The mixer
    # without D x reads 0.65; THE SCAN WITHOUT THE STATE CARRIED BETWEEN CHUNKS READS 0.057-0.061
    # AND PASSES (PERF.md sections 2 and 7: under leaves drawn N(0, init_std) a chunk forgets
    # its past within five positions)
    "grad_diff_mean": 0.094,
    # worst leaf, norm of the parameters' change after the steps: 5.9e-3 (1.8e-3-5.9e-3) /
    # 6.9e-3 / 375 (375-379: at lr 1e-6 a bfloat16 store's rounding is hundreds of times the
    # update).  PARAMETER precision, and a step that leaves its state unchanged, which reads
    # 1.0 and has to fail: between the largest sound reading and 1, with the more room above
    # the reading (10 x; 17 x under a stuck step)
    "update_norm_gap": 0.06,
}
# the configuration's own rate (configs/nemotron3-nano-30b-a3b.json: argv, assumed.optimizer)
ADAM = {**_ADAM, "lr": 1e-6}
RENORM_EPS = 1e-20
# the balancing rule's schedule where the benchmark balances a seeded bias (``routing``)
BALANCE = {"steps": 96, "first": 0.03, "last": 0.0003}
SCAN_BLOCK = 128            # positions between two kept states of the step-by-step scan
HEAD_BLOCK = 1024           # rows of logits at a time
KINDS = {"M": "mamba", "*": "attention", "E": "moe"}


def ssm_scan(x, delta, A, B, C, D, precision="float32"):
    """The selective state-space recurrence of one sequence, a position at a
    time.  ``x`` (T, H, P); ``delta`` (T, H); ``A``, ``D`` (H,); ``B``, ``C``
    (T, G, N), head h reading group h // (H / G) -> ``y`` (T, H, P)."""
    T, H, Pd = x.shape
    G, N = B.shape[1:]
    per = H // G
    block = math.gcd(T, SCAN_BLOCK)

    def step(S, inp):
        x_t, d_t, B_t, C_t = inp
        B_h, C_h = jnp.repeat(B_t, per, axis=0), jnp.repeat(C_t, per, axis=0)     # (H, N)
        S = (jnp.exp(d_t * A)[:, None, None] * S
             + (d_t[:, None] * B_h)[:, :, None] * x_t[:, None, :])
        return S, P.einsum("hn,hnp->hp", C_h, S, precision) + D[:, None] * x_t

    @jax.checkpoint
    def run(S, inputs):
        return jax.lax.scan(step, S, inputs)

    cut = lambda a: a.reshape(T // block, block, *a.shape[1:])
    _, y = jax.lax.scan(run, jnp.zeros((H, N, Pd), x.dtype),
                        (cut(x), cut(delta), cut(B), cut(C)))
    return y.reshape(T, H, Pd)


def mamba(p, u, cfg, precision):
    """u: (T, d) of one sequence -> (T, d): the Mamba-2 mixer."""
    T = u.shape[0]
    H, Pd, N, G = (cfg["mamba_num_heads"], cfg["mamba_head_dim"], cfg["ssm_state_size"],
                   cfg["n_groups"])
    d_in, gn = H * Pd, G * N
    zxbcdt = P.matmul(u, p["in_proj"]["weight"], precision)
    z, xbc, dt = jnp.split(zxbcdt, [d_in, 2 * d_in + 2 * gn], axis=-1)
    taps = p["conv1d"]["weight"]                    # (L, channels), a tap a row
    L = taps.shape[0]
    g = jnp.concatenate([jnp.zeros((L - 1, xbc.shape[1]), xbc.dtype), xbc])
    xbc = jax.nn.silu(sum(taps[k] * g[k:k + T] for k in range(L)) + p["conv1d"]["bias"])
    x, B, C = jnp.split(xbc, [d_in, d_in + gn], axis=-1)
    delta = jax.nn.softplus(dt + p["dt_bias"])
    y = ssm_scan(x.reshape(T, H, Pd), delta, -jnp.exp(p["A_log"]), B.reshape(T, G, N),
                 C.reshape(T, G, N), p["D"], precision)
    y = y.reshape(T, d_in) * jax.nn.silu(z)
    parts = y.reshape(T, G, d_in // G)
    parts = parts * jax.lax.rsqrt(jnp.mean(parts * parts, -1, keepdims=True) + cfg["norm_eps"])
    return P.matmul(parts.reshape(T, d_in) * p["norm"]["weight"], p["out_proj"]["weight"],
                    precision)


def attention(p, x, cfg, precision):
    """x: (T, d) of one sequence -> (T, d): causal, grouped, no positions."""
    T, D = x.shape[0], cfg["head_dim"]
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    group = heads // kv
    q = P.matmul(x, p["q_proj"]["weight"], precision).reshape(T, heads, D)
    k = P.matmul(x, p["k_proj"]["weight"], precision).reshape(T, kv, D)
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


def relu2_mlp(x, w_in, w_out, precision):
    """``w_in`` (hidden, d), ``w_out`` (d, hidden) — (out, in) weights."""
    return P.matmul(jnp.square(jax.nn.relu(P.matmul(x, w_in, precision))), w_out, precision)


def scores(p, x, precision):
    """x: (T, d) -> (T, E): the router's sigmoid scores over all published experts."""
    router_precision = "float32" if precision == "float32" else "bfloat16"
    return jax.nn.sigmoid(P.matmul(x, p["router"].T, router_precision))


def route(p, x, cfg, precision):
    """x: (T, d) -> each token's weights and experts, (T, k) both: sigmoid
    scores over all published experts in float32 (one precision down: bfloat16
    operands); the k largest of score + bias; the chosen scores over their sum,
    times the scaling factor."""
    s = scores(p, x, precision)
    _, chosen = jax.lax.top_k(jax.lax.stop_gradient(s + p["expert_bias"]),
                              cfg["num_experts_per_tok"])
    top = jnp.take_along_axis(s, chosen, -1)
    w = top / (jnp.sum(top, -1, keepdims=True) + RENORM_EPS)
    return cfg["routed_scaling_factor"] * w, chosen


def experts(p, x, cfg, precision):
    """x: (T, d).  The experts held run one at a time over all tokens, each
    weighted by what the tokens that chose it gave it; the shared expert once."""
    held, start = p["w_in"].shape[0], cfg.get("experts_held_start", 0)
    w, idx = route(p, x, cfg, precision)
    t = lambda a: jnp.swapaxes(a, -1, -2)           # (in, out) -> (out, in)

    @jax.checkpoint
    def one(y, e):
        weight = jnp.sum(jnp.where(idx == start + e, w, 0.0), -1)
        return y + weight[:, None] * relu2_mlp(x, t(p["w_in"][e]), t(p["w_out"][e]),
                                               precision), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(x), jnp.arange(held))
    return y + relu2_mlp(x, t(p["shared"]["w_in"]), t(p["shared"]["w_out"]), precision)


def mixer(lp, u, cfg, kind, precision):
    if kind == "mamba":
        return mamba(lp["mamba"], u, cfg, precision)
    if kind == "attention":
        return attention(lp["self_attn"], u, cfg, precision)
    return experts(lp["mlp"], u, cfg, precision)


def hidden(p, ids, cfg, precision):
    """(T,) ids of one sequence -> (T, d) after the final norm."""
    eps = cfg["norm_eps"]
    x = p["embed_tokens"]["weight"][ids]
    for i, letter in enumerate(cfg["hybrid_override_pattern"]):
        @jax.checkpoint
        def layer(lp, x, kind=KINDS[letter]):
            return x + mixer(lp, rms_norm(x, lp["input_layernorm"]["weight"], eps), cfg, kind,
                             precision)

        x = layer(p["layers"][str(i)], x)
    return rms_norm(x, p["norm"]["weight"], eps)


def expert_loads(s, bias, k):
    """Rows each of the E experts gets where every row of the scores ``s``
    (rows, E) takes the ``k`` largest of score + bias."""
    _, chosen = jax.lax.top_k(s + bias, k)
    return jnp.zeros(s.shape[-1], jnp.float32).at[chosen.reshape(-1)].add(1.0)


def balance_bias(s, bias, k):
    """The family's balancing rule on one batch's scores ``s`` (rows, E), from
    ``bias`` (E,): ``bias_e += u * sign(mean load - load_e)``, ``BALANCE["steps"]``
    times, ``u`` falling geometrically from ``BALANCE["first"]`` to
    ``BALANCE["last"]``."""
    steps, first, last = BALANCE["steps"], BALANCE["first"], BALANCE["last"]
    ratio = (last / first) ** (1.0 / (steps - 1))

    def step(i, b):
        n = expert_loads(s, b, k)
        return b + first * ratio ** i * jnp.sign(jnp.mean(n) - n)

    return jax.lax.fori_loop(0, steps, step, bias)


def routing(params, ids, cfg, balance=False):
    """(B, T) ids through the blocks in float32, and what every expert layer's
    router does with them: a row a layer of ``bias`` (the selection bias the
    stream went on with), ``loads`` (rows each published expert got under it),
    ``drawn_loads`` (under the bias as the tree holds it), ``common`` and
    ``specific`` (the norm of the mean normed state, which every token shares,
    and the root mean square distance from it).  Where ``balance``, each layer's
    bias is first balanced on this batch (``balance_bias``) from the tree's own,
    layer after layer, so that a later layer reads the stream the balanced
    earlier ones leave; else ``bias`` is the tree's."""
    eps, k = cfg["norm_eps"], cfg["num_experts_per_tok"]
    embed = P.to_f32(params["embed_tokens"]["weight"])
    xs = [embed[row] for row in ids]
    out = []
    for i, letter in enumerate(cfg["hybrid_override_pattern"]):
        lp = P.to_f32(params["layers"][str(i)])
        us = [rms_norm(x, lp["input_layernorm"]["weight"], eps) for x in xs]
        if KINDS[letter] == "moe":
            every = jnp.concatenate(us)
            s, drawn = scores(lp["mlp"], every, "float32"), lp["mlp"]["expert_bias"]
            bias = balance_bias(s, drawn, k) if balance else drawn
            lp = {**lp, "mlp": {**lp["mlp"], "expert_bias": bias}}
            mean = jnp.mean(every, 0)
            out.append({"layer": i, "bias": bias, "loads": expert_loads(s, bias, k),
                        "drawn_loads": expert_loads(s, drawn, k),
                        "common": jnp.linalg.norm(mean),
                        "specific": jnp.sqrt(jnp.mean(jnp.sum((every - mean) ** 2, -1)))})
        xs = [x + mixer(lp, u, cfg, KINDS[letter], "float32") for x, u in zip(xs, us)]
    return out


def logits(p, ids, cfg, precision="float32"):
    """(B, T) ids -> (B, T, V) over the vocabulary slice held."""
    return jnp.stack([P.matmul(hidden(p, row, cfg, precision), p["lm_head"]["weight"], precision)
                      for row in ids])


def summed_nll(p, ids, cfg, precision="float32"):
    """Sum over the rows of ``ids`` and every position but the last of the
    next token's negative log-likelihood, the head ``HEAD_BLOCK`` rows at a time."""
    total = jnp.float32(0)
    for row in ids:
        T = row.shape[0]
        h = hidden(p, row, cfg, precision)
        labels = jnp.concatenate([row[1:], jnp.zeros((1,), row.dtype)])
        counted = jnp.arange(T) < T - 1
        block = math.gcd(T, HEAD_BLOCK)

        @jax.checkpoint
        def nll(hb, lb, cb):
            logp = jax.nn.log_softmax(P.matmul(hb, p["lm_head"]["weight"], precision), -1)
            return -jnp.sum(jnp.take_along_axis(logp, lb[:, None], -1)[:, 0] * cb)

        cut = lambda a: a.reshape(T // block, block, *a.shape[1:])
        total = total + jnp.sum(jax.lax.map(lambda a: nll(*a), (cut(h), cut(labels),
                                                                cut(counted))))
    return total


def train(params, batches, cfg, groups=1, block_rows=1, precision="float32",
          param_dtype="float32", hp=ADAM, keep=False):
    """Follow the first ``len(batches)`` steps from the seeded weights.
    Returns each step's loss (the mean over the rows' positions), the per-leaf
    norm and projections of the first gradient, and those of the parameters'
    change after the last step.  Gradients are accumulated over blocks of
    ``block_rows`` rows so that it fits.  **The caller gives ``params`` up**, as
    ``runners/train_causal_lm.py`` does: its device buffers are freed once they
    are copied (the steps of the controls do not fit the chip beside them:
    1.3 GB) and the seeded values wait on the host for the last comparison.
    ``keep``: the caller goes on using ``params`` (the tests' tiny trees)."""
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
        norms = leaf_norms(g)
        p, m, v = adam_update(p, m, v, g, t, hp, param_dtype)
        return p, m, v, loss, norms

    # 667 M parameters in float32 are 2.7 GB a copy: the seeded weights stay in the type they
    # came in and are widened again where they are compared
    p = jax.tree_util.tree_map(lambda x: jnp.array(x, jnp.float32), params)      # a copy: step donates
    if not keep:
        seeded = jax.device_get(params)
        for leaf in jax.tree_util.tree_leaves(params):
            leaf.delete()
        params = seeded
    m = jax.tree_util.tree_map(jnp.zeros_like, p)
    v = jax.tree_util.tree_map(jnp.zeros_like, p)
    losses, first_grad = [], None
    for t, (ids,) in enumerate(batches, start=1):
        p, m, v, loss, gn = step(p, m, v, jnp.float32(t), jnp.asarray(ids))
        losses.append(float(loss))
        if t == 1:
            first_grad = np.asarray(gn)
    del m, v                        # 5.3 GB the comparison below does not need
    change = np.asarray(jax.jit(lambda p, p0: leaf_norms(jax.tree_util.tree_map(
        jnp.subtract, p, P.to_f32(p0))))(p, params))
    return {"losses": losses, "first_grad_norms": first_grad, "update_norms": change}
