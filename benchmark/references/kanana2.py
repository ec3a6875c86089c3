"""Next-token pretraining of the ``deepseek_v3`` decoder in plain jax.numpy and
float32, from the published config's keys
(https://huggingface.co/kakaocorp/kanana-2-30b-a3b-instruct-2601/blob/main/config.json):
the forward pass, the loss over the held vocabulary slice, its gradient and
Adam, with no kernel, no policy and no code of ``apex_tpu``.  It reads a
parameter tree in the program's layout (torch-style (out, in) ``weight``
leaves; an expert layer's ``router`` (d, E), ``expert_bias`` (E,),
``w_gate``/``w_in`` (n, d, h), ``w_out`` (n, h, d) and ``shared``) that the
benchmark made from the seed.  The pieces that are the same mathematics in every
such decoder (RMSNorm, SwiGLU, the RoPE tables and rotate-half, Adam) are
``references/laguna.py``'s, the leaf rows with their random projections
``references/lfm2.py``'s and their comparison ``references/mellum2.py``'s; the
latent attention, the expert layer, the model and the limits are this file's.

The layer equations (pre-norm, RMSNorm eps ``rms_norm_eps``, no biases; ``u``
the normed input of a sub-block):

    a = h + Attn_l(RMSNorm(h));  h' = a + MLP_l(RMSNorm(a));  final RMSNorm; head

``Attn_l``, multi-head latent attention (H heads, ``d_n = qk_nope_head_dim``,
``d_r = qk_rope_head_dim``, ``d_v = v_head_dim``, ``r = kv_lora_rank``):
``[q_n,i | q_r,i] = u W_q`` a head; ``[c | k_r] = u W_kva``, ``k_r`` ONE head for
all query heads; ``[k_n,i | v_i] = RMSNorm_c(c) W_kvb``; ``q_r,i`` and ``k_r``
rotated at ``rope_theta`` on interleaved pairs (``rope_interleave``), computed as
``transformers`` does: de-interleave (evens, then odds), then rotate halves, the
same permutation on both sides of every score; scores
``(q_n,i . k_n,i + q_r,i . k_r) / sqrt(d_n + d_r)``, causal (``rope_scaling``
null: no scale on cos, sin or the softmax); ``o_i = P_i v_i``; out
``[o_1 .. o_H] W_o``.  K and V are materialized from the latent head by head.
``MLP_l``, ``l < first_k_dense_replace``: SwiGLU of ``intermediate_size``.
``MLP_l``, the others: ``s = sigmoid(u W_r)`` in float32 over all published
experts; the choice is the ``num_experts_per_tok`` largest of ``s + b``
(``expert_bias``; ``n_group`` 1, ``topk_group`` 1: one group, no limit; the bias
enters the choice only and takes no gradient);
``w = routed_scaling_factor * s[choice] / (sum s[choice] + 1e-20)``;
``sum_k w_k SwiGLU_{e_k}(u) + Shared(u)``, ``Shared`` one SwiGLU of
``n_shared_experts * moe_intermediate_size``.

DEPARTURE from the published layout, none from its mathematics: the tree holds
``W_q``'s columns sorted by part, ``q_nope_proj`` (H d_n, d) and ``q_rope_proj``
(H d_r, d), head ``i`` of each head ``i``'s part, ``W_kvb``'s as ``k_up_proj`` /
``v_up_proj`` and ``W_kva``'s as ``kv_down_proj`` (r, d) / ``k_rope_proj`` (d_r, d)
likewise (``apex_tpu/transformer/mla.py`` has why); :func:`attention` joins a
head's parts again.

A chip's share (configs/kanana-2-30b-a3b.json: ``deployment``): the tree holds
``n_routed_experts`` experts from ``experts_held_start``; the router and its
bias keep all ``num_experts_published``; an assignment to an expert held
elsewhere adds nothing.  The vocabulary is the slice the tree holds.

Assumed, each the configuration's own (``assumed`` in that file): no auxiliary
loss; a bias that the seed draws and nothing updates but Adam's decoupled
decay; the 1e-20 of the renormalization (the family's code); Adam as apex's
FusedAdam defines it.

To fit 8k sequences in float32 on one chip: the rows go one after another,
each layer is recomputed in the backward pass, attention runs in blocks of
``QUERY_BLOCK`` queries against all keys (the scores as two products, a head's
own 128 and the one rotated key head's 64: K's two parts are never joined), the
experts one at a time over all tokens and the head ``HEAD_BLOCK`` rows at a
time; ``train`` takes the seeded tree from its caller (``keep`` says otherwise).

``routing(balance=True)`` is ``references/nemotron3.py``'s balancing rule on
this family's expert layers (the family moves the selection bias by that rule
during training): what ``runners/train_balanced_lm.py`` balances the seeded
bias with, on the seed's first batch, before the program or this reference
reads the weights.

LIMITS: what the timed path may differ by, and why; set from chip readings at
the cell's own size (PERF.md, "Limits of correct"; PR 48's call of 17:03 UTC,
``tools/kanana2_limits.py``, ``tools/kanana2_faults.py`` and three runs of the
cell: seven sound seeds, both controls on three, both planted faults on one),
each midway by ratio between the largest a sound bf16 run gave and the smallest
that what it has to refuse gave; ``loss_gap``, which no control parts, is the
accepted decoder cells'.
"""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from . import _precision as P
from .laguna import ADAM, adam_update, apply_rope, rms_norm, rope_tables, swiglu  # noqa: F401
from .lfm2 import leaf_norms  # noqa: F401
from .nemotron3 import BALANCE, balance_bias, expert_loads  # noqa: F401
from .mellum2 import compare as compare_leaves, difference_norms, leaf_differences  # noqa: F401

# number -> limit.  Readings they were set from, on the chip at the cell's own size (PR 48;
# PERF.md, "Limits of correct"): the largest of seven sound seeds / the smallest of the
# fp8-compute control's three / of the bf16-parameter control's three / the key head
# unrotated / its gradient from one grid step's half and not the sum.
LIMITS = {
    "loss_gap": 1.35e-3,            # 3.5e-4 / 2.8e-4 / 2.5e-4 / 2.2e-3 / 1.4e-4: parted by no control
    "grad_diff_mean": 0.234,        # 0.101 / 0.5405 / 0 / 0.775 / 0.230
    "grad_norm_own_worst": 0.122,   # 0.0197 / 0.0433 / 0 / 0.058 / 0.761: the sum over the heads
    "grad_diff_own_5th": 0.58,      # 0.3305 / 1.037 / 0 / 1.626 / 1.012
    "update_norm_gap": 1.08e-2,     # 5.6e-4 / 8.4e-4 / 0.2089 / 1.5e-3 / 2.0e-3
}
RENORM_EPS = 1e-20
QUERY_BLOCK = 128           # query rows of scores at a time: (heads, 128, T) float32
HEAD_BLOCK = 1024           # rows of logits at a time


OWN_RANK = 5    # the mechanism's own leaves come one a layer, and the cut has five layers


def compare(program: dict, reference: dict) -> dict:
    """``references/mellum2.py``'s numbers and two more of the first gradient,
    each leaf against ITS OWN reference norm (a leaf without a gradient, the
    selection bias, left out).  ``grad_norm_gap`` and ``grad_diff_mean`` weigh a
    leaf by the median leaf's norm where its own is smaller, and the projections
    of the rotated parts (``q_rope_proj``, ``k_rope_proj``: their whole gradient
    comes through near-uniform scores) are such leaves: a gradient there that is
    wrong by its own size moves those by a hundredth.

    - ``grad_norm_own_worst``: the worst leaf's gap between the two norms.  A
      leaf's length is steadier under rounding than the leaf (an error of e of
      its length adds e^2 / 2 to it), so a sound run's worst stays small; the
      one key head's gradient taken from one query head and not the sum is
      short by most of its length.
    - ``grad_diff_own_5th``: the ``OWN_RANK``-th largest of the leaves'
      estimated norm of (program - reference).  The routers' few leaves differ
      by much in a sound run (a rounding flips choices); a key head left
      unrotated turns one leaf a layer, and its query twin, into another
      gradient of the same length, which only a difference sees."""
    out = compare_leaves(program, reference)
    got, want = (np.asarray(x["first_grad_norms"]) for x in (program, reference))
    has, own = want[:, 0] > 0, np.maximum(want[:, 0], 1e-30)
    gap = np.where(has, np.abs(got[:, 0] - want[:, 0]) / own, 0.0)
    diff = np.where(has, np.sqrt(np.mean(np.square(got[:, 1:] - want[:, 1:]), axis=1)) / own, 0.0)
    out.update(grad_norm_own_worst=float(gap.max()), grad_norm_own_leaf=int(gap.argmax()),
               grad_diff_own_5th=float(np.sort(diff)[-OWN_RANK]),
               grad_diff_own_worst_leaf=int(diff.argmax()))
    return out


def rope_pairs(x, cos, sin):
    """x: (T, heads, d_r), interleaved pairs -> rotated, as ``transformers``
    computes it for ``rope_interleave``: evens then odds, then rotate-half.
    The result's numbers are in that order; q and k both are, so every score is
    the one of the pairwise rotation."""
    T, heads, d = x.shape
    x = jnp.swapaxes(x.reshape(T, heads, d // 2, 2), -1, -2).reshape(T, heads, d)
    return apply_rope(x, cos, sin)


def attention(p, x, cfg, precision):
    """x: (T, d) of one sequence -> (T, d)."""
    T, H = x.shape[0], cfg["num_attention_heads"]
    dn, dr, dv = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    eps = cfg["rms_norm_eps"]
    cos, sin = rope_tables({"rope_theta": cfg["rope_theta"]}, dr, T)
    q_n = P.matmul(x, p["q_nope_proj"]["weight"], precision).reshape(T, H, dn)
    q_r = P.matmul(x, p["q_rope_proj"]["weight"], precision).reshape(T, H, dr)
    c = rms_norm(P.matmul(x, p["kv_down_proj"]["weight"], precision), p["kv_norm"]["weight"], eps)
    k_r = P.matmul(x, p["k_rope_proj"]["weight"], precision)
    k_n = P.matmul(c, p["k_up_proj"]["weight"], precision).reshape(T, H, dn)
    v = P.matmul(c, p["v_up_proj"]["weight"], precision).reshape(T, H, dv)
    q_r, k_r = rope_pairs(q_r, cos, sin), rope_pairs(k_r[:, None], cos, sin)[:, 0]
    bq = min(QUERY_BLOCK, T)
    assert T % bq == 0, (T, bq)

    @jax.checkpoint
    def block(start):
        cut = lambda a: jax.lax.dynamic_slice_in_dim(a, start, bq)
        # a head's own 128 against its own keys, its rotated 64 against the one key head
        s = (P.einsum("qhd,shd->hqs", cut(q_n), k_n, precision)
             + P.einsum("qhd,sd->hqs", cut(q_r), k_r, precision)) / math.sqrt(dn + dr)
        see = jnp.arange(T)[None, :] <= start + jnp.arange(bq)[:, None]
        a = jax.nn.softmax(jnp.where(see, s, -jnp.inf), -1)
        return P.einsum("hqs,shd->qhd", a, v, precision).reshape(bq, H * dv)

    ctx = jax.lax.map(block, jnp.arange(0, T, bq)).reshape(T, H * dv)
    return P.matmul(ctx, p["o_proj"]["weight"], precision)


def scores(p, x, precision):
    """x: (T, d) -> the router's sigmoid scores over all published experts."""
    router_precision = "float32" if precision == "float32" else "bfloat16"
    return jax.nn.sigmoid(P.matmul(x, p["router"].T, router_precision))


def route(p, x, cfg, precision):
    """x: (T, d) -> each token's weights and experts, (T, k) both."""
    s = scores(p, x, precision)
    _, chosen = jax.lax.top_k(jax.lax.stop_gradient(s + p["expert_bias"]),
                              cfg["num_experts_per_tok"])
    top = jnp.take_along_axis(s, chosen, -1)
    w = top / (jnp.sum(top, -1, keepdims=True) + RENORM_EPS)
    return cfg["routed_scaling_factor"] * w, chosen


def sparse_mlp(p, x, cfg, precision, shared=True):
    """x: (T, d).  The experts held run one at a time over all tokens, each
    weighted by what the tokens that chose it gave it; ``shared``: with the
    shared experts (every chip of a group computes them alike)."""
    held, start = p["w_in"].shape[0], cfg.get("experts_held_start", 0)
    w, idx = route(p, x, cfg, precision)
    t = lambda a: jnp.swapaxes(a, -1, -2)           # (in, out) -> (out, in)

    @jax.checkpoint
    def one(e):         # recomputed in the backward pass from the expert's number alone
        weight = jnp.sum(jnp.where(idx == start + e, w, 0.0), -1)
        out = swiglu(x, t(p["w_gate"][e]), t(p["w_in"][e]), t(p["w_out"][e]), precision)
        return weight[:, None] * out

    y, _ = jax.lax.scan(lambda y, e: (y + one(e), None), jnp.zeros_like(x), jnp.arange(held))
    if not shared:
        return y
    sh = p["shared"]
    return y + swiglu(x, t(sh["w_gate"]), t(sh["w_in"]), t(sh["w_out"]), precision)


def sparse(cfg, i):
    return i >= cfg["first_k_dense_replace"] and i % cfg.get("moe_layer_freq", 1) == 0


def layer(lp, x, cfg, i, precision):
    """One block on one sequence's (T, d) stream."""
    eps = cfg["rms_norm_eps"]
    x = x + attention(lp["self_attn"], rms_norm(x, lp["input_layernorm"]["weight"], eps), cfg,
                      precision)
    h = rms_norm(x, lp["post_attention_layernorm"]["weight"], eps)
    if sparse(cfg, i):
        return x + sparse_mlp(lp["mlp"], h, cfg, precision)
    m = lp["mlp"]
    return x + swiglu(h, m["gate_proj"]["weight"], m["up_proj"]["weight"],
                      m["down_proj"]["weight"], precision)


def hidden(p, ids, cfg, precision, after=0.0):
    """(T,) ids of one sequence -> (T, d) after the final norm.  ``after``: a
    number this sequence waits for and does not read (``summed_nll``)."""
    x = p["embed_tokens"]["weight"][ids] + 0.0 * after
    for i in range(cfg["num_hidden_layers"]):
        x = jax.checkpoint(lambda lp, x, i=i: layer(lp, x, cfg, i, precision))(
            p["layers"][str(i)], x)
    return rms_norm(x, p["norm"]["weight"], cfg["rms_norm_eps"])


def routing(params, ids, cfg, balance=False):
    """(B, T) ids through the blocks in float32, and what every expert layer's
    router does with them: a row a layer of ``bias`` (the selection bias the
    stream went on with), ``loads`` (rows each published expert got under it)
    and ``drawn_loads`` (under the bias as the tree holds it).  Where
    ``balance``, each layer's bias is first balanced on this batch
    (``references/nemotron3.balance_bias``) from the tree's own, layer after
    layer, so that a later layer reads the stream the balanced earlier ones
    leave; else ``bias`` is the tree's."""
    eps, k = cfg["rms_norm_eps"], cfg["num_experts_per_tok"]
    xs = [P.to_f32(params["embed_tokens"]["weight"])[row] for row in ids]
    out = []
    for i in range(cfg["num_hidden_layers"]):
        lp = P.to_f32(params["layers"][str(i)])
        if sparse(cfg, i):
            # what the router reads: the stream after this block's attention, normed
            after = [x + attention(lp["self_attn"], rms_norm(
                x, lp["input_layernorm"]["weight"], eps), cfg, "float32") for x in xs]
            every = jnp.concatenate([rms_norm(x, lp["post_attention_layernorm"]["weight"], eps)
                                     for x in after])
            s, drawn = scores(lp["mlp"], every, "float32"), lp["mlp"]["expert_bias"]
            bias = balance_bias(s, drawn, k) if balance else drawn
            lp = {**lp, "mlp": {**lp["mlp"], "expert_bias": bias}}
            out.append({"layer": i, "bias": bias, "loads": expert_loads(s, bias, k),
                        "drawn_loads": expert_loads(s, drawn, k)})
        xs = [layer(lp, x, cfg, i, "float32") for x in xs]
    return out


def logits(p, ids, cfg, precision="float32"):
    """(B, T) ids -> (B, T, V) over the vocabulary slice held."""
    return jnp.stack([P.matmul(hidden(p, row, cfg, precision), p["lm_head"]["weight"], precision)
                      for row in ids])


def summed_nll(p, ids, cfg, precision="float32"):
    """Sum over the rows of ``ids`` and every position but the last of the
    next token's negative log-likelihood, the head ``HEAD_BLOCK`` rows at a
    time.  A row starts from the sum so far times zero: nothing of the
    mathematics, and the rows, forward and backward, then run one after another
    instead of side by side, which is what fits the chip."""
    total = jnp.float32(0)
    for row in ids:
        T = row.shape[0]
        h = hidden(p, row, cfg, precision, after=total)
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


def make_steps(cfg, block_rows=1, precision="float32", param_dtype="float32", hp=ADAM):
    """One step of ``train`` as two programs, so that Adam's moments need not be
    on the chip while the gradient is computed (parameters, moments, a gradient
    and a row's activations in float32 are more than the chip holds):
    ``grads(p, ids) -> (loss, g, the gradient's rows)``, the blocks of
    ``block_rows`` rows adding into one tree, and ``update(p, m, v, g, t) ->
    (p, m, v)``, all four donated."""
    @jax.jit
    def grads(p, ids):
        rows, T = ids.shape
        scale = 1.0 / (rows * (T - 1))
        loss, g = jnp.float32(0), None
        for block in ids.reshape(rows // block_rows, block_rows, T):
            l, gb = jax.value_and_grad(lambda q: scale * summed_nll(q, block, cfg, precision))(p)
            loss = loss + l
            g = gb if g is None else jax.tree_util.tree_map(jnp.add, g, gb)
        return loss, g, leaf_norms(g)

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2, 3))
    def update(p, m, v, g, t):
        return adam_update(p, m, v, g, t, hp, param_dtype)

    return grads, update


def train(params, batches, cfg, groups=1, block_rows=1, precision="float32",
          param_dtype="float32", hp=ADAM, keep=False):
    """Follow the first ``len(batches)`` steps from the seeded weights.
    Returns each step's loss (the mean over the rows' positions), the per-leaf
    norm and projections of the first gradient, and those of the parameters'
    change after the last step.  **The caller gives ``params`` up**, as
    ``runners/train_causal_lm.py`` does: its device buffers are freed once they
    are copied and the seeded values wait on the host for the last comparison
    (``references/nemotron3.py`` has why); Adam's moments wait on the host too
    while a gradient is computed.  ``keep``: the caller goes on using ``params``
    (the tests' tiny trees)."""
    del groups                      # every row is full: a mean over chips is the mean over all
    grads, update = make_steps(cfg, block_rows, precision, param_dtype, hp)
    p = jax.tree_util.tree_map(lambda x: jnp.array(x, jnp.float32), params)      # a copy: update donates
    if not keep:
        seeded = jax.device_get(params)
        for leaf in jax.tree_util.tree_leaves(params):
            leaf.delete()
        params = seeded
    zeros = lambda: jax.tree_util.tree_map(lambda x: np.zeros(x.shape, np.float32), p)
    m, v = zeros(), zeros()         # on the host between the updates
    losses, first_grad = [], None
    for t, (ids,) in enumerate(batches, start=1):
        loss, g, gn = grads(p, jnp.asarray(ids))
        losses.append(float(loss))
        if t == 1:
            first_grad = np.asarray(gn)
        p, m, v = update(p, jax.device_put(m), jax.device_put(v), g, jnp.float32(t))
        del g
        if t < len(batches):
            m, v = (jax.device_get(x) for x in (m, v))
    del m, v
    change = np.asarray(jax.jit(lambda p, p0: leaf_norms(jax.tree_util.tree_map(
        jnp.subtract, p, P.to_f32(p0))))(p, params))
    return {"losses": losses, "first_grad_norms": first_grad, "update_norms": change}
