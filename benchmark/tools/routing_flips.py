#!/usr/bin/env python3
"""Why the first gradient of a sound bf16 run differs from the float32
reference's by half its norm in ``mellum2-12b`` (``grad_diff_mean``,
references/mellum2.py), shown on the plain reference alone: its gradient with
matmul operands rounded to a lower precision, against its float32 gradient,
(a) with the experts the rounded run picks itself and (b) with the float32
run's choice given to it.  Beside them the assignments the rounding flipped,
layer by layer, and what ``grad_diff_mean`` reads of a planted fault (one
layer's expert gradients with the wrong sign).

    python benchmark/tools/routing_flips.py --config benchmark/configs/mellum2-12b.json \\
        --seed 3100000201 --seq-len 2048 --vocab 4096

It runs wherever JAX does: on the CPU at a shorter row and a smaller vocabulary
slice (minutes).  What it prints are counts and ratios of the reference's own
gradients, never a device metric, and no limit of ``correct`` is set from it.
"""

import argparse
import contextlib
import functools
import importlib
import json
import os
import sys

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
KINDS = {"experts": ("w_gate", "w_in", "w_out"), "router": ("router",),
         "attention": ("_proj",), "norms": ("norm",), "embedding and head": ("embed", "lm_head")}


def shapes(cfg):
    """The parameter tree of the reference, in the program's layout."""
    import jax
    import jax.numpy as jnp
    f = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32)
    d, v, hd = cfg["hidden_size"], cfg["vocab_size"], cfg["head_dim"]
    q, kv = cfg["num_attention_heads"] * hd, cfg["num_key_value_heads"] * hd
    n, h = cfg["num_experts"], cfg["moe_intermediate_size"]
    layer = {"input_layernorm": {"weight": f(d)}, "post_attention_layernorm": {"weight": f(d)},
             "self_attn": {"q_proj": {"weight": f(q, d)}, "k_proj": {"weight": f(kv, d)},
                           "v_proj": {"weight": f(kv, d)}, "o_proj": {"weight": f(d, q)}},
             "mlp": {"router": f(d, cfg["num_experts_published"]), "w_gate": f(n, d, h),
                     "w_in": f(n, d, h), "w_out": f(n, h, d)}}
    return {"embed_tokens": {"weight": f(v, d)}, "norm": {"weight": f(d)},
            "lm_head": {"weight": f(v, d)},
            "layers": {str(i): layer for i in range(cfg["num_hidden_layers"])}}


def chosen_experts(ref, p, ids, cfg, precision):
    """Each layer's (T, k) chosen experts along the forward pass of one row."""
    import jax
    from references.laguna import rms_norm

    @functools.partial(jax.jit, static_argnums=2)
    def layer(lp, x, kind):
        x = x + ref.attention(lp["self_attn"], rms_norm(x, lp["input_layernorm"]["weight"],
                                                         cfg["rms_norm_eps"]), cfg, kind, precision)
        h = rms_norm(x, lp["post_attention_layernorm"]["weight"], cfg["rms_norm_eps"])
        chosen = ref.route(lp["mlp"], h, cfg, precision)[1]
        return x + ref.sparse_mlp(lp["mlp"], h, cfg, precision), chosen

    x, out = p["embed_tokens"]["weight"][ids], []
    for i in range(cfg["num_hidden_layers"]):
        x, idx = layer(p["layers"][str(i)], x, cfg["layer_types"][i])
        out.append(idx)
    return out


@contextlib.contextmanager
def given(ref, chosen):
    """The reference's router handed each layer's experts, in layer order."""
    rest, real = iter(chosen), ref.route
    ref.route = lambda p, x, cfg, precision: real(p, x, cfg, precision, chosen=next(rest))
    try:
        yield
    finally:
        ref.route = real
    assert next(rest, None) is None, "a layer was traced without its experts"


def first_gradient(ref, p, ids, cfg, precision):
    """One row per leaf: norm and projections (``leaf_norms``) of the mean loss's gradient."""
    import jax
    import numpy as np
    scale = 1.0 / (ids.shape[0] * (ids.shape[1] - 1))
    grad = jax.jit(jax.grad(lambda q, rows: scale * ref.summed_nll(q, rows, cfg, precision)))
    return np.asarray(ref.leaf_norms(grad(p, ids)))


def reading(ref, rows, want, names):
    import numpy as np
    rel = ref.leaf_differences(rows, want)
    worst, leaf, mean = ref.difference_norms(rows, want)
    by_kind = {kind: float(np.mean([min(r, 1.0) for r, name in zip(rel, names)
                                    if any(tag in name for tag in tags)]))
               for kind, tags in KINDS.items()}
    return {"grad_diff_mean": mean, "median_leaf": float(np.median(rel)), "worst_leaf": worst,
            "worst_leaf_name": names[leaf], "mean_by_kind": by_kind}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seq-len", type=int, default=2048)
    ap.add_argument("--vocab", type=int, default=4096)
    ap.add_argument("--precisions", nargs="+", default=["bfloat16", "fp8"])
    ap.add_argument("--init-std", type=float, default=None, help="default: the configuration's")
    args = ap.parse_args()
    sys.path.insert(0, BENCH_DIR)
    import jax
    import numpy as np
    from lib import weights
    from runners.train_causal_lm import causal_lm_batch
    with open(args.config) as f:
        cfg = dict(json.load(f), vocab_size=args.vocab)
    ref = importlib.import_module("references." + cfg["reference"])
    tree = shapes(cfg)
    names = [jax.tree_util.keystr(path)
             for path, _ in jax.tree_util.tree_flatten_with_path(tree)[0]]
    p = weights.make_weights(tree, args.seed, args.init_std or cfg["init_std"])
    (ids,) = causal_lm_batch({"seq_len": args.seq_len}, args.seed, 0, 1, args.vocab)
    ids = jax.numpy.asarray(ids)
    full_choice = chosen_experts(ref, p, ids[0], cfg, "float32")
    want = first_gradient(ref, p, ids, cfg, "float32")
    # how even the float32 routing is: rows of each held expert (T * k / published expected)
    loads = [np.bincount(np.asarray(idx).ravel(), minlength=cfg["num_experts_published"])
             [cfg.get("experts_held_start", 0):][:cfg["num_experts"]] for idx in full_choice]
    print(json.dumps({"backend": jax.default_backend(), "seed": args.seed, "seq_len": args.seq_len,
                      "vocab": args.vocab, "init_std": args.init_std or cfg["init_std"],
                      "leaves": len(names),
                      "assignments_held_by_layer": [int(l.sum()) for l in loads],
                      "fullest_held_expert_rows_by_layer": [int(l.max()) for l in loads],
                      "reference_norms": {n: float(w) for n, w in zip(names, want[:, 0])
                                          if "q_proj" in n}}), flush=True)
    experts = [i for i, n in enumerate(names) if any(t in n for t in KINDS["experts"])
               and f"['{cfg['num_hidden_layers'] // 2}']" in n]
    for precision in args.precisions:
        own_choice = chosen_experts(ref, p, ids[0], cfg, precision)
        flips = [float(np.mean(~(np.asarray(a)[:, :, None] == np.asarray(b)[:, None, :]).any(-1)))
                 for a, b in zip(own_choice, full_choice)]
        own = first_gradient(ref, p, ids, cfg, precision)
        with given(ref, full_choice):
            jax.clear_caches()
            same = first_gradient(ref, p, ids, cfg, precision)
        jax.clear_caches()
        planted = own.copy()
        planted[experts, 1:] *= -1.0
        print(json.dumps({
            "precision": precision,
            "assignments_flipped_by_layer": flips,
            "own_choice": reading(ref, own, want, names),
            "float32_choice_given": reading(ref, same, want, names),
            "own_choice_and_one_layers_expert_gradients_negated":
                reading(ref, planted, want, names),
        }), flush=True)


if __name__ == "__main__":
    main()
