#!/usr/bin/env python3
"""The two sweeps behind a sparse decoder's ``init_std``, for a configuration
whose layers are not all attention and whose router has a selection bias
(``lfm2-8b-a1b``): what ``tools/routing_balance.py`` and
``tools/routing_flips.py`` read for the accepted cells, through the blocks'
own token mixer (``conv`` or ``self_attn``) and the reference named by the
configuration's ``reference`` key.

``balance`` (on the chip, at the cell's own size): for each ``--std`` and seed,
one forward pass of the program's model over one batch of the cell's traffic,
and for every expert layer the rows the fullest and the emptiest of all
published experts got, the assignments that fell on the experts held, the
fullest held expert, and how much of the router's input is common to all tokens.

    python benchmark/tools/lfm2_routing.py balance --workload lfm2-8b-a1b.pretrain-8k \\
        --std 0.02 0.03 0.04 0.05 --seeds 3300000001 7

``flips`` (wherever JAX runs; minutes on the CPU at a shorter row and a smaller
vocabulary slice): the plain reference alone, its first gradient with matmul
operands rounded to a lower precision against its float32 gradient
(``grad_diff_mean``), with the experts the rounded run picks and with the
float32 run's choice given to it, and the assignments the rounding flipped.
Counts and ratios of the reference's own gradients, never a device metric.

    python benchmark/tools/lfm2_routing.py flips --config benchmark/configs/lfm2-8b-a1b.json \\
        --seed 3300000201 --seq-len 2048 --vocab 4096 --init-std 0.04
"""

import argparse
import functools
import importlib
import importlib.util
import json
import os
import sys

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


def _flips_module():
    """``tools/routing_flips.py``: its ``given``, ``first_gradient`` and
    ``reading`` serve any reference with a ``route(..., chosen=)``."""
    spec = importlib.util.spec_from_file_location(
        "routing_flips", os.path.join(BENCH_DIR, "tools", "routing_flips.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def shapes(cfg):
    """The parameter tree in the program's layout, float32, from the program's
    own model (the reference reads that layout)."""
    import jax
    from apex_tpu import models
    net = models.Laguna(models.LagunaConfig.from_dict(cfg, remat=None))
    return jax.eval_shape(lambda k: net.init(k)[0], jax.random.PRNGKey(0))


def chosen_experts(ref, p, ids, cfg, precision):
    """Each expert layer's (T, k) chosen experts along the forward pass of one row."""
    import jax
    from references.laguna import rms_norm
    eps = cfg["rms_norm_eps"]

    @functools.partial(jax.jit, static_argnums=(2, 3))
    def layer(lp, x, kind, sparse):
        x = x + ref.operator(lp, rms_norm(x, lp["input_layernorm"]["weight"], eps), cfg, kind,
                             precision)
        h = rms_norm(x, lp["post_attention_layernorm"]["weight"], eps)
        chosen = ref.route(lp["mlp"], h, cfg, precision)[1] if sparse else None
        return x + ref.feed_forward(lp, h, cfg, sparse, precision), chosen

    x, out = p["embed_tokens"]["weight"][ids], []
    for i in range(cfg["num_hidden_layers"]):
        x, idx = layer(p["layers"][str(i)], x, cfg["layer_types"][i],
                       cfg["mlp_layer_types"][i] == "sparse")
        if idx is not None:
            out.append(idx)
    return out


def balance(args):
    sys.path.insert(0, ROOT)
    sys.path.insert(0, BENCH_DIR)
    import jax
    import jax.numpy as jnp
    from apex_tpu import amp, models, optimizers
    from lib import harness, weights
    from runners.train_causal_lm import causal_lm_batch
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        cell = harness.load_cell(json.load(f), args.workload, 0, 1.0, False, ROOT)
    cfg, traffic = cell.config, cell.traffic["params"]
    net = models.Laguna(models.LagunaConfig.from_dict(cfg, remat=None))
    model, _ = amp.initialize(net, optimizers.FusedAdam(lr=1e-4), opt_level="O2", verbosity=0)
    tree = jax.eval_shape(lambda k: model.init(k)[0], jax.random.PRNGKey(0))
    published, held = cfg["num_experts_published"], cfg["num_experts"]
    first = cfg["experts_held_start"]

    def layers(params, ids):
        out = []
        x = net.embed_tokens(params["embed_tokens"], ids)
        for i, block in enumerate(net.layers):
            p = params["layers"][str(i)]
            x = x + getattr(block, block.mixer)(
                p[block.mixer], block.input_layernorm(p["input_layernorm"], x))
            h = block.post_attention_layernorm(p["post_attention_layernorm"], x)
            if block.sparse:
                rows = h.astype(jnp.float32).reshape(-1, h.shape[-1])
                mean = rows.mean(0)
                bias = {"bias": p["mlp"]["expert_bias"]} if block.mlp.router_bias else {}
                _, experts, _ = block.mlp._route(rows, block.mlp._router(p["mlp"]), False, **bias)
                counts = jnp.sum(experts.reshape(-1)[:, None] == jnp.arange(published)[None], 0)
                mine = counts[first:first + held]
                out.append({"layer": i, "load_max": counts.max(), "load_min": counts.min(),
                            "held": mine.sum(), "held_max": mine.max(),
                            "common": jnp.linalg.norm(mean),
                            "specific": jnp.sqrt(jnp.mean(jnp.sum((rows - mean) ** 2, -1)))})
            x = x + block.mlp(p["mlp"], h)
        return out

    forward = jax.jit(layers)
    for std in args.std:
        for seed in args.seeds:
            params = weights.make_weights(tree, seed, std)
            ids, = causal_lm_batch(traffic, seed, 0, cfg["per_chip_batch"], cfg["vocab_size"])
            for rec in jax.device_get(forward(params, jnp.asarray(ids))):
                print(json.dumps({"std": std, "seed": seed, "layer": int(rec.pop("layer")),
                                  **{k: round(float(v), 5) for k, v in rec.items()}}), flush=True)
            del params


def flips(args):
    sys.path.insert(0, ROOT)
    sys.path.insert(0, BENCH_DIR)
    import jax
    import numpy as np
    from lib import weights
    from runners.train_causal_lm import causal_lm_batch
    rf = _flips_module()
    with open(args.config) as f:
        cfg = dict(json.load(f), vocab_size=args.vocab)
    ref = importlib.import_module("references." + cfg["reference"])
    tree = shapes(cfg)
    names = [jax.tree_util.keystr(path)
             for path, _ in jax.tree_util.tree_flatten_with_path(tree)[0]]
    std = args.init_std or cfg["init_std"]
    p = weights.make_weights(tree, args.seed, std)
    (ids,) = causal_lm_batch({"seq_len": args.seq_len}, args.seed, 0, 1, args.vocab)
    ids = jax.numpy.asarray(ids)
    full_choice = chosen_experts(ref, p, ids[0], cfg, "float32")
    want = rf.first_gradient(ref, p, ids, cfg, "float32")
    loads = [np.bincount(np.asarray(idx).ravel(), minlength=cfg["num_experts_published"])
             [cfg.get("experts_held_start", 0):][:cfg["num_experts"]] for idx in full_choice]
    print(json.dumps({"backend": jax.default_backend(), "seed": args.seed, "seq_len": args.seq_len,
                      "vocab": args.vocab, "init_std": std, "leaves": len(names),
                      "assignments_held_by_layer": [int(l.sum()) for l in loads],
                      "fullest_held_expert_rows_by_layer": [int(l.max()) for l in loads]}),
          flush=True)
    for precision in args.precisions:
        own_choice = chosen_experts(ref, p, ids[0], cfg, precision)
        flipped = [float(np.mean(~(np.asarray(a)[:, :, None] == np.asarray(b)[:, None, :]).any(-1)))
                   for a, b in zip(own_choice, full_choice)]
        own = rf.first_gradient(ref, p, ids, cfg, precision)
        with rf.given(ref, full_choice):
            jax.clear_caches()
            same = rf.first_gradient(ref, p, ids, cfg, precision)
        jax.clear_caches()
        print(json.dumps({"precision": precision, "assignments_flipped_by_layer": flipped,
                          "own_choice": rf.reading(ref, own, want, names),
                          "float32_choice_given": rf.reading(ref, same, want, names)}), flush=True)


def main():
    ap = argparse.ArgumentParser()
    sub = ap.add_subparsers(dest="what", required=True)
    b = sub.add_parser("balance")
    b.add_argument("--workload", required=True)
    b.add_argument("--std", type=float, nargs="+", required=True)
    b.add_argument("--seeds", type=int, nargs="+", required=True)
    f = sub.add_parser("flips")
    f.add_argument("--config", required=True)
    f.add_argument("--seed", type=int, required=True)
    f.add_argument("--seq-len", type=int, default=2048)
    f.add_argument("--vocab", type=int, default=4096)
    f.add_argument("--precisions", nargs="+", default=["bfloat16", "fp8"])
    f.add_argument("--init-std", type=float, default=None, help="default: the configuration's")
    args = ap.parse_args()
    {"balance": balance, "flips": flips}[args.what](args)


if __name__ == "__main__":
    main()
