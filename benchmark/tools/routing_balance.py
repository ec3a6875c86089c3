#!/usr/bin/env python3
"""How the seeded weights' scale balances a sparse decoder's routing, on the
chip at a cell's own size: for each ``--std`` and seed, one forward pass of the
configuration's model over one batch of the cell's traffic, and for every
expert layer the rows the fullest and the emptiest of all published experts
got, the assignments that fell on the experts held, the fullest held expert,
and how much of the router's input is common to all tokens (the norm of the
mean hidden state) against what tells them apart.  ``init_std`` of
``configs/laguna-xs2.json`` was chosen from this sweep (PERF.md section 6, PR 26).

    python benchmark/tools/routing_balance.py --workload laguna-xs2.pretrain-8k \\
        --std 0.005 0.02 0.05 0.1 --seeds 2600000001 7
"""

import argparse
import json
import os
import sys

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--std", type=float, nargs="+", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args()
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
    shapes = jax.eval_shape(lambda k: model.init(k)[0], jax.random.PRNGKey(0))
    published, held = cfg["num_experts_published"], cfg["num_experts"]
    first = cfg["experts_held_start"]

    def layers(params, ids):
        out = []
        x = net.embed_tokens(params["embed_tokens"], ids)
        for i, block in enumerate(net.layers):
            p = params["layers"][str(i)]
            x = x + block.self_attn(p["self_attn"],
                                    block.input_layernorm(p["input_layernorm"], x))
            h = block.post_attention_layernorm(p["post_attention_layernorm"], x)
            if block.sparse:
                rows = h.astype(jnp.float32).reshape(-1, h.shape[-1])
                mean = rows.mean(0)
                _, experts, _ = block.mlp._route(rows, p["mlp"]["router"], False)
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
            params = weights.make_weights(shapes, seed, std)
            ids, = causal_lm_batch(traffic, seed, 0, cfg["per_chip_batch"], cfg["vocab_size"])
            for rec in jax.device_get(forward(params, jnp.asarray(ids))):
                print(json.dumps({"std": std, "seed": seed, "layer": int(rec.pop("layer")),
                                  **{k: round(float(v), 5) for k, v in rec.items()}}), flush=True)
            del params


if __name__ == "__main__":
    main()
