#!/usr/bin/env python3
"""``tools/routing_balance.py`` for the one-branch decoder (its blocks are not
the per-layer decoder's, so that tool's walk over ``self_attn`` and ``mlp`` does
not fit it): how the seeded weights' scale balances the routing of
``nemotron3-nano-30b-a3b``, on the chip at the cell's own size.  For each
``--std`` and seed, ``references/nemotron3.routing`` (the plain float32 walk
``runners/train_balanced_lm.py`` balances the bias with) over one batch of the
cell's traffic, and for every expert layer the rows the fullest and the emptiest
of all published experts got, the assignments that fell on the experts held and
the fullest held expert; beside them what the Mamba-2 mixers before it did to
the stream (the norm of the mean normed state, common to all tokens, against
what tells them apart) and the first loss (what the runner's balancing then
reaches is on every run's ``bias_balanced`` line).  ``init_std`` of
``configs/nemotron3-nano-30b-a3b.json`` was chosen from this sweep (PERF.md
section 6, PR 44: read with the tool's first form, which walked the program's
bf16 blocks).

    python benchmark/tools/nemotron3_routing.py --std 0.005 0.02 0.05 --seeds 4400000001 7
"""

import argparse
import json
import os
import sys

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default="nemotron3-nano-30b-a3b.pretrain-8k")
    ap.add_argument("--std", type=float, nargs="+", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args()
    sys.path.insert(0, ROOT)
    sys.path.insert(0, BENCH_DIR)
    import jax
    import jax.numpy as jnp
    from apex_tpu import amp, models, optimizers
    from lib import harness, weights
    from references import nemotron3 as ref
    from runners.train_causal_lm import causal_lm_batch
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        cell = harness.load_cell(json.load(f), args.workload, 0, 1.0, False, ROOT)
    cfg, traffic = cell.config, cell.traffic["params"]
    net = models.NemotronH(models.NemotronHConfig.from_dict(cfg, remat=None))
    model, _ = amp.initialize(net, optimizers.FusedAdam(lr=1e-4), opt_level="O2", verbosity=0)
    shapes = jax.eval_shape(lambda k: model.init(k)[0], jax.random.PRNGKey(0))
    held, first = cfg["n_routed_experts"], cfg["experts_held_start"]

    @jax.jit
    def walk(params, ids):
        rows = ref.routing(params, ids, cfg)
        positions = ids.shape[0] * (ids.shape[1] - 1)
        return rows, ref.summed_nll(ref.P.to_f32(params), ids, cfg) / positions

    for std in args.std:
        for seed in args.seeds:
            params = weights.make_weights(shapes, seed, std)
            ids, = causal_lm_batch(traffic, seed, 0, cfg["per_chip_batch"], cfg["vocab_size"])
            rows, loss = jax.device_get(walk(params, jnp.asarray(ids)))
            for row in rows:
                loads, mine = row["loads"], row["loads"][first:first + held]
                rec = {"load_max": loads.max(), "load_min": loads.min(), "held": mine.sum(),
                       "held_max": mine.max(), "common": row["common"],
                       "specific": row["specific"]}
                print(json.dumps({"std": std, "seed": seed, "layer": int(row["layer"]),
                                  "first_loss": round(float(loss), 4),
                                  **{k: round(float(v), 5) for k, v in rec.items()}}), flush=True)
            del params


if __name__ == "__main__":
    main()
