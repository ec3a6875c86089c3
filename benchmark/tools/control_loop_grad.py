#!/usr/bin/env python3
"""The third control of a looped decoder's cell (``ouro-2.6b``): does it show
in the comparison that decides ``correct`` in which dtype a stack weight's
gradient, a sum of ``total_ut_steps`` contributions, is kept between the
passes?  The configuration states it (``assumed.loop_grad_dtype``); this tool
reads what the other dtype would change.

The plain reference alone (``references/ouro.py``; wherever JAX runs), its
first gradient on one batch of the cell's traffic from the seed, three ways:

    float32      float32 matmuls, the contributions summed in float32: the reference
    wide         matmul operands rounded to the configuration's compute dtype, the
                 contributions summed in float32
    narrow       the same matmuls; each pass's contribution to a stack weight rounded
                 to the compute dtype and the running sum rounded after every add,
                 as a scan's carry of that dtype keeps it

and prints ``wide`` and ``narrow`` against ``float32`` by the numbers the cell
compares (``grad_diff_mean``, ``grad_norm_gap_mean``, ``gate_grad_gap``), and
``narrow`` against ``wide``.  Ratios of the reference's own gradients: never a
device metric, and no limit is set from them.

    python benchmark/tools/control_loop_grad.py --config benchmark/configs/ouro-2.6b.json \\
        --seed 4000000301 [--seq-len 2048 --vocab 8192 --layers 2]
"""

import argparse
import importlib
import json
import os
import sys

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


def shapes(cfg):
    """The parameter tree in the program's layout, float32, from the program's
    own model (the reference reads that layout)."""
    import jax
    from apex_tpu import models
    net = models.Laguna(models.LagunaConfig.from_dict(cfg, remat=None))
    return jax.eval_shape(lambda k: net.init(k)[0], jax.random.PRNGKey(0))


def first_gradients(ref, p, ids, cfg, precision, carry_dtype=None):
    """Rows (``ref.leaf_norms``) of the first gradient of the mean loss.  With
    ``carry_dtype`` the stack's weights get their gradient as a scan's carry of
    that dtype would hold it: pass by pass, rounded after every add."""
    import jax
    import jax.numpy as jnp
    passes = cfg["total_ut_steps"]
    scale = 1.0 / (ids.shape[0] * (ids.shape[1] - 1))

    @jax.jit
    def run(p):
        if carry_dtype is None:
            return ref.leaf_norms(jax.grad(
                lambda q: scale * ref.summed_loss(q, ids, cfg, precision))(p))
        g, by_pass = jax.grad(
            lambda q, copies: scale * ref.summed_loss(q, ids, cfg, precision, by_pass=copies),
            argnums=(0, 1))(p, [p["layers"]] * passes)
        narrow = lambda x: x.astype(carry_dtype)
        kept = jax.tree_util.tree_map(narrow, by_pass[-1])          # the backward runs last to first
        for contribution in reversed(by_pass[:-1]):
            kept = jax.tree_util.tree_map(lambda a, b: a + narrow(b), kept, contribution)
        return ref.leaf_norms({**g, "layers": jax.tree_util.tree_map(
            lambda x: x.astype(jnp.float32), kept)})

    return run(p)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seq-len", type=int, default=8192)
    ap.add_argument("--vocab", type=int, default=None, help="a smaller vocabulary, off the chip")
    ap.add_argument("--layers", type=int, default=None, help="fewer layers, off the chip")
    ap.add_argument("--init-std", type=float, default=None)
    args = ap.parse_args()
    sys.path.insert(0, ROOT)
    sys.path.insert(0, BENCH_DIR)
    import jax
    import jax.numpy as jnp
    import numpy as np
    from lib import weights
    from runners.train_causal_lm import causal_lm_batch
    with open(args.config) as f:
        cfg = json.load(f)
    if args.vocab:
        cfg["vocab_size"] = args.vocab
    if args.layers:
        cfg.update(num_hidden_layers=args.layers, layer_types=cfg["layer_types"][:args.layers],
                   mlp_layer_types=cfg["mlp_layer_types"][:args.layers])
    std = args.init_std or cfg["init_std"]
    ref = importlib.import_module("references." + cfg["reference"])
    p = weights.make_weights(shapes(cfg), args.seed, std)
    ids = jnp.asarray(causal_lm_batch({"seq_len": args.seq_len}, args.seed, 0,
                                      cfg["per_chip_batch"], cfg["vocab_size"])[0])
    compute = cfg["compute_dtype"]
    rows = {"float32": first_gradients(ref, p, ids, cfg, "float32"),
            "wide": first_gradients(ref, p, ids, cfg, compute),
            "narrow": first_gradients(ref, p, ids, cfg, compute, jnp.dtype(compute))}
    rows = {k: np.asarray(v) for k, v in rows.items()}
    gate = ref.gate_leaves(p)

    def against(got, want):
        return {"grad_diff_mean": ref.difference_norms(got, want)[2],
                "grad_norm_gap_mean": ref.norm_gap(got[:, 0], want[:, 0])[2],
                "gate_grad_gap": ref.gate_gap(got, want, gate)}

    print(json.dumps({"config": cfg["name"], "seed": args.seed, "seq_len": args.seq_len,
                      "vocab_size": cfg["vocab_size"], "layers": cfg["num_hidden_layers"],
                      "init_std": std, "device": jax.devices()[0].platform,
                      "carry_dtype": compute,
                      "wide_against_float32": against(rows["wide"], rows["float32"]),
                      "narrow_against_float32": against(rows["narrow"], rows["float32"]),
                      "narrow_against_wide": against(rows["narrow"], rows["wide"])}), flush=True)


if __name__ == "__main__":
    main()
