#!/usr/bin/env python3
"""Leaf by leaf, on the chip and at a cell's own size, what ``tools/control.py``
gives as one number: the norm of every leaf of the first gradient as the
program's Adam got it, as the float32 reference computes it, and as the
compute control does (the reference with its matmuls one precision below what
the configuration states).  From these a statistic over the leaves is chosen
for ``correct`` and its limit is set (PERF.md, "Limits of correct").

    python benchmark/tools/control_leaves.py --workload laguna-xs2.pretrain-8k \\
        --seeds 31 32 33 34 35 36 37 38 --control-seeds 6 --out chiprun_out/leaves.jsonl

The control follows the first step only (the first gradient needs no more),
so a seed costs a third of what it costs ``tools/control.py``.  One line a seed
goes to ``--out`` (names, three norms a leaf) and a summary of it to the output.
"""

import argparse
import importlib
import json
import os
import sys
import time

import numpy as np

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


def leaf_gaps(norms, reference):
    """Each leaf's gap as the references' ``norm_gap`` scales it."""
    norms, reference = np.asarray(norms, np.float64), np.asarray(reference, np.float64)
    return np.abs(norms - reference) / np.maximum(reference, np.median(reference))


def summary(gaps):
    return {"mean": float(np.minimum(gaps, 1.0).mean()), "median": float(np.median(gaps)),
            "worst": float(gaps.max()), "worst_leaf": int(gaps.argmax())}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, default=6,
                    help="read the control on the first N seeds only")
    ap.add_argument("--out", required=True)
    args = ap.parse_args()
    sys.path.insert(0, ROOT)
    sys.path.insert(0, BENCH_DIR)
    import jax
    if jax.default_backend() != "tpu":
        sys.exit("control_leaves: needs a TPU")
    from apex_tpu.utils import configure_compile_cache
    from lib import harness
    from references._precision import NEXT_LOWER
    configure_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    for n, seed in enumerate(args.seeds):
        t = time.perf_counter()
        cell = harness.load_cell(manifest, args.workload, seed, 1.0, False, ROOT)
        runner = importlib.import_module("runners." + cell.config["runner"]).Runner(
            cell, harness.Spans(), lambda o: None)
        runner.setup()
        runner.window(1.0, harness.Tracer(False, 1.0, ""))
        names = [jax.tree_util.keystr(p) for p, _ in
                 jax.tree_util.tree_flatten_with_path(runner.param_shapes)[0]]
        runner.release()
        reference = runner.reference_readings()["first_grad_norms"]
        row = {"seed": seed, "leaves": names, "reference": np.asarray(reference).tolist(),
               "program": np.asarray(runner.first["first_grad_norms"]).tolist()}
        line = {"seed": seed, "sound": summary(leaf_gaps(row["program"], reference))}
        if n < args.control_seeds:
            runner.check_steps = 1
            low = runner.reference_readings(
                precision=NEXT_LOWER[cell.config["compute_dtype"]])["first_grad_norms"]
            row["control"] = np.asarray(low).tolist()
            line["control"] = summary(leaf_gaps(low, reference))
        line["seconds"] = time.perf_counter() - t
        with open(args.out, "a") as f:
            f.write(json.dumps(row) + "\n")
        print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
