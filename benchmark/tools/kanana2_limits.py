#!/usr/bin/env python3
"""Chip: ``tools/control.py``'s loop for ``kanana-2-30b-a3b.pretrain-8k`` with
what that tool drops kept: every number ``references/kanana2.py``'s ``compare``
gives (not the limited ones alone), and every step's held assignments as
``moe_held_shortfall`` (what PR 42's rule chooses a ``window_steps`` from).

    python benchmark/tools/kanana2_limits.py --seeds 1 2 3 4 5 6 7 8 \\
        --sound-seeds 6 --control-seeds 3

The clock ends every window (``window_steps`` is taken off the configuration
for the length of the tool).  The first ``--control-seeds`` seeds read the two
controls (the reference with fp8-rounded matmul operands, then with
bf16-stored parameters, each against the float32 reference), the first
``--sound-seeds`` the program against the reference, the others the window's
series alone.  One line a seed.
"""

import argparse
import gc
import importlib
import json
import os
import sys
import time

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default="kanana-2-30b-a3b.pretrain-8k")
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=40.0)
    ap.add_argument("--sound-seeds", type=int, default=6)
    ap.add_argument("--control-seeds", type=int, default=3)
    args = ap.parse_args()
    sys.path.insert(0, ROOT)
    sys.path.insert(0, BENCH_DIR)
    import jax
    if jax.default_backend() != "tpu":
        sys.exit("kanana2_limits: needs a TPU")
    from apex_tpu.utils import configure_compile_cache
    from lib import harness
    from references._precision import NEXT_LOWER
    configure_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    for n, seed in enumerate(args.seeds):
        t = time.perf_counter()
        cell = harness.load_cell(manifest, args.workload, seed, args.seconds, False, ROOT)
        cell.config.pop("window_steps", None)
        lines = []
        runner = importlib.import_module("runners." + cell.config["runner"]).Runner(
            cell, harness.Spans(), lines.append)
        runner.setup()
        measured = runner.window(args.seconds, harness.Tracer(False, args.seconds, ""))
        line = lambda key: next((l[key] for l in lines if key in l), None)
        by_step = line("by_step") or {}
        out = {"seed": seed, "steps": measured["attempted"], "failed": measured["failed"],
               "setup_steps": line("setup_steps"), "step_ms": line("step_ms"),
               "bias_balanced": line("bias_balanced"),
               "shortfall_by_step": [round(runner.held_shortfall(h), 4)
                                     for h in by_step.get("moe_assignments_held", [])],
               "load_max_by_step": by_step.get("moe_expert_load_max")}
        runner.release()
        gc.collect()
        if n < max(args.sound_seeds, args.control_seeds):
            ref = runner.reference_readings()
            out.update(sound=runner.reference.compare(runner.first, ref),
                       losses=runner.first["losses"], reference_losses=ref["losses"])
        if n < args.control_seeds:
            low = runner.reference_readings(precision=NEXT_LOWER[cell.config["compute_dtype"]])
            out["control"] = runner.reference.compare(low, ref)
            del low
            gc.collect()
            low = runner.reference_readings(param_dtype=NEXT_LOWER[cell.config["param_dtype"]])
            out["control_params"] = runner.reference.compare(low, ref)
            del low
        out["seconds"] = time.perf_counter() - t
        print(json.dumps(out), flush=True)
        del runner, out
        ref = None                  # the next seed's program needs the room
        gc.collect()
        jax.clear_caches()


if __name__ == "__main__":
    main()
