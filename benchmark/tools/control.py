#!/usr/bin/env python3
"""Read, on the chip and at a cell's own size, the two numbers every limit of
``correct`` is set from: the largest that sound runs of the program give over
the seeds, and the smallest that the control gives (the plain reference put in
the program's place, one precision below what the configuration states).

    python benchmark/tools/control.py --workload bert-large.pretrain-512 \\
        --seeds 11 12 13 14 --seconds 1

One process reads all the seeds, so the compile cache is paid once.  Its
output is what PERF.md's "Limits of correct" quotes.
"""

import argparse
import json
import os
import sys
import time

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=1.0)
    ap.add_argument("--control-seeds", type=int, default=3,
                    help="read the control on the first N seeds only")
    args = ap.parse_args()
    sys.path.insert(0, ROOT)
    sys.path.insert(0, BENCH_DIR)
    import importlib
    import jax
    if jax.default_backend() != "tpu":
        sys.exit("control: needs a TPU")
    from apex_tpu.utils import configure_compile_cache
    from lib import harness
    configure_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    rows = []
    for n, seed in enumerate(args.seeds):
        t = time.perf_counter()
        cell = harness.load_cell(manifest, args.workload, seed, args.seconds, False, ROOT)
        runner = importlib.import_module("runners." + cell.config["runner"]).Runner(
            cell, harness.Spans(), lambda o: None)
        runner.setup()
        tracer = harness.Tracer(False, args.seconds, "")
        measured = runner.window(args.seconds, tracer)
        runner.release()
        got = runner.control_readings(with_control=n < args.control_seeds)
        got.update(seed=seed, attempted=measured["attempted"], failed=measured["failed"],
                   seconds=time.perf_counter() - t)
        print(json.dumps(got), flush=True)
        rows.append(got)
    summary = {"workload": args.workload, "seeds": len(rows)}
    for group in ("sound", "control", "control_params"):
        if group in rows[0]:
            pick = max if group == "sound" else min
            summary[group] = {k: pick(r[group][k] for r in rows if group in r)
                              for k in rows[0][group]}
    print(json.dumps({"summary (sound: largest; controls: smallest)": summary}), flush=True)


if __name__ == "__main__":
    main()
