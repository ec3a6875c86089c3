#!/usr/bin/env python3
"""The benchmark's one command:

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A new process each time: set-up (weights from the seed on the device, warm-up
of this cell's shapes, compile-cache load), the timed window, then the
comparison with the plain reference, and as the last line of standard output
one JSON object with ``correct``, ``attempted``, ``failed``, ``metrics`` and
``device`` (and ``breakdown`` when traced).  Everything else it has to say
goes on earlier lines.  It needs a TPU with as many chips as the cell asks
for and has no way to run on anything else: without one it exits 2 in
seconds, before any model is built, and prints no result.
"""

import time
T_START = time.perf_counter()

import argparse
import json
import os
import sys

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=[0, 1], default=0)
    args = ap.parse_args(argv)

    if not os.path.isdir(os.path.join(ROOT, "apex_tpu")):
        print(f"benchmark: {ROOT} holds no apex_tpu: nothing to measure", file=sys.stderr)
        return 2
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    sys.path.insert(0, ROOT)
    sys.path.insert(0, BENCH_DIR)
    import jax
    from lib import harness

    cell = harness.load_cell(manifest, args.workload, args.seed, args.seconds, bool(args.trace),
                             ROOT, t_start=T_START)
    backend, found = jax.default_backend(), len(jax.devices())
    if backend != "tpu" or found < cell.chips:
        print(f"benchmark: {cell.name} needs {cell.chips} TPU chip(s); JAX found backend "
              f"{backend!r} with {found} device(s). Nothing was built or run.", file=sys.stderr)
        return 2
    from apex_tpu.utils import configure_compile_cache
    cache_dir = configure_compile_cache()
    # cache the sub-second compiles too (op-by-op model construction, admission)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)

    def log(obj):
        print(json.dumps(obj), flush=True)

    log({"workload": cell.name, "seed": cell.seed, "seconds": cell.seconds,
         "trace": cell.trace, "cache_dir": cache_dir})
    result = harness.run_cell(cell, log)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
