#!/usr/bin/env python3
"""The two faults of latent attention that ``correct`` has to refuse, planted
in the program, and the cell read with each, on the chip at the cell's own
size (the tests plant the same two at the tiny size):

- ``unrotated``: the one shared key head is left as its projection wrote it,
  while the query heads' rope parts are rotated;
- ``one_head``: the key head's gradient is not the sum over the query heads.
  On the chip the fault sits where the timed path adds the heads up
  (``ops/pallas_flash_attention.py``, ``_rope_heads_sum``: the dk/dv kernel's
  float32 partial sums, a grid step's even heads in one half of a tile and its
  odd ones in the other): the first step's lower half alone is kept, query
  heads 0 and 2 of 32 (nothing outside the kernel parts the two).  Off the chip
  (the tests' tiny size, the dense path) each head is handed a copy of the key
  head and the copies of heads 1.. are cut off the gradient: query head 0's
  alone.  The forward pass is the sound one either way.

    python benchmark/tools/kanana2_faults.py --seed 4800000031

One process: the sound program and each fault through the runner's own set-up
(three steps from the seeded weights), each compared with one reading of the
plain reference; every compared number beside its limit.
"""

import argparse
import contextlib
import json
import os
import sys

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


@contextlib.contextmanager
def planted(fault):
    """``apex_tpu.transformer.mla`` with ``fault`` in it: ``None`` (sound),
    ``"unrotated"`` or ``"one_head"``."""
    import jax
    import jax.numpy as jnp
    from apex_tpu.ops import pallas_flash_attention as pfa
    from apex_tpu.transformer import mla
    rotate, attend = mla.rope_interleaved, mla.dot_product_attention_token_major
    heads_sum = pfa._rope_heads_sum

    def key_head_as_it_is(x, inv_freq):
        return x if x.shape[-1] == 2 * len(inv_freq) else rotate(x, inv_freq)

    def one_head(q, k, v, *, q_rope, k_rope, **kw):
        B, T, H, R = q_rope.shape
        rest = jnp.broadcast_to(jax.lax.stop_gradient(k_rope), (B, T, H - 1, R))
        return attend(q, k, v, q_rope=q_rope, k_rope=jnp.concatenate([k_rope, rest], 2), **kw)

    if fault == "unrotated":
        mla.rope_interleaved = key_head_as_it_is
    elif fault == "one_head" and jax.default_backend() == "tpu":
        pfa._rope_heads_sum = lambda parts: parts[:, 0, :, 0]
    elif fault == "one_head":
        mla.dot_product_attention_token_major = one_head
    elif fault is not None:
        raise ValueError(f"unknown fault {fault!r}")
    pfa._bwd.clear_cache()      # traced with the sum it found
    try:
        yield
    finally:
        mla.rope_interleaved, mla.dot_product_attention_token_major = rotate, attend
        pfa._rope_heads_sum = heads_sum
        pfa._bwd.clear_cache()


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default="kanana-2-30b-a3b.pretrain-8k")
    ap.add_argument("--seed", type=int, required=True)
    args = ap.parse_args()
    sys.path.insert(0, ROOT)
    sys.path.insert(0, BENCH_DIR)
    import importlib
    import jax
    if jax.default_backend() != "tpu":
        sys.exit("kanana2_faults: needs a TPU")
    from apex_tpu.utils import configure_compile_cache
    from lib import harness
    configure_compile_cache()
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    cell = harness.load_cell(manifest, args.workload, args.seed, 1.0, False, ROOT)
    readings, reference = {}, None
    for fault in (None, "unrotated", "one_head"):
        runner = importlib.import_module("runners." + cell.config["runner"]).Runner(
            cell, harness.Spans(), lambda o: None)
        with planted(fault):
            runner.setup()
        runner.release()
        if reference is None:
            reference = runner.reference_readings()
        got = runner.reference.compare(runner.first, reference)
        limits = runner.reference.LIMITS
        readings[fault or "sound"] = {k: got[k] for k in limits}
        refused = sorted(k for k in limits if not got[k] <= limits[k])
        print(json.dumps({"fault": fault or "sound", "seed": args.seed, "refused_by": refused,
                          **{k: got[k] for k in got if k in limits or k.endswith("_leaf")}}),
              flush=True)
        del runner
        jax.clear_caches()
    print(json.dumps({"limits": limits, "readings": readings}), flush=True)


if __name__ == "__main__":
    main()
