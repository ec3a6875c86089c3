#!/usr/bin/env python3
"""Chip: latent attention's flash call at the cell's shape (2, 8192, 32,
192 / 128, bf16), forward + backward, against the ways it could have been run
(PERF.md section 6, PR 48):

- ``shared``: the program's call, ``flash_attention_token_major(q_rope=,
  k_rope=)``: the score head in two parts, one rotated key head for all query
  heads, its gradient the kernels' sum;
- ``padded256``: the parent's kernels, which take one head width: q, k and v
  joined and padded to 256 in HBM, the result sliced back;
- ``plain128``: the accepted cells' call at 128 / 128, no rope part (what the
  extra 64 of the score head cost).

    python benchmark/tools/kanana2_flash_variants.py

A line a variant (wall ms of ten calls, forward alone and with the backward),
``shared`` against ``padded256`` (bf16: a rounding apart), and each variant's
device operations by name from a profiler trace of three steps.  The key head
written out once a query head (ISSUE 48's variant (a)) was timed in PR 48's
first call through a second path of the kernels and read 50.33 ms against
``shared``'s 50.40; the path was taken out with the variant.
"""

import json
import math
import os
import shutil
import sys
import time

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)
SCALE = 1.0 / math.sqrt(192)


def main():
    sys.path.insert(0, ROOT)
    sys.path.insert(0, BENCH_DIR)
    import jax
    import jax.numpy as jnp
    if jax.default_backend() != "tpu":
        sys.exit("kanana2_flash_variants: needs a TPU")
    from apex_tpu.ops import pallas_flash_attention as pfa
    from lib import trace as tr

    B, T, H = 2, 8192, 32
    ks = jax.random.split(jax.random.PRNGKey(0), 6)
    draw = lambda key, heads, width: (0.5 * jax.random.normal(
        key, (B, T, heads, width), jnp.float32)).astype(jnp.bfloat16)
    q, k, v, w = (draw(key, H, 128) for key in ks[:4])
    qr, kr = draw(ks[4], H, 64), draw(ks[5], 1, 64)
    f32 = lambda x: x.astype(jnp.float32)

    def shared(q, k, v, qr, kr):
        return pfa.flash_attention_token_major(q, k, v, causal=True, q_rope=qr, k_rope=kr)

    def padded256(q, k, v, qr, kr):
        pad = lambda x: jnp.pad(x, ((0, 0), (0, 0), (0, 0), (0, 256 - x.shape[-1])))
        qq = pad(jnp.concatenate([q, qr], -1))
        kk = pad(jnp.concatenate([k, jnp.broadcast_to(kr, qr.shape)], -1))
        return pfa.flash_attention_token_major(qq, kk, pad(v), causal=True,
                                               scale=SCALE)[..., :128]

    def plain128(q, k, v, qr, kr):
        return pfa.flash_attention_token_major(q, k, v, causal=True)

    def timed(fn, *args):
        jax.block_until_ready(fn(*args))
        t0 = time.perf_counter()
        for _ in range(10):
            out = fn(*args)
        jax.block_until_ready(out)
        return (time.perf_counter() - t0) / 10 * 1e3, out

    steps, outs = {}, {}
    for fn in (shared, padded256, plain128):
        steps[fn.__name__] = step = jax.jit(lambda *a, fn=fn: jax.value_and_grad(
            lambda *b: jnp.sum(f32(fn(*b)) * f32(w)), (0, 1, 2, 3, 4))(*a))
        both, (_, grads) = timed(step, q, k, v, qr, kr)
        fwd, o = timed(jax.jit(fn), q, k, v, qr, kr)
        outs[fn.__name__] = (o, grads)
        print(json.dumps({fn.__name__: {"fwd_bwd_ms": both, "fwd_ms": fwd}}), flush=True)

    (o, g), (po, pg) = outs["shared"], outs["padded256"]
    errs = {"o": float(jnp.max(jnp.abs(f32(o) - f32(po))))}
    for name, a, b in zip(("dq", "dk", "dv", "dq_rope", "dk_rope"), g, pg):
        errs[name] = float(jnp.max(jnp.abs(f32(a) - f32(b))) / (1e-9 + jnp.max(jnp.abs(f32(b)))))
    print(json.dumps({"shared_vs_padded256": errs}), flush=True)

    at = os.path.join(ROOT, "chiprun_out", "flash_trace")
    for name, step in steps.items():
        shutil.rmtree(at, ignore_errors=True)
        jax.profiler.start_trace(at)
        for _ in range(3):
            jax.block_until_ready(step(q, k, v, qr, kr))
        jax.profiler.stop_trace()
        ops = tr.device_ops(tr.load_xplane(tr.find_xplane(at), tr.default_keep))[0]
        by = {}
        for e in tr.leaf_ops(ops):
            by[tr.op_label(e)] = by.get(tr.op_label(e), 0.0) + e[2] / 1e6 / 3
        top = sorted(by.items(), key=lambda kv: -kv[1])[:8]
        print(json.dumps({name + "_ops_ms": {k: round(ms, 3) for k, ms in top}}), flush=True)
    shutil.rmtree(at, ignore_errors=True)


if __name__ == "__main__":
    main()
