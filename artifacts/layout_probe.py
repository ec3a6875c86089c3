"""Layout probe: measure, compiled on the real chip, (a) NCHW vs NHWC
conv layout on a ResNet-50-shaped conv stack, (b) the cost of
training-mode BN stats, (c) the full model fwd under both layouts.
Chained iterations amortize per-dispatch host latency; a hard D2H fetch
is the barrier.

Run:  python artifacts/layout_probe.py
"""

import time
import sys

sys.path.insert(0, __file__.rsplit("/artifacts", 1)[0])

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax


def timed(f, *a, iters=10):
    g = jax.jit(f)
    float(jnp.sum(g(*a).astype(jnp.float32)))
    t0 = time.perf_counter()
    for _ in range(iters):
        r = g(*a)
    float(jnp.sum(r.astype(jnp.float32)))
    return (time.perf_counter() - t0) / iters


# ResNet-50 stage shapes (B=128): (Cin, Cout, H, k, stride)
STAGES = [(64, 64, 56, 1, 1), (64, 64, 56, 3, 1), (64, 256, 56, 1, 1),
          (128, 128, 28, 3, 1), (256, 512, 28, 1, 2),
          (256, 256, 14, 3, 1), (512, 512, 7, 3, 1)]
B = 128


def conv_stack(fmt):
    k = jax.random.PRNGKey(0)
    xs, ws = [], []
    for (ci, co, h, kk, s) in STAGES:
        if fmt == "NCHW":
            xs.append(jax.random.normal(k, (B, ci, h, h), jnp.bfloat16))
            ws.append(jax.random.normal(k, (co, ci, kk, kk), jnp.bfloat16))
        else:
            xs.append(jax.random.normal(k, (B, h, h, ci), jnp.bfloat16))
            ws.append(jax.random.normal(k, (kk, kk, ci, co), jnp.bfloat16))

    dn = ((f"NCHW", "OIHW", "NCHW") if fmt == "NCHW"
          else ("NHWC", "HWIO", "NHWC"))

    def run(*args):
        n = len(STAGES)
        xs, ws = args[:n], args[n:]
        out = jnp.zeros((), jnp.float32)
        for x, w, (ci, co, h, kk, s) in zip(xs, ws, STAGES):
            for _ in range(4):          # amortize dispatch
                y = lax.conv_general_dilated(
                    x, w, (s, s), "SAME", dimension_numbers=dn,
                    preferred_element_type=jnp.float32)
                out = out + jnp.sum(y) * 1e-9
                # feed the result back so iterations depend on each other
                # — identical pure ops would otherwise be CSE'd into one
                # and the x4 repeat would measure nothing
                x = x + (out * 1e-9).astype(x.dtype)
        return out

    # per-stage conv FLOPs from the shared analytic cost model
    # (observability.costmodel — XLA valid-position counting replaces
    # this probe's hand-rolled padded-tap formula), x4 for the chained
    # repeats inside run()
    from apex_tpu.observability import costmodel

    def one(x, w, s):
        return lax.conv_general_dilated(
            x, w, (s, s), "SAME", dimension_numbers=dn,
            preferred_element_type=jnp.float32)

    flops = 4 * sum(
        costmodel.jaxpr_cost(jax.make_jaxpr(
            lambda a, b, s=s: one(a, b, s))(x, w)).flops
        for x, w, (ci, co, h, kk, s) in zip(xs, ws, STAGES))
    dt = timed(run, *(xs + ws))
    print(f"conv stack {fmt}: {dt*1e3:.2f} ms  "
          f"{flops/dt/1e12:.1f} TFLOP/s")
    return dt


def bn_cost():
    from apex_tpu.nn import functional as F
    x = jax.random.normal(jax.random.PRNGKey(0), (B, 256, 28, 28),
                          jnp.bfloat16)

    def stats(x):
        out = jnp.zeros((), jnp.float32)
        for _ in range(8):
            _, m, v = F.batch_norm_stats(x, (0, 2, 3))
            out = out + jnp.sum(m) + jnp.sum(v)
            x = x + (out * 1e-9).astype(x.dtype)   # defeat CSE
        return out

    def apply_only(x):
        m = jnp.zeros((256,), jnp.float32)
        v = jnp.ones((256,), jnp.float32)
        out = jnp.zeros((), jnp.float32)
        for _ in range(8):
            y = F.batch_norm_apply(x, m, v, None, None, 1e-5)
            out = out + jnp.sum(y).astype(jnp.float32)
            x = x + (out * 1e-9).astype(x.dtype)   # defeat CSE
        return out

    print(f"bn stats x8: {timed(stats, x)*1e3:.2f} ms")
    print(f"bn apply x8: {timed(apply_only, x)*1e3:.2f} ms")


def model_fwd(channels_last=False):
    from apex_tpu import amp, models, optimizers
    model, _ = amp.initialize(models.resnet50(channels_last=channels_last),
                              optimizers.FusedAdam(lr=0.1),
                              opt_level="O2", verbosity=0)
    params, bn = model.init(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (B, 3, 224, 224))
    tag = "NHWC" if channels_last else "NCHW"

    def fwd(p, x):
        out, _ = model.apply(p, x, state=bn, train=True)
        return jnp.sum(out.astype(jnp.float32))

    dt = timed(fwd, params, x)
    print(f"resnet50 O2 {tag} fwd (train-mode BN): {dt*1e3:.2f} ms  "
          f"({B/dt:.0f} img/s)")

    def fwd_eval(p, x):
        out, _ = model.apply(p, x, state=bn, train=False)
        return jnp.sum(out.astype(jnp.float32))

    dt = timed(fwd_eval, params, x)
    print(f"resnet50 O2 {tag} fwd (eval-mode BN): {dt*1e3:.2f} ms  "
          f"({B/dt:.0f} img/s)")

    def fwdbwd(p, x):
        g = jax.grad(lambda p: fwd(p, x))(p)
        # timed() wants one array; sum one representative leaf
        return jax.tree_util.tree_leaves(g)[0]

    dt = timed(fwdbwd, params, x, iters=5)
    print(f"resnet50 O2 {tag} fwd+bwd (train): {dt*1e3:.2f} ms  "
          f"({B/dt:.0f} img/s)")


if __name__ == "__main__":
    conv_stack("NCHW")
    conv_stack("NHWC")
    bn_cost()
    model_fwd(channels_last=False)
    model_fwd(channels_last=True)
