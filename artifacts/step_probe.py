"""Step decomposition probe for the ResNet-50 amp-O2 hot path on TPU.

Times, compiled on the real chip with a hard D2H fetch as the barrier:
  1. forward + loss
  2. forward + backward (scaled_grad)
  3. forward + backward + fused-Adam step
  4. the full sharded DDP step (what bench.py's headline measures)
  5. (4) wrapped in a steps_per_call=4 lax.scan — amortizes per-dispatch
     host latency and lets XLA overlap host dispatch

Backward decomposition (VERDICT r3 item 2 — 54 of 70 ms was
bwd+optimizer with no breakdown):
  6. grad wrt INPUT only — the dgrad chain without any wgrad convs
  7. eval-mode fwd+bwd — BN uses running stats, so the batch-stat
     backward (fp32 reductions over activations) drops out
  8. conv microbench: fwd / dgrad / wgrad per representative ResNet-50
     conv shape, NCHW vs NHWC, bf16 — names which conv family and which
     grad direction eats the backward

Run:  python artifacts/step_probe.py  [batch]
"""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from apex_tpu import amp, optimizers, parallel, models
from apex_tpu.nn import functional as F

B = int(sys.argv[1]) if len(sys.argv) > 1 else 128


def timed(f, *a, iters=10):
    g = jax.jit(f)
    out = g(*a)
    float(jnp.sum(jax.tree_util.tree_leaves(out)[0].astype(jnp.float32)))
    t0 = time.perf_counter()
    for _ in range(iters):
        out = g(*a)
    float(jnp.sum(jax.tree_util.tree_leaves(out)[0].astype(jnp.float32)))
    return (time.perf_counter() - t0) / iters


def main():
    model, optimizer = amp.initialize(
        models.resnet50(), optimizers.FusedAdam(lr=0.1), opt_level="O2",
        verbosity=0)
    ddp = parallel.DistributedDataParallel(model)
    params, bn_state = model.init(jax.random.PRNGKey(0))
    opt_state = optimizer.init(params)
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(B, 3, 224, 224), jnp.float32)
    y = jnp.asarray(rng.randint(0, 1000, B), jnp.int32)

    def loss_fn(p):
        out, new_bn = model.apply(p, x, state=bn_state, train=True)
        return F.cross_entropy(out, y), new_bn

    def fwd(p):
        l, _ = loss_fn(p)
        return l

    dt = timed(fwd, params)
    print(f"fwd+loss:        {dt*1e3:7.2f} ms")

    def fwdbwd(p):
        _, _, grads = amp.scaled_grad(loss_fn, p, opt_state, has_aux=True)
        return grads

    dt = timed(fwdbwd, params)
    print(f"fwd+bwd:         {dt*1e3:7.2f} ms")

    # -- backward decomposition ------------------------------------------
    # dgrad-only: differentiate wrt the INPUT — the cotangent chain runs
    # through every layer but no weight-gradient convs are built
    def dgrad_only(xx):
        out, _ = model.apply(params, xx, state=bn_state, train=True)
        return F.cross_entropy(out, y)

    dt = timed(jax.grad(dgrad_only), x)
    print(f"fwd+dgrad only:  {dt*1e3:7.2f} ms   (no wgrad convs)")

    # eval-mode backward: BN applies running stats, so the fp32
    # batch-stat reductions and their backward drop out of the graph
    def eval_loss(p):
        out, _ = model.apply(p, x, state=bn_state, train=False)
        return F.cross_entropy(out, y)

    dt = timed(lambda p: eval_loss(p), params)
    print(f"fwd eval:        {dt*1e3:7.2f} ms")
    dt = timed(jax.grad(eval_loss), params)
    print(f"fwd+bwd eval:    {dt*1e3:7.2f} ms   (no BN-stat backward)")

    def full(p, st):
        _, _, grads = amp.scaled_grad(loss_fn, p, opt_state, has_aux=True)
        p2, _, _ = optimizer.step(p, st, grads)
        return p2

    dt = timed(full, params, opt_state)
    print(f"fwd+bwd+opt:     {dt*1e3:7.2f} ms")

    mesh = Mesh(np.array(jax.devices()), ("data",))

    def step(state, batch):
        params, bn_st, opt_st = state
        xb, yb = batch

        def loss_fn(p):
            out, new_bn = model.apply(p, xb, state=bn_st, train=True)
            return F.cross_entropy(out, yb), new_bn

        loss, new_bn, grads = amp.scaled_grad(loss_fn, params, opt_st,
                                              has_aux=True)
        grads = ddp.allreduce_grads(grads)
        params, opt_st, _ = optimizer.step(params, opt_st, grads)
        return (params, new_bn, opt_st), lax.pmean(loss, "data")

    train = jax.jit(jax.shard_map(
        step, mesh=mesh, in_specs=(P(), (P("data"), P("data"))),
        out_specs=(P(), P()), check_vma=False))
    state = (params, bn_state, opt_state)
    batch = (x, y)
    state, out = train(state, batch)
    state, out = train(state, batch)
    float(jnp.sum(jax.tree_util.tree_leaves(out)[0]))
    t0 = time.perf_counter()
    for _ in range(20):
        state, out = train(state, batch)
    float(jnp.sum(jax.tree_util.tree_leaves(out)[0]))
    dt = (time.perf_counter() - t0) / 20
    ndev = len(jax.devices())
    print(f"full DDP step:   {dt*1e3:7.2f} ms   "
          f"{B/dt/ndev:6.0f} img/s/chip")

    # K steps per dispatch via the make_step scan wrapper
    K = 4
    scan_step = ddp.make_step(step, mesh=mesh, steps_per_call=K)
    kbatch = (jnp.broadcast_to(x, (K,) + x.shape),
              jnp.broadcast_to(y, (K,) + y.shape))
    state, out = scan_step(state, kbatch)
    float(jnp.sum(jax.tree_util.tree_leaves(out)[0]))
    t0 = time.perf_counter()
    for _ in range(5):
        state, out = scan_step(state, kbatch)
    float(jnp.sum(jax.tree_util.tree_leaves(out)[0]))
    dt = (time.perf_counter() - t0) / (5 * K)
    print(f"scan x{K} step:    {dt*1e3:7.2f} ms   "
          f"{B/dt/ndev:6.0f} img/s/chip")


def conv_bench(shapes=None, K=8, iters=3):
    """fwd / dgrad / wgrad per representative ResNet-50 conv, both
    layouts, bf16.  K-chained with a data dependence (tanh(mean) folded
    back) so XLA cannot CSE the repeats and the per-dispatch host
    latency amortizes over K convs."""
    rng = np.random.RandomState(0)
    if shapes is None:
        # (name, kh, cin, cout, hw, stride) — B fixed at probe batch
        shapes = [
            ("stem 7x7s2 3->64 @224", 7, 3, 64, 224, 2),
            ("3x3 64->64 @56", 3, 64, 64, 56, 1),
            ("1x1 256->64 @56", 1, 256, 64, 56, 1),
            ("3x3 128->128 @28", 3, 128, 128, 28, 1),
            ("3x3 512->512 @7", 3, 512, 512, 7, 1),
        ]
    for layout in ("NCHW", "NHWC"):
        dn_in, dn_k, dn_out = ((layout, "OIHW", layout)
                               if layout == "NCHW"
                               else (layout, "HWIO", layout))
        for name, kh, cin, cout, hw, stride in shapes:
            if layout == "NCHW":
                xs = (B, cin, hw, hw)
                ks = (cout, cin, kh, kh)
            else:
                xs = (B, hw, hw, cin)
                ks = (kh, kh, cin, cout)
            x = jnp.asarray(rng.randn(*xs), jnp.bfloat16)
            w = jnp.asarray(rng.randn(*ks) * 0.05, jnp.bfloat16)

            def conv(xx, ww):
                # pure-bf16 conv, like the model's under amp O2 (the MXU
                # accumulates fp32 internally regardless)
                return lax.conv_general_dilated(
                    xx, ww, (stride, stride), "SAME",
                    dimension_numbers=(dn_in, dn_k, dn_out))

            ct = conv(x, w)  # cotangent template (output shape)
            # conv FLOPs from the shared analytic cost model
            # (observability.costmodel, XLA valid-position counting) —
            # this probe's old hand-rolled 2*B*H*W*Cout*Cin*k^2 counted
            # padding taps as math and, on grad convs, overcounted a
            # strided dgrad by stride^2.  One source of truth now; the
            # dgrad/wgrad rows deliberately reuse the FORWARD count
            # (valid-position makes them equal) so TF/s stays
            # comparable across the three directions.
            from apex_tpu.observability import costmodel
            flops = costmodel.jaxpr_cost(
                jax.make_jaxpr(conv)(x, w)).flops

            def chain_fwd(xx, ww):
                def body(c, _):
                    y = conv(c, ww)
                    c = c + jnp.tanh(jnp.mean(y)).astype(c.dtype) * 1e-3
                    return c, ()
                return lax.scan(body, xx, None, length=K)[0]

            # conv is LINEAR in each operand, so dx depends only on
            # (w, ct) and dw only on (x, ct) — never on the carry.  The
            # cotangent must be perturbed BY the carry each iteration or
            # XLA hoists the gradient conv out of the scan and the
            # "per-op" time is K-times too fast (the CSE-in-probes trap
            # again, loop-invariant-code-motion flavor).
            def chain_dgrad(xx, ww, cct):
                def body(c, _):
                    ci = cct * (1 + jnp.tanh(jnp.mean(c))
                                .astype(cct.dtype) * 1e-3)
                    dx = jax.vjp(lambda a: conv(a, ww), c)[1](ci)[0]
                    return c + dx.astype(c.dtype) * 1e-6, ()
                return lax.scan(body, xx, None, length=K)[0]

            def chain_wgrad(xx, ww, cct):
                def body(c, _):
                    ci = cct * (1 + jnp.tanh(jnp.mean(c))
                                .astype(cct.dtype) * 1e-3)
                    dw = jax.vjp(lambda a: conv(xx, a), c)[1](ci)[0]
                    return c + dw.astype(c.dtype) * 1e-6, ()
                return lax.scan(body, ww, None, length=K)[0]

            # cotangent in bf16 — matches the real backward, where the
            # cast transposes deliver bf16 cotangents into the convs
            ctb = ct.astype(jnp.bfloat16)
            rows = []
            for tag, fn, args in (
                    ("fwd", chain_fwd, (x, w)),
                    ("dgrad", chain_dgrad, (x, w, ctb)),
                    ("wgrad", chain_wgrad, (x, w, ctb))):
                dt = timed(fn, *args, iters=iters) / K
                rows.append(f"{tag} {dt*1e3:6.2f} ms "
                            f"{flops/dt/1e12:5.1f} TF/s")
            print(f"  {layout} {name:24s} " + "  ".join(rows))


if __name__ == "__main__":
    main()
    print("conv microbench (per-op, K-chained, bf16):")
    conv_bench()
