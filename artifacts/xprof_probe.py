"""Capture ONE xprof trace of the headline train step (VERDICT r4
item 7 — parity with how the reference actually used its nvtx ranges:
profiled runs informed its keep_batchnorm_fp32 guidance,
reference examples/imagenet/README.md:76-84).

Runs the same ResNet-50 amp-O2 DDP step bench.py's headline measures,
warms the compile cache, then traces `ITERS` steps through
apex_tpu.utils.profiler (range_push/pop annotate the phases) into
artifacts/xprof_trace_<ts>/.  The trace is the artifact; the companion
top-3 time-sink paragraph goes in PERF.md once step_probe's
decomposition has run on the same silicon.

Run:  python artifacts/xprof_probe.py  [batch]
"""

import datetime
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from apex_tpu import amp, optimizers, parallel, models
from apex_tpu.nn import functional as F
from apex_tpu.utils import profiler

B = int(sys.argv[1]) if len(sys.argv) > 1 else 128
ITERS = 6
# APEX_XPROF_ARCH=resnet18 for a cheap CPU smoke of the capture
# mechanics; the hardware artifact uses the headline resnet50
ARCH = os.environ.get("APEX_XPROF_ARCH", "resnet50")


def main():
    model, optimizer = amp.initialize(
        getattr(models, ARCH)(), optimizers.FusedAdam(lr=0.1),
        opt_level="O2", verbosity=0)
    ddp = parallel.DistributedDataParallel(model)
    params, bn_state = model.init(jax.random.PRNGKey(0))
    opt_state = optimizer.init(params)
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(B, 3, 224, 224), jnp.float32)
    y = jnp.asarray(rng.randint(0, 1000, B), jnp.int32)
    mesh = Mesh(np.array(jax.devices()), ("data",))

    def train(state, batch):
        p, bn_st, opt_st = state
        xb, yb = batch

        def loss_fn(p_):
            out, new_bn = model.apply(p_, xb, state=bn_st, train=True)
            return F.cross_entropy(out, yb), new_bn

        with profiler.nvtx_range("fwd_bwd"):
            loss, new_bn, grads = amp.scaled_grad(
                loss_fn, p, opt_st, has_aux=True)
            grads = ddp.allreduce_grads(grads)
        with profiler.nvtx_range("optimizer"):
            p, opt_st, _ = optimizer.step(p, opt_st, grads)
        return (p, new_bn, opt_st), jax.lax.pmean(loss, "data")

    step_sharded = jax.jit(jax.shard_map(
        train, mesh=mesh, in_specs=(P(), (P("data"), P("data"))),
        out_specs=(P(), P()), check_vma=False))
    state = (params, bn_state, opt_state)
    batch = (x, y)

    def step(st):
        return step_sharded(st, batch)[0]

    # warm the compile cache OUTSIDE the trace window so the artifact
    # is steady-state steps, not one giant XLA compile block
    state = step(state)
    jax.block_until_ready(state)
    t0 = time.perf_counter()
    state = step(state)
    jax.block_until_ready(state)
    step_ms = (time.perf_counter() - t0) * 1e3
    print(f"steady-state step: {step_ms:.1f} ms at B={B} "
          f"({jax.default_backend()}, {len(jax.devices())} dev)")

    ts = datetime.datetime.now(datetime.timezone.utc).strftime(
        "%Y%m%dT%H%M%S")
    logdir = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          f"xprof_trace_{ts}")
    profiler.start_profile(logdir)
    for i in range(ITERS):
        profiler.range_push(f"step_{i}")
        state = step(state)
        profiler.range_pop()
    jax.block_until_ready(state)
    profiler.stop_profile()

    n_files = sum(len(fs) for _, _, fs in os.walk(logdir))
    # compress to a single artifact: the session runbook auto-commits
    # artifacts/, and a raw xplane.pb tree would bloat every commit
    import shutil
    tar = shutil.make_archive(logdir, "gztar",
                              root_dir=os.path.dirname(logdir),
                              base_dir=os.path.basename(logdir))
    shutil.rmtree(logdir)
    sz = os.path.getsize(tar) / 1e6
    print(f"trace captured: {tar} ({n_files} files, {ITERS} steps, "
          f"{sz:.1f} MB compressed)")


if __name__ == "__main__":
    main()
