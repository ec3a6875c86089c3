"""Shared L1 runner: train ResNet-18 under one amp config and record the
exact loss trajectory + a final-parameter digest.

The apex_tpu analogue of the reference's instrumented L1 trainer
(tests/L1/common/main_amp.py: run_info_dict of per-iteration Loss/Speed,
keyed by config) — same discipline, TPU-shaped: one deterministic synthetic
dataset, two dispatch paths (Pallas kernels vs pure jnp), bitwise
comparison where dtypes make it meaningful (compare.py:35-64).
"""

from __future__ import annotations

import hashlib
import os
from typing import Optional

import numpy as np


def train_one(opt_level: str, loss_scale: Optional[str],
              keep_bn: Optional[str], pallas: bool, iters: int = 100,
              batch: int = 16, image: int = 32, arch: str = "resnet18",
              lr: float = 1e-3, nbatches: int = 10):
    """Returns (loss_trajectory float32 array, sha256 of final params)."""
    import jax
    import jax.numpy as jnp
    from apex_tpu import amp, models, optimizers
    from apex_tpu.nn import functional as F

    # FORCE_PALLAS=1 reproduces the production TPU dispatch (fused
    # optimizer / multi-tensor / flash kernels Pallas, BN jnp): Adam
    # turns any sub-ulp grad difference near zero into a full ±lr step,
    # so bitwise trajectories require the fwd/bwd to be the *same* XLA
    # program in both runs
    old = {k: os.environ.pop(k, None)
           for k in ("APEX_TPU_FORCE_PALLAS", "APEX_TPU_DISABLE_PALLAS")}
    if pallas:
        os.environ["APEX_TPU_FORCE_PALLAS"] = "1"
    else:
        os.environ["APEX_TPU_DISABLE_PALLAS"] = "1"
    env_key = ("APEX_TPU_FORCE_PALLAS" if pallas
               else "APEX_TPU_DISABLE_PALLAS")
    try:
        model, optimizer = amp.initialize(
            getattr(models, arch)(num_classes=10),
            optimizers.FusedAdam(lr=lr), opt_level=opt_level,
            loss_scale=loss_scale, keep_batchnorm_fp32=keep_bn,
            verbosity=0, hard_override=True)
        params, bn_state = model.init(jax.random.PRNGKey(0))
        opt_state = optimizer.init(params)

        rng = np.random.RandomState(0)
        xs = jnp.asarray(rng.randn(nbatches, batch, 3, image, image),
                         jnp.float32)
        ys = jnp.asarray(rng.randint(0, 10, (nbatches, batch)), jnp.int32)

        def step(params, bn_state, opt_state, x, y):
            def loss_fn(p):
                out, s = model.apply(p, x, state=bn_state, train=True)
                return F.cross_entropy(out, y), s

            loss, new_bn, grads = amp.scaled_grad(loss_fn, params,
                                                  opt_state, has_aux=True)
            params, opt_state, info = optimizer.step(params, opt_state,
                                                     grads)
            return params, new_bn, opt_state, loss

        jstep = jax.jit(step)
        traj = np.zeros((iters,), np.float32)
        for i in range(iters):
            params, bn_state, opt_state, loss = jstep(
                params, bn_state, opt_state, xs[i % nbatches],
                ys[i % nbatches])
            traj[i] = np.float32(float(loss))
        digest = hashlib.sha256()
        for leaf in jax.tree_util.tree_leaves(params):
            digest.update(np.asarray(leaf).tobytes())
        return traj, digest.hexdigest()
    finally:
        os.environ.pop(env_key, None)
        for k, v in old.items():
            if v is not None:
                os.environ[k] = v


# the reference driver's matrix (tests/L1/common/run_test.sh:64-135):
# {O0..O3} x {default, 1.0, 128.0, dynamic} x {keep_batchnorm_fp32 unset/
# True/False}
FULL_MATRIX = [
    (ol, ls, kbn)
    for ol in ("O0", "O1", "O2", "O3")
    for ls in (None, "1.0", "128.0", "dynamic")
    for kbn in (None, "True", "False")
]


def is_fp32_config(opt_level: str) -> bool:
    """Configs whose whole numeric path is fp32 — where the reference
    demands bitwise equality between extension and Python paths."""
    return opt_level == "O0"
