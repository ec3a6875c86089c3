"""DCGAN training example — apex_tpu clone of the reference's
examples/dcgan/main_amp.py: two models + two optimizers under amp, each
with its own loss scaler, demonstrating the multiple-models/optimizers
initialize surface (reference passes [netD, netG] and [optD, optG] to a
single amp.initialize call and uses per-loss loss_id scalers).

The whole G+D update is one jitted step: D on real + fake, then G through
D — XLA fuses the shared fake-image forward. Synthetic 64x64 data by
default (the container has no dataset).

Rehearse on the CPU:
  JAX_PLATFORMS=cpu \
  python examples/dcgan/main_amp.py -b 8 --iters 5 --ngf 16 --ndf 16
On a TPU host (one process per chip set; `python chip_smoke.py` first):
  python examples/dcgan/main_amp.py
"""

import argparse
import os
import sys
import time

import numpy as np

_repo = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
if os.path.isdir(os.path.join(_repo, "apex_tpu")) and _repo not in sys.path:
    sys.path.insert(0, _repo)


def parse_args():
    p = argparse.ArgumentParser(description="apex_tpu DCGAN")
    p.add_argument("-b", "--batch-size", type=int, default=64)
    p.add_argument("--iters", type=int, default=200)
    p.add_argument("--nz", type=int, default=100)
    p.add_argument("--ngf", type=int, default=64)
    p.add_argument("--ndf", type=int, default=64)
    p.add_argument("--lr", type=float, default=2e-4)
    p.add_argument("--beta1", type=float, default=0.5)
    p.add_argument("--opt-level", default="O1")
    p.add_argument("--loss-scale", default=None)
    p.add_argument("--half-dtype", default=None,
                   choices=[None, "bfloat16", "float16"])
    p.add_argument("--print-freq", type=int, default=10)
    p.add_argument("--seed", type=int, default=0)
    return p.parse_args()


def main():
    args = parse_args()
    import jax
    import jax.numpy as jnp

    from apex_tpu import amp, models, optimizers
    from apex_tpu.nn import functional as F
    from apex_tpu.utils import configure_compile_cache

    configure_compile_cache()
    netG, netD = models.dcgan(nz=args.nz, ngf=args.ngf, ndf=args.ndf)

    optG = optimizers.FusedAdam(lr=args.lr, betas=(args.beta1, 0.999))
    optD = optimizers.FusedAdam(lr=args.lr, betas=(args.beta1, 0.999))

    # one initialize call, lists preserved — the reference's multi-model
    # surface (examples/dcgan uses amp.initialize([netD, netG], [optD, optG]))
    (netD, netG), (optD, optG) = amp.initialize(
        [netD, netG], [optD, optG], opt_level=args.opt_level,
        loss_scale=args.loss_scale, half_dtype=args.half_dtype)

    key = jax.random.PRNGKey(args.seed)
    kG, kD, key = jax.random.split(key, 3)
    paramsG, stateG = netG.init(kG)
    paramsD, stateD = netD.init(kD)
    optG_state = optG.init(paramsG)
    optD_state = optD.init(paramsD)

    def train_step(carry, real, z):
        paramsD, paramsG, stateD, stateG, optD_state, optG_state = carry

        fake = netG.apply(paramsG, z, state=stateG, train=True)[0]

        # --- D: real up, fake down --------------------------------------
        def d_loss(pD):
            logit_real, sD = netD.apply(pD, real, state=stateD, train=True)
            logit_fake, sD2 = netD.apply(pD, jax.lax.stop_gradient(fake),
                                         state=sD, train=True)
            loss = F.binary_cross_entropy_with_logits(
                logit_real, jnp.ones_like(logit_real)) + \
                F.binary_cross_entropy_with_logits(
                    logit_fake, jnp.zeros_like(logit_fake))
            return loss, sD2

        lossD, new_stateD, gD = amp.scaled_grad(d_loss, paramsD, optD_state,
                                                has_aux=True)
        paramsD, optD_state, _ = optD.step(paramsD, optD_state, gD)

        # --- G: fool the updated D --------------------------------------
        def g_loss(pG):
            fake, sG = netG.apply(pG, z, state=stateG, train=True)
            logit, _ = netD.apply(paramsD, fake, state=new_stateD, train=True)
            return F.binary_cross_entropy_with_logits(
                logit, jnp.ones_like(logit)), sG

        lossG, new_stateG, gG = amp.scaled_grad(g_loss, paramsG, optG_state,
                                                has_aux=True)
        paramsG, optG_state, _ = optG.step(paramsG, optG_state, gG)

        return (paramsD, paramsG, new_stateD, new_stateG, optD_state,
                optG_state), (lossD, lossG)

    step = jax.jit(train_step, donate_argnums=(0,))

    carry = (paramsD, paramsG, stateD, stateG, optD_state, optG_state)
    rng = np.random.RandomState(args.seed)
    t0 = time.time()
    for i in range(args.iters):
        real = jnp.asarray(rng.randn(args.batch_size, 3, 64, 64),
                           jnp.float32)
        z = jnp.asarray(rng.randn(args.batch_size, args.nz, 1, 1),
                        jnp.float32)
        carry, (lossD, lossG) = step(carry, real, z)
        if i % args.print_freq == 0 or i == args.iters - 1:
            jax.block_until_ready(lossD)
            print(f"[{i:4d}/{args.iters}] loss_D {float(lossD):7.4f} "
                  f"loss_G {float(lossG):7.4f} "
                  f"({(time.time() - t0) / (i + 1) * 1000:.1f} ms/it)")
    print("done")


if __name__ == "__main__":
    main()
