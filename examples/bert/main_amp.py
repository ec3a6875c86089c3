"""BERT pretraining example — the FusedLayerNorm + FusedAdam / FusedLAMB
benchmark configs (BASELINE.md #4 BERT-base Adam, #5 BERT-large LAMB
large-batch).  MLM + NSP on synthetic data, amp O2, data-parallel over the
device mesh.  The reference has no BERT example of its own — these configs
are how its kernels were consumed downstream (BASELINE.md); this script is
the runnable equivalent.

Rehearse on the CPU mesh:
  JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
  python examples/bert/main_amp.py --config tiny -b 2 --iters 5
On a TPU host (one process per chip set; `python chip_smoke.py` first):
  python examples/bert/main_amp.py --config base -b 32

``build(args)`` returns the model, mesh, state and jitted train step that
``main()`` loops over; ``chip_smoke.py`` and the tests drive the same
objects.
"""

import argparse
import os
import sys
import time
import types

import numpy as np

_repo = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
if os.path.isdir(os.path.join(_repo, "apex_tpu")) and _repo not in sys.path:
    sys.path.insert(0, _repo)


def parse_args(argv=None):
    p = argparse.ArgumentParser(description="apex_tpu BERT pretraining")
    p.add_argument("--config", default="base",
                   choices=["tiny", "base", "large"])
    p.add_argument("-b", "--batch-size", type=int, default=8,
                   help="per-device batch size")
    p.add_argument("--seq-len", type=int, default=128)
    p.add_argument("--iters", type=int, default=50)
    p.add_argument("--optimizer", default="adam", choices=["adam", "lamb"])
    p.add_argument("--lr", type=float, default=None,
                   help="default: 1e-4 adam, 4e-3 lamb (large batch)")
    p.add_argument("--opt-level", default="O2")
    p.add_argument("--loss-scale", default=None)
    p.add_argument("--half-dtype", default=None,
                   choices=[None, "bfloat16", "float16"])
    p.add_argument("--mask-prob", type=float, default=0.15)
    p.add_argument("--print-freq", type=int, default=10)
    p.add_argument("--seed", type=int, default=0)
    return p.parse_args(argv)


def build(args):
    """Everything up to (not including) the first step: synthetic data
    source, amp-initialized model + optimizer, DDP wrapper, mesh, placed
    state and the jitted, state-donating train step."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from apex_tpu import amp, models, optimizers, parallel
    from apex_tpu.observability import get_recorder
    from apex_tpu.observability.compilation import instrumented_jit

    span = get_recorder().span      # set-up by phase: build.*

    if args.config == "tiny":
        cfg = models.BertConfig(vocab_size=1024, hidden_size=64,
                                num_hidden_layers=2, num_attention_heads=4,
                                intermediate_size=128)
    elif args.config == "base":
        cfg = models.bert_base()
    else:
        cfg = models.bert_large()

    lr = args.lr or (4e-3 if args.optimizer == "lamb" else 1e-4)
    if args.optimizer == "lamb":
        optimizer = optimizers.FusedLAMB(lr=lr, weight_decay=0.01,
                                         max_grad_norm=1.0)
    else:
        optimizer = optimizers.FusedAdam(lr=lr, weight_decay=0.01)

    with span("build.amp_initialize"):
        model, optimizer = amp.initialize(
            models.BertForPretraining(cfg), optimizer,
            opt_level=args.opt_level, loss_scale=args.loss_scale,
            half_dtype=args.half_dtype)
        ddp = parallel.DistributedDataParallel(model)

    ndev = len(jax.devices())
    global_batch = args.batch_size * ndev
    mesh = Mesh(np.array(jax.devices()), ("data",))
    # state lives replicated on the mesh and each global batch is split
    # over it at the host boundary — the step never sees a single-device
    # array it has to re-lay-out (and recompile for) on the second call
    replicated = NamedSharding(mesh, P())
    batch_sharding = NamedSharding(mesh, P("data"))

    def put_batch(batch):
        return jax.device_put(batch, batch_sharding)

    with span("build.model_init"):
        params, _ = model.init(jax.random.PRNGKey(args.seed))
    with span("build.place_params"):
        params = jax.device_put(params, replicated)
    with span("build.optimizer_init"):
        opt_state = jax.device_put(optimizer.init(params), replicated)

    rng = np.random.RandomState(args.seed)
    T = args.seq_len

    def synth_batch():
        ids = rng.randint(5, cfg.vocab_size, (global_batch, T))
        mask = rng.rand(global_batch, T) < args.mask_prob
        labels = np.where(mask, ids, -100)
        ids = np.where(mask & (rng.rand(global_batch, T) < 0.8), 3, ids)
        nsp = rng.randint(0, 2, (global_batch,))
        return (ids.astype(np.int32), labels.astype(np.int32),
                nsp.astype(np.int32))

    def step(state, batch):
        params, opt_state = state
        ids, mlm_labels, nsp_labels = batch

        def loss_fn(p):
            # through model.apply so the amp cast policy is in scope
            (mlm_logits, nsp_logits), _ = model.apply(p, ids)
            with jax.named_scope("loss"):
                logp = jax.nn.log_softmax(
                    mlm_logits.astype(jnp.float32), -1)
                valid = mlm_labels != -100
                lbl = jnp.where(valid, mlm_labels, 0)
                nll = -jnp.take_along_axis(logp, lbl[..., None], -1)[..., 0]
                mlm = jnp.sum(nll * valid) / jnp.maximum(jnp.sum(valid), 1)
                nsp_logp = jax.nn.log_softmax(
                    nsp_logits.astype(jnp.float32), -1)
                nsp = -jnp.mean(jnp.take_along_axis(
                    nsp_logp, nsp_labels[:, None], -1))
                return mlm + nsp

        loss, grads = amp.scaled_grad(loss_fn, params, opt_state)
        grads = ddp.allreduce_grads(grads)
        params, opt_state, info = optimizer.step(params, opt_state, grads)
        return (params, opt_state), {"loss": lax.pmean(loss, "data"),
                                     "loss_scale": info["loss_scale"],
                                     "found_inf": info["found_inf"]}

    # the compilation ledger watches the step (a retrace mid-run is a
    # bug, and the ledger names the argument that caused it); the old
    # state's buffers are donated to the new one
    with span("build.step_wrap"):
        train_step = instrumented_jit(jax.shard_map(
            step, mesh=mesh,
            in_specs=(P(), (P("data"), P("data"), P("data"))),
            out_specs=(P(), P()), check_vma=False),
            "bert.train_step", arg_names=("state", "batch"),
            donate_argnums=(0,))

    return types.SimpleNamespace(
        model=model, optimizer=optimizer, ddp=ddp, mesh=mesh,
        state=(params, opt_state), train_step=train_step,
        get_batch=lambda i: synth_batch(), put_batch=put_batch,
        global_batch=global_batch, ndev=ndev)


def main(argv=None):
    args = parse_args(argv)
    import jax

    from apex_tpu.utils import AverageMeter, configure_compile_cache

    configure_compile_cache()
    run = build(args)
    train_step, get_batch, put_batch = (run.train_step, run.get_batch,
                                        run.put_batch)
    global_batch, ndev, state = run.global_batch, run.ndev, run.state
    print(f"=> BERT-{args.config} {args.optimizer} "
          f"global batch {global_batch} seq {args.seq_len}; compiling...")
    t0 = time.time()
    state, metrics = train_step(state, put_batch(get_batch(0)))
    jax.block_until_ready(metrics)
    print(f"=> compiled in {time.time() - t0:.1f}s")

    batch_time = AverageMeter()
    losses = AverageMeter()
    end = time.time()
    for i in range(args.iters):
        state, metrics = train_step(state, put_batch(get_batch(i)))
        jax.block_until_ready(metrics)
        batch_time.update(time.time() - end)
        end = time.time()
        losses.update(float(metrics["loss"]))
        if i % args.print_freq == 0 or i == args.iters - 1:
            sps = global_batch / batch_time.val
            print(f"[{i:4d}/{args.iters}]  "
                  f"Time {batch_time.val:.3f} ({batch_time.avg:.3f})  "
                  f"Speed {sps:.1f} seq/s  "
                  f"Loss {losses.val:.4f} ({losses.avg:.4f})  "
                  f"scale {float(metrics['loss_scale']):.0f}")
    sps = global_batch / batch_time.avg
    print(f"=> done. avg {sps:.1f} seq/s ({sps / ndev:.2f} seq/s/device)")
    return sps


if __name__ == "__main__":
    main()
