"""ImageNet training example — apex_tpu clone of the reference's
examples/imagenet/main_amp.py: the 3-line amp enablement + DDP wrap, same
CLI surface (--opt-level, --loss-scale, --keep-batchnorm-fp32, --sync_bn,
--b, --prof), adapted to JAX: data-parallel over the device mesh via
shard_map, synthetic ImageNet-shaped data by default (the container has no
dataset; pass --data for a real numpy-file pipeline).

Rehearse on the CPU mesh:
  JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
  python examples/imagenet/main_amp.py --arch resnet18 --b 8 --iters 10
On a TPU host (one process per chip set; `python chip_smoke.py` first):
  python examples/imagenet/main_amp.py --b 128

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

# allow running straight from a source checkout
_repo = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
if os.path.isdir(os.path.join(_repo, "apex_tpu")) and _repo not in sys.path:
    sys.path.insert(0, _repo)


def parse_args(argv=None):
    p = argparse.ArgumentParser(description="apex_tpu ImageNet training")
    p.add_argument("--data", default=None,
                   help="optional .npz with images/labels; synthetic if unset")
    p.add_argument("--arch", "-a", default="resnet50",
                   choices=["resnet18", "resnet34", "resnet50",
                            "resnet101", "resnet152"])
    p.add_argument("-b", "--batch-size", type=int, default=128,
                   help="per-device batch size")
    p.add_argument("--epochs", type=int, default=1)
    p.add_argument("--iters", type=int, default=100,
                   help="iterations per epoch (synthetic data)")
    p.add_argument("--lr", type=float, default=0.1)
    p.add_argument("--lr-decay-epochs", type=int, default=30,
                   help="epoch period of the reference's step decay "
                        "(lr * 0.1^(epoch//N), main_amp.py:490-501)")
    p.add_argument("--warmup-epochs", type=int, default=0,
                   help="linear LR warmup epochs (reference's scaled-LR "
                        "recipe ramps over the first 5 epochs)")
    p.add_argument("--momentum", type=float, default=0.9)
    p.add_argument("--weight-decay", type=float, default=1e-4)
    p.add_argument("--target-acc", type=float, default=None,
                   help="exit non-zero unless final val Prec@1 reaches "
                        "this (convergence gate)")
    p.add_argument("--print-freq", type=int, default=10)
    p.add_argument("--image-size", type=int, default=224)
    p.add_argument("--opt-level", default="O2")
    p.add_argument("--loss-scale", default=None)
    p.add_argument("--keep-batchnorm-fp32", default=None)
    p.add_argument("--half-dtype", default=None,
                   choices=[None, "bfloat16", "float16"])
    p.add_argument("--stem", default="conv7",
                   choices=["conv7", "space_to_depth"],
                   help="stem form: torchvision 7x7/s2 conv (reference "
                        "parity) or the MLPerf-TPU exact space-to-depth "
                        "rewrite (see models.resnet.stem_weight_to_s2d)")
    p.add_argument("--channels-last", action="store_true",
                   help="run the whole pipeline NHWC: loader delivery, "
                        "model input, and every internal activation "
                        "(channels on the TPU's 128-lane minor axis)")
    p.add_argument("--sync_bn", action="store_true",
                   help="convert BatchNorm to SyncBatchNorm")
    p.add_argument("--fused-adam", action="store_true",
                   help="use FusedAdam instead of SGD")
    p.add_argument("--zero", action="store_true",
                   help="ZeRO-1: shard optimizer state over the data "
                        "axis (reduce-scatter grads, all-gather params)")
    p.add_argument("--prof", action="store_true",
                   help="emit a jax profiler trace of 10 hot iterations")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--checkpoint-dir", default=None,
                   help="save an epoch checkpoint here (keep last 3)")
    p.add_argument("--resume", action="store_true",
                   help="resume from the latest checkpoint in "
                        "--checkpoint-dir")
    return p.parse_args(argv)


def build(args):
    """Everything up to (not including) the first step: data source,
    amp-initialized model + optimizer, DDP wrapper, mesh, placed state
    and the jitted, state-donating train/eval steps."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from apex_tpu import amp, optimizers, parallel, models
    from apex_tpu.nn import functional as F
    from apex_tpu.observability.compilation import instrumented_jit

    ndev = len(jax.devices())
    print(f"=> {ndev} device(s) on backend {jax.default_backend()}")
    print(f"=> creating model '{args.arch}'")
    # with --channels-last the whole pipeline is NHWC end to end: the
    # loader delivers NHWC (no host transpose), the model consumes it
    # directly (input_format), and every internal activation stays NHWC
    fmt = "NHWC" if args.channels_last else "NCHW"
    model = getattr(models, args.arch)(channels_last=args.channels_last,
                                       input_format=fmt, stem=args.stem)
    if args.sync_bn:
        print("using apex_tpu synced BN")
        model = parallel.convert_syncbn_model(model)

    global_batch = args.batch_size * ndev
    rng = np.random.RandomState(args.seed)
    val_images = val_labels = None
    if args.data:
        blob = np.load(args.data)
        if "val_images" in getattr(blob, "files", ()):
            val_images = blob["val_images"]
            val_labels = blob["val_labels"].astype(np.int32)
        if len(blob["images"]) < global_batch:
            raise SystemExit(
                f"dataset has {len(blob['images'])} images < one global "
                f"batch ({global_batch}); lower --batch-size")
        if (blob["images"].dtype == np.uint8
                and blob["images"].shape[-1] == 3):
            # NHWC uint8 -> the native prefetching pipeline (C++ worker
            # threads normalize + assemble batches ahead of the loop)
            from apex_tpu.data import DataLoader
            loader = DataLoader(blob["images"], blob["labels"],
                                batch_size=global_batch, shuffle=True,
                                seed=args.seed, data_format=fmt)
            print(f"=> native data loader: {loader.native} "
                  f"({loader.batches_per_epoch} batches/epoch)")
            args.iters = min(args.iters, loader.batches_per_epoch)

            def get_batch(i):
                imgs, lbls, _ = loader.next_batch()
                return imgs, lbls
        else:
            # float blobs are NCHW by contract (uint8 blobs are NHWC);
            # no layout sniffing — transpose exactly when the model
            # consumes NHWC
            images_all = blob["images"].astype(np.float32)
            if images_all.shape[1] != 3:
                raise SystemExit(
                    f"float image blobs must be NCHW with C=3, got "
                    f"shape {images_all.shape}")
            if fmt == "NHWC":
                images_all = np.ascontiguousarray(
                    images_all.transpose(0, 2, 3, 1))
            labels_all = blob["labels"].astype(np.int32)
            n_batches = len(images_all) // global_batch
            args.iters = min(args.iters, n_batches)

            def get_batch(i):
                s = (i % n_batches) * global_batch
                return (images_all[s:s + global_batch],
                        labels_all[s:s + global_batch])
    else:
        shape = ((global_batch, args.image_size, args.image_size, 3)
                 if fmt == "NHWC"
                 else (global_batch, 3, args.image_size, args.image_size))
        images_all = rng.randn(*shape).astype(np.float32)
        labels_all = rng.randint(0, 1000, global_batch).astype(np.int32)

        def get_batch(i):
            return images_all, labels_all

    # fail misconfigurations at startup, not after an epoch of training:
    # a convergence gate needs a val split, and the val split must cover
    # at least one global batch
    if args.target_acc is not None and val_images is None:
        raise SystemExit("--target-acc set but the data blob has no "
                         "val_images/val_labels split — the gate would "
                         "silently never run")
    if val_images is not None and len(val_images) < global_batch:
        raise SystemExit(f"val split ({len(val_images)}) smaller than one "
                         f"global batch ({global_batch}); lower "
                         f"--batch-size")
    # preprocess the val split ONCE (not per epoch): same normalization
    # the training loader applies
    val_x = None
    if val_images is not None:
        if val_images.dtype == np.uint8 and val_images.shape[-1] == 3:
            from apex_tpu import _native
            from apex_tpu.data import IMAGENET_MEAN, IMAGENET_STD
            val_x = _native.preprocess_images(val_images, IMAGENET_MEAN,
                                              IMAGENET_STD, fmt)
        else:
            val_x = val_images.astype(np.float32)
            if fmt == "NHWC":
                val_x = np.ascontiguousarray(val_x.transpose(0, 2, 3, 1))

    # LR recipe after the data section so the schedule knows the real
    # iters/epoch: the reference's step decay lr * 0.1^(epoch // N)
    # (main_amp.py:490-501) plus optional linear warmup, expressed as a
    # step->lr schedule traced into the jitted step (no re-compile on
    # epoch boundaries)
    iters_per_epoch = max(args.iters, 1)

    def lr_schedule(step):
        epoch = step // iters_per_epoch
        lr = args.lr * jnp.power(
            0.1, (epoch // args.lr_decay_epochs).astype(jnp.float32))
        if args.warmup_epochs:
            warm = args.warmup_epochs * iters_per_epoch
            lr = lr * jnp.minimum(1.0, (step + 1.0) / warm)
        return lr

    if args.fused_adam:
        optimizer = optimizers.FusedAdam(lr=lr_schedule,
                                         weight_decay=args.weight_decay)
    else:
        optimizer = optimizers.SGD(lr=lr_schedule, momentum=args.momentum,
                                   weight_decay=args.weight_decay)

    model, optimizer = amp.initialize(
        model, optimizer, opt_level=args.opt_level,
        keep_batchnorm_fp32=args.keep_batchnorm_fp32,
        loss_scale=args.loss_scale, half_dtype=args.half_dtype)
    ddp = parallel.DistributedDataParallel(model)

    mesh = Mesh(np.array(jax.devices()), ("data",))
    # state lives replicated on the mesh and each global batch is split
    # over it at the host boundary — the step never sees a single-device
    # array it has to re-lay-out (and recompile for) on the second call
    replicated = NamedSharding(mesh, P())
    batch_sharding = NamedSharding(mesh, P("data"))

    def put_batch(batch):
        return jax.device_put(batch, batch_sharding)

    params, bn_state = jax.device_put(
        model.init(jax.random.PRNGKey(args.seed)), replicated)

    if args.zero:
        # ZeRO-1: per-device master/moment shards, built inside the
        # mesh; the step reduce-scatters grads itself (no DDP allreduce)
        print("=> ZeRO-1 optimizer-state sharding over the data axis")
        ospecs = amp.zero_optimizer_specs(optimizer, params, "data")
        opt_state = jax.jit(jax.shard_map(
            lambda p: optimizer.init(p, zero_axis="data"), mesh=mesh,
            in_specs=(P(),), out_specs=ospecs, check_vma=False))(params)
        state_specs = (P(), P(), ospecs)
    else:
        opt_state = jax.device_put(optimizer.init(params), replicated)
        state_specs = P()

    def step(state, batch):
        params, bn_state, opt_state = state
        x, y = batch

        def loss_fn(p):
            out, new_bn = model.apply(p, x, state=bn_state, train=True)
            return F.cross_entropy(out, y), (new_bn, out)

        loss, (new_bn, out), grads = amp.scaled_grad(
            loss_fn, params, opt_state, has_aux=True)
        if not args.zero:
            grads = ddp.allreduce_grads(grads)
        params, opt_state, info = optimizer.step(params, opt_state, grads)
        acc = jnp.mean((jnp.argmax(out, -1) == y).astype(jnp.float32))
        metrics = {"loss": lax.pmean(loss, "data"),
                   "prec1": lax.pmean(acc, "data") * 100.0,
                   "loss_scale": info["loss_scale"],
                   "found_inf": info["found_inf"]}
        return (params, new_bn, opt_state), metrics

    # the compilation ledger watches the step (a retrace mid-run is a
    # bug, and the ledger names the argument that caused it); the old
    # state's buffers are donated to the new one
    train_step = instrumented_jit(jax.shard_map(
        step, mesh=mesh,
        in_specs=(state_specs, (P("data"), P("data"))),
        out_specs=(state_specs, P()), check_vma=False),
        "imagenet.train_step", arg_names=("state", "batch"),
        donate_argnums=(0,))

    # validation pass (reference's validate(), main_amp.py:330-390):
    # eval-mode forward over the held-out split, Prec@1 pmean'd
    def _eval(state, batch):
        params, bn_st, _ = state
        x, y = batch
        out, _ = model.apply(params, x, state=bn_st, train=False)
        acc = jnp.mean((jnp.argmax(out, -1) == y).astype(jnp.float32))
        return lax.pmean(acc, "data") * 100.0

    eval_step = jax.jit(jax.shard_map(
        _eval, mesh=mesh, in_specs=(state_specs, (P("data"), P("data"))),
        out_specs=P(), check_vma=False))

    def validate(state):
        if val_x is None:
            return None
        nvb = len(val_x) // global_batch
        accs = []
        for i in range(nvb):
            s = i * global_batch
            accs.append(float(eval_step(
                state, put_batch((val_x[s:s + global_batch],
                                  val_labels[s:s + global_batch])))))
        return float(np.mean(accs))

    n_val_eval = (0 if val_x is None
                  else len(val_x) // global_batch * global_batch)

    return types.SimpleNamespace(
        model=model, optimizer=optimizer, ddp=ddp, mesh=mesh,
        state=(params, bn_state, opt_state), train_step=train_step,
        get_batch=get_batch, put_batch=put_batch, validate=validate,
        n_val_eval=n_val_eval, global_batch=global_batch, ndev=ndev,
        lr_schedule=lr_schedule, fmt=fmt)


def main(argv=None):
    args = parse_args(argv)

    import jax

    from apex_tpu import amp, optimizers, parallel, models
    from apex_tpu.utils import AverageMeter, configure_compile_cache

    configure_compile_cache()
    run = build(args)
    model, optimizer = run.model, run.optimizer
    train_step, get_batch, put_batch = (run.train_step, run.get_batch,
                                        run.put_batch)
    validate, n_val_eval = run.validate, run.n_val_eval
    global_batch, ndev, fmt = run.global_batch, run.ndev, run.fmt
    lr_schedule, state = run.lr_schedule, run.state

    start_epoch = 0
    if args.checkpoint_dir and args.resume:
        from apex_tpu.utils import checkpoint as ckpt
        last = ckpt.latest_step(args.checkpoint_dir)
        if last is not None:
            try:
                state = ckpt.restore_checkpoint(args.checkpoint_dir, state,
                                                step=last)
            except ValueError as e:
                # only the conv1 stem mismatch is convertible; any other
                # shape drift (num_classes, arch) is a real user error
                if args.stem != "space_to_depth" or "conv1" not in str(e):
                    raise
                if args.zero:
                    raise SystemExit(
                        "resuming a conv7 checkpoint into --stem "
                        "space_to_depth is not supported with --zero "
                        "(the sharded optimizer state cannot be "
                        "re-templated in-process); convert offline with "
                        "models.convert_stem_to_s2d")
                # conv7-trained checkpoint: restore into a conv7-shaped
                # template, exactly convert the stem weight
                # (models.convert_stem_to_s2d), reinit optimizer state
                print("=> checkpoint has the conv7 stem; converting "
                      "(identical function; optimizer moments and loss "
                      "scale reset)")
                m7 = getattr(models, args.arch)(
                    channels_last=args.channels_last, input_format=fmt,
                    stem="conv7")
                if args.sync_bn:
                    m7 = parallel.convert_syncbn_model(m7)
                m7, _ = amp.initialize(
                    m7, optimizers.SGD(lr=lr_schedule),
                    opt_level=args.opt_level,
                    keep_batchnorm_fp32=args.keep_batchnorm_fp32,
                    loss_scale=args.loss_scale,
                    half_dtype=args.half_dtype, verbosity=0)
                p7, bn7 = m7.init(jax.random.PRNGKey(args.seed))
                # template (params, bn) only: restore_checkpoint reads
                # just the template's leaves, so the stored optimizer
                # state (discarded anyway) is never materialized
                p7, bn7 = ckpt.restore_checkpoint(
                    args.checkpoint_dir, (p7, bn7), step=last)
                p_new = models.convert_stem_to_s2d(p7)
                state = (p_new, bn7, optimizer.init(p_new))
            # back onto the mesh, laid out as build() placed the fresh one
            state = jax.tree_util.tree_map(
                lambda x, like: jax.device_put(x, like.sharding),
                state, run.state)
            start_epoch = last
            print(f"=> resumed from epoch {last} "
                  f"(reference main_amp.py:170-185 resume flow)")
            if start_epoch >= args.epochs:
                print(f"=> nothing to do: resumed epoch {start_epoch} >= "
                      f"--epochs {args.epochs}")
                return 0.0

    print("=> compiling train step...")
    t0 = time.time()
    state, metrics = train_step(state, put_batch(get_batch(0)))
    jax.block_until_ready(metrics)
    print(f"=> compiled in {time.time() - t0:.1f}s")

    batch_time = AverageMeter()
    losses = AverageMeter()
    top1 = AverageMeter()
    val_acc = None

    for epoch in range(start_epoch, args.epochs):
        end = time.time()
        for i in range(args.iters):
            if args.prof and epoch == 0 and i == 10:
                jax.profiler.start_trace("/tmp/apex_tpu_trace")
            state, metrics = train_step(state, put_batch(get_batch(i)))
            jax.block_until_ready(metrics)
            if args.prof and epoch == 0 and i == 20:
                jax.profiler.stop_trace()
            batch_time.update(time.time() - end)
            end = time.time()
            losses.update(float(metrics["loss"]))
            top1.update(float(metrics["prec1"]))
            if i % args.print_freq == 0:
                ips = global_batch / batch_time.val
                print(f"Epoch: [{epoch}][{i}/{args.iters}]  "
                      f"Time {batch_time.val:.3f} ({batch_time.avg:.3f})  "
                      f"Speed {ips:.1f} img/s  "
                      f"Loss {losses.val:.4f} ({losses.avg:.4f})  "
                      f"Prec@1 {top1.val:.2f}  "
                      f"scale {float(metrics['loss_scale']):.0f}")
        val_acc = validate(state)
        if val_acc is not None:
            # n_val_eval, not len(val_labels): the remainder batch is
            # dropped, and claiming otherwise would misreport the gate
            print(f" * Prec@1 {val_acc:.3f}  (epoch {epoch}, "
                  f"{n_val_eval} val images)")
        if args.checkpoint_dir:
            from apex_tpu.utils import checkpoint as ckpt
            ckpt.save_checkpoint(args.checkpoint_dir, epoch + 1, state,
                                 keep=3)
    ips = (global_batch / batch_time.avg if batch_time.avg > 0 else 0.0)
    print(f"=> done. avg {ips:.1f} img/s over {args.iters} iters "
          f"({ips / ndev if ndev else 0.0:.1f} img/s/device)")
    # val_acc already covers the final state: the last loop iteration
    # validated after the last step
    if val_acc is None:
        val_acc = validate(state)
    if val_acc is not None:
        print(f"=> FINAL val Prec@1 {val_acc:.3f}")
        if args.target_acc is not None and val_acc < args.target_acc:
            raise SystemExit(
                f"convergence gate FAILED: val Prec@1 {val_acc:.2f} < "
                f"target {args.target_acc}")
        if args.target_acc is not None:
            print(f"=> convergence gate PASSED "
                  f"(>= {args.target_acc})")
    return ips


if __name__ == "__main__":
    main()
