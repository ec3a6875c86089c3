"""GPT causal-LM example: train on a character corpus, then generate.

Demonstrates the decoder-only path end-to-end — causal flash attention,
amp O2, DDP over the mesh, and KV-cached generation — on a
self-contained char-level corpus (no dataset download; pass --text for
your own file).  The reference toolkit has no decoder example; this is
the runnable form of the framework's long-context/serving surface.

Rehearse on the CPU mesh:
  JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
  python examples/gpt/main_amp.py --config tiny --iters 20 --generate 64
On a TPU host (one process per chip set; `python chip_smoke.py` first):
  python examples/gpt/main_amp.py --config small -b 8
The per-layer decoder with routed experts, from a published config file
(--arch names the file's ``model_type``):
  python examples/gpt/main_amp.py --arch laguna -b 2 --block-size 8192 \
      --model-config benchmark/configs/laguna-xs2.json
  python examples/gpt/main_amp.py --arch mellum -b 1 --block-size 8192 \
      --model-config benchmark/configs/mellum2-12b.json
  python examples/gpt/main_amp.py --arch lfm2_moe -b 2 --block-size 8192 \
      --model-config benchmark/configs/lfm2-8b-a1b.json
  python examples/gpt/main_amp.py --arch ouro -b 1 --block-size 8192 \
      --model-config benchmark/configs/ouro-2.6b.json
and the one-branch decoder of models/nemotron_h.py (Mamba-2 mixers, routed
relu2 experts, position-free attention) the same way:
  python examples/gpt/main_amp.py --arch nemotron_h -b 1 --block-size 8192 \
      --model-config benchmark/configs/nemotron3-nano-30b-a3b.json
and the latent-attention decoder of models/deepseek_v3.py:
  python examples/gpt/main_amp.py --arch deepseek_v3 -b 2 --block-size 8192 \
      --model-config benchmark/configs/kanana-2-30b-a3b.json

``build(args)`` returns the model, mesh, state and jitted train step that
``main()`` loops over; the benchmark and the tests drive the same objects.
"""

import argparse
import inspect
import json
import os
import sys
import time
import types

import numpy as np

_repo = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
if os.path.isdir(os.path.join(_repo, "apex_tpu")) and _repo not in sys.path:
    sys.path.insert(0, _repo)

# --arch values built from a --model-config file: by models/laguna.py, and
# (nemotron_h, deepseek_v3) by the modules of those names over the same parts
PER_LAYER_ARCHS = ("laguna", "mellum", "lfm2_moe", "ouro", "nemotron_h",
                   "deepseek_v3")

# enough structure to be learnable at tiny scale: a looping pangram
_BUILTIN_TEXT = ("the quick brown fox jumps over the lazy dog. " * 200)


def _stdlib_corpus(mb: float) -> str:
    """A real multi-megabyte text corpus with zero downloads: the
    Python standard library's own sources, concatenated in sorted
    (deterministic) file order and ASCII-filtered, truncated to ``mb``
    megabytes.  Real code text has genuine structure (syntax,
    identifiers, indentation) a char LM must learn — a substantive
    step past toy pangrams for the convergence gate when the machine
    has no datasets."""
    import glob
    import sysconfig
    root = sysconfig.get_paths()["stdlib"]
    parts, total, limit = [], 0, int(mb * 1e6)
    for path in sorted(glob.glob(os.path.join(root, "*.py"))):
        try:
            with open(path, encoding="utf-8", errors="ignore") as f:
                t = f.read()
        except OSError:
            continue
        t = "".join(c for c in t if c == "\n" or 32 <= ord(c) < 127)
        parts.append(t)
        total += len(t)
        if total >= limit:
            break
    text = "".join(parts)[:limit]
    if len(text) < limit:
        print(f"=> stdlib corpus smaller than requested: "
              f"{len(text) / 1e6:.1f} MB")
    return text


def parse_args(argv=None):
    p = argparse.ArgumentParser(description="apex_tpu GPT training")
    p.add_argument("--arch", default="gpt",
                   choices=["gpt", "llama", *PER_LAYER_ARCHS],
                   help="decoder family: GPT-2 (LayerNorm + learned "
                        "positions), Llama (RMSNorm + RoPE + SwiGLU "
                        "+ GQA), or a per-layer decoder of "
                        "models/laguna.py (window and full attention, "
                        "routed experts) built from --model-config: "
                        "laguna (dense first layer, gated attention, "
                        "sigmoid router, shared expert), mellum (every "
                        "layer sparse, softmax router, no shared expert), "
                        "lfm2_moe (gated short-convolution layers 3:1 "
                        "with attention, a selection bias on the router, "
                        "a tied head) or ouro (dense layers applied "
                        "total_ut_steps times over the same weights, "
                        "sandwich norms, a learned exit gate); or "
                        "nemotron_h, the one-branch decoder of "
                        "models/nemotron_h.py (Mamba-2 mixers, routed relu2 "
                        "experts with a shared one, attention without "
                        "positions, by hybrid_override_pattern); or "
                        "deepseek_v3, models/deepseek_v3.py (multi-head "
                        "latent attention in every layer, a leading dense "
                        "layer, routed experts with shared ones behind a "
                        "sigmoid router with a selection bias)")
    p.add_argument("--model-config", default=None, metavar="JSON",
                   help="laguna, mellum, lfm2_moe, ouro, nemotron_h, "
                        "deepseek_v3: a config file "
                        "with the published keys, whose model_type is "
                        "--arch (benchmark/configs/laguna-xs2.json, "
                        "mellum2-12b.json, lfm2-8b-a1b.json, "
                        "ouro-2.6b.json, nemotron3-nano-30b-a3b.json, "
                        "kanana-2-30b-a3b.json); the "
                        "sequence length is "
                        "--block-size")
    p.add_argument("--n-kv-head", type=int, default=None,
                   help="grouped-query attention KV heads (llama; "
                        "default MHA)")
    p.add_argument("--config", default="tiny",
                   choices=["tiny", "small", "medium"])
    p.add_argument("-b", "--batch-size", type=int, default=8,
                   help="per-device batch size")
    p.add_argument("--block-size", "--seq-len", type=int, default=None,
                   help="sequence length (default: config's)")
    p.add_argument("--iters", type=int, default=100)
    p.add_argument("--lr", type=float, default=3e-4)
    p.add_argument("--weight-decay", type=float, default=0.0)
    p.add_argument("--opt-level", default="O2")
    p.add_argument("--text", default=None,
                   help="path to a UTF-8 text corpus (char-level); "
                        "built-in pangram corpus if unset")
    p.add_argument("--stdlib-corpus", type=float, default=None,
                   metavar="MB",
                   help="build a real-text corpus from the Python "
                        "stdlib sources on this machine (deterministic "
                        "sorted file order, ASCII-filtered), truncated "
                        "to MB megabytes — a no-download real dataset "
                        "for the convergence gate")
    p.add_argument("--val-frac", type=float, default=0.0,
                   help="hold out this trailing fraction of the corpus "
                        "for validation (contiguous tail, no leakage)")
    p.add_argument("--val-batches", type=int, default=8,
                   help="fixed deterministic val batches per eval")
    p.add_argument("--eval-freq", type=int, default=0,
                   help="evaluate val loss every N iters (0: only at "
                        "the end)")
    p.add_argument("--target-val-loss", type=float, default=None,
                   help="convergence gate: exit 1 if the final val "
                        "loss (nats/char) is above this")
    p.add_argument("--generate", type=int, default=0,
                   help="after training, KV-cached-generate N tokens "
                        "from a corpus prompt")
    p.add_argument("--temperature", type=float, default=0.0)
    p.add_argument("--print-freq", type=int, default=10)
    p.add_argument("--seed", type=int, default=0)
    return p.parse_args(argv)


def _corpus(args):
    if args.stdlib_corpus:
        return _stdlib_corpus(args.stdlib_corpus)
    if args.text:
        return open(args.text, encoding="utf-8").read()
    return _BUILTIN_TEXT


def _network(args, n_chars):
    """(module, sequence length) of ``--arch``."""
    from apex_tpu import models
    if args.arch in PER_LAYER_ARCHS:
        if not args.model_config:
            raise SystemExit(f"--arch {args.arch} needs --model-config "
                             f"<file>")
        with open(args.model_config) as f:
            file_cfg = json.load(f)
        if file_cfg.get("model_type", args.arch) != args.arch:
            raise SystemExit(f"--arch {args.arch}: {args.model_config} is "
                             f"a {file_cfg['model_type']!r} config")
        T = args.block_size or file_cfg.get("seq_len", 512)
        config, network = {
            "nemotron_h": (models.NemotronHConfig, models.NemotronH),
            "deepseek_v3": (models.DeepseekV3Config, models.DeepseekV3),
        }.get(args.arch, (models.LagunaConfig, models.Laguna))
        cfg = config.from_dict(
            file_cfg, max_position_embeddings=max(
                T, file_cfg.get("max_position_embeddings", T)))
        if cfg.vocab_size < n_chars:
            raise SystemExit(f"the corpus has {n_chars} characters, the "
                             f"model's vocabulary {cfg.vocab_size}")
        return network(cfg), T
    shapes = {"tiny": dict(n_layer=2, n_head=4, n_embd=64, block_size=64),
              "small": dict(n_layer=12, n_head=12, n_embd=768,
                            block_size=512),
              "medium": dict(n_layer=24, n_head=16, n_embd=1024,
                             block_size=512)}[args.config]
    if args.block_size:
        shapes["block_size"] = args.block_size
    T = shapes["block_size"]
    if args.arch == "llama":
        return models.Llama(models.LlamaConfig(
            vocab_size=max(n_chars, 2),
            hidden_size=shapes["n_embd"],
            intermediate_size=4 * shapes["n_embd"],
            num_hidden_layers=shapes["n_layer"],
            num_attention_heads=shapes["n_head"],
            num_key_value_heads=args.n_kv_head,
            max_position_embeddings=T, tie_word_embeddings=True)), T
    return models.GPT(models.GPTConfig(
        vocab_size=max(n_chars, 2), dropout=0.0,
        n_kv_head=args.n_kv_head, **shapes)), T


def build(args):
    """Everything up to (not including) the first step, as
    examples/bert/main_amp.py's ``build``: corpus, amp-initialized model +
    optimizer, DDP wrapper, mesh, placed state and the jitted,
    state-donating train step ``(state, (ids,)) -> (state, metrics)``."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from apex_tpu import amp, optimizers, parallel
    from apex_tpu.observability import get_recorder
    from apex_tpu.observability.compilation import instrumented_jit

    span = get_recorder().span      # set-up by phase: build.*
    ndev = len(jax.devices())
    text = _corpus(args)
    vocab = sorted(set(text))
    stoi = {c: i for i, c in enumerate(vocab)}
    data = np.asarray([stoi[c] for c in text], np.int32)
    n_val = int(len(data) * args.val_frac)
    val_data = data[len(data) - n_val:] if n_val else None
    data = data[:len(data) - n_val]
    print(f"=> corpus: {len(data)} train / {n_val} val chars, "
          f"vocab {len(vocab)}; {ndev} device(s) on "
          f"{jax.default_backend()}")

    net, T = _network(args, len(vocab))
    if val_data is not None and len(val_data) <= T:
        # mirrors the imagenet example's refuse-undersized-val-split
        # startup guard: run_eval needs at least one full block
        raise SystemExit(
            f"--val-frac {args.val_frac} holds out only "
            f"{len(val_data)} chars but the block size is {T}; raise "
            f"--val-frac or use a bigger corpus")

    with span("build.amp_initialize"):
        model, optimizer = amp.initialize(
            net, optimizers.FusedAdam(lr=args.lr,
                                      weight_decay=args.weight_decay),
            opt_level=args.opt_level, verbosity=0)
        ddp = parallel.DistributedDataParallel(model)
    mesh = Mesh(np.array(jax.devices()), ("data",))
    replicated = NamedSharding(mesh, P())
    batch_sharding = NamedSharding(mesh, P("data"))
    with span("build.model_init"):
        params, _ = model.init(jax.random.PRNGKey(args.seed))
    with span("build.place_params"):
        params = jax.device_put(params, replicated)
    with span("build.optimizer_init"):
        opt_state = jax.device_put(optimizer.init(params), replicated)
    B = args.batch_size * ndev
    rng = np.random.RandomState(args.seed)

    def get_batch(i=None):
        ix = rng.randint(0, len(data) - T, B)
        return (np.stack([data[i:i + T] for i in ix]),)

    def put_batch(batch):
        return jax.device_put(batch, batch_sharding)

    # a model whose loss can hand back its step sums (the expert layers'
    # counters, a looped stack's exit sums)
    with_stats = "return_stats" in inspect.signature(
        model.loss).parameters

    def step(state, batch):
        params, opt_state = state
        (ids,) = batch

        def loss_fn(p):
            if with_stats:          # + the step's sums
                return model.loss(p, ids, return_stats=True)
            return model.loss(p, ids), {}

        loss, stats, grads = amp.scaled_grad(loss_fn, params, opt_state,
                                             has_aux=True)
        grads = ddp.allreduce_grads(grads)
        params, opt_state, info = optimizer.step(params, opt_state, grads)
        return (params, opt_state), {
            "loss": lax.pmean(loss, "data"),
            "loss_scale": info["loss_scale"],
            "found_inf": info["found_inf"],
            **{k: lax.pmax(v, "data") if k.endswith("_max")
               else lax.psum(v, "data") for k, v in stats.items()}}

    # the compilation ledger watches the step; the old state's buffers
    # are donated to the new one
    with span("build.step_wrap"):
        train_step = instrumented_jit(jax.shard_map(
            step, mesh=mesh, in_specs=(P(), (P("data"),)),
            out_specs=(P(), P()), check_vma=False),
            "lm.train_step", arg_names=("state", "batch"),
            donate_argnums=(0,))

    eval_loss = jax.jit(jax.shard_map(
        lambda p, ids: lax.pmean(model.loss(p, ids), "data"),
        mesh=mesh, in_specs=(P(), P("data")), out_specs=P(),
        check_vma=False))

    def run_eval(p):
        """Mean loss over a fixed, deterministic set of val batches
        (sequential non-overlapping windows from the held-out tail)."""
        stride = max(1, (len(val_data) - T - 1) // max(
            1, args.val_batches * B))
        starts = [(i * stride) % (len(val_data) - T)
                  for i in range(args.val_batches * B)]
        tot = 0.0
        for k in range(args.val_batches):
            ix = starts[k * B:(k + 1) * B]
            ids = jnp.asarray(np.stack([val_data[i:i + T]
                                        for i in ix]))
            tot += float(eval_loss(p, ids))
        return tot / args.val_batches

    return types.SimpleNamespace(
        model=model, optimizer=optimizer, ddp=ddp, mesh=mesh,
        state=(params, opt_state), train_step=train_step,
        get_batch=get_batch, put_batch=put_batch, global_batch=B,
        ndev=ndev, seq_len=T, run_eval=run_eval,
        has_val=val_data is not None, text=text, vocab=vocab, stoi=stoi)


def main(argv=None):
    args = parse_args(argv)

    import jax
    import jax.numpy as jnp

    from apex_tpu.parallel.expert_parallel import record_moe_counters
    from apex_tpu.utils import AverageMeter, configure_compile_cache

    configure_compile_cache()
    run = build(args)
    train, get_batch, put_batch = run.train_step, run.get_batch, run.put_batch
    B, ndev, T, state = run.global_batch, run.ndev, run.seq_len, run.state
    print("=> compiling train step...")
    t0 = time.time()
    state, metrics = train(state, put_batch(get_batch()))
    jax.block_until_ready(metrics)
    print(f"=> compiled in {time.time() - t0:.1f}s")

    bt, losses = AverageMeter(), AverageMeter()
    end = time.time()
    for i in range(args.iters):
        state, metrics = train(state, put_batch(get_batch()))
        jax.block_until_ready(metrics)
        bt.update(time.time() - end)
        end = time.time()
        losses.update(float(metrics["loss"]))
        record_moe_counters(metrics)
        if i % args.print_freq == 0:
            print(f"iter [{i}/{args.iters}]  Time {bt.val:.3f} "
                  f"({bt.avg:.3f})  Speed {B / bt.val:.1f} seq/s  "
                  f"Loss {losses.val:.4f} ({losses.avg:.4f})")
        if (run.has_val and args.eval_freq
                and i and i % args.eval_freq == 0):
            print(f"iter [{i}/{args.iters}]  val_loss "
                  f"{run.run_eval(state[0]):.4f}")
    if bt.avg > 0:
        print(f"=> done. avg {B / bt.avg:.1f} seq/s "
              f"({B / bt.avg / ndev:.1f} seq/s/device)")
    else:
        print("=> done. (no timed iterations)")

    final_val = None
    if run.has_val:
        final_val = run.run_eval(state[0])
        uniform = float(np.log(max(len(run.vocab), 2)))
        print(f"FINAL val_loss {final_val:.4f} nats/char "
              f"(uniform {uniform:.2f})")
    if args.target_val_loss is not None:
        if final_val is None:
            raise SystemExit("--target-val-loss needs --val-frac > 0")
        ok = final_val <= args.target_val_loss
        print(f"convergence gate: val_loss {final_val:.4f} "
              f"{'<=' if ok else '>'} target {args.target_val_loss} "
              f"-> {'PASS' if ok else 'FAIL'}")
        if not ok:
            raise SystemExit(1)

    if args.generate:
        if args.arch in PER_LAYER_ARCHS:
            raise SystemExit("--generate: the per-layer decoders have no cached "
                             "decoding (training and full forward only)")
        params, stoi = state[0], run.stoi
        prompt = run.text[:min(16, T // 2)]
        buf = np.zeros((1, T), np.int32)
        buf[0, :len(prompt)] = [stoi[c] for c in prompt]
        n = min(args.generate, T - len(prompt))
        gen_rng = (jax.random.PRNGKey(args.seed)
                   if args.temperature > 0 else None)
        out, flen = jax.jit(lambda p, b: run.model.generate_cached(
            p, b, len(prompt), n, temperature=args.temperature,
            rng=gen_rng))(params, jnp.asarray(buf))
        toks = np.asarray(out)[0][:int(flen[0])]
        itos = {i: c for c, i in stoi.items()}
        # vocab is padded to >= 2; a padding id has no corpus char
        print("=> sample:", "".join(itos.get(int(t), "\ufffd")
                                    for t in toks))
    return losses.avg


if __name__ == "__main__":
    main()
