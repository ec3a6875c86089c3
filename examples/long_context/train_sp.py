"""Long-context training example: causal transformer LM with ring-attention
sequence parallelism over a (data, sp) mesh.

No reference equivalent (the 2019 snapshot predates attention); this is
the runnable face of apex_tpu's first-class long-context support: the
sequence dimension is sharded across the ``sp`` mesh axis, K/V blocks
rotate over ICI inside ``ring_attention``, activations per device stay
O(T/n), and the whole thing composes with amp O2 + DDP grad psum on the
``data`` axis.

Rehearse on the CPU mesh (2 dp x 4 sp):
  JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
  python examples/long_context/train_sp.py --dp 2 --sp 4 --seq-len 512
On a four-chip TPU host (one process; `python chip_smoke.py` first):
  python examples/long_context/train_sp.py --dp 1 --sp 4 --seq-len 4096

Run ulysses instead of ring: add --strategy ulysses
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
    p = argparse.ArgumentParser(description="apex_tpu long-context LM")
    p.add_argument("--dp", type=int, default=2)
    p.add_argument("--sp", type=int, default=4)
    p.add_argument("-b", "--batch-size", type=int, default=2,
                   help="per-dp-group batch size")
    p.add_argument("--seq-len", type=int, default=512,
                   help="GLOBAL sequence length (sharded over sp)")
    p.add_argument("--vocab", type=int, default=1024)
    p.add_argument("--dim", type=int, default=128)
    p.add_argument("--heads", type=int, default=8)
    p.add_argument("--layers", type=int, default=2)
    p.add_argument("--iters", type=int, default=10)
    p.add_argument("--lr", type=float, default=3e-4)
    p.add_argument("--strategy", choices=["ring", "ulysses"], default="ring")
    p.add_argument("--opt-level", default="O2")
    p.add_argument("--print-freq", type=int, default=2)
    p.add_argument("--seed", type=int, default=0)
    return p.parse_args()


def main():
    args = parse_args()
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import PartitionSpec as P

    from apex_tpu import amp, optimizers, parallel
    from apex_tpu.transformer import ring_self_attention, \
        ulysses_self_attention
    from apex_tpu.utils import configure_compile_cache

    configure_compile_cache()
    ndev = len(jax.devices())
    assert args.dp * args.sp <= ndev, (
        f"need {args.dp * args.sp} devices, have {ndev}")
    mesh = parallel.make_mesh(devices=jax.devices()[:args.dp * args.sp],
                              data=args.dp, sp=args.sp)
    print("=>", parallel.mesh_info(mesh))

    E, H, L, V, T = args.dim, args.heads, args.layers, args.vocab, \
        args.seq_len
    assert T % args.sp == 0

    sp_attn = (ring_self_attention if args.strategy == "ring"
               else ulysses_self_attention)

    rng = np.random.RandomState(args.seed)

    def init_params():
        def lin(*shape):
            return jnp.asarray(rng.randn(*shape) / np.sqrt(shape[-1]),
                               jnp.float32)
        layer = lambda: {
            "ln1_w": jnp.ones((E,)), "ln1_b": jnp.zeros((E,)),
            "wqkv": lin(3 * E, E), "wo": lin(E, E),
            "ln2_w": jnp.ones((E,)), "ln2_b": jnp.zeros((E,)),
            "w1": lin(4 * E, E), "w2": lin(E, 4 * E),
        }
        return {"embed": lin(V, E),
                "pos": lin(T, E) * 0.02,
                "layers": [layer() for _ in range(L)],
                "lnf_w": jnp.ones((E,)), "lnf_b": jnp.zeros((E,))}

    def ln(x, w, b):
        x32 = x.astype(jnp.float32)
        m = jnp.mean(x32, -1, keepdims=True)
        v = jnp.var(x32, -1, keepdims=True)
        return ((x32 - m) * jax.lax.rsqrt(v + 1e-5) * w + b).astype(x.dtype)

    def forward(params, ids, t0):
        # ids: (B, T/sp) local shard; t0: this shard's global offset
        x = params["embed"][ids] + \
            lax.dynamic_slice_in_dim(params["pos"], t0, ids.shape[1])
        half = jnp.bfloat16 if args.opt_level in ("O2", "O3") else \
            jnp.float32
        x = x.astype(half)
        for lyr in params["layers"]:
            h = ln(x, lyr["ln1_w"], lyr["ln1_b"])
            h = sp_attn(h, lyr["wqkv"].astype(half),
                        lyr["wo"].astype(half), H, axis_name="sp",
                        causal=True)
            x = x + h
            h = ln(x, lyr["ln2_w"], lyr["ln2_b"])
            h = jnp.einsum("bti,oi->bto", h, lyr["w1"].astype(half))
            h = jax.nn.gelu(h)
            h = jnp.einsum("bti,oi->bto", h, lyr["w2"].astype(half))
            x = x + h
        x = ln(x, params["lnf_w"], params["lnf_b"])
        return jnp.einsum("bte,ve->btv", x.astype(jnp.float32),
                          params["embed"])

    optimizer = optimizers.FusedAdam(lr=args.lr)
    params = init_params()
    opt_state = optimizer.init(params)

    def step(params, opt_state, inputs, labels):
        t0 = lax.axis_index("sp") * (T // args.sp)

        def loss_fn(p):
            logits = forward(p, inputs, t0)
            logp = jax.nn.log_softmax(logits, -1)
            nll = -jnp.take_along_axis(logp, labels[..., None], -1)
            # mean over the GLOBAL sequence: psum local sums over sp
            loc = jnp.sum(nll)
            cnt = jnp.asarray(nll.size, jnp.float32)
            return lax.psum(loc, "sp") / lax.psum(cnt, "sp")

        loss, grads = jax.value_and_grad(loss_fn)(params)
        # params are replicated on both axes: sum partial grads over the
        # sequence shards (sp), average over the data-parallel groups
        grads = jax.tree_util.tree_map(
            lambda g: lax.pmean(lax.psum(g, "sp"), "data"), grads)
        params, opt_state = optimizer.update(grads, opt_state, params)
        return params, opt_state, lax.pmean(loss, "data")

    train = jax.jit(jax.shard_map(
        step, mesh=mesh,
        in_specs=(P(), P(), P("data", "sp"), P("data", "sp")),
        out_specs=(P(), P(), P()), check_vma=False))

    B = args.batch_size * args.dp
    ids = rng.randint(0, V, (B, T + 1))
    inputs = jnp.asarray(ids[:, :-1], jnp.int32)
    labels = jnp.asarray(ids[:, 1:], jnp.int32)

    print(f"=> {args.strategy} SP: global seq {T} over sp={args.sp}, "
          f"batch {B} over dp={args.dp}; compiling...")
    t0 = time.time()
    params, opt_state, loss = train(params, opt_state, inputs, labels)
    jax.block_until_ready(loss)
    print(f"=> compiled in {time.time() - t0:.1f}s")

    t0 = time.time()
    for i in range(args.iters):
        params, opt_state, loss = train(params, opt_state, inputs, labels)
        if i % args.print_freq == 0 or i == args.iters - 1:
            jax.block_until_ready(loss)
            tok_s = B * T * (i + 1) / (time.time() - t0)
            print(f"[{i:3d}/{args.iters}] loss {float(loss):.4f}  "
                  f"{tok_s:,.0f} tok/s")
    print("done")


if __name__ == "__main__":
    main()
