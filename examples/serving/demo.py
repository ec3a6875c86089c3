"""Serving-path showcase: every decode lever in one script.

Builds a small GPT target (plus a half-size draft for speculation) and
runs the same prompt batch through each serving mode, printing tokens
and wall time:

  greedy   — KV-cached greedy decode (chunked prefill)
  sample   — temperature + top-k + nucleus sampling
  int8     — weight-only int8 + int8 KV cache (HBM levers)
  spec     — lossless speculative decoding with the draft model
  beam     — beam search (num_beams hypotheses)
  engine   — continuous batching with a shared-prefix KV pool
  seq2seq  — encoder-decoder (T5) continuous batching

Weights are random (content-free); the point is the mechanics and the
relative costs.

Rehearse on the CPU:
  JAX_PLATFORMS=cpu \
  python examples/serving/demo.py --batch 4 --prompt 16 --new 32
On a TPU host (one process per chip; `python chip_smoke.py` first):
  python examples/serving/demo.py --batch 8 --prompt 64 --new 64
"""

import argparse
import os
import sys
import time

_repo = os.path.abspath(os.path.join(os.path.dirname(__file__),
                                     "..", ".."))
if _repo not in sys.path:
    sys.path.insert(0, _repo)

import numpy as np

import jax
import jax.numpy as jnp

from apex_tpu import models, quantization
from apex_tpu.models import beam_search, generate_speculative
from apex_tpu.utils import configure_compile_cache


def build(n_layer, n_embd, seed, vocab, block):
    m = models.GPT(models.GPTConfig(
        vocab_size=vocab, block_size=block, n_layer=n_layer,
        n_head=4, n_embd=n_embd, dropout=0.0, n_kv_head=2))
    params, _ = m.init(jax.random.PRNGKey(seed))
    params = jax.tree_util.tree_map(
        lambda x: x.astype(jnp.bfloat16)
        if x.dtype == jnp.float32 else x, params)
    return m, params


def timed(label, fn):
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn())
    t1 = time.perf_counter()
    print(f"{label:8s} {t1 - t0:7.3f}s", flush=True)
    return out


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--batch", type=int, default=4)
    p.add_argument("--prompt", type=int, default=16)
    p.add_argument("--new", type=int, default=32)
    p.add_argument("--vocab", type=int, default=256)
    p.add_argument("--block", type=int, default=None)
    p.add_argument("--layers", type=int, default=4)
    p.add_argument("--width", type=int, default=128)
    p.add_argument("--beams", type=int, default=4)
    p.add_argument("--gamma", type=int, default=4)
    args = p.parse_args()
    configure_compile_cache()
    block = args.block or (args.prompt + args.new)

    target, tp = build(args.layers, args.width, 0, args.vocab, block)
    draft, dp = build(max(1, args.layers // 2), args.width // 2, 1,
                      args.vocab, block)
    rng = np.random.RandomState(0)
    buf = np.zeros((args.batch, block), np.int32)
    buf[:, :args.prompt] = rng.randint(0, args.vocab,
                                       (args.batch, args.prompt))
    ids = jnp.asarray(buf)
    plen = jnp.full((args.batch,), args.prompt)

    greedy = timed("greedy", jax.jit(
        lambda: target.generate_cached(tp, ids, plen, args.new)[0]))

    timed("sample", jax.jit(
        lambda: target.generate_cached(
            tp, ids, plen, args.new, temperature=0.8, top_k=40,
            top_p=0.95, rng=jax.random.PRNGKey(7))[0]))

    qp = quantization.quantize_for_decode(tp)
    timed("int8", jax.jit(
        lambda: target.generate_cached(qp, ids, plen, args.new,
                                       cache_dtype=jnp.int8)[0]))

    spec = timed("spec", jax.jit(
        lambda: generate_speculative(target, tp, draft, dp, ids, plen,
                                     args.new, gamma=args.gamma)[0]))
    exact = bool(np.array_equal(np.asarray(spec), np.asarray(greedy)))
    print(f"speculative == greedy: {exact}")
    if not exact:
        sys.exit("LOSSLESSNESS VIOLATED")

    timed("beam", jax.jit(
        lambda: beam_search(target, tp, ids, plen, args.new,
                            num_beams=args.beams)[0]))

    # continuous-batching engine with a shared-prefix pool: half the
    # requests share a registered system prefix and admit via KV splice
    from apex_tpu import serving

    half = max(1, args.prompt // 2)

    def run_engine():
        eng = serving.Engine(target, tp, slots=args.batch,
                             buf_len=block, prefix_pool=1)
        sys_prefix = list(rng.randint(0, args.vocab, half))
        eng.register_prefix(sys_prefix)
        for i in range(2 * args.batch):
            pr = (sys_prefix if i % 2 == 0 else
                  list(rng.randint(0, args.vocab, half))) \
                + list(rng.randint(0, args.vocab, half))
            eng.submit(pr, max_new_tokens=args.new)
        n = 0
        while eng.live() or eng.stats()["waiting"]:
            n += sum(len(t) for t in eng.step().values())
        return eng.stats(), n

    st, n = timed("engine", run_engine)
    print(f"engine: {n} tokens over {st['finished']} requests, "
          f"{st['prefix_hits']} prefix-splice admissions")

    # encoder-decoder continuous batching (T5)
    t5 = models.T5(models.T5Config(
        vocab_size=args.vocab, d_model=args.width, d_kv=16,
        d_ff=2 * args.width, num_layers=max(1, args.layers // 2),
        num_heads=4, dropout_rate=0.0))
    t5p, _ = t5.init(jax.random.PRNGKey(2))

    def run_seq2seq():
        eng = serving.Seq2SeqEngine(t5, t5p, slots=args.batch,
                                    src_len=args.prompt,
                                    max_new_cap=args.new)
        for _ in range(2 * args.batch):
            n_src = int(rng.randint(1, args.prompt + 1))
            eng.submit(list(rng.randint(2, args.vocab, n_src)),
                       max_new_tokens=args.new)
        n = 0
        while eng.live() or eng.stats()["waiting"]:
            n += sum(len(t) for t in eng.step().values())
        return eng.stats(), n

    st, n = timed("seq2seq", run_seq2seq)
    print(f"seq2seq engine: {n} tokens over {st['finished']} requests")
    print("done", flush=True)


if __name__ == "__main__":
    main()
