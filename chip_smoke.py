#!/usr/bin/env python3
"""chip_smoke.py — does the system still start, compile and step on the chip?

Drives the main path once through the entry points a user calls, at the
full published width of models the repo supports, on however many chips
``jax.devices()`` returns (one process):

  resnet50_o2_ddp          examples/imagenet/main_amp.py: ResNet-50, amp O2,
                           SGD+momentum, DDP over ("data",); 128 img/chip,
                           224x224 NCHW; compile + 5 steps
  bert_base_o2_fused_adam  examples/bert/main_amp.py --config base: FusedAdam
                           on the flat master, FusedLayerNorm, flash
                           attention; 32 seq/chip x 128; compile + 5 steps
  gpt2_small_paged_engine  GPT-2-small bf16 through serving.PagedEngine
                           (8 slots, buf_len 512, block 64, window 8):
                           warmup(), then 12 staggered requests of
                           64-token prompts, 32 new tokens each, on device
                           0 (the last four queue behind full slots and
                           are admitted inside a decode window)
  (+ on more than one chip) the ResNet leg again under --sync_bn and
                           under --zero, two steps each

Weights are random from a seed.  Each leg prints one JSON line; the last
line of stdout is ``{"ok": true, "device": {...}}`` and the exit status is
0 only if every leg passed its checks.  The script has no CPU mode and
sets no platform: without a TPU it exits non-zero in seconds, before any
model is built.  Wall times it prints are smoke observations, not
benchmark results.

    python chip_smoke.py
"""

import gc
import importlib.util
import json
import os
import re
import sys
import time

import numpy as np

_ROOT = os.path.dirname(os.path.abspath(__file__))

# the Mosaic kernels the compiled BERT-base step must hold (pallas_call
# ``name``s / kernel function names, as the lowered module records them)
BERT_KERNELS = ("_adam_kernel", "layer_norm_fwd", "layer_norm_bwd",
                "flash_fwd", "flash_dq", "flash_dkv")

_CACHE_HIT = "/jax/compilation_cache/cache_hits"
_CACHE_MISS = "/jax/compilation_cache/cache_misses"
_cache_events = {"hit": 0, "miss": 0}


class LegFailed(Exception):
    """A leg ran but one of its checks did not hold; ``rec`` is what it
    had measured by then."""

    def __init__(self, why, rec):
        super().__init__(why)
        self.why, self.rec = why, rec


def _on_cache_event(event, **_):
    if event == _CACHE_HIT:
        _cache_events["hit"] += 1
    elif event == _CACHE_MISS:
        _cache_events["miss"] += 1


def _load_example(rel_path):
    """Import an example script as a module (two of them are both called
    main_amp.py, so the module name comes from the directory)."""
    path = os.path.join(_ROOT, rel_path)
    name = "apex_example_" + os.path.basename(os.path.dirname(path))
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _versions():
    import jax
    import jaxlib
    from importlib import metadata
    try:
        libtpu = metadata.version("libtpu")
    except metadata.PackageNotFoundError:
        libtpu = None
    return {"jax": jax.__version__, "jaxlib": jaxlib.__version__,
            "libtpu": libtpu}


def _device_fields():
    import jax
    dev = jax.devices()
    return {"platform": dev[0].platform, "device_kind": dev[0].device_kind,
            "device_count": len(dev)}


def _memory(devices):
    """Per-device peak and current bytes (the peak is the process's so
    far: it does not reset between legs)."""
    from apex_tpu.observability.memory import device_memory_stats
    stats = [device_memory_stats(d) or {} for d in devices]
    return {"peak_bytes": [s.get("peak_bytes_in_use") for s in stats],
            "bytes_in_use": [s.get("bytes_in_use") for s in stats]}


def _compiled_facts(lowered):
    """What the step compiled to: kernel names of the lowered module's
    ``tpu_custom_call``s (with counts), how many custom calls to that
    target the executable still holds, and its planned peak bytes (the
    runtime's own peak counter does not show program temporaries)."""
    from apex_tpu.observability.memory import memory_plan
    compiled = lowered.compile()
    names = {}
    for m in re.finditer(r'kernel_name = "([^"]+)"', lowered.as_text()):
        names[m.group(1)] = names.get(m.group(1), 0) + 1
    return {"kernels": names,
            "compiled_tpu_custom_calls":
                compiled.as_text().count('"tpu_custom_call"'),
            "planned_peak_bytes": memory_plan(compiled)["peak_bytes"]}


def _require_kernels(found, expect, rec):
    missing = [k for k in expect if k not in found["kernels"]]
    if missing:
        raise LegFailed(f"compiled step lacks Mosaic kernels {missing}; "
                        f"found {sorted(found['kernels'])}", rec)
    if found["compiled_tpu_custom_calls"] < len(expect):
        raise LegFailed(
            f"compiled executable holds "
            f"{found['compiled_tpu_custom_calls']} tpu_custom_calls, "
            f"expected at least {len(expect)}", rec)


def _replicas_identical(params, mesh):
    """Bitwise-equal parameter digests on every device of the mesh
    (observability.numerics' per-leaf [sum, sum of squares])."""
    import jax
    from jax.sharding import PartitionSpec as P
    from apex_tpu.observability.numerics import divergence_digest
    per_device = jax.jit(jax.shard_map(
        lambda p: divergence_digest(p)[None], mesh=mesh, in_specs=P(),
        out_specs=P("data"), check_vma=False))(params)
    d = np.asarray(per_device)
    return bool((d == d[:1]).all())


def _logit_margin(model, params, prefix, ref_tok, engine_tok, position):
    """How far apart the two candidate tokens are where the engine and
    ``generate_cached`` part ways, from a float32 forward at full matmul
    precision over the shared prefix: a gap far below bf16's resolution
    of the logits is a near-tie that either path may break either way."""
    import jax
    import jax.numpy as jnp
    p32 = jax.tree_util.tree_map(
        lambda x: x.astype(jnp.float32)
        if jnp.issubdtype(x.dtype, jnp.floating) else x, params)
    with jax.default_matmul_precision("highest"):
        logits, _ = model.apply(p32, jnp.asarray([prefix], jnp.int32))
    row = np.asarray(logits[0, -1], np.float32)
    return {"position": position, "reference_token": ref_tok,
            "engine_token": engine_tok,
            "f32_logit_reference_token": float(row[ref_tok]),
            "f32_logit_engine_token": float(row[engine_tok]),
            "f32_logit_max": float(row.max())}


def train_leg(name, example, argv, steps, expect_kernels=(),
              expect_attention=None):
    """Build an example's trainer through its own ``build()``, compile,
    take ``steps`` steps, and check what came out."""
    import jax
    from apex_tpu.observability import compilation
    from apex_tpu.transformer import attention

    mod = _load_example(example)
    args = mod.parse_args(argv)
    paths = []
    attention.set_path_hook(paths.append)
    try:
        run = mod.build(args)
        ledger = compilation.get_ledger()
        state, losses, step_ms, found_inf = run.state, [], [], 0.0
        for i in range(steps + 1):          # call 0 compiles
            t = time.perf_counter()
            state, metrics = run.train_step(
                state, run.put_batch(run.get_batch(i)))
            jax.block_until_ready(metrics)
            step_ms.append((time.perf_counter() - t) * 1e3)
            losses.append(float(metrics["loss"]))
            found_inf += float(metrics["found_inf"])
            if i == 0:
                traces0 = ledger.total_traces()
        compile_s = step_ms.pop(0) / 1e3
        retraces = ledger.total_traces() - traces0
        loss_scale = float(metrics["loss_scale"])
        batch = run.put_batch(run.get_batch(0))
        calls = _compiled_facts(run.train_step.lower(state, batch))
    finally:
        attention.set_path_hook(None)

    mesh_devices = list(run.mesh.devices.flat)
    mem = _memory(mesh_devices)
    comm_bytes = sum(int(b.get("bytes", 0)) for b in run.ddp.last_comm_stats)
    rec = {"leg": name, "compile_s": round(compile_s, 2),
           "steps_done": steps, "first_loss": losses[0],
           "last_loss": losses[-1], "found_inf_steps": found_inf,
           "loss_scale": loss_scale,
           "smoke_step_ms": [round(t, 2) for t in step_ms],
           "traces_after_warmup": retraces,
           "compiled": calls, "attention_paths": sorted(set(paths)),
           "ddp_comm_bytes_per_step": comm_bytes, **mem}
    if len(mesh_devices) > 1:
        rec["replicas_identical"] = _replicas_identical(state[0], run.mesh)

    if not all(np.isfinite(losses)):
        raise LegFailed(f"loss not finite: {losses}", rec)
    if not (np.isfinite(loss_scale) and loss_scale >= 1.0):
        raise LegFailed(f"loss scale collapsed to {loss_scale}", rec)
    if retraces:
        raise LegFailed(f"{retraces} trace(s) after warm-up: "
                        f"{ledger.snapshot()['entries']}", rec)
    _require_kernels(calls, expect_kernels, rec)
    if expect_attention is not None and set(paths) != {expect_attention}:
        raise LegFailed(f"attention took {sorted(set(paths))}, expected "
                        f"only {expect_attention!r}", rec)
    if len(mesh_devices) > 1:
        if not rec["replicas_identical"]:
            raise LegFailed("parameters differ between devices", rec)
        if not getattr(args, "zero", False) and comm_bytes <= 0:
            raise LegFailed("ddp.last_comm_stats shows no bytes", rec)
        peaks = [p for p in mem["peak_bytes"] if p]
        if peaks and max(peaks) >= 2 * min(peaks):
            raise LegFailed(f"uneven peak bytes across devices: {peaks}",
                            rec)
    return rec


def engine_leg(name, cfg, slots, buf_len, block_size, window, requests,
               prompt_len, new_tokens, seed=0):
    """GPT through ``serving.PagedEngine``: warm up, serve staggered
    requests to the drain, check tokens and block accounting, and
    compare the greedy tokens with ``generate_cached``."""
    import jax
    import jax.numpy as jnp
    from apex_tpu import models, serving
    from apex_tpu.observability import compilation
    from apex_tpu.transformer import attention

    model = models.GPT(cfg)
    params, _ = model.init(jax.random.PRNGKey(seed))
    params = jax.tree_util.tree_map(
        lambda x: x.astype(jnp.bfloat16) if x.dtype == jnp.float32 else x,
        params)
    rng = np.random.RandomState(seed)
    prompts = [rng.randint(0, cfg.vocab_size, prompt_len).tolist()
               for _ in range(requests)]
    paths = []
    attention.set_path_hook(paths.append)
    try:
        eng = serving.PagedEngine(model, params, slots=slots,
                                  buf_len=buf_len, block_size=block_size,
                                  window=window)
        ledger = compilation.get_ledger()
        t0 = time.perf_counter()
        eng.warmup()
        compile_s = time.perf_counter() - t0
        traces0 = ledger.total_traces()
        # staggered: two requests arrive before each window, so some are
        # admitted at a window boundary while others decode, and — with
        # more requests than slots — the last ones queue and are admitted
        # inside a window, into blocks a finished request just recycled
        todo = list(prompts)
        rids = []
        window_ms = []
        while todo or eng.live() or eng.queue_depth():
            for _ in range(min(2, len(todo))):
                rids.append(eng.submit(todo.pop(0),
                                       max_new_tokens=new_tokens))
            t = time.perf_counter()
            eng.step()
            window_ms.append((time.perf_counter() - t) * 1e3)
        retraces = ledger.total_traces() - traces0
        engine_paths = sorted(set(paths))
        results = [eng.result(r) for r in rids]
        stats = eng.stats()

        # what the decode window compiled to (the args the engine itself
        # passes, as analysis.entry_points builds them)
        step_args = (eng.ids, eng.cur_len, eng.kv_len, eng.pool,
                     eng._slot_keys, eng._slot_temp, eng.limit, eng._eos,
                     eng.tables, eng.n_blk, eng.free_stack, eng.free_top,
                     eng._stage_pending())
        calls = _compiled_facts(eng._paged_step_k.lower(*step_args))
        del paths[:]

        # reference: the model's own KV-cached greedy decode
        buf = np.zeros((requests, buf_len), np.int32)
        buf[:, :prompt_len] = np.asarray(prompts, np.int32)
        ref_ids, _ = jax.jit(
            lambda p, ids: model.generate_cached(
                p, ids, jnp.full((requests,), prompt_len), new_tokens))(
            params, jnp.asarray(buf))
        ref = np.asarray(ref_ids)[:, prompt_len:prompt_len + new_tokens]
    finally:
        attention.set_path_hook(None)

    matched, first_div = 0, {}
    for i, toks in enumerate(results):
        got = np.asarray(toks[:new_tokens])
        if len(got) == new_tokens and (got == ref[i]).all():
            matched += 1
            continue
        n = min(len(got), new_tokens)
        diff = np.nonzero(got[:n] != ref[i][:n])[0]
        k = int(diff[0]) if len(diff) else n
        first_div[i] = ({"position": k} if k >= n else _logit_margin(
            model, params, prompts[i] + ref[i][:k].tolist(),
            int(ref[i][k]), int(got[k]), k))
    produced = sum(len(t) for t in results)
    rec = {"leg": name, "compile_s": round(compile_s, 2),
           "requests_done": len(results), "tokens_produced": produced,
           "smoke_window_ms": [round(t, 2) for t in window_ms],
           "window": window, "traces_after_warmup": retraces,
           "blocks_free": stats["blocks_free"],
           "blocks_total": stats["blocks_total"],
           "midwindow_admissions": stats["midwindow_admissions"],
           "matches_generate_cached": matched,
           "first_divergence": first_div,
           "compiled": calls, "attention_paths": engine_paths,
           "reference_attention_paths": sorted(set(paths)),
           **_memory(jax.devices()[:1])}

    short = [i for i, t in enumerate(results) if len(t) != new_tokens]
    if short:
        raise LegFailed(f"requests {short} returned "
                        f"{[len(results[i]) for i in short]} tokens, "
                        f"asked {new_tokens}", rec)
    bad = [i for i, t in enumerate(results)
           if not all(0 <= int(x) < cfg.vocab_size for x in t)]
    if bad:
        raise LegFailed(f"requests {bad} hold tokens outside the "
                        f"vocabulary", rec)
    if stats["blocks_free"] != stats["blocks_total"]:
        raise LegFailed(f"{stats['blocks_total'] - stats['blocks_free']} "
                        f"blocks still held after the drain", rec)
    if retraces:
        raise LegFailed(f"{retraces} trace(s) after warm-up: "
                        f"{ledger.snapshot()['entries']}", rec)
    return rec


def legs(ndev):
    """The smoke's legs, in order, as (callable, kwargs)."""
    from apex_tpu import models
    imagenet = "examples/imagenet/main_amp.py"
    resnet = ["--arch", "resnet50", "-b", "128", "--image-size", "224",
              "--opt-level", "O2"]
    out = [
        (train_leg, dict(
            name="resnet50_o2_ddp", example=imagenet, argv=resnet,
            steps=5)),
        (train_leg, dict(
            name="bert_base_o2_fused_adam",
            example="examples/bert/main_amp.py",
            argv=["--config", "base", "-b", "32", "--seq-len", "128",
                  "--optimizer", "adam", "--opt-level", "O2"],
            steps=5, expect_kernels=BERT_KERNELS,
            expect_attention="flash")),
        (engine_leg, dict(
            name="gpt2_small_paged_engine",
            cfg=models.GPTConfig(n_layer=12, n_head=12, n_embd=768,
                                 vocab_size=50257, block_size=512,
                                 dropout=0.0),
            slots=8, buf_len=512, block_size=64, window=8, requests=12,
            prompt_len=64, new_tokens=32)),
    ]
    if ndev > 1:
        out += [
            (train_leg, dict(
                name="resnet50_o2_ddp_sync_bn", example=imagenet,
                argv=resnet + ["--sync_bn"], steps=2)),
            (train_leg, dict(
                name="resnet50_o2_ddp_zero", example=imagenet,
                argv=resnet + ["--zero"], steps=2)),
        ]
    return out


def run_legs(leg_list, common):
    """Run each leg, print its JSON line (with the compile-cache events
    seen during it), return the names that failed."""
    failed = []
    for fn, kwargs in leg_list:
        name = kwargs["name"]
        events0 = dict(_cache_events)
        try:
            rec = {**fn(**kwargs), "ok": True}
        except LegFailed as e:
            rec = {**e.rec, "ok": False, "error": e.why}
        except Exception as e:      # noqa: BLE001 — a leg that crashed
            # (compile error, OOM) must not hide the legs after it
            import traceback
            traceback.print_exc()
            rec = {"leg": name, "ok": False,
                   "error": f"{type(e).__name__}: {e}"[:2000]}
        if not rec["ok"]:
            failed.append(name)
            print(f"chip_smoke: leg {name} FAILED: {rec['error']}",
                  file=sys.stderr)
        rec["cache_hits"] = _cache_events["hit"] - events0["hit"]
        rec["cache_misses"] = _cache_events["miss"] - events0["miss"]
        print(json.dumps({**rec, **common}), flush=True)
        gc.collect()        # drop the leg's device buffers before the next
    return failed


def main():
    for var in ("APEX_TPU_DISABLE_PALLAS", "APEX_TPU_FORCE_PALLAS"):
        if os.environ.get(var):
            print(f"chip_smoke: {var} is set; the smoke runs the "
                  f"production kernel dispatch only", file=sys.stderr)
            return 2
    import jax
    backend = jax.default_backend()
    if backend != "tpu":
        print(f"chip_smoke: needs a TPU, but JAX found backend "
              f"{backend!r} ({jax.devices()[0].device_kind} x "
              f"{len(jax.devices())}); nothing was built or run",
              file=sys.stderr)
        return 2
    try:
        from apex_tpu import _native
        from apex_tpu.utils import configure_compile_cache
    except ImportError as e:
        print(f"chip_smoke: run it from the root of an apex_tpu checkout "
              f"({e})", file=sys.stderr)
        return 2
    from jax import monitoring
    monitoring.register_event_listener(_on_cache_event)
    common = {**_device_fields(), **_versions(),
              "cache_dir": configure_compile_cache(),
              "native_available": _native.available(),
              "timing_note": "smoke observation, not a benchmark"}
    failed = run_legs(legs(common["device_count"]), common)
    device = {"platform": common["platform"],
              "kind": common["device_kind"],
              "count": common["device_count"]}
    if failed:
        print(json.dumps({"ok": False, "failed": failed,
                          "device": device}), flush=True)
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
