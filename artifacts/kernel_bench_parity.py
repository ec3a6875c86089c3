"""Pallas-vs-jnp kernel parity AND timing at BENCH-SCALE shapes
(VERDICT r4 item 8: r3 validated the six families at small test shapes;
this re-runs them at the shapes the bench actually exercises, on
whatever backend is default — the TPU in the hardware session).

Per family, the probe runs the SAME high-level entry point twice in
subprocesses — once with APEX_TPU_DISABLE_PALLAS=1 (jnp path), once
with APEX_TPU_FORCE_PALLAS=1 so EVERY family routes through its Pallas
kernel (including parity-only ones like the standalone syncbn apply
that production dispatch deliberately leaves to XLA fusion) — and
compares the dumped outputs.  The steady_ms columns therefore time the
forced-kernel path, not necessarily what the bench executes.
Subprocess isolation keeps one hung/OOM family from killing the
sweep, and guarantees the dispatch env is read fresh (it is consulted
at trace time, so in-process toggling could silently reuse a cached
compilation).

Bench-scale shapes:
  multi_tensor scale/axpby/l2norm : 25.6M-elem flat fp32 (ResNet-50)
  fused_adam                      : 25.6M-param flat step
  lamb stage1+2                   : 25.6M flat, per-tensor ratio on 1
  layer_norm fwd+bwd              : (16384, 1024)  (BERT-large B*T, C)
  syncbn apply fwd+bwd            : (128, 64, 112, 112) (ResNet stem)
  flash attention fwd+bwd         : (8, 16, 2048, 64) causal bf16
                                    (the T=4096 train config halved to
                                     keep the dense jnp reference's
                                     T^2 scores in memory)

Run:  python artifacts/kernel_bench_parity.py            # full sweep
      APEX_KBP_SMALL=1 ... # divided-down shapes for a CPU smoke
"""

import json
import os
import subprocess
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

SMALL = os.environ.get("APEX_KBP_SMALL") == "1"
FAMILIES = ["multi_tensor", "adam", "lamb", "layer_norm", "syncbn",
            "flash"]


def _shapes():
    if SMALL:
        return dict(flat=100_000, ln=(256, 512), bn=(8, 16, 28, 28),
                    fa=(2, 4, 256, 64))
    return dict(flat=25_600_000, ln=(16384, 1024),
                bn=(128, 64, 112, 112), fa=(8, 16, 2048, 64))


def worker(family: str, out_path: str):
    """Compute the family's outputs at bench shapes, save to npz.
    The dispatch env (set by the parent) decides Pallas vs jnp."""
    import numpy as np
    import jax
    import jax.numpy as jnp

    sh = _shapes()
    rng = np.random.RandomState(0)
    t_compile = time.perf_counter()
    outs = {}
    steady = None

    def _tree(n, n_leaves=64, scale=1.0, seed_off=0):
        """n elements split over n_leaves mixed-size leaves (the bench
        optimizers run on trees, and LAMB's trust ratio is per-leaf)."""
        sizes = [n // n_leaves] * (n_leaves - 1)
        sizes.append(n - sum(sizes))
        r = np.random.RandomState(1 + seed_off)
        return {f"w{i}": jnp.asarray(
            (scale * r.randn(s)).astype(np.float32))
            for i, s in enumerate(sizes)}

    if family == "multi_tensor":
        from apex_tpu import multi_tensor_apply as mta
        g = _tree(sh["flat"])
        p = _tree(sh["flat"], seed_off=1)
        scale_j = jax.jit(
            lambda t: mta.multi_tensor_scale(t, 1.0 / 128.0))
        scaled, flag = scale_j(g)
        steady = lambda: scale_j(g)
        axp, aflag = jax.jit(
            lambda a, b: mta.multi_tensor_axpby(1.0, -2.0, a, b))(g, p)
        nrm, _ = jax.jit(mta.multi_tensor_l2norm)(g)
        _, per_t = jax.jit(
            lambda t: mta.multi_tensor_l2norm(t, per_tensor=True))(g)
        outs = {"flag": flag, "aflag": aflag, "nrm": nrm,
                "per_t": per_t,
            **{f"s_{k}": x for k, x in scaled.items()},
            **{f"a_{k}": x for k, x in axp.items()}}
    elif family == "adam":
        from apex_tpu.optimizers import FusedAdam
        p = _tree(sh["flat"])
        g = _tree(sh["flat"], scale=0.01, seed_off=2)
        opt = FusedAdam(lr=1e-3, weight_decay=0.01)
        st = opt.init(p)
        step_j = jax.jit(opt.step)
        p2, st2 = step_j(p, st, g)
        steady = lambda: step_j(p, st, g)
        outs = {**{f"p_{k}": x for k, x in p2.items()},
                "m": st2.m, "v": st2.v}
    elif family == "lamb":
        from apex_tpu.optimizers import FusedLAMB
        p = _tree(sh["flat"])
        g = _tree(sh["flat"], scale=0.01, seed_off=3)
        opt = FusedLAMB(lr=1e-3, weight_decay=0.01)
        st = opt.init(p)
        step_j = jax.jit(opt.step)
        p2, st2 = step_j(p, st, g)
        steady = lambda: step_j(p, st, g)
        outs = {**{f"p_{k}": x for k, x in p2.items()},
                "m": st2.m.buf, "v": st2.v.buf}
    elif family == "layer_norm":
        from apex_tpu import normalization as fln
        R, C = sh["ln"]
        x = jnp.asarray(rng.randn(R, C).astype(np.float32))
        w = jnp.asarray(rng.randn(C).astype(np.float32))
        b = jnp.asarray(rng.randn(C).astype(np.float32))
        dy = jnp.asarray(rng.randn(R, C).astype(np.float32))

        def f(x, w, b):
            return fln.fused_layer_norm_affine(x, w, b, (C,), 1e-5)

        y = jax.jit(f)(x, w, b)
        g_j = jax.jit(jax.grad(
            lambda *a: jnp.vdot(f(*a), dy), argnums=(0, 1, 2)))
        dx, dw, db = g_j(x, w, b)
        steady = lambda: g_j(x, w, b)
        outs = {"y": y, "dx": dx, "dw": dw, "db": db}
    elif family == "syncbn":
        from apex_tpu.nn import functional as NF
        N, C, H, W = sh["bn"]
        x = jnp.asarray(rng.randn(N, C, H, W).astype(np.float32))
        mean = jnp.asarray(rng.randn(C).astype(np.float32))
        var = jnp.asarray((1 + rng.rand(C)).astype(np.float32))
        w = jnp.asarray(rng.randn(C).astype(np.float32))
        b = jnp.asarray(rng.randn(C).astype(np.float32))
        dy = jnp.asarray(rng.randn(N, C, H, W).astype(np.float32))

        def f(x, mean, var, w, b):
            return NF.batch_norm_apply(x, mean, var, w, b, 1e-5)

        y = jax.jit(f)(x, mean, var, w, b)
        g_j = jax.jit(jax.grad(
            lambda xx, ww, bb: jnp.vdot(f(xx, mean, var, ww, bb), dy),
            argnums=(0, 1, 2)))
        dx, dwg, dbg = g_j(x, w, b)
        steady = lambda: g_j(x, w, b)
        outs = {"y": y, "dx": dx, "dw": dwg, "db": dbg}
    elif family == "flash":
        from apex_tpu.transformer import dot_product_attention
        B, H, T, D = sh["fa"]
        ks = jax.random.split(jax.random.PRNGKey(0), 3)
        q, k, v = (jax.random.normal(kk, (B, H, T, D),
                                     jnp.bfloat16) for kk in ks)
        do = jax.random.normal(jax.random.PRNGKey(3), (B, H, T, D),
                               jnp.bfloat16)

        def f(q, k, v):
            return dot_product_attention(q, k, v, causal=True)

        y = jax.jit(f)(q, k, v)
        g_j = jax.jit(jax.grad(
            lambda *a: jnp.vdot(f(*a).astype(jnp.float32),
                                do.astype(jnp.float32)),
            argnums=(0, 1, 2)))
        dq, dk, dv = g_j(q, k, v)
        steady = lambda: g_j(q, k, v)
        outs = {"y": y, "dq": dq, "dk": dk, "dv": dv}
    else:
        raise SystemExit(f"unknown family {family}")

    jax.block_until_ready(outs)
    t_warm = time.perf_counter()
    # steady-state timing of the family's heaviest already-jitted op
    # (first-call time above is dominated by import + XLA compile)
    steady_ms = float("nan")
    if steady is not None:
        jax.block_until_ready(steady())
        n_it = 3 if SMALL else 10
        t0 = time.perf_counter()
        for _ in range(n_it):
            r = steady()
        jax.block_until_ready(r)
        steady_ms = (time.perf_counter() - t0) / n_it * 1e3
    np.savez(out_path,
             **{k: np.asarray(v, np.float32) for k, v in outs.items()},
             __compile_s=np.float64(t_warm - t_compile),
             __steady_ms=np.float64(steady_ms),
             __backend=np.array(jax.default_backend()))
    print(f"  [{family}] worker done on {jax.default_backend()} "
          f"(first-call {t_warm - t_compile:.1f}s, "
          f"steady {steady_ms:.1f} ms)")


def main():
    import numpy as np

    results = []
    tol = {"multi_tensor": 1e-6, "adam": 1e-6, "lamb": 5e-5,
           "layer_norm": 2e-3, "syncbn": 2e-2, "flash": 6e-2}
    for fam in FAMILIES:
        row = {"family": fam}
        with tempfile.TemporaryDirectory() as td:
            paths = {}
            for mode, env in (("jnp", {"APEX_TPU_DISABLE_PALLAS": "1"}),
                              ("pallas",
                               {"APEX_TPU_FORCE_PALLAS": "1"})):
                out = os.path.join(td, f"{fam}_{mode}.npz")
                e = {k: v for k, v in os.environ.items()
                     if not k.startswith("APEX_TPU_")}
                e.update(env)
                t0 = time.perf_counter()
                try:
                    r = subprocess.run(
                        [sys.executable, os.path.abspath(__file__),
                         "worker", fam, out],
                        env=e, timeout=900, capture_output=True,
                        text=True)
                except subprocess.TimeoutExpired:
                    # a hung family must not kill the sweep — that is
                    # the whole point of the subprocess isolation
                    row[f"{mode}_error"] = "worker hung > 900s"
                    break
                row[f"{mode}_wall_s"] = round(time.perf_counter() - t0,
                                              1)
                if r.stdout.strip():
                    print(r.stdout.strip(), flush=True)
                if r.returncode != 0:
                    row[f"{mode}_error"] = r.stderr.strip()[-300:]
                    break
                paths[mode] = out
            if len(paths) == 2:
                a = np.load(paths["jnp"])
                b = np.load(paths["pallas"])
                row["backend"] = str(b["__backend"])
                row["jnp_steady_ms"] = round(
                    float(a["__steady_ms"]), 2)
                row["pallas_steady_ms"] = round(
                    float(b["__steady_ms"]), 2)
                row["pallas_compile_s"] = round(
                    float(b["__compile_s"]), 1)
                diffs = {}
                for key in a.files:
                    if key.startswith("__"):
                        continue
                    d = float(np.max(np.abs(a[key] - b[key])))
                    ref = float(np.max(np.abs(a[key]))) or 1.0
                    diffs[key] = round(d / ref, 8)
                row["rel_max_diff"] = diffs
                row["ok"] = all(v <= tol[fam] for v in diffs.values())
        results.append(row)
        print(json.dumps(row), flush=True)
    n_ok = sum(1 for r in results if r.get("ok"))
    print(f"kernel bench-shape parity: {n_ok}/{len(results)} families "
          f"ok")
    if n_ok < len(results):
        raise SystemExit(1)


if __name__ == "__main__":
    if len(sys.argv) > 1 and sys.argv[1] == "worker":
        worker(sys.argv[2], sys.argv[3])
    else:
        main()
