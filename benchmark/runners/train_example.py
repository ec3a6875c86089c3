"""Runner for a training job built through one of the repo's example scripts:
``build(args)`` gives the model, mesh, state and jitted, state-donating step;
the benchmark replaces the state with weights it made from the seed and feeds
batches it made from the seed through ``put_batch`` and ``train_step``.

Set-up builds ONE object (the compiled step with its state), drives it through
its first steps by the window's own call and feed, keeps what those steps
showed, and hands the same object to the window.  After the window the state
is freed and the plain reference follows the same steps from the same seed.
The reference runs on one chip whatever the cell has, so it follows three
steps of a one-chip batch and two of a larger one (the shortening the
benchmark's contract allows), which keeps it under the window's length.
"""

import gc
import importlib.util
import os
import time

import numpy as np

from lib import traffic as tg, weights


def load_example(root: str, rel_path: str):
    path = os.path.join(root, rel_path)
    name = "bm_example_" + os.path.basename(os.path.dirname(path))
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def build_example(config: dict, root: str):
    mod = load_example(root, config["example"])
    argv = list(config["argv"]) + ["-b", str(config["per_chip_batch"]),
                                   "--seq-len", str(config["seq_len"])]
    return mod.build(mod.parse_args(argv))


def _shapes(tree):
    import jax
    return jax.tree_util.tree_map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tree)


class Runner:
    SPAN_NAMES = ("make_batch", "put_batch", "train_step", "block")
    ITERATION_SPAN = "make_batch"

    def __init__(self, cell, spans, log):
        self.cell, self.spans, self.log = cell, spans, log
        self.timings = {}
        self.reference = importlib.import_module("references." + cell.config["reference"])
        self.model_cfg = cell.config
        if cell.traffic["generator"] != "mlm_nsp_batch":
            raise ValueError("train_example drives the mlm_nsp_batch generator")
        self.traffic = cell.traffic["params"]
        self.check_steps = 3 if cell.chips == 1 else 2
        self.step_index = 0

    # -- building --------------------------------------------------------------
    def _build(self):
        """The example's own ``build()`` (tests wrap this to break the step)."""
        cfg = dict(self.cell.config, seq_len=self.traffic["seq_len"])
        return build_example(cfg, self.cell.root)

    def setup(self):
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P
        from apex_tpu.transformer import attention
        self.paths = []
        attention.set_path_hook(self.paths.append)
        t0 = time.perf_counter()
        run = self._build()
        if run.ndev != self.cell.chips:
            raise RuntimeError(f"the example built a mesh of {run.ndev} devices, "
                               f"the cell asks for {self.cell.chips}")
        self.run, self.devices = run, list(run.mesh.devices.flat)
        self.rows = self.cell.config["per_chip_batch"] * self.cell.chips
        # the benchmark's own weights from the seed, in the types the program
        # trains in, and the optimizer state the program makes of them
        self.param_shapes = _shapes(run.state[0])
        run.state = None
        gc.collect()
        rep = NamedSharding(run.mesh, P())
        params = weights.make_weights(self.param_shapes, self.cell.seed,
                                      self.cell.config["init_std"], rep)
        opt_state = jax.jit(run.optimizer.init, out_shardings=rep)(params)
        self.state = (params, opt_state)
        jax.block_until_ready(self.state)
        self.timings["init_s"] = time.perf_counter() - t0

        beta1 = self.reference.ADAM["beta1"]
        layout = opt_state.masters.layout

        def first_grad_norms(opt_state):      # m = (1 - beta1) * g after one step
            m = layout.unpack_masters(opt_state.inner.m)
            return self.reference.leaf_norms(m) / (1.0 - beta1)

        def update_norms(opt_state, seed_params):
            now = layout.unpack_masters(opt_state.masters.buf)
            return self.reference.leaf_norms(jax.tree_util.tree_map(
                lambda a, b: a - b.astype(a.dtype), now, seed_params))

        t0 = time.perf_counter()
        self.first = {"losses": [], "found_inf": []}
        for i in range(self.check_steps):
            metrics = self._one_step()
            self.first["losses"].append(float(metrics["loss"]))
            self.first["found_inf"].append(float(metrics["found_inf"]))
            if i == 0:
                self.timings["compile_s"] = time.perf_counter() - t0
                self.first["first_grad_norms"] = np.asarray(
                    jax.jit(first_grad_norms)(self.state[1]))
        seed_params = weights.make_weights(self.param_shapes, self.cell.seed,
                                           self.cell.config["init_std"], rep)
        self.first["update_norms"] = np.asarray(
            jax.jit(update_norms)(self.state[1], seed_params))
        del seed_params
        attention.set_path_hook(None)

    # -- the window's call and feed ----------------------------------------------
    def _one_step(self):
        import jax
        with self.spans.span("make_batch"):
            batch = tg.mlm_nsp_batch(self.traffic, self.cell.seed, self.step_index, self.rows,
                                     self.model_cfg["vocab_size"])
        with self.spans.span("put_batch"):
            batch = self.run.put_batch(batch)
        with self.spans.span("train_step"):
            self.state, metrics = self.run.train_step(self.state, batch)
        with self.spans.span("block"):
            jax.block_until_ready(metrics)
        self.step_index += 1
        return metrics

    def window(self, seconds, tracer):
        """Steps until the clock passes ``seconds``; where the configuration
        states ``window_steps`` (a job whose work a step depends on its own
        trajectory), until that many steps have run, if the clock has not come
        first: then both sides of a pair do the same steps of the same run."""
        count = self.cell.config.get("window_steps")
        kept, ends, t0 = [], [0.0], time.perf_counter()
        while True:
            tracer.tick(time.perf_counter() - t0, len(kept))
            kept.append(self._one_step())
            elapsed = time.perf_counter() - t0
            ends.append(elapsed)
            if len(kept) == count or elapsed >= seconds:
                break
        self.log({"window_ended_by": "count" if len(kept) == count else "clock",
                  "at_step": len(kept), "window_steps": count, "seconds": seconds,
                  "elapsed_s": elapsed})
        # how the steps spread over the window: a run that reads far off shows
        # here whether every step was slow or a few stalled, and where
        each_ms = np.diff(ends) * 1e3
        self.log({"step_ms_by_step": [round(float(d), 2) for d in each_ms]})
        step_ms = np.sort(each_ms)
        self.log({"step_ms": {"min": step_ms[0], "median": float(np.median(step_ms)),
                              "p99": step_ms[int(0.99 * (len(step_ms) - 1))], "max": step_ms[-1],
                              "slowest_at_s": float(ends[1 + int(np.argmax(each_ms))])}})
        loss = np.array([float(m["loss"]) for m in kept])
        bad = ~np.isfinite(loss) | np.array([float(m["found_inf"]) > 0 for m in kept])
        self.last = {"loss_scale": float(kept[-1]["loss_scale"]), "loss_last": float(loss[-1])}
        comm = getattr(self.run.ddp, "last_comm_stats", [])
        return {"attempted": len(kept), "failed": int(bad.sum()),
                "metrics": {"train.samples_per_s": len(kept) * self.rows / elapsed
                            / self.cell.chips},
                "facts": {"steps": len(kept), "window_s": elapsed, "rows_per_step": self.rows,
                          "seq_len": self.traffic["seq_len"], "model": self.model_cfg,
                          "n_params": int(self.state[1].masters.buf.size),
                          "ddp_wire_bytes": sum(int(b.get("bytes", 0)) for b in comm)}}

    def planned_temp_bytes(self) -> int:
        """Temporaries the compiled step plans per chip (its cache entry is there)."""
        if not hasattr(self.run.train_step, "lower"):
            return 0
        batch = self.run.put_batch(tg.mlm_nsp_batch(self.traffic, self.cell.seed, 0, self.rows,
                                                    self.model_cfg["vocab_size"]))
        plan = self.run.train_step.lower(self.state, batch).compile().memory_analysis()
        return int(plan.temp_size_in_bytes)

    def _replicas_differ(self) -> int:
        """Parameter digests (sum, sum of squares per leaf) on every device."""
        import jax
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P

        def digest(p):
            leaves = jax.tree_util.tree_leaves(p)
            return jnp.stack([jnp.stack([jnp.sum(x.astype(jnp.float32)),
                                         jnp.sum(jnp.square(x.astype(jnp.float32)))])
                              for x in leaves])[None]

        per_dev = np.asarray(jax.jit(jax.shard_map(
            digest, mesh=self.run.mesh, in_specs=P(), out_specs=P("data"),
            check_vma=False))(self.state[0]))
        return int((per_dev != per_dev[:1]).any())

    def release(self):
        self.replicas_differ = self._replicas_differ() if self.cell.chips > 1 else 0
        self.state = None
        self.run = None
        gc.collect()

    # -- correct -------------------------------------------------------------------
    def reference_readings(self, precision="float32", param_dtype="float32"):
        """The plain reference over the same first steps from the same seed, on
        one device (the program's state is freed by now)."""
        import jax
        params = weights.make_weights(self.param_shapes, self.cell.seed,
                                      self.cell.config["init_std"])
        batches = [tg.mlm_nsp_batch(self.traffic, self.cell.seed, i, self.rows,
                                    self.model_cfg["vocab_size"]) for i in range(self.check_steps)]
        out = self.reference.train(params, batches, self.model_cfg, groups=self.cell.chips,
                                   block_rows=self.cell.config["reference_block_rows"],
                                   precision=precision, param_dtype=param_dtype)
        del params
        jax.clear_caches()
        return out

    def control_readings(self, with_control=True):
        """Sound and control readings of one run, for tools/control.py: the
        controls are the reference with its matmuls, and then its stored
        parameters, one precision below what the file states."""
        from references._precision import NEXT_LOWER
        ref = self.reference_readings()
        names = list(self.reference.LIMITS)
        pick = lambda got: {k: got[k] for k in names}
        sound = {"sound": pick(self.reference.compare(self.first, ref)),
                 "detail": {"losses": self.first["losses"], "reference_losses": ref["losses"]}}
        if not with_control:
            return sound
        low_compute = self.reference_readings(
            precision=NEXT_LOWER[self.cell.config["compute_dtype"]])
        low_params = self.reference_readings(
            param_dtype=NEXT_LOWER[self.cell.config["param_dtype"]])
        return {**sound, "control": pick(self.reference.compare(low_compute, ref)),
                "control_params": pick(self.reference.compare(low_params, ref))}

    def check(self):
        ref = self.reference_readings()
        got = self.reference.compare(self.first, ref)
        self.log({"reference_losses": ref["losses"], "program_losses": self.first["losses"],
                  "worst_leaves": {k: v for k, v in got.items()
                                   if k.endswith("_leaf") or k == "grad_norm_gap"},
                  "loss_scale": self.last["loss_scale"], "attention_paths": sorted(set(self.paths))})
        lim = self.reference.LIMITS
        numbers = {k: (got[k], lim[k]) for k in lim}
        numbers["skipped_first_steps"] = (sum(self.first["found_inf"]), 0)
        numbers["loss_scale_below_one"] = (int(not self.last["loss_scale"] >= 1.0), 0)
        expected = self.cell.config.get("attention_path")
        if expected:
            numbers["other_attention_paths"] = (len(set(self.paths) - {expected}), 0)
        if self.cell.chips > 1:
            numbers["replicas_differ"] = (self.replicas_differ, 0)
        return numbers
