"""Runner for next-token pretraining of a decoder built through
``examples/gpt/main_amp.py``'s ``build(args)``: ``runners.train_example``'s
set-up, window and comparison, driven by the generator ``causal_lm_batch``
below (one tuple ``(ids,)`` a step; the model shifts the labels itself), with
the configuration file handed to the example as its ``--model-config`` and the
expert layers' counters kept from every step.

A program without such a ``build`` (the parent of the PR that added it)
fails here within seconds, before anything is compiled.
"""

import dataclasses
import os

import numpy as np

from lib import traffic as tg
from runners import train_example


def causal_lm_batch(params: dict, seed: int, index: int, rows: int, vocab_size: int):
    """``rows`` full sequences of ``seq_len`` token ids, uniform over the
    vocabulary slice held, one document a row and no padding.  The label of a
    position is the next token, and a row's last position has none."""
    rng = tg.rng_for(seed, 2, index)
    return (rng.integers(0, vocab_size, (rows, params["seq_len"])).astype(np.int32),)


MOE_COUNTERS = ("moe_assignments_held", "moe_expert_load_max", "moe_dropped_assignments")
BY_STEP = ("moe_assignments_held", "moe_expert_load_max")      # logged for every step of the run
BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HELD_SHORTFALL_LIMIT = 0.2


class Runner(train_example.Runner):
    def __init__(self, cell, spans, log):
        if cell.traffic["generator"] != "causal_lm_batch":
            raise ValueError("train_causal_lm drives the causal_lm_batch generator")
        # the parent class refuses every generator but its own
        generator = dict(cell.traffic, generator="mlm_nsp_batch")
        super().__init__(dataclasses.replace(cell, traffic=generator), spans, log)
        self.cell = cell
        self.steps = []                 # every step's metrics, set-up's included

    def _batch(self, index: int):
        return causal_lm_batch(self.traffic, self.cell.seed, index, self.rows,
                               self.model_cfg["vocab_size"])

    def _build(self):
        mod = train_example.load_example(self.cell.root, self.cell.config["example"])
        if not hasattr(mod, "build"):
            raise SystemExit(f"{self.cell.config['example']} has no build(args): this program "
                             f"cannot run {self.cell.name}")
        file = os.path.join(self.cell.bench_dir, "configs", self.cell.config["name"] + ".json")
        argv = list(self.cell.config["argv"]) + [
            "--model-config", file, "-b", str(self.cell.config["per_chip_batch"]),
            "--seq-len", str(self.traffic["seq_len"])]
        return mod.build(mod.parse_args(argv))

    def _one_step(self):
        import jax
        with self.spans.span("make_batch"):
            batch = self._batch(self.step_index)
        with self.spans.span("put_batch"):
            batch = self.run.put_batch(batch)
        with self.spans.span("train_step"):
            self.state, metrics = self.run.train_step(self.state, batch)
        with self.spans.span("block"):
            jax.block_until_ready(metrics)
        self.step_index += 1
        self.steps.append(metrics)
        return metrics

    def _counter(self, name, reduce, steps):
        values = [int(m[name]) for m in steps if name in m]
        return reduce(values) if values else None

    def window(self, seconds, tracer):
        before = len(self.steps)
        out = super().window(seconds, tracer)
        mine = self.steps[before:]
        out["facts"].update({
            "tokens_per_step": self.rows * self.traffic["seq_len"],
            "moe_assignments_held": self._counter("moe_assignments_held", np.mean, mine),
            "moe_expert_load_max": self._counter("moe_expert_load_max", max, mine),
            "moe_dropped_assignments": self._counter("moe_dropped_assignments", sum, self.steps)})
        # the window trains without a balancing loss: how far the routing drifted,
        # and step by step from the first of set-up's (what a window_steps is chosen from)
        self.log({"moe": {k: out["facts"][k] for k in MOE_COUNTERS},
                  "first_step": {k: int(mine[0][k]) for k in MOE_COUNTERS if k in mine[0]},
                  "last_step": {k: int(mine[-1][k]) for k in MOE_COUNTERS if k in mine[-1]}})
        if BY_STEP[0] in mine[0]:
            self.log({"by_step": {k: [int(m[k]) for m in self.steps] for k in BY_STEP},
                      "setup_steps": before})
        return out

    def planned_temp_bytes(self) -> int:
        if not hasattr(self.run.train_step, "lower"):
            return 0
        batch = self.run.put_batch(self._batch(0))
        plan = self.run.train_step.lower(self.state, batch).compile().memory_analysis()
        return int(plan.temp_size_in_bytes)

    def held_shortfall(self, held: float) -> float:
        """How far one step's held assignments fall short of a uniform
        routing's share (tokens x choices x sparse layers x held / published
        experts), as a part of that share; 0 at or above it."""
        cfg = self.model_cfg
        expected = (self.rows * self.traffic["seq_len"] * cfg["num_experts_per_tok"]
                    * cfg["mlp_layer_types"].count("sparse")
                    * cfg["num_experts"] / cfg["num_experts_published"])
        return max(0.0, 1.0 - held / expected)

    def release(self):
        self.dropped = self._counter("moe_dropped_assignments", sum, self.steps)
        self.held_last = self._counter("moe_assignments_held", lambda v: v[-1], self.steps)
        self.steps = []
        super().release()

    def reference_readings(self, precision="float32", param_dtype="float32"):
        import jax
        from lib import weights
        params = weights.make_weights(self.param_shapes, self.cell.seed,
                                      self.cell.config["init_std"])
        batches = [self._batch(i) for i in range(self.check_steps)]
        out = self.reference.train(params, batches, self.model_cfg, groups=self.cell.chips,
                                   block_rows=self.cell.config["reference_block_rows"],
                                   precision=precision, param_dtype=param_dtype)
        del params
        jax.clear_caches()
        return out

    def check(self):
        numbers = super().check()
        if self.dropped is not None:
            numbers["moe_dropped_assignments"] = (self.dropped, 0)
            # the window trains without a balancing loss: a routing that has
            # collapsed onto experts held elsewhere by its last step (0.71 read
            # on the chip at init_std 0.02; 0.007 at most balanced, PERF.md
            # section 6) is not the deployment the cell stands for
            numbers["moe_held_shortfall"] = (self.held_shortfall(self.held_last),
                                             HELD_SHORTFALL_LIMIT)
        for name, limit in self.limits_override().items():
            numbers[name] = (numbers[name][0], limit)
        return numbers

    def limits_override(self) -> dict:
        """The limits are the chip-size cell's (references/); only a
        configuration of the tests (benchmark/tests/tiny) may state its own."""
        override = self.cell.config.get("limits", {})
        if override and os.path.samefile(self.cell.bench_dir, BENCH_DIR):
            raise ValueError(f"{self.cell.config['name']}: a configuration of the benchmark "
                             f"states no limits; references/ holds them")
        return override
