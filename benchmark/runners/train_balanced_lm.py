"""Runner for next-token pretraining of a sparse decoder whose routers choose
by ``score + expert_bias`` and whose family keeps its experts' loads even by
moving that bias (a rule outside the optimizer, which no configuration
publishes): ``runners.train_causal_lm``'s set-up, window, traffic and
comparison, with ONE thing changed: the selection bias of the seeded weights is
**balanced on the seed's first batch** before anything reads the weights, and
the same tree goes to the program and to the plain reference.

Why (PERF.md section 6, PR 44): a checkpoint of such a family holds a bias at
the balancing rule's fixed point, where every expert's load is even.  A bias
drawn N(0, init_std) like the other leaves balances nothing, and under seeded
weights this family's routers are far from even (non-gated relu2 experts and a
convolution's bias give the stream a part common to all tokens, which a random
router column either likes or does not): the share of the assignments that
falls on the 8 experts a chip holds then varies by a sixth of itself from seed
to seed, and one seed in eight starts under the runner's own limit on
``moe_held_shortfall`` before a single step has trained.

The balanced bias is the seed's and plain code's alone: the configuration's
reference (``references/<name>.py``: ``routing(params, ids, cfg, balance=True)``,
float32, nothing of the program) walks the seed's first batch through the
seeded weights and applies the family's rule to each expert layer's scores in
turn.  What was reached goes on an earlier line (``bias_balanced``: the
fullest expert's load over the mean, before and after, by the reference's own
float32 scores; the program's loads are the window's ``by_step`` line).

``lib/weights.make_weights`` is the one place ``runners/train_example.py``
draws a tree from (the program's, the one its update is read against, the
reference's), and it has no seam of the runner's own: the draw is wrapped for
the length of ``setup`` and of ``reference_readings`` and handed back as each
returns (PERF.md section 7 names the seam a ``benchmark`` issue would add).
"""

import contextlib

import numpy as np

from lib import weights
from runners import train_causal_lm


class Runner(train_causal_lm.Runner):
    def __init__(self, cell, spans, log):
        super().__init__(cell, spans, log)
        self.bias = None            # {layer: balanced bias (E,)}, from the first draw on

    def _balance(self, params):
        """The first tree drawn -> {layer: bias}, on the host."""
        import jax
        walk = jax.jit(lambda p, ids: self.reference.routing(p, ids, self.model_cfg, balance=True))
        with self.spans.span("balance_bias"):
            rows = jax.device_get(walk(params, self._batch(0)[0]))
        fullest = lambda name: [float(r[name].max() / r[name].mean()) for r in rows]
        self.log({"bias_balanced": {"layers": [str(r["layer"]) for r in rows],
                                    "steps": self.reference.BALANCE["steps"],
                                    "fullest_over_mean_before": fullest("drawn_loads"),
                                    "fullest_over_mean_after": fullest("loads")}})
        return {str(r["layer"]): np.asarray(r["bias"]) for r in rows}

    @contextlib.contextmanager
    def _balanced_draw(self):
        """``weights.make_weights`` gives the seed's tree with the balanced bias."""
        import jax
        drawn = weights.make_weights

        def make(shapes, seed, std=0.02, sharding=None):
            params = drawn(shapes, seed, std, sharding)
            if self.bias is None:
                self.bias = self._balance(params)
            layers = dict(params["layers"])
            for i, bias in self.bias.items():
                mlp = layers[i]["mlp"]
                layers[i] = {**layers[i], "mlp": {
                    **mlp, "expert_bias": jax.device_put(bias, mlp["expert_bias"].sharding)}}
            return {**params, "layers": layers}

        weights.make_weights = make
        try:
            yield
        finally:
            weights.make_weights = drawn

    def setup(self):
        with self._balanced_draw():
            super().setup()

    def reference_readings(self, precision="float32", param_dtype="float32"):
        with self._balanced_draw():
            return super().reference_readings(precision, param_dtype)
