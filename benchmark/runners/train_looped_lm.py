"""Runner for next-token pretraining of a decoder whose layer stack is looped
(``total_ut_steps`` passes over the same weights, a learned exit gate):
``runners.train_causal_lm``'s set-up, window, traffic and comparison, with two
facts of the window added from the step's own sums (``exit_step_sum``,
``nll_last_sum``, ``exit_positions``, which the step adds up over the chips):

    exit_mean_step   the expected exit pass, the mean over the window's steps
                     and positions: whether the exit distribution has collapsed
                     onto one pass inside the window
    nll_last         the last pass's mean next-token loss over the same steps

and the first and last step's values on an earlier line.
"""

import numpy as np

from runners import train_causal_lm


def _ratios(m) -> dict:
    n = float(m["exit_positions"])
    return {"exit_mean_step": float(m["exit_step_sum"]) / n,
            "nll_last": float(m["nll_last_sum"]) / n}


class Runner(train_causal_lm.Runner):
    def window(self, seconds, tracer):
        before = len(self.steps)
        out = super().window(seconds, tracer)
        mine = [_ratios(m) for m in self.steps[before:]]
        out["facts"].update({k: float(np.mean([m[k] for m in mine])) for k in mine[0]})
        self.log({"exit": {k: out["facts"][k] for k in mine[0]},
                  "first_step": mine[0], "last_step": mine[-1]})
        return out
