"""The benchmark window's rule, guarded in tier-1: the 14 cases of
``benchmark/tests/test_bm_window.py`` as they are, imported and not copied (a
stub runner and a stub profiler, no model, a few seconds).  Four
configurations state ``window_steps`` (the three sparse decoders since PR 42
and ``nemotron3-nano-30b-a3b``), so a change to ``runners/train_example.py``'s
window or to ``lib/harness.py``'s tracer is held here by every PR's own test
run and not only by the harness's tests."""

import os
import sys

BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmark")
for path in (BENCH, os.path.join(BENCH, "tests")):
    if path not in sys.path:
        sys.path.insert(0, path)

from test_bm_window import (  # noqa: E402,F401
    quiet_profiler, test_a_tracer_that_is_off_never_starts,
    test_tracer_starts_at_a_quarter_of_the_count_or_of_the_clock,
    test_window_ends_at_the_stated_step_or_at_the_clock)
