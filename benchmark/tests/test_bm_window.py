"""The window's rule, with no model: a stub runner whose step sleeps and
counts.  Where the configuration states ``window_steps`` the window ends at
that step whatever ``--seconds`` is (or at the clock, if that comes first), an
earlier line says which ended it, and the tracer starts at a quarter of the
count; where it states none the window and the tracer go by the clock."""

import time
import types

import pytest

from lib import harness
from runners import train_example

STEP_S = 0.004


def _stub(window_steps):
    """A ``train_example.Runner`` that was never built: what ``window`` reads."""
    config = {} if window_steps is None else {"window_steps": window_steps}
    r = object.__new__(train_example.Runner)
    r.cell = types.SimpleNamespace(config=config, chips=1)
    r.lines, r.rows, r.traffic, r.model_cfg = [], 2, {"seq_len": 32}, config
    r.log = r.lines.append
    r.run = types.SimpleNamespace(ddp=None)
    r.state = (None, types.SimpleNamespace(masters=types.SimpleNamespace(
        buf=types.SimpleNamespace(size=10))))
    r.step_index = 0

    def one_step():
        time.sleep(STEP_S)
        r.step_index += 1
        return {"loss": 1.0, "found_inf": 0.0, "loss_scale": 1.0, "index": r.step_index}
    r._one_step = one_step
    return r


class _Ticks:
    """A tracer that only keeps what it was told."""

    def __init__(self):
        self.seen = []

    def tick(self, elapsed, step):
        self.seen.append((elapsed, step))


@pytest.mark.parametrize("window_steps,seconds,ended_by,steps", [
    (8, 5.0, "count", 8),            # the same step for two different --seconds
    (8, 10.0, "count", 8),
    (1, 5.0, "count", 1),
    (10**6, 0.05, "clock", None),    # the clock comes first
    (None, 0.05, "clock", None),     # no count stated: today's rule
    (None, 0.1, "clock", None),
])
def test_window_ends_at_the_stated_step_or_at_the_clock(window_steps, seconds, ended_by, steps):
    r, ticks = _stub(window_steps), _Ticks()
    out = r.window(seconds, ticks)
    said = [l for l in r.lines if "window_ended_by" in l]
    assert len(said) == 1 and said[0]["window_ended_by"] == ended_by
    assert said[0]["at_step"] == out["attempted"] == out["facts"]["steps"] == r.step_index
    assert said[0]["window_steps"] == window_steps and said[0]["seconds"] == seconds
    if steps is not None:
        assert out["attempted"] == steps and out["facts"]["window_s"] < seconds
    else:
        # the first step that ends past the clock is the last
        assert 1 <= out["attempted"] <= seconds / STEP_S + 1
        assert out["facts"]["window_s"] >= seconds
    assert out["failed"] == 0
    # the rate is every step that ran over all the time they took
    rate = out["attempted"] * r.rows / out["facts"]["window_s"]
    assert abs(out["metrics"]["train.samples_per_s"] - rate) < 1e-9
    # the tracer hears the step index before every step
    assert [s for _, s in ticks.seen] == list(range(out["attempted"]))


@pytest.fixture
def quiet_profiler(monkeypatch):
    import jax
    calls = []
    monkeypatch.setattr(jax.profiler, "start_trace", lambda *a, **k: calls.append("start"))
    monkeypatch.setattr(jax.profiler, "stop_trace", lambda *a, **k: calls.append("stop"))
    return calls


@pytest.mark.parametrize("steps,seconds,step_s,first_traced", [
    (96, 40.0, 0.3, 24),             # a quarter of the count, whatever --seconds
    (96, 60.0, 0.3, 24),
    (96, 40.0, 0.2, 24),             # and however fast the step
    (184, 40.0, 0.2, 46),
    (None, 40.0, 0.3, 34),           # none stated: the first tick at or past seconds / 4
    (None, 60.0, 0.3, 50),
    (None, 40.0, 0.2, 50),
])
def test_tracer_starts_at_a_quarter_of_the_count_or_of_the_clock(quiet_profiler, tmp_path, steps,
                                                                 seconds, step_s, first_traced):
    tracer = harness.Tracer(True, seconds, str(tmp_path / "trace"), steps)
    states = []
    for step in range(200):
        tracer.tick(step * step_s, step)
        states.append(tracer.state)
    assert states.index("tracing") == first_traced == tracer.started[1]
    # it stops at the first tick both TRACE_SECONDS and TRACE_ITERATIONS later
    ticks = max(harness.TRACE_ITERATIONS, -(-harness.TRACE_SECONDS // step_s))
    if steps is None:                # by the clock, from seconds / 4 and not from the tick
        ticks = next(k for k in range(1, 200) if k >= harness.TRACE_ITERATIONS and
                     (first_traced + k) * step_s >= seconds / 4 + harness.TRACE_SECONDS)
    assert states.index("done") == first_traced + ticks and tracer.iterations == ticks
    assert quiet_profiler == ["start", "stop"]


def test_a_tracer_that_is_off_never_starts(quiet_profiler, tmp_path):
    tracer = harness.Tracer(False, 40.0, str(tmp_path / "trace"), 96)
    for step in range(100):
        tracer.tick(step * 0.3, step)
    tracer.stop()
    assert tracer.state == "off" and tracer.started is None and quiet_profiler == []
