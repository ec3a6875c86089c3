"""Compilation-plane ledger (PR 15): the retrace-cause differ names
the right culprit argument for seeded shape / dtype / static-arg
signature changes (and an unchanged signature reports no retrace),
the ledger classifies causes / attributes wall durations and cache
outcomes on real jits, and the jit wrapper keeps the `.lower()` /
`make_jaxpr` surfaces the analysis entry points depend on."""

import json
import os
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.observability import compilation as C
from apex_tpu.observability.flightrec import EventRing
from apex_tpu.observability.metrics import MetricsRegistry


def _sig(**args):
    """Hand-built signature: name -> leaves list or ('static', repr)."""
    out = {}
    for name, spec in args.items():
        if isinstance(spec, tuple) and spec and spec[0] == "static":
            out[name] = {"static": spec[1]}
        else:
            out[name] = {"leaves": spec}
    return out


# -- the differ (jax-free) -------------------------------------------------

def test_differ_names_shape_culprit():
    prev = _sig(ids=[["int32", [4, 32]]], cache=[["bfloat16", [4, 2, 32, 8]]])
    cur = _sig(ids=[["int32", [4, 48]]], cache=[["bfloat16", [4, 2, 32, 8]]])
    culprits = C.diff_signatures(prev, cur)
    assert len(culprits) == 1
    assert culprits[0]["arg"] == "ids"
    assert culprits[0]["cause"] == "shape"
    assert culprits[0]["before"] == "i32[4,32]"
    assert culprits[0]["after"] == "i32[4,48]"


def test_differ_names_dtype_culprit():
    prev = _sig(ids=[["int32", [4, 32]]], cache=[["bfloat16", [4, 8]]])
    cur = _sig(ids=[["int32", [4, 32]]], cache=[["float32", [4, 8]]])
    culprits = C.diff_signatures(prev, cur)
    assert [c["arg"] for c in culprits] == ["cache"]
    assert culprits[0]["cause"] == "dtype"
    assert "bf16" in culprits[0]["before"]
    assert "f32" in culprits[0]["after"]


def test_differ_names_static_arg_culprit():
    prev = _sig(x=[["float32", [8]]], n=("static", "3"))
    cur = _sig(x=[["float32", [8]]], n=("static", "4"))
    culprits = C.diff_signatures(prev, cur)
    assert [c["arg"] for c in culprits] == ["n"]
    assert culprits[0]["cause"] == "static_arg"
    assert culprits[0]["before"] == "static:3"
    assert culprits[0]["after"] == "static:4"


def test_differ_unchanged_signature_reports_no_retrace():
    sig = _sig(ids=[["int32", [4, 32]]], n=("static", "3"))
    assert C.diff_signatures(sig, dict(sig)) == []


def test_differ_multiple_culprits_in_arg_order():
    prev = _sig(a=[["float32", [4]]], b=[["float32", [4]]],
                c=("static", "1"))
    cur = _sig(a=[["float32", [5]]], b=[["int32", [4]]],
               c=("static", "2"))
    culprits = C.diff_signatures(prev, cur)
    assert [c["arg"] for c in culprits] == ["a", "b", "c"]
    assert [c["cause"] for c in culprits] == ["shape", "dtype",
                                             "static_arg"]


def test_differ_shape_wins_over_dtype_on_one_leaf():
    # one leaf changed BOTH shape and dtype: shape is the primary
    # cause (a dtype flap on a reshaped buffer is a shape problem)
    prev = _sig(x=[["float32", [4, 8]]])
    cur = _sig(x=[["bfloat16", [4, 9]]])
    assert C.diff_signatures(prev, cur)[0]["cause"] == "shape"


# -- the ledger (jax-free recording) --------------------------------------

def test_ledger_cause_classification_and_ring():
    reg, ring = MetricsRegistry(), EventRing(capacity=64)
    led = C.CompilationLedger(registry=reg, ring=ring)
    s1 = _sig(ids=[["int32", [4, 32]]])
    s2 = _sig(ids=[["int32", [4, 48]]])
    ev1 = led.record_trace("engine._step_k", s1, closure_id=0)
    assert ev1["cause"] == "new_entry"
    ev2 = led.record_trace("engine._step_k", s2, closure_id=0)
    assert ev2["cause"] == "shape" and ev2["culprit"] == "ids"
    # same signature, NEW closure: the per-replica re-jit class
    ev3 = led.record_trace("engine._step_k", s2, closure_id=1)
    assert ev3["cause"] == "new_closure"
    # same signature, same closure: an explicit re-trace
    ev4 = led.record_trace("engine._step_k", s2, closure_id=1)
    assert ev4["cause"] == "repeat"
    snap = led.snapshot()
    st = snap["entries"]["engine._step_k"]
    assert st["traces"] == 4 and st["retraces"] == 3
    assert st["causes"] == {"new_entry": 1, "shape": 1,
                            "new_closure": 1, "repeat": 1}
    assert st["last_retrace"]["cause"] == "shape"
    assert st["last_retrace"]["culprit"] == "ids"
    assert snap["totals"]["traces"] == 4
    # ONLY the signature-change retrace reached the flight ring
    retrace_evs = ring.snapshot(kind="xla_retrace")
    assert len(retrace_evs) == 1
    assert retrace_evs[0]["cause"] == "shape"
    assert retrace_evs[0]["culprit"] == "ids"
    assert retrace_evs[0]["before"] == "i32[4,32]"
    assert retrace_evs[0]["after"] == "i32[4,48]"
    # counters carry the volume, labeled by entry and cause
    traces = reg.get("xla_traces_total")
    assert traces.labels(entry="engine._step_k").value == 4
    retr = reg.get("xla_retraces_total")
    assert retr.labels(entry="engine._step_k", cause="shape").value == 1
    assert retr.labels(entry="engine._step_k",
                       cause="new_entry").value == 1
    # the snapshot is plain JSON
    json.dumps(snap)


def test_ledger_fingerprint_identity():
    led = C.CompilationLedger(registry=MetricsRegistry(),
                              ring=EventRing(capacity=8))
    s = _sig(x=[["float32", [4]]])
    a = led.record_trace("e", s, closure_id=0)
    b = led.record_trace("e", dict(s), closure_id=1)
    c = led.record_trace("e", _sig(x=[["float32", [5]]]), closure_id=1)
    assert a["fingerprint"] == b["fingerprint"]
    assert a["fingerprint"] != c["fingerprint"]
    # different entries never share a fingerprint at the same sig
    other = C.CompilationLedger(registry=MetricsRegistry(),
                                ring=EventRing(capacity=8))
    d = other.record_trace("f", s, closure_id=0)
    assert d["fingerprint"] != a["fingerprint"]


def test_ledger_dump_roundtrip(tmp_path):
    led = C.CompilationLedger(registry=MetricsRegistry(),
                              ring=EventRing(capacity=8))
    led.record_trace("e", _sig(x=[["float32", [4]]]), closure_id=0)
    p = led.dump(str(tmp_path / "ledger.json"))
    with open(p) as f:
        snap = json.load(f)
    assert snap["kind"] == "compilation"
    assert snap["entries"]["e"]["traces"] == 1


# -- real jits --------------------------------------------------------------

def test_instrumented_jit_counts_traces_exactly():
    led = C.CompilationLedger(registry=MetricsRegistry(),
                              ring=EventRing(capacity=64))
    f = C.instrumented_jit(lambda x, n: x * n, "t.mul", ledger=led,
                           arg_names=("x", "n"), static_argnums=(1,))
    x = jnp.ones((4, 8), jnp.float32)
    assert float(f(x, 3)[0, 0]) == 3.0
    assert led.total_traces() == 1
    st = led.snapshot()["entries"]["t.mul"]
    assert st["causes"] == {"new_entry": 1}
    # the first compile's wall duration and cache column landed
    assert st["compiles"] == 1
    assert st["compile_wall_s"] > 0
    assert sum(st["cache"].values()) == 1
    # cached dispatches add nothing
    for _ in range(5):
        f(x, 3)
    assert led.total_traces() == 1
    # shape change retraces and names the culprit
    f(jnp.ones((4, 9), jnp.float32), 3)
    st = led.snapshot()["entries"]["t.mul"]
    assert st["causes"]["shape"] == 1
    assert st["last_retrace"]["culprit"] == "x"
    # dtype change
    f(jnp.ones((4, 9), jnp.bfloat16), 3)
    assert led.snapshot()["entries"]["t.mul"]["causes"]["dtype"] == 1
    # static-arg change (shapes held fixed)
    f(jnp.ones((4, 9), jnp.bfloat16), 4)
    st = led.snapshot()["entries"]["t.mul"]
    assert st["causes"]["static_arg"] == 1
    assert st["last_retrace"]["culprit"] == "n"
    assert st["traces"] == 4


def test_instrumented_jit_keeps_lower_and_make_jaxpr():
    """The analysis entry points call `.lower(*args)` and
    `jax.make_jaxpr(fn)` on the engine closures — both must survive
    the wrapper (and record un-timed traces, never a compile)."""
    led = C.CompilationLedger(registry=MetricsRegistry(),
                              ring=EventRing(capacity=64))
    f = C.instrumented_jit(lambda x: x + 1, "t.inc", ledger=led,
                           arg_names=("x",))
    x = jnp.ones((3,), jnp.float32)
    low = f.lower(x)
    assert "stablehlo" in low.as_text().lower() or low is not None
    jaxpr = jax.make_jaxpr(f)(x)
    assert jaxpr is not None
    st = led.snapshot()["entries"]["t.inc"]
    assert st["traces"] >= 1
    assert st["compiles"] == 0          # nothing dispatched
    # a same-shape dispatch reuses the trace lower() left in the jit
    # cache (no new trace, still no timed compile); a NEW shape traces
    # during dispatch and books the compile
    assert float(f(x)[0]) == 2.0
    assert led.snapshot()["entries"]["t.inc"]["compiles"] == 0
    f(jnp.ones((4,), jnp.float32))
    assert led.snapshot()["entries"]["t.inc"]["compiles"] == 1


def test_instrumented_jit_donation_passthrough():
    """donate_argnums reaches the underlying jit: the lowered module
    aliases the donated buffer (the serving engines' contract)."""
    led = C.CompilationLedger(registry=MetricsRegistry(),
                              ring=EventRing(capacity=8))
    f = C.instrumented_jit(lambda buf, v: buf + v, "t.donate",
                           ledger=led, arg_names=("buf", "v"),
                           donate_argnums=(0,))
    buf = jnp.zeros((128,), jnp.float32)
    low_text = f.lower(buf, 1.0).as_text()
    assert "tf.aliasing_output" in low_text
    out = f(buf, 1.0)
    assert float(out[0]) == 1.0


def test_process_ledger_swap_followed_per_dispatch():
    """instrumented_jit with no explicit ledger resolves the process
    ledger PER DISPATCH (the set_registry/set_ring discipline)."""
    a, b = C.CompilationLedger(), C.CompilationLedger()
    prev = C.set_ledger(a)
    try:
        f = C.instrumented_jit(lambda x: x - 1, "t.swap",
                               arg_names=("x",))
        f(jnp.ones((2,), jnp.float32))
        assert a.total_traces() == 1 and b.total_traces() == 0
        C.set_ledger(b)
        f(jnp.ones((3,), jnp.float32))    # new shape -> traces into b
        assert b.total_traces() == 1
        assert a.total_traces() == 1
    finally:
        C.set_ledger(prev)


def test_persistent_cache_attribution(tmp_path):
    """With a fresh persistent compilation cache, the first compile of
    an entry attributes MISS and a fresh closure of identical code+sig
    attributes HIT — the double_run gate's positive measurement,
    exercised in-process."""
    led = C.CompilationLedger(registry=MetricsRegistry(),
                              ring=EventRing(capacity=8))
    cache_dir = str(tmp_path / "cache")
    prev_dir = jax.config.jax_compilation_cache_dir
    prev_min = jax.config.jax_persistent_cache_min_compile_time_secs
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs",
                      0.0)
    try:
        def body(x):
            return (x * 2.0 + 1.0).sum()

        f1 = C.instrumented_jit(body, "t.cached", ledger=led,
                                arg_names=("x",))
        x = jnp.arange(64, dtype=jnp.float32)
        f1(x)
        st = led.snapshot()["entries"]["t.cached"]
        if st["cache"]["uncached"]:
            pytest.skip("jax.monitoring cache events unavailable on "
                        "this backend/version")
        assert st["cache"]["miss"] == 1
        # a fresh closure, identical code + signature: reload
        f2 = C.instrumented_jit(body, "t.cached", ledger=led,
                                arg_names=("x",))
        f2(x)
        st = led.snapshot()["entries"]["t.cached"]
        assert st["cache"]["hit"] == 1
        assert st["causes"]["new_closure"] == 1
    finally:
        jax.config.update("jax_compilation_cache_dir", prev_dir)
        jax.config.update(
            "jax_persistent_cache_min_compile_time_secs", prev_min)


def test_fresh_closure_at_new_signature_is_not_a_retrace():
    """Differently-configured engines share entry labels (gpt w1/w8 +
    llama engines all trace `engine._step_k` at different shapes): a
    fresh closure's FIRST trace is new_closure whatever its signature
    — diffing it against another closure's history is not evidence of
    shape polymorphism, and must emit no storm-class ring event."""
    reg, ring = MetricsRegistry(), EventRing(capacity=64)
    led = C.CompilationLedger(registry=reg, ring=ring)
    sig_a = _sig(ids=[["int32", [2, 16]]])
    sig_b = _sig(ids=[["int32", [2, 24]]])
    led.record_trace("engine._step_k", sig_a, closure_id=0)
    ev = led.record_trace("engine._step_k", sig_b, closure_id=1)
    assert ev["cause"] == "new_closure"
    assert ring.snapshot(kind="xla_retrace") == []
    # each closure's OWN history still diagnoses real retraces: the
    # first closure re-tracing at a new shape is a shape retrace
    # against ITS last signature, interleaving notwithstanding
    ev2 = led.record_trace("engine._step_k",
                           _sig(ids=[["int32", [2, 48]]]),
                           closure_id=0)
    assert ev2["cause"] == "shape"
    assert ev2["culprits"][0]["before"] == "i32[2,16]"
    assert ev2["culprits"][0]["after"] == "i32[2,48]"
    assert len(ring.snapshot(kind="xla_retrace")) == 1


def test_sequential_engines_do_not_storm_the_supervisor():
    """The end-to-end false-positive guard: building three
    differently-shaped engines back to back (each re-jitting the same
    entry labels) must fire ZERO recompilation_storm anomalies on a
    supervisor watching the shared ring."""
    from apex_tpu import models, serving
    from apex_tpu.observability import (EventRing as _ER,
                                        RunSupervisor, SupervisorConfig,
                                        flightrec)
    ring = _ER(capacity=256)
    prev = flightrec.set_ring(ring)
    try:
        sup = RunSupervisor("t", ring=ring,
                            config=SupervisorConfig(
                                storm_retraces=3,
                                storm_window_observations=20))
        for i, (buf, win) in enumerate(((16, 1), (16, 8), (24, 2))):
            cfg = models.GPTConfig(vocab_size=64, block_size=buf,
                                   n_layer=1, n_head=2, n_embd=16,
                                   dropout=0.0)
            mm = models.GPT(cfg)
            pp, _ = mm.init(jax.random.PRNGKey(i))
            serving.Engine(mm, pp, slots=2, buf_len=buf,
                           window=win).warmup()
            found = sup.observe_step(step=i, loss=1.0)
            assert found == [], found
        assert sup._counts["recompilation_storm"] == 0
    finally:
        flightrec.set_ring(prev)


# -- PR 24: stage times of a tracing dispatch, and the entry's own text -----

@pytest.fixture
def fresh_cache(tmp_path):
    """A persistent compilation cache of this test's own, every compile cached."""
    from jax.experimental.compilation_cache import compilation_cache
    options = {"jax_compilation_cache_dir": str(tmp_path / "cache"),
               "jax_persistent_cache_min_compile_time_secs": 0.0,
               "jax_persistent_cache_min_entry_size_bytes": -1}
    prev = {k: getattr(jax.config, k) for k in options}
    for k, v in options.items():
        jax.config.update(k, v)
    compilation_cache.reset_cache()          # this directory, whatever was open
    yield tmp_path / "cache"
    for k, v in prev.items():
        jax.config.update(k, v)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("dispatch", ["cold", "cached"])
def test_stage_times_of_a_cold_and_a_cached_dispatch(fresh_cache, dispatch):
    led = C.CompilationLedger()

    def body(x):
        return jnp.tanh(x @ x).sum()
    x = jnp.ones((32, 32))
    C.instrumented_jit(body, "t.stages", ledger=led)(x)
    record = led.snapshot()["entries"]["t.stages"]
    if dispatch == "cached":
        if record["cache"]["uncached"]:
            pytest.skip("jax.monitoring cache events unavailable")
        cold = dict(record)
        C.instrumented_jit(body, "t.stages", ledger=led)(x)      # a fresh closure: reload
        record = led.snapshot()["entries"]["t.stages"]
        assert record["cache"]["hit"] == 1
        assert record["cache_load_s"] > 0.0
        # the load is not counted again as compiling
        added = record["backend_compile_s"] - cold["backend_compile_s"]
        assert added <= 0.25 * cold["backend_compile_s"] + 0.01
    else:
        assert record["cache_load_s"] == 0.0 and record["backend_compile_s"] > 0.0
    assert record["trace_s"] > 0.0 and record["lower_s"] > 0.0
    assert set(C.STAGE_FIELDS) <= set(record)
    assert sum(record[f] for f in C.STAGE_FIELDS) <= record["compile_wall_s"] * 1.05


def _scoped(led, scope):
    def step(x):
        with jax.named_scope(scope):
            return jnp.tanh(x @ x).sum()
    return C.instrumented_jit(step, "t." + scope, ledger=led)


def test_compiled_text_is_this_programs_own_whatever_the_cache_held(fresh_cache):
    """The persistent cache's key leaves metadata out: a dispatch may load an
    executable compiled from the same operations under other scopes."""
    led = C.CompilationLedger()

    x = jnp.ones((48, 48))
    older, newer = _scoped(led, "older_scope"), _scoped(led, "amp.update")
    older(x)
    newer(x)
    if led.snapshot()["entries"]["t.amp.update"]["cache"]["hit"] != 1:
        pytest.skip("the second program did not load the first one's executable")
    entries = lambda: sorted(f for f in os.listdir(fresh_cache) if not f.endswith("-atime"))
    held = entries()
    text = led.compiled_text("t.amp.update")
    assert "/amp.update/" in text and "older_scope" not in text
    # nothing was written to the cache, and the options are as they were
    assert entries() == held
    assert not jax.config.jax_compilation_cache_include_metadata_in_key
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0


def test_compiled_text_of_a_dispatch_that_compiled_is_that_executables(fresh_cache):
    """A dispatch that missed the cache compiled this program's own module:
    its text is read back from jax's in-memory caches, with no second compile."""
    led = C.CompilationLedger()
    program = _scoped(led, "amp.pack")
    program(np.ones((40, 40), np.float32))       # a numpy argument is abstracted too
    ref, args, kwargs, compiled_here = led._lowerable["t.amp.pack"]
    assert compiled_here and isinstance(args[0], jax.ShapeDtypeStruct)

    def no_second_compile(*a, **k):
        raise AssertionError("compiled past the cache")
    program.lower_afresh = no_second_compile
    before = led.total_traces()
    assert "/amp.pack/" in led.compiled_text("t.amp.pack")
    assert led.total_traces() == before


def test_compiled_text_from_two_threads_leaves_the_settings_alone(fresh_cache):
    """Two callers at once, both past the cache: the settings are this
    thread's only, so no other thread's compile sees them and none is left
    behind; the compiles take their turns."""
    from jax._src import config as jax_config
    led = C.CompilationLedger()
    x = jnp.ones((56, 56))
    programs = {scope: _scoped(led, scope)     # the ledger holds them weakly
                for scope in ("older_a", "amp.update", "older_b", "amp.unscale")}
    for program in programs.values():
        program(x)
    entries = led.snapshot()["entries"]
    if not all(entries["t." + s]["cache"]["hit"] == 1 for s in ("amp.update", "amp.unscale")):
        pytest.skip("the second programs did not load the first ones' executables")
    inside, seen = [0], []
    for scope in ("amp.update", "amp.unscale"):
        fn = programs[scope]

        def watched(*a, _afresh=fn.lower_afresh, **k):
            inside[0] += 1
            seen.append((inside[0],
                         jax_config.compilation_cache_include_metadata_in_key.value))
            try:
                time.sleep(0.05)
                return _afresh(*a, **k)
            finally:
                inside[0] -= 1
        fn.lower_afresh = watched
    texts = {}
    threads = [threading.Thread(
        target=lambda s=s: texts.__setitem__(s, led.compiled_text("t." + s)))
        for s in ("amp.update", "amp.unscale")]
    for t in threads:
        t.start()
    while any(t.is_alive() for t in threads):
        # what this thread would compile with, all the while
        assert not jax_config.compilation_cache_include_metadata_in_key.value
        time.sleep(0.005)
    for t in threads:
        t.join()
    assert seen == [(1, True), (1, True)]
    assert "/amp.update/" in texts["amp.update"] and "older" not in texts["amp.update"]
    assert "/amp.unscale/" in texts["amp.unscale"] and "older" not in texts["amp.unscale"]
    assert not jax.config.jax_compilation_cache_include_metadata_in_key
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0


def test_nested_jit_traces_are_counted_once():
    rec = C._Dispatch(C.CompilationLedger(), "t")
    rec.trace_spans += [(1.0, 2.0), (1.2, 1.4), (0.0, 3.0), (5.0, 6.0)]
    assert rec.trace_s == pytest.approx(4.0)
