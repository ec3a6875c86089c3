"""What one run of one cell does, after ``run.py`` has found the chips.

    cell files -> runner.setup() -> runner.window() -> memory peak
               -> runner.release() -> runner.check() -> one JSON line

Everything that belongs to one configuration, traffic mix or per-layer metric
is a file found by the name in ``BENCHMARK.json``:

    configs/<config>.json     sizes as run; names its ``runner`` and ``reference``
    traffic/<mix>.json        ``generator`` of lib/traffic.py and its parameters
    metrics/<metric>.json     ``reader`` and its parameters
    readers/<reader>.py       ``read(ctx, **params) -> number or None``
    runners/<runner>.py       ``Runner(cell, spans, log)``: how a kind of job is
                              built, warmed, driven and checked
    references/<name>.py      the plain reference and the limits of ``correct``
"""

from __future__ import annotations

import contextlib
import dataclasses
import gc
import importlib
import json
import os
import shutil
import sys
import time
from typing import Any, Callable, Dict, List, Optional

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if BENCH_DIR not in sys.path:
    sys.path.insert(0, BENCH_DIR)

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT = "/jax/compilation_cache/cache_hits"
CACHE_MISS = "/jax/compilation_cache/cache_misses"
TRACE_SECONDS = 2.0          # of the steady stretch; the whole window is never traced
TRACE_ITERATIONS = 5         # and at least this many harness iterations


@dataclasses.dataclass
class Cell:
    name: str
    config: dict
    traffic: dict
    chips: int
    seed: int
    seconds: float
    trace: bool
    manifest: dict
    root: str                       # the checkout (BENCHMARK.json, the program)
    bench_dir: str = BENCH_DIR      # where configs/, traffic/, metrics/ are
    t_start: float = 0.0            # perf_counter at process start


def _load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(manifest: dict, workload: str, seed: int, seconds: float, trace: bool,
              root: str, bench_dir: str = BENCH_DIR, t_start: Optional[float] = None) -> Cell:
    entry = next((w for w in manifest["workloads"] if w["name"] == workload), None)
    if entry is None:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    config = _load_json(os.path.join(bench_dir, "configs", entry["config"] + ".json"))
    traffic = _load_json(os.path.join(bench_dir, "traffic", entry["traffic"] + ".json"))
    return Cell(workload, config, traffic, int(entry["chips"]), int(seed), float(seconds),
                bool(trace), manifest, root, bench_dir,
                time.perf_counter() if t_start is None else t_start)


class Spans:
    """The harness's own spans around its calls into each layer: kept in
    memory on the host clock, and written into the profiler's trace too."""

    def __init__(self):
        self.records: List[tuple] = []

    @contextlib.contextmanager
    def span(self, name: str):
        import jax
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(name):
            yield
        self.records.append((name, t0, time.perf_counter()))


class CompileWatch:
    """Counts of backend compiles and persistent-cache events in the process."""

    def __init__(self):
        from jax import monitoring
        self.compiles = self.hits = self.misses = 0
        monitoring.register_event_duration_secs_listener(self._on_duration)
        monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, duration, **_):
        if event == COMPILE_EVENT:
            self.compiles += 1

    def _on_event(self, event, **_):
        if event == CACHE_HIT:
            self.hits += 1
        elif event == CACHE_MISS:
            self.misses += 1

    def snapshot(self) -> dict:
        return {"backend_compiles": self.compiles, "cache_hits": self.hits,
                "cache_misses": self.misses}


class Tracer:
    """Traces a few iterations of the steady stretch, never the whole window.
    The runner calls ``tick(elapsed, step)`` between iterations: the trace
    starts at a quarter of the window and stops at the first tick that is both
    ``TRACE_SECONDS`` and ``TRACE_ITERATIONS`` later.  A quarter of the window
    is a quarter of ``steps`` where the configuration states how many steps
    its window runs (``window_steps``: a job whose work a step depends on its
    own trajectory, so both sides of a pair have to trace the same steps), and
    ``seconds / 4`` of the clock where it states none.  The first traced
    iteration pays the profiler's start and is left out of the stretch."""

    def __init__(self, enabled: bool, seconds: float, out_dir: str,
                 steps: Optional[int] = None):
        self.enabled, self.dir = enabled, out_dir
        self.start_at = seconds / 4.0
        self.start_step = None if steps is None else steps // 4
        self.state = "off" if not enabled else "waiting"
        self.iterations = 0
        self.started = None             # (elapsed, step) of the tick that started the trace

    def _due(self, elapsed: float, step: int) -> bool:
        return elapsed >= self.start_at if self.start_step is None else step >= self.start_step

    def tick(self, elapsed: float, step: int) -> None:
        if self.state == "waiting" and self._due(elapsed, step):
            import jax
            shutil.rmtree(self.dir, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(self.dir, profiler_options=opts)
            self.state, self.started = "tracing", (elapsed, step)
        elif self.state == "tracing":
            self.iterations += 1
            # TRACE_SECONDS from where the trace was due: the clock's quarter, or the
            # tick of the counted step
            since = self.start_at if self.start_step is None else self.started[0]
            if elapsed >= since + TRACE_SECONDS and self.iterations >= TRACE_ITERATIONS:
                self.stop()

    def stop(self) -> None:
        if self.state == "tracing":
            import jax
            jax.profiler.stop_trace()
            self.state = "done"


@dataclasses.dataclass
class ReadContext:
    """What a reader may read."""
    cell: Cell
    facts: Dict[str, Any]            # counts and harness-clock timings of the run
    spans: List[tuple]               # (name, t0, t1) on the host clock
    trace: Optional[dict]            # lib/trace.py structure, or None
    ops: Dict[int, list]             # chip -> XLA Ops events
    stretch: Optional[tuple]         # (lo, hi) ns of the traced steady stretch
    iterations: int                  # harness iterations wholly inside the stretch
    peaks: Optional[dict]            # lib/peaks.py row, None off the chip


def device_info() -> dict:
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind, "count": len(devs)}


def memory_bytes(devices, key: str) -> int:
    values = [(d.memory_stats() or {}).get(key, 0) for d in devices]
    return int(max(values)) if values else 0


def _metric_entries(cell: Cell, group: str) -> List[dict]:
    return [m for m in cell.manifest[group]
            if "workloads" not in m or cell.name in m["workloads"]]


def _reduce_trace(cell: Cell, tracer: Tracer, runner, spans: Spans, facts: dict, log) -> tuple:
    """(ReadContext, device fields, breakdown) of a traced run."""
    from lib import peaks as pk, trace as tr
    info = device_info()
    peaks = pk.peaks_for(info["kind"]) if info["platform"] == "tpu" else None
    trace, ops, stretch, iterations = None, {}, None, 0
    if tracer.state == "done":
        trace = tr.load_xplane(tr.find_xplane(tracer.dir), tr.default_keep)
        shutil.rmtree(tracer.dir, ignore_errors=True)
        ops = {c: e for c, e in tr.device_ops(trace).items() if c < cell.chips}
        marks = tr.host_spans(trace, [runner.ITERATION_SPAN])
        # whole iterations only, without the first (it pays the profiler's start):
        # from the second iteration span's start to the last one's
        if len(marks) >= 4:
            stretch = (marks[1][1], marks[-1][1])
            iterations = len(marks) - 2
    ctx = ReadContext(cell, facts, spans.records, trace, ops, stretch, iterations, peaks)
    device, breakdown = {}, None
    if ops and stretch:
        lo, hi = stretch
        busy = [tr.busy(ev, lo, hi) / 1e9 for ev in ops.values()]
        device = {"busy_s": sum(busy) / len(busy), "window_s": (hi - lo) / 1e9}
        first = ops[min(ops)]
        breakdown = {"device_ops": tr.top_ops(first, lo, hi, 10),
                     "idle_gaps": tr.idle_gaps(first, tr.host_spans(trace, runner.SPAN_NAMES),
                                               lo, hi, 10)}
    elif cell.trace:
        log({"note": "the trace holds no device operations", "tracer": tracer.state})
    return ctx, device, breakdown


def read_per_layer(cell: Cell, ctx: ReadContext, log: Callable) -> Dict[str, dict]:
    out = {}
    for entry in _metric_entries(cell, "per_layer"):
        spec = _load_json(os.path.join(cell.bench_dir, "metrics", entry["name"] + ".json"))
        reader = importlib.import_module("readers." + spec["reader"])
        value = reader.read(ctx, **spec.get("params", {}))
        if value is None:
            continue
        if isinstance(value, dict):          # a reader may say more on an earlier line
            log({"metric": entry["name"], **value})
            value = value["value"]
        out[entry["name"]] = {"value": float(value), "unit": entry["unit"]}
    return out


def run_cell(cell: Cell, log: Callable[[dict], None]) -> dict:
    """One run.  Returns the object of the last line; ``log`` gets every
    earlier line (lateness, cache events, each compared number and its limit)."""
    import jax
    spans, watch = Spans(), CompileWatch()
    runner_mod = importlib.import_module("runners." + cell.config["runner"])
    runner = runner_mod.Runner(cell, spans, log)
    runner.setup()
    setup_s = time.perf_counter() - cell.t_start
    warm = watch.snapshot()
    log({"setup_s": setup_s, **runner.timings, **warm, "device": device_info()})

    from apex_tpu.observability import compilation
    traces0 = compilation.get_ledger().total_traces()
    tracer = Tracer(cell.trace, cell.seconds,
                    os.path.join(cell.root, ".bm_trace", cell.name),
                    cell.config.get("window_steps"))
    measured = runner.window(cell.seconds, tracer)
    tracer.stop()
    if tracer.started:
        log({"traced_from": {"elapsed_s": tracer.started[0], "step": tracer.started[1],
                             "ticks": tracer.iterations}})
    in_window = {"traces": compilation.get_ledger().total_traces() - traces0,
                 "backend_compiles": watch.compiles - warm["backend_compiles"]}
    log({"inside_window": in_window})
    # the runtime's peak counter shows live buffers and never a program's
    # temporaries (PR 21), so the peak on the fullest chip is what was live when
    # the window closed plus the temporaries its program plans
    live, temp = memory_bytes(runner.devices, "bytes_in_use"), runner.planned_temp_bytes()
    peak = max(memory_bytes(runner.devices, "peak_bytes_in_use"), live + temp)
    log({"memory": {"bytes_in_use": live, "window_program_temp_bytes": temp,
                    "runtime_peak_bytes_in_use": memory_bytes(runner.devices,
                                                              "peak_bytes_in_use")}})

    facts = {**runner.timings, **measured.get("facts", {}), "setup_s": setup_s}
    if cell.trace:
        ctx, device_extra, breakdown = _reduce_trace(cell, tracer, runner, spans, facts, log)
        metrics = read_per_layer(cell, ctx, log)
    else:
        device_extra, breakdown = {}, None
        values = {**measured["metrics"], "setup_s": setup_s}
        metrics = {m["name"]: {"value": float(values[m["name"]]), "unit": m["unit"]}
                   for m in _metric_entries(cell, "end_to_end")}

    runner.release()
    gc.collect()
    t = time.perf_counter()
    numbers = runner.check()
    log({"check_s": time.perf_counter() - t})
    numbers["traces_inside_window"] = (in_window["traces"], 0)
    numbers["compiles_inside_window"] = (in_window["backend_compiles"], 0)
    correct = True
    for name, (value, limit) in numbers.items():
        ok = bool(value <= limit)
        correct &= ok
        log({"compared": name, "value": value, "limit": limit, "ok": ok})
    failed = int(measured["failed"])
    if in_window["traces"] or in_window["backend_compiles"]:
        failed = int(measured["attempted"])
    result = {"correct": bool(correct), "attempted": int(measured["attempted"]),
              "failed": failed, "metrics": metrics,
              "device": {**device_info(), "memory_peak_bytes": peak, **device_extra}}
    if breakdown:
        result["breakdown"] = breakdown
    return result
