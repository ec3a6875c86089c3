"""Compilation-plane ledger: every jit trace/compile, measured in-process.

Apex's identity is "compile once, then run" — yet until this module the
observability plane was blind to XLA compilation itself, even though
four logged gotchas are compile-plane failures: per-replica re-jits
making a cold fleet measure N compiles (PR 4), the
donated-executable persistent-cache reload corruption (PR 2),
concurrent compile-cache poisoning (PR 2's parallel-pytest note), and
compile seconds folded into a goodput rate (PR 10).
:class:`CompilationLedger` records every trace of an instrumented jit
entry — the entry label, the abstract argument
signature (leaf shapes/dtypes + static-arg values), the dispatch's wall
duration, the persistent-compilation-cache hit/miss attribution, and a
signature fingerprint — and classifies each trace's CAUSE against the
entry's previous signature via the retrace differ
(:func:`diff_signatures`), which names *which argument* changed and
how.

How traces are counted — the jit-side-effect trick: the instrumented
function body runs only while jax is TRACING (cached dispatches never
re-enter python), so a host-side ``record_trace`` call inside the
wrapped function fires exactly once per trace, with the abstract
signature computed from the tracer avals it was handed.  Steady-state
(cached) dispatches pay one thread-local push/pop and two clock reads —
no signature walk, no locks on the hot path.

Persistent-cache attribution rides ``jax.monitoring``: the
``/jax/compilation_cache/cache_hits`` / ``cache_misses`` events and the
``/jax/core/compile/backend_compile_duration`` duration fire on the
dispatching thread, so a process-wide listener attributes them to the
ledger dispatch in flight on that thread (installed lazily at the first
:func:`instrumented_jit`; absent monitoring support the cache column
reads ``uncached``).

The same listeners keep, per tracing dispatch, the stages jax itself
times (:data:`STAGE_FIELDS`: tracing, jaxpr -> MLIR, the persistent
cache's retrieval, the backend compile), and a tracing dispatch leaves
what :meth:`CompilationLedger.compiled_text` needs to hand out the
entry's optimized HLO text later (``observability.phases`` reads the
phase of every instruction from it).  A cached dispatch leaves nothing.

Causes (:data:`RETRACE_CAUSES`):

- ``new_entry`` — the entry's first trace ever (the expected warmup
  compile);
- ``shape`` / ``dtype`` / ``static_arg`` — a *signature-change*
  retrace: some argument's abstract signature differs from THIS
  closure's previous trace (the diff always runs against the same
  closure's own history — two differently-shaped engines sharing an
  entry label are not each other's retraces); the differ names the
  culprit argument and its before/after signatures.  These are the
  storm class (shape-polymorphic recompilation in serving is exactly
  what ROADMAP item 1's paged-KV/chunked-prefill refactor risks) and
  the only causes that reach the flight ring (``xla_retrace`` events —
  the ``RunSupervisor``'s ``recompilation_storm`` detector feeds on
  them);
- ``new_closure`` — a *fresh* jit closure's first trace of an
  already-known entry, whatever its signature: the per-replica re-jit
  class (every ``Engine`` instance builds its own closures), which
  :meth:`~apex_tpu.fleet.Fleet.warmup` exists to pay before traffic;
- ``repeat`` — the same closure re-traced an identical signature (an
  explicit ``.lower()`` / ``make_jaxpr`` pass, or a jit cache
  eviction).

Metrics (process registry unless the ledger is given one):
``xla_traces_total{entry}``, ``xla_retraces_total{entry, cause}``,
``xla_compiles_total{entry, cache}`` (cache in hit/miss/uncached),
``xla_compile_seconds`` (wall duration of tracing dispatches).

The zero-retrace contracts are delta checks over :meth:`total_traces`:
after warmup, N mixed decode windows (serving) or a fleet failover
restarting reclaimed requests on survivors must add exactly 0 traces —
pinned in tests/test_serving.py and tests/test_fleet.py the way the
host-transfer audit pins its own invariant.

Import-light by design (stdlib only at module scope): the
``/compilez`` endpoint and tests/ci/server_smoke.py consume snapshots
without jax; :meth:`CompilationLedger.record_trace` is the jax-free
recording primitive the jit wrapper (and jax-free tests) drive.
"""

from __future__ import annotations

import hashlib
import itertools
import json
import os
import threading
import time
import weakref
from collections import deque
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

__all__ = ["RETRACE_CAUSES", "SIGNATURE_CHANGE_CAUSES",
           "STAGE_FIELDS", "CompilationLedger",
           "abstract_signature", "diff_signatures", "format_signature",
           "signature_fingerprint", "instrumented_jit",
           "get_ledger", "set_ledger"]

# every cause a recorded trace can carry (see module docstring)
RETRACE_CAUSES = ("new_entry", "shape", "dtype", "static_arg",
                  "new_closure", "repeat")
# the storm class: a signature actually CHANGED between two traces of
# one entry — only these reach the flight ring / supervisor detector
SIGNATURE_CHANGE_CAUSES = ("shape", "dtype", "static_arg")

# compile wall durations span sub-ms toy CPU traces to minutes-scale
# hardware compiles
_COMPILE_SECONDS_BUCKETS = (0.001, 0.005, 0.01, 0.05, 0.1, 0.25, 0.5,
                            1.0, 2.5, 5.0, 10.0, 30.0, 60.0, 120.0,
                            300.0)

_closure_ids = itertools.count()

# per-thread stack of in-flight instrumented dispatches: the jit-time
# side effect and the jax.monitoring listeners attribute what they see
# to the top of the dispatching thread's stack
_inflight = threading.local()


def _stack() -> List["_Dispatch"]:
    st = getattr(_inflight, "stack", None)
    if st is None:
        st = _inflight.stack = []
    return st


def current_dispatch() -> Optional["_Dispatch"]:
    st = _stack()
    return st[-1] if st else None


class _Dispatch:
    """One in-flight call of an instrumented jit: collects the trace
    events recorded during it plus the cache/compile-duration events
    the monitoring listeners attribute to this thread."""

    __slots__ = ("ledger", "entry", "events", "cache_hits",
                 "cache_misses", "backend_compile_s", "lower_s",
                 "cache_load_s", "trace_spans")

    def __init__(self, ledger: "CompilationLedger", entry: str):
        self.ledger = ledger
        self.entry = entry
        self.events: List[Dict[str, Any]] = []
        self.cache_hits = 0
        self.cache_misses = 0
        self.backend_compile_s = 0.0
        self.lower_s = 0.0
        self.cache_load_s = 0.0
        # jax times every nested jit's trace inside the outer one's:
        # the spans are kept and their union is the tracing time
        self.trace_spans: List[Tuple[float, float]] = []

    @property
    def compile_s(self) -> float:
        """Seconds in the backend's compiler proper: jax's event spans
        compile-or-load, so a persistent-cache hit's retrieval is taken
        out of it and the stage fields stay disjoint."""
        return max(self.backend_compile_s - self.cache_load_s, 0.0)

    @property
    def compiled_here(self) -> bool:
        """The backend compiled in this dispatch and nothing came out of
        the persistent cache: the executable is of this program's own
        module (false also where the listeners saw nothing)."""
        return self.cache_hits == 0 and self.backend_compile_s > 0.0

    @property
    def trace_s(self) -> float:
        total, end = 0.0, float("-inf")
        for s, e in sorted(self.trace_spans):
            if e > end:
                total += e - max(s, end)
                end = e
        return total

    @property
    def cache_label(self) -> str:
        # a partial hit (some nested executable missed) is a miss for
        # the dispatch: something was compiled fresh
        if self.cache_misses:
            return "miss"
        if self.cache_hits:
            return "hit"
        return "uncached"


# -- jax.monitoring attribution -------------------------------------------

_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
_CACHE_MISS_EVENT = "/jax/compilation_cache/cache_misses"
# spans compile-or-load: on a persistent-cache hit it holds the
# retrieval (_CACHE_LOAD_EVENT) and no compile (_Dispatch.compile_s)
_BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
_LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_CACHE_LOAD_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"
# the per-stage seconds an entry's record shows beside compile_wall_s,
# disjoint and in the order they happen inside a tracing dispatch
STAGE_FIELDS = ("trace_s", "lower_s", "cache_load_s", "backend_compile_s")

_monitoring_installed = False
_monitoring_lock = threading.Lock()


def _on_monitoring_event(event: str, **kwargs):
    rec = current_dispatch()
    if rec is None:
        return
    if event == _CACHE_HIT_EVENT:
        rec.cache_hits += 1
    elif event == _CACHE_MISS_EVENT:
        rec.cache_misses += 1


def _on_monitoring_duration(event: str, duration: float, **kwargs):
    rec = current_dispatch()
    if rec is None:
        return
    if event == _BACKEND_COMPILE_EVENT:
        rec.backend_compile_s += float(duration)
    elif event == _LOWER_EVENT:
        rec.lower_s += float(duration)
    elif event == _CACHE_LOAD_EVENT:
        rec.cache_load_s += float(duration)


def _on_monitoring_time_span(event: str, start: float, end: float,
                             **kwargs):
    rec = current_dispatch()
    if rec is not None and event == _TRACE_EVENT:
        rec.trace_spans.append((float(start), float(end)))


def _install_monitoring():
    """Register the process-wide jax.monitoring listeners once.  The
    listeners are no-ops off the instrumented dispatch path (one
    thread-local read per event) and attribute to whatever ledger the
    in-flight dispatch belongs to, so a ``set_ledger`` swap follows."""
    global _monitoring_installed
    with _monitoring_lock:
        if _monitoring_installed:
            return
        try:
            from jax import monitoring as _mon
            _mon.register_event_listener(_on_monitoring_event)
            _mon.register_event_duration_secs_listener(
                _on_monitoring_duration)
            _mon.register_event_time_span_listener(
                _on_monitoring_time_span)
        except Exception:       # noqa: BLE001 — API drift: the ledger
            # still counts traces; the cache column reads "uncached"
            pass
        _monitoring_installed = True


# -- abstract signatures ---------------------------------------------------

def _leaf_sig(leaf) -> List[Any]:
    """One array leaf's abstract signature: ``[dtype, shape]`` (plus a
    weak-type marker — a python scalar retraces against a committed
    array of the same dtype, and the differ must see why)."""
    aval = getattr(leaf, "aval", None)
    src = aval if aval is not None else leaf
    dtype = getattr(src, "dtype", None)
    shape = getattr(src, "shape", None)
    if dtype is None or shape is None:
        # a non-array python value closed over dynamically (jit would
        # have rejected it; keep the differ total anyway)
        return ["py", repr(type(leaf).__name__)]
    sig = [str(dtype), [int(d) for d in shape]]
    if getattr(src, "weak_type", False):
        sig.append("weak")
    return sig


def abstract_signature(args: Sequence[Any],
                       kwargs: Optional[Dict[str, Any]] = None,
                       static_argnums: Sequence[int] = (),
                       static_argnames: Sequence[str] = (),
                       arg_names: Optional[Sequence[str]] = None
                       ) -> Dict[str, Any]:
    """The per-argument abstract signature of one call: each argument
    maps to either ``{"static": repr(value)}`` or
    ``{"leaves": [[dtype, shape], ...]}`` over its pytree.  Computed at
    trace time from tracer avals (or eagerly from concrete arrays) —
    plain JSON-able python, so snapshots serve without jax."""
    import jax
    static = set(int(i) for i in static_argnums)
    names = list(arg_names or ())
    sig: Dict[str, Any] = {}
    for i, a in enumerate(args):
        name = names[i] if i < len(names) else f"arg{i}"
        if i in static:
            sig[name] = {"static": repr(a)}
        else:
            sig[name] = {"leaves": [
                _leaf_sig(leaf)
                for leaf in jax.tree_util.tree_leaves(a)]}
    snames = set(static_argnames)
    for k in sorted(kwargs or {}):
        v = (kwargs or {})[k]
        if k in snames:
            sig[k] = {"static": repr(v)}
        else:
            sig[k] = {"leaves": [
                _leaf_sig(leaf)
                for leaf in jax.tree_util.tree_leaves(v)]}
    return sig


def format_signature(arg_sig: Any) -> str:
    """Compact human form of ONE argument's signature, e.g.
    ``f32[4,8] i32[4]`` or ``static:3`` — what the ring events and
    ``/compilez`` show as before/after."""
    if not isinstance(arg_sig, dict):
        return repr(arg_sig)
    if "static" in arg_sig:
        return f"static:{arg_sig['static']}"
    parts = []
    for leaf in arg_sig.get("leaves", ()):
        dtype = str(leaf[0]) if leaf else "?"
        shape = leaf[1] if len(leaf) > 1 else None
        short = (dtype.replace("float", "f").replace("uint", "u")
                 .replace("int", "i").replace("bool", "pred")
                 .replace("bfloat", "bf"))
        dims = ",".join(str(d) for d in shape) if isinstance(
            shape, (list, tuple)) else "?"
        # the weak marker must survive into the display form: a
        # weak-vs-committed retrace (python scalar vs device array of
        # the same dtype) would otherwise show an identical
        # before/after pair — an un-actionable "nothing changed" diff
        weak = "(weak)" if "weak" in leaf[2:] else ""
        parts.append(f"{short}[{dims}]{weak}")
    return " ".join(parts) if parts else "(empty)"


def signature_fingerprint(entry: str, signature: Dict[str, Any]) -> str:
    """Stable fingerprint of (entry, abstract signature) — the identity
    two traces share iff jit would have shared their executable (same
    entry, same avals, same statics).  The cross-run join key the
    double-run cache gate compares."""
    blob = json.dumps([entry, signature], sort_keys=True,
                      default=repr).encode()
    return hashlib.sha256(blob).hexdigest()[:16]


def diff_signatures(prev: Dict[str, Any], cur: Dict[str, Any]
                    ) -> List[Dict[str, Any]]:
    """The retrace-cause differ: compare two abstract signatures of the
    same entry and name every argument whose signature changed —
    ``[{"arg", "cause", "before", "after"}, ...]`` with cause one of
    ``shape`` / ``dtype`` / ``static_arg`` (``arity`` when an argument
    appeared or vanished).  An **unchanged signature returns []** — no
    retrace cause (the trace was a fresh closure or an explicit
    re-trace, not shape polymorphism)."""
    culprits: List[Dict[str, Any]] = []
    for name in list(prev) + [n for n in cur if n not in prev]:
        a, b = prev.get(name), cur.get(name)
        if a == b:
            continue
        if a is None or b is None:
            cause = "arity"
        elif "static" in (a or {}) or "static" in (b or {}):
            cause = "static_arg"
        else:
            la = a.get("leaves", [])
            lb = b.get("leaves", [])
            if len(la) != len(lb):
                cause = "shape"
            else:
                cause = None
                for xa, xb in zip(la, lb):
                    if xa == xb:
                        continue
                    sa = xa[1] if len(xa) > 1 else None
                    sb = xb[1] if len(xb) > 1 else None
                    if sa != sb:
                        cause = "shape"
                        break
                    cause = "dtype"
                cause = cause or "dtype"
        culprits.append({"arg": name, "cause": cause,
                         "before": format_signature(a),
                         "after": format_signature(b)})
    return culprits


# -- the ledger ------------------------------------------------------------

class CompilationLedger:
    """In-process record of every instrumented jit trace/compile.

    ``registry`` / ``ring`` default to the process singletons resolved
    per use (the ``flightrec.resolve`` rule every producer follows);
    ``max_events_per_entry`` bounds the retained per-entry trace detail
    (counts stay exact forever — flight-ring discipline).
    """

    def __init__(self, registry=None, ring=None,
                 clock: Callable[[], float] = time.perf_counter,
                 max_events_per_entry: int = 64):
        self.registry = registry
        self._ring = ring
        self._clock = clock
        self._t0 = clock()
        self._lock = threading.RLock()
        self._entries: Dict[str, Dict[str, Any]] = {}
        self._max_events = int(max_events_per_entry)
        self._total_traces = 0
        self._total_wall_s = 0.0
        # entry -> (weakref to the instrumented callable, abstract
        # args, kwargs, compiled here) of its last traced signature, and
        # the optimized HLO text of it, read on demand (compiled_text)
        self._lowerable: Dict[str, Tuple[Any, tuple, dict, bool]] = {}
        self._compiled_text: Dict[str, str] = {}

    # -- default resolution (per use) ----------------------------------
    def _reg(self):
        from .metrics import get_registry
        return self.registry if self.registry is not None \
            else get_registry()

    @property
    def ring(self):
        from . import flightrec
        return flightrec.resolve(self._ring)

    # -- recording ------------------------------------------------------
    def _entry_state(self, entry: str) -> Dict[str, Any]:
        st = self._entries.get(entry)
        if st is None:
            st = self._entries[entry] = {
                "traces": 0, "retraces": 0, "compiles": 0,
                "cache": {"hit": 0, "miss": 0, "uncached": 0},
                "causes": {},
                # per-closure last signatures: the retrace diff runs
                # against the SAME closure's history (see record_trace)
                "closures": {},
                "last_signature": None, "last_closure": None,
                "last_fingerprint": None,
                "last_retrace": None,
                "compile_wall_s": 0.0, "trace_s": 0.0, "lower_s": 0.0,
                "cache_load_s": 0.0, "backend_compile_s": 0.0,
                "last_trace_t_s": None,
                "events": deque(maxlen=self._max_events)}
        return st

    def record_trace(self, entry: str, signature: Dict[str, Any],
                     closure_id: Optional[int] = None,
                     dispatch: Optional[_Dispatch] = None
                     ) -> Dict[str, Any]:
        """The jax-free recording primitive: one trace of ``entry`` at
        ``signature``.  Classifies the cause against the entry's
        previous trace, updates counters, and (for signature-change
        causes) appends the ``xla_retrace`` flight event carrying the
        differ's culprit.  Returns the trace event dict."""
        t_s = round(self._clock() - self._t0, 6)
        fp = signature_fingerprint(entry, signature)
        with self._lock:
            st = self._entry_state(entry)
            closures = st["closures"]
            # a RETRACE is a closure re-tracing: the diff must run
            # against THIS closure's own previous signature.  Diffing a
            # fresh closure against another closure's signature is not
            # evidence of shape polymorphism — two differently-shaped
            # engines sharing an entry label (gpt w1/w8 + llama
            # engines built back to back) would otherwise emit
            # storm-class xla_retrace events and false-positive the
            # supervisor, with a "culprit" that never varied within any
            # one closure.
            prev = closures.get(closure_id)
            if not closures and st["last_signature"] is None:
                cause, culprits = "new_entry", []
            elif prev is None:
                cause, culprits = "new_closure", []
            else:
                culprits = diff_signatures(prev, signature)
                if culprits:
                    cause = culprits[0]["cause"]
                    if cause == "arity":
                        cause = "static_arg"
                else:
                    cause = "repeat"
            closures[closure_id] = signature
            # bound the per-closure history: entries whose closures are
            # born per engine instance must not grow without limit in a
            # weeks-long process (counts stay exact forever)
            while len(closures) > 256:
                closures.pop(next(iter(closures)))
            ev: Dict[str, Any] = {
                "entry": entry, "cause": cause, "t_s": t_s,
                "fingerprint": fp,
                "signature": signature}
            if culprits:
                ev["culprits"] = culprits
                ev["culprit"] = culprits[0]["arg"]
            st["traces"] += 1
            st["causes"][cause] = st["causes"].get(cause, 0) + 1
            if cause != "new_entry":
                st["retraces"] += 1
            st["last_signature"] = signature
            st["last_closure"] = closure_id
            st["last_fingerprint"] = fp
            st["last_trace_t_s"] = t_s
            if cause in SIGNATURE_CHANGE_CAUSES:
                st["last_retrace"] = {
                    "cause": cause, "t_s": t_s,
                    "culprit": ev.get("culprit"),
                    "culprits": culprits}
            st["events"].append(ev)
            self._total_traces += 1
        reg = self._reg()
        reg.counter(
            "xla_traces_total",
            help="jit traces of instrumented entries (first compiles "
                 "and retraces alike)").labels(entry=entry).inc()
        reg.counter(
            "xla_retraces_total",
            help="traces by cause: new_entry is the warmup compile, "
                 "shape/dtype/static_arg are signature-change "
                 "retraces, new_closure the per-replica re-jit class"
        ).labels(entry=entry, cause=cause).inc()
        if cause in SIGNATURE_CHANGE_CAUSES:
            top = culprits[0] if culprits else {}
            self.ring.append("xla_retrace", entry=entry, cause=cause,
                             culprit=top.get("arg"),
                             before=top.get("before"),
                             after=top.get("after"))
        if dispatch is not None:
            dispatch.events.append(ev)
        return ev

    def _finalize_dispatch(self, rec: _Dispatch, wall_s: float,
                           lowerable: Optional[tuple] = None):
        """Close the books on one instrumented dispatch that traced:
        the wall duration (trace + lower + compile + first execution —
        the honest 'how long did the cold call cost' number), the
        stages jax itself timed inside it (:data:`STAGE_FIELDS`), the
        persistent-cache attribution, the compile counters, and what
        :meth:`compiled_text` needs to lower the signature again."""
        if not rec.events:
            return
        label = rec.cache_label
        with self._lock:
            st = self._entry_state(rec.entry)
            st["compiles"] += 1
            st["cache"][label] = st["cache"].get(label, 0) + 1
            st["compile_wall_s"] = round(
                st["compile_wall_s"] + wall_s, 6)
            for field, spent in zip(STAGE_FIELDS, (
                    rec.trace_s, rec.lower_s, rec.cache_load_s,
                    rec.compile_s)):
                st[field] = round(st[field] + spent, 6)
            if lowerable is not None:
                self._lowerable[rec.entry] = lowerable
                self._compiled_text.pop(rec.entry, None)
            for ev in rec.events:
                ev["wall_s"] = round(wall_s, 6)
                ev["cache"] = label
            self._total_wall_s += wall_s
        reg = self._reg()
        reg.counter(
            "xla_compiles_total",
            help="compiling dispatches by persistent-cache outcome"
        ).labels(entry=rec.entry, cache=label).inc()
        reg.histogram(
            "xla_compile_seconds",
            buckets=_COMPILE_SECONDS_BUCKETS,
            help="wall duration of dispatches that traced (trace + "
                 "lower + compile + first run)").observe(wall_s)

    # -- the jit wrapper -------------------------------------------------
    def jit(self, fun, entry: str, **kwargs):
        """:func:`instrumented_jit` bound to THIS ledger."""
        return instrumented_jit(fun, entry, ledger=self, **kwargs)

    def compiled_text(self, entry: str) -> Optional[str]:
        """Optimized HLO text of ``entry``'s last traced signature (what
        :func:`~apex_tpu.observability.phases.instruction_phases` reads),
        computed on first demand and kept.  Where the tracing dispatch
        compiled the program itself it is the text of that executable,
        from jax's in-memory caches; where it loaded the executable from
        the persistent cache, the program is compiled once more past the
        cache (:func:`_own_text` says why).  None when the entry never
        traced through :func:`instrumented_jit` or its callable is
        gone."""
        with self._lock:
            text = self._compiled_text.get(entry)
        if text is not None:
            return text
        with _text_lock:            # one compile at a time, one an entry
            with self._lock:
                text = self._compiled_text.get(entry)
                lowerable = self._lowerable.get(entry)
            if text is None and lowerable is not None:
                text = _own_text(*lowerable)
                with self._lock:
                    if (text is not None
                            and self._lowerable.get(entry) is lowerable):
                        self._compiled_text[entry] = text
        return text

    # -- contract / snapshot surface -------------------------------------
    def total_traces(self) -> int:
        """Monotonic count of every recorded trace — the zero-retrace
        contracts are delta checks over this."""
        with self._lock:
            return self._total_traces

    def compile_wall_s(self) -> float:
        """Total wall seconds spent in tracing dispatches."""
        with self._lock:
            return self._total_wall_s

    def counts(self) -> Dict[str, int]:
        """{entry: traces} snapshot."""
        with self._lock:
            return {e: st["traces"] for e, st in self._entries.items()}

    def entries(self) -> Tuple[str, ...]:
        with self._lock:
            return tuple(self._entries)

    def snapshot(self) -> Dict[str, Any]:
        """Plain-JSON ledger view — what ``/compilez`` serves.  Each
        entry carries its trace/retrace/compile counts, per-cause and
        per-cache tallies, compile seconds, the last trace's signature
        fingerprint, the last *signature-change* retrace (cause +
        the differ's culprit argument), and the bounded recent-trace
        detail."""
        with self._lock:
            entries = {}
            hits = misses = uncached = 0
            retraces = compiles = 0
            for name, st in self._entries.items():
                # events are COPIED per dict: _finalize_dispatch adds
                # wall_s/cache to the live event objects after a slow
                # compile, and a /compilez scrape serializing a shared
                # dict mid-mutation would 500 on "dictionary changed
                # size during iteration"
                entries[name] = {
                    k: ([dict(e) for e in v] if isinstance(v, deque)
                        else dict(v) if isinstance(v, dict) else v)
                    for k, v in st.items() if k != "closures"}
                hits += st["cache"].get("hit", 0)
                misses += st["cache"].get("miss", 0)
                uncached += st["cache"].get("uncached", 0)
                retraces += st["retraces"]
                compiles += st["compiles"]
            return {
                "kind": "compilation",
                "entries": entries,
                "totals": {"traces": self._total_traces,
                           "retraces": retraces,
                           "compiles": compiles,
                           "cache_hits": hits,
                           "cache_misses": misses,
                           "cache_uncached": uncached,
                           "compile_wall_s": round(self._total_wall_s,
                                                   6)},
                "uptime_s": round(self._clock() - self._t0, 3)}

    def dump(self, path: str) -> str:
        """Write the snapshot as one JSON document (atomic replace, the
        flight-ring dump discipline) — what the double-run CI gate
        reads to assert run 2's serving compiles were cache-HIT."""
        snap = self.snapshot()
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(snap, f, indent=2, default=repr)
            f.write("\n")
        os.replace(tmp, path)
        return path


# -- instrumentation --------------------------------------------------------

def _lowerable(fn, args, kwargs, static_argnums, static_argnames,
               compiled_here):
    """What ``compiled_text`` needs to lower a traced call again: the
    callable, weakly held, its arguments with every array leaf (jax or
    numpy) replaced by its shape, dtype and sharding (all still readable
    on a donated array), and whether the dispatch compiled the program
    itself; static arguments and python scalars stay as they are.  None
    for a call made under an outer trace: its arguments are tracers, and
    the outer program is what gets compiled."""
    import jax
    import numpy as np

    if any(isinstance(x, jax.core.Tracer)
           for x in jax.tree_util.tree_leaves((args, kwargs))):
        return None

    def leaf(x):
        if isinstance(x, jax.Array):
            return jax.ShapeDtypeStruct(x.shape, x.dtype,
                                        sharding=x.sharding,
                                        weak_type=x.weak_type)
        if isinstance(x, np.ndarray):
            return jax.ShapeDtypeStruct(x.shape, x.dtype)
        return x

    abstract = lambda tree: jax.tree_util.tree_map(leaf, tree)
    return (weakref.ref(fn),
            tuple(a if i in static_argnums else abstract(a)
                  for i, a in enumerate(args)),
            {k: v if k in static_argnames else abstract(v)
             for k, v in kwargs.items()},
            compiled_here)


# compiled_text compiles one entry at a time, in whatever ledger
_text_lock = threading.Lock()


def _own_text(ref, args, kwargs, compiled_here) -> Optional[str]:
    """Optimized HLO text of the program ``_lowerable`` described, with
    THIS program's ``op_name``s.

    The persistent cache's key leaves metadata out, so an executable a
    dispatch loaded from it may have been compiled from another
    checkout's module and carry ITS ``op_name``s (none of this program's
    scopes); instruction names are the same either way.  Whether a
    loaded executable is foreign cannot be told from its text: the
    compiler drops whole scopes (``amp.grad_norm`` in the BERT step), so
    a scope that is missing proves nothing.  So only a dispatch that
    compiled here vouches for its executable, and jax's in-memory caches
    then hand back that very module and executable.  Otherwise this
    program's own module is compiled past the cache: a key that holds the
    metadata finds nothing to read, and nothing is written (a second copy
    of a large step would push the entries the next start needs out of a
    size-capped cache).  Either way a process pays one backend compile
    of the step, in its dispatch or here, and not both."""
    fn = ref()
    if fn is None:
        return None
    from jax._src import config as jax_config     # thread-local settings
    _inflight.quiet = True       # this lowering is not a trace to count
    try:
        if compiled_here:
            return fn.lower(*args, **kwargs).compile().as_text()
        with jax_config.compilation_cache_include_metadata_in_key(True), \
                jax_config.persistent_cache_min_compile_time_secs(1e30):
            return fn.lower_afresh(*args, **kwargs).compile().as_text()
    finally:
        _inflight.quiet = False


def instrumented_jit(fun, entry: str, *, ledger=None,
                     arg_names: Optional[Sequence[str]] = None,
                     static_argnums: Sequence[int] = (),
                     static_argnames: Sequence[str] = (),
                     **jit_kwargs):
    """``jax.jit`` with the compilation ledger watching: returns a
    callable that dispatches the jitted function and records every
    TRACE (entry label, abstract arg signature, wall duration,
    cache attribution) into ``ledger`` — the process ledger when None,
    resolved per dispatch so a ``set_ledger`` swap follows.

    ``arg_names`` labels the positional arguments for the retrace
    differ (falls back to the function's own signature, then
    ``arg0..``).  ``.lower`` / the underlying jit object stay reachable
    (``wrapped.lower`` / ``wrapped.jitted``) for the analysis entry
    points; an explicit ``.lower()`` or ``make_jaxpr`` pass records an
    un-timed trace (cause ``repeat`` once warm), never a compile.
    """
    import functools
    import inspect
    import jax

    _install_monitoring()
    cid = next(_closure_ids)
    sargs = tuple(int(i) for i in static_argnums)
    snames = tuple(static_argnames)
    names: Sequence[str]
    if arg_names is not None:
        names = tuple(arg_names)
    else:
        try:
            names = tuple(inspect.signature(fun).parameters)
        except (TypeError, ValueError):
            names = ()

    def _resolve(led):
        return led if led is not None else get_ledger()

    def _traced(*args, **kwargs):
        if getattr(_inflight, "quiet", False):      # compiled_text
            return fun(*args, **kwargs)
        rec = current_dispatch()
        led = rec.ledger if rec is not None else _resolve(ledger)
        sig = abstract_signature(args, kwargs, static_argnums=sargs,
                                 static_argnames=snames,
                                 arg_names=names)
        led.record_trace(entry, sig, closure_id=cid, dispatch=rec)
        return fun(*args, **kwargs)

    # keep the user fn's name on the traced callable: XLA module names
    # and profiler annotations should read `_step_k`, not `_traced`
    _traced.__name__ = getattr(fun, "__name__", entry)
    _traced.__qualname__ = getattr(fun, "__qualname__",
                                   _traced.__name__)
    jitted = jax.jit(_traced, static_argnums=sargs or None,
                     static_argnames=snames or None, **jit_kwargs)

    @functools.wraps(fun)
    def wrapped(*args, **kwargs):
        led = _resolve(ledger)
        rec = _Dispatch(led, entry)
        st = _stack()
        st.append(rec)
        t0 = led._clock()
        try:
            return jitted(*args, **kwargs)
        finally:
            dt = led._clock() - t0
            # pop by identity: an exception inside a nested
            # instrumented dispatch must not strand this frame
            try:
                st.remove(rec)
            except ValueError:
                pass
            if rec.events:      # this dispatch traced: never per dispatch
                led._finalize_dispatch(rec, dt, _lowerable(
                    wrapped, args, kwargs, sargs, snames,
                    rec.compiled_here))

    def lower_afresh(*args, **kwargs):
        """Trace and lower again through a new jit of a new function
        object: past every in-memory cache jax keeps per function."""
        def again(*a, **k):
            return _traced(*a, **k)
        again.__name__, again.__qualname__ = (_traced.__name__,
                                              _traced.__qualname__)
        return jax.jit(again, static_argnums=sargs or None,
                       static_argnames=snames or None,
                       **jit_kwargs).lower(*args, **kwargs)

    wrapped.lower = jitted.lower
    wrapped.lower_afresh = lower_afresh
    wrapped.jitted = jitted
    wrapped.entry = entry
    wrapped.closure_id = cid
    if hasattr(jitted, "clear_cache"):
        wrapped.clear_cache = jitted.clear_cache
    return wrapped


# -- process singleton ------------------------------------------------------

_process_ledger = CompilationLedger()


def get_ledger() -> CompilationLedger:
    """The process-wide default ledger (every ``instrumented_jit``
    without an explicit ledger records here; ``/compilez`` serves it)."""
    return _process_ledger


def set_ledger(ledger: CompilationLedger) -> CompilationLedger:
    global _process_ledger
    prev, _process_ledger = _process_ledger, ledger
    return prev
