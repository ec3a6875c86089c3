"""Device-time truth: parse the Chrome trace ``jax.profiler`` already
writes and attribute a step's DEVICE time — measured, not inferred.

Host wall-clock differencing (a full step minus a twin without its
collectives) is the indirect methodology Apex's README warns about for
comm/compute overlap claims.  FlexLink (arXiv:2510.15882) and the
weight-update-sharding paper (arXiv:2004.13336) both evaluate with
per-kernel device timelines; this module is the in-tree equivalent for
the library's own ``/profilez`` and ``utils.profiler`` captures (the
benchmark's phase reader is ``benchmark/lib/trace.py``): a
**stdlib-only** parser (gzip +
json; jax is imported lazily and only by the capture helpers) for the
``*.trace.json.gz`` that ``jax.profiler.start_trace`` drops under its
logdir, producing per-step device-time attribution — total device busy
time, per-kernel top-k, compute vs collective vs gap split, and a
*measured* ``overlap_fraction`` from actual kernel-interval overlap.

Trace-format notes (pinned empirically by tests/test_timeline.py on
this container's jax): the capture lands at
``<logdir>/plugins/profile/<session>/<host>.trace.json.gz`` — gzipped
Chrome-trace JSON ``{"traceEvents": [...]}``.  Kernel executions are
``"ph": "X"`` complete events whose ``args`` carry ``hlo_op`` /
``hlo_module``; on XLA:CPU they run on ``tf_XLATfrtCpuClient`` /
``tf_XLAEigen`` threads (so the 8-virtual-device conftest mesh
exercises the whole pipeline in tier-1), on TPU on the
``/device:TPU:*`` process rows — either way the ``hlo_op`` arg is what
separates device kernels from the python tracer's thousands of host
frames.  Timestamps/durations are microseconds.

Two gotchas this module exists to encode:

- **Collectives are classified by kernel name** (``all-reduce`` /
  ``all-gather`` / ``reduce-scatter`` / ``collective-permute`` /
  ``all-to-all`` — the names XLA gives psum/ppermute&co lowerings);
  the pattern list is public so the lint/tests can pin it.
- **Session dirs collide**: ``start_trace`` names its session
  subdirectory by wall-clock *second*, so two captures into one logdir
  within a second silently overwrite each other — which is why
  ``utils.profiler`` now allocates a unique per-capture directory and
  :func:`find_trace_file` insists on exactly resolving the newest
  session under whatever directory it is handed.
"""

from __future__ import annotations

import glob
import gzip
import json
import os
import re
from typing import Any, Callable, Dict, Iterable, List, Optional, Tuple

__all__ = ["COLLECTIVE_PATTERNS", "PROFILE_FIELDS", "classify_kernel",
           "find_trace_file", "load_trace", "device_events",
           "merge_intervals", "overlap_us", "attribute_timeline",
           "analyze_capture", "profile_record", "capture",
           "make_profiler"]

# substrings (lowercase) of HLO kernel names that are cross-device
# communication: XLA lowers psum -> all-reduce, all_gather ->
# all-gather, psum_scatter -> reduce-scatter, ppermute ->
# collective-permute, all_to_all -> all-to-all.  Matched against the
# event name AND its hlo_op so fusion-wrapped collectives
# ("all-reduce-start.1") still classify.
COLLECTIVE_PATTERNS = ("all-reduce", "allreduce", "all-gather",
                       "allgather", "reduce-scatter", "reducescatter",
                       "collective-permute", "collectivepermute",
                       "all-to-all", "alltoall", "collective-broadcast",
                       "psum", "ppermute")

# the timing fields every ``kind: profile`` record must carry
# (exporters.validate_profile_record keys its checks off these; they
# are all in MILLISECONDS except the fraction)
PROFILE_FIELDS = ("span_ms", "device_busy_ms", "compute_ms",
                  "collective_ms", "gap_ms", "overlap_ms",
                  "measured_overlap_fraction")

_TRACE_SUFFIXES = (".trace.json.gz", ".trace.json")


def classify_kernel(name: str) -> str:
    """``"collective"`` or ``"compute"`` for one HLO kernel name."""
    low = str(name).lower()
    for pat in COLLECTIVE_PATTERNS:
        if pat in low:
            return "collective"
    return "compute"


def find_trace_file(logdir: str) -> str:
    """Resolve the trace file of the NEWEST capture session under
    ``logdir`` (a direct ``*.trace.json[.gz]`` path passes through).
    Searches ``logdir`` itself and the ``plugins/profile/<session>/``
    layout ``jax.profiler`` writes; raises ``FileNotFoundError`` when
    no trace file exists — the caller should be handing a unique
    per-capture directory (``utils.profiler.profile()`` yields one), so
    "newest" is normally "the only one"."""
    if os.path.isfile(logdir):
        return logdir
    candidates: List[str] = []
    for root in (logdir, os.path.join(logdir, "plugins", "profile")):
        for path in glob.glob(os.path.join(glob.escape(root), "*")) \
                + glob.glob(os.path.join(glob.escape(root), "*", "*")):
            if os.path.isfile(path) and path.endswith(_TRACE_SUFFIXES):
                candidates.append(path)
    if not candidates:
        raise FileNotFoundError(
            f"no *.trace.json[.gz] under {logdir!r} — was the capture "
            f"stopped (profiler.stop_profile) before parsing?")
    # newest session wins; mtime first, path as the deterministic tie
    return max(candidates, key=lambda p: (os.path.getmtime(p), p))


def load_trace(path: str) -> Dict[str, Any]:
    """Load one Chrome-trace JSON document (gzipped or plain)."""
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rt", encoding="utf-8") as f:
        doc = json.load(f)
    if not isinstance(doc, dict) or not isinstance(
            doc.get("traceEvents"), list):
        raise ValueError(f"{path}: not a Chrome trace (no traceEvents "
                         f"list)")
    return doc


def device_events(doc: Dict[str, Any],
                  modules: Optional[Iterable[str]] = None
                  ) -> List[Dict[str, Any]]:
    """Extract device kernel executions from one trace document:
    complete (``ph: X``) events whose args carry ``hlo_op`` — the
    python tracer's host frames and the thread-metadata rows never do.
    ``modules`` optionally restricts to events whose ``hlo_module``
    contains any of the given substrings (e.g. ``("jit_step",)`` to
    attribute ONE jitted program and drop the blocked-fetch plumbing
    around it)."""
    mods = tuple(modules) if modules is not None else None
    out: List[Dict[str, Any]] = []
    for e in doc.get("traceEvents", []):
        if not isinstance(e, dict) or e.get("ph") != "X":
            continue
        args = e.get("args")
        if not isinstance(args, dict):
            continue
        op = args.get("hlo_op")
        if not isinstance(op, str) or not op:
            continue
        module = args.get("hlo_module")
        if mods is not None and not (
                isinstance(module, str)
                and any(m in module for m in mods)):
            continue
        try:
            ts = float(e["ts"])
            dur = float(e.get("dur", 0.0))
        except (KeyError, TypeError, ValueError):
            continue
        name = e.get("name") if isinstance(e.get("name"), str) else op
        kind = classify_kernel(name)
        if kind == "compute":
            kind = classify_kernel(op)
        out.append({"name": name, "op": op, "module": module,
                    "ts": ts, "dur": max(dur, 0.0),
                    "lane": (e.get("pid"), e.get("tid")),
                    "kind": kind})
    return out


def merge_intervals(intervals: Iterable[Tuple[float, float]]
                    ) -> List[Tuple[float, float]]:
    """Union of half-open intervals as a sorted disjoint list."""
    ivs = sorted((s, e) for s, e in intervals if e > s)
    out: List[Tuple[float, float]] = []
    for s, e in ivs:
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def overlap_us(a: List[Tuple[float, float]],
               b: List[Tuple[float, float]]) -> float:
    """Total overlap between two MERGED interval lists (two-pointer
    sweep)."""
    i = j = 0
    total = 0.0
    while i < len(a) and j < len(b):
        lo = max(a[i][0], b[j][0])
        hi = min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] <= b[j][1]:
            i += 1
        else:
            j += 1
    return total


_SUFFIX_RE = re.compile(r"\.\d+$")


def _kernel_base(name: str) -> str:
    """Aggregate key for top-k: strip XLA's ``.N`` instance suffix so
    ``dot.1`` / ``dot.3`` report as one ``dot`` line."""
    return _SUFFIX_RE.sub("", name)


def attribute_timeline(events: List[Dict[str, Any]], top_k: int = 10
                       ) -> Dict[str, Any]:
    """Per-capture device-time attribution over extracted events.

    All times are the UNION over lanes (a kernel running on 8 virtual
    devices at once counts its wall extent once — the schedule view):

    - ``span_ms``: first kernel start to last kernel end;
    - ``device_busy_ms``: union of all kernel intervals;
    - ``compute_ms`` / ``collective_ms``: per-class unions;
    - ``gap_ms``: ``span - busy`` — scheduling stall / host time
      between kernels;
    - ``overlap_ms``: time covered by BOTH a compute and a collective
      interval — the measured comm/compute overlap;
    - ``measured_overlap_fraction``: ``overlap / collective`` (0.0
      with no collectives).
    """
    comp = merge_intervals((e["ts"], e["ts"] + e["dur"])
                           for e in events if e["kind"] == "compute")
    coll = merge_intervals((e["ts"], e["ts"] + e["dur"])
                           for e in events if e["kind"] == "collective")
    busy = merge_intervals([(s, e) for s, e in comp] +
                           [(s, e) for s, e in coll])
    busy_us = sum(e - s for s, e in busy)
    comp_us = sum(e - s for s, e in comp)
    coll_us = sum(e - s for s, e in coll)
    if busy:
        span_us = (max(e for _, e in busy) - min(s for s, _ in busy))
    else:
        span_us = 0.0
    ovl_us = overlap_us(comp, coll)
    frac = (ovl_us / coll_us) if coll_us > 0 else 0.0

    agg: Dict[Tuple[str, str], Dict[str, Any]] = {}
    for e in events:
        key = (_kernel_base(e["name"]), e["kind"])
        a = agg.setdefault(key, {"name": key[0], "kind": key[1],
                                 "count": 0, "total_us": 0.0})
        a["count"] += 1
        a["total_us"] += e["dur"]
    top = sorted(agg.values(), key=lambda a: -a["total_us"])[:top_k]

    def ms(us):
        return round(us / 1e3, 4)

    return {"span_ms": ms(span_us),
            "device_busy_ms": ms(busy_us),
            "compute_ms": ms(comp_us),
            "collective_ms": ms(coll_us),
            "gap_ms": ms(max(span_us - busy_us, 0.0)),
            "overlap_ms": ms(ovl_us),
            "measured_overlap_fraction": round(min(max(frac, 0.0), 1.0),
                                               4),
            "kernel_count": len(events),
            "lane_count": len({e["lane"] for e in events}),
            "top_kernels": [{"name": a["name"], "kind": a["kind"],
                             "count": a["count"],
                             "total_ms": ms(a["total_us"])}
                            for a in top]}


def analyze_capture(logdir: str,
                    modules: Optional[Iterable[str]] = None,
                    steps: int = 1, top_k: int = 10) -> Dict[str, Any]:
    """Find + parse the capture under ``logdir`` and attribute it.
    ``steps`` divides the time fields (a capture of N identical steps
    reports per-step ms; the fraction and counts stay whole-capture),
    recorded on the result as ``steps``."""
    if steps < 1:
        raise ValueError(f"steps must be >= 1, got {steps}")
    path = find_trace_file(logdir)
    att = attribute_timeline(device_events(load_trace(path),
                                           modules=modules),
                             top_k=top_k)
    if steps > 1:
        for k in ("span_ms", "device_busy_ms", "compute_ms",
                  "collective_ms", "gap_ms", "overlap_ms"):
            att[k] = round(att[k] / steps, 4)
        for a in att["top_kernels"]:
            a["total_ms"] = round(a["total_ms"] / steps, 4)
    att["steps"] = steps
    att["trace_path"] = path
    return att


def profile_record(attribution: Dict[str, Any], metric: str,
                   **extra) -> Dict[str, Any]:
    """Shape one attribution as a ``kind: profile`` record body (the
    caller routes it through ``JsonlExporter.enrich`` for the
    envelope); ``extra`` lands verbatim (e.g. ``kv_waste_bytes`` /
    ``kv_utilization`` on serving profiles)."""
    return {"kind": "profile", "metric": metric, **attribution, **extra}


# -- capture helpers (the only jax-touching surface, imported lazily) ----

def _blocked_fetch(out) -> None:
    # one D2H fetch of an output leaf: it cannot complete before the
    # dispatched program finishes, so every kernel the window
    # dispatched lands INSIDE the window
    import jax
    import jax.numpy as jnp
    leaves = jax.tree_util.tree_leaves(out)
    if leaves:
        float(jnp.sum(leaves[0]).astype(jnp.float32))


def capture(fn: Callable, *args, iters: int = 1,
            logdir: Optional[str] = None,
            modules: Optional[Iterable[str]] = None,
            top_k: int = 10) -> Dict[str, Any]:
    """Run ``fn(*args)`` ``iters`` times inside a fresh profiler window
    (unique per-capture directory via ``utils.profiler.profile``) with
    a blocked fetch before the window closes, then parse and return the
    per-step attribution.  The caller should have warmed/compiled
    ``fn`` first — a cold call captures the compile, not the step."""
    if iters < 1:
        raise ValueError(f"iters must be >= 1, got {iters}")
    from ..utils import profiler
    out = None
    with profiler.profile(*(() if logdir is None else (logdir,))) as cap:
        for _ in range(iters):
            out = fn(*args)
        _blocked_fetch(out)
    return analyze_capture(cap, modules=modules, steps=iters,
                           top_k=top_k)


def make_profiler(subject: str = "live_process",
                  default_duration_ms: float = 250.0,
                  max_duration_ms: float = 2000.0,
                  logdir: Optional[str] = None,
                  top_k: int = 10,
                  cleanup: bool = True) -> Callable:
    """Build the on-demand capture hook ``/profilez`` calls: a
    one-optional-arg callable that opens a BOUNDED profiler window on
    the live process (whatever the serving/training loop dispatches
    during it is what gets attributed), parses it, and returns the
    ``kind: profile`` record body.  Raises
    ``server.ProfileInFlight`` when a trace window is already open
    (ours or a foreign ``start_trace``), which the endpoint maps to
    HTTP 409.  ``cleanup=True`` (the default here, unlike test
    captures whose dirs are the artifact) deletes the capture
    directory after parsing — a monitor scraping ``/profilez``
    periodically must not grow /tmp without bound."""
    if max_duration_ms <= 0 or default_duration_ms <= 0:
        raise ValueError("durations must be > 0")

    def _capture(duration_ms: Optional[float] = None) -> Dict[str, Any]:
        import shutil
        import time as _time

        from ..utils import profiler
        from .server import ProfileInFlight
        if profiler.profiling_active():
            raise ProfileInFlight(
                "a profiler trace window is already open in this "
                "process")
        want = float(duration_ms) if duration_ms is not None \
            else float(default_duration_ms)
        if want != want:                   # NaN: the clamp would pass it
            raise ValueError("duration_ms must be a finite number")
        bounded = min(max(want, 1.0), float(max_duration_ms))
        try:
            with profiler.profile(
                    *(() if logdir is None else (logdir,))) as cap:
                _time.sleep(bounded / 1e3)
        except RuntimeError as e:
            # a foreign trace raced us between the check and the start
            raise ProfileInFlight(str(e)) from e
        if profiler.profiling_active():
            # an in-library window opened between the check and our
            # profile(): we JOINED it (refcount semantics), our stop
            # was a no-op, and no trace file exists yet — that is an
            # in-flight capture, not a parse error.  ``cap`` is the
            # OUTER window's directory here: never delete it.
            raise ProfileInFlight(
                "the capture window joined another profile() in "
                "flight; retry once it closes")
        try:
            att = analyze_capture(cap, top_k=top_k)
        except FileNotFoundError as e:
            # the window was ours and closed, yet no trace file —
            # treat as a racing capture; the dir holds nothing worth
            # keeping either way
            if cleanup:
                shutil.rmtree(cap, ignore_errors=True)
            raise ProfileInFlight(str(e)) from e
        except Exception:
            # malformed trace & co: don't leak the capture dir on the
            # way to the 500
            if cleanup:
                shutil.rmtree(cap, ignore_errors=True)
            raise
        if cleanup:
            att.pop("trace_path", None)    # about to dangle
            shutil.rmtree(cap, ignore_errors=True)
        return profile_record(att, metric=subject,
                              duration_ms=round(bounded, 3))

    return _capture
