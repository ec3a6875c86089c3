"""Reduction of a JAX profiler trace to the numbers the readers report.

A trace is loaded from the profiler's ``.xplane.pb`` (through
``jax.profiler.ProfileData``, nothing but JAX) into a plain structure, which
can also be loaded from a trimmed ``.json.gz`` (``json.dump`` of the structure
through ``gzip``: the fixture the tests check this file against was recorded
on the chip that way):

    {"planes": [{"name": str, "lines": [{"name": str,
                 "events": [[name, start_ns, duration_ns, {stat: value}], ...]}]}]}

What a v5e trace holds (jax 0.9.0, libtpu 0.0.34; see PERF.md section 3):
one plane ``/device:TPU:<n>`` per chip with the lines ``Steps``, ``XLA
Modules`` (one event per executed program), ``XLA Ops`` (one event per
executed HLO instruction, named by the instruction's whole text, ``%name =
shape opkind(...)``; nested: a ``while`` or ``conditional`` spans its body's
events; a Mosaic kernel is a ``custom-call`` whose instruction name is the
kernel's, ``%flash_fwd.3``) and ``Async XLA Ops`` (one span from each
``-start`` to its ``-done``); and a ``/host:CPU`` plane whose ``python`` line
holds the ``jax.profiler.TraceAnnotation`` spans the harness puts around its
calls.  Host and device events share one clock.  Events carry no stats that
this reduction needs; on loading, each device event's text is parsed into
``{"instr": name without its number, "kind": opkind}`` and cut short.
"""

from __future__ import annotations

import glob
import gzip
import json
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
INSTR = re.compile(r"^%(\S+) = .*? ([a-z][a-z\-]*)\(")
NAME_CHARS = 160
# HLO instructions that only wrap other instructions' time
CONTAINERS = ("while", "conditional", "call")
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute")

Interval = Tuple[float, float]


def find_xplane(logdir: str) -> str:
    found = sorted(glob.glob(os.path.join(logdir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    return found[-1]


def load_xplane(path: str, keep_line=None) -> dict:
    """``keep_line(plane_name, line_name) -> bool`` trims while loading."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    planes = []
    for plane in data.planes:
        lines = []
        for line in plane.lines:
            if keep_line is not None and not keep_line(plane.name, line.name):
                continue
            on_device = bool(DEVICE_PLANE.match(plane.name))
            events = [[ev.name[:NAME_CHARS], float(ev.start_ns), float(ev.duration_ns),
                       parse_instr(ev.name) if on_device else {}] for ev in line.events]
            lines.append({"name": line.name, "events": events})
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def parse_instr(text: str) -> dict:
    m = INSTR.match(text)
    if not m:
        return {}
    return {"instr": re.sub(r"[.\d]+$", "", m.group(1)), "kind": m.group(2)}


def default_keep(plane_name: str, line_name: str) -> bool:
    """Device op lines, and the host threads (annotations are filtered later)."""
    if DEVICE_PLANE.match(plane_name):
        return line_name in (OPS_LINE, ASYNC_LINE, "XLA Modules")
    return plane_name == "/host:CPU"


def load_trimmed(path: str) -> dict:
    with gzip.open(path, "rt") as f:
        return json.load(f)


# -- intervals ---------------------------------------------------------------

def merge(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def total(intervals: Sequence[Interval]) -> float:
    return sum(e - s for s, e in intervals)


def subtract(a: Sequence[Interval], b: Sequence[Interval]) -> List[Interval]:
    """The parts of merged ``a`` that merged ``b`` does not cover."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def clip(intervals: Iterable[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


# -- views of a trace --------------------------------------------------------

def device_ops(trace: dict) -> Dict[int, List[list]]:
    """chip -> its ``XLA Ops`` events."""
    out = {}
    for plane in trace["planes"]:
        m = DEVICE_PLANE.match(plane["name"])
        if not m:
            continue
        for line in plane["lines"]:
            if line["name"] == OPS_LINE and line["events"]:
                out[int(m.group(1))] = line["events"]
    return out


def is_container(ev: list) -> bool:
    return ev[3].get("kind") in CONTAINERS


def is_collective(ev: list) -> bool:
    return str(ev[3].get("kind", "")).startswith(COLLECTIVES)


def leaf_ops(events: Sequence[list]) -> List[list]:
    """Events that are real work, not a loop or branch around other events."""
    return [e for e in events if not is_container(e)]


def async_spans(trace: dict, chip: int) -> List[list]:
    for plane in trace["planes"]:
        m = DEVICE_PLANE.match(plane["name"])
        if m and int(m.group(1)) == chip:
            for line in plane["lines"]:
                if line["name"] == ASYNC_LINE:
                    return line["events"]
    return []


def host_spans(trace: dict, names: Optional[Sequence[str]] = None) -> List[list]:
    """The harness's ``TraceAnnotation`` spans on the host plane."""
    out = []
    for plane in trace["planes"]:
        if plane["name"] != "/host:CPU":
            continue
        for line in plane["lines"]:
            for ev in line["events"]:
                if names is None or ev[0] in names:
                    out.append(ev)
    return sorted(out, key=lambda e: e[1])


def busy_intervals(events: Sequence[list], lo: float, hi: float) -> List[Interval]:
    """The parts of [lo, hi) in which some operation ran on the device.  Leaf
    operations only: a ``while`` or ``conditional`` spans its whole body, gaps
    between the body's operations included, and would hide them."""
    return merge(clip(((e[1], e[1] + e[2]) for e in leaf_ops(events)), lo, hi))


def busy(events: Sequence[list], lo: float, hi: float) -> float:
    """Nanoseconds of [lo, hi) in which some operation ran on the device."""
    return total(busy_intervals(events, lo, hi))


def kernel_seconds(events: Sequence[list], pattern: str, lo: float, hi: float
                   ) -> Tuple[float, int]:
    """Device seconds and calls of the leaf events inside [lo, hi) whose
    instruction name matches ``pattern`` (a kernel's name, ``flash_fwd``)."""
    rx = re.compile(pattern)
    ns, calls = 0.0, 0
    for e in leaf_ops(events):
        if e[1] >= lo and e[1] + e[2] <= hi and rx.fullmatch(e[3].get("instr", "")):
            ns += e[2]
            calls += 1
    return ns / 1e9, calls


def exposed_collective_seconds(events: Sequence[list], async_events: Sequence[list],
                               lo: float, hi: float) -> float:
    """Time in collectives (blocking ones on the op line, and the start-to-done
    span of asynchronous ones) during which no other operation runs on that
    device."""
    leaves = leaf_ops(events)
    coll = [(e[1], e[1] + e[2]) for e in leaves if is_collective(e)]
    coll += [(e[1], e[1] + e[2]) for e in async_events if is_collective(e)]
    other = [(e[1], e[1] + e[2]) for e in leaves if not is_collective(e)]
    return total(subtract(merge(clip(coll, lo, hi)), merge(clip(other, lo, hi)))) / 1e9


def top_ops(events: Sequence[list], lo: float, hi: float, n: int = 10) -> List[list]:
    sums: Dict[str, float] = {}
    for e in leaf_ops(events):
        if e[1] >= lo and e[1] + e[2] <= hi:
            key = op_label(e)
            sums[key] = sums.get(key, 0.0) + e[2] / 1e9
    return [[k, v] for k, v in sorted(sums.items(), key=lambda kv: -kv[1])[:n]]


def op_label(ev: list) -> str:
    """The instruction's name without its number (a Mosaic kernel's own name,
    or what XLA called the fusion), with the op kind where that says more."""
    instr, kind = ev[3].get("instr"), ev[3].get("kind")
    if not instr:
        return ev[0][:60]
    return instr if kind in (None, instr, "custom-call", "fusion") else f"{instr}:{kind}"


def idle_gaps(events: Sequence[list], spans: Sequence[list], lo: float, hi: float, n: int = 10
              ) -> List[list]:
    """Idle device time inside [lo, hi) by what the host was doing: each gap
    is charged to the innermost harness span that covers its middle."""
    gaps = subtract([(lo, hi)], busy_intervals(events, lo, hi))
    sums: Dict[str, float] = {}
    for s, e in gaps:
        mid, best = (s + e) / 2, None
        for sp in spans:
            if sp[1] <= mid < sp[1] + sp[2] and (best is None or sp[2] < best[2]):
                best = sp
        key = best[0] if best else "outside_harness_spans"
        sums[key] = sums.get(key, 0.0) + (e - s) / 1e9
    return [[k, v] for k, v in sorted(sums.items(), key=lambda kv: -kv[1])[:n]]
