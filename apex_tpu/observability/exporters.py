"""Exporters: schema-versioned JSONL, Prometheus text exposition.

The JSONL exporter is the machine-readable telemetry trail: every
emitted record carries ``schema_version``, the capture host, and a
first-class boolean ``stale`` field.  Each record ``kind`` the library
produces has a validator here; :func:`validate_telemetry_record`
dispatches on ``kind`` and rejects a record without a known one, and
``tests/ci/check_telemetry_schema.py`` runs it over a stream.

Chrome-trace export lives on :class:`tracing.SpanRecorder`; this module
adds the registry-wide surfaces: Prometheus text exposition for
scrape-style consumers and a registry→JSONL dump.
"""

from __future__ import annotations

import json
import numbers
import os
import platform
import re
import socket
import sys
from typing import Any, Dict, IO, Iterable, List, Optional

from .metrics import Counter, Gauge, Histogram, MetricsRegistry

__all__ = ["SCHEMA_VERSION", "TENANT_COUNTS", "CLASS_COUNTS",
           "host_info", "JsonlExporter",
           "prometheus_text", "parse_prometheus_text",
           "validate_prometheus_text", "validate_lint_record",
           "validate_fleet_record", "validate_trace_record",
           "validate_memory_record", "validate_numerics_record",
           "validate_run_record", "validate_recovery_record",
           "validate_sharding_record",
           "validate_telemetry_record", "validate_telemetry_jsonl"]

# The one schema every record is judged against.  A new OPTIONAL field
# does not bump it; it changes only when an existing field changes
# meaning, and a record that declares another version is refused
# (``_check_envelope``).
SCHEMA_VERSION = 15

_host_info_cache: Optional[Dict[str, Any]] = None


def host_info() -> Dict[str, Any]:
    """Capture-host provenance stamped onto every exported record."""
    global _host_info_cache
    if _host_info_cache is None:
        _host_info_cache = {
            "hostname": socket.gethostname(),
            "pid": os.getpid(),
            "platform": sys.platform,
            "python": platform.python_version(),
        }
    return dict(_host_info_cache)


class JsonlExporter:
    """Write records as schema-versioned JSON lines.

    ``enrich`` fills only *missing* fields: a replayed record that
    already carries ``stale: true`` / the capture host of the original
    measurement keeps that provenance instead of being restamped.
    """

    def __init__(self, path: Optional[str] = None,
                 stream: Optional[IO[str]] = None):
        if (path is None) == (stream is None):
            raise ValueError("exactly one of path/stream required")
        self._stream = stream
        self._path = path
        self._file: Optional[IO[str]] = None

    @staticmethod
    def enrich(record: Dict[str, Any], stale: bool = False
               ) -> Dict[str, Any]:
        out = dict(record)
        out.setdefault("schema_version", SCHEMA_VERSION)
        out.setdefault("host", host_info())
        out.setdefault("stale", bool(stale))
        out["stale"] = bool(out["stale"])
        return out

    def _out(self) -> IO[str]:
        if self._stream is not None:
            return self._stream
        if self._file is None:
            self._file = open(self._path, "a")
        return self._file

    def emit(self, record: Dict[str, Any], stale: bool = False
             ) -> Dict[str, Any]:
        line = self.enrich(record, stale=stale)
        out = self._out()
        out.write(json.dumps(line) + "\n")
        out.flush()
        return line

    def emit_registry(self, registry: MetricsRegistry,
                      **extra) -> List[Dict[str, Any]]:
        """One record per metric (histograms as their summary)."""
        lines = []
        for m in registry.collect():
            rec = {"metric": m.name, "kind": m.kind, **extra}
            if isinstance(m, Histogram):
                rec.update(m.summary())
            else:
                rec["value"] = m.value
            lines.append(self.emit(rec))
        return lines

    def close(self):
        if self._file is not None:
            self._file.close()
            self._file = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()


# -- Prometheus text exposition ------------------------------------------

def _escape_label_value(v) -> str:
    """Exposition-format label-value escaping: backslash, double quote
    and newline must be escaped or a label like ``layer="conv\\1"`` /
    a path with a quote corrupts every line after it."""
    return (str(v).replace("\\", r"\\").replace('"', r'\"')
            .replace("\n", r"\n"))


def _unescape_label_value(v: str) -> str:
    out, i = [], 0
    while i < len(v):
        c = v[i]
        if c == "\\" and i + 1 < len(v):
            nxt = v[i + 1]
            out.append({"\\": "\\", '"': '"', "n": "\n"}.get(nxt,
                                                             c + nxt))
            i += 2
        else:
            out.append(c)
            i += 1
    return "".join(out)


def _escape_help(h: str) -> str:
    """HELP text escaping (backslash + newline; quotes are legal
    there)."""
    return h.replace("\\", r"\\").replace("\n", r"\n")


def _fmt_labels(label_set) -> str:
    if not label_set:
        return ""
    return "{" + ",".join(
        f'{k}="{_escape_label_value(v)}"' for k, v in label_set) + "}"


def _edge_str(e: float) -> str:
    return repr(e) if e != int(e) else str(int(e))


def _expose_one(lines: List[str], m, label_set=()):
    if isinstance(m, Histogram):
        acc = 0
        with m._lock:
            counts, total, n = list(m._counts), m._sum, m._count
        for e, c in zip(m.edges, counts):
            acc += c
            ls = tuple(label_set) + (("le", _edge_str(e)),)
            lines.append(f"{m.name}_bucket{_fmt_labels(ls)} {acc}")
        ls = tuple(label_set) + (("le", "+Inf"),)
        lines.append(f"{m.name}_bucket{_fmt_labels(ls)} {acc + counts[-1]}")
        lines.append(f"{m.name}_sum{_fmt_labels(label_set)} {total}")
        lines.append(f"{m.name}_count{_fmt_labels(label_set)} {n}")
    else:
        lines.append(f"{m.name}{_fmt_labels(label_set)} {m.value}")


def prometheus_text(registry: Optional[MetricsRegistry] = None) -> str:
    """Registry contents in the Prometheus text exposition format
    (labeled children exported under the parent name)."""
    from .metrics import get_registry
    reg = registry or get_registry()
    lines: List[str] = []
    for m in reg.collect():
        if m.help:
            lines.append(f"# HELP {m.name} {_escape_help(m.help)}")
        lines.append(f"# TYPE {m.name} {m.kind}")
        children = m.children()
        # a parent that only ever fans out to labeled children (bare
        # value untouched) contributes no unlabeled sample
        untouched = (m.count == 0 if isinstance(m, Histogram)
                     else m.value == 0)
        if not (children and untouched):
            _expose_one(lines, m)
        for key, child in sorted(children.items()):
            _expose_one(lines, child, key)
    return "\n".join(lines) + "\n"


# a sample line: name, optional {labels}, value.  Label values are
# double-quoted with \\ \" \n escapes (the regex accepts any escaped
# char and _unescape_label_value resolves it).
_PROM_SAMPLE_RE = re.compile(
    r'^([a-zA-Z_:][a-zA-Z0-9_:]*)'
    r'(?:\{((?:[a-zA-Z_][a-zA-Z0-9_]*="(?:[^"\\]|\\.)*",?)*)\})?'
    r'\s+(\S+)\s*$')
_PROM_LABEL_RE = re.compile(
    r'([a-zA-Z_][a-zA-Z0-9_]*)="((?:[^"\\]|\\.)*)"')
# suffixes a histogram family's samples may carry
_HIST_SUFFIXES = ("_bucket", "_sum", "_count")


def parse_prometheus_text(text: str) -> Dict[str, Dict[str, Any]]:
    """Parse one text exposition into
    ``{family: {type, help, samples: [(name, labels, value)]}}`` with
    label values UNESCAPED — the round-trip half of the conformance
    test.  Raises ``ValueError`` on a malformed line (the validator
    wrapper reports instead)."""
    families: Dict[str, Dict[str, Any]] = {}

    def fam(name):
        return families.setdefault(
            name, {"type": None, "help": None, "samples": []})

    for i, raw in enumerate(text.splitlines(), 1):
        line = raw.strip()
        if not line:
            continue
        if line.startswith("# HELP "):
            rest = line[len("# HELP "):].split(" ", 1)
            fam(rest[0])["help"] = (rest[1] if len(rest) > 1 else "")
            continue
        if line.startswith("# TYPE "):
            rest = line[len("# TYPE "):].split(" ", 1)
            if len(rest) != 2:
                raise ValueError(f"line {i}: malformed TYPE: {raw!r}")
            fam(rest[0])["type"] = rest[1]
            continue
        if line.startswith("#"):
            continue                     # plain comment
        m = _PROM_SAMPLE_RE.match(line)
        if m is None:
            raise ValueError(f"line {i}: not a valid sample: {raw!r}")
        name, labels_raw, value_raw = m.groups()
        try:
            value = float(value_raw.replace("+Inf", "inf")
                          .replace("-Inf", "-inf"))
        except ValueError:
            raise ValueError(f"line {i}: non-numeric value "
                             f"{value_raw!r}") from None
        labels = {k: _unescape_label_value(v)
                  for k, v in _PROM_LABEL_RE.findall(labels_raw or "")}
        base = name
        for sfx in _HIST_SUFFIXES:
            if name.endswith(sfx) and name[:-len(sfx)] in families:
                base = name[:-len(sfx)]
                break
        fam(base)["samples"].append((name, labels, value))
    return families


def validate_prometheus_text(text: str) -> List[str]:
    """Exposition-format conformance check (the `/metricsz` contract,
    shared by the pytest round-trip and tests/ci/server_smoke.py):
    every line parses; every sample belongs to a ``# TYPE``-declared
    family; counters never go negative; histogram families expose a
    ``+Inf`` bucket per label set, cumulative bucket counts that are
    monotone over ascending ``le`` edges, and ``_count`` equal to the
    ``+Inf`` bucket; label values survive the escape round-trip (the
    parser has already unescaped them — a raw quote/newline would have
    failed the parse)."""
    errs: List[str] = []
    try:
        families = parse_prometheus_text(text)
    except ValueError as e:
        return [str(e)]
    for name, f in sorted(families.items()):
        if f["type"] is None:
            errs.append(f"{name}: samples with no # TYPE line")
            continue
        if f["type"] not in ("counter", "gauge", "histogram",
                             "summary", "untyped"):
            errs.append(f"{name}: unknown type {f['type']!r}")
        if f["type"] == "counter":
            for sname, labels, value in f["samples"]:
                if value < 0:
                    errs.append(f"{name}: counter sample {sname} "
                                f"{labels} is negative ({value})")
        if f["type"] != "histogram":
            for sname, labels, _ in f["samples"]:
                if sname != name:
                    errs.append(f"{name}: unexpected sample name "
                                f"{sname!r} for a {f['type']}")
            continue
        # histogram: group buckets by their non-le label set
        series: Dict[tuple, Dict[str, Any]] = {}
        for sname, labels, value in f["samples"]:
            key = tuple(sorted((k, v) for k, v in labels.items()
                               if k != "le"))
            s = series.setdefault(key, {"buckets": [], "sum": None,
                                        "count": None})
            if sname == name + "_bucket":
                if "le" not in labels:
                    errs.append(f"{name}: bucket sample missing le "
                                f"label ({labels})")
                    continue
                le = labels["le"]
                edge = float("inf") if le == "+Inf" else float(le)
                s["buckets"].append((edge, value))
            elif sname == name + "_sum":
                s["sum"] = value
            elif sname == name + "_count":
                s["count"] = value
            else:
                errs.append(f"{name}: unexpected histogram sample "
                            f"{sname!r}")
        for key, s in sorted(series.items()):
            lbl = dict(key)
            buckets = sorted(s["buckets"])
            if not buckets or buckets[-1][0] != float("inf"):
                errs.append(f"{name}{lbl}: histogram has no +Inf "
                            f"bucket")
                continue
            prev = None
            for edge, c in buckets:
                if prev is not None and c < prev:
                    errs.append(f"{name}{lbl}: cumulative bucket "
                                f"counts decrease at le={edge}")
                prev = c
            if s["count"] is None or s["sum"] is None:
                errs.append(f"{name}{lbl}: histogram missing _sum or "
                            f"_count")
            elif s["count"] != buckets[-1][1]:
                errs.append(f"{name}{lbl}: _count ({s['count']}) != "
                            f"+Inf bucket ({buckets[-1][1]})")
    return errs


# -- shared field checks --------------------------------------------------

def _need(rec, errs, key, types, allow_none=False):
    """Shared required-key type check (bool is not an int here)."""
    if key not in rec:
        errs.append(f"missing required key {key!r}")
        return None
    v = rec[key]
    if v is None and allow_none:
        return v
    if not isinstance(v, types) or isinstance(v, bool) != (types is bool):
        errs.append(f"{key!r} must be {types}, got {type(v).__name__}")
    return v


def _check_envelope(rec, errs):
    """The common record envelope every exported line carries
    (schema_version / capture host / first-class ``stale``) — one
    implementation for every record kind."""
    sv = _need(rec, errs, "schema_version", int)
    if (isinstance(sv, int) and not isinstance(sv, bool)
            and sv != SCHEMA_VERSION):
        errs.append(f"schema_version {sv} is not the current schema "
                    f"({SCHEMA_VERSION}); records of another version "
                    f"are refused")
    _need(rec, errs, "stale", bool)
    host = _need(rec, errs, "host", dict)
    if isinstance(host, dict):
        if not isinstance(host.get("hostname"), str):
            errs.append("host.hostname must be a string")
        if not isinstance(host.get("pid"), int):
            errs.append("host.pid must be an int")


# -- graph-lint record schema ---------------------------------------------

_LINT_SEVERITIES = ("error", "warning", "info")


def validate_lint_record(rec: Any) -> List[str]:
    """Schema check for one graph-lint JSONL record (what
    ``python -m apex_tpu.analysis`` and tests/ci/graph_lint.py emit):
    the common envelope (schema_version / host / stale) plus either a
    finding (``kind: graph_lint``) or the run summary
    (``kind: graph_lint_summary``)."""
    errs: List[str] = []
    if not isinstance(rec, dict):
        return [f"record is {type(rec).__name__}, not an object"]

    def need(key, types):
        return _need(rec, errs, key, types)

    _check_envelope(rec, errs)
    kind = rec.get("kind")
    if kind == "graph_lint":
        for key in ("rule", "entry_point", "message"):
            v = need(key, str)
            if isinstance(v, str) and not v:
                errs.append(f"{key!r} must be non-empty")
        sev = need("severity", str)
        if isinstance(sev, str) and sev not in _LINT_SEVERITIES:
            errs.append(f"severity must be one of {_LINT_SEVERITIES}, "
                        f"got {sev!r}")
        if "detail" in rec and not isinstance(rec["detail"], dict):
            errs.append("'detail' must be an object when present")
    elif kind == "graph_lint_summary":
        for key in ("entry_points", "rules", "findings", "errors",
                    "warnings"):
            v = need(key, int)
            if isinstance(v, int) and not isinstance(v, bool) and v < 0:
                errs.append(f"{key!r} must be >= 0, got {v}")
        f, e, w = (rec.get("findings"), rec.get("errors"),
                   rec.get("warnings"))
        if all(isinstance(v, int) for v in (f, e, w)) and f != e + w:
            errs.append(f"findings ({f}) != errors ({e}) + warnings ({w})")
    else:
        errs.append(f"unknown lint kind {kind!r}")
    try:
        json.dumps(rec)
    except (TypeError, ValueError) as e:
        errs.append(f"record is not JSON-serializable: {e}")
    return errs


# -- fleet record schema ---------------------------------------------------

# monotonic fleet totals every ``kind: fleet`` record must carry —
# Fleet.record() emits exactly these (plus replicas/policy/state tallies)
_FLEET_COUNTS = ("queue_depth", "submitted", "finished", "failed",
                 "shed", "retries", "failovers", "drains", "tokens")

# the per-tenant bucket tallies a ``tenants`` block carries —
# the stdlib-side duplicate of fleet.slo's tenant bucket (this module
# must stay importable without jax; tests pin the shapes equal).
# Every field is a non-negative int; ``slo_attainment`` /
# ``goodput_tokens_per_s`` ride alongside with the fleet-level
# contract (null-or-fraction / non-negative number).
TENANT_COUNTS = ("submitted", "finished", "failed", "shed",
                 "deadline_exceeded", "slo_misses", "goodput_tokens",
                 "with_deadline", "within_deadline")

# the per-class bucket tallies a ``classes`` block carries — the
# tenant bucket plus ``preempted`` (requests evicted mid-decode to
# admit a higher-priority class; the evictee is re-queued from its
# prompt, so ``preempted`` is not a failure count).  Stdlib-side
# duplicate of fleet.slo's class bucket; tests pin the shapes equal.
CLASS_COUNTS = TENANT_COUNTS + ("preempted",)


def _check_tenants_block(rec, errs):
    """The per-tenant rollup contract, validated whenever present:
    ``tenants`` maps non-empty tenant names to buckets of TENANT_COUNTS
    tallies (ints >= 0, internally consistent — finishes cannot exceed
    submissions, within-deadline is a subset of with-deadline), and the
    per-tenant sums stay within the fleet totals (untagged requests are
    counted fleet-wide but deliberately kept OUT of the tenant map, so
    the sums are <=, never ==)."""
    if "tenants_dropped" in rec:
        v = rec["tenants_dropped"]
        if not isinstance(v, int) or isinstance(v, bool) or v < 0:
            errs.append(f"'tenants_dropped' must be an int >= 0, "
                        f"got {v!r}")
    if "tenants" not in rec:
        return
    tenants = rec["tenants"]
    if not isinstance(tenants, dict):
        errs.append("'tenants' must be an object when present")
        return
    sums = {k: 0 for k in ("shed", "deadline_exceeded",
                           "goodput_tokens")}
    for name, b in tenants.items():
        if not isinstance(name, str) or not name:
            errs.append(f"tenant names must be non-empty strings, "
                        f"got {name!r}")
        if not isinstance(b, dict):
            errs.append(f"tenants[{name!r}] must be an object")
            continue
        for key in TENANT_COUNTS:
            v = b.get(key)
            if key not in b:
                errs.append(f"tenants[{name!r}] missing {key!r}")
            elif not isinstance(v, int) or isinstance(v, bool) or v < 0:
                errs.append(f"tenants[{name!r}].{key} must be an int "
                            f">= 0, got {v!r}")
            elif key in sums:
                sums[key] += v
        fin, sub = b.get("finished"), b.get("submitted")
        if (isinstance(fin, int) and isinstance(sub, int)
                and not isinstance(fin, bool)
                and not isinstance(sub, bool) and fin > sub):
            errs.append(f"tenants[{name!r}]: finished ({fin}) exceeds "
                        f"submitted ({sub})")
        wi, wd = b.get("within_deadline"), b.get("with_deadline")
        if (isinstance(wi, int) and isinstance(wd, int)
                and not isinstance(wi, bool)
                and not isinstance(wd, bool) and wi > wd):
            errs.append(f"tenants[{name!r}]: within_deadline ({wi}) "
                        f"exceeds with_deadline ({wd})")
        att = b.get("slo_attainment")
        if att is not None and (
                not isinstance(att, numbers.Number)
                or isinstance(att, bool)
                or not (0.0 <= att <= 1.0)):
            errs.append(f"tenants[{name!r}].slo_attainment must be "
                        f"null or in [0, 1], got {att!r}")
        gp = b.get("goodput_tokens_per_s")
        if gp is not None and (
                not isinstance(gp, numbers.Number)
                or isinstance(gp, bool) or not (gp >= 0)):
            errs.append(f"tenants[{name!r}].goodput_tokens_per_s must "
                        f"be null or a number >= 0, got {gp!r}")
    # untagged traffic keeps the tenant sums strictly within the fleet
    # totals; a sum EXCEEDING its total is double-counting
    for key, total_key in (("shed", "shed"),
                           ("deadline_exceeded", "deadline_exceeded"),
                           ("goodput_tokens", "tokens_within_slo")):
        total = rec.get(total_key)
        if (isinstance(total, int) and not isinstance(total, bool)
                and sums[key] > total):
            errs.append(f"sum of per-tenant {key} ({sums[key]}) "
                        f"exceeds fleet {total_key} ({total})")


def _check_classes_block(rec, errs):
    """The per-class rollup contract, validated whenever present:
    ``classes`` maps non-empty priority-class names to buckets of
    CLASS_COUNTS tallies (ints >= 0, internally consistent the tenant
    way), each riding with the SLO pair (null-or-fraction attainment,
    non-negative goodput rate) and the live queue shape (depth/cap
    ints, weight >= 1, preemptible bool) — and the per-class sums stay
    within the fleet totals (every admitted request resolves to
    exactly one class, so under a multi-class policy the sums may
    reach the totals but never exceed them).  ``preemptions`` is the
    fleet-level eviction total the per-class ``preempted`` tallies
    roll up into."""
    if "preemptions" in rec:
        v = rec["preemptions"]
        if not isinstance(v, int) or isinstance(v, bool) or v < 0:
            errs.append(f"'preemptions' must be an int >= 0, "
                        f"got {v!r}")
    if "classes" not in rec:
        return
    classes = rec["classes"]
    if not isinstance(classes, dict):
        errs.append("'classes' must be an object when present")
        return
    sums = {k: 0 for k in ("shed", "deadline_exceeded",
                           "goodput_tokens")}
    preempted_sum = 0
    for name, b in classes.items():
        if not isinstance(name, str) or not name:
            errs.append(f"class names must be non-empty strings, "
                        f"got {name!r}")
        if not isinstance(b, dict):
            errs.append(f"classes[{name!r}] must be an object")
            continue
        for key in CLASS_COUNTS:
            v = b.get(key)
            if key not in b:
                errs.append(f"classes[{name!r}] missing {key!r}")
            elif not isinstance(v, int) or isinstance(v, bool) or v < 0:
                errs.append(f"classes[{name!r}].{key} must be an int "
                            f">= 0, got {v!r}")
            elif key in sums:
                sums[key] += v
            elif key == "preempted":
                preempted_sum += v
        fin, sub = b.get("finished"), b.get("submitted")
        if (isinstance(fin, int) and isinstance(sub, int)
                and not isinstance(fin, bool)
                and not isinstance(sub, bool) and fin > sub):
            errs.append(f"classes[{name!r}]: finished ({fin}) exceeds "
                        f"submitted ({sub})")
        wi, wd = b.get("within_deadline"), b.get("with_deadline")
        if (isinstance(wi, int) and isinstance(wd, int)
                and not isinstance(wi, bool)
                and not isinstance(wd, bool) and wi > wd):
            errs.append(f"classes[{name!r}]: within_deadline ({wi}) "
                        f"exceeds with_deadline ({wd})")
        att = b.get("slo_attainment")
        if att is not None and (
                not isinstance(att, numbers.Number)
                or isinstance(att, bool)
                or not (0.0 <= att <= 1.0)):
            errs.append(f"classes[{name!r}].slo_attainment must be "
                        f"null or in [0, 1], got {att!r}")
        gp = b.get("goodput_tokens_per_s")
        if gp is not None and (
                not isinstance(gp, numbers.Number)
                or isinstance(gp, bool) or not (gp >= 0)):
            errs.append(f"classes[{name!r}].goodput_tokens_per_s must "
                        f"be null or a number >= 0, got {gp!r}")
        for key in ("queue_depth", "queue_cap"):
            if key in b:
                v = b[key]
                if (not isinstance(v, int) or isinstance(v, bool)
                        or v < 0):
                    errs.append(f"classes[{name!r}].{key} must be an "
                                f"int >= 0 when present, got {v!r}")
        if "weight" in b:
            w = b["weight"]
            if not isinstance(w, int) or isinstance(w, bool) or w < 1:
                errs.append(f"classes[{name!r}].weight must be an int "
                            f">= 1 when present, got {w!r}")
        if "preemptible" in b and not isinstance(b["preemptible"],
                                                 bool):
            errs.append(f"classes[{name!r}].preemptible must be a "
                        f"bool when present, got "
                        f"{b['preemptible']!r}")
    for key, total_key in (("shed", "shed"),
                           ("deadline_exceeded", "deadline_exceeded"),
                           ("goodput_tokens", "tokens_within_slo")):
        total = rec.get(total_key)
        if (isinstance(total, int) and not isinstance(total, bool)
                and sums[key] > total):
            errs.append(f"sum of per-class {key} ({sums[key]}) "
                        f"exceeds fleet {total_key} ({total})")
    pre = rec.get("preemptions")
    if (isinstance(pre, int) and not isinstance(pre, bool)
            and preempted_sum > pre):
        errs.append(f"sum of per-class preempted ({preempted_sum}) "
                    f"exceeds fleet preemptions ({pre})")


def validate_fleet_record(rec: Any) -> List[str]:
    """Schema check for one ``kind: fleet`` JSONL record
    (``Fleet.record()`` enriched by the exporter): the common envelope
    plus the replica/state tallies and the fleet counters
    (shed/retries/failovers/drains & co), with the cross-field sanity
    checks a dashboard would otherwise discover at 3am — state tallies
    cannot exceed the replica count, finishes cannot exceed
    submissions."""
    errs: List[str] = []
    if not isinstance(rec, dict):
        return [f"record is {type(rec).__name__}, not an object"]

    def need(key, types):
        return _need(rec, errs, key, types)

    _check_envelope(rec, errs)
    if rec.get("kind") != "fleet":
        errs.append(f"kind must be 'fleet', got {rec.get('kind')!r}")
    # the flight-recorder cross-reference: every fleet snapshot names
    # the fleet-run trace whose request traces (``kind: trace``,
    # trace_id "<fleet>/r<rid>") it aggregates — a dashboard can join
    # the two streams on this id.
    tid = need("trace_id", str)
    if isinstance(tid, str) and not tid:
        errs.append("trace_id must be non-empty")
    pol = need("policy", str)
    if isinstance(pol, str) and not pol:
        errs.append("policy must be non-empty")
    n = need("replicas", int)
    if isinstance(n, int) and not isinstance(n, bool) and n < 1:
        errs.append(f"replicas must be >= 1, got {n}")
    tally = 0
    for key in ("healthy", "degraded", "dead"):
        v = need(key, int)
        if isinstance(v, int) and not isinstance(v, bool):
            if v < 0:
                errs.append(f"{key!r} must be >= 0, got {v}")
            tally += v
    if isinstance(n, int) and not isinstance(n, bool) and tally > n:
        errs.append(f"healthy+degraded+dead ({tally}) exceeds "
                    f"replicas ({n})")
    for key in _FLEET_COUNTS:
        v = need(key, int)
        if isinstance(v, int) and not isinstance(v, bool) and v < 0:
            errs.append(f"{key!r} must be >= 0, got {v}")
    fin, sub = rec.get("finished"), rec.get("submitted")
    if (isinstance(fin, int) and isinstance(sub, int)
            and not isinstance(fin, bool) and not isinstance(sub, bool)
            and fin > sub):
        errs.append(f"finished ({fin}) exceeds submitted ({sub})")
    # SLO / goodput / deadline-sweep fields (OPTIONAL, but whenever
    # present they must be internally consistent: goodput
    # cannot exceed total tokens, attainment is a fraction or null,
    # and the deadline-sweep aggregate mirrors what the flight ring's
    # ``deadline_exceeded`` events carry)
    for opt in ("deadline_exceeded", "tokens_within_slo"):
        if opt in rec:
            v = rec[opt]
            if not isinstance(v, int) or isinstance(v, bool) or v < 0:
                errs.append(f"{opt!r} must be an int >= 0 when "
                            f"present, got {v!r}")
    tw, tok = rec.get("tokens_within_slo"), rec.get("tokens")
    if (isinstance(tw, int) and isinstance(tok, int)
            and not isinstance(tw, bool) and not isinstance(tok, bool)
            and tw > tok):
        errs.append(f"tokens_within_slo ({tw}) exceeds tokens ({tok})")
    if "goodput_tokens_per_s" in rec:
        v = rec["goodput_tokens_per_s"]
        if (not isinstance(v, numbers.Number) or isinstance(v, bool)
                or not (v >= 0)):
            errs.append(f"'goodput_tokens_per_s' must be a number "
                        f">= 0 when present, got {v!r}")
    if "slo_attainment" in rec and rec["slo_attainment"] is not None:
        v = rec["slo_attainment"]
        if (not isinstance(v, numbers.Number) or isinstance(v, bool)
                or not (0.0 <= v <= 1.0)):
            errs.append(f"'slo_attainment' must be null or in [0, 1], "
                        f"got {v!r}")
    if "mttr" in rec:
        # optional: the fleet's failover→first-progress
        # aggregate ({last, mean, count}), same nullability contract
        # as the recovery record's mttr_s
        mttr = rec["mttr"]
        if not isinstance(mttr, dict):
            errs.append("'mttr' must be an object when present")
        else:
            c = mttr.get("count")
            if not isinstance(c, int) or isinstance(c, bool) or c < 0:
                errs.append(f"mttr.count must be an int >= 0, got "
                            f"{c!r}")
            for k in ("last", "mean"):
                v = mttr.get(k)
                if v is None:
                    continue
                if (not isinstance(v, numbers.Number)
                        or isinstance(v, bool) or v != v
                        or not (v >= 0)):
                    errs.append(f"mttr.{k} must be null or a finite "
                                f"number >= 0, got {v!r}")
    # the tenant and QoS planes: Fleet.record() always emits both
    # blocks (an empty object when no request was tagged, zero buckets
    # for every policy class when nothing ran), so a record missing
    # one was hand-built
    for key in ("tenants", "tenants_dropped", "classes", "preemptions"):
        if key not in rec:
            errs.append(f"fleet records must carry {key!r}")
    _check_tenants_block(rec, errs)
    _check_classes_block(rec, errs)
    if "deadline_last_sweep" in rec:
        sweep = rec["deadline_last_sweep"]
        if not isinstance(sweep, dict):
            errs.append("'deadline_last_sweep' must be an object when "
                        "present")
        else:
            c = sweep.get("count")
            if not isinstance(c, int) or isinstance(c, bool) or c < 0:
                errs.append(f"deadline_last_sweep.count must be an "
                            f"int >= 0, got {c!r}")
            rids = sweep.get("rids")
            if not isinstance(rids, list) or any(
                    not isinstance(r, int) or isinstance(r, bool)
                    for r in rids):
                errs.append("deadline_last_sweep.rids must be a list "
                            "of ints")
            elif isinstance(c, int) and not isinstance(c, bool) \
                    and len(rids) > c:
                errs.append(f"deadline_last_sweep lists {len(rids)} "
                            f"rids for a count of {c}")
    try:
        json.dumps(rec)
    except (TypeError, ValueError) as e:
        errs.append(f"record is not JSON-serializable: {e}")
    return errs


# -- memory record schema ---------------------------------------------------

# Compiled.memory_analysis() components every ``kind: memory`` record
# must carry; ``peak_bytes`` must reassemble from them exactly.
# Public: observability.memory builds its plans from THIS tuple, so
# the producer and the validator cannot drift.  (This module stays
# import-light — memory.py imports from here, never the reverse, so
# tests/ci/check_telemetry_schema.py's jax-free loader keeps working.)
MEMORY_PLAN_KEYS = ("argument_bytes", "output_bytes", "temp_bytes",
                    "alias_bytes", "generated_code_bytes")
_MEMORY_PLAN_KEYS = MEMORY_PLAN_KEYS


def validate_memory_record(rec: Any) -> List[str]:
    """Schema check for one ``kind: memory`` JSONL record (the
    cost-model/memory-plan dump emitted per analysis entry point by
    ``python -m apex_tpu.analysis --memory``): the common envelope, a
    subject (``entry_point`` or ``metric``), non-negative analytic FLOP/byte totals, the compiled
    memory-plan components, and the arithmetic cross-check — a
    ``peak_bytes`` that does not reassemble from its own components is
    a hand-built record, not a plan."""
    errs: List[str] = []
    if not isinstance(rec, dict):
        return [f"record is {type(rec).__name__}, not an object"]

    def need(key, types):
        return _need(rec, errs, key, types)

    _check_envelope(rec, errs)
    if rec.get("kind") != "memory":
        errs.append(f"kind must be 'memory', got {rec.get('kind')!r}")
    subject = rec.get("entry_point", rec.get("metric"))
    if not isinstance(subject, str) or not subject:
        errs.append("memory records must carry a non-empty "
                    "'entry_point' or 'metric'")
    for key in ("flops", "transcendentals", "matmul_flops"):
        v = need(key, numbers.Number)
        if (isinstance(v, numbers.Number) and not isinstance(v, bool)
                and v < 0):
            errs.append(f"{key!r} must be >= 0, got {v}")
    parts = {}
    for key in _MEMORY_PLAN_KEYS + ("peak_bytes", "bytes_accessed"):
        v = need(key, int)
        if isinstance(v, int) and not isinstance(v, bool):
            if v < 0:
                errs.append(f"{key!r} must be >= 0, got {v}")
            parts[key] = v
    if len(parts) == len(_MEMORY_PLAN_KEYS) + 2:
        expect = (parts["argument_bytes"] + parts["output_bytes"]
                  + parts["temp_bytes"] + parts["generated_code_bytes"]
                  - parts["alias_bytes"])
        if parts["peak_bytes"] != expect:
            errs.append(
                f"peak_bytes ({parts['peak_bytes']}) != argument + "
                f"output + temp + generated_code - alias ({expect})")
    for opt in ("analytic_live_bytes", "analytic_temp_bytes",
                "kv_cache_bytes"):
        if opt in rec:
            v = rec[opt]
            if not isinstance(v, int) or isinstance(v, bool) or v < 0:
                errs.append(f"{opt!r} must be an int >= 0 when "
                            f"present, got {v!r}")
    for opt in ("matmul_flops_by_dtype", "bytes_by_dtype",
                "analytic_temp_bytes_by_dtype"):
        if opt in rec and not isinstance(rec[opt], dict):
            errs.append(f"{opt!r} must be an object when present")
    try:
        json.dumps(rec)
    except (TypeError, ValueError) as e:
        errs.append(f"record is not JSON-serializable: {e}")
    return errs


# -- sharding record schema -------------------------------------------------

def validate_sharding_record(rec: Any) -> List[str]:
    """Schema check for one ``kind: sharding`` JSONL record (the static
    replication ledger from ``analysis.sharding.
    entry_point_sharding_record``): the common envelope, a
    non-empty ``entry_point``, a coherent mesh (``world`` equals the
    product of ``mesh_axes``), non-negative byte totals with the
    arithmetic identity ``unique_bytes + replicated_bytes == world *
    argument_bytes`` (the ledger must reassemble from its own parts),
    a per-dtype split that sums to ``replicated_bytes``, a
    ``replicated_fraction`` consistent with the totals, well-formed
    ``top_replicated`` entries, and a resharding census of
    non-negative eqn counts."""
    errs: List[str] = []
    if not isinstance(rec, dict):
        return [f"record is {type(rec).__name__}, not an object"]

    def need(key, types):
        return _need(rec, errs, key, types)

    _check_envelope(rec, errs)
    if rec.get("kind") != "sharding":
        errs.append(f"kind must be 'sharding', got {rec.get('kind')!r}")
    epn = need("entry_point", str)
    if isinstance(epn, str) and not epn:
        errs.append("entry_point must be non-empty")
    src = need("source", str)
    if isinstance(src, str) and not src:
        errs.append("source must be non-empty")
    world = need("world", int)
    if isinstance(world, int) and not isinstance(world, bool) \
            and world < 1:
        errs.append(f"world must be >= 1, got {world}")
    axes = need("mesh_axes", dict)
    if isinstance(axes, dict):
        prod = 1
        ok = bool(axes)
        for name, sz in axes.items():
            if not isinstance(name, str) or not name:
                errs.append(f"mesh axis names must be non-empty "
                            f"strings, got {name!r}")
                ok = False
            if not isinstance(sz, int) or isinstance(sz, bool) or sz < 1:
                errs.append(f"mesh_axes[{name!r}] must be an int >= 1, "
                            f"got {sz!r}")
                ok = False
            else:
                prod *= sz
        if not axes:
            errs.append("mesh_axes must be non-empty")
        if (ok and isinstance(world, int) and not isinstance(world, bool)
                and prod != world):
            errs.append(f"world ({world}) != product of mesh_axes "
                        f"({prod})")
    sm = need("shard_maps", int)
    if isinstance(sm, int) and not isinstance(sm, bool) and sm < 1:
        errs.append(f"shard_maps must be >= 1, got {sm}")
    parts = {}
    for key in ("argument_bytes", "unique_bytes", "replicated_bytes"):
        v = need(key, int)
        if isinstance(v, int) and not isinstance(v, bool):
            if v < 0:
                errs.append(f"{key!r} must be >= 0, got {v}")
            else:
                parts[key] = v
    if (len(parts) == 3 and isinstance(world, int)
            and not isinstance(world, bool) and world >= 1
            and parts["unique_bytes"] + parts["replicated_bytes"]
            != world * parts["argument_bytes"]):
        errs.append(
            f"unique_bytes + replicated_bytes "
            f"({parts['unique_bytes']} + {parts['replicated_bytes']}) "
            f"!= world * argument_bytes "
            f"({world} * {parts['argument_bytes']}) — the ledger must "
            f"reassemble from its own parts")
    by_dtype = need("replicated_bytes_by_dtype", dict)
    if isinstance(by_dtype, dict):
        total = 0
        ok = True
        for dt, v in by_dtype.items():
            if not isinstance(dt, str) or not dt:
                errs.append(f"replicated_bytes_by_dtype keys must be "
                            f"non-empty strings, got {dt!r}")
                ok = False
            if not isinstance(v, int) or isinstance(v, bool) or v < 0:
                errs.append(f"replicated_bytes_by_dtype[{dt!r}] must "
                            f"be an int >= 0, got {v!r}")
                ok = False
            else:
                total += v
        if ok and "replicated_bytes" in parts \
                and total != parts["replicated_bytes"]:
            errs.append(f"replicated_bytes_by_dtype sums to {total}, "
                        f"!= replicated_bytes "
                        f"({parts['replicated_bytes']})")
    frac = need("replicated_fraction", numbers.Number)
    if (isinstance(frac, numbers.Number) and not isinstance(frac, bool)
            and not (0.0 <= frac <= 1.0)):
        errs.append(f"replicated_fraction must be in [0, 1], got "
                    f"{frac!r}")
    if (isinstance(frac, numbers.Number) and not isinstance(frac, bool)
            and len(parts) == 3 and isinstance(world, int)
            and not isinstance(world, bool) and world >= 1
            and parts["argument_bytes"] > 0):
        expect = (parts["replicated_bytes"]
                  / (world * parts["argument_bytes"]))
        if abs(frac - expect) > 1e-9:
            errs.append(f"replicated_fraction ({frac}) inconsistent "
                        f"with replicated_bytes / (world * "
                        f"argument_bytes) ({expect:.6g})")
    top = need("top_replicated", list)
    if isinstance(top, list):
        for i, t in enumerate(top):
            if not isinstance(t, dict):
                errs.append(f"top_replicated[{i}] is not an object")
                continue
            idx = t.get("index")
            if not isinstance(idx, int) or isinstance(idx, bool) \
                    or idx < 0:
                errs.append(f"top_replicated[{i}].index must be an "
                            f"int >= 0, got {idx!r}")
            if not isinstance(t.get("shape"), list):
                errs.append(f"top_replicated[{i}].shape must be a list")
            if not isinstance(t.get("dtype"), str) or not t.get("dtype"):
                errs.append(f"top_replicated[{i}].dtype must be a "
                            f"non-empty string")
            lb = t.get("local_bytes")
            if not isinstance(lb, int) or isinstance(lb, bool) or lb < 0:
                errs.append(f"top_replicated[{i}].local_bytes must be "
                            f"an int >= 0, got {lb!r}")
            rf = t.get("replication_factor")
            if (not isinstance(rf, numbers.Number)
                    or isinstance(rf, bool) or not (rf >= 1)):
                errs.append(f"top_replicated[{i}].replication_factor "
                            f"must be a number >= 1, got {rf!r}")
            if not isinstance(t.get("spec"), str) or not t.get("spec"):
                errs.append(f"top_replicated[{i}].spec must be a "
                            f"non-empty string")
    census = need("resharding_eqns", dict)
    if isinstance(census, dict):
        for prim, n in census.items():
            if not isinstance(prim, str) or not prim:
                errs.append(f"resharding_eqns keys must be non-empty "
                            f"strings, got {prim!r}")
            if not isinstance(n, int) or isinstance(n, bool) or n < 0:
                errs.append(f"resharding_eqns[{prim!r}] must be an "
                            f"int >= 0, got {n!r}")
    # a ledger for a ZeRO entry point must say which stage it
    # measured — stage 3's collapse (nothing replicated but BN state
    # and scalars) is only comparable against stage 1/2 ledgers when
    # each carries its stage; validated whenever present, required on
    # fresh zero-EP records
    if "zero_stage" in rec:
        zs = rec["zero_stage"]
        if not isinstance(zs, int) or isinstance(zs, bool) \
                or zs not in (1, 2, 3):
            errs.append(f"'zero_stage' must be 1, 2 or 3 when present, "
                        f"got {zs!r}")
    if (isinstance(epn, str) and "zero" in epn
            and not rec.get("stale") and "zero_stage" not in rec):
        errs.append("fresh sharding records for ZeRO entry points must "
                    "carry 'zero_stage'")
    try:
        json.dumps(rec)
    except (TypeError, ValueError) as e:
        errs.append(f"record is not JSON-serializable: {e}")
    return errs


# -- numerics record schema -------------------------------------------------

def validate_numerics_record(rec: Any) -> List[str]:
    """Schema check for one ``kind: numerics`` JSONL record
    (``NumericsMonitor.to_record`` enriched by the exporter): the
    common envelope, a subject (``metric`` or ``entry_point``), the
    step/overflow tallies, a non-empty per-layer health list
    (nonfinite counts, abs-max, grad norm, underflow fraction), a
    ``culprit`` that — when named — must actually be one of the
    record's layers (an attribution pointing at a layer the record
    does not describe is a hand-built record, not a flush), plus the
    optional per-bucket and divergence-digest sections with their own
    cross-field consistency (``in_sync`` iff zero desync steps)."""
    errs: List[str] = []
    if not isinstance(rec, dict):
        return [f"record is {type(rec).__name__}, not an object"]

    def need(key, types, allow_none=False):
        return _need(rec, errs, key, types, allow_none)

    _check_envelope(rec, errs)
    if rec.get("kind") != "numerics":
        errs.append(f"kind must be 'numerics', got {rec.get('kind')!r}")
    subject = rec.get("entry_point", rec.get("metric"))
    if not isinstance(subject, str) or not subject:
        errs.append("numerics records must carry a non-empty "
                    "'entry_point' or 'metric'")
    steps = need("steps", int)
    ov = need("overflow_steps", int)
    for key, v in (("steps", steps), ("overflow_steps", ov)):
        if isinstance(v, int) and not isinstance(v, bool) and v < 0:
            errs.append(f"{key!r} must be >= 0, got {v}")
    if (isinstance(steps, int) and isinstance(ov, int)
            and not isinstance(steps, bool) and not isinstance(ov, bool)
            and ov > steps):
        errs.append(f"overflow_steps ({ov}) exceeds steps ({steps})")
    for opt in ("loss_scale", "grad_norm", "tiny"):
        if opt in rec:
            v = rec[opt]
            if (not isinstance(v, numbers.Number)
                    or isinstance(v, bool) or v < 0):
                errs.append(f"{opt!r} must be a number >= 0 when "
                            f"present, got {v!r}")
    if "half_dtype" in rec and rec["half_dtype"] not in (
            "float16", "bfloat16"):
        errs.append(f"'half_dtype' must be float16/bfloat16, got "
                    f"{rec['half_dtype']!r}")
    layer_names = set()
    layers = need("layers", list)
    if isinstance(layers, list):
        if not layers:
            errs.append("layers must be non-empty (a health record "
                        "with no layers describes nothing)")
        for i, lyr in enumerate(layers):
            if not isinstance(lyr, dict):
                errs.append(f"layers[{i}] is not an object")
                continue
            name = lyr.get("name")
            if not isinstance(name, str) or not name:
                errs.append(f"layers[{i}].name must be a non-empty "
                            f"string")
            else:
                layer_names.add(name)
            nf = lyr.get("nonfinite")
            if not isinstance(nf, int) or isinstance(nf, bool) or nf < 0:
                errs.append(f"layers[{i}].nonfinite must be an int "
                            f">= 0, got {nf!r}")
            for key in ("abs_max", "grad_norm"):
                v = lyr.get(key)
                # `not (v >= 0)` also rejects NaN (all NaN
                # comparisons are false) — a health record carrying
                # un-numbers is worse than none
                if (not isinstance(v, numbers.Number)
                        or isinstance(v, bool) or not (v >= 0)):
                    errs.append(f"layers[{i}].{key} must be a number "
                                f">= 0, got {v!r}")
            uf = lyr.get("underflow_fraction")
            if (not isinstance(uf, numbers.Number)
                    or isinstance(uf, bool)
                    or not (0.0 <= uf <= 1.0)):
                errs.append(f"layers[{i}].underflow_fraction must be "
                            f"in [0, 1], got {uf!r}")
    culprit = rec.get("culprit")
    if culprit is not None:
        if not isinstance(culprit, str) or not culprit:
            errs.append(f"'culprit' must be null or a non-empty "
                        f"string, got {culprit!r}")
        elif isinstance(layers, list) and culprit not in layer_names:
            errs.append(f"culprit {culprit!r} is not one of the "
                        f"record's layers")
    if culprit is not None and isinstance(ov, int) \
            and not isinstance(ov, bool) and ov == 0:
        errs.append("a culprit with zero overflow_steps attributes an "
                    "overflow that never happened")
    if "buckets" in rec:
        bks = rec["buckets"]
        if not isinstance(bks, list):
            errs.append("'buckets' must be a list when present")
        else:
            for i, b in enumerate(bks):
                if not isinstance(b, dict):
                    errs.append(f"buckets[{i}] is not an object")
                    continue
                lbl = b.get("label")
                if not isinstance(lbl, str) or not lbl:
                    errs.append(f"buckets[{i}].label must be a "
                                f"non-empty string")
                nf = b.get("nonfinite")
                if not isinstance(nf, int) or isinstance(nf, bool) \
                        or nf < 0:
                    errs.append(f"buckets[{i}].nonfinite must be an "
                                f"int >= 0, got {nf!r}")
                for key in ("abs_max", "grad_norm",
                            "compression_sq_error"):
                    if key in b:
                        v = b[key]
                        if (not isinstance(v, numbers.Number)
                                or isinstance(v, bool)
                                or not (v >= 0)):
                            errs.append(f"buckets[{i}].{key} must be "
                                        f"a number >= 0, got {v!r}")
    if "divergence" in rec:
        div = rec["divergence"]
        if not isinstance(div, dict):
            errs.append("'divergence' must be an object when present")
        else:
            mr = div.get("max_rel_dev")
            if (not isinstance(mr, numbers.Number)
                    or isinstance(mr, bool) or not (mr >= 0)):
                errs.append(f"divergence.max_rel_dev must be a number "
                            f">= 0, got {mr!r}")
            ds = div.get("desync_steps")
            if not isinstance(ds, int) or isinstance(ds, bool) or ds < 0:
                errs.append(f"divergence.desync_steps must be an int "
                            f">= 0, got {ds!r}")
            ins = div.get("in_sync")
            if not isinstance(ins, bool):
                errs.append(f"divergence.in_sync must be a bool, got "
                            f"{ins!r}")
            elif isinstance(ds, int) and not isinstance(ds, bool) \
                    and ins != (ds == 0):
                errs.append(f"divergence.in_sync ({ins}) inconsistent "
                            f"with desync_steps ({ds})")
    try:
        json.dumps(rec)
    except (TypeError, ValueError) as e:
        errs.append(f"record is not JSON-serializable: {e}")
    return errs


# -- run record schema ------------------------------------------------------

# anomaly kinds a supervisor may declare — kept in sync with
# observability.supervisor.ANOMALY_KINDS (duplicated here so the
# stdlib-only CI loader never imports the supervisor module; the
# pytest coverage pins the two tuples equal)
RUN_ANOMALY_KINDS = ("stall", "loss_spike", "nan",
                     "throughput_regression", "replica_divergence",
                     "recompilation_storm")


def validate_run_record(rec: Any) -> List[str]:
    """Schema check for one ``kind: run`` JSONL record
    (``RunSupervisor.record`` enriched by the exporter):
    the common envelope, a non-empty ``run`` name, the observation /
    watermark tallies, per-kind anomaly counts over the KNOWN kinds,
    a bounded anomaly-detail list whose entries each name a counted
    kind, and the verdict cross-check — ``ok`` iff zero anomalies
    (a record claiming health while counting anomalies is lying to
    the dashboard)."""
    errs: List[str] = []
    if not isinstance(rec, dict):
        return [f"record is {type(rec).__name__}, not an object"]

    def need(key, types, allow_none=False):
        return _need(rec, errs, key, types, allow_none)

    _check_envelope(rec, errs)
    if rec.get("kind") != "run":
        errs.append(f"kind must be 'run', got {rec.get('kind')!r}")
    run = need("run", str)
    if isinstance(run, str) and not run:
        errs.append("run must be non-empty")
    obs = need("observations", int)
    if isinstance(obs, int) and not isinstance(obs, bool) and obs < 0:
        errs.append(f"observations must be >= 0, got {obs}")
    wm = rec.get("watermark")
    if wm is not None and (not isinstance(wm, int)
                           or isinstance(wm, bool)):
        errs.append(f"'watermark' must be null or an int, got {wm!r}")
    verdict = need("verdict", str)
    if isinstance(verdict, str) and verdict not in ("ok", "attention"):
        errs.append(f"verdict must be 'ok' or 'attention', got "
                    f"{verdict!r}")
    counts = need("anomaly_counts", dict)
    total = None
    if isinstance(counts, dict):
        total = 0
        for k, v in sorted(counts.items()):
            if k not in RUN_ANOMALY_KINDS:
                errs.append(f"anomaly_counts names unknown kind {k!r} "
                            f"(known: {RUN_ANOMALY_KINDS})")
            if not isinstance(v, int) or isinstance(v, bool) or v < 0:
                errs.append(f"anomaly_counts[{k!r}] must be an int "
                            f">= 0, got {v!r}")
            else:
                total += v
    if isinstance(verdict, str) and total is not None \
            and verdict in ("ok", "attention") \
            and (verdict == "ok") != (total == 0):
        errs.append(f"verdict {verdict!r} inconsistent with "
                    f"{total} counted anomalies")
    anomalies = need("anomalies", list)
    if isinstance(anomalies, list):
        per_kind: Dict[str, int] = {}
        for i, a in enumerate(anomalies):
            if not isinstance(a, dict):
                errs.append(f"anomalies[{i}] is not an object")
                continue
            k = a.get("kind")
            if k not in RUN_ANOMALY_KINDS:
                errs.append(f"anomalies[{i}].kind must be one of "
                            f"{RUN_ANOMALY_KINDS}, got {k!r}")
            else:
                per_kind[k] = per_kind.get(k, 0) + 1
            o = a.get("observation")
            if not isinstance(o, int) or isinstance(o, bool) or o < 1:
                errs.append(f"anomalies[{i}].observation must be an "
                            f"int >= 1, got {o!r}")
        if isinstance(counts, dict):
            for k, n in sorted(per_kind.items()):
                c = counts.get(k)
                if isinstance(c, int) and not isinstance(c, bool) \
                        and n > c:
                    errs.append(
                        f"anomalies lists {n} {k!r} entries but "
                        f"anomaly_counts[{k!r}] is {c} (the detail "
                        f"list is bounded, the counts are exact — "
                        f"details can never exceed the count)")
    # the loss / step-time summaries, when present, must be objects of
    # numbers-or-null with NaN rejected (x == x is False only for NaN)
    for opt in ("loss", "step_time_s"):
        if opt in rec:
            d = rec[opt]
            if not isinstance(d, dict):
                errs.append(f"{opt!r} must be an object when present")
                continue
            for k, v in sorted(d.items()):
                if v is None:
                    continue
                if (not isinstance(v, numbers.Number)
                        or isinstance(v, bool) or v != v):
                    errs.append(f"{opt}.{k} must be a finite number "
                                f"or null, got {v!r}")
    for opt in ("checkpoints",):
        if opt in rec:
            v = rec[opt]
            if not isinstance(v, int) or isinstance(v, bool) or v < 0:
                errs.append(f"{opt!r} must be an int >= 0 when "
                            f"present, got {v!r}")
    if "duration_s" in rec:
        v = rec["duration_s"]
        if (not isinstance(v, numbers.Number) or isinstance(v, bool)
                or not (v >= 0)):
            errs.append(f"'duration_s' must be a number >= 0, got "
                        f"{v!r}")
    try:
        json.dumps(rec)
    except (TypeError, ValueError) as e:
        errs.append(f"record is not JSON-serializable: {e}")
    return errs


# -- recovery record schema -------------------------------------------------

# fleet.recovery.RECOVERY_ROLES / RECOVERY_ACTION_KINDS /
# RECOVERY_CAUSES (duplicated here so the stdlib-side validator needs
# no jax-adjacent import — tests pin the pairs equal, the
# RUN_ANOMALY_KINDS discipline)
RECOVERY_ROLES = ("training", "serving")
RECOVERY_ACTION_KINDS = (
    "world_shrink", "resume", "rollback", "preempt_snapshot",
    "admission_tighten", "admission_relax",
    "class_admission_tighten", "class_admission_relax",
    "window_shrink", "window_grow",
    "drain", "undrain",
    "cooldown_shorten", "cooldown_extend")
RECOVERY_CAUSES = ("fault", "verdict", "preemption")


def validate_recovery_record(rec: Any) -> List[str]:
    """Schema check for one ``kind: recovery`` JSONL record
    (``fleet.recovery.RecoveryLog.record`` enriched by the exporter):
    the common envelope, a known controller ``role``, the
    episode/action tallies, a bounded action-detail list whose entries
    each name a known action kind inside a counted episode, and the
    MTTR aggregate — internally consistent the way a dashboard
    assumes (details never exceed the total, the per-episode maximum
    never exceeds it either, MTTR numbers are finite and
    non-negative)."""
    errs: List[str] = []
    if not isinstance(rec, dict):
        return [f"record is {type(rec).__name__}, not an object"]

    def need(key, types, allow_none=False):
        return _need(rec, errs, key, types, allow_none)

    _check_envelope(rec, errs)
    if rec.get("kind") != "recovery":
        errs.append(f"kind must be 'recovery', got {rec.get('kind')!r}")
    role = need("role", str)
    if isinstance(role, str) and role not in RECOVERY_ROLES:
        errs.append(f"role must be one of {RECOVERY_ROLES}, got "
                    f"{role!r}")
    subj = need("subject", str)
    if isinstance(subj, str) and not subj:
        errs.append("subject must be non-empty")
    eps = need("episodes", int)
    if isinstance(eps, int) and not isinstance(eps, bool) and eps < 0:
        errs.append(f"episodes must be >= 0, got {eps}")
    total = need("actions_total", int)
    if isinstance(total, int) and not isinstance(total, bool) \
            and total < 0:
        errs.append(f"actions_total must be >= 0, got {total}")
    mx = need("max_actions_in_episode", int)
    if isinstance(mx, int) and not isinstance(mx, bool):
        if mx < 0:
            errs.append(f"max_actions_in_episode must be >= 0, got "
                        f"{mx}")
        elif isinstance(total, int) and not isinstance(total, bool) \
                and mx > total:
            errs.append(f"max_actions_in_episode ({mx}) exceeds "
                        f"actions_total ({total})")
        elif (isinstance(eps, int) and not isinstance(eps, bool)
              and eps == 0 and mx > 0):
            errs.append(f"max_actions_in_episode ({mx}) with zero "
                        f"episodes")
    need("in_flight", bool)
    actions = need("actions", list)
    if isinstance(actions, list):
        if isinstance(total, int) and not isinstance(total, bool) \
                and len(actions) > total:
            errs.append(f"actions lists {len(actions)} entries but "
                        f"actions_total is {total} (the detail list "
                        f"is bounded, the counts are exact)")
        for i, a in enumerate(actions):
            if not isinstance(a, dict):
                errs.append(f"actions[{i}] is not an object")
                continue
            k = a.get("kind")
            if k not in RECOVERY_ACTION_KINDS:
                errs.append(f"actions[{i}].kind must be one of "
                            f"{RECOVERY_ACTION_KINDS}, got {k!r}")
            ep = a.get("episode")
            if ep is None:
                # an action taken before any episode opened (the
                # unwinding/correction case) carries a null episode
                pass
            elif not isinstance(ep, int) or isinstance(ep, bool) \
                    or ep < 1:
                errs.append(f"actions[{i}].episode must be null or "
                            f"an int >= 1, got {ep!r}")
            elif isinstance(eps, int) and not isinstance(eps, bool) \
                    and ep > eps:
                errs.append(f"actions[{i}].episode ({ep}) exceeds "
                            f"episodes ({eps})")
            t = a.get("t_s")
            if (not isinstance(t, numbers.Number)
                    or isinstance(t, bool) or not (t >= 0)):
                errs.append(f"actions[{i}].t_s must be a number >= 0, "
                            f"got {t!r}")
    mttr = need("mttr_s", dict)
    if isinstance(mttr, dict):
        c = mttr.get("count")
        if not isinstance(c, int) or isinstance(c, bool) or c < 0:
            errs.append(f"mttr_s.count must be an int >= 0, got {c!r}")
        for k in ("last", "mean"):
            v = mttr.get(k)
            if v is None:
                if isinstance(c, int) and not isinstance(c, bool) \
                        and c > 0:
                    errs.append(f"mttr_s.{k} is null with count {c}")
                continue
            if (not isinstance(v, numbers.Number)
                    or isinstance(v, bool) or v != v or not (v >= 0)):
                errs.append(f"mttr_s.{k} must be null or a finite "
                            f"number >= 0, got {v!r}")
            elif isinstance(c, int) and not isinstance(c, bool) \
                    and c == 0:
                errs.append(f"mttr_s.{k} is {v} with zero "
                            f"measurements")
    # role extras, validated whenever present
    if "world" in rec:
        v = rec["world"]
        if not isinstance(v, int) or isinstance(v, bool) or v < 1:
            errs.append(f"'world' must be an int >= 1 when present, "
                        f"got {v!r}")
    # preemption fields, validated whenever present
    if "cause" in rec and rec["cause"] is not None:
        if rec["cause"] not in RECOVERY_CAUSES:
            errs.append(f"'cause' must be null or one of "
                        f"{RECOVERY_CAUSES}, got {rec['cause']!r}")
    if "preempted" in rec and not isinstance(rec["preempted"], bool):
        errs.append(f"'preempted' must be a bool when present, got "
                    f"{rec['preempted']!r}")
    if "data_state" in rec and rec["data_state"] is not None:
        ds = rec["data_state"]
        if not isinstance(ds, dict):
            errs.append("'data_state' must be an object when present")
        else:
            for key in ("samples_consumed", "epoch", "cursor"):
                if key in ds:
                    v = ds[key]
                    if (not isinstance(v, int) or isinstance(v, bool)
                            or v < 0):
                        errs.append(f"data_state.{key} must be an int "
                                    f">= 0, got {v!r}")
            sid, ns = ds.get("shard_id"), ds.get("num_shards")
            for key, v in (("shard_id", sid), ("num_shards", ns)):
                if v is not None and (not isinstance(v, int)
                                      or isinstance(v, bool) or v < 0):
                    errs.append(f"data_state.{key} must be an int "
                                f">= 0, got {v!r}")
            if (isinstance(sid, int) and isinstance(ns, int)
                    and not isinstance(sid, bool)
                    and not isinstance(ns, bool) and ns >= 1
                    and not 0 <= sid < ns):
                errs.append(f"data_state.shard_id ({sid}) out of "
                            f"range for num_shards ({ns})")
    for opt in ("recoveries", "max_queue", "base_max_queue"):
        if opt in rec:
            v = rec[opt]
            if not isinstance(v, int) or isinstance(v, bool) or v < 0:
                errs.append(f"{opt!r} must be an int >= 0 when "
                            f"present, got {v!r}")
    if "duration_s" in rec:
        v = rec["duration_s"]
        if (not isinstance(v, numbers.Number) or isinstance(v, bool)
                or not (v >= 0)):
            errs.append(f"'duration_s' must be a number >= 0, got "
                        f"{v!r}")
    try:
        json.dumps(rec)
    except (TypeError, ValueError) as e:
        errs.append(f"record is not JSON-serializable: {e}")
    return errs


# -- trace record schema ----------------------------------------------------

def validate_trace_record(rec: Any) -> List[str]:
    """Schema check for one ``kind: trace`` JSONL record
    (``SpanRecorder.trace_record`` enriched by the exporter): the
    common envelope, a non-empty ``trace_id``, and a non-empty span
    list where every span belongs to the record's trace, carries a
    unique positive ``span_id``, and any ``parent_id`` references an
    EARLIER span id (span ids are allocated in causal order — a child
    pointing at a later or unknown parent means the recorder lost the
    chain, exactly the worker-thread interleaving bug this schema
    exists to catch)."""
    errs: List[str] = []
    if not isinstance(rec, dict):
        return [f"record is {type(rec).__name__}, not an object"]

    def need(key, types):
        return _need(rec, errs, key, types)

    _check_envelope(rec, errs)
    if rec.get("kind") != "trace":
        errs.append(f"kind must be 'trace', got {rec.get('kind')!r}")
    tid = need("trace_id", str)
    if isinstance(tid, str) and not tid:
        errs.append("trace_id must be non-empty")
    spans = need("spans", list)
    n = need("span_count", int)
    if isinstance(spans, list):
        if not spans:
            errs.append("spans must be non-empty (an empty trace is "
                        "not a trace)")
        if isinstance(n, int) and not isinstance(n, bool) \
                and n != len(spans):
            errs.append(f"span_count ({n}) != len(spans) "
                        f"({len(spans)})")
        all_ids = {sp.get("span_id") for sp in spans
                   if isinstance(sp, dict)}
        seen: set = set()
        for i, sp in enumerate(spans):
            if not isinstance(sp, dict):
                errs.append(f"spans[{i}] is not an object")
                continue
            name = sp.get("name")
            if not isinstance(name, str) or not name:
                errs.append(f"spans[{i}].name must be a non-empty "
                            f"string")
            if sp.get("ph") not in ("X", "i"):
                errs.append(f"spans[{i}].ph must be 'X' or 'i', got "
                            f"{sp.get('ph')!r}")
            if not isinstance(sp.get("ts"), numbers.Number):
                errs.append(f"spans[{i}].ts must be a number")
            if isinstance(tid, str) and sp.get("trace_id") != tid:
                errs.append(f"spans[{i}] belongs to trace "
                            f"{sp.get('trace_id')!r}, record is {tid!r}")
            sid = sp.get("span_id")
            if not isinstance(sid, int) or isinstance(sid, bool) \
                    or sid < 1:
                errs.append(f"spans[{i}].span_id must be an int >= 1")
                continue
            if sid in seen:
                errs.append(f"duplicate span_id {sid}")
            seen.add(sid)
            pid = sp.get("parent_id")
            if pid is not None:
                if not isinstance(pid, int) or isinstance(pid, bool):
                    errs.append(f"spans[{i}].parent_id must be an int")
                elif pid >= sid:
                    errs.append(
                        f"spans[{i}] (span_id {sid}) parents on "
                        f"{pid}, which is not causally earlier")
                elif pid not in all_ids:
                    # a parent that is not in the record at all means
                    # the chain's head was lost (e.g. evicted from a
                    # bounded recorder): not a complete trace
                    errs.append(
                        f"spans[{i}] (span_id {sid}) parents on "
                        f"{pid}, which is not in this record")
    try:
        json.dumps(rec)
    except (TypeError, ValueError) as e:
        errs.append(f"record is not JSON-serializable: {e}")
    return errs


_VALIDATORS = {
    "graph_lint": validate_lint_record,
    "graph_lint_summary": validate_lint_record,
    "fleet": validate_fleet_record,
    "trace": validate_trace_record,
    "memory": validate_memory_record,
    "numerics": validate_numerics_record,
    "run": validate_run_record,
    "recovery": validate_recovery_record,
    "sharding": validate_sharding_record,
}


def validate_telemetry_record(rec: Any) -> List[str]:
    """Dispatching validator: a record goes through the schema its
    ``kind`` names — graph-lint findings and their summary
    (``python -m apex_tpu.analysis``), fleet snapshots
    (``Fleet.record``), request traces, cost-model dumps (``kind:
    memory``, ``--memory``), gradient-health dumps
    (``NumericsMonitor.to_record``), run verdicts
    (``RunSupervisor.record``), recovery-controller snapshots
    (``RecoveryLog.record``) and replication ledgers
    (``--sharding``) may
    interleave in one stream.  A record whose ``kind`` is absent or
    unknown is an error: there is no default schema."""
    if not isinstance(rec, dict):
        return [f"record is {type(rec).__name__}, not an object"]
    kind = rec.get("kind")
    validate = _VALIDATORS.get(kind) if isinstance(kind, str) else None
    if validate is None:
        return [f"'kind' must be one of {sorted(_VALIDATORS)}, got "
                f"{kind!r}"]
    return validate(rec)


def validate_telemetry_jsonl(lines: Iterable[str]) -> List[str]:
    """Validate a JSONL stream of the record kinds above."""
    return _validate_jsonl(lines, validate_telemetry_record)


def _validate_jsonl(lines: Iterable[str], validate) -> List[str]:
    errs: List[str] = []
    n = 0
    for i, raw in enumerate(lines, 1):
        raw = raw.strip()
        if not raw:
            continue
        n += 1
        try:
            rec = json.loads(raw)
        except ValueError as e:
            errs.append(f"line {i}: not JSON ({e})")
            continue
        label = rec.get("metric") or rec.get("kind") or "?" \
            if isinstance(rec, dict) else "?"
        for e in validate(rec):
            errs.append(f"line {i} ({label}): {e}")
    if n == 0:
        errs.append("no records found")
    return errs
