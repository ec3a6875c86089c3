"""Metrics registry: counters, gauges, fixed-bucket histograms.

Two accumulation domains behind one reporting surface:

- **Host metrics** (:class:`Counter` / :class:`Gauge` / :class:`Histogram`
  owned by a :class:`MetricsRegistry`): thread-safe Python accumulation
  for eager-path instrumentation — serving step latency, data-loader
  wait times, DDP comm accounting.
- **Device metrics** (:class:`DeviceMetrics`): training-step counters
  that live *inside* the jitted step as jnp scalars threaded through the
  step carry.  ``inc`` / ``set`` / ``observe`` are pure jnp ops — zero
  host syncs per step, preserving the amp/scaler.py invariant — and
  ``flush()`` is the single explicit host fetch (one ``jax.device_get``
  of the whole state tree) that folds device totals into host metrics.

Histograms are Prometheus-shaped: fixed upper-bound bucket edges with
``le`` (<=) semantics, a running sum, and a total count; the exporter
emits the cumulative form.
"""

from __future__ import annotations

import bisect
import threading
from typing import Any, Dict, Iterable, Optional, Sequence, Tuple

__all__ = ["Counter", "Gauge", "Histogram", "MetricsRegistry",
           "DeviceMetrics", "get_registry", "set_registry",
           "DEFAULT_LATENCY_BUCKETS", "DEFAULT_MAX_LABEL_SETS",
           "OVERFLOW_LABEL_VALUE"]

# seconds; spans sub-ms kernel dispatches to multi-second compiles
DEFAULT_LATENCY_BUCKETS = (
    0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1,
    0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 30.0)

# cardinality cap: at most this many distinct label sets per metric.
# Label values can be user-supplied strings (tenant ids on the fleet
# serving path) — an unbounded child dict is an OOM with extra steps.
# Past the cap, new label sets fold into a shared overflow child whose
# values are all OVERFLOW_LABEL_VALUE, and the fold is counted on
# ``labels_dropped`` so the totals stay conserved AND accounted.
DEFAULT_MAX_LABEL_SETS = 64
OVERFLOW_LABEL_VALUE = "other"


def _label_key(labels: Dict[str, Any]) -> Tuple[Tuple[str, str], ...]:
    return tuple(sorted((str(k), str(v)) for k, v in labels.items()))


class _Metric:
    kind = "untyped"

    def __init__(self, name: str, help: str = ""):
        self.name = name
        self.help = help
        self._lock = threading.Lock()
        self._children: Dict[Tuple, "_Metric"] = {}
        self.max_label_sets = DEFAULT_MAX_LABEL_SETS
        self._labels_dropped = 0

    def _new_child(self):
        return type(self)(self.name, self.help)

    def labels(self, **labels):
        """Child metric for a label set (e.g. per-dtype comm counters);
        children are exported under the parent's name with the labels.

        Distinct label sets are capped at ``max_label_sets``: once full,
        an unseen set folds into the shared overflow child (every value
        replaced by ``OVERFLOW_LABEL_VALUE``) and ``labels_dropped``
        counts the fold — the increments still land somewhere exported,
        but a flood of user-supplied values (tenant ids) cannot grow
        the registry without bound."""
        key = _label_key(labels)
        with self._lock:
            child = self._children.get(key)
            if child is None:
                if len(self._children) >= self.max_label_sets:
                    self._labels_dropped += 1
                    key = tuple((k, OVERFLOW_LABEL_VALUE)
                                for k, _ in key)
                    child = self._children.get(key)
                if child is None:
                    child = self._new_child()
                    child._label_set = key
                    self._children[key] = child
            return child

    @property
    def labels_dropped(self) -> int:
        """Label sets folded into the overflow child so far."""
        with self._lock:
            return self._labels_dropped

    def children(self):
        with self._lock:
            return dict(self._children)


class Counter(_Metric):
    """Monotonic counter."""
    kind = "counter"

    def __init__(self, name: str, help: str = ""):
        super().__init__(name, help)
        self._value = 0.0

    def inc(self, value: float = 1.0):
        if value < 0:
            raise ValueError(f"counter {self.name} cannot decrease "
                             f"(inc {value})")
        with self._lock:
            self._value += value

    def set_total(self, value: float):
        """Overwrite with an externally-accumulated monotonic total —
        the DeviceMetrics flush path (device counters already hold the
        total; adding would double-count repeated flushes)."""
        with self._lock:
            self._value = float(value)

    @property
    def value(self) -> float:
        with self._lock:
            return self._value


class Gauge(_Metric):
    """Point-in-time value."""
    kind = "gauge"

    def __init__(self, name: str, help: str = ""):
        super().__init__(name, help)
        self._value = 0.0

    def set(self, value: float):
        with self._lock:
            self._value = float(value)

    def inc(self, value: float = 1.0):
        with self._lock:
            self._value += value

    @property
    def value(self) -> float:
        with self._lock:
            return self._value


class Histogram(_Metric):
    """Fixed-bucket histogram with Prometheus ``le`` (<=) edge semantics:
    an observation exactly on an edge lands in that edge's bucket."""
    kind = "histogram"

    def __init__(self, name: str, help: str = "",
                 buckets: Sequence[float] = DEFAULT_LATENCY_BUCKETS):
        super().__init__(name, help)
        edges = tuple(float(e) for e in buckets)
        if list(edges) != sorted(set(edges)):
            raise ValueError(f"histogram {name} buckets must be strictly "
                             f"increasing, got {buckets}")
        if not edges:
            raise ValueError(f"histogram {name} needs at least one bucket")
        self.edges = edges
        # per-bucket (non-cumulative) counts; last slot is the +Inf
        # overflow bucket
        self._counts = [0] * (len(edges) + 1)
        self._sum = 0.0
        self._count = 0
        # summary() memo, invalidated by every write: Engine.stats()
        # builds five summaries per read and routers/fleets read stats
        # far more often than engines observe — recomputing the
        # bucket-walk quantiles per read was the PR 4 fleet-bench drag.
        # _summary_computes counts actual recomputes (test pin).
        self._summary_cache: Optional[Dict[str, Any]] = None
        self._summary_computes = 0

    def _new_child(self):
        return Histogram(self.name, self.help, self.edges)

    def observe(self, value: float):
        idx = bisect.bisect_left(self.edges, value)
        with self._lock:
            self._counts[idx] += 1
            self._sum += value
            self._count += 1
            self._summary_cache = None

    def _restore(self, counts: Sequence[float], total: float):
        """Overwrite from externally-accumulated totals (DeviceMetrics
        flush); ``counts`` is per-bucket non-cumulative incl. overflow."""
        if len(counts) != len(self._counts):
            raise ValueError(
                f"histogram {self.name} expects {len(self._counts)} "
                f"bucket counts, got {len(counts)}")
        with self._lock:
            self._counts = [int(c) for c in counts]
            self._count = sum(self._counts)
            self._sum = float(total)
            self._summary_cache = None

    @property
    def count(self) -> int:
        with self._lock:
            return self._count

    @property
    def sum(self) -> float:
        with self._lock:
            return self._sum

    def cumulative(self) -> Dict[str, int]:
        """{le_edge_or_'+Inf': cumulative count} — the exposition form."""
        with self._lock:
            out, acc = {}, 0
            for e, c in zip(self.edges, self._counts):
                acc += c
                out[repr(e)] = acc
            out["+Inf"] = acc + self._counts[-1]
            return out

    def _percentile_locked(self, q: float) -> Optional[float]:
        # caller holds self._lock
        if self._count == 0:
            return None
        target = q * self._count
        acc, lo = 0.0, 0.0
        for e, c in zip(self.edges, self._counts):
            if acc + c >= target and c > 0:
                frac = (target - acc) / c
                return lo + frac * (e - lo)
            acc += c
            lo = e
        return self.edges[-1]

    def percentile(self, q: float) -> Optional[float]:
        """Bucket-interpolated quantile estimate (q in [0, 1]); None when
        empty.  Values past the last edge clamp to it — fixed buckets
        cannot resolve the overflow tail."""
        with self._lock:
            return self._percentile_locked(q)

    def summary(self) -> Dict[str, Any]:
        """{count, sum, mean, p50, p99}.  Memoized between writes: a
        read-heavy consumer (``Engine.stats()`` under a fleet router)
        pays the two bucket walks once per observation, not once per
        read."""
        with self._lock:
            if self._summary_cache is None:
                count, total = self._count, self._sum
                self._summary_cache = {
                    "count": count, "sum": total,
                    "mean": (total / count) if count else None,
                    "p50": self._percentile_locked(0.5),
                    "p99": self._percentile_locked(0.99)}
                self._summary_computes += 1
            return dict(self._summary_cache)


class MetricsRegistry:
    """Named metric store; ``counter``/``gauge``/``histogram`` are
    get-or-create (a kind clash on an existing name raises)."""

    def __init__(self):
        self._lock = threading.Lock()
        self._metrics: Dict[str, _Metric] = {}

    def _get(self, cls, name: str, help: str, **kwargs):
        with self._lock:
            m = self._metrics.get(name)
            if m is None:
                m = cls(name, help, **kwargs)
                self._metrics[name] = m
            elif not isinstance(m, cls):
                raise ValueError(f"metric {name!r} already registered as "
                                 f"{m.kind}, requested {cls.kind}")
            return m

    def counter(self, name: str, help: str = "") -> Counter:
        return self._get(Counter, name, help)

    def gauge(self, name: str, help: str = "") -> Gauge:
        return self._get(Gauge, name, help)

    def histogram(self, name: str, help: str = "",
                  buckets: Sequence[float] = DEFAULT_LATENCY_BUCKETS
                  ) -> Histogram:
        return self._get(Histogram, name, help, buckets=buckets)

    def get(self, name: str) -> Optional[_Metric]:
        with self._lock:
            return self._metrics.get(name)

    def collect(self) -> Iterable[_Metric]:
        with self._lock:
            return list(self._metrics.values())

    def snapshot(self) -> Dict[str, Any]:
        """Plain-python view: counters/gauges as numbers, histograms as
        their summary dict."""
        out = {}
        for m in self.collect():
            out[m.name] = (m.summary() if isinstance(m, Histogram)
                           else m.value)
        return out

    def clear(self):
        with self._lock:
            self._metrics.clear()


_global_registry = MetricsRegistry()


def get_registry() -> MetricsRegistry:
    """The process-wide default registry (DDP comm accounting, data
    loader timings, and DeviceMetrics flushes land here unless given an
    explicit registry)."""
    return _global_registry


def set_registry(registry: MetricsRegistry) -> MetricsRegistry:
    global _global_registry
    prev, _global_registry = _global_registry, registry
    return prev


class DeviceMetrics:
    """Device-resident metric set for jitted training steps.

    The state returned by :meth:`init` is a flat ``{name: jnp.ndarray}``
    dict — a pytree that rides the step carry like optimizer state.  All
    mutators are pure (state in, new state out) and lower to a handful
    of scalar adds, so a telemetry-enabled step emits **zero** host
    transfers (pinned by tests/test_step_graph_audit.py); the one host
    fetch is the explicit :meth:`flush`.

        dm = DeviceMetrics(counters=("steps", "overflows"),
                           gauges=("loss_scale",))
        tele = dm.init()
        # ... inside the jitted step:
        tele = dm.inc(tele, "steps")
        tele = dm.inc(tele, "overflows", info["found_inf"])
        tele = dm.set(tele, "loss_scale", info["loss_scale"])
        # ... on the host, every N steps:
        vals = dm.flush(tele)          # ONE device_get; updates registry
    """

    def __init__(self, counters: Sequence[str] = (),
                 gauges: Sequence[str] = (),
                 histograms: Optional[Dict[str, Sequence[float]]] = None,
                 prefix: str = "", registry: Optional[MetricsRegistry] = None):
        self.counters = tuple(counters)
        self.gauges = tuple(gauges)
        self.histograms = {k: tuple(float(e) for e in v)
                           for k, v in (histograms or {}).items()}
        names = (list(self.counters) + list(self.gauges)
                 + list(self.histograms))
        if len(set(names)) != len(names):
            raise ValueError(f"duplicate metric names: {sorted(names)}")
        if not names:
            raise ValueError("DeviceMetrics needs at least one metric")
        self.prefix = prefix
        self.registry = registry

    def init(self) -> Dict[str, Any]:
        import jax.numpy as jnp
        state: Dict[str, Any] = {}
        for n in self.counters:
            state[n] = jnp.zeros((), jnp.float32)
        for n in self.gauges:
            state[n] = jnp.zeros((), jnp.float32)
        for n, edges in self.histograms.items():
            # [per-bucket counts incl. +Inf overflow..., running sum]
            state[n] = jnp.zeros((len(edges) + 2,), jnp.float32)
        return state

    def _check(self, name: str, kinds: Tuple[str, ...]):
        pools = {"counter": self.counters, "gauge": self.gauges,
                 "histogram": self.histograms}
        for k in kinds:
            if name in pools[k]:
                return
        raise KeyError(f"{name!r} is not a device {'/'.join(kinds)} "
                       f"(counters={self.counters}, gauges={self.gauges}, "
                       f"histograms={tuple(self.histograms)})")

    def inc(self, state: Dict[str, Any], name: str,
            value: Any = 1.0) -> Dict[str, Any]:
        import jax.numpy as jnp
        self._check(name, ("counter",))
        return {**state,
                name: state[name] + jnp.asarray(value, jnp.float32)}

    def set(self, state: Dict[str, Any], name: str,
            value: Any) -> Dict[str, Any]:
        import jax.numpy as jnp
        self._check(name, ("gauge",))
        return {**state, name: jnp.asarray(value, jnp.float32)}

    def observe(self, state: Dict[str, Any], name: str,
                value: Any) -> Dict[str, Any]:
        import jax.numpy as jnp
        self._check(name, ("histogram",))
        edges = jnp.asarray(self.histograms[name], jnp.float32)
        v = jnp.asarray(value, jnp.float32)
        idx = jnp.searchsorted(edges, v, side="left")  # le semantics
        buf = state[name].at[idx].add(1.0).at[-1].add(v)
        return {**state, name: buf}

    def flush(self, state: Dict[str, Any],
              registry: Optional[MetricsRegistry] = None) -> Dict[str, Any]:
        """ONE host fetch of the whole state tree; folds totals into the
        host registry (counters ``set_total``, gauges ``set``, histogram
        counts restored) and returns the plain-python values."""
        import jax
        import numpy as np
        reg = registry or self.registry or get_registry()
        host = jax.device_get(state)
        out: Dict[str, Any] = {}
        for n in self.counters:
            v = float(host[n])
            reg.counter(self.prefix + n).set_total(v)
            out[n] = v
        for n in self.gauges:
            v = float(host[n])
            reg.gauge(self.prefix + n).set(v)
            out[n] = v
        for n, edges in self.histograms.items():
            buf = np.asarray(host[n])
            counts, total = buf[:-1], float(buf[-1])
            reg.histogram(self.prefix + n,
                          buckets=edges)._restore(counts, total)
            out[n] = {"counts": [int(c) for c in counts], "sum": total}
        return out
