"""apex_tpu.observability — telemetry the library records about itself.

What the package holds (docs/observability.md), by module:

- ``metrics`` — :class:`MetricsRegistry` of counters / gauges /
  fixed-bucket histograms for host-side instrumentation, and
  :class:`DeviceMetrics` for training-step counters that accumulate as
  jnp arrays *inside* the jitted step (no host sync per step; one
  explicit fetch at ``flush()``).
- ``phases`` — the vocabulary of ``jax.named_scope`` names the training
  step and the paged engine's tick carry; ``benchmark/`` reads
  per-phase device time from them.
- ``tracing`` — :class:`SpanRecorder` wall-clock spans and events on
  ``utils.profiler``'s ranges, request-scoped trace ids with
  thread-correct parentage, Chrome-trace and JSONL export,
  ``kind: trace`` records.
- ``flightrec`` — :class:`EventRing`, a bounded ring of operational
  transitions (breaker, failover, drain, stall, scaler skips) dumped
  on fault.
- ``compilation`` — the trace/compile ledger over every instrumented
  jit entry: abstract argument signatures, stage times, persistent-cache
  hits and misses, the retrace-cause differ, ``compiled_text`` for the
  phase join, ``/compilez``.
- ``costmodel`` / ``memory`` — the analytic FLOPs/bytes model over
  jaxprs and the compiled memory plans, static liveness and live-array
  gauges behind ``kind: memory`` records and the ``flop-accounting`` /
  ``memory-budget`` lint rules.
- ``numerics`` — device-resident gradient-health telemetry (per-layer
  nonfinite counts, abs-max, norms, underflow share, overflow
  attribution, the cross-replica divergence digest), in-graph with no
  host sync; ``kind: numerics`` records.
- ``supervisor`` — the host-side training-run supervisor (stall, loss
  spike, NaN, throughput regression, replica divergence,
  recompilation storm) over each step's already-flushed signals;
  ``wrap_step`` is an identity; ``kind: run`` records.
- ``server`` — a stdlib ``http.server`` serving ``/healthz``,
  ``/metricsz`` (Prometheus exposition), ``/statusz``, ``/flightz``,
  ``/tracez``, ``/compilez``, ``/tenantz`` off a live
  registry / ring / recorder.
- ``exporters`` — schema-versioned JSONL, Prometheus text exposition,
  and one validator per record ``kind`` the library produces.

Speed is not measured here: the benchmark is ``benchmark/run.py`` over
``BENCHMARK.json``, and it reads this package's scopes, spans, counters
and compile ledger.
"""

from .metrics import (Counter, Gauge, Histogram, MetricsRegistry,
                      DeviceMetrics, get_registry, set_registry,
                      DEFAULT_LATENCY_BUCKETS)
from .tracing import (SpanRecorder, get_recorder, set_recorder, span,
                      event, export_chrome_trace, export_jsonl,
                      new_trace_id, current_trace, maybe_span,
                      maybe_event)
from .flightrec import EventRing, get_ring, set_ring
from .exporters import (SCHEMA_VERSION, JsonlExporter, prometheus_text,
                        host_info)
from .costmodel import Cost, jaxpr_cost
from .memory import (memory_plan, jaxpr_live_bytes, live_array_bytes,
                     record_live_arrays)
from .numerics import (NumericsMonitor, divergence_check,
                       divergence_digest, digest_comm_plan)
from .compilation import (CompilationLedger, instrumented_jit,
                          diff_signatures, get_ledger, set_ledger)
from .server import ObservabilityServer
from .supervisor import RunSupervisor, SupervisorConfig
from . import metrics
from . import tracing
from . import flightrec
from . import exporters
from . import costmodel
from . import memory
from . import numerics
from . import compilation
from . import server
from . import supervisor

__all__ = [
    "Counter", "Gauge", "Histogram", "MetricsRegistry", "DeviceMetrics",
    "get_registry", "set_registry", "DEFAULT_LATENCY_BUCKETS",
    "SpanRecorder", "get_recorder", "set_recorder", "span", "event",
    "export_chrome_trace", "export_jsonl",
    "new_trace_id", "current_trace", "maybe_span", "maybe_event",
    "EventRing", "get_ring", "set_ring",
    "SCHEMA_VERSION", "JsonlExporter", "prometheus_text", "host_info",
    "Cost", "jaxpr_cost",
    "memory_plan", "jaxpr_live_bytes", "live_array_bytes",
    "record_live_arrays",
    "NumericsMonitor", "divergence_check", "divergence_digest",
    "digest_comm_plan",
    "CompilationLedger", "instrumented_jit", "diff_signatures",
    "get_ledger", "set_ledger",
    "ObservabilityServer", "RunSupervisor", "SupervisorConfig",
    "metrics", "tracing", "flightrec",
    "exporters", "costmodel", "memory", "numerics", "server",
    "supervisor", "compilation",
]
