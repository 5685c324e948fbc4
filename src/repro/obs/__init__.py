"""repro.obs — process-wide observability: traces, metrics, exporters.

The paper's whole point is throughput (O(NKD²) riding a live stream), so
the system around it must be able to *measure* itself in-process: this
package is the one vertical layer every tier wears — ``StreamRuntime``
chunk ingest/lifecycle/drift, ``FleetCoordinator`` consolidation + scale
events, the ``ScoringFrontend`` read path (per-request latency, QPS,
snapshot staleness) and the ``api.Mixture`` entry points.

  trace.py     structured spans: nested, thread-safe, ~zero-cost when
               disabled; JSONL + Chrome trace_event exports; optional
               jax.profiler.TraceAnnotation bridge into XLA profiles
  metrics.py   counters / gauges / fixed-log-bucket latency histograms
               (exact bucket p50/p99/p999; mergeable + delta-able across
               threads, replicas and autoscaler decision windows via the
               immutable-snapshot-swap pattern of fleet/telemetry.py)
  registry.py  get-or-create metric registry (one per export surface)
  export.py    Prometheus text exposition (+ HTTP server for scrapes),
               JSON metric dumps, and the shared ``to_json`` envelope
               (schema_version) every BENCH_*/telemetry file goes through
  prof.py      profiling harness: compile-excluded donation-safe wall
               timing, HLO-derived roofline terms against the
               ``repro.devices`` peaks — feeds ``stream.costmodel``'s
               calibration

The serving→autoscaler loop closes through here: ``ScoringFrontend``
records request latency into a mergeable histogram, the coordinator diffs
its cumulative snapshots between consolidation boundaries, and
``fleet.autoscale`` treats the windowed p99/QPS as one more scale-up
pressure term (see ``autoscale.ServingSignal``).
"""
from repro.obs import export, metrics, prof, registry, trace
from repro.obs.export import metrics_dict, prometheus_text, to_json
from repro.obs.metrics import (Counter, Gauge, HistSnapshot, Histogram,
                               LATENCY_BOUNDS, log_bounds)
from repro.obs.registry import Registry, default_registry, set_default
from repro.obs.trace import SpanRecord, Tracer, get_tracer, span

__all__ = [
    "Counter", "Gauge", "HistSnapshot", "Histogram", "LATENCY_BOUNDS",
    "Registry", "SpanRecord", "Tracer", "default_registry", "export",
    "get_tracer", "log_bounds", "metrics", "metrics_dict",
    "prof", "prometheus_text", "registry", "set_default", "span",
    "to_json", "trace",
]
