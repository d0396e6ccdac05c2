"""Serving observability: metrics registry, request-lifecycle tracing,
and latency/throughput reporting shared by the live engine and the
simulator.

* ``repro.obs.schema`` — the ONE metric/event vocabulary (names, labels,
  histogram boundaries, step-record fields). Both emitters are
  schema-strict; a name outside the schema raises at the call site.
* ``repro.obs.metrics`` — dependency-free ``MetricsRegistry`` (monotone
  counters, gauges, fixed-boundary histograms) with Prometheus-text and
  JSON exporters.
* ``repro.obs.events`` — bounded structured event log: every request span
  point carries the monotone step index and a wall-clock timestamp.
* ``repro.obs.observer`` — ``Observability``, the facade an emitter holds
  (registry + events + rolling per-step audit records + ``span``, the
  step's phases as profiler annotations with their host seconds);
  ``NullObs`` is the disabled twin for overhead A/Bs. ``compile_count``
  counts the executables the process built.
* ``repro.obs.report`` — TTFT/TPOT/queue/E2E percentiles and the
  latency-vs-throughput tables matching the paper's evaluation, from a
  dump of either emitter.
"""
from . import schema
from .events import EventLog
from .metrics import Counter, Gauge, Histogram, MetricsRegistry, \
    merge_snapshots
from .observer import Observability, NullObs, compile_count
from .report import build_report, format_report, latency_throughput_table

__all__ = ["schema", "EventLog", "Counter", "Gauge", "Histogram",
           "MetricsRegistry", "merge_snapshots", "Observability", "NullObs",
           "compile_count", "build_report", "format_report",
           "latency_throughput_table"]
