"""Observability: span tracing, metrics, and Perfetto export.

The cross-cutting layer ISSUE 4 adds over the two performance-critical
subsystems (planned dispatch with its fused launches, paged decode):

* :mod:`.trace` — structured span tracer (nested spans, categories,
  injectable clock);
* :mod:`.metrics` — counters/gauges/histograms with a stable JSON
  snapshot schema;
* :mod:`.export` — Chrome/Perfetto rendering of either a tracer's
  unified timeline or a timed schedule;
* :mod:`.attribution` — the run doctor's measured critical-path
  reconstruction and compute/transfer/dispatch/idle makespan split;
* :mod:`.drift` — per-task predicted-vs-measured cost-model audit;
* :mod:`.memprof` — measured per-device HBM timelines with watermark
  attribution (the memory half of the doctor);
* :mod:`.memdrift` — measured-vs-predicted memory peaks, per device and
  per task, with the near-OOM headroom warnings;
* :mod:`.reqlog` — per-request lifecycle records (queue-wait, TTFT,
  token-delivery series, e2e) with the ``dls.requests/1`` schema;
* :mod:`.slo` — sliding-window SLO accounting (windowed p50/p95/p99,
  goodput vs raw throughput, breach gate) over the request log;
* :mod:`.flight` — always-on bounded ring-buffer flight recorder that
  dumps trace + request log on SLO breach / near-OOM / straggler /
  soak health breach / sustained chunk-budget stalls;
* :mod:`.reqtrace` — per-request waterfall tracks (cause-stamped wait
  spans, compute spans, lifecycle instants, interference flow arrows)
  re-projected from the engine's hoisted clock reads;
* :mod:`.interference` — the request doctor's exact latency
  attribution: per-request e2e decomposed into wait/compute buckets
  that tile it to ≤1e-9, with ranked aggressor→victim pairs;
* :mod:`.clockutil` — the ONE injected-or-default timebase decision
  every module above routes its ``clock`` argument through;
* :mod:`.timeseries` — bounded-memory time series (fixed capacity,
  deterministic 2:1 decimation) with the ``dls.timeseries/1`` schema,
  Theil–Sen trend estimation, and the soak sampler;
* :mod:`.health` — the soak doctor's trend gate: leak/degradation
  detectors (HLT001–HLT006) over time series, ``exceeds``-style report.

Spans, counters and per-request records are opt-in.  Two ways to turn
them on:

* **Explicit**: pass ``trace=Tracer()`` / ``metrics=MetricsRegistry()``
  to ``DeviceBackend.execute`` (or the paged decode engine), then
  ``export.export_perfetto(tracer, path)``.
* **Ambient**: set ``DLS_TRACE=1`` and every ``execute``/engine in the
  process records into one shared tracer + registry
  (:func:`ambient_tracer` / :func:`ambient_metrics`); benches and
  ``eval/capture_artifacts.py`` attach the registry snapshot to their
  artifacts, and the ``execute`` CLI exports the trace on exit.

With the env var unset and no explicit objects passed, the ambient
getters return ``None`` and the per-launch and per-token paths skip all
recording (``if tracer is not None`` guards).

One thing is always on: every ``DeviceBackend.execute`` call tiles its
own wall time into leaf phases (``DeviceReport.dispatch_phases``) and
observes each once into :func:`process_metrics`, the process-wide
registry an operator or a benchmark reads without holding the report.
"""

from __future__ import annotations

from typing import Optional

from ..utils.config import env_flag
from .attribution import Attribution, attribute_run, attribute_trace
from .clockutil import Clock, default_clock, resolve_clock
from .drift import DriftReport, compute_drift
from .flight import FlightRecorder, RingTracer, TeeTracer
from .interference import (
    InterferenceReport,
    attribute_requests,
    events_from_perfetto,
)
from .health import (
    Detector,
    HealthFinding,
    HealthMonitor,
    HealthReport,
    default_detectors,
    report_from_soak_artifact,
)
from .fleet import (
    FleetHealthReport,
    fleet_detectors,
    merge_snapshots,
    report_from_fleet_artifact,
    validate_fleet_health,
)
from .memdrift import MemDriftReport, compute_mem_drift
from .memprof import MemoryProfiler
from .metrics import MetricsRegistry
from .reqlog import (
    RequestLog,
    RequestRecord,
    stitch_logical_chains,
    summarize_request_log,
    validate_request_log,
)
from .reqtrace import RequestTraceRecorder, base_rid, request_track
from .slo import SLOPolicy, SLOReport, evaluate_slo
from .timeseries import (
    Series,
    SoakSampler,
    TimeSeriesStore,
    load_timeseries,
    save_timeseries,
    snapshot_at,
    theil_sen_slope,
    validate_timeseries,
)
from .trace import HOST_TRACK, PhaseClock, Tracer, annotate

_ambient_tracer: Optional[Tracer] = None
_ambient_metrics: Optional[MetricsRegistry] = None
_ambient_flight: Optional[FlightRecorder] = None
_process_metrics = MetricsRegistry()


def trace_enabled() -> bool:
    """True when ``DLS_TRACE`` requests ambient observability."""
    return env_flag("DLS_TRACE")


def ambient_tracer() -> Optional[Tracer]:
    """The process-wide tracer when ``DLS_TRACE`` is set, else None.
    Created lazily on first use; one tracer accumulates every run in
    the process so the export is a single unified timeline."""
    global _ambient_tracer
    if not trace_enabled():
        return None
    if _ambient_tracer is None:
        _ambient_tracer = Tracer()
    return _ambient_tracer


def ambient_metrics() -> Optional[MetricsRegistry]:
    """The process-wide registry when ``DLS_TRACE`` is set, else None."""
    global _ambient_metrics
    if not trace_enabled():
        return None
    if _ambient_metrics is None:
        _ambient_metrics = MetricsRegistry()
    return _ambient_metrics


def process_metrics() -> MetricsRegistry:
    """The process-wide registry that is always on, ``DLS_TRACE`` or not:
    per ``execute`` call one observation into each ``execute.phase.*_s``
    histogram, into ``execute.wall_s`` and (planned calls) into
    ``execute.tasks_per_launch``; the gauge ``compile.group_structures``
    counts the fused-launch executables built.  Nothing per launch or per
    token is recorded here; the per-edge transfer counters stay behind
    the explicit / ambient registry."""
    return _process_metrics


def flight_enabled() -> bool:
    """True when ``DLS_FLIGHT`` requests the ambient flight recorder."""
    return env_flag("DLS_FLIGHT")


def ambient_flight() -> Optional[FlightRecorder]:
    """The process-wide flight recorder when ``DLS_FLIGHT`` is set, else
    None.  Same discipline as :func:`ambient_tracer`: with the env var
    unset and no explicit recorder passed, engine hot paths see None and
    do zero work — there is no no-op recorder object."""
    global _ambient_flight
    if not flight_enabled():
        return None
    if _ambient_flight is None:
        _ambient_flight = FlightRecorder()
    return _ambient_flight


def reset_ambient() -> None:
    """Drop the ambient tracer/registry/flight and empty the process
    registry (tests; fresh CLI legs)."""
    global _ambient_tracer, _ambient_metrics, _ambient_flight
    global _process_metrics
    _ambient_tracer = None
    _ambient_metrics = None
    _ambient_flight = None
    _process_metrics = MetricsRegistry()


__all__ = [
    "Attribution",
    "Clock",
    "Detector",
    "DriftReport",
    "FleetHealthReport",
    "FlightRecorder",
    "HOST_TRACK",
    "HealthFinding",
    "HealthMonitor",
    "HealthReport",
    "InterferenceReport",
    "MemDriftReport",
    "MemoryProfiler",
    "MetricsRegistry",
    "PhaseClock",
    "RequestLog",
    "RequestRecord",
    "RequestTraceRecorder",
    "RingTracer",
    "SLOPolicy",
    "SLOReport",
    "Series",
    "SoakSampler",
    "TeeTracer",
    "TimeSeriesStore",
    "Tracer",
    "ambient_flight",
    "ambient_metrics",
    "ambient_tracer",
    "annotate",
    "attribute_requests",
    "attribute_run",
    "attribute_trace",
    "base_rid",
    "compute_drift",
    "compute_mem_drift",
    "default_clock",
    "default_detectors",
    "evaluate_slo",
    "events_from_perfetto",
    "fleet_detectors",
    "flight_enabled",
    "load_timeseries",
    "merge_snapshots",
    "process_metrics",
    "report_from_fleet_artifact",
    "report_from_soak_artifact",
    "validate_fleet_health",
    "request_track",
    "reset_ambient",
    "resolve_clock",
    "save_timeseries",
    "snapshot_at",
    "stitch_logical_chains",
    "summarize_request_log",
    "theil_sen_slope",
    "trace_enabled",
    "validate_request_log",
    "validate_timeseries",
]
