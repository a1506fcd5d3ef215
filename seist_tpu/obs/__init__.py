"""Unified telemetry plane (docs/OBSERVABILITY.md).

Three pillars, one import:

* **Metrics bus** (:mod:`~seist_tpu.obs.bus`): process-wide counters /
  gauges / histograms + the span API every timing path in the repo is
  deduplicated onto; Prometheus text exposition + JSONL event log.
* **Region scopes** (:mod:`~seist_tpu.obs.scopes`): which part of the step
  owns a device op of a profiler trace, from the optimized HLO's op_names;
  **compile accounting** (:mod:`~seist_tpu.obs.jit_events`): JAX's
  monitoring events as bus counters.
* **Per-op attribution** (:mod:`~seist_tpu.obs.attribution`): analytic
  jaxpr walk + roofline time shares behind BENCH's ``step_breakdown`` (a
  model; the region scopes are the measurement).
* **Flight recorder** (:mod:`~seist_tpu.obs.flight`): ring buffer of the
  last N steps' metrics/spans, dumped to JSON on every death path.
* **Distributed request tracing** (:mod:`~seist_tpu.obs.trace`):
  W3C-``traceparent`` IDs propagated across the serving fleet, per-process
  span rings with tail-based retention, ``GET /traces`` exposition.
* **Fleet metrics aggregation** (:mod:`~seist_tpu.obs.fleet`): merge N
  replicas' bus snapshots into one ``GET /fleet/metrics`` pane.

``obs/http.py`` serves the bus on the train worker's ``--metrics-port``.
"""

from seist_tpu.obs import flight, trace
from seist_tpu.obs.attribution import attribute_step, jaxpr_op_costs
from seist_tpu.obs.bus import (
    BUS,
    EventLog,
    MetricsBus,
    monotonic,
    register_default_collectors,
    render_prometheus,
    span_frame,
    stopwatch,
    timed_iter,
)
from seist_tpu.obs.flight import FlightRecorder
from seist_tpu.obs.http import (
    MetricsHTTPServer,
    ProfileTrigger,
    start_metrics_server,
)
from seist_tpu.obs.trace import RequestTrace, TraceBuffer

__all__ = [
    "BUS",
    "EventLog",
    "FlightRecorder",
    "MetricsBus",
    "MetricsHTTPServer",
    "ProfileTrigger",
    "RequestTrace",
    "TraceBuffer",
    "attribute_step",
    "flight",
    "jaxpr_op_costs",
    "monotonic",
    "register_default_collectors",
    "render_prometheus",
    "span_frame",
    "start_metrics_server",
    "stopwatch",
    "timed_iter",
    "trace",
]
