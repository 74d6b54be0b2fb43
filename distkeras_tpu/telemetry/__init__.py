"""Unified observability layer: spans, recompile auditing, metrics.

The reference system's observability was wall-clock getters plus the
Spark web UI (SURVEY §5); through PR 1 this repo had grown two disjoint
islands — ``tracing.py`` (training step timers / metric streams) and
``serving/metrics.py`` (latency percentiles). This package is the single
layer both sides publish into. Four pillars:

- **spans** (:mod:`.spans`) — hierarchical host-timeline spans
  (``with span("decode_tick"): ...``) with thread/task-correct parent
  tracking, near-zero overhead when disabled, exported as Chrome-trace
  JSON that Perfetto renders as one timeline per run;
- **recompile auditing** (:mod:`.recompile`) — wrap jitted callables,
  count compiles with the triggering abstract shapes, and arm after
  warmup so a silent retrace becomes a loud :class:`RecompileError`;
- **metrics registry** (:mod:`.registry`) — counter/gauge/histogram
  get-or-create registry every subsystem publishes into, with the ONE
  shared :func:`percentile` definition;
- **exposition** (:mod:`.exposition`) — Prometheus text + JSONL
  snapshots; the serving server serves both via its ``metricsz`` control
  verb, ``run.py`` wires ``--trace-out`` / ``--audit-recompiles``;
- **fleet timeseries** (:mod:`.timeseries`) — the push-plane half:
  registry delta encoding for replica→router telemetry pushes, the
  router-side fold into fleet-merged histograms (bucket-exact fleet
  p99s), and a ring-buffer store of per-window aggregates the SLO
  burn-rate engine queries by metric name and span;
- **request tracing** (:mod:`.request_trace`) — per-request trace ids
  propagated across the serving cluster's processes, per-hop timeline
  records, bounded stores behind the ``tracez`` control verb, and
  one-lane-per-request Chrome export;
- **wide events** (:mod:`.wide_events`) — one canonical flat record
  per finished request in a bounded columnar ring, with a filter /
  group-by / aggregate query engine behind the ``queryz`` verb whose
  percentile aggregates merge bucket-exactly across the fleet;
- **flight recorder** (:mod:`.flight_recorder`) — bounded overwrite
  rings of recent state transitions + request timelines, dumped as a
  replica's "last words" on crash and mined for slow-request exemplars;
- **training health** (:mod:`.training_health`) — the training-side
  peer of the serving stack: per-worker commit staleness histograms
  with exemplars, EASGD center-divergence gauges, goodput (effective
  vs staleness-damped update mass), and the ``statusz`` snapshot
  ``run.py --statusz-out`` writes live;
- **device accounting** (:mod:`.device`) — ``memory_stats()`` probes
  behind a typed "unavailable" sentinel, per-device memory gauges, and
  the promoted ``jax.profiler`` capture (``--profile-out``).
"""

from distkeras_tpu.telemetry.spans import (
    Tracer,
    active_tracer,
    disable_tracing,
    enable_tracing,
    span,
)
from distkeras_tpu.telemetry.recompile import (
    CompileEvent,
    RecompileAuditor,
    RecompileError,
    abstract_signature,
    named_program,
)
from distkeras_tpu.telemetry.registry import (
    DEFAULT_BUCKETS,
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    hist_state_delta,
    hist_state_percentile,
    log_buckets,
    merge_hist_states,
    percentile,
    sanitize_metric_name,
)
from distkeras_tpu.telemetry.timeseries import (
    DeltaEncoder,
    FleetAggregator,
    TimeSeriesStore,
)
from distkeras_tpu.telemetry.exposition import (
    prometheus_text,
    write_snapshot_jsonl,
)
from distkeras_tpu.telemetry.request_trace import (
    TailRetention,
    TimelineRecord,
    TraceStore,
    chrome_trace,
    merge_trace,
    new_trace_id,
)
from distkeras_tpu.telemetry.wide_events import (
    WideEventStore,
    merge_query_results,
)
from distkeras_tpu.telemetry.flight_recorder import (
    FlightRecorder,
    load_flight_dump,
)
from distkeras_tpu.telemetry.training_health import (
    STALENESS_BUCKETS,
    TrainingHealth,
)
from distkeras_tpu.telemetry.device import (
    DeviceMemory,
    all_device_memory,
    device_memory,
    profile_trace,
    publish_memory_gauges,
)

__all__ = [
    "Tracer",
    "span",
    "enable_tracing",
    "disable_tracing",
    "active_tracer",
    "RecompileAuditor",
    "RecompileError",
    "CompileEvent",
    "abstract_signature",
    "named_program",
    "MetricsRegistry",
    "Counter",
    "Gauge",
    "Histogram",
    "percentile",
    "sanitize_metric_name",
    "DEFAULT_BUCKETS",
    "log_buckets",
    "hist_state_delta",
    "hist_state_percentile",
    "merge_hist_states",
    "DeltaEncoder",
    "TimeSeriesStore",
    "FleetAggregator",
    "prometheus_text",
    "write_snapshot_jsonl",
    "new_trace_id",
    "TimelineRecord",
    "TailRetention",
    "TraceStore",
    "WideEventStore",
    "merge_query_results",
    "merge_trace",
    "chrome_trace",
    "FlightRecorder",
    "load_flight_dump",
    "TrainingHealth",
    "STALENESS_BUCKETS",
    "DeviceMemory",
    "device_memory",
    "all_device_memory",
    "publish_memory_gauges",
    "profile_trace",
]
