"""Observability: spans, step timing, hang watchdog, in-graph bucket
tracing, device-trace overlap analysis, and structured metrics export.

The package splits by layer — :mod:`~bagua_tpu.observability.core` is the
host-side primitives (spans/timer/watchdog/profiler),
:mod:`~bagua_tpu.observability.annotations` the in-graph labels,
:mod:`~bagua_tpu.observability.trace_analysis` the offline trace parser,
:mod:`~bagua_tpu.observability.metrics` the registry/JSONL/Prometheus
plumbing, and :mod:`~bagua_tpu.observability.telemetry` the hub tying them
to the engine — but the public names all live here.
"""

from bagua_tpu.observability.core import (
    ProfilerSession,
    SpanRecorder,
    StepTimer,
    Watchdog,
)
from bagua_tpu.observability.annotations import (
    EXCHANGE_PREFIX,
    STEP_PREFIX,
    bucket_scope,
    host_span,
    mp_scope,
    parse_exchange_label,
    parse_mp_label,
    parse_step_phase,
    step_scope,
)
from bagua_tpu.observability.metrics import (
    Counter,
    Gauge,
    Histogram,
    JsonlSink,
    MetricsRegistry,
    rotated_metrics_files,
    validate_metrics_event,
    validate_metrics_file,
)
from bagua_tpu.observability.telemetry import RecompileDetector, Telemetry
from bagua_tpu.observability.attribution import (
    BUDGET_COMPONENTS,
    BudgetModel,
    StepBudget,
)
from bagua_tpu.observability.regression import Cusum, RegressionSentinel
from bagua_tpu.observability.goodput import (
    GoodputLedger,
    GoodputMeter,
    flops_from_cost_analysis,
    model_flops_per_sample,
    predicted_wire_time,
    register_model_flops,
)
from bagua_tpu.observability.health import (
    HealthConfig,
    HealthMonitor,
    PrecisionDemotionAction,
    SnapshotOnAnomalyAction,
    health_scalars,
)
from bagua_tpu.observability.aggregate import (
    GangAggregator,
    GangView,
    StepSummary,
    straggler_score,
    summarize_telemetry,
)
from bagua_tpu.observability.flight_recorder import (
    FLIGHT_DUMP_SCHEMA,
    HANG_REPORT_SCHEMA,
    VERDICTS,
    FlightRecorder,
    build_hang_report,
    capture_program,
    flight_dump_path,
    push_flight_digest,
    validate_flight_dump,
    validate_hang_report,
)
from bagua_tpu.observability.trace_analysis import (
    COLLECTIVE_OPS,
    analyze_trace,
    find_capture,
    hlo_op_labels,
    load_trace_events,
    summarize_capture,
)
from bagua_tpu.observability.tracing import (
    SPAN_SCHEMA,
    Span,
    Tracer,
    client_span,
    format_traceparent,
    get_global_tracer,
    new_span_id,
    new_trace_id,
    parse_traceparent,
    set_global_tracer,
    validate_span,
)

__all__ = [
    # core
    "ProfilerSession",
    "SpanRecorder",
    "StepTimer",
    "Watchdog",
    # annotations
    "EXCHANGE_PREFIX",
    "STEP_PREFIX",
    "bucket_scope",
    "host_span",
    "mp_scope",
    "step_scope",
    "parse_exchange_label",
    "parse_mp_label",
    "parse_step_phase",
    # metrics
    "Counter",
    "Gauge",
    "Histogram",
    "JsonlSink",
    "MetricsRegistry",
    "rotated_metrics_files",
    "validate_metrics_event",
    "validate_metrics_file",
    # telemetry
    "RecompileDetector",
    "Telemetry",
    # budget attribution / regression sentinel
    "BUDGET_COMPONENTS",
    "BudgetModel",
    "StepBudget",
    "Cusum",
    "RegressionSentinel",
    # goodput / MFU
    "GoodputLedger",
    "GoodputMeter",
    "flops_from_cost_analysis",
    "model_flops_per_sample",
    "predicted_wire_time",
    "register_model_flops",
    # health guardrail
    "HealthConfig",
    "HealthMonitor",
    "PrecisionDemotionAction",
    "SnapshotOnAnomalyAction",
    "health_scalars",
    # gang aggregation
    "GangAggregator",
    "GangView",
    "StepSummary",
    "straggler_score",
    "summarize_telemetry",
    # flight recorder / hang forensics
    "FLIGHT_DUMP_SCHEMA",
    "HANG_REPORT_SCHEMA",
    "VERDICTS",
    "FlightRecorder",
    "build_hang_report",
    "capture_program",
    "flight_dump_path",
    "push_flight_digest",
    "validate_flight_dump",
    "validate_hang_report",
    # trace analysis
    "COLLECTIVE_OPS",
    "analyze_trace",
    "find_capture",
    "hlo_op_labels",
    "load_trace_events",
    "summarize_capture",
    # distributed tracing
    "SPAN_SCHEMA",
    "Span",
    "Tracer",
    "client_span",
    "format_traceparent",
    "get_global_tracer",
    "new_span_id",
    "new_trace_id",
    "parse_traceparent",
    "set_global_tracer",
    "validate_span",
]
