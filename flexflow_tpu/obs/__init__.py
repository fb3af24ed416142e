"""FlexFlow-TPU observability: serving telemetry, metrics, calibration.

The serving stack (RequestManager / InferenceManager /
PipelinedInferenceManager / serve_with_arrivals) is instrumented behind one
:class:`Telemetry` handle — a trace recorder (Chrome/Perfetto export), a
metrics registry, and a predicted-vs-measured calibration ledger.  Host-side
only by construction: telemetry never enters a jitted program, so serve
outputs are bit-identical with it on or off.  Beside the handle, and on
without one: the scheduler's :class:`TickJournal` (one bounded record per
tick; the slow-tick report).  See README "Observability".
"""

from .calibration import (
    DEFAULT_STORE_PATH,
    CalibrationLedger,
    CalibrationStore,
    StoreConfig,
)
from .drift import (
    DriftDetector,
    WorkloadProfile,
    drift_score,
    psi,
)
from .journal import TickJournal
from .memory import (
    KV_OCCUPANCY_HIST,
    MEMORY_GAUGES,
    MemoryLedger,
)
from .metrics import (
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    percentile,
)
from .plan_health import PlanHealthConfig, PlanHealthMonitor, health_score
from .profiler import (
    COMPONENTS,
    NULL_PROFILER,
    TIME_COMPONENT_FIELDS,
    WORK_COUNTERS,
    NullStepProfiler,
    PlanCostCard,
    StepProfiler,
    plan_cost_card,
    profiler_or_null,
)
from .replay import (
    TRACE_VERSION,
    ReplayHarness,
    TrafficTrace,
    TrafficTraceRecorder,
    VirtualClock,
)
from .report import (
    memory_section,
    summarize_events,
    summarize_jsonl,
    time_budget_section,
    under_load_summary,
    validate_jsonl,
)
from .telemetry import (
    NULL_TELEMETRY,
    NullTelemetry,
    Telemetry,
    telemetry_or_null,
)
from .trace import TraceRecorder

__all__ = [
    "Telemetry",
    "NullTelemetry",
    "NULL_TELEMETRY",
    "telemetry_or_null",
    "TraceRecorder",
    "TickJournal",
    "MetricsRegistry",
    "Counter",
    "Gauge",
    "Histogram",
    "percentile",
    "CalibrationLedger",
    "CalibrationStore",
    "StoreConfig",
    "DEFAULT_STORE_PATH",
    "WorkloadProfile",
    "DriftDetector",
    "drift_score",
    "psi",
    "PlanHealthConfig",
    "PlanHealthMonitor",
    "MemoryLedger",
    "MEMORY_GAUGES",
    "KV_OCCUPANCY_HIST",
    "memory_section",
    "summarize_events",
    "summarize_jsonl",
    "time_budget_section",
    "under_load_summary",
    "validate_jsonl",
    "StepProfiler",
    "NullStepProfiler",
    "NULL_PROFILER",
    "profiler_or_null",
    "PlanCostCard",
    "plan_cost_card",
    "COMPONENTS",
    "TIME_COMPONENT_FIELDS",
    "WORK_COUNTERS",
    "TrafficTraceRecorder",
    "TrafficTrace",
    "ReplayHarness",
    "VirtualClock",
    "TRACE_VERSION",
]
