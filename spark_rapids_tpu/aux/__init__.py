"""Auxiliary subsystems (SURVEY.md §2.13/§5): op metrics with verbosity
levels, the query-scoped tracing/event subsystem (span tree, event log,
Prometheus exposition), profiler trace ranges, debug batch dumps,
execution-plan capture, and the cost-based optimizer's helpers."""

from spark_rapids_tpu.aux.events import (  # noqa: F401
    Event, EventSink, JsonlEventLogSink, RingBufferSink, emit,
    parse_event_line, render_prometheus)
from spark_rapids_tpu.aux.faults import (  # noqa: F401
    CircuitBreaker, InjectedFault, arm_fault, arm_from_conf, disarm,
    disarm_all, fault_stats, maybe_fire, recovery_stats)
from spark_rapids_tpu.aux.profiler import Profiler  # noqa: F401
from spark_rapids_tpu.aux.metrics import (  # noqa: F401
    MetricLevel, OpMetric, collect_metrics, instrument_plan, reset_metrics)
from spark_rapids_tpu.aux.tracing import (  # noqa: F401
    QueryExecution, Span, last_query_summary, query_scope, span)
from spark_rapids_tpu.aux.capture import (  # noqa: F401
    ExecutionPlanCaptureCallback)
