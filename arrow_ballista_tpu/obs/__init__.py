"""Observability: distributed tracing spans + per-job query profiles.

Parity: the reference crate's `ballista/core/src/metrics` +
tracing-opentelemetry wiring, reduced to the pieces this engine needs —
a span layer propagated client -> scheduler -> executor -> operator ->
device boundary, a process-wide ring of finished spans, a per-job profile
ring buffer behind the REST API, and a pluggable span collector (noop /
the ring / OTLP-shaped export hook).
"""
from .tracing import (  # noqa: F401
    RING,
    ROOT,
    NoopSpanCollector,
    OtlpSpanCollector,
    Span,
    SpanCollector,
    SpanRing,
    TaskSpanRecorder,
    TracedLock,
    make_collector,
    new_span_id,
    new_trace_id,
    span,
    span_from_obj,
    span_to_obj,
)
from .profile import JobObservability, ProfileStore  # noqa: F401
from .stats import (  # noqa: F401
    ClusterHistory,
    RuntimeStatsStore,
    duration_quantiles,
    explain_analyze_report,
    local_explain_report,
    nearest_rank_quantile,
    render_explain_analyze,
    row_histogram,
    skew_coefficient,
    stage_summary,
)
from .trace_event import spans_to_chrome  # noqa: F401
from .journal import JournalEvent  # noqa: F401
from .doctor import (  # noqa: F401
    assemble_forensics,
    diagnose,
    render_diagnosis,
    validate_bundle,
)
from .progress import (  # noqa: F401
    job_progress,
    monotonic_fraction,
    render_progress_bar,
)
from .live import LiveDoctor  # noqa: F401
from .slo import (  # noqa: F401
    NullSloTracker,
    SloPolicy,
    SloTracker,
    tracker_from_config,
)
