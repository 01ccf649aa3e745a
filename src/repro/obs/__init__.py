"""repro.obs — hierarchical tracing, solver metrics, and exporters.

See ``src/repro/obs/README.md`` for the API tour and exporter formats.
"""

from repro.obs.export import (
    SCHEMA,
    expected_span_names,
    git_sha,
    load_manifest,
    manifest_lines,
    run_path,
    to_trace_events,
    validate_manifest,
    write_manifest,
    write_trace_events,
)
from repro.obs.registry import (
    MetricDef,
    lookup,
    merge_metrics,
    register,
    registered,
)
from repro.obs.trace import (
    NOOP_SPAN,
    Span,
    counter_add,
    current_span,
    disabled,
    gauge_max,
    gauge_set,
    obs_enabled,
    render,
    set_enabled,
    span,
    timed,
    trace,
)

__all__ = [
    "NOOP_SPAN", "Span", "counter_add", "current_span", "disabled",
    "gauge_max", "gauge_set", "obs_enabled", "render",
    "set_enabled",
    "span", "timed", "trace",
    "MetricDef", "lookup", "merge_metrics", "register", "registered",
    "SCHEMA", "expected_span_names", "git_sha", "load_manifest",
    "manifest_lines", "run_path", "to_trace_events", "validate_manifest",
    "write_manifest", "write_trace_events",
]
