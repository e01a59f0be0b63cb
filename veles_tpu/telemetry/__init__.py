"""veles_tpu.telemetry — unified observability layer.

One process-wide :data:`metrics` registry (counters / gauges /
histograms with bounded reservoirs, labeled series), a span pipeline
over the JSONL :data:`veles_tpu.logger.events` sink, JIT compile
tracking, and two export surfaces:

- Prometheus text exposition at ``GET /metrics`` (served by both
  :mod:`veles_tpu.web_status` and :mod:`veles_tpu.restful_api`);
- Chrome ``trace_event`` JSON from a recorded span log
  (``python -m veles_tpu.telemetry.trace_export run.jsonl trace.json``).

See ``docs/observability.md`` for the metric names and span schema.
"""

from veles_tpu.telemetry.alerts import (  # noqa: F401
    AlertEngine, AlertRule, default_rules, firing_table)
from veles_tpu.telemetry.compile_tracker import (  # noqa: F401
    compile_summary, cost_summary, maybe_profiler_trace, trace_named,
    track_jit)
from veles_tpu.telemetry.federation import (  # noqa: F401
    fleet_families, merge_scrapes, parse_prometheus)
from veles_tpu.telemetry.flight_recorder import (  # noqa: F401
    FlightRecorder, recorder)
from veles_tpu.telemetry.health import (  # noqa: F401
    HealthMonitor, health_config, monitor)
from veles_tpu.telemetry.registry import (  # noqa: F401
    Counter, DEFAULT_BUCKETS, Gauge, Histogram, MS_BUCKETS,
    MetricsRegistry, metrics, nearest_rank, render_families_text)
from veles_tpu.telemetry.reqtrace import (  # noqa: F401
    TRACE_HEADER, clean_trace_id, ensure_trace_id, new_trace_id)
from veles_tpu.telemetry.spans import (  # noqa: F401
    annotation, iter_spans, next_span_id, span)
from veles_tpu.telemetry.tsdb import (  # noqa: F401
    DEFAULT_TIERS, TimeSeriesStore, bundle_history, history_query)


def enabled():
    """Whether host-side instrumentation (per-unit spans + histograms)
    is on — ``root.common.telemetry.enabled``, default True.  The
    metrics registry itself is always live; this gates only the
    per-run hot-path hooks."""
    from veles_tpu.config import root
    return bool(root.common.telemetry.get("enabled", True))


def unit_timing_summary(top=None):
    """Per-unit run-time digest from the shared histograms —
    ``{unit: {count, sum, mean, p50, p95, ...}}`` sorted by total
    time, optionally truncated to the ``top`` heaviest units."""
    fam = metrics.get("veles_unit_run_seconds")
    if fam is None:
        return {}
    rows = [(child.sum, name, child.summary())
            for (name,), child in fam.children().items()]
    rows.sort(reverse=True)
    if top is not None:
        rows = rows[:top]
    return {name: digest for _, name, digest in rows}
