"""``repro.obs`` — zero-sync tracing + metrics for the serving stack.

Structured observability threaded through the serve path (ROADMAP
"Observability" contract): :class:`Observer` bundles a ring-buffered
:class:`Tracer` and a :class:`MetricsRegistry`; ``ServeEngine(obs=...)``
records request-lifecycle and per-wave spans **only at its existing host
syncs** (the O(1)-syncs-per-wave contract is untouched — tokens,
``host_syncs`` and ``admissions`` are bit-identical with tracing on or
off); :mod:`repro.obs.export` renders the stream as Chrome/Perfetto
``trace_event`` JSON, the metrics as JSONL, or a human-readable snapshot.

:mod:`repro.obs.scopes` names what the profiler sees instead: the
``jax.named_scope`` of each layer inside the compiled programs
(:data:`SCOPES`) and the continuous driver's host spans (:data:`SPANS`),
which share the device trace's clock.
"""

from repro.obs.export import (
    metrics_records,
    perfetto_trace,
    snapshot_text,
    write_metrics_jsonl,
    write_perfetto,
)
from repro.obs.metrics import (
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    percentile,
    scrape_engine,
    slo_stats,
)
from repro.obs.scopes import SCOPES, SPANS, span
from repro.obs.trace import Event, Observer, Tracer

__all__ = [
    "Counter",
    "Event",
    "Gauge",
    "Histogram",
    "MetricsRegistry",
    "Observer",
    "SCOPES",
    "SPANS",
    "metrics_records",
    "Tracer",
    "percentile",
    "perfetto_trace",
    "scrape_engine",
    "slo_stats",
    "snapshot_text",
    "span",
    "write_metrics_jsonl",
    "write_perfetto",
]
