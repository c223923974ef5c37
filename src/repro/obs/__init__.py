"""repro.obs — unified telemetry: metrics registry, span tracing, cost hooks.

Stdlib-only and dependency-free within the tree (``repro.obs`` imports
nothing from the rest of ``repro``), so every layer — core engines, storage,
rdbms, launch — can depend on it without cycles.

``clock`` is the single sanctioned monotonic clock; everything under
``src/repro`` outside this package must time through it (or through the
span/metrics API) — raw ``time.perf_counter()``/``time.time()`` calls are
flagged by the ``repro.analysis`` TEL001 rule.
"""
from repro.obs.metrics import (
    DEFAULT_COUNT_BUCKETS,
    DEFAULT_TIME_BUCKETS,
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
)
from repro.obs.trace import (Span, clock, current, finish, render_tree,
                             set_mirror, span, start)
from repro.obs.cost import ViewCostRecorder

__all__ = [
    "DEFAULT_COUNT_BUCKETS",
    "DEFAULT_TIME_BUCKETS",
    "Counter",
    "Gauge",
    "Histogram",
    "MetricsRegistry",
    "Span",
    "ViewCostRecorder",
    "clock",
    "current",
    "finish",
    "render_tree",
    "set_mirror",
    "span",
    "start",
]
