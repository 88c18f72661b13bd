"""repro.telemetry: streaming observability (docs/TELEMETRY.md).

Constant-memory online metrics for 10M+-query runs: mergeable quantile
sketches (:class:`QuantileSketch`), windowed rollups
(:class:`WindowedRollup`), a Prometheus/JSON metrics registry
(:class:`MetricsRegistry`), periodic snapshot sinks
(:class:`MetricsSink` and friends), and the ``trace_mode="streaming"``
result types (:class:`StreamingTrace`, :class:`StreamingClusterTrace`)
that expose the dense ``summary()`` surface at flat memory.

This package imports nothing from the rest of ``repro``: the run loops
depend on telemetry, never the reverse.  ``repro.telemetry.spans`` (host
spans and markers on the JAX profiler's clock, docs/TELEMETRY.md
"Spans") is imported on its own, since it needs JAX.
"""

from repro.telemetry.metrics import (
    SUMMARY_QUANTILES,
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    Summary,
    export_path_format,
    render_export,
)
from repro.telemetry.rollup import DEFAULT_MAX_WINDOWS, WindowedRollup
from repro.telemetry.sink import (
    CallbackSink,
    JsonLinesSink,
    MemorySink,
    MetricsSink,
    ThresholdRule,
    ThresholdSink,
)
from repro.telemetry.sketch import (
    DEFAULT_BUFFER,
    DEFAULT_COMPRESSION,
    QuantileSketch,
)
from repro.telemetry.streaming import (
    DEFAULT_SINK_INTERVAL,
    StreamingClusterTrace,
    StreamingCollector,
    StreamingTrace,
)

__all__ = [
    "QuantileSketch",
    "DEFAULT_COMPRESSION",
    "DEFAULT_BUFFER",
    "WindowedRollup",
    "DEFAULT_MAX_WINDOWS",
    "Counter",
    "Gauge",
    "Histogram",
    "Summary",
    "MetricsRegistry",
    "SUMMARY_QUANTILES",
    "render_export",
    "export_path_format",
    "MetricsSink",
    "MemorySink",
    "CallbackSink",
    "JsonLinesSink",
    "ThresholdRule",
    "ThresholdSink",
    "StreamingCollector",
    "StreamingTrace",
    "StreamingClusterTrace",
    "DEFAULT_SINK_INTERVAL",
]
