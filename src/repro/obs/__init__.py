"""repro.obs: query telemetry, metrics, and trace export.

The measurement layer under every execution path: per-step frontier
tracing inside the engine fixpoints (`telemetry`), a process-local
metrics registry with quantile histograms (`metrics`), a
Chrome-trace/Perfetto span exporter (`trace`), and the program's spans
in `jax.profiler`'s trace (`span`). Tracing is opt-in and
exact -- results and step counts are bit-identical with it on -- and
its step-cost overhead is CI-guarded at <=10%
(benchmarks/bench_telemetry_overhead.py). See docs/OBSERVABILITY.md.
"""
from repro.obs.metrics import Counter, Gauge, Histogram, MetricsRegistry
from repro.obs.spans import span
from repro.obs.telemetry import (DispatchTelemetry, QueryTelemetry,
                                 StepTrace, from_sim)
from repro.obs.trace import (TraceBuilder, chrome_trace_from_result,
                             chrome_trace_from_telemetry,
                             write_chrome_trace)

__all__ = [
    "Counter", "Gauge", "Histogram", "MetricsRegistry",
    "StepTrace", "DispatchTelemetry", "QueryTelemetry", "from_sim",
    "TraceBuilder", "chrome_trace_from_telemetry",
    "chrome_trace_from_result", "write_chrome_trace", "span",
]
